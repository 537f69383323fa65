"""The benchmark's own tests: run with ``python3 -m pytest benchmark/tests -q``
from the root of the checkout. They run on the CPU (a rehearsal): the program's
device paths are entitled to the CPU only when the environment names it."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
