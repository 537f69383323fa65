"""The one command, off the TPU: with ``JAX_PLATFORMS=cpu`` the whole path
runs at the rehearsal size, then exits non-zero at the platform check and
prints no result line and no number under a device metric's name; in a
directory that lacks the program it fails at once."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _run(cwd: Path, cell: str, trace: int):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell, "--seed",
         "4294967311", "--seconds", "1", "--trace", str(trace)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cpu_rehearsal_runs_the_whole_path_and_prints_no_result(cell, trace):
    out = _run(ROOT, cell, trace)
    assert out.returncode == 3, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "rehearsal, not a chip run: no result line" in out.stderr
    assert "FAIL" not in out.stderr
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in BENCH[g]]
    assert not any(n in out.stderr or n in out.stdout for n in names)


def test_without_the_program_it_fails_at_once(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, BENCH["workloads"][0]["name"], 0)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
