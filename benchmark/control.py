#!/usr/bin/env python3
"""The control of the rebuild cells: the plain reference put in the program's
place with ONE guarantee of the configuration broken, which has to come out
as not correct. The benchmark's own runs never run it.

    python3 benchmark/control.py --workload <cell> --seeds 3 [--break lost_leaf]
                                 [--full-size]

The system states no precision (Keccak-256 and RLP are exact), so the control
breaks a guarantee instead: ``lost_leaf`` leaves the last leaf of every job
out of its trie (a write that is acknowledged and not read back: the stale
answer a cache of subtrie roots across chunks would give), ``no_tree_mask``
returns every branch node with ``tree_mask`` 0 (branch records that the next
incremental walk would skip children by), ``none`` breaks nothing and has to
come out correct. One short window for each seed, at the cell's own size on
the chip's machine or with ``--full-size`` (the control never touches the
chip), else at the rehearsal size; prints one line for each seed and exits 0
only if every control came out as expected.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.reference.mpt import build_trie  # noqa: E402


class ReferenceCommitter:
    """``commit_hashed_pipelined`` by the plain reference, with a break."""

    def __init__(self, broken: str = "none"):
        self.broken = broken

    def commit_hashed_pipelined(self, jobs, collect_branches=False,
                                start_depth=0):
        out, hashed = [], 0
        for keys, vals in jobs:
            if self.broken == "lost_leaf" and len(vals) > 1:
                keys, vals = keys[:-1], vals[:-1]
            ref = build_trie(keys, vals, start_depth)
            hashed += ref.n_hashes
            branches = {}
            if collect_branches:
                for path, (sm, tm, hm, hashes) in ref.branches.items():
                    if self.broken == "no_tree_mask":
                        tm = 0
                    branches[path] = SimpleNamespace(
                        state_mask=sm, tree_mask=tm, hash_mask=hm, hashes=hashes)
            out.append(SimpleNamespace(root=ref.root, branch_nodes=branches,
                                       hashed_nodes=0))
        if out:
            out[-1].hashed_nodes = hashed
        return out


def run_control(cell_name: str, seed: int, broken: str, seconds: float,
                rehearsal: bool) -> dict:
    from benchmark import run as runmod
    from benchmark.harness import spec as specmod

    spec = specmod.Spec()

    def hook(driver):
        driver.make_committer = lambda: ReferenceCommitter(broken)

    device = {"platform": "control", "kind": "reference", "count": 1}
    result, _ = runmod.measure(spec, spec.cell(cell_name), seed, seconds, False,
                               device, rehearsal, driver_hook=hook)
    return result


def main(argv=None) -> int:
    import os

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--break", dest="broken", default="lost_leaf")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--full-size", action="store_true")
    args = ap.parse_args(argv)
    rehearsal = (os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
                 and not args.full_size)
    ok = True
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        res = run_control(args.workload, seed, args.broken, args.seconds,
                          rehearsal)
        want = args.broken == "none"
        ok &= res["correct"] == want
        print(json.dumps({"control": args.broken, "workload": args.workload,
                          "seed": seed, "correct": res["correct"],
                          "expected": want, "checks": res["checks"]}),
              flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
