"""``correct`` comes out false when it should: for the control (the reference
in the program's place with one guarantee broken) and for each fault a rebuild
cell can have, planted underneath the timed path. Each case skips the harness's
look for a chip and drives the rest of a run at the rehearsal size."""

import numpy as np
import pytest

from benchmark import run as runmod
from benchmark.control import ReferenceCommitter, run_control
from benchmark.harness import spec as specmod

SPEC = specmod.Spec()
CELLS = [w["name"] for w in SPEC.bench["workloads"]
         if SPEC.workload_file(w["name"])["driver"] == "rebuild"]
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _run(cell: str, seed: int, hook=None) -> dict:
    result, _ = runmod.measure(SPEC, SPEC.cell(cell), seed, 0.5, False, DEVICE,
                               True, driver_hook=hook)
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct_at_the_rehearsal_size(cell):
    res = _run(cell, 11)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert all(c["value"] == 0 for c in res["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("broken,fails", [("lost_leaf", "root_mismatches"),
                                          ("no_tree_mask", "branch_node_mismatches")])
@pytest.mark.parametrize("seed", [21, 22, 23])
def test_control_is_not_correct(cell, broken, fails, seed):
    res = run_control(cell, seed, broken, 0.5, rehearsal=True)
    assert not res["correct"]
    assert res["checks"][fails]["value"] > res["checks"][fails]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_reference_in_the_programs_place_is_correct(cell):
    assert run_control(cell, 24, "none", 0.5, rehearsal=True)["correct"]


def _altered_answer(driver):
    """A digest altered where it is produced: every row that comes back from
    the device has its last bit flipped."""
    from reth_tpu.ops import fused_commit as fc

    def flipped(fn):
        def wrapped(self, *a, **k):
            out = np.array(fn(self, *a, **k))
            out[..., -1] ^= 1
            return out
        return wrapped

    cls = fc.FusedLevelEngine      # the base both engines fetch through
    for name in ("finish", "fetch_slots"):
        orig = vars(cls)[name]
        setattr(cls, name, flipped(orig))
        driver._undo = getattr(driver, "_undo", []) + [(cls, name, orig)]


def _half_the_batch(driver):
    """Half of the batch left out: every job is answered from the first half
    of its leaves alone."""
    make = driver.make_committer

    class Half:
        def __init__(self):
            self.inner = make()

        def commit_hashed_pipelined(self, jobs, **kw):
            return self.inner.commit_hashed_pipelined(
                [(k[: max(1, len(v) // 2)], v[: max(1, len(v) // 2)])
                 for k, v in jobs], **kw)

    driver.make_committer = Half


def _state_unchanged(driver):
    """A step that returns its state unchanged: every operation answers with
    what the first one computed."""
    make = driver.make_committer

    class Stale:
        def __init__(self):
            self.inner, self.first = make(), None

        def commit_hashed_pipelined(self, jobs, **kw):
            if self.first is None:
                self.first = self.inner.commit_hashed_pipelined(jobs, **kw)
            return self.first

    driver.make_committer = Stale


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_altered_answer, _half_the_batch,
                                   _state_unchanged])
def test_fault_under_the_timed_path_is_not_correct(cell, fault):
    held = {}

    def hook(driver):
        held["driver"] = driver
        fault(driver)

    try:
        res = _run(cell, 31, hook)
    finally:
        for cls, name, orig in getattr(held.get("driver"), "_undo", []):
            setattr(cls, name, orig)
    assert not res["correct"], res["checks"]


def test_reference_committer_matches_the_program():
    """The control's stand-in and the program agree when nothing is broken
    (so the control differs from a sound run by its one break alone)."""
    from reth_tpu.trie.turbo import TurboCommitter
    from benchmark.harness import traffic as gen

    traffic = {"kind": "trie_jobs", "distinct_ops": 1,
               "jobs": {"kind": "prefix_subtries", "chunk_leaves": 1500,
                        "leaves_per_subtrie": 500},
               "values": {"kind": "account", "contract_share": 0.1,
                          "balance_len_weights": {"0": 1, "7": 3}}}
    jobs = gen.trie_job_ops(traffic, 5)[0]
    ours = ReferenceCommitter().commit_hashed_pipelined(
        jobs, collect_branches=True, start_depth=2)
    theirs = TurboCommitter(backend="numpy").commit_hashed_pipelined(
        jobs, collect_branches=True, start_depth=2)
    assert [r.root for r in ours] == [r.root for r in theirs]
    assert ours[-1].hashed_nodes == theirs[-1].hashed_nodes
