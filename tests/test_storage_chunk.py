"""A STORAGE chunk of the clean rebuild through the stage's call: tens of
thousands of whole storage tries a chunk at mainnet's sizes, most of one to
three slots, ``commit_hashed_pipelined(jobs, collect_branches=True,
start_depth=0)``. Here at a small size (the benchmark cell's rehearsal: size
law cut at 400, 2,000 slots a chunk), on the CPU, on the numpy twin and on the
device engine.

What only this shape has: every trie has its own root, extensions at the top,
values of 1-33 bytes, leaves and branch children UNDER 32 bytes (embedded in
their parent, not hashed), sweep groups of hundreds or thousands of tries,
closed by their leaves alone and marshalled in one piece, ONE window a chunk.
Every answer is held to the plain reference (``benchmark/reference/mpt.py``),
bit for bit.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import traffic_storage as gen
from benchmark.reference.mpt import EMPTY_ROOT, build_trie
from reth_tpu import tracing
from reth_tpu.metrics import REGISTRY, compile_tracker, pipeline_metrics
from reth_tpu.ops import fused_commit as fc
from reth_tpu.trie import turbo
from reth_tpu.trie.turbo import TurboCommitter

ROOT = Path(__file__).resolve().parents[1]
TRAFFIC = {
    "kind": "storage_chunks", "distinct_ops": 1,
    "jobs": {"chunk_leaves": 2000,
             "size_law": {"form": "power", "alpha": 2.0, "max": 400}},
    "values": {"rlp_len_weights": {"1": 0.30, "3": 0.10, "9": 0.15,
                                   "21": 0.25, "33": 0.20}},
}


def _chunk(seed: int) -> list:
    return gen.storage_chunk_ops(TRAFFIC, seed)[0]


def _commit(backend: str, jobs, **kw):
    return TurboCommitter(backend=backend, **kw).commit_hashed_pipelined(
        jobs, collect_branches=True, start_depth=0)


def _assert_equals_the_reference(jobs, results) -> list:
    """Root, every branch node's three masks and child hashes, job for job,
    and the sum of hashed nodes. Returns the reference's answers."""
    refs = [build_trie(keys, values, 0) for keys, values in jobs]
    assert len(results) == len(jobs)
    for got, ref in zip(results, refs):
        assert got.root == ref.root
        plain = {bytes(p): (b.state_mask, b.tree_mask, b.hash_mask,
                            tuple(b.hashes))
                 for p, b in got.branch_nodes.items()}
        assert plain == ref.branches
    assert results[-1].hashed_nodes == sum(r.n_hashes for r in refs)
    return refs


# -- the storage shape against the reference ----------------------------------


@pytest.mark.parametrize("seed", [1, 2, 4294967311])
@pytest.mark.parametrize("backend", ["numpy", "device"])
def test_a_storage_chunk_equals_the_plain_reference(backend, seed):
    jobs = _chunk(seed)
    sizes = [len(v) for _, v in jobs]
    assert sum(sizes) >= 2000 and max(sizes) <= 400
    assert sizes.count(1) > len(jobs) // 2          # most tries hold one slot
    assert {len(v) for _, vals in jobs for v in vals} == {1, 3, 9, 21, 33}
    _assert_equals_the_reference(jobs, _commit(backend, jobs))


# -- embedded nodes -----------------------------------------------------------


def _set_nibble(key: np.ndarray, i: int, v: int) -> None:
    key[i // 2] = ((v << 4) | (key[i // 2] & 0x0F) if i % 2 == 0
                   else (key[i // 2] & 0xF0) | v)


def _embedded_jobs(share: int, seed: int) -> list:
    """Four tries. (a): two keys that share ``share`` leading nibbles, with
    one-byte values, and a third key under another first nibble: the root
    branch, an extension of ``share - 1`` nibbles, a branch at depth
    ``share`` whose two leaves are UNDER 32 bytes and so sit inside it. (b) a
    one-slot trie, (c) a two-slot trie, (d) an empty job."""
    rng = np.random.default_rng(seed)
    keys = np.repeat(rng.integers(0, 256, (1, 32), dtype=np.uint8), 3, axis=0)
    _set_nibble(keys[0], share, 1)
    _set_nibble(keys[1], share, 7)
    keys[2] = rng.integers(0, 256, 32, dtype=np.uint8)
    _set_nibble(keys[2], 0, (int(keys[0, 0]) >> 4) ^ 8)
    two = rng.integers(0, 256, (2, 32), dtype=np.uint8)
    _set_nibble(two[0], 0, 2)
    _set_nibble(two[1], 0, 9)
    return [(keys, [b"\x01", b"\x7f", b"\xa0" + bytes(range(1, 33))]),
            (rng.integers(0, 256, (1, 32), dtype=np.uint8), [b"\x01"]),
            (two, [b"\x05", b"\x83abc"]),
            (np.zeros((0, 32), dtype=np.uint8), [])]


@pytest.mark.parametrize("share", [9, 10, 11, 12])
@pytest.mark.parametrize("backend", ["numpy", "device"])
def test_leaves_and_branch_children_under_32_bytes_are_embedded(backend, share):
    jobs = _embedded_jobs(share, seed=100 + share)
    keys = jobs[0][0]
    refs = _assert_equals_the_reference(jobs, _commit(backend, jobs))
    # what the reference built for (a): the two short leaves are not hashed
    # (the root branch, the extension, the branch below it, the long leaf)
    assert refs[0].n_hashes == 4
    first = int(keys[0, 0]) >> 4                   # the pair's first nibble
    path = bytes(np.stack([keys[0] >> 4, keys[0] & 0xF], axis=1).ravel()[:share])
    assert path[0] == first and set(refs[0].branches) == {b"", path}
    state, tree, hashed, hashes = refs[0].branches[path]
    assert (state, tree, hashed, hashes) == ((1 << 1) | (1 << 7), 0, 0, ())
    root_state, root_tree, root_hashed, root_hashes = refs[0].branches[b""]
    # the root branch: the extension's child holds a branch, both hashed
    assert root_tree == 1 << first and root_hashed == root_state
    assert len(root_hashes) == 2
    assert (refs[1].n_hashes, refs[2].n_hashes) == (1, 3)
    assert refs[1].branches == {} and set(refs[2].branches) == {b""}
    assert (refs[3].root, refs[3].n_hashes) == (EMPTY_ROOT, 0)


@pytest.mark.parametrize("backend", ["numpy", "device"])
def test_a_branch_under_32_bytes_is_embedded_too(backend):
    """Keys that share 60-63 nibbles: the branch of two or three tiny leaves
    is itself under 32 bytes and sits inside the extension above it."""
    jobs = []
    for share in (58, 60, 62, 63):
        rng = np.random.default_rng(share)
        keys = np.repeat(rng.integers(0, 256, (1, 32), dtype=np.uint8), 3,
                         axis=0)
        for row, nibble in zip(keys, (1, 7, 9)):
            _set_nibble(row, share, nibble)
        jobs.append((keys, [b"\x01", b"\x7f", b"\x05"]))
    refs = _assert_equals_the_reference(jobs, _commit(backend, jobs))
    assert [r.n_hashes for r in refs] == [2, 1, 1, 1]


# -- the plan of a storage-shaped chunk ---------------------------------------

# A group closes at the job that brings it to LEAVES_PER_SWEEP, whatever the
# number of tries in it. "cell": the cell's layout in small (32,768 leaves a
# group of a 500,000-leaf chunk, 16 groups a window: at most 16 groups, ONE
# window); "windows": smaller groups, four a window, so that slots are rebased
# across many windows. The arena's tier and the staging buffers' lengths are
# the same under both; the dispatches are not.
_LAYOUT = dict(LEAVES_PER_SWEEP=60, PACK_WINDOW=4, SWEEP_THREADS=4)
_LAYOUTS = {
    "windows": _LAYOUT,
    "cell": dict(LEAVES_PER_SWEEP=131, PACK_WINDOW=16, SWEEP_THREADS=4),
}
_GOLDEN = {
    "windows": {"jobs": 505, "groups": 24, "windows": 6, "dispatches": 50,
                "s_tier": 4096, "lens": (114688, 7168)},
    "cell": {"jobs": 505, "groups": 13, "windows": 1, "dispatches": 9,
             "s_tier": 4096, "lens": (114688, 7168)},
}
_GOLDEN_SIGNATURES = {
    "windows": {
        ("mega.packed", 1, 64, 64), ("mega.packed", 1, 128, 64),
        ("mega.packed", 1, 256, 64), ("mega.branch", 64, 64),
        ("mega.branch", 64, 128), ("mega.branch", 64, 256),
        ("mega.branch", 64, 512), ("mega.branch", 128, 256),
    },
    "cell": {
        ("mega.packed", 1, 64, 64), ("mega.packed", 1, 512, 64),
        ("mega.packed", 1, 1024, 64), ("mega.branch", 64, 64),
        ("mega.branch", 256, 512), ("mega.branch", 256, 1024),
        ("mega.branch", 512, 1024),
    },
}


@pytest.fixture
def small_tiers(monkeypatch):
    monkeypatch.setattr(fc.MegaFusedEngine, "_ROW_FLOOR", 64)
    monkeypatch.setattr(fc.MegaFusedEngine, "_HOLE_FLOOR", 64)


def _planned_commit(monkeypatch, seen_plans, jobs, jitter=None):
    """One commit on the device engine: what it asked of the device. ``jitter`` makes each sweep group return after a delay of
    its own, so the groups FINISH in another order than they were sent."""
    keys = set()
    real_record, real_sweep = compile_tracker.record, turbo._sweep_group

    def record(kind, shape, seconds):
        keys.add((kind,) + tuple(shape))
        return real_record(kind, shape, seconds)

    delays = (np.random.default_rng(jitter).uniform(0, 0.012, len(jobs))
              if jitter is not None else None)

    def sweep(lib, group, job_ids, *rest):
        out = real_sweep(lib, group, job_ids, *rest)
        if delays is not None:
            time.sleep(delays[job_ids[0]])
        return out

    del seen_plans[:]
    with monkeypatch.context() as mp:
        mp.setattr(compile_tracker, "record", record)
        mp.setattr(turbo, "_sweep_group", sweep)
        results = _commit("device", jobs, min_tier=8)
    (plan,) = seen_plans                  # MegaFusedEngine runs in finish()
    last = pipeline_metrics.last
    return results, {
        "jobs": last["jobs"], "groups": last["groups"],
        "windows": last["windows"], "dispatches": len(plan["plan"]),
        "s_tier": plan["s_tier"], "lens": plan["lens"],
        "plan": plan["plan"],
        # a program's signature without the buffer lengths and the arena
        # tier, which close every one of them alike
        "signatures": {k[:-3] for k in keys if k[0].startswith("mega.")},
        "closing": {k[-3:] for k in keys if k[0].startswith("mega.")},
    }


@pytest.mark.parametrize("jitter", [None, 7, 8])
@pytest.mark.parametrize("layout", ["windows", "cell"])
def test_the_plan_of_a_storage_chunk_follows_from_the_job_list(
        monkeypatch, rebuild_layout, small_tiers, seen_plans, layout, jitter):
    jobs = _chunk(3300000001)
    rebuild_layout(**_LAYOUTS[layout])
    results, got = _planned_commit(monkeypatch, seen_plans, jobs, jitter)
    golden = _GOLDEN[layout]
    for name, want in golden.items():
        assert got[name] == want, name
    assert got["signatures"] == _GOLDEN_SIGNATURES[layout]
    assert got["closing"] == {golden["lens"] + (golden["s_tier"],)}
    # many windows fragment the chunk: far more dispatches than levels
    assert got["dispatches"] > 3 * got["windows"]
    again_results, again = _planned_commit(monkeypatch, seen_plans, jobs, jitter)
    assert again == got                              # entry for entry
    assert [r.root for r in again_results] == [r.root for r in results]
    _assert_equals_the_reference(jobs, results)


# -- a chunk is ONE window -----------------------------------------------------


def _layout_of_the_last_commit():
    last = pipeline_metrics.last
    return last["jobs"], last["groups"], last["windows"]


def test_the_rehearsal_chunk_is_one_group_in_one_window():
    """At the program's constants 2,000 slots close no group: ~505 tries are
    ONE group, marshalled in one piece and swept by the caller."""
    jobs = _chunk(6)
    assert sum(len(v) for _, v in jobs) < turbo.LEAVES_PER_SWEEP
    results = _commit("numpy", jobs)
    assert _layout_of_the_last_commit() == (len(jobs), 1, 1)
    _assert_equals_the_reference(jobs, results)


@pytest.mark.parametrize("backend", ["numpy", "device"])
def test_a_chunk_under_the_real_law_is_one_window(rebuild_layout, backend):
    """The cell's own size law (one trie holds a third of the chunk) at 4,000
    slots for 500,000, ``LEAVES_PER_SWEEP`` scaled down alike: as the cell's
    chunk, at most ``PACK_WINDOW`` groups whatever the number of tries."""
    config = json.loads((ROOT / "benchmark" / "configs"
                         / "sync-rebuild-storage.json").read_text())
    chunk_leaves = 4000
    traffic = dict(TRAFFIC, jobs={
        "chunk_leaves": chunk_leaves,
        "size_law": config["storage_trie_size_law"]})
    jobs = gen.storage_chunk_ops(traffic, 11)[0]
    assert max(len(v) for _, v in jobs) > chunk_leaves // 4
    rebuild_layout(LEAVES_PER_SWEEP=turbo.LEAVES_PER_SWEEP * chunk_leaves
                   // config["chunk_leaves"])
    results = _commit(backend, jobs, min_tier=8)
    n_jobs, groups, windows = _layout_of_the_last_commit()
    assert n_jobs == len(jobs) > 100
    assert 4 <= groups <= turbo.PACK_WINDOW and windows == 1
    _assert_equals_the_reference(jobs, results)


# -- the counter and the phase this shape brought -----------------------------


def test_groups_counter_and_collect_phase_move_once_a_commit(rebuild_layout):
    names = ["trie_pipeline_groups_total", "trie_pipeline_windows_total",
             "trie_pipeline_subtries_total",
             "trie_commit_collect_seconds_total"]
    jobs = _chunk(5)
    rebuild_layout(**_LAYOUT)
    before = {n: REGISTRY.counter(n).value for n in names}
    tracing.set_trace_enabled(True)
    try:
        rec = tracing.flight_recorder()
        n0 = rec.recorded
        t0 = time.perf_counter()
        _commit("numpy", jobs)
        wall = time.perf_counter() - t0
        spans = [s for s in rec.snapshot()[-(rec.recorded - n0):]
                 if (s["target"], s["name"]) == ("trie::commit", "collect")]
    finally:
        tracing.set_trace_enabled(False)
    moved = {n: REGISTRY.counter(n).value - before[n] for n in names}
    groups = turbo._group_jobs(jobs, _LAYOUT["LEAVES_PER_SWEEP"])
    assert moved["trie_pipeline_groups_total"] == len(groups) > 16
    assert moved["trie_pipeline_windows_total"] == -(-len(groups) // 4)
    assert moved["trie_pipeline_subtries_total"] == len(jobs)
    assert len(spans) == 1                  # one a commit, never one a job
    assert 0 < moved["trie_commit_collect_seconds_total"] <= wall
    assert (spans[0]["dur_ms"] / 1e3 * 0.8 - 2e-3
            <= moved["trie_commit_collect_seconds_total"])
    rendered = REGISTRY.render()
    assert "trie_pipeline_groups_total" in rendered
    assert "trie_commit_collect_seconds_total" in rendered
