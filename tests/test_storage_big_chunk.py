"""The storage chunk that a trie larger than the chunk closes:
``MerkleStage._storage_chunk`` adds WHOLE tries while the chunk holds fewer
than ``chunk_leaves`` slots, so a trie of millions of slots is the LAST job of
a chunk that already holds thousands of small tries. Here in small (the
benchmark cell's rehearsal: half a chunk of 2,000 slots under the law cut at
400, then one trie of 6,000 slots), on the CPU, on the numpy twin and on the
device engine, through ``commit_hashed_pipelined(jobs, collect_branches=True,
start_depth=0)``.

What only this shape has: one sweep group many times the others (a job is
never cut), its wide levels merged with the small tries' rows in ONE window
and split at the row cap with holes on both sides, leaves and a branch under
32 bytes deep inside a large trie. Every answer is held to the plain
reference (``benchmark/reference/mpt.py``), roots and branch nodes, bit for
bit.
"""

from __future__ import annotations

import numpy as np
import pytest

from benchmark.harness import traffic_storage_big as gen
from reth_tpu import tracing
from reth_tpu.metrics import REGISTRY, pipeline_metrics
from reth_tpu.ops import fused_commit as fc
from reth_tpu.trie import turbo
from test_storage_chunk import (_assert_equals_the_reference, _set_nibble,
                                _commit as _commit_at)

TRAFFIC = {
    "kind": "storage_chunk_closed_by_big_trie", "distinct_ops": 1,
    "jobs": {"chunk_leaves": 2000,
             "size_law": {"form": "power", "alpha": 2.0, "max": 400},
             "big_trie": {"slots": 6000},
             "fill_before": {"rule": "half_chunk"}},
    "values": {"rlp_len_weights": {"1": 0.30, "3": 0.10, "9": 0.15,
                                   "21": 0.25, "33": 0.20}},
}
# the cell's layout in small: 32,768 leaves a group of a 500,000-slot chunk
LEAVES_PER_SWEEP = turbo.LEAVES_PER_SWEEP * 2000 // 500_000


def _chunk(seed: int) -> list:
    return gen.big_chunk_ops(TRAFFIC, seed)[0]


def _commit(backend: str, jobs):
    return _commit_at(backend, jobs, min_tier=8)


def _group_leaves(jobs, max_leaves: int) -> list[int]:
    return [sum(len(v) for _, v in jobs[lo:hi])
            for lo, hi in turbo._group_jobs(jobs, max_leaves)]


# -- the mixed chunk against the reference ------------------------------------


@pytest.mark.parametrize("seed", [1, 4294967311])
@pytest.mark.parametrize("backend", ["numpy", "device"])
def test_the_chunk_a_big_trie_closes_equals_the_plain_reference(
        rebuild_layout, backend, seed):
    jobs = _chunk(seed)
    sizes = [len(v) for _, v in jobs]
    assert sizes[-1] == 6000 == max(sizes)           # the big trie comes LAST
    assert 1000 <= sum(sizes[:-1]) < 1400 and max(sizes[:-1]) <= 400
    rebuild_layout(LEAVES_PER_SWEEP=LEAVES_PER_SWEEP)
    results = _commit(backend, jobs)
    last = pipeline_metrics.last
    leaves = _group_leaves(jobs, LEAVES_PER_SWEEP)
    # the big trie joins whatever group was open, and that group is many
    # times the others; the whole chunk is one window
    assert (last["jobs"], last["groups"], last["windows"]) == (
        len(jobs), len(leaves), 1)
    assert len(leaves) >= 5 and leaves[-1] >= 6000
    assert leaves[-1] > 20 * max(leaves[:-1])
    _assert_equals_the_reference(jobs, results)


# -- embedded nodes deep inside a large trie ----------------------------------


def _big_trie_with_embedded_nodes(seed: int, n: int = 3000):
    """A trie of ``n`` uniform keys with long values, and crafted among
    them (each under an extension below one of the bulk's branches): 24
    pairs of keys that share 10, 11 or 12 nibbles, both values of
    one byte in the even pairs (two leaves under 32 bytes inside their
    branch), one of one byte and one of 33 in the odd ones (an embedded leaf
    beside a hashed one); and three keys that share 61 nibbles with one-byte
    values: a branch that is itself under 32 bytes, inside the extension
    above it."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    values = [b"\xa0" + bytes(rng.integers(0, 256, 32, dtype=np.uint8))
              for _ in range(n)]
    extra_k, extra_v, pairs = [], [], []
    for i in range(24):
        share = 10 + i % 3
        pair = np.repeat(rng.integers(0, 256, (1, 32), dtype=np.uint8), 2,
                         axis=0)
        a, b = 1 + i % 5, 9 + i % 5
        _set_nibble(pair[0], share, a)
        _set_nibble(pair[1], share, b)
        extra_k.append(pair)
        extra_v += [b"\x01", b"\x7f" if i % 2 == 0 else values[i]]
        pairs.append((pair[0], share, a, b, i % 2 == 0))
    triple = np.repeat(rng.integers(0, 256, (1, 32), dtype=np.uint8), 3,
                       axis=0)
    for row, nibble in zip(triple, (1, 7, 9)):
        _set_nibble(row, 61, nibble)
    extra_k.append(triple)
    extra_v += [b"\x01", b"\x7f", b"\x05"]
    keys = np.concatenate([keys] + extra_k)
    values = values + extra_v
    order = np.argsort(keys.view("S32").ravel())
    assert len(np.unique(keys.view("S32"))) == len(keys)
    return (np.ascontiguousarray(keys[order]),
            [values[i] for i in order]), pairs, triple


def _nibbles(key: np.ndarray, n: int) -> bytes:
    return bytes(np.stack([key >> 4, key & 0xF], axis=1).ravel()[:n])


@pytest.mark.parametrize("backend", ["numpy", "device"])
def test_embedded_leaves_and_branch_deep_inside_a_large_trie(
        rebuild_layout, backend):
    big, pairs, triple = _big_trie_with_embedded_nodes(77)
    jobs = _chunk(5)[:-1] + [big]
    rebuild_layout(LEAVES_PER_SWEEP=LEAVES_PER_SWEEP)
    ref = _assert_equals_the_reference(jobs, _commit(backend, jobs))[-1]
    for key, share, a, b, both in pairs:
        # the pair's branch sits ``share`` nibbles deep, below the bulk's
        # branches: an embedded child is in no hash mask
        state, tree, hashed, hashes = ref.branches[_nibbles(key, share)]
        assert (state, tree) == ((1 << a) | (1 << b), 0)
        assert (hashed, len(hashes)) == ((0, 0) if both else (1 << b, 1))
    # the triple's branch has a record of its own and is under 32 bytes
    # (three leaves of 5 bytes, thirteen empty children, no value: 30): it
    # sits inside the extension above it, which is hashed
    path = _nibbles(triple[0], 61)
    assert ref.branches[path] == ((1 << 1) | (1 << 7) | (1 << 9), 0, 0, ())
    # every node but the embedded ones is hashed: 24 + 12 + 3 leaves and the
    # one branch. An extension stands above each branch that is more than
    # one nibble below the nearest branch above it
    parents = {p: max(k for k in range(len(p)) if p[:k] in ref.branches)
               for p in ref.branches if p}
    extensions = sum(len(p) - k > 1 for p, k in parents.items())
    assert extensions >= 25
    assert ref.n_hashes == (len(big[1]) + len(ref.branches) + extensions
                            - (24 + 12 + 3 + 1))


# -- a wide level split at the row cap ----------------------------------------


def test_a_level_of_the_big_group_splits_at_the_row_cap_with_holes_on_both_sides(
        monkeypatch, rebuild_layout, seen_plans):
    """``dispatch_packed`` splits a level over the row cap by row ranges and
    filters its holes (row, byte, source slot) into each part, rebased. At a
    cap of 256 rows the big group's wide levels split many times, and the
    rows that carry holes (extensions, branches with a child inline) fall on
    both sides of a split."""
    jobs = _chunk(9)
    rebuild_layout(LEAVES_PER_SWEEP=LEAVES_PER_SWEEP)
    monkeypatch.setattr(fc.FusedLevelEngine, "_row_cap", lambda self: 256)
    monkeypatch.setattr(fc.MegaFusedEngine, "_ROW_FLOOR", 64)
    monkeypatch.setattr(fc.MegaFusedEngine, "_HOLE_FLOOR", 64)
    splits = []            # of each split level: the holes each part kept
    real = fc.FusedLevelEngine._filter_triples

    def spy(triples, lo, hi):
        out = real(triples, lo, hi)
        if triples is not None and triples.shape[0] == 3:
            if lo == 0:
                splits.append([])
            splits[-1].append(0 if out is None else out.shape[1])
        return out

    monkeypatch.setattr(fc.FusedLevelEngine, "_filter_triples",
                        staticmethod(spy))
    results = _commit("device", jobs)
    (plan,) = seen_plans
    rows = [e[9] - 1 for e in plan["plan"] if e[0] == "packed"]
    assert max(rows) == 255 and rows.count(255) >= 10     # parts at the cap
    holed = [parts for parts in splits if sum(n > 0 for n in parts) >= 2]
    assert holed, splits
    assert any(parts[0] > 0 and parts[-1] > 0 for parts in holed)
    _assert_equals_the_reference(jobs, results)


# -- the plan follows from the job list ---------------------------------------

_GOLDEN = {"jobs": 258, "groups": 7, "windows": 1, "leaves": 7003,
           "largest_group_leaves": 6023, "s_tier": 16384}


def _plan(seen_plans, jobs):
    del seen_plans[:]
    results = _commit("device", jobs)
    (plan,) = seen_plans
    last = pipeline_metrics.last
    return results, dict(
        {k: last[k] for k in ("jobs", "groups", "windows", "leaves",
                              "largest_group_leaves")},
        s_tier=plan["s_tier"])


def test_the_plan_of_the_chunk_is_the_same_on_two_seeds(
        rebuild_layout, seen_plans):
    """Groups, the ONE window, the largest group's share of the leaves and
    the arena's tier follow from the sizes alone, which no seed moves (a
    level's rows, and so its tier where it sits near an edge, follow the
    keys)."""
    rebuild_layout(LEAVES_PER_SWEEP=LEAVES_PER_SWEEP)
    a, b = _chunk(3600000001), _chunk(17)
    assert [len(v) for _, v in a] == [len(v) for _, v in b]
    assert not (a[-1][0] == b[-1][0]).all()
    assert _group_leaves(a, LEAVES_PER_SWEEP)[-1] == \
        _GOLDEN["largest_group_leaves"]
    res_a, got_a = _plan(seen_plans, a)
    res_b, got_b = _plan(seen_plans, b)
    assert got_a == got_b == _GOLDEN
    assert round(100 * got_a["largest_group_leaves"] / got_a["leaves"], 1) \
        == 86.0
    _assert_equals_the_reference(a, res_a)
    _assert_equals_the_reference(b, res_b)


# -- the two counters this shape brought --------------------------------------


@pytest.mark.parametrize("layout,want", [
    (LEAVES_PER_SWEEP, (7003, 6023)),      # the big trie's group
    (10**9, (7003, 7003)),                 # ONE group: the caller sweeps all
])
def test_leaves_and_largest_group_counters_move_once_a_commit(
        rebuild_layout, layout, want):
    names = ["trie_pipeline_leaves_total",
             "trie_pipeline_largest_group_leaves_total",
             "trie_pipeline_runs_total"]
    jobs = _chunk(5)
    rebuild_layout(LEAVES_PER_SWEEP=layout)
    before = {n: REGISTRY.counter(n).value for n in names}
    tracing.set_trace_enabled(True)
    try:
        rec = tracing.flight_recorder()
        n0 = rec.recorded
        _commit("numpy", jobs)
        spans = [s for s in rec.snapshot()[-(rec.recorded - n0):]
                 if (s["target"], s["name"]) == ("trie::pipeline", "rebuild")]
    finally:
        tracing.set_trace_enabled(False)
    moved = tuple(REGISTRY.counter(n).value - before[n] for n in names)
    assert moved == want + (1,)
    assert want == (sum(len(v) for _, v in jobs),
                    max(_group_leaves(jobs, layout)))
    (span,) = spans
    assert (span["fields"]["leaves"],
            span["fields"]["largest_group_leaves"]) == want
    rendered = REGISTRY.render()
    assert all(n in rendered for n in names)
