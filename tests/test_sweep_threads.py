"""The sweep of one large job on several threads inside ``rtb_build``
(native/triebuild.cpp ``Build::build_children_threaded``): a job of
``SWEEP_THREADS * LEAVES_PER_SWEEP`` leaves or more builds its first
branch's children side by side and lays them into the same arrays.

Thread timing may decide WHEN a child is built, never where its rows land:
every array of the sweep's result is byte-equal to the one-thread sweep's,
whatever the trie's shape, wherever in its group the large job sits, and a
rejected job is rejected with the same words. The layout's constants are
moved down (``rebuild_layout``) so that a few dozen leaves are a large job.
"""

from __future__ import annotations

import numpy as np
import pytest

from reth_tpu.metrics import REGISTRY
from reth_tpu.primitives.keccak import keccak256_batch_np
from reth_tpu.primitives.nibbles import unpack_nibbles
from reth_tpu.primitives.rlp import rlp_encode
from reth_tpu.trie import turbo
from reth_tpu.trie.committer import TrieCommitter
from reth_tpu.trie.naive import naive_trie_root
from reth_tpu.trie.turbo import TurboCommitter, _sweep_group

LEAVES_PER_SWEEP = 8  # times SWEEP_THREADS (2 or 4): a large job is 16 or 32 leaves


def _keys(n, seed, shared=b""):
    """``n`` distinct 32-byte keys that all start with ``shared``."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 256, size=(3 * n, 32), dtype=np.uint8)
    keys[:, : len(shared)] = np.frombuffer(shared, dtype=np.uint8)
    keys = np.unique(keys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
    rng.shuffle(keys)
    return keys[:n]


def _values(n, seed, lo=1, hi=80):
    rng = np.random.default_rng(seed)
    return [rlp_encode(bytes(rng.integers(0, 256, size=int(ln), dtype=np.uint8)))
            for ln in rng.integers(lo, hi, size=n)]


def _uniform(n=600, seed=1):
    return _keys(n, seed), _values(n, seed)


def _under_prefix(n=600, seed=2):
    """The account chunk's shape: every key under one two-nibble prefix."""
    return _keys(n, seed, shared=b"\xab"), _values(n, seed)


def _root_extension(n=500, seed=3):
    """All keys share three more nibbles than the sweep starts at: the split
    branch sits under an extension (low nibble of the second byte varies)."""
    keys = _keys(n, seed, shared=b"\x5c")
    keys[:, 1] = 0x70 | (keys[:, 1] & 0x0F)
    keys = np.unique(keys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
    return keys, _values(len(keys), seed)


def _one_child(n=400, seed=4):
    """All keys share the next nibble, and no more: one child where the
    sweep starts, so an extension of one nibble and the branch under it."""
    keys = _keys(n, seed)
    keys[:, 0] = 0x30 | (keys[:, 0] & 0x0F)
    keys = np.unique(keys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
    assert len(np.unique(keys[:, 0])) == 16
    return keys, _values(len(keys), seed)


def _missing_nibbles(n=600, seed=5):
    """No key under nibbles 0, 7 and f of the split branch."""
    keys = _keys(n, seed)
    keys = keys[~np.isin(keys[:, 0] >> 4, (0x0, 0x7, 0xF))]
    return keys, _values(len(keys), seed)


def _inline_children(seed=6):
    """One-byte values 60 nibbles deep: every leaf is under 32 bytes, the
    split branch (under a 60-nibble extension) has one child that is such a
    leaf, inline, so the split branch is a PACKED row with a hole a hashed
    child; small branches under it are inline as well."""
    rng = np.random.default_rng(seed)
    tails = rng.choice(np.arange(0x1000, 0x10000), size=300, replace=False)
    tails = np.append(tails, 0x0ABC)  # nibble 0 of the split branch: one key
    keys = np.full((len(tails), 32), 0x11, dtype=np.uint8)
    keys[:, 30] = tails >> 8
    keys[:, 31] = tails & 0xFF
    values = [bytes([1 + i % 0x7F]) for i in range(len(keys))]
    return keys, values


def _small(n, seed):
    return _keys(n, 100 + seed), _values(n, 100 + seed, 1, 34)


_EMPTY = (np.zeros((0, 32), dtype=np.uint8), [])

# name -> (the group's jobs, start_depth)
_SHAPES = {
    "uniform": ([_uniform()], 0),
    "uniform_start_depth_2": ([_under_prefix()], 2),
    "root_extension": ([_root_extension()], 0),
    "root_extension_start_depth_2": ([_root_extension()], 2),
    "one_child": ([_one_child()], 0),
    "missing_nibbles": ([_missing_nibbles()], 0),
    "inline_child_packed_split_branch": ([_inline_children()], 0),
    # the rebuild.storage.big shape: small tries, then the one that closes
    # the group
    "large_job_last": ([_small(3, 1), _small(1, 2), _small(7, 3), _uniform()], 0),
    "large_job_in_the_middle": (
        [_small(5, 4), _uniform(300, 7), _small(2, 5), _small(9, 6)], 0),
    "two_large_jobs": ([_uniform(200, 8), _small(4, 7), _uniform(250, 9)], 0),
    "large_job_beside_an_empty_job": ([_EMPTY, _uniform(200, 10), _EMPTY], 0),
}


def _sweep(rebuild_layout, threads, jobs, start_depth=0):
    rebuild_layout(SWEEP_THREADS=threads, LEAVES_PER_SWEEP=LEAVES_PER_SWEEP)
    return _sweep_group(turbo.load_library(), jobs, range(len(jobs)), True,
                        start_depth)


def _assert_byte_equal(got, want):
    assert got.max_slot == want.max_slot
    assert got.n_levels == want.n_levels
    assert got.root_slots.tobytes() == want.root_slots.tobytes()
    assert got.root_inlines == want.root_inlines
    assert got.meta_rec.tobytes() == want.meta_rec.tobytes()
    assert got.keys.tobytes() == want.keys.tobytes()
    for g, w in zip(got.levels, want.levels):
        assert (g.depth, g.b_tier) == (w.depth, w.b_tier)
        for name in ("flat", "row_off", "row_len", "row_slot", "holes",
                     "masks", "bmp_slot", "children"):
            a, b = getattr(g, name), getattr(w, name)
            if a is None or b is None:
                assert a is None and b is None, name
            else:
                assert a.dtype == b.dtype and a.shape == b.shape, name
                assert a.tobytes() == b.tobytes(), (name, g.depth)


@pytest.mark.parametrize("threads", [1, 2, 4])
@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_threaded_sweep_is_the_serial_sweep_byte_for_byte(
        rebuild_layout, shape, threads):
    jobs, start_depth = _SHAPES[shape]
    # one thread for every job: the threshold is out of reach
    rebuild_layout(SWEEP_THREADS=4, LEAVES_PER_SWEEP=1 << 40)
    lib = turbo.load_library()
    want = _sweep_group(lib, jobs, range(len(jobs)), True, start_depth)
    assert (want.threaded_jobs, want.threaded_leaves) == (0, 0)
    got = _sweep(rebuild_layout, threads, jobs, start_depth)
    _assert_byte_equal(got, want)
    large = [len(v) for _, v in jobs if len(v) >= threads * LEAVES_PER_SWEEP]
    if threads == 1:
        large = []  # SWEEP_THREADS 1 is the serial sweep whatever the leaves
    assert (got.threaded_jobs, got.threaded_leaves) == (len(large), sum(large))


def test_the_inline_shape_is_what_it_says(rebuild_layout):
    """The split branch of that case IS a packed row with an inline child,
    sixty nibbles down, and inline branches exist under it."""
    jobs, _ = _SHAPES["inline_child_packed_split_branch"]
    sw = _sweep(rebuild_layout, 4, jobs)
    by_depth = {int(lv.depth): lv for lv in sw.levels}
    assert sorted(by_depth)[:2] == [0, 60]  # the extension, the split branch
    split = by_depth[60]
    assert len(split.row_slot) == 1 and len(split.masks) == 0
    assert split.holes.shape[1] == 15  # nibble 0's leaf is inline, no hole
    rec = sw.meta_rec.view(turbo._META_REC).ravel()
    top = rec[rec["depth"] == 60]
    assert int(top["state_mask"][0]) == 0xFFFF
    assert int(top["hash_mask"][0]) == 0xFFFE
    inline_branches = rec[(rec["depth"] > 60) & (rec["hash_mask"] == 0)]
    assert len(inline_branches) > 0


@pytest.mark.parametrize("threads", [1, 2, 4])
@pytest.mark.parametrize("fault", ["duplicate_keys", "oversized_value"])
@pytest.mark.parametrize("position", ["alone", "last_of_group"])
def test_a_fault_in_the_third_child_is_rejected_in_the_serial_sweeps_words(
        rebuild_layout, fault, position, threads):
    keys, values = _uniform(400, 11)
    third = np.nonzero(keys[:, 0] >> 4 == 0x2)[0]
    later = np.nonzero(keys[:, 0] >> 4 == 0x9)[0]
    assert len(third) > 2 and len(later) > 2
    keys, values = keys.copy(), list(values)
    if fault == "duplicate_keys":
        keys[third[1]] = keys[third[0]]
        words = r"triebuild failed \(err=2: duplicate keys\)"
    else:
        # 70,000 bytes need a length of three bytes: no leaf value has one.
        # A second one in a later child: the first in nibble order is told
        values[third[0]] = rlp_encode(bytes(70_000))
        values[later[0]] = rlp_encode(bytes(70_000))
        words = r"triebuild failed \(err=4: oversized leaf value\)"
    jobs = [(keys, values)]
    if position == "last_of_group":
        jobs = [_small(3, 8), _small(6, 9)] + jobs
    with pytest.raises(ValueError, match=words):
        _sweep(rebuild_layout, threads, jobs)


def test_the_counters_move_by_the_large_jobs_alone(rebuild_layout):
    def counters():
        return (REGISTRY.counter("trie_sweep_threaded_jobs_total").value,
                REGISTRY.counter("trie_sweep_threaded_leaves_total").value)

    jobs = [_small(3, 1), _uniform(40, 12), _small(31, 2), _uniform(32, 13)]
    before = counters()
    sw = _sweep(rebuild_layout, 4, jobs)  # a large job: 32 leaves or more
    after = counters()
    assert (sw.threaded_jobs, sw.threaded_leaves) == (2, 72)
    assert (after[0] - before[0], after[1] - before[1]) == (2, 72)
    sw = _sweep(rebuild_layout, 1, jobs)
    assert (sw.threaded_jobs, sw.threaded_leaves) == (0, 0)
    assert counters() == after


@pytest.mark.parametrize("shape,start_depth", [
    ("uniform", 0), ("inline_child_packed_split_branch", 0),
    ("uniform_start_depth_2", 2)])
def test_a_threaded_job_commits_to_the_naive_root_and_branch_nodes(
        rebuild_layout, shape, start_depth):
    """End to end on the numpy twin: the root is ``trie/naive.py``'s, the
    stored branch nodes the general committer's (itself pinned to it), and
    the ``trie::pipeline`` span says what was swept on threads."""
    from reth_tpu import tracing

    (keys, values), = _SHAPES[shape][0]
    rebuild_layout(SWEEP_THREADS=4, LEAVES_PER_SWEEP=LEAVES_PER_SWEEP)
    small = _small(5, 3) if start_depth == 0 else None
    jobs = [small, (keys, values)] if small else [(keys, values)]
    tracing.set_trace_enabled(True)
    try:
        rec = tracing.flight_recorder()
        n0 = rec.recorded
        got = TurboCommitter(backend="numpy").commit_hashed_pipelined(
            jobs, collect_branches=True, start_depth=start_depth)[-1]
        spans = [s for s in rec.snapshot()[-(rec.recorded - n0):]
                 if (s["target"], s["name"]) == ("trie::pipeline", "rebuild")]
    finally:
        tracing.set_trace_enabled(False)
    leaves = [(unpack_nibbles(k.tobytes())[start_depth:], v)
              for k, v in zip(keys, values)]
    want = TrieCommitter(hasher=keccak256_batch_np).commit(
        leaves, collect_branches=True)
    assert got.root == want.root
    assert got.branch_nodes == want.branch_nodes and got.branch_nodes
    if start_depth == 0:
        assert got.root == naive_trie_root(
            {k.tobytes(): v for k, v in zip(keys, values)})
    assert [(s["fields"]["threaded_jobs"], s["fields"]["threaded_leaves"])
            for s in spans] == [(1, len(values))]
