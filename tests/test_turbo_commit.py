"""Turbo commit path (native sweep + array backends): parity tests.

Pins native/triebuild.cpp + TurboCommitter (numpy and device backends)
against the Python TrieCommitter, which is itself pinned to the naive
oracle (tests/test_trie.py). Covers inline leaves (deep shared prefixes
with tiny values — the <32-byte RLP case), branch-with-inline-child rows,
TrieUpdates branch metadata, and the SPMD mesh backend.
"""

from __future__ import annotations

import struct
import time

import numpy as np
import pytest
from branch_decode_oracle import collect_meta_records_loop

from reth_tpu.metrics import REGISTRY
from reth_tpu.primitives.keccak import keccak256_batch_np
from reth_tpu.primitives.nibbles import unpack_nibbles
from reth_tpu.primitives.rlp import rlp_encode
from reth_tpu.trie import turbo
from reth_tpu.trie.committer import TrieBuildResult, TrieCommitter
from reth_tpu.trie.turbo import TurboCommitter


def _job(n, seed, val_len=(1, 100)):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    keys = np.unique(keys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
    rng.shuffle(keys)
    values = [
        rlp_encode(bytes(rng.integers(0, 256, size=int(rng.integers(*val_len)), dtype=np.uint8)))
        for _ in range(len(keys))
    ]
    return keys, values


def _baseline_result(jobs, collect=False):
    base = TrieCommitter(hasher=keccak256_batch_np)
    py_jobs = [
        ([(unpack_nibbles(k.tobytes()), v) for k, v in zip(keys, values)], None)
        for keys, values in jobs
    ]
    return base.commit_many(py_jobs, collect_branches=collect)


@pytest.fixture(scope="module")
def turbo_np():
    return TurboCommitter(backend="numpy")


@pytest.mark.parametrize("n", [1, 2, 30, 500, 3000])
def test_turbo_numpy_root_parity(turbo_np, n):
    jobs = [_job(n, seed=n)]
    got = turbo_np.commit_hashed_many(jobs)
    want = _baseline_result(jobs)
    assert got[0].root == want[0].root


def test_turbo_many_jobs(turbo_np):
    jobs = [_job(40, seed=10 + i, val_len=(1, 32)) for i in range(8)] + [_job(900, seed=99)]
    got = turbo_np.commit_hashed_many(jobs)
    want = _baseline_result(jobs)
    assert [r.root for r in got] == [r.root for r in want]


def test_turbo_empty_job(turbo_np):
    from reth_tpu.primitives.types import EMPTY_ROOT_HASH

    keys = np.zeros((0, 32), dtype=np.uint8)
    got = turbo_np.commit_hashed_many([(keys, []), _job(5, seed=1)])
    assert got[0].root == EMPTY_ROOT_HASH
    assert got[1].root == _baseline_result([_job(5, seed=1)])[0].root


def test_turbo_inline_leaves(turbo_np):
    """Keys sharing 60 nibbles with 1-byte values produce <32-byte leaf RLPs
    (inline) and a branch row with literal inline-child bytes."""
    prefix = bytes(range(30))
    keys = np.array(
        [list(prefix + bytes([i, 7])) for i in range(6)]
        + [list(bytes(31) + bytes([9]))],
        dtype=np.uint8,
    )
    values = [rlp_encode(b"\x01")] * len(keys)
    got = turbo_np.commit_hashed_many([(keys, values)])
    want = _baseline_result([(keys, values)])
    assert got[0].root == want[0].root


def test_turbo_branch_meta(turbo_np):
    jobs = [_job(400, seed=4)]
    got = turbo_np.commit_hashed_many(jobs, collect_branches=True)
    want = _baseline_result(jobs, collect=True)
    assert got[0].root == want[0].root
    assert got[0].branch_nodes == want[0].branch_nodes


def test_turbo_duplicate_keys_rejected(turbo_np):
    keys = np.zeros((2, 32), dtype=np.uint8)
    with pytest.raises(ValueError, match="duplicate"):
        turbo_np.commit_hashed_many([(keys, [b"\x01", b"\x02"])])


def test_turbo_device_backend_parity(turbo_np):
    dev = TurboCommitter(backend="device", min_tier=64)
    jobs = [_job(60, seed=21, val_len=(1, 40)) for _ in range(3)] + [_job(800, seed=22)]
    got = dev.commit_hashed_many(jobs, collect_branches=True)
    want = turbo_np.commit_hashed_many(jobs, collect_branches=True)
    assert [r.root for r in got] == [r.root for r in want]
    assert got[-1].branch_nodes == want[-1].branch_nodes


def test_turbo_device_inline_leaves():
    dev = TurboCommitter(backend="device", min_tier=16)
    prefix = bytes(range(30))
    keys = np.array([list(prefix + bytes([i, 7])) for i in range(6)], dtype=np.uint8)
    values = [rlp_encode(b"\x01")] * len(keys)
    got = dev.commit_hashed_many([(keys, values)])
    want = _baseline_result([(keys, values)])
    assert got[0].root == want[0].root


@pytest.mark.parametrize("n_dev", [8, 6])
def test_turbo_mesh_backend_parity(turbo_np, n_dev):
    """Mesh sharding incl. a non-power-of-two device count (6): every tier
    (batch, holes, children) must round to a device-count multiple."""
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    dev = TurboCommitter(backend="device", min_tier=64, mesh=mesh)
    jobs = [_job(600, seed=31)]
    got = dev.commit_hashed_many(jobs)
    want = turbo_np.commit_hashed_many(jobs)
    assert got[0].root == want[0].root


def test_turbo_start_depth_subtrie_parity(turbo_np):
    """start_depth=2 must yield the embedded subtree: root AND branch-node
    paths (subtrie-relative, skipping the prefix nibbles — review finding)
    equal to the general committer over prefix-stripped paths."""
    rng = np.random.default_rng(77)
    keys = rng.integers(0, 256, size=(64, 32), dtype=np.uint8)
    keys[:, 0] = 0x12  # shared 2-nibble prefix
    values = [rlp_encode(bytes([i + 1])) for i in range(64)]
    got = turbo_np.commit_hashed_many([(keys, values)], collect_branches=True,
                                      start_depth=2)[0]
    base = TrieCommitter(hasher=keccak256_batch_np)
    leaves = [(unpack_nibbles(k.tobytes())[2:], v) for k, v in zip(keys, values)]
    want = base.commit(leaves, collect_branches=True)
    assert got.root == want.root
    assert got.branch_nodes == want.branch_nodes
    assert any(len(p) >= 1 for p in got.branch_nodes), "expected deep branches"


# -- the bulk decode of branch records against the record-by-record loop ------


def _loop_results(meta_rec, keys, digests, n_jobs, start_depth, slot_base):
    """What the loop that ``_collect_meta_records`` replaced gives, over the
    sweep group's one sorted key array (``rep_key`` is a row of it: every job
    starts at 0 for the loop)."""
    want = [TrieBuildResult(root=b"") for _ in range(n_jobs)]
    return collect_meta_records_loop(meta_rec, [keys] * n_jobs, [0] * n_jobs,
                                     digests, want, start_depth, slot_base)


def _assert_same_branch_nodes(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert type(g.branch_nodes) is dict
        # order too: the stage writes the nodes in iteration order
        assert list(g.branch_nodes.items()) == list(w.branch_nodes.items())
        for path, node in g.branch_nodes.items():
            assert type(path) is bytes
            assert (type(node.state_mask), type(node.tree_mask),
                    type(node.hash_mask)) == (int, int, int)
            assert type(node.hashes) is tuple
            assert all(type(h) is bytes and len(h) == 32 for h in node.hashes)


def _prefixed(job, prefix):
    keys, values = job
    keys = keys.copy()
    keys[:, 0] = prefix
    keys = np.unique(keys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
    return keys, values[:len(keys)]


@pytest.mark.parametrize("start_depth", [0, 2])
@pytest.mark.parametrize("sizes", [(700,), (3, 40, 1, 700, 150)],
                         ids=["1job", "5jobs"])
def test_bulk_decode_equals_the_loop_on_real_sweeps(turbo_np, monkeypatch,
                                                    start_depth, sizes):
    calls, fetched = [], []
    real, real_finish = turbo._collect_meta_records, turbo._NumpyBackend.finish

    def spy(meta_rec, keys, results, start_depth=0, slot_base=0):
        calls.append((meta_rec.copy(), keys, len(results), start_depth,
                      slot_base))
        return real(meta_rec, keys, results, start_depth, slot_base)

    def finish(backend):
        fetched.append(real_finish(backend))
        return fetched[-1]

    monkeypatch.setattr(turbo, "_collect_meta_records", spy)
    monkeypatch.setattr(turbo._NumpyBackend, "finish", finish)
    jobs = [_job(n, seed=50 + i) for i, n in enumerate(sizes)]
    if start_depth:
        jobs = [_prefixed(j, 0x30 + i) for i, j in enumerate(jobs)]
    before = REGISTRY.counter("trie_commit_decode_records_total").value
    got = turbo_np.commit_hashed_many(jobs, collect_branches=True,
                                      start_depth=start_depth)
    ((meta_rec, keys, *rest),), (digests,) = calls, fetched
    assert rest == [len(jobs), start_depth, 0]
    _assert_same_branch_nodes(got, _loop_results(meta_rec, keys, digests,
                                                 *rest))
    n_records = sum(len(r.branch_nodes) for r in got)
    assert n_records == len(meta_rec) > 0
    assert (REGISTRY.counter("trie_commit_decode_records_total").value
            - before) == n_records
    if len(sizes) > 1:
        assert got[2].branch_nodes == {}  # a job of one leaf has no branch


def _record(job, rep_key, depth, state, tree, hash_mask, child_slots):
    """One native BranchMeta record (native/triebuild.cpp rtb_meta_get)."""
    return struct.pack("<IIHHHH16i", job, rep_key, depth, state, tree,
                       hash_mask, *child_slots)


def _synthetic_records(rng, n, job_sizes, max_slot, max_depth=6):
    """Records of the native layout with random fields, over jobs whose
    sorted key arrays have ``job_sizes`` rows."""
    job_ends = np.cumsum(job_sizes)
    recs = []
    for _ in range(n):
        rep = int(rng.integers(0, job_ends[-1]))
        hm = int(rng.integers(0, 1 << 16) & rng.integers(0, 1 << 16))
        recs.append(_record(
            int(np.searchsorted(job_ends, rep, side="right")), rep,
            int(rng.integers(0, max_depth + 1)),
            hm | int(rng.integers(0, 1 << 16)), int(rng.integers(0, 1 << 16)),
            hm, rng.integers(1, max_slot, 16).tolist()))
    return np.frombuffer(b"".join(recs), dtype=np.uint8).reshape(-1, 80)


@pytest.mark.parametrize("slot_base", [0, 4096])
@pytest.mark.parametrize("start_depth", [0, 2])
def test_bulk_decode_equals_the_loop_on_synthetic_records(start_depth,
                                                          slot_base):
    rng = np.random.default_rng(start_depth * 7 + slot_base)
    # three jobs of 5, 4 and 6 sorted keys in the group's one array
    keys = rng.integers(0, 256, (5 + 4 + 6, 32), dtype=np.uint8)
    digests = rng.integers(0, 256, (slot_base + 64, 32), dtype=np.uint8)
    slots = list(range(1, 17))
    meta_rec = np.frombuffer(b"".join([
        # the subtrie's root: path b"", and no child hashed: hashes ()
        _record(0, 2, 0, 0x0101, 0x0001, 0, [0] * 16),
        _record(2, 9 + 3, 7, 0xFFFF, 0x0F0F, 0xFFFF, slots),  # all sixteen
        _record(0, 4, 5, 0x8421, 0, 0x8001, [63 - s for s in slots]),
        _record(2, 9 + 0, 62 - start_depth, 0x0006, 0x0004, 0x0002, slots),
        _record(0, 0, 1, 0x00F0, 0x0030, 0x0050, [40] * 16),
        # job 1 of the three gets no record
    ]), dtype=np.uint8).reshape(-1, 80)
    got = [TrieBuildResult(root=b"") for _ in range(3)]
    assert turbo._collect_meta_records(meta_rec, keys, got, start_depth,
                                       slot_base).lay_in(digests) is got
    _assert_same_branch_nodes(
        got, _loop_results(meta_rec, keys, digests, 3, start_depth,
                           slot_base))
    assert [len(r.branch_nodes) for r in got] == [3, 0, 2]
    assert got[0].branch_nodes[b""].hashes == ()
    full = got[2].branch_nodes[bytes(
        unpack_nibbles(keys[9 + 3].tobytes())[start_depth:start_depth + 7])]
    assert full.hashes == tuple(
        digests[slot_base + s].tobytes() for s in slots)
    # and a larger random set, with records of every job interleaved
    many = _synthetic_records(rng, 300, (5, 4, 6), 64)
    got = [TrieBuildResult(root=b"") for _ in range(3)]
    turbo._collect_meta_records(many, keys, got, start_depth,
                                slot_base).lay_in(digests)
    _assert_same_branch_nodes(
        got, _loop_results(many, keys, digests, 3, start_depth,
                           slot_base))


def test_bulk_decode_of_no_records_leaves_the_results_alone():
    got = [TrieBuildResult(root=b"r")]
    before = REGISTRY.counter("trie_commit_decode_records_total").value
    turbo._collect_meta_records(
        np.zeros((0, 80), dtype=np.uint8), np.zeros((1, 32), dtype=np.uint8),
        got).lay_in(np.zeros((8, 32), dtype=np.uint8))
    assert got[0].branch_nodes == {} and type(got[0].branch_nodes) is dict
    assert REGISTRY.counter(
        "trie_commit_decode_records_total").value == before


def test_bulk_decode_stays_faster_than_the_loop():
    """A guard that the per-record numpy loop does not come back: a ratio
    in one process, so it holds on a loaded machine (expected 2.5x-5x)."""
    rng = np.random.default_rng(27)
    keys = rng.integers(0, 256, (60_000, 32), dtype=np.uint8)
    digests = rng.integers(0, 256, (1 << 16, 32), dtype=np.uint8)
    meta_rec = _synthetic_records(rng, 20_000, (60_000,), 1 << 16)

    def timed(fn):
        t0 = time.thread_time()
        out = fn()
        return time.thread_time() - t0, out

    # this thread's CPU seconds, so a turn the scheduler parked counts as
    # what it ran; and the best of five turns a side, taken in turn, so
    # what else slows a loaded machine (six test workers share it) has to
    # hit every turn of one side and none of the other
    new, loop = [], []
    for _ in range(5):
        new.append(timed(lambda: turbo._collect_meta_records(
            meta_rec, keys, [TrieBuildResult(root=b"")], 2).lay_in(digests)))
        loop.append(timed(
            lambda: _loop_results(meta_rec, keys, digests, 1, 2, 0)))
    (new_s, got), (loop_s, want) = (min(r, key=lambda t: t[0])
                                    for r in (new, loop))
    _assert_same_branch_nodes(got, want)
    assert loop_s >= 1.5 * new_s, (loop_s, new_s)


def test_turbo_oversized_value_rejected(turbo_np):
    keys = np.arange(32, dtype=np.uint8).reshape(1, 32)
    with pytest.raises(ValueError, match="triebuild failed"):
        turbo_np.commit_hashed_many([(keys, [b"\x01" * 70000])])


def test_full_state_root_turbo_matches_general(tmp_path):
    """End-to-end: a synced provider's turbo full rebuild equals the general
    committer's root AND the header root (storage tries + account trie,
    with storage roots flowing into the account values)."""
    from reth_tpu.consensus.validation import EthBeaconConsensus
    from reth_tpu.primitives.types import Account
    from reth_tpu.stages import default_stages
    from reth_tpu.stages.api import Pipeline
    from reth_tpu.storage.genesis import import_chain, init_genesis
    from reth_tpu.storage.kv import MemDb
    from reth_tpu.storage.provider import ProviderFactory
    from reth_tpu.testing import ChainBuilder, Wallet
    from reth_tpu.trie.incremental import full_state_root, full_state_root_turbo

    cpu = TrieCommitter(hasher=keccak256_batch_np)
    alice = Wallet(0xA11CE)
    store = bytes.fromhex("5f355f5500")  # sstore(0, calldata[0])
    init = bytes([0x60, len(store), 0x60, 0x0B, 0x5F, 0x39, 0x60, len(store),
                  0x5F, 0xF3]) + b"\x00" + store
    b = ChainBuilder({alice.address: Account(balance=10**21)}, committer=cpu)
    b.build_block([alice.deploy(init)])
    contract = next(iter(a for a, acc in b.accounts.items() if acc.code_hash != Account().code_hash and a != alice.address))
    b.build_block([alice.call(contract, (0xBEEF).to_bytes(32, "big")),
                   alice.transfer(b"\x42" * 20, 777)])
    factory = ProviderFactory(MemDb())
    init_genesis(factory, b.genesis, dict(b.accounts_at_genesis), committer=cpu)
    import_chain(factory, b.blocks[1:], EthBeaconConsensus(cpu))
    Pipeline(factory, default_stages(committer=cpu)).run(b.tip.number)
    with factory.provider_rw() as p:
        want = full_state_root(p, cpu)
    with factory.provider_rw() as p:
        got = full_state_root_turbo(p, backend="numpy")
    assert got == want == b.tip.state_root
