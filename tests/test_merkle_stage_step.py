"""The storage phase of ``stage run merkle`` on a paged datadir, one
``Pipeline.step`` at a time: ``MerkleExecute`` from ``default_stages`` with
the committer ``cli._make_committer`` makes for ``--hasher device`` (JAX's
CPU, as ``JAX_PLATFORMS=cpu`` entitles it here) and ``--hasher cpu``.

After a reopen of the datadir every trie's root is its ``HashedAccounts``
entry's ``storage_root`` (``trie/naive.py``) and every ``StoragesTrie`` node
is the plain reference's (``benchmark/reference/mpt_many.py``); the progress
blobs fall where the stage's rule (``while leaves < chunk_leaves``) puts the
chunks' ends, one inside a run of one-slot tries; an aborted step leaves the
previous checkpoint; ``Pipeline.run`` writes the same tables as the steps;
one step moves every counter of the stage's legs and of the store's commit.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from benchmark.reference.mpt_many import build_tries
from reth_tpu import cli, tracing
from reth_tpu.metrics import REGISTRY
from reth_tpu.primitives.rlp import encode_int, rlp_encode
from reth_tpu.primitives.types import Account, Header
from reth_tpu.stages import Pipeline, default_stages
from reth_tpu.storage import open_database
from reth_tpu.storage import tables as T
from reth_tpu.storage.provider import ProviderFactory
from reth_tpu.storage.tables import Tables
from reth_tpu.trie.naive import naive_trie_root

# one-slot tries (their one leaf is the root), a trie over DUP_SPILL (8 or
# more 64-byte entries spill to a subtree), and at CHUNK_LEAVES = 38 the
# first chunk ends on the third of a run of six one-slot tries
SIZES = (5, 30, 1, 1, 1, 1, 1, 1, 60, 2, 1, 9, 120, 7, 8, 1, 1, 1, 3)
CHUNK_LEAVES = 38
HASHERS = ("device", "cpu")


def _tries(seed: int = 7):
    """[(hashed address, [(hashed slot, value)] ascending)], addresses
    ascending; values of 1 to 32 bytes, so some leaves embed."""
    rng = np.random.default_rng(seed)
    addrs = sorted(r.tobytes()
                   for r in rng.integers(0, 256, (len(SIZES), 32), np.uint8))
    out = []
    for addr, n in zip(addrs, SIZES):
        slots = sorted({rng.integers(0, 256, 32, np.uint8).tobytes()
                        for _ in range(n)})
        widths = rng.integers(1, 33, n)
        vals = [int.from_bytes(rng.integers(1, 256, int(w), np.uint8).tobytes(),
                               "big") for w in widths]
        out.append((addr, list(zip(slots, vals))))
    return out


def _account(i: int) -> Account:
    return Account(nonce=1, balance=10**18 + i, code_hash=bytes([i + 1]) * 32)


def _state_root(tries) -> bytes:
    leaves = {}
    for i, (addr, slots) in enumerate(tries):
        root = naive_trie_root({s: rlp_encode(encode_int(v)) for s, v in slots})
        leaves[addr] = _account(i).with_(storage_root=root).trie_encode()
    return naive_trie_root(leaves)


def _datadir(path, tries):
    """A paged datadir as the hashing stages leave it: HashedStorages and
    HashedAccounts filled by the sorted bulk append, every storage_root the
    empty trie's; the header of block 1 holds the state root."""
    db = open_database("paged", path)
    with ProviderFactory(db).provider_rw() as p:
        p.tx.append(Tables.HashedStorages.name,
                    [a for a, slots in tries for _ in slots],
                    [T.encode_storage_entry(s, v) for _, slots in tries
                     for s, v in slots], dupsort=True)
        p.tx.append(Tables.HashedAccounts.name, [a for a, _ in tries],
                    [T.encode_account(_account(i)) for i in range(len(tries))])
        p.insert_header(Header(number=1, state_root=_state_root(tries)))
    db.close()


def _stage(hasher: str):
    args = cli.build_parser().parse_args(
        ["stage", "run", "--datadir", "unused", "--stage", "MerkleExecute",
         "--db", "paged", "--hasher", hasher])
    (stage,) = [s for s in default_stages(committer=cli._make_committer(args))
                if s.id == "MerkleExecute"]
    stage.chunk_leaves = CHUNK_LEAVES
    return stage


def _chunk_ends(tries) -> list[bytes]:
    """The last address of each chunk, by the stage's own rule."""
    ends, leaves = [], 0
    for i, (addr, slots) in enumerate(tries):
        leaves += len(slots)
        if leaves >= CHUNK_LEAVES or i == len(tries) - 1:
            ends.append(addr)
            leaves = 0
    return ends


def _progress(factory):
    with factory.provider() as p:
        return p.stage_progress("MerkleExecute")


def _step_through(factory, stage):
    """Step the stage to the end; the storage chunks' progress blobs in
    order."""
    pipe = Pipeline(factory, [stage])
    blobs = []
    while True:
        out = pipe.step(stage, 1)
        blob = _progress(factory)
        if blob is not None and blob[:1] == b"S" and len(blob) > 9:
            blobs.append(blob[9:])
        if out is None or out.done:
            return blobs


def _dump(db) -> dict:
    with db.tx() as tx:
        return {t.name: list(tx.cursor(t.name).walk()) for t in Tables.all()}


def _check_tables(db, tries) -> None:
    refs = build_tries([
        (np.frombuffer(b"".join(s for s, _ in slots), np.uint8).reshape(-1, 32),
         [rlp_encode(encode_int(v)) for _, v in slots]) for _, slots in tries])
    with ProviderFactory(db).provider() as p:
        for (addr, slots), ref in zip(tries, refs):
            root = naive_trie_root({s: rlp_encode(encode_int(v))
                                    for s, v in slots})
            assert ref.root == root
            assert p.hashed_account(addr).storage_root == root
            got = {}
            for _, dup in p.tx.cursor(Tables.StoragesTrie.name).walk_dup(addr):
                path, node = T.decode_storage_trie_entry(dup)
                got[path] = (node.state_mask, node.tree_mask, node.hash_mask,
                             tuple(node.hashes))
            assert got == ref.branches


@pytest.mark.parametrize("hasher", HASHERS)
def test_stepping_the_storage_phase_writes_what_the_reference_builds(
        tmp_path, hasher):
    tries = _tries()
    _datadir(tmp_path, tries)
    db = open_database("paged", tmp_path)
    factory = ProviderFactory(db)
    blobs = _step_through(factory, _stage(hasher))
    assert blobs == _chunk_ends(tries)
    assert blobs[0] == tries[4][0]   # the third of six one-slot tries
    with factory.provider() as p:
        assert p.stage_checkpoint("MerkleExecute") == 1   # the root held
        assert p.stage_progress("MerkleExecute") is None
    db.close()
    db = open_database("paged", tmp_path)
    _check_tables(db, tries)
    db.close()


@pytest.mark.parametrize("hasher", HASHERS)
def test_an_aborted_step_leaves_the_last_checkpoint_and_a_rerun_agrees(
        tmp_path, monkeypatch, hasher):
    from reth_tpu.storage.provider import DatabaseProvider

    tries = _tries()
    for d in ("clean", "aborted"):
        _datadir(tmp_path / d, tries)
    clean = open_database("paged", tmp_path / "clean")
    _step_through(ProviderFactory(clean), _stage(hasher))
    want = _dump(clean)
    clean.close()

    db = open_database("paged", tmp_path / "aborted")
    factory = ProviderFactory(db)
    stage = _stage(hasher)
    pipe = Pipeline(factory, [stage])
    for _ in range(3):   # the tables cleared, then two chunks
        pipe.step(stage, 1)
    before = _dump(db)
    real = DatabaseProvider.save_stage_progress

    def fail_after_the_writes(self, stage_id, blob):
        if blob is not None and blob[:1] == b"S" and len(blob) > 9:
            raise OSError("power lost before the commit")
        return real(self, stage_id, blob)

    monkeypatch.setattr(DatabaseProvider, "save_stage_progress",
                        fail_after_the_writes)
    with pytest.raises(OSError):
        pipe.step(stage, 1)
    monkeypatch.setattr(DatabaseProvider, "save_stage_progress", real)
    assert _dump(db) == before
    db.close()
    db = open_database("paged", tmp_path / "aborted")
    assert _dump(db) == before
    _step_through(ProviderFactory(db), _stage(hasher))
    assert _dump(db) == want
    db.close()


@pytest.mark.parametrize("hasher", HASHERS)
def test_run_writes_the_same_tables_as_the_steps(tmp_path, hasher):
    tries = _tries(seed=8)
    dumps = []
    for way in ("run", "step"):
        _datadir(tmp_path / way, tries)
        db = open_database("paged", tmp_path / way)
        factory = ProviderFactory(db)
        stage = _stage(hasher)
        if way == "run":
            Pipeline(factory, [stage]).run(1)
        else:
            _step_through(factory, stage)
        db.close()
        db = open_database("paged", tmp_path / way)
        dumps.append(_dump(db))
        db.close()
    assert dumps[0] == dumps[1]
    assert dumps[0][Tables.StoragesTrie.name]


STEP_COUNTERS = (
    "stage_merkle_read_seconds_total", "stage_merkle_commit_seconds_total",
    "stage_merkle_write_seconds_total", "stage_merkle_slots_read_total",
    "stage_merkle_branch_nodes_written_total",
    "stage_merkle_accounts_written_total", "db_commit_seconds_total",
    "db_commits_total", "db_commit_pages_written_total")


def _counters() -> dict[str, float]:
    return {name: m.value for name, m in REGISTRY.items()
            if name in STEP_COUNTERS}


def test_one_step_moves_every_counter_and_span(tmp_path, monkeypatch):
    """The second step is the first storage chunk: 35 + 3 slots of five
    tries. The commit's seconds hold the engine's whole commit call, the
    fdatasync calls inside it included: a commit made 0.2 s slower (a slow
    disk's sync) moves ``db_commit_seconds_total`` by as much."""
    tries = _tries()
    _datadir(tmp_path, tries)
    db = open_database("paged", tmp_path)
    factory = ProviderFactory(db)
    stage = _stage("cpu")
    pipe = Pipeline(factory, [stage])
    pipe.step(stage, 1)   # the tables cleared, progress "S"
    real_commit = db._api.commit

    def slow_commit(txn):
        time.sleep(0.2)
        return real_commit(txn)

    monkeypatch.setattr(db._api, "commit", slow_commit)
    before = _counters()
    tracing.set_trace_enabled(True)
    try:
        rec = tracing.flight_recorder()
        n0 = rec.recorded
        pipe.step(stage, 1)
        spans = [(s["target"], s["name"]) for s in
                 rec.snapshot()[-(rec.recorded - n0):]]
    finally:
        tracing.set_trace_enabled(False)
    moved = {k: v - before.get(k, 0.0) for k, v in _counters().items()}
    assert set(moved) == set(STEP_COUNTERS)
    assert all(v > 0 for v in moved.values()), moved
    assert moved["stage_merkle_slots_read_total"] == 38
    assert moved["stage_merkle_accounts_written_total"] == 5
    with factory.provider() as p:
        nodes = sum(1 for a, _ in tries[:5] for _ in p.tx.cursor(
            Tables.StoragesTrie.name).walk_dup(a))
    assert moved["stage_merkle_branch_nodes_written_total"] == nodes > 0
    assert moved["db_commits_total"] == 1
    assert moved["db_commit_seconds_total"] >= 0.2
    assert spans.index(("stages::merkle", "read")) < spans.index(
        ("stages::merkle", "commit")) < spans.index(
        ("stages::merkle", "write")) < spans.index(("storage", "commit"))
    db.close()
