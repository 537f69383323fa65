"""The storage chunk's branch nodes into ``StoragesTrie`` by one sorted append
(``DatabaseProvider.storage_branch_batch``), on the paged engine, the WAL
engine and ``MemDb``.

A clean storage phase stepped through ``Pipeline.step`` leaves
``StoragesTrie``, ``HashedAccounts`` and the progress blob byte for byte as
the per-node puts do, every node appended; a table holding an entry at or
past the batch's first address, a path put twice and a batch out of (length,
path) order each replay the batch node by node, to the per-node result; an
exception inside the scope writes nothing.
"""

from __future__ import annotations

import contextlib

import numpy as np
import pytest

from reth_tpu.metrics import REGISTRY
from reth_tpu.primitives.types import Account
from reth_tpu.stages import Pipeline, merkle
from reth_tpu.stages.merkle import MerkleStage
from reth_tpu.storage import tables as T
from reth_tpu.storage.kv import MemDb
from reth_tpu.storage.native import NativeDb, PagedDb
from reth_tpu.storage.provider import (DatabaseProvider, ProviderFactory,
                                       StorageBranchBatch)
from reth_tpu.storage.tables import Tables
from reth_tpu.trie.committer import BranchNode

ENGINES = ("paged", "native", "memdb")
SIZES = (5, 30, 1, 1, 60, 2, 1, 9, 120, 7, 8, 1, 3)
COMPARED = (Tables.StoragesTrie.name, Tables.HashedAccounts.name,
            Tables.StageCheckpointProgresses.name)
APPENDED = "stage_merkle_branch_nodes_appended_total"
WRITTEN = "stage_merkle_branch_nodes_written_total"
REPLAYS = "stage_merkle_append_replays_total"


def _open(engine, path):
    if engine == "paged":
        return PagedDb(path)
    if engine == "native":
        return NativeDb(path)
    return MemDb(None)


def _close(db):
    if not isinstance(db, MemDb):   # MemDb(None) holds no file
        db.close()


def _tries(seed: int = 11):
    """[(hashed address, [(hashed slot, value)] ascending)], addresses
    ascending."""
    rng = np.random.default_rng(seed)
    addrs = sorted(r.tobytes()
                   for r in rng.integers(0, 256, (len(SIZES), 32), np.uint8))
    out = []
    for addr, n in zip(addrs, SIZES):
        slots = sorted({rng.integers(0, 256, 32, np.uint8).tobytes()
                        for _ in range(n)})
        out.append((addr, [(s, int(rng.integers(1, 1 << 40))) for s in slots]))
    return out


def _db(engine, path, tries):
    """A store as the hashing stages leave it: HashedStorages and
    HashedAccounts filled, every storage_root the empty trie's."""
    db = _open(engine, path)
    with ProviderFactory(db).provider_rw() as p:
        p.tx.append(Tables.HashedStorages.name,
                    [a for a, slots in tries for _ in slots],
                    [T.encode_storage_entry(s, v) for _, slots in tries
                     for s, v in slots], dupsort=True)
        p.tx.append(Tables.HashedAccounts.name, [a for a, _ in tries],
                    [T.encode_account(Account(nonce=1, balance=i + 1))
                     for i in range(len(tries))])
    return db


def _dump(db) -> dict:
    with db.tx() as tx:
        return {t: list(tx.cursor(t).walk()) for t in COMPARED}


@contextlib.contextmanager
def _unbatched(self):
    """``storage_branch_batch`` as a scope that collects nothing: every
    ``put_storage_branch`` inside it is the per-node ``_replace_dup``."""
    yield StorageBranchBatch()


def _storage_phase(db, chunk_leaves, before_chunks=None) -> list[dict]:
    """Step ``MerkleExecute`` through its storage phase; the compared tables
    after each step. ``before_chunks(provider)`` runs in a transaction of
    its own once the first step has cleared the trie tables."""
    factory = ProviderFactory(db)
    stage = MerkleStage(chunk_leaves=chunk_leaves)
    pipe = Pipeline(factory, [stage])
    dumps = []
    while True:
        pipe.step(stage, 1)
        if before_chunks is not None and not dumps:
            with factory.provider_rw() as p:
                before_chunks(p)
        dumps.append(_dump(db))
        with factory.provider() as p:
            if p.stage_progress(stage.id)[:1] == b"A":
                return dumps


def _counters() -> dict:
    return {n: REGISTRY.counter(n).value for n in (APPENDED, WRITTEN, REPLAYS)}


def _moved(c0) -> dict:
    return {n: v - c0[n] for n, v in _counters().items()}


def _node(seed: int) -> BranchNode:
    rng = np.random.default_rng(seed)
    return BranchNode(0xFFFF, 0, 0b11, tuple(
        rng.integers(0, 256, 32, np.uint8).tobytes() for _ in range(2)))


def _run_both(tmp_path, monkeypatch, engine, tries, chunk_leaves,
              before_chunks=None):
    """The storage phase on two stores of ``tries``: batched, then with the
    scope collecting nothing. (batched dumps, per-node dumps, the batched
    run's counters moved)."""
    c0 = _counters()
    db = _db(engine, tmp_path / "batched", tries)
    got = _storage_phase(db, chunk_leaves, before_chunks)
    _close(db)
    moved = _moved(c0)
    with monkeypatch.context() as mp:
        mp.setattr(DatabaseProvider, "storage_branch_batch", _unbatched)
        db = _db(engine, tmp_path / "per_node", tries)
        want = _storage_phase(db, chunk_leaves, before_chunks)
        _close(db)
    return got, want, moved


@pytest.mark.parametrize("engine", ENGINES)
def test_a_clean_storage_phase_appends_the_per_node_tables(
        tmp_path, monkeypatch, engine):
    tries = _tries()
    got, want, moved = _run_both(tmp_path, monkeypatch, engine, tries, 40)
    assert len(got) > 3     # the clear, then several chunks
    assert got == want
    assert got[-1][Tables.StoragesTrie.name]
    assert moved[WRITTEN] == len(got[-1][Tables.StoragesTrie.name])
    assert moved[APPENDED] == moved[WRITTEN]
    assert moved[REPLAYS] == 0


def _table_holds_the_first_address(tries, monkeypatch):
    def plant(p):
        # the first trie's root path, as a node whose entry sorts below the
        # one the chunk writes there: the store's own order check passes it
        p.put_storage_branch(tries[0][0], b"", BranchNode(0b11, 0, 0, ()))
    return plant


def _a_path_put_twice(tries, monkeypatch):
    real = DatabaseProvider.put_storage_branch

    def put(self, addr, path, node):
        if addr == tries[0][0]:   # first as another node, then as itself
            real(self, addr, path, _node(3))
        real(self, addr, path, node)

    monkeypatch.setattr(DatabaseProvider, "put_storage_branch", put)


def _paths_longest_first(tries, monkeypatch):
    monkeypatch.setattr(merkle, "_trie_order", lambda path: (-len(path), path))


@pytest.mark.parametrize("refusal", [_table_holds_the_first_address,
                                     _a_path_put_twice, _paths_longest_first])
@pytest.mark.parametrize("engine", ENGINES)
def test_a_refused_batch_replays_to_the_per_node_tables(
        tmp_path, monkeypatch, engine, refusal):
    tries = _tries()
    # one storage chunk: the batch the refusal meets is the phase's only one
    plant = refusal(tries, monkeypatch)
    got, want, moved = _run_both(tmp_path, monkeypatch, engine, tries,
                                 sum(SIZES), plant)
    assert len(got) == 3    # the clear, the chunk, the phase's end
    assert got == want
    assert moved[REPLAYS] == 1
    assert moved[APPENDED] == 0
    assert moved[WRITTEN] > 0


def _entries(tx, addr):
    return [dup for _, dup in tx.cursor(Tables.StoragesTrie.name).walk_dup(addr)]


@pytest.mark.parametrize("engine", ENGINES)
def test_an_exception_in_the_scope_writes_nothing(tmp_path, engine):
    addr = b"\x42" * 32
    db = _open(engine, tmp_path / "db")
    with ProviderFactory(db).provider_rw() as p:
        p.put_storage_branch(addr, b"\x01", _node(4))
        before = _entries(p.tx, addr)
        with pytest.raises(RuntimeError, match="mid-chunk"):
            with p.storage_branch_batch() as batch:
                p.put_storage_branch(b"\x43" * 32, b"", _node(5))
                p.put_storage_branch(addr, b"\x01", _node(6))
                raise RuntimeError("mid-chunk")
        assert (batch.appended, batch.replayed) == (0, False)
        assert _entries(p.tx, addr) == before
        assert _entries(p.tx, b"\x43" * 32) == []
        # outside the scope a put is per node again
        p.put_storage_branch(addr, b"\x01", _node(7))
        assert _entries(p.tx, addr) == [T.encode_storage_trie_entry(
            b"\x01", _node(7))]
    _close(db)


@pytest.mark.parametrize("engine", ENGINES)
def test_an_ordered_batch_after_the_last_key_is_appended(tmp_path, engine):
    db = _open(engine, tmp_path / "db")
    paths = [b"", b"\x00", b"\x0f", b"\x03\x01"]
    with ProviderFactory(db).provider_rw() as p:
        p.put_storage_branch(b"\x01" * 32, b"", _node(8))
        with p.storage_branch_batch() as batch:
            for addr in (b"\x02" * 32, b"\x03" * 32):
                for i, path in enumerate(paths):
                    p.put_storage_branch(addr, path, _node(i))
        assert (batch.appended, batch.replayed) == (8, False)
        assert _entries(p.tx, b"\x03" * 32) == [
            T.encode_storage_trie_entry(path, _node(i))
            for i, path in enumerate(paths)]
        assert p.storage_branch(b"\x02" * 32, b"\x03\x01") == _node(3)
    _close(db)
