"""Sorted bulk append (``Tx.append``; ``rtpg_append`` in native/pagedkv.cpp):
MDBX's ``MDB_APPEND`` / ``MDB_APPENDDUP``, reth's ``DbCursorRW::append`` /
``DbDupCursorRW::append_dup``.

A table filled by the append reads back, through every cursor call, exactly
as the same pairs put one by one; duplicate sets of 1, 7, 8, 9 and 100,000
entries of 64 bytes cross ``DUP_SPILL`` (512 bytes inline: 7 such entries
stay in the leaf cell, 8 spill to a subtree); a pair out of order refuses the
batch with nothing written; what a commit after it wrote survives a reopen.
The hashing stages' clean drains use it.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from reth_tpu.storage.kv import MemDb
from reth_tpu.storage.native import NativeDb, PagedDb

DUP_SETS = (1, 7, 8, 9, 100_000)


def sha(*parts) -> bytes:
    return hashlib.sha256(repr(parts).encode()).digest()


def _entries(sizes, seed=0):
    """(key, dup) pairs in ascending order: one key a size, 64-byte dups
    (hashed slot || value, a ``HashedStorages`` entry's shape)."""
    rng = np.random.default_rng(seed)
    keys = sorted(sha("key", seed, i) for i in range(len(sizes)))
    out = []
    for key, n in zip(keys, sizes):
        dups = np.unique(rng.integers(0, 256, size=(n, 64), dtype=np.uint8),
                         axis=0)
        out += [(key, d.tobytes()) for d in dups]
    return out


def _open(engine, path):
    if engine == "paged":
        return PagedDb(path)
    if engine == "native":
        return NativeDb(path)
    return MemDb(None)


def _every_cursor_call(tx, table, keys, probes):
    """What a cursor gives back over ``table``: both walks, first and last,
    and every kind of seek at each probe key (present and absent)."""
    out = {"count": tx.entry_count(table)}
    c = tx.cursor(table)
    fwd, e = [], c.first()
    while e is not None:
        fwd.append(e)
        e = c.next()
    out["forward"] = fwd
    back, e = [], c.last()
    while e is not None:
        back.append(e)
        e = c.prev()
    out["backward"] = back
    nodup, e = [], c.first()
    while e is not None:
        nodup.append(e)
        e = c.next_no_dup()
    out["next_no_dup"] = nodup
    for k in probes:
        c = tx.cursor(table)
        dups, e = [], c.seek_by_key_subkey(k, b"\x80")
        while e is not None:
            dups.append(e)
            e = c.next_dup()
        out[("seek", k)] = (tx.cursor(table).seek(k),
                            tx.cursor(table).seek_exact(k), tx.get(table, k),
                            dups, tx.get_dups(table, k))
    return out


@pytest.mark.parametrize("engine", ["paged", "native", "memdb"])
@pytest.mark.parametrize("dupsort", [True, False])
def test_append_reads_back_as_the_per_entry_path(tmp_path, engine, dupsort):
    if dupsort:
        pairs = _entries(DUP_SETS)
    else:
        pairs = [(sha("plain", i), sha("v", i) * (1 + i % 40))  # up to 1,280 B
                 for i in range(3_000)]
        pairs.sort()
    keys = sorted({k for k, _ in pairs})
    probes = keys + [sha("absent", i) for i in range(8)] + [b"", b"\xff" * 33]
    views = []
    for way in ("put", "append"):
        db = _open(engine, tmp_path / way)
        with db.tx_mut() as tx:
            if way == "put":
                for k, v in pairs:
                    tx.put("t", k, v, dupsort=dupsort)
            else:   # in batches: each one after the table's last pair
                for lo in range(0, len(pairs), 40_000):
                    part = pairs[lo:lo + 40_000]
                    tx.append("t", [k for k, _ in part], [v for _, v in part],
                              dupsort=dupsort)
        with db.tx() as tx:
            views.append(_every_cursor_call(tx, "t", keys, probes))
        if engine != "memdb":
            db.close()
    assert views[0] == views[1]
    assert views[1]["count"] == len(pairs)


@pytest.mark.parametrize("n", DUP_SETS)
def test_appendup_onto_the_last_key_crosses_the_spill(tmp_path, n):
    """More duplicates of the table's last key, in a second call: inline
    sets grow into a subtree, subtrees grow along their right edge."""
    pairs = _entries([3, n], seed=1)
    cut = 3 + n // 2
    views = []
    for way in ("put", "append"):
        db = PagedDb(tmp_path / way)
        for part in (pairs[:cut], pairs[cut:]):
            with db.tx_mut() as tx:
                if way == "put":
                    for k, v in part:
                        tx.put("t", k, v, dupsort=True)
                elif part:
                    tx.append("t", [k for k, _ in part], [v for _, v in part],
                              dupsort=True)
        with db.tx() as tx:
            views.append(_every_cursor_call(tx, "t", [pairs[-1][0]], []))
        db.close()
    assert views[0] == views[1]


@pytest.mark.parametrize("engine", ["paged", "native", "memdb"])
@pytest.mark.parametrize("fault", ["inside", "below_last", "equal_dup",
                                   "equal_key_plain"])
def test_out_of_order_is_refused_with_nothing_written(tmp_path, engine, fault):
    dupsort = fault != "equal_key_plain"
    db = _open(engine, tmp_path / "kv")
    base = _entries([2, 9], seed=2)
    with db.tx_mut() as tx:
        tx.append("t", [k for k, _ in base], [v for _, v in base], dupsort=True)
    with db.tx() as tx:
        before = _every_cursor_call(tx, "t", [base[0][0], base[-1][0]], [])
    last_k, last_v = base[-1]
    above = sha("zz") if sha("zz") > last_k else b"\xff" * 32
    batch = {
        # ascending but for one pair in the middle
        "inside": [(above, b"\x02" * 64), (above, b"\x01" * 64),
                   (above + b"\x01", b"\x03" * 64)],
        # the batch starts below the table's last pair
        "below_last": [(last_k, b"\x00" * 64), (above, b"\x01" * 64)],
        # a duplicate the table already holds
        "equal_dup": [(last_k, last_v), (above, b"\x01" * 64)],
        # without dupsort a key equal to the last one is out of order
        "equal_key_plain": [(last_k, b"\xff" * 64)],
    }[fault]
    with db.tx_mut() as tx:
        with pytest.raises(ValueError):
            tx.append("t", [k for k, _ in batch], [v for _, v in batch],
                      dupsort=dupsort)
        assert _every_cursor_call(tx, "t", [base[0][0], last_k], []) == before
    with db.tx() as tx:
        assert _every_cursor_call(tx, "t", [base[0][0], last_k], []) == before
    if engine != "memdb":
        db.close()


def test_rows_of_an_array_and_lists_of_bytes_append_alike(tmp_path):
    pairs = _entries([5, 300], seed=3)
    keys = np.frombuffer(b"".join(k for k, _ in pairs), np.uint8).reshape(-1, 32)
    vals = np.frombuffer(b"".join(v for _, v in pairs), np.uint8).reshape(-1, 64)
    views = []
    for way, (k, v) in {"arrays": (keys, vals),
                        "lists": ([k for k, _ in pairs],
                                  [v for _, v in pairs])}.items():
        db = PagedDb(tmp_path / way)
        with db.tx_mut() as tx:
            tx.append("t", k, v, dupsort=True)
        with db.tx() as tx:
            views.append(_every_cursor_call(tx, "t", [pairs[0][0]], []))
        db.close()
    assert views[0] == views[1]


def test_a_commit_after_append_survives_a_reopen(tmp_path):
    pairs = _entries([1, 8, 9, 20_000], seed=4)
    db = PagedDb(tmp_path / "kv")
    with db.tx_mut() as tx:
        tx.append("t", [k for k, _ in pairs], [v for _, v in pairs], dupsort=True)
        tx.put("u", b"k", b"v")
    db.close()
    db = PagedDb(tmp_path / "kv")
    with db.tx() as tx:
        assert tx.entry_count("t") == len(pairs)
        assert list(tx.cursor("t").walk()) == pairs
        assert tx.get("u", b"k") == b"v"
    # and the tree takes ordinary writes after the append
    extra = (pairs[0][0], b"\x00" * 64)
    with db.tx_mut() as tx:
        tx.put("t", *extra, dupsort=True)
        tx.delete("t", pairs[-1][0], pairs[-1][1])
    db.close()
    db = PagedDb(tmp_path / "kv")
    with db.tx() as tx:
        assert list(tx.cursor("t").walk()) == sorted([extra] + pairs[:-1])
    db.close()


def test_an_aborted_append_leaves_the_last_commit(tmp_path):
    pairs = _entries([4, 50], seed=5)
    db = PagedDb(tmp_path / "kv")
    with db.tx_mut() as tx:
        tx.append("t", [k for k, _ in pairs[:20]], [v for _, v in pairs[:20]],
                  dupsort=True)
    tx = db.tx_mut()
    tx.append("t", [k for k, _ in pairs[20:]], [v for _, v in pairs[20:]],
              dupsort=True)
    tx.abort()
    with db.tx() as tx:
        assert list(tx.cursor("t").walk()) == pairs[:20]
    db.close()


def test_commit_pages_are_counted(tmp_path):
    from reth_tpu.metrics import db_metrics

    db = PagedDb(tmp_path / "kv")
    pages0, commits0 = db_metrics._pages.value, db_metrics._commits.value
    pairs = _entries([20_000], seed=6)
    with db.tx_mut() as tx:
        tx.append("t", [k for k, _ in pairs], [v for _, v in pairs], dupsort=True)
    pages = db_metrics._pages.value - pages0
    # 20,000 cells of 72 bytes fill ~55 to a page: the subtree's leaves alone
    assert 20_000 / 56 < pages < 20_000 / 40
    assert db_metrics._commits.value - commits0 == 1
    db.close()
