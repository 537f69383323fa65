"""Turbo commit path: native structure sweep + array-level hashing backends.

The MerkleStage rebuild and every other full-trie commit, with NO per-node
Python, on ONE path (``TurboCommitter.commit_hashed_pipelined``;
``commit_hashed_many`` is its older name):

  jobs: 32-byte hashed keys + RLP values, one job a trie
    └─ _group_jobs → sweep groups, closed by their leaves alone;
       PACK_WINDOW consecutive groups a window
        └─ _sweep_group, one a group: the group marshalled in one piece,
           then native/triebuild.cpp (C++ sweep: structure + RLP
           templates/masks, flat per-level arrays, in place of
           trie/committer.py's per-node recursion). Several groups: on a
           thread pool, side by side. ONE group (a chunk of one subtrie, a
           job list under LEAVES_PER_SWEEP leaves): by the caller. A job of
           SWEEP_THREADS * LEAVES_PER_SWEEP leaves or more: its first
           branch's children on SWEEP_THREADS threads inside the native
           call, into the arrays one thread would have made
            └─ _pack_window: same-depth levels of a window's groups merged
               (one group's pass through uncopied); per level, deepest first:
               PACKED rows  → backend.dispatch_packed   (device)
               BITMAP rows  → backend.dispatch_branch   (device)
               ... or the numpy twin (`_NumpyBackend`), the measured CPU
               baseline and the no-jax fallback
                └─ ONE digest fetch: roots (+ branch-node hashes when
                   TrieUpdates collection is requested: what of the
                   results needs no digest is built between the programs'
                   launch and the wait)

Reference analogue: StateRoot's cursor walk + HashBuilder + asm-keccak
(reference crates/trie/trie/src/trie.rs:32, crates/stages/stages/src/
stages/hashing_account.rs:29-32), re-partitioned so the host does memcpy
work and the device does all hashing.
"""

from __future__ import annotations

import ctypes
import gc
import os
import subprocess
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from itertools import chain, islice, repeat
from pathlib import Path

import numpy as np

from .. import tracing
from ..primitives.keccak import (
    RATE,
    keccak256,
    keccak256_words_masked_np,
)
from ..primitives.types import EMPTY_ROOT_HASH
from .committer import TrieBuildResult, branch_nodes_hashed_later

_SRC = Path(__file__).resolve().parent.parent.parent / "native" / "triebuild.cpp"
_SO = _SRC.parent / "build" / "libtriebuild.so"
_build_lock = threading.Lock()
_lib = None

_i32p = ctypes.POINTER(ctypes.c_int32)
_u8p = ctypes.POINTER(ctypes.c_uint8)
_u16p = ctypes.POINTER(ctypes.c_uint16)
_u32p = ctypes.POINTER(ctypes.c_uint32)
_u64p = ctypes.POINTER(ctypes.c_uint64)


def load_library() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        if not _SO.exists() or _SO.stat().st_mtime < _SRC.stat().st_mtime:
            _SO.parent.mkdir(parents=True, exist_ok=True)
            cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
                   str(_SRC), "-o", str(_SO)]
            proc = subprocess.run(cmd, capture_output=True, text=True)
            if proc.returncode != 0:
                raise RuntimeError(f"g++ failed building triebuild:\n{proc.stderr}")
        lib = ctypes.CDLL(str(_SO))
        lib.rtb_build.restype = ctypes.c_void_p
        lib.rtb_build.argtypes = [_u8p, ctypes.c_uint64, _u64p, ctypes.c_uint32,
                                  _u8p, _u64p, ctypes.c_int, ctypes.c_int,
                                  ctypes.c_int, ctypes.c_uint64, _i32p]
        lib.rtb_free.argtypes = [ctypes.c_void_p]
        for name, res in [("rtb_num_levels", ctypes.c_int32),
                          ("rtb_max_slot", ctypes.c_int32)]:
            getattr(lib, name).restype = res
            getattr(lib, name).argtypes = [ctypes.c_void_p]
        lib.rtb_level_depth.restype = ctypes.c_uint32
        lib.rtb_level_depth.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.rtb_packed_bytes.restype = ctypes.c_uint64
        lib.rtb_packed_bytes.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        for name in ["rtb_packed_rows", "rtb_packed_holes", "rtb_bmp_rows",
                     "rtb_bmp_children"]:
            getattr(lib, name).restype = ctypes.c_uint32
            getattr(lib, name).argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.rtb_packed_get.argtypes = [ctypes.c_void_p, ctypes.c_int32, _u8p, _u32p, _i32p]
        lib.rtb_packed_get_holes.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                             _i32p, _i32p, _i32p]
        lib.rtb_bmp_get.argtypes = [ctypes.c_void_p, ctypes.c_int32, _u16p, _i32p]
        lib.rtb_bmp_get_children.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                             _i32p, _i32p, _i32p]
        lib.rtb_roots.argtypes = [ctypes.c_void_p, _i32p]
        lib.rtb_root_inline_len.restype = ctypes.c_uint32
        lib.rtb_root_inline_len.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        lib.rtb_root_inline.argtypes = [ctypes.c_void_p, ctypes.c_uint32, _u8p]
        lib.rtb_meta_count.restype = ctypes.c_uint64
        lib.rtb_meta_count.argtypes = [ctypes.c_void_p]
        lib.rtb_meta_get.argtypes = [ctypes.c_void_p, _u8p]
        _lib = lib
        return lib


def _ptr(arr: np.ndarray, ty):
    return arr.ctypes.data_as(ty)


class _Level:
    """One depth level as flat numpy arrays, straight from the native sweep."""

    __slots__ = ("depth", "flat", "row_off", "row_len", "row_slot", "holes",
                 "masks", "bmp_slot", "children", "b_tier")

    def __init__(self, lib, h, i):
        self.depth = lib.rtb_level_depth(h, i)
        nb = int(lib.rtb_packed_bytes(h, i))
        nr = int(lib.rtb_packed_rows(h, i))
        self.flat = np.zeros((nb,), dtype=np.uint8)
        row_off_full = np.zeros((nr + 1,), dtype=np.uint32)
        self.row_slot = np.zeros((nr,), dtype=np.int32)
        if nr:
            lib.rtb_packed_get(h, i, _ptr(self.flat, _u8p),
                               _ptr(row_off_full, _u32p), _ptr(self.row_slot, _i32p))
        self.row_off = row_off_full[:-1]
        self.row_len = np.diff(row_off_full).astype(np.uint32)
        nh = int(lib.rtb_packed_holes(h, i))
        if nh:
            self.holes = np.zeros((3, nh), dtype=np.int32)
            lib.rtb_packed_get_holes(h, i, _ptr(self.holes[0], _i32p),
                                     _ptr(self.holes[1], _i32p), _ptr(self.holes[2], _i32p))
        else:
            self.holes = None
        nbm = int(lib.rtb_bmp_rows(h, i))
        self.masks = np.zeros((nbm,), dtype=np.uint16)
        self.bmp_slot = np.zeros((nbm,), dtype=np.int32)
        nch = int(lib.rtb_bmp_children(h, i))
        self.children = np.zeros((3, max(nch, 0)), dtype=np.int32)
        if nbm:
            lib.rtb_bmp_get(h, i, _ptr(self.masks, _u16p), _ptr(self.bmp_slot, _i32p))
        if nch:
            lib.rtb_bmp_get_children(h, i, _ptr(self.children[0], _i32p),
                                     _ptr(self.children[1], _i32p),
                                     _ptr(self.children[2], _i32p))
        maxlen = int(self.row_len.max()) if nr else 0
        bt = 1
        while bt * RATE <= maxlen:
            bt *= 2
        self.b_tier = bt


class DigestArena:
    """Resident host staging for the numpy hashing twin.

    One arena lives as long as its committer and is REUSED across commits:
    the (S, 32) digest buffer grows geometrically and is never freed
    between rebuild chunks, and each hashing thread keeps a resident
    row-staging scratch — replacing the per-subtrie buffer allocations the
    chunked rebuild used to pay once per prefix per pass. Growth preserves
    already-written digests, so a commit can extend the arena mid-flight
    (``ensure``) without re-hashing earlier subtries."""

    def __init__(self):
        self._digests: np.ndarray | None = None
        self._tls = threading.local()
        self.grows = 0  # observability: how often the arena re-allocated

    def digest_buf(self, n_slots: int) -> np.ndarray:
        cur = self._digests
        if cur is None or cur.shape[0] < n_slots:
            cap = 1024 if cur is None else cur.shape[0]
            while cap < n_slots:
                cap *= 2
            buf = np.zeros((cap, 32), dtype=np.uint8)
            if cur is not None:
                buf[: cur.shape[0]] = cur
                self.grows += 1
            self._digests = buf
        return self._digests

    def rows(self, n: int, length: int) -> np.ndarray:
        """Per-thread resident staging for one dispatch's padded rows
        (thread-local: commits on two threads never share a scratch buffer)."""
        need = n * length
        buf = getattr(self._tls, "buf", None)
        if buf is None or buf.size < need:
            buf = np.empty((max(need, 1 << 16),), dtype=np.uint8)
            self._tls.buf = buf
        return buf[:need].reshape(n, length)


class _NumpyBackend:
    """CPU twin of the device engine — the measured baseline, the no-jax
    fallback, and the supervisor's mid-commit failover target
    (ops/supervisor.py SupervisedBackend). Same array protocol as the
    fused engines — including the committer's bucket protocol
    (``alloc_slot``/``dispatch_level``) — with digests in a host buffer.
    With an ``arena`` the digest buffer and row staging are resident
    (reused across commits) instead of per-commit allocations."""

    effective_kind = "numpy"

    def __init__(self, arena: DigestArena | None = None):
        self._arena = arena
        self._buf = None
        self._n_slots = 1

    def begin(self, max_slots: int) -> None:
        if self._arena is not None:
            self._buf = self._arena.digest_buf(max_slots + 1)
        else:
            self._buf = np.zeros((max_slots + 1, 32), dtype=np.uint8)
        self._n_slots = 1  # slot 0 = dummy (mirrors FusedLevelEngine)

    def ensure(self, max_slots: int) -> None:
        """Grow the digest buffer to hold ``max_slots`` slots, preserving
        written digests. The committer only learns a window's slot
        high-water mark when its sweeps land, so capacity extends
        mid-commit."""
        need = max_slots + 1
        if self._buf is not None and self._buf.shape[0] >= need:
            return
        if self._arena is not None:
            self._buf = self._arena.digest_buf(need)
            return
        cap = max(1024, self._buf.shape[0] if self._buf is not None else 0)
        while cap < need:
            cap *= 2
        grown = np.zeros((cap, 32), dtype=np.uint8)
        if self._buf is not None:
            grown[: self._buf.shape[0]] = self._buf
        self._buf = grown

    def _rows_scratch(self, n: int, length: int) -> np.ndarray:
        if self._arena is not None:
            return self._arena.rows(n, length)
        return np.empty((n, length), dtype=np.uint8)

    def alloc_slot(self) -> int:
        slot = self._n_slots
        self._n_slots += 1
        return slot

    def dispatch_level(self, bucket) -> None:
        """CPU twin of ``FusedLevelEngine.dispatch_level``: pad the bucket's
        RLP templates, splice child digests from the host buffer, hash."""
        n = len(bucket.templates)
        if n == 0:
            return
        b_tier = 2
        while b_tier < bucket.nb_max:
            b_tier *= 2
        L = b_tier * RATE
        rows = self._rows_scratch(n, L)
        rows[:] = 0
        for i, t in enumerate(bucket.templates):
            rows[i, : len(t)] = np.frombuffer(t, dtype=np.uint8)
            rows[i, len(t)] ^= 0x01
            rows[i, bucket.counts[i] * RATE - 1] ^= 0x80
        for row, off, src in bucket.holes:
            rows[row, off : off + 32] = self._buf[src]
        self._hash_rows(rows, np.asarray(bucket.counts, dtype=np.int64),
                        np.asarray(bucket.slots, dtype=np.int64), b_tier)

    def _hash_rows(self, rows: np.ndarray, counts: np.ndarray, slots: np.ndarray,
                   b_tier: int) -> None:
        lanes = keccak256_words_masked_np(
            np.ascontiguousarray(rows).view("<u8"), b_tier, counts
        )
        self._buf[slots] = np.ascontiguousarray(lanes).view(np.uint8).reshape(-1, 32)

    def dispatch_packed(self, flat, row_off, row_len, slots, holes, b_tier) -> None:
        n = len(row_off)
        if n == 0:
            return
        L = b_tier * RATE
        col = np.arange(L, dtype=np.uint32)[None, :]
        idx = np.minimum(row_off[:, None] + col, max(len(flat) - 1, 0))
        rows = self._rows_scratch(n, L)
        if len(flat):
            np.take(flat, idx.astype(np.int64, copy=False), out=rows)
            np.multiply(rows, col < row_len[:, None], out=rows, casting="unsafe")
        else:
            rows[:] = 0
        r = np.arange(n)
        counts = (row_len // RATE + 1).astype(np.int64)
        rows[r, row_len] ^= 0x01
        rows[r, counts * RATE - 1] ^= 0x80
        if holes is not None:
            hr, ho, hs = holes
            rows[hr[:, None], ho[:, None] + np.arange(32)] = self._buf[hs]
        self._hash_rows(rows, counts, slots, b_tier)

    def dispatch_branch(self, masks, slots, children) -> None:
        n = len(masks)
        if n == 0:
            return
        L = 4 * RATE
        nibs = np.arange(16, dtype=np.int32)[None, :]
        present = ((masks[:, None].astype(np.int32) >> nibs) & 1).astype(np.int64)
        sizes = 1 + 32 * present
        csum = np.cumsum(sizes, axis=1) - sizes
        payload = sizes.sum(axis=1) + 1
        hl = np.where(payload > 0xFF, 3, 2)
        total = hl + payload
        rows = self._rows_scratch(n, L)
        rows[:] = 0
        rows[:, 0] = np.where(hl == 3, 0xF9, 0xF8)
        rows[:, 1] = np.where(hl == 3, payload >> 8, payload & 0xFF)
        rows[:, 2] = payload & 0xFF  # f8 rows: overwritten by first marker
        r16 = np.repeat(np.arange(n), 16)
        rows[r16, (hl[:, None] + csum).reshape(-1)] = np.where(
            present == 1, 0xA0, 0x80
        ).reshape(-1)
        rows[np.arange(n), total - 1] = 0x80
        cr, cn, cs = children
        off = hl[cr] + csum[cr, cn] + 1
        rows[cr[:, None], off[:, None] + np.arange(32)] = self._buf[cs]
        counts = total // RATE + 1
        rows[np.arange(n), total] ^= 0x01
        rows[np.arange(n), counts * RATE - 1] ^= 0x80
        self._hash_rows(rows, counts, slots, 4)

    def fetch_slots(self, slots: np.ndarray) -> np.ndarray:
        out = self._buf[slots]
        self._buf = None
        return out

    def finish(self) -> np.ndarray:
        buf, self._buf = self._buf, None
        return buf

    def flush_window(self) -> None:
        """Window-boundary hook (whole-subtrie engines execute their
        staged chunk here); the CPU twin hashes eagerly, so no-op."""


def _marshal_one(keys, values):
    """One job's keys sorted and its values in that order."""
    keys = np.ascontiguousarray(keys, dtype=np.uint8).reshape(-1, 32)
    if len(keys) != len(values):
        raise ValueError("keys/values length mismatch")
    order = np.argsort(keys.view("S32").ravel(), kind="stable")
    return keys[order], [values[i] for i in order]


def _marshal_group(jobs):
    """A group of several jobs, marshalled in one piece: ONE stable sort of
    the group's rows by (job number, key) and one pass over the values, so
    the cost follows the group's leaves and not its number of tries. The
    job number leads the sort key, so a key two jobs share is no duplicate,
    and an order that comes out the identity (the stage hands over sorted
    keys) moves neither keys nor values. Returns the sorted keys, the
    values in that order and the jobs' key counts."""
    key_list = [np.ascontiguousarray(k, dtype=np.uint8).reshape(-1, 32)
                for k, _ in jobs]
    counts = np.fromiter(map(len, key_list), dtype=np.int64, count=len(jobs))
    if counts.tolist() != [len(v) for _, v in jobs]:
        raise ValueError("keys/values length mismatch")
    keys = np.concatenate(key_list)
    rows = np.empty((len(keys), 36), dtype=np.uint8)
    rows[:, :4] = np.repeat(
        np.arange(len(jobs), dtype=">u4"), counts).view(np.uint8).reshape(-1, 4)
    rows[:, 4:] = keys
    order = np.argsort(rows.view("S36").ravel(), kind="stable")
    values = list(chain.from_iterable(v for _, v in jobs))
    if (order[1:] < order[:-1]).any():
        keys = keys[order]
        values = list(map(values.__getitem__, order.tolist()))
    return keys, values, counts


def _marshal_and_build(lib, jobs, collect_branches: bool, start_depth: int):
    """Sort each job's keys, flatten values, and run the native structure
    sweep: one job by its own sort (``_marshal_one``), several in one piece
    (``_marshal_group``). A job of ``SWEEP_THREADS * LEAVES_PER_SWEEP``
    leaves or more is swept on ``SWEEP_THREADS`` threads inside the native
    call, into the arrays one thread would have made. Returns (handle, the
    group's sorted keys, job after job in one array, the number of jobs
    swept so and their leaves); the caller owns the handle (``rtb_free``).
    Raises ``ValueError`` on sweep rejection — exactly the condition the
    MerkleStage uses to fall back to the general committer."""
    from ..metrics import pipeline_metrics, trie_metrics

    with trie_metrics.phase("marshal"):
        if len(jobs) == 1:
            all_keys, val_chunks = _marshal_one(*jobs[0])
            counts = [len(all_keys)]
        else:
            all_keys, val_chunks, counts = _marshal_group(jobs)
        job_off = np.zeros((len(jobs) + 1,), dtype=np.uint64)
        job_off[1:] = np.cumsum(counts)
        flat_vals = b"".join(val_chunks)
        val_off = np.zeros((len(val_chunks) + 1,), dtype=np.uint64)
        if val_chunks:
            val_off[1:] = np.cumsum(
                np.fromiter((len(v) for v in val_chunks), dtype=np.uint64,
                            count=len(val_chunks))
            )
        vals_np = np.frombuffer(flat_vals, dtype=np.uint8) if flat_vals else np.zeros(1, np.uint8)
    # read at call time: the layout's constants are what a test moves
    threads, threaded_job_leaves = SWEEP_THREADS, SWEEP_THREADS * LEAVES_PER_SWEEP
    err = ctypes.c_int32(0)
    with trie_metrics.phase("sweep"):
        h = lib.rtb_build(
            _ptr(all_keys, _u8p), len(all_keys),
            _ptr(job_off, _u64p), len(jobs),
            _ptr(vals_np, _u8p), _ptr(val_off, _u64p),
            1 if collect_branches else 0, start_depth,
            threads, threaded_job_leaves, ctypes.byref(err),
        )
    if not h:
        reason = {1: "unsorted", 2: "duplicate keys", 3: "bad input",
                  4: "oversized leaf value"}.get(err.value, "unknown")
        raise ValueError(f"triebuild failed (err={err.value}: {reason})")
    # the native side's own comparison, on the same two numbers
    counts = np.asarray(counts, dtype=np.int64)
    threaded = counts[counts >= threaded_job_leaves] if threads > 1 else counts[:0]
    threaded_jobs, threaded_leaves = len(threaded), int(threaded.sum())
    pipeline_metrics.record_threaded_sweeps(threaded_jobs, threaded_leaves)
    return h, all_keys, threaded_jobs, threaded_leaves


# -- the rebuild pipeline: every commit's path ------------------------------


class _SweepResult:
    """One sweep group's host arrays, extracted from the native handle so
    the handle can be freed inside the producer thread. Slots are the
    group's own 1..max_slot namespace; the consumer rebases them into the
    shared arena."""

    __slots__ = ("job_ids", "keys", "levels", "root_slots",
                 "root_inlines", "meta_rec", "max_slot", "n_levels",
                 "wire_bytes", "hashed_nodes", "threaded_jobs",
                 "threaded_leaves")

    def __init__(self, job_ids, keys, levels, root_slots, root_inlines,
                 meta_rec, max_slot, wire_bytes, threaded_jobs,
                 threaded_leaves):
        self.job_ids = job_ids
        self.keys = keys  # the group's sorted keys, job after job
        self.levels = levels
        self.root_slots = root_slots
        self.root_inlines = root_inlines
        self.meta_rec = meta_rec
        self.max_slot = max_slot
        self.n_levels = len(levels)
        self.wire_bytes = wire_bytes
        self.hashed_nodes = sum(len(lv.row_slot) + len(lv.masks) for lv in levels)
        # the group's jobs that the native call swept on several threads
        self.threaded_jobs = threaded_jobs
        self.threaded_leaves = threaded_leaves


def _sweep_group(lib, jobs, job_ids, collect_branches, start_depth) -> _SweepResult:
    """Producer body: native sweep of one job group (the C++ build releases
    the GIL, so groups sweep concurrently) + full array extraction."""
    from ..metrics import trie_metrics

    h, keys, threaded_jobs, threaded_leaves = _marshal_and_build(
        lib, jobs, collect_branches, start_depth)
    try:
        n_levels = lib.rtb_num_levels(h)
        # one "stage" a group: the levels and the roots out of the handle
        # (the root loop is per job: a thousand and more a group of a
        # storage chunk)
        with trie_metrics.phase("stage"):
            levels = [_Level(lib, h, i) for i in range(n_levels)]
            root_slots = np.zeros((len(jobs),), dtype=np.int32)
            lib.rtb_roots(h, _ptr(root_slots, _i32p))
            root_inlines: list[bytes | None] = [None] * len(jobs)
            for j in range(len(jobs)):
                if root_slots[j] <= 0:
                    ln = lib.rtb_root_inline_len(h, j)
                    buf = np.zeros((ln,), dtype=np.uint8)
                    if ln:
                        lib.rtb_root_inline(h, j, _ptr(buf, _u8p))
                    root_inlines[j] = buf.tobytes()
        meta_rec = None
        if collect_branches:
            nmeta = int(lib.rtb_meta_count(h))
            meta_rec = np.zeros((nmeta, 80), dtype=np.uint8)
            if nmeta:
                lib.rtb_meta_get(h, _ptr(meta_rec, _u8p))
        max_slot = lib.rtb_max_slot(h)
    finally:
        lib.rtb_free(h)
    wire_bytes = sum(lv.flat.nbytes + lv.row_off.nbytes + lv.row_len.nbytes
                     + lv.masks.nbytes + lv.children.nbytes for lv in levels)
    return _SweepResult(job_ids, keys, levels, root_slots, root_inlines,
                        meta_rec, max_slot, wire_bytes, threaded_jobs,
                        threaded_leaves)


class _MergedLevel:
    """One fused dispatch worth of same-depth rows packed across subtrie
    sweeps (slots already rebased into the shared arena)."""

    __slots__ = ("depth", "flat", "row_off", "row_len", "row_slot", "holes",
                 "b_tier", "masks", "bmp_slot", "children")


def _rebase_level(lv: _Level, base: int) -> None:
    """Shift a freshly-swept level's slot references into the arena's slot
    space. In place: each _Level is consumed exactly once."""
    if base == 0:
        return
    if len(lv.row_slot):
        lv.row_slot += base
    if lv.holes is not None:
        lv.holes[2] += base
    if len(lv.bmp_slot):
        lv.bmp_slot += base
    if lv.children.shape[1]:
        lv.children[2] += base


def _pack_window(parts: list[tuple[int, _SweepResult]]) -> list[_MergedLevel]:
    """Cross-subtrie level packing: merge the window's per-sweep levels by
    depth into one fused dispatch per (depth, kind), deepest first. Within
    a sweep, deeper levels must hash before their parents; across sweeps
    there is no ordering constraint, so same-depth rows from different
    subtries share a dispatch — larger batch tiers, fewer dispatches, and
    a bounded compiled-program count on the device backends."""
    by_depth: dict[int, list[_Level]] = {}
    for base, sw in parts:
        for lv in sw.levels:
            _rebase_level(lv, base)
            by_depth.setdefault(int(lv.depth), []).append(lv)
    out = []
    for depth in sorted(by_depth, reverse=True):
        group = by_depth[depth]
        m = _MergedLevel()
        m.depth = depth
        packed = [lv for lv in group if len(lv.row_slot)]
        if len(packed) == 1:
            lv = packed[0]
            m.flat, m.row_off, m.row_len = lv.flat, lv.row_off, lv.row_len
            m.row_slot, m.holes, m.b_tier = lv.row_slot, lv.holes, lv.b_tier
        elif packed:
            m.flat = np.concatenate([lv.flat for lv in packed])
            byte_off = np.cumsum([0] + [lv.flat.nbytes for lv in packed])
            row_cnt = np.cumsum([0] + [len(lv.row_slot) for lv in packed])
            m.row_off = np.concatenate(
                [lv.row_off + np.uint32(byte_off[i]) for i, lv in enumerate(packed)])
            m.row_len = np.concatenate([lv.row_len for lv in packed])
            m.row_slot = np.concatenate([lv.row_slot for lv in packed])
            holes = []
            for i, lv in enumerate(packed):
                if lv.holes is not None:
                    hs = lv.holes
                    hs[0] += np.int32(row_cnt[i])
                    holes.append(hs)
            m.holes = np.concatenate(holes, axis=1) if holes else None
            m.b_tier = max(lv.b_tier for lv in packed)
        else:
            m.flat = np.zeros((0,), dtype=np.uint8)
            m.row_off = m.row_len = np.zeros((0,), dtype=np.uint32)
            m.row_slot = np.zeros((0,), dtype=np.int32)
            m.holes, m.b_tier = None, 1
        bmp = [lv for lv in group if len(lv.bmp_slot)]
        if len(bmp) == 1:
            m.masks, m.bmp_slot, m.children = bmp[0].masks, bmp[0].bmp_slot, bmp[0].children
        elif bmp:
            mask_cnt = np.cumsum([0] + [len(lv.bmp_slot) for lv in bmp])
            m.masks = np.concatenate([lv.masks for lv in bmp])
            m.bmp_slot = np.concatenate([lv.bmp_slot for lv in bmp])
            kids = []
            for i, lv in enumerate(bmp):
                ch = lv.children
                if ch.shape[1]:
                    ch[0] += np.int32(mask_cnt[i])
                    kids.append(ch)
            m.children = (np.concatenate(kids, axis=1) if kids
                          else np.zeros((3, 0), dtype=np.int32))
        else:
            m.masks = np.zeros((0,), dtype=np.uint16)
            m.bmp_slot = np.zeros((0,), dtype=np.int32)
            m.children = np.zeros((3, 0), dtype=np.int32)
        out.append(m)
    return out


# The layout of a commit: constants, not options. No caller ever set one,
# and a value that moves, moves the program shapes every chunk asks for.
# Sweep threads: rtb_build releases the GIL, marshalling does not, so more
# than four only contend for the interpreter; never fewer than two, so one
# group is swept while the consumer packs another. 2 * SWEEP_THREADS sweeps
# are submitted ahead of the consumer (a result parked behind every running
# sweep; no more host arrays alive than that). The same number of threads
# sweeps ONE job inside rtb_build where the job holds what that many groups
# would, SWEEP_THREADS * LEAVES_PER_SWEEP leaves (the children of its first
# branch side by side, laid into the arrays of the one-thread sweep byte for
# byte: native/triebuild.cpp). A job is never cut into groups, so without
# that a chunk of one large trie waited on one thread. A pool thread that
# meets such a job starts SWEEP_THREADS - 1 more while the others sweep:
# at most 2 * SWEEP_THREADS - 1 native threads, none holding the interpreter
SWEEP_THREADS = max(2, min(4, os.cpu_count() or 1))
# consecutive groups a window: same-depth rows of the window's tries share
# a dispatch. A stage chunk of 500,000 leaves is at most 16 groups, so ONE
# window: one `ensure`, one set of merged levels, one program a level
PACK_WINDOW = 16
# a group closes at the job that reaches the bound, and at nothing else: a
# sweep short against a chunk of 500,000 leaves, long against the cost of a
# native call and of the extraction of its levels. What a group costs on the
# host follows its leaves (`_marshal_group`), never its number of tries, so
# thousands of one-slot storage tries are one group like one trie of as
# many slots
LEAVES_PER_SWEEP = 32768


def _group_jobs(jobs, max_leaves: int):
    """Slice the job list into sweep groups: each group is one native
    build (shared levels within the group), closed by the job that brings
    it to ``max_leaves``, so sweeps stay small enough to run side by side."""
    groups = []
    lo = leaves = 0
    for hi, (_, values) in enumerate(jobs, 1):
        leaves += len(values)
        if leaves >= max_leaves:
            groups.append((lo, hi))
            lo, leaves = hi, 0
    if lo < len(jobs):
        groups.append((lo, len(jobs)))
    return groups


class RebuildPipeline:
    """The commit of one chunk: sweep groups, windows, one digest arena.

    The job list is cut into sweep groups (``_group_jobs``: a group closes
    at the job that brings it to ``LEAVES_PER_SWEEP``, however many tries
    it holds) and the groups into windows of ``PACK_WINDOW`` consecutive
    groups before the first sweep starts; a chunk of the stage's 500,000
    leaves is at most 16 groups, so ONE window. A group of several jobs is
    marshalled in one piece (``_marshal_group``), so what it costs on the
    host follows its leaves and not its number of tries. ONE group (one
    large subtrie, any job list under ``LEAVES_PER_SWEEP`` leaves) is swept
    by the calling thread; several by a small thread pool
    (``native/triebuild.cpp``; the ctypes call releases the GIL), side by
    side and ahead of the consumer. A job is never cut, so a group may be
    one trie of millions of leaves: the native call sweeps a job of
    ``SWEEP_THREADS * LEAVES_PER_SWEEP`` leaves or more on ``SWEEP_THREADS``
    threads of its own, the children of its first branch side by side, and
    hands back the arrays of the one-thread sweep byte for byte (the
    group's marshalling and the extraction of its levels stay on the one
    thread). The consumer takes the results in SUBMISSION
    order, however the threads finish, and packs same-depth levels of a
    window's groups into fused dispatches (``_pack_window``) against the
    resident digest arena. A window of one group is that group's own
    arrays, slot base 0, passed through.

    What runs is a function of the job list alone. The arena is grown to
    the power-of-two tier that holds the slots swept so far (the tier the
    engines round to themselves; for one group, ``begin(max_slot)``'s), so
    it rises O(log) times a commit. So the merged levels' row and hole
    tiers, the staged buffer lengths, the arena's tier and the number of
    windows, everything that keys a device program, repeat from run to run
    and do not depend on the chunk that came before.

    What overlaps what depends on the backend. The numpy twin and the
    per-level engines hash a window as it is dispatched, and an engine
    with ``flush_window`` (the whole-subtrie family) executes its staged
    window there: on those, hashing window k overlaps the sweeps of
    window k+1; a job list of one window (under ``PACK_WINDOW`` *
    ``LEAVES_PER_SWEEP`` leaves, whatever its number of tries) is hashed
    there after its last sweep. ``MegaFusedEngine`` (the single-chip
    default) only STAGES what it is fed and starts every level program in
    its ``launch()``: on it the sweeps overlap one another and the packing
    and staging of earlier windows, and the device's levels overlap the
    half of the results and the branch decode that needs no digest
    (``_collect``), whatever the number of groups.

    Fault surface: a supervised backend ("auto") fails over mid-commit to
    the numpy twin via its journal; the pipeline keeps feeding it, which
    is exactly the "drain the queue onto the CPU" semantics; an injected
    ``RETH_TPU_FAULT_PIPELINE_ABORT`` kills the run at a window boundary
    to exercise chunked-rebuild resume.
    """

    def __init__(self, backend, lib=None, injector=None):
        self.backend = backend
        self.lib = lib or load_library()
        self.injector = injector
        self.windows = 0
        self.queue_peak = 0
        self.wire_bytes = 0
        self.wait_s = 0.0
        self.overlapped = False  # the backend had a `launch`: see _collect

    def _sweeps(self, groups, sweep):
        """Each group's ``sweep(lo, hi)``, in the groups' order. One group:
        the caller's thread runs it. Several: a thread pool runs them side
        by side, ``2 * SWEEP_THREADS`` submitted ahead of the consumer, who
        waits for the next one IN ORDER (a ``trie::pipeline:wait`` span,
        ``wait_s``); one that landed early stays parked in ``ahead``."""
        from ..metrics import pipeline_metrics as met

        if len(groups) == 1:
            self.queue_peak = 1
            yield sweep(*groups[0])
            return
        pool = ThreadPoolExecutor(max_workers=SWEEP_THREADS,
                                  thread_name_prefix="trie-sweep")
        try:
            todo = iter(groups)
            ahead: deque = deque()  # sweeps in flight, in submission order
            for _ in groups:
                ahead.extend(
                    pool.submit(sweep, lo, hi) for lo, hi in
                    islice(todo, 2 * SWEEP_THREADS - len(ahead)))
                t0 = time.perf_counter()
                with tracing.span("trie::pipeline", "wait"):
                    sw = ahead.popleft().result()
                self.wait_s += time.perf_counter() - t0
                # the depth gauge: sweeps finished and not yet taken
                # (queue_peak counts the one in hand)
                parked = sum(f.done() for f in ahead)
                self.queue_peak = max(self.queue_peak, parked + 1)
                met.set_queue_depth(parked)
                yield sw
        finally:
            pool.shutdown(wait=True, cancel_futures=True)
            met.set_queue_depth(0)

    def run(self, jobs, collect_branches: bool = False, start_depth: int = 0):
        from ..metrics import gc_metrics, pipeline_metrics, trie_metrics

        if not jobs:
            return []
        t_wall = time.perf_counter()
        phases0, (gc0, gc_full0) = (trie_metrics.phase_seconds(),
                                    gc_metrics.seconds())
        met = pipeline_metrics
        groups = _group_jobs(jobs, LEAVES_PER_SWEEP)
        # a job is never cut, so the largest group is what ONE thread
        # marshals and extracts (its large jobs are swept on several)
        group_leaves = [sum(len(values) for _, values in jobs[lo:hi])
                        for lo, hi in groups]
        leaves, largest = sum(group_leaves), max(group_leaves)
        busy = [0]
        busy_lock = threading.Lock()
        lib, backend = self.lib, self.backend

        def sweep(lo: int, hi: int):
            with busy_lock:
                busy[0] += 1
                met.set_pool_busy(busy[0])
            try:
                return _sweep_group(lib, jobs[lo:hi], range(lo, hi),
                                    collect_branches, start_depth)
            finally:
                with busy_lock:
                    busy[0] -= 1
                    met.set_pool_busy(busy[0])

        swept: list[tuple[int, _SweepResult]] = []  # (slot_base, sweep)
        hwm = 0        # arena slots handed out so far (slot 0 is the dummy)
        ensured = 0    # the slot count the arena was last grown to hold
        drained = 0
        trace_ctx = tracing.current_context()
        sweeps = self._sweeps(groups, sweep)
        try:
            backend.begin(0)
            for _ in range(0, len(groups), PACK_WINDOW):
                window = list(islice(sweeps, PACK_WINDOW))
                with trie_metrics.phase("pack"):
                    parts = []
                    for sw in window:
                        self.wire_bytes += sw.wire_bytes
                        parts.append((hwm, sw))  # group slot s -> arena hwm+s
                        hwm += sw.max_slot
                    swept += parts
                    merged = _pack_window(parts)
                # grow to the power-of-two tier that holds what has been
                # swept (capacity hwm + 1), the tier the engines would round
                # ensure(hwm) to themselves: the arena a commit ends with
                # follows from the slots it holds, not from how they
                # arrived, and it rises O(log) times a commit
                if hwm > ensured:
                    ensured = (1 << hwm.bit_length()) - 1
                    backend.ensure(ensured)
                if self.injector is not None:
                    self.injector.on_pipeline_window()
                t1 = time.perf_counter()
                t1_wall = time.time()
                with trie_metrics.phase("stage"):
                    for m in merged:
                        backend.dispatch_packed(m.flat, m.row_off, m.row_len,
                                                m.row_slot, m.holes, m.b_tier)
                        backend.dispatch_branch(m.masks, m.bmp_slot,
                                                m.children)
                # k-level window boundary: a whole-subtrie engine STAGES the
                # per-depth calls above and executes the window here as
                # O(levels/k) fused dispatches, so device hashing of this
                # window overlaps the next window's sweeps
                flush_window = getattr(backend, "flush_window", None)
                if flush_window is not None:
                    flush_window()
                dt = time.perf_counter() - t1
                tracing.record_span(
                    "trie::pipeline", "rebuild.window", t1_wall, dt,
                    ctx=trace_ctx,
                    fields={"levels": len(merged), "subtries": len(window)})
                if getattr(backend, "failed_over", False):
                    drained += 1
                self.windows += 1
            return self._collect(swept, len(jobs), collect_branches,
                                 start_depth)
        finally:
            sweeps.close()  # an aborted run: the pool is shut down here
            wall_s = time.perf_counter() - t_wall
            phases = {k: v - phases0[k]
                      for k, v in trie_metrics.phase_seconds().items()}
            gc1, gc_full1 = gc_metrics.seconds()
            gc_s, gc_full_s = gc1 - gc0, gc_full1 - gc_full0
            met.record_run(
                jobs=len(jobs), groups=len(groups), leaves=leaves,
                largest_group_leaves=largest, windows=self.windows,
                queue_peak=self.queue_peak, drained_windows=drained,
                backend=getattr(backend, "effective_kind", None),
                wall_s=wall_s, wait=self.wait_s, phases=phases, gc_s=gc_s,
                gc_full_s=gc_full_s)
            tracing.record_span(
                "trie::pipeline", "rebuild", time.time() - wall_s, wall_s,
                ctx=trace_ctx,
                fields={"jobs": len(jobs), "windows": self.windows,
                        "leaves": leaves, "largest_group_leaves": largest,
                        "threaded_jobs": sum(
                            sw.threaded_jobs for _, sw in swept),
                        "threaded_leaves": sum(
                            sw.threaded_leaves for _, sw in swept),
                        "wait": round(self.wait_s, 4),
                        "predecode_s": round(phases["predecode"], 4),
                        "overlapped": self.overlapped,
                        "gc_s": round(gc_s, 4),
                        "gc_full_s": round(gc_full_s, 4)})

    def _collect(self, swept, n_jobs, collect_branches, start_depth):
        """One result a job. Roots alone: the roots' slots fetched, then the
        results built. With branch nodes: the backend's ``launch`` (an
        engine that stages the commit starts its level programs), then the
        half that needs no digest (``predecode``: the results, inline roots
        and each group's first half of the decode) while the device hashes,
        then ``finish()``'s wait and fetch, then the roots (``collect``) and
        the child hashes (``decode``) laid in. A backend without ``launch``
        runs the same halves in the same order; its ``finish`` starts what
        it has not. Results are handed out only once both halves ran."""
        from ..metrics import trie_metrics

        backend = self.backend
        rows = np.concatenate([sw.root_slots[sw.root_slots > 0] + base
                               for base, sw in swept])
        if not collect_branches:
            roots = backend.fetch_slots(rows)
            with trie_metrics.phase("collect"):
                results, hashed = _job_results(swept, n_jobs)
                _lay_in_roots(hashed, roots)
            return results
        launch = getattr(backend, "launch", None)
        self.overlapped = launch is not None
        if launch is not None:
            launch()
        with trie_metrics.phase("predecode"), _collector_held_off():
            results, hashed = _job_results(swept, n_jobs)
            pending = [
                _collect_meta_records(sw.meta_rec, sw.keys,
                                      [results[j] for j in sw.job_ids],
                                      start_depth, slot_base=base)
                for base, sw in swept if len(sw.meta_rec)]
        digests = backend.finish()
        with trie_metrics.phase("collect"):
            _lay_in_roots(hashed, digests[rows])
        with trie_metrics.phase("decode"), _collector_held_off():
            for branches in pending:
                branches.lay_in(digests)
        return results


@contextmanager
def _collector_held_off():
    """The collector off over a block that makes objects by the million:
    a decode's nodes, tuples and paths hold no cycle, and its passes over
    them, were it left on, would add a third to the block's time."""
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was_enabled:
            gc.enable()


def _job_results(swept, n_jobs):
    """One result a job, in job order, the roots of inline tries set (their
    ``keccak256``, or the empty root); returns the results and, in the
    order of the swept groups' hashed root slots, the results whose root
    is a digest of the arena still to come."""
    results: list = [None] * n_jobs
    hashed: list = []
    total_hashed = 0
    for _, sw in swept:
        total_hashed += sw.hashed_nodes
        levels = sw.n_levels
        for j, slot, inline in zip(sw.job_ids, sw.root_slots.tolist(),
                                   sw.root_inlines):
            if slot > 0:
                results[j] = res = TrieBuildResult(root=b"", levels=levels)
                hashed.append(res)
            else:
                results[j] = TrieBuildResult(
                    root=keccak256(inline) if inline else EMPTY_ROOT_HASH,
                    levels=levels)
    results[-1].hashed_nodes = total_hashed
    return results, hashed


def _lay_in_roots(hashed, roots: np.ndarray) -> None:
    """The fetched root digests, row after row, into their results."""
    deque(map(setattr, hashed, repeat("root"),
              roots.view("V32").ravel().tolist()), 0)


class TurboCommitter:
    """Full-rebuild state committer over 32-byte hashed keys.

    ``backend``: "device" (fused HBM-resident engine, optionally SPMD over
    ``mesh``), "numpy" (CPU twin — the measured baseline), or "auto"
    (device under the ``ops/supervisor.py`` watchdog+breaker, with
    journaled mid-commit failover onto the numpy twin).

    ``hash_service``: an ``ops/hash_service.py`` HashService — the
    device-touching backends ("device"/"auto") then hold the service's
    LEASE for each commit (begin → terminal fetch). On a single-backend
    service that lease is EXCLUSIVE (coalesced lanes pause; aged live-tip
    requests bypass to the CPU twin); on a MESHED service it is a
    SUB-MESH lease — the rebuild claims k of n devices and streams its
    windows through a ``FusedMeshEngine`` sharded over them while the
    live/payload/proof lanes keep dispatching on the rest. The numpy
    backend never touches the device and takes no lease.

    ``mesh``: a ``jax.sharding.Mesh`` or ``parallel/mesh.py`` HashMesh —
    fused level dispatches then SPMD-shard over it; inherited from the
    hash service's mesh when not given explicitly."""

    def __init__(self, backend: str = "device", min_tier: int = 1024, mesh=None,
                 supervisor=None, hash_service=None,
                 subtrie_levels: int | None = None):
        self.backend_kind = backend
        self.min_tier = min_tier
        if mesh is None and hash_service is not None:
            mesh = getattr(hash_service, "mesh", None)
        self.mesh = mesh
        self.supervisor = supervisor
        self.hash_service = hash_service
        # whole-subtrie fused kernels (--subtrie-levels / [node]
        # subtrie_levels / RETH_TPU_SUBTRIE_LEVELS): k > 1 collapses the
        # per-depth dispatch loop into ONE device dispatch per k levels;
        # 0/1 keeps the per-level engines
        if subtrie_levels is None:
            subtrie_levels = int(
                os.environ.get("RETH_TPU_SUBTRIE_LEVELS", "0") or 0)
        self.subtrie_levels = max(0, int(subtrie_levels))
        self.arena = DigestArena()  # resident across this committer's commits
        self._lib = load_library()

    def _device_engine(self):
        from ..ops.device import require_device
        from ..ops.fused_commit import (
            FusedMeshEngine,
            MegaFusedEngine,
            SubtrieFusedEngine,
            SubtrieMeshEngine,
        )

        require_device()  # the chip, or an error — never a silent CPU run
        k = self.subtrie_levels
        warmup = getattr(self.supervisor, "warmup", None)
        svc = self.hash_service
        sub = None
        if svc is not None and getattr(svc, "rebuild_mesh", None) is not None:
            sub = svc.rebuild_mesh()
        mesh = sub if sub is not None else self.mesh
        if mesh is not None:
            # sub-mesh lease held (sub): this commit's shardings form over
            # the k devices the lease carved out; live lanes keep the rest
            if k > 1:
                return SubtrieMeshEngine(mesh, min_tier=self.min_tier, k=k,
                                         warmup=warmup)
            return FusedMeshEngine(mesh, min_tier=self.min_tier)
        if k > 1:
            # whole-subtrie kernels: staging like the mega engine, but the
            # depth loop runs INSIDE the jit — one dispatch per k levels
            return SubtrieFusedEngine(min_tier=self.min_tier, k=k,
                                      warmup=warmup)
        # single-chip: whole-commit staging — one H2D, one program PER
        # LEVEL, one D2H
        return MegaFusedEngine(min_tier=self.min_tier)

    def _make_backend(self):
        if self.backend_kind == "numpy":
            return _NumpyBackend(arena=self.arena)

        def build():
            if self.backend_kind == "auto":
                from ..ops.supervisor import (DeviceSupervisor,
                                              SupervisedBackend)

                sup = self.supervisor or DeviceSupervisor.shared()
                return SupervisedBackend(sup, self._device_engine,
                                         arena=self.arena)
            return self._device_engine()

        if self.hash_service is not None:
            # shared-service discipline: this commit owns its devices via
            # the (sub-mesh) lease instead of grabbing them unilaterally.
            # Construction is DEFERRED so the engine's shardings form over
            # the sub-mesh the lease carves out at begin().
            return self.hash_service.lease_backend(factory=build)
        return build()

    def commit_hashed_pipelined(
        self,
        jobs: list[tuple[np.ndarray, list[bytes]]],
        collect_branches: bool = False,
        start_depth: int = 0,
    ) -> list[TrieBuildResult]:
        """Commit many independent secure tries: THE commit, whatever the
        number of jobs (:class:`RebuildPipeline`).

        ``jobs``: (keys (n, 32) uint8, need not be sorted; values aligned
        RLP-encoded bytes) per trie. ``start_depth`` builds each job as the
        SUBTRIE below that nibble depth (keys must share the prefix); the
        root is then the embedded subtree node's hash: the chunked-rebuild
        boundary stitch uses this. Returns one TrieBuildResult per job
        (root + optional BranchNode TrieUpdates, paths subtrie-relative).

        Groups of jobs are swept side by side on a thread pool (one group:
        by the caller; a job of ``SWEEP_THREADS * LEAVES_PER_SWEEP`` leaves
        or more: on threads inside the native call, to the same arrays),
        same-depth levels packed across them
        into fused dispatches, and hashed into the resident digest arena.
        The windows, the arena's tier and every program shape follow from
        the job list alone, never from which sweep thread finished first.
        Hashing overlaps the sweeps on the numpy twin, the per-level engines
        and the whole-subtrie engines; the single-chip default,
        ``MegaFusedEngine``, stages the windows and starts the device in
        ``launch()``, and the half of the decode that needs no digest runs
        while it hashes. A sweep's rejection is a ``ValueError``, the condition
        on which the MerkleStage falls back to the general committer."""
        if not jobs:
            return []
        from ..metrics import trie_metrics
        from ..ops.supervisor import FaultInjector

        t_start = time.time()
        backend = self._make_backend()
        injector = getattr(self.supervisor, "injector", None)
        if injector is None:
            injector = FaultInjector.from_env()
        pipe = RebuildPipeline(backend, self._lib, injector)
        try:
            results = pipe.run(jobs, collect_branches, start_depth)
        finally:
            release = getattr(backend, "release", None)
            if release is not None:
                release()  # idempotent: aborted commits must drop the lease
        # TrieTracker-style commit stats (reference trie metrics/tracker):
        # a supervised commit that failed over reports the backend that
        # actually produced the digests, not the one that was asked for
        effective = getattr(backend, "effective_kind", self.backend_kind)
        trie_metrics.record_commit(
            backend=effective,
            nodes=results[-1].hashed_nodes,
            levels=max(r.levels for r in results),
            leaves=sum(len(j[1]) for j in jobs),
            wire_bytes=pipe.wire_bytes,
            seconds=time.time() - t_start)
        return results

    def commit_hashed_many(self, jobs, collect_branches=False, start_depth=0):
        """The older name of :meth:`commit_hashed_pipelined`."""
        return self.commit_hashed_pipelined(jobs, collect_branches, start_depth)


# one native BranchMeta record as rtb_meta_get packs it
# (native/triebuild.cpp), 80 bytes
_META_REC = np.dtype([
    ("job", "<u4"), ("rep_key", "<u4"), ("depth", "<u2"),
    ("state_mask", "<u2"), ("tree_mask", "<u2"), ("hash_mask", "<u2"),
    ("child_slot", "<i4", (16,)),
])


class _PendingBranches:
    """One sweep group's branch records with the half of their decode that
    needs no digest done: the records' runs of one job, their paths, their
    nodes made without hashes, and the arena rows of their child hashes.
    ``lay_in`` is the other half."""

    __slots__ = ("results", "runs", "paths", "rows", "n_hashed",
                 "nodes_later")

    def __init__(self, results, runs=(), paths=(), rows=None, n_hashed=(),
                 nodes_later=None):
        self.results, self.runs, self.paths = results, runs, paths
        self.rows, self.n_hashed = rows, n_hashed
        self.nodes_later = nodes_later

    def lay_in(self, digests):
        """The decode's second half, once ``digests`` holds the arena: ONE
        gather of the child digests, a tuple of them a node, the nodes
        finished, and each run of records put into its job's
        ``branch_nodes`` in record order; C-level maps, so no Python frame
        a record (a loop step a run). Returns the results."""
        from ..metrics import trie_metrics

        if self.nodes_later is None:
            return self.results
        trie_metrics.record_decode(len(self.paths))
        child_hashes = iter(digests[self.rows].view("V32").ravel().tolist())
        nodes = iter(self.nodes_later(
            map(tuple, map(islice, repeat(child_hashes), self.n_hashed))))
        paths, results = iter(self.paths), self.results
        for j, k in self.runs:
            results[j].branch_nodes.update(zip(islice(paths, k),
                                               islice(nodes, k)))
        return results


def _collect_meta_records(meta_rec, keys, results, start_depth=0,
                          slot_base=0) -> _PendingBranches:
    """Decode native BranchMeta records into per-job TrieUpdates, split at
    the digest boundary: this call is the half that needs no digest, every
    record of the group at once (the fields, the paths as ``bytes``, the
    masks, each node made without its hashes, the arena rows of the child
    hashes); the ``lay_in(digests)`` of what it returns is the other.
    ``keys``: the sweep group's sorted keys, job after job in one array.
    ``results``: the group's results, indexed by the records' job number.
    ``slot_base`` rebases the records' group-local digest slots into the
    pipeline's shared arena slot space."""
    from ..metrics import trie_metrics

    rec = np.ascontiguousarray(meta_rec).view(_META_REC).ravel()
    n = len(rec)
    trie_metrics.record_predecode(n)
    if not n:
        return _PendingBranches(results)
    # paths: the leading nibbles of every record's representative key, row
    # after row in one blob. BranchMeta depths are SUBTRIE-relative; the
    # stored path skips the start_depth prefix nibbles of the full key
    depth = rec["depth"].astype(np.intp)
    n_bytes = (start_depth + int(depth.max()) + 1) // 2
    heads = keys[:, :n_bytes][rec["rep_key"]]  # rep_key: a row of the group
    nibs = np.empty((n, 2 * n_bytes), dtype=np.uint8)
    nibs[:, 0::2] = heads >> 4
    nibs[:, 1::2] = heads & 0xF
    path_lo = np.arange(n, dtype=np.intp) * (2 * n_bytes) + start_depth
    paths = list(map(nibs.tobytes().__getitem__,
                     map(slice, path_lo.tolist(), (path_lo + depth).tolist())))
    # child hashes: the arena rows of the hashed children of all records,
    # in record order and ascending nibble order within a record
    hmask = rec["hash_mask"]
    hashed = ((hmask[:, None] >> np.arange(16, dtype=np.uint16)) & 1).astype(bool)
    # runs of consecutive records of one job (the sweep emits a job's
    # records together): one dict update a run
    job = rec["job"]
    starts = np.flatnonzero(np.r_[True, job[1:] != job[:-1]])
    runs = list(zip(job[starts].tolist(), np.diff(np.r_[starts, n]).tolist()))
    return _PendingBranches(
        results, runs, paths,
        rec["child_slot"][hashed] + slot_base, hashed.sum(axis=1).tolist(),
        branch_nodes_hashed_later(rec["state_mask"].tolist(),
                                  rec["tree_mask"].tolist(), hmask.tolist()))
