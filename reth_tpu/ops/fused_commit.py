"""Fused multi-level trie commit — child digests stay in HBM between levels.

The round-1 committer paid one host↔device round trip per trie depth level:
host RLP-encodes a level (needs child digests), uploads, hashes, downloads
digests, repeats. This module removes every mid-commit D2H:

- The host builds per-level **RLP byte templates**: complete node RLP with
  zero-filled 32-byte *holes* where a hashed child's digest goes. Crucially
  this needs NO digest values — whether a child is inlined (<32 B RLP) or
  hashed (0xa0 + 32-byte ref) depends only on lengths, so the template and
  every hole offset are host-computable bottom-up without syncing.
- The device keeps a resident **digest buffer** (S, 32) u8 in HBM. Each
  level dispatch gathers child digests from the buffer, scatter-splices
  them into the level's templates, runs the masked keccak absorb, and
  scatters the level's digests back into the buffer. Dispatches chain
  through the donated buffer, so XLA executes them in order and the host
  never blocks — template building for level d-1 overlaps device hashing
  of level d.
- ONE D2H at the end (the digest buffer) yields every node hash.

Shape discipline (compile-count bounded): batch tiers grow x4 from ``min_tier``; block tiers are {2, 4, 8, ...}; the
hole tier is fixed at 4x the batch tier (levels with more holes are split
across dispatches). Program count for a bench-style workload with a single
forced batch tier is <=3.

Reference analogue: the rayon subtrie hash loop
(crates/trie/sparse/src/arena/mod.rs:2500-2548) and the per-level batching
seam this replaces (crates/stages/stages/src/stages/hashing_account.rs:29-32).
"""

from __future__ import annotations

import os
import threading
import time as _time
from functools import lru_cache, partial

import numpy as np

import jax
import jax.numpy as jnp

from ..primitives.keccak import RATE
from ..trie.node import HASH_REF_HOLE  # noqa: F401  (re-export; defined jax-free)
from .keccak_jax import masked_absorb_words


def _timed_call(kind: str, shape, fn, *args):
    """Run one jitted dispatch inside an ``ops::dispatch:<kind>`` span (on
    the device trace's clock: the call beside the device operations it
    waited behind) and report (shape, wall) to the compile tracker: the
    first call of a shape builds its program (jit compiles, or loads it from
    the persistent cache, synchronously, then enqueues), so builds split out
    from the steady-state enqueue cost."""
    from .. import tracing
    from ..metrics import compile_tracker

    fields = {"shape": str(shape)} if tracing.trace_enabled() else {}
    t0 = _time.perf_counter()
    with tracing.span("ops::dispatch", kind, **fields):
        out = fn(*args)
    compile_tracker.record(kind, shape, _time.perf_counter() - t0)
    return out


def _h2d(arr: np.ndarray) -> np.ndarray:
    """Count a host array on its way to the device (every ``_device_put``
    / ``_put_batch`` passes through here)."""
    from ..metrics import fused_metrics

    fused_metrics.record_h2d(arr.nbytes)
    return arr


def _to_host(dev) -> np.ndarray:
    """A commit's terminal D2H, split at the one sync the copy makes
    anyway: the wait for the device to finish, then the copy itself."""
    from ..metrics import fused_metrics, trie_metrics

    with trie_metrics.phase("device_wait"):
        dev.block_until_ready()
    with trie_metrics.phase("fetch"):
        out = np.asarray(dev)
    fused_metrics.record_d2h(out.nbytes)
    return out


def _bytes_to_words(t):
    """(N, L) u8 templates -> (N, L//4) u32 little-endian lane words."""
    w = t.reshape(t.shape[0], -1, 4).astype(jnp.uint32)
    return w[..., 0] | (w[..., 1] << 8) | (w[..., 2] << 16) | (w[..., 3] << 24)


def _digests_to_bytes(d):
    """(N, 8) u32 digests -> (N, 32) u8 (little-endian per word)."""
    b = jnp.stack([(d >> (8 * k)) & 0xFF for k in range(4)], axis=-1)
    return b.astype(jnp.uint8).reshape(d.shape[0], 32)


def _plain_level(templates, counts, slots, digest_buf, *, b_tier: int):
    d = masked_absorb_words(_bytes_to_words(templates), b_tier, counts)
    return digest_buf.at[slots].set(_digests_to_bytes(d))


def _splice_level(
    templates, counts, hole_node, hole_byte, hole_src, slots, digest_buf, *, b_tier: int
):
    L = b_tier * RATE
    dig = digest_buf[hole_src]  # (H, 32) u8 gather from resident buffer
    flat = templates.reshape(-1)
    idx = (hole_node * L + hole_byte)[:, None] + jnp.arange(32, dtype=jnp.int32)[None, :]
    flat = flat.at[idx.reshape(-1)].set(dig.reshape(-1))
    d = masked_absorb_words(_bytes_to_words(flat.reshape(templates.shape)), b_tier, counts)
    return digest_buf.at[slots].set(_digests_to_bytes(d))


def _packed_level(
    flat, row_off, row_len, counts, hole_node, hole_byte, hole_src, slots,
    digest_buf, *, b_tier: int
):
    """Unpack tightly-concatenated RLP rows by gather, apply keccak padding,
    splice child digests, hash, scatter digests. The packed form is what
    crosses the host->device wire — no per-row padding is transferred."""
    L = b_tier * RATE
    n = row_off.shape[0]
    col = jnp.arange(L, dtype=jnp.uint32)[None, :]
    idx = jnp.minimum(row_off[:, None] + col, flat.shape[0] - 1)
    rows = jnp.where(col < row_len[:, None], flat[idx], 0)
    # multi-rate padding: 0x01 at the message end, 0x80 at the block end
    rows = rows ^ jnp.where(col == row_len[:, None], 0x01, 0).astype(jnp.uint8)
    last = (counts.astype(jnp.uint32) * RATE - 1)[:, None]
    rows = rows ^ jnp.where(col == last, 0x80, 0).astype(jnp.uint8)
    if hole_node is not None:
        dig = digest_buf[hole_src]
        fr = rows.reshape(-1)
        sidx = (hole_node * L + hole_byte)[:, None] + jnp.arange(32, dtype=jnp.int32)[None, :]
        rows = fr.at[sidx.reshape(-1)].set(dig.reshape(-1)).reshape(n, L)
    d = masked_absorb_words(_bytes_to_words(rows), b_tier, counts)
    return digest_buf.at[slots].set(_digests_to_bytes(d))


def _branch_level(masks, slots, ch_row, ch_nib, ch_src, digest_buf, *, b_tier: int):
    """Construct whole branch-node RLPs ON DEVICE from 2-byte state masks.

    A secure-trie branch whose 16 children are all hashed has a fully
    determined byte layout: list header (f8 <len> for <=7 children, f9
    <len:2> above), then per nibble (a0 + 32-byte ref) or 80, then 80
    (empty value). Only the mask and the child (row, nibble, digest-slot)
    triples cross the wire — ~250x less H2D than the 532-byte template.

    No index addresses a single byte of the rows (on the TPU a per-byte
    scatter costs ~5 ns an index and drags in a sort of all of them;
    PERF.md, PR 32): the triples become a dense ``(n, 16)`` table of digest
    slots by one scatter of WORDS (junk triples land on a padding row,
    whose mask is 0; entries of absent nibbles are never read), the digests
    reach the rows as 32-byte rows of one gather, and `_branch_rows` lays
    them out by static slices and selects."""
    n = masks.shape[0]
    table = jnp.zeros((n * 16,), jnp.int32).at[ch_row * 16 + ch_nib].set(
        ch_src).reshape(n, 16)
    rows, total = _branch_rows(masks, digest_buf[table], b_tier * RATE)
    # keccak padding from the computed total length
    col = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :]
    counts = total // RATE + 1
    rows = rows ^ jnp.where(col == total[:, None], 0x01, 0).astype(jnp.uint8)
    rows = rows ^ jnp.where(col == (counts * RATE - 1)[:, None], 0x80, 0).astype(jnp.uint8)
    d = masked_absorb_words(_bytes_to_words(rows), b_tier, counts)
    return digest_buf.at[slots].set(_digests_to_bytes(d))


def _branch_rows(masks, dense, L: int):
    """(n, L) u8 branch-node RLPs, zero past each row's length, and the
    (n,) i32 lengths, from the (n,) i32 state masks and the (n, 16, 32) u8
    digests of the sixteen child slots (read only where the mask's bit is
    set).

    Built from the tail, as `_level_rows`' barrel shifter: ``rest`` starts
    as the empty value's 0x80 and each nibble, 15 down to 0, prepends either
    0xa0 + its digest or 0x80 by one select between two static
    concatenations, the width growing by the 33 bytes a later stage can
    still need; one more select puts the list header (f8 <len>, or f9
    <len:2> above 255) in front."""
    n = masks.shape[0]
    byte = lambda v: jnp.full((n, 1), v, jnp.uint8)  # noqa: E731
    gap = jnp.zeros((n, 32), jnp.uint8)
    rest = byte(0x80)
    for k in reversed(range(16)):
        rest = jnp.where(
            ((masks >> k) & 1)[:, None] == 1,
            jnp.concatenate([byte(0xA0), dense[:, k], rest], axis=1),
            jnp.concatenate([byte(0x80), rest, gap], axis=1))
    payload = 17 + 32 * jax.lax.population_count(masks)
    long = payload > 0xFF
    low = (payload & 0xFF).astype(jnp.uint8)[:, None]
    rows = jnp.where(
        long[:, None],
        jnp.concatenate(
            [byte(0xF9), (payload >> 8).astype(jnp.uint8)[:, None], low, rest],
            axis=1),
        jnp.concatenate([byte(0xF8), low, rest, byte(0)], axis=1))
    # a subtrie chunk of packed steps alone still traces its branch step, at
    # the chunk's narrower L; no branch row runs there
    rows = jnp.pad(rows, ((0, 0), (0, max(L - rows.shape[1], 0))))[:, :L]
    return rows, payload + jnp.where(long, 3, 2)


def _branch_index_elems(n_pow: int, ch_pow: int) -> int:
    """Elements of every index array a branch level program's gathers and
    scatters take: the table's word scatter (one a triple), the digest-row
    gather (sixteen a row) and the digest write (one a row)."""
    return ch_pow + 16 * n_pow + n_pow


@lru_cache(maxsize=None)
def _jitted(kind: str, b_tier: int, sharding_key=None):
    """One compiled program per (kind, block tier); shapes add tiers via the
    caller's padding. ``sharding_key`` is an opaque hashable handle the mesh
    layer uses to get distinctly-sharded variants (see ``FusedMeshEngine``)."""
    fn = {
        "plain": _plain_level,
        "splice": _splice_level,
        "packed": _packed_level,
        "branch": _branch_level,
    }[kind]
    donate = {"plain": 3, "splice": 6, "packed": 8, "branch": 5}[kind]
    level = partial(fn, b_tier=b_tier)
    level.__name__ = f"level_{kind}"  # the module's name in a device trace
    return jax.jit(level, donate_argnums=donate)


def _tier(n: int, min_tier: int, growth: int = 4) -> int:
    t = min_tier
    while t < n:
        t *= growth
    return t


def _pow2(n: int, floor: int = 2) -> int:
    t = floor
    while t < n:
        t *= 2
    return t


class _Bucket:
    """One pending device dispatch: rows of equal-ish shape within a level."""

    __slots__ = ("templates", "counts", "slots", "holes", "nb_max")

    def __init__(self):
        self.templates: list[bytes] = []
        self.counts: list[int] = []
        self.slots: list[int] = []
        self.holes: list[tuple[int, int, int]] = []  # (row, byte_off, src_slot)
        self.nb_max = 1

    def add(self, template: bytes, nb: int, slot: int, holes) -> None:
        row = len(self.templates)
        self.templates.append(template)
        self.counts.append(nb)
        self.slots.append(slot)
        self.nb_max = max(self.nb_max, nb)
        for byte_off, src_slot in holes:
            self.holes.append((row, byte_off, src_slot))


class FusedLevelEngine:
    """Device-resident digest buffer + per-level dispatch.

    Usage: ``begin(max_slots)`` → repeated ``dispatch_level(bucket)`` deepest
    level first → ``finish()`` returns the (S, 32) numpy digest array (the
    single D2H of the whole commit). Slot 0 is a reserved dummy target for
    padding rows.
    """

    effective_kind = "device"

    # hole budget per dispatch = _HOLE_FACTOR * batch tier; levels with more
    # holes (branch-heavy near-root levels) are split across dispatches
    _HOLE_FACTOR = 4
    # row cap per dispatch: keeps flat byte indices (row * L + off) well
    # under 2^31 — scatter indices are int32 on the TPU, and a silent wrap
    # would drop splices and corrupt roots (2^21 rows * 544 B = 2^30.09)
    _MAX_ROWS = 1 << 21
    # declared menu ceilings (ops/warmup.py, mirroring KeccakDevice): levels
    # with more rows split across dispatches so one giant level can never
    # mint a batch tier above the menu; block tiers past the ceiling raise
    # (an MPT node tops out ~533 B = 4 rate blocks — 64 is generous slack,
    # and there is no per-row CPU fallback mid-fused-commit to hide behind)
    MAX_BATCH_ROWS = 1 << 16
    MAX_BLOCK_TIER = 64

    def __init__(self, min_tier: int = 1024):
        self.min_tier = min_tier
        self._buf = None
        self._n_slots = 0
        self.dispatches = 0  # device program calls since begin()
        # ladder caps hoisted out of the dispatch path (PR 10 follow-up):
        # the ladder walk used to rerun on EVERY dispatch_level/_split call;
        # it is now computed once per (ceilings, min_tier, mesh) key — the
        # key guard keeps tests that mutate MAX_BATCH_ROWS post-init exact
        self._caps_key: tuple | None = None
        self._caps()

    def _caps(self) -> tuple[int, list[int]]:
        """(row cap, batch-tier ladder) under the declared ceilings,
        memoized by the inputs that define them. The row cap is the
        LARGEST tier on the batch ladder (x4 growth from the
        device-count-rounded floor) that still fits under the ceilings.
        Splitting at a raw ceiling minted a tier ABOVE it whenever the
        mesh-rounded floor put the ladder off the pow2 grid (e.g. 6
        devices: 1026 → 4104 → 16416 → 65664 > MAX_BATCH_ROWS) — a chunk
        split must never create a shape the warm-up menu doesn't declare
        or the mesh can't divide."""
        key = (self._MAX_ROWS, self.MAX_BATCH_ROWS, self.min_tier,
               self._batch_multiple())
        if self._caps_key != key:
            ceiling = min(self._MAX_ROWS, self.MAX_BATCH_ROWS)
            t = max(self.min_tier, key[3])
            ladder = [t]
            while t * 4 <= ceiling:
                t *= 4
                ladder.append(t)
            self._caps_key = key
            self._caps_value = (ladder[-1], ladder)
        return self._caps_value

    def _row_cap(self) -> int:
        return self._caps()[0]

    def _hole_budget(self, n: int) -> int:
        """Hole budget for an ``n``-row level: _HOLE_FACTOR x the smallest
        ladder tier holding ``n`` — looked up on the hoisted ladder
        instead of re-walking it per dispatch/split call."""
        cap, ladder = self._caps()
        for t in ladder:
            if n <= t:
                return self._HOLE_FACTOR * t
        return self._HOLE_FACTOR * cap  # over the cap: callers split by rows

    def _check_batch_tier(self, n_tier: int) -> int:
        """Invariant guard on every minted batch tier: divisible by the
        mesh device count AND inside the declared menu ceiling. A
        violation here would silently shard unevenly or compile an
        off-menu program mid-commit — fail loudly instead."""
        mult = self._batch_multiple()
        # the floor tier itself is always admissible (a min_tier configured
        # above the ceiling has nothing smaller to fall back to)
        ceiling = max(min(self._MAX_ROWS, self.MAX_BATCH_ROWS),
                      max(self.min_tier, mult))
        assert n_tier % mult == 0, (
            f"batch tier {n_tier} not divisible by the {mult}-device mesh")
        assert n_tier <= ceiling, (
            f"batch tier {n_tier} exceeds the declared ceiling {ceiling}")
        return n_tier

    def _check_block_tier(self, b_tier: int) -> int:
        if b_tier > self.MAX_BLOCK_TIER:
            raise ValueError(
                f"node of {b_tier} rate blocks exceeds the declared "
                f"block-tier ceiling {self.MAX_BLOCK_TIER} "
                f"(ops/warmup.py shape menu)")
        return b_tier

    # -- lifecycle ---------------------------------------------------------

    def begin(self, max_slots: int) -> None:
        s_tier = _pow2(max_slots + 1, floor=max(self.min_tier, 2))
        self._buf = self._device_put(np.zeros((s_tier, 32), dtype=np.uint8))
        self._n_slots = 1  # slot 0 = dummy
        self.dispatches = 0

    def _count_dispatch(self, levels: int = 1) -> None:
        """One device program actually ran, carrying ``levels`` staged
        levels — the number the whole-subtrie kernel family exists to
        shrink (fused_* metrics + the bench's dispatches/block)."""
        from ..metrics import fused_metrics

        self.dispatches += 1
        fused_metrics.record_dispatch(levels)

    def alloc_slot(self) -> int:
        slot = self._n_slots
        self._n_slots += 1
        return slot

    def ensure(self, max_slots: int) -> None:
        """Grow the resident digest buffer to ``max_slots`` slots,
        preserving written digests (the pipelined rebuild only learns a
        window's slot high-water mark when its sweep lands). Pow2 tiers
        keep the copy-program count logarithmic."""
        need = max_slots + 1
        cur = 0 if self._buf is None else self._buf.shape[0]
        if need <= cur:
            return
        from ..metrics import fused_metrics

        fused_metrics.record_arena_grow()
        new_tier = _pow2(need, floor=max(self.min_tier, 2, cur))
        grown = self._device_put(np.zeros((new_tier, 32), dtype=np.uint8))
        if cur:
            grown = grown.at[:cur].set(self._buf)
        self._buf = grown

    def finish(self) -> np.ndarray:
        buf, self._buf = self._buf, None
        return _to_host(buf)

    def _take_slots(self, slots: np.ndarray) -> np.ndarray:
        ids = np.zeros((_pow2(max(len(slots), 1), floor=8),), dtype=np.int32)
        ids[: len(slots)] = slots
        out = _to_host(jnp.take(self._buf, self._device_put(ids), axis=0))
        return out[: len(slots)]

    def fetch_slots(self, slots: np.ndarray) -> np.ndarray:
        """Small D2H: gather specific digest slots (e.g. per-job roots)
        without pulling the whole buffer; ends the commit."""
        out = self._take_slots(slots)
        self._buf = None
        return out

    # -- mesh seam (overridden by FusedMeshEngine) -------------------------

    def _device_put(self, arr: np.ndarray):
        return jnp.asarray(_h2d(arr))

    def _put_batch(self, arr: np.ndarray):
        return jnp.asarray(_h2d(arr))

    def _sharding_key(self):
        return None

    def _batch_multiple(self) -> int:
        return 1

    # -- dispatch ----------------------------------------------------------

    def dispatch_level(self, bucket: _Bucket) -> None:
        """Queue one level bucket on the device (async, no sync)."""
        n = len(bucket.templates)
        if n == 0:
            return
        b_tier = self._check_block_tier(_pow2(bucket.nb_max, floor=2))
        hole_budget = self._hole_budget(n + 1)
        over_holed = bucket.holes and len(bucket.holes) > hole_budget
        if over_holed or n + 1 > self._row_cap():
            for part in self._split(bucket, hole_budget):
                self._dispatch_one(part, b_tier)
            return
        self._dispatch_one(bucket, b_tier)

    def _split(self, bucket: _Bucket, hole_budget: int):
        """Split an oversized bucket by rows; within-level order is free."""
        holes_by_row: dict[int, list[tuple[int, int]]] = {}
        for row, off, src in bucket.holes:
            holes_by_row.setdefault(row, []).append((off, src))
        part = _Bucket()
        for row in range(len(bucket.templates)):
            row_holes = holes_by_row.get(row, [])
            if part.templates and (
                len(part.holes) + len(row_holes) > hole_budget
                or len(part.templates) + 2 > self._row_cap()
            ):
                yield part
                part = _Bucket()
            part.add(bucket.templates[row], bucket.counts[row], bucket.slots[row], row_holes)
        if part.templates:
            yield part

    def _dispatch_one(self, bucket: _Bucket, b_tier: int) -> None:
        n = len(bucket.templates)
        mult = self._batch_multiple()
        n_tier = self._check_batch_tier(
            _tier(max(n + 1, mult), max(self.min_tier, mult), growth=4))
        L = b_tier * RATE

        templates = np.zeros((n_tier, L), dtype=np.uint8)
        for i, t in enumerate(bucket.templates):
            tl = len(t)
            templates[i, :tl] = np.frombuffer(t, dtype=np.uint8)
            # keccak multi-rate padding at the message's own final block
            templates[i, tl] ^= 0x01
            templates[i, bucket.counts[i] * RATE - 1] ^= 0x80
        counts = np.zeros((n_tier,), dtype=np.int32)
        counts[:n] = bucket.counts
        counts[n:] = 1  # padding rows absorb one zero block into dummy slot 0
        slots = np.zeros((n_tier,), dtype=np.int32)
        slots[:n] = bucket.slots

        key = self._sharding_key()
        if not bucket.holes:
            fn = _jitted("plain", b_tier, key)
            self._buf = _timed_call(
                "fused.plain", (b_tier, n_tier), fn,
                self._put_batch(templates), self._put_batch(counts),
                self._put_batch(slots), self._buf,
            )
            self._count_dispatch()
            return
        h_tier = _pow2(len(bucket.holes), floor=self._HOLE_FACTOR * self.min_tier)
        hole_node = np.full((h_tier,), n, dtype=np.int32)  # padding row target
        hole_byte = np.zeros((h_tier,), dtype=np.int32)
        hole_src = np.zeros((h_tier,), dtype=np.int32)
        for i, (row, off, src) in enumerate(bucket.holes):
            hole_node[i] = row
            hole_byte[i] = off
            hole_src[i] = src
        fn = _jitted("splice", b_tier, key)
        self._buf = _timed_call(
            "fused.splice", (b_tier, n_tier, h_tier), fn,
            self._put_batch(templates), self._put_batch(counts),
            self._put_batch(hole_node), self._put_batch(hole_byte),
            self._put_batch(hole_src), self._put_batch(slots), self._buf,
        )
        self._count_dispatch()

    # -- raw turbo dispatch (arrays straight from native/triebuild.cpp) ----

    def _pad_rows(self, n: int, *arrays):
        """Pad row-indexed arrays to the batch tier; returns (n_tier, padded)."""
        mult = self._batch_multiple()
        n_tier = self._check_batch_tier(
            _tier(max(n + 1, mult), max(self.min_tier, mult), growth=4))
        out = []
        for arr, fill in arrays:
            p = np.full((n_tier,), fill, dtype=arr.dtype)
            p[:n] = arr
            out.append(p)
        return n_tier, out

    @staticmethod
    def _filter_triples(triples, lo: int, hi: int):
        """Select (row, coord, src) triples with lo <= row < hi, rebased."""
        if triples is None:
            return None
        m = (triples[0] >= lo) & (triples[0] < hi)
        if not m.any():
            return None
        return np.stack((triples[0][m] - lo, triples[1][m], triples[2][m]))

    def _pad_holes(self, holes, n: int, floor: int, growth_mult):
        """Pad (row, off/nib, src) triples; padding rows target row ``n``
        (always a padding row since n_tier >= n+1) and dummy slot 0."""
        h = holes.shape[1] if holes is not None else 0
        mult = self._batch_multiple()
        h_tier = -(-floor // mult) * mult  # hole arrays shard over the mesh too
        while h_tier < h:
            h_tier *= growth_mult
        assert h_tier % mult == 0, (
            f"hole tier {h_tier} not divisible by the {mult}-device mesh")
        rows = np.full((h_tier,), n, dtype=np.int32)
        offs = np.zeros((h_tier,), dtype=np.int32)
        srcs = np.zeros((h_tier,), dtype=np.int32)
        if h:
            rows[:h], offs[:h], srcs[:h] = holes[0], holes[1], holes[2]
        return rows, offs, srcs

    def dispatch_packed(
        self,
        flat: np.ndarray,
        row_off: np.ndarray,
        row_len: np.ndarray,
        slots: np.ndarray,
        holes: np.ndarray | None,
        b_tier: int,
    ) -> None:
        """One level of tightly-packed RLP rows from the native builder.

        ``flat``: concatenated row bytes (the only bulk H2D of the level);
        ``holes``: (3, H) int32 [row, byte_off, src_slot] or None."""
        n = len(row_off)
        if n == 0:
            return
        self._check_block_tier(b_tier)
        if n + 1 > self._row_cap():
            # menu/row-cap clamp: split the level by row ranges (within-
            # level order is free), rebasing the packed bytes and holes
            cap = self._row_cap() - 1
            for lo in range(0, n, cap):
                hi = min(lo + cap, n)
                base = int(row_off[lo])
                end = int(row_off[hi - 1] + row_len[hi - 1])
                self.dispatch_packed(
                    flat[base:end], row_off[lo:hi] - base, row_len[lo:hi],
                    slots[lo:hi], self._filter_triples(holes, lo, hi), b_tier)
            return
        counts = (row_len // RATE + 1).astype(np.int32)
        n_tier, (row_off_p, row_len_p, counts_p, slots_p) = self._pad_rows(
            n, (row_off.astype(np.uint32), 0), (row_len.astype(np.uint32), 0),
            (counts, 1), (slots.astype(np.int32), 0),
        )
        flat_tier = _pow2(max(len(flat), 1), floor=4096)
        flat_p = np.zeros((flat_tier,), dtype=np.uint8)
        flat_p[: len(flat)] = flat
        hr, ho, hs = self._pad_holes(holes, n, floor=256, growth_mult=4)
        fn = _jitted("packed", b_tier, self._sharding_key())
        self._buf = _timed_call(
            "fused.packed", (b_tier, n_tier, flat_tier, len(hr)), fn,
            self._device_put(flat_p), self._put_batch(row_off_p),
            self._put_batch(row_len_p), self._put_batch(counts_p),
            self._put_batch(hr), self._put_batch(ho), self._put_batch(hs),
            self._put_batch(slots_p), self._buf,
        )
        self._count_dispatch()

    def dispatch_branch(
        self, masks: np.ndarray, slots: np.ndarray, children: np.ndarray
    ) -> None:
        """One level of all-hashed-children branches: 2-byte masks + child
        (row, nibble, src-slot) triples; the RLP bytes are constructed on
        device (``_branch_level``)."""
        n = len(masks)
        if n == 0:
            return
        if n + 1 > self._row_cap():
            cap = self._row_cap() - 1
            for lo in range(0, n, cap):
                hi = min(lo + cap, n)
                self.dispatch_branch(masks[lo:hi], slots[lo:hi],
                                     self._filter_triples(children, lo, hi))
            return
        n_tier, (masks_p, slots_p) = self._pad_rows(
            n, (masks.astype(np.int32), 0), (slots.astype(np.int32), 0)
        )
        # children <= 16n; tier as a multiple of the batch tier to bound the
        # number of compiled (n_tier, h_tier) combinations
        cr, cn, cs = self._pad_holes(children, n, floor=2 * n_tier, growth_mult=2)
        fn = _jitted("branch", 4, self._sharding_key())
        self._buf = _timed_call(
            "fused.branch", (n_tier, len(cr)), fn,
            self._put_batch(masks_p), self._put_batch(slots_p),
            self._put_batch(cr), self._put_batch(cn), self._put_batch(cs), self._buf,
        )
        self._count_dispatch()


def _level_extent(n_pow: int, L: int, u8_len: int) -> int:
    """Bytes of the staging buffer a packed level's row read addresses: a
    level's rows are contiguous from its ``flat_off`` and at most ``n_pow``
    rows of ``L`` bytes long, so no more than that (all of a buffer that is
    shorter) is the operand of the read."""
    return min(n_pow * L, u8_len)


# the aligned blocks a level's extent is read in: the TPU's lane width in bytes
_ROW_BLOCK = 128


def _level_rows(u8, flat_off, row_off, row_len, L: int):
    """(n_pow, L) u8: the tightly staged rows of ONE level, zero past each
    row's length.

    The operand of the read is the level's own extent of ``u8``, not the
    buffer, and no index addresses a single byte: on the TPU a per-byte
    gather costs ~8 ns an index whatever it reads from and more out of a
    large operand (PERF.md, PR 30). The extent's start is clamped by hand so
    that it ends inside the buffer, and the level's offset in it carries
    what the clamp moved: ``dynamic_slice`` would clamp silently and
    misalign the level. All of a level's bytes lie in
    ``[first[0], first[0] + sum(row_len))``, inside ``[0, ext)``.

    A row starts at any byte, so it is read as the whole ``_ROW_BLOCK``-byte
    blocks it can lie in (a gather of aligned rows of a 2-D view of the
    extent, ``n_blk`` indices a row) and then moved left by its phase in
    the first block, one bit of the phase at a time, highest first: a barrel
    shifter of static slices and selects, each stage keeping only the
    columns a later stage can still reach. The zero padding keeps the last
    row's blocks, and the junk rows' at ``sum(row_len)``, in bounds."""
    n_pow = row_off.shape[0]
    ext = _level_extent(n_pow, L, u8.shape[0])
    start = jnp.clip(flat_off, 0, u8.shape[0] - ext)
    seg = jax.lax.dynamic_slice(u8, (start,), (ext,))
    first = (flat_off - start) + row_off
    W = _ROW_BLOCK
    n_blk = -(-(W - 1 + L) // W)
    blocks = jnp.pad(seg, (0, -ext % W + n_blk * W)).reshape(-1, W)
    at = (first // W)[:, None] + jnp.arange(n_blk, dtype=jnp.int32)[None, :]
    rows = blocks[at].reshape(n_pow, n_blk * W)
    phase = first % W
    for bit in reversed(range((W - 1).bit_length())):
        step, keep = 1 << bit, L + (1 << bit) - 1
        rows = jnp.where(((phase >> bit) & 1)[:, None] == 1,
                         rows[:, step:step + keep], rows[:, :keep])
    col = jnp.arange(L, dtype=jnp.int32)[None, :]
    return jnp.where(col < row_len[:, None].astype(jnp.int32), rows, 0)


@lru_cache(maxsize=64)
def _staged_packed(b_tier: int, n_pow: int, h_pow: int, u8_len: int,
                   i32_len: int, s_tier: int):
    """One compiled per-LEVEL program over the staged whole-commit buffers.

    The first mega variant unrolled EVERY level into one XLA graph, whose
    compile time grew with the commit's depth. This variant keeps the mega engine's wire win (two H2D uploads per commit, zero
    mid-commit D2H — dispatches of device-resident buffers are cheap) but
    compiles SMALL per-level programs shared across levels: static shapes
    are pow2 row/hole tiers, while the level's location in the staging
    buffers (offsets) and its live row/hole counts arrive as traced scalars.
    Program count is O(log levels), each one a single masked-absorb graph.
    The level's rows are read from its own extent of ``u8`` (`_level_rows`),
    never from the whole buffer.
    """

    def mega_packed(u8, i32, digest_buf, flat_off, len_o, slot_o, hidx_o,
                    hsrc_o, n_valid, h_valid):
        L = b_tier * RATE
        raw = jax.lax.dynamic_slice(u8, (len_o,), (2 * n_pow,))
        raw = raw.reshape(n_pow, 2).astype(jnp.uint32)
        ridx = jnp.arange(n_pow, dtype=jnp.int32)
        vrow = ridx < n_valid
        row_len = jnp.where(vrow, raw[:, 0] | (raw[:, 1] << 8), 0)
        row_off = (jnp.cumsum(row_len) - row_len).astype(jnp.int32)
        counts = (row_len // RATE + 1).astype(jnp.int32)
        slots = jnp.where(
            vrow, jax.lax.dynamic_slice(i32, (slot_o,), (n_pow,)), 0)
        col = jnp.arange(L, dtype=jnp.int32)[None, :]
        rows = _level_rows(u8, flat_off, row_off, row_len, L)
        rl = row_len[:, None].astype(jnp.int32)
        rows = rows ^ jnp.where(col == rl, 0x01, 0).astype(jnp.uint8)
        last = (counts * RATE - 1)[:, None]
        rows = rows ^ jnp.where(col == last, 0x80, 0).astype(jnp.uint8)
        # splice child digests; junk hole entries retarget the level's
        # always-padding row (row n_valid-1 has row_len 0)
        hidxr = jax.lax.dynamic_slice(i32, (hidx_o,), (h_pow,))
        hsrcr = jax.lax.dynamic_slice(i32, (hsrc_o,), (h_pow,))
        hv = jnp.arange(h_pow, dtype=jnp.int32) < h_valid
        dump = (n_valid - 1) * L
        hidx = jnp.where(hv, hidxr, dump)
        hsrc = jnp.where(hv, hsrcr, 0)
        dig = digest_buf[hsrc]
        fr = rows.reshape(-1)
        sidx = hidx[:, None] + jnp.arange(32, dtype=jnp.int32)[None, :]
        rows = fr.at[sidx.reshape(-1)].set(dig.reshape(-1)).reshape(n_pow, L)
        d = masked_absorb_words(_bytes_to_words(rows), b_tier, counts)
        return digest_buf.at[slots].set(_digests_to_bytes(d))

    return jax.jit(mega_packed, donate_argnums=2)


@lru_cache(maxsize=64)
def _staged_branch(n_pow: int, ch_pow: int, u8_len: int, i32_len: int,
                   s_tier: int):
    """Per-level staged branch program (see `_staged_packed`)."""

    def mega_branch(u8, i32, digest_buf, mask_o, slot_o, chidx_o, chsrc_o,
                    n_valid, ch_valid):
        raw = jax.lax.dynamic_slice(u8, (mask_o,), (2 * n_pow,))
        raw = raw.reshape(n_pow, 2).astype(jnp.uint32)
        vrow = jnp.arange(n_pow, dtype=jnp.int32) < n_valid
        masks = jnp.where(vrow, (raw[:, 0] | (raw[:, 1] << 8)), 0)
        slots = jnp.where(
            vrow, jax.lax.dynamic_slice(i32, (slot_o,), (n_pow,)), 0)
        crn_r = jax.lax.dynamic_slice(i32, (chidx_o,), (ch_pow,))
        cs_r = jax.lax.dynamic_slice(i32, (chsrc_o,), (ch_pow,))
        cv = jnp.arange(ch_pow, dtype=jnp.int32) < ch_valid
        dump = (n_valid - 1) * 16
        crn = jnp.where(cv, crn_r, dump)
        cs = jnp.where(cv, cs_r, 0)
        return _branch_level(masks.astype(jnp.int32), slots, crn // 16,
                             crn % 16, cs, digest_buf, b_tier=4)

    return jax.jit(mega_branch, donate_argnums=2)


class MegaFusedEngine(FusedLevelEngine):
    """Whole-commit staging variant of the fused engine.

    The per-level engine pays ~18 dispatches x ~5 small host->device
    transfers per commit. This engine records every level dispatch, concatenates
    all inputs into TWO staging buffers (u8 bytes, i32 indices), uploads
    them in ONE device_put each, then runs one SMALL compiled program per
    level over the resident buffers (`_staged_packed`/`_staged_branch`),
    digest buffer donated through the chain. D2H stays a single
    digest/root fetch.

    Reference analogue: the same per-level batching seam
    (crates/stages/stages/src/stages/hashing_account.rs:29-32), collapsed
    to one device round trip per MerkleStage chunk.
    """

    def __init__(self, min_tier: int = 1024):
        super().__init__(min_tier=min_tier)
        self._plan: list[tuple] = []
        self._u8_parts: list[np.ndarray] = []
        self._i32_parts: list[np.ndarray] = []
        self._u8_off = 0
        self._i32_off = 0
        # per-commit H2D accounting (bench hotstate's bytes/block signal)
        self.staged_u8_bytes = 0
        self.staged_i32_bytes = 0

    def begin(self, max_slots: int) -> None:
        self._s_tier = _pow2(max_slots + 1, floor=max(self.min_tier, 2))
        self._n_slots = 1
        self._plan, self._u8_parts, self._i32_parts = [], [], []
        self._u8_off = self._i32_off = 0
        self._buf = None
        self.dispatches = 0
        self.staged_u8_bytes = 0
        self.staged_i32_bytes = 0

    def ensure(self, max_slots: int) -> None:
        """Staged variant: before ``_execute`` the buffer is only a planned
        shape, so growth is free — just raise the tier."""
        if self._buf is None:
            tier = _pow2(max_slots + 1, floor=max(self.min_tier, 2))
            if tier > self._s_tier:
                from ..metrics import fused_metrics

                fused_metrics.record_arena_grow()
                self._s_tier = tier
        else:  # already materialized (post-fetch reuse): real copy-grow
            super().ensure(max_slots)

    # program-shape tiers are pow2 from these floors: compile count stays
    # O(log workload) while the STAGED bytes remain tight (padding never
    # crosses the wire; the programs mask junk rows/holes via n_valid)
    _ROW_FLOOR = 2048
    _HOLE_FLOOR = 2048

    @staticmethod
    def _step(n: int, floor: int) -> int:
        """Quantize the final staging-buffer length: 4 steps per octave —
        ≤12.5% wire waste, logarithmic buffer-shape variety (the buffer
        length is part of every level program's signature)."""
        if n <= floor:
            return floor
        e = (n - 1).bit_length() - 1  # n in (2^e, 2^(e+1)]
        base = 1 << e
        for frac in (5, 6, 7, 8):
            v = base * frac // 4
            if v >= n:
                return v
        return base * 2

    def _stage_u8(self, arr: np.ndarray) -> int:
        arr = np.ascontiguousarray(arr, dtype=np.uint8).ravel()
        off = self._u8_off
        self._u8_parts.append(arr)
        self._u8_off += arr.size
        self.staged_u8_bytes += int(arr.size)
        return off

    def _stage_i32(self, *arrays: np.ndarray) -> int:
        off = self._i32_off
        for a in arrays:
            a = np.ascontiguousarray(a).astype(np.int32, copy=False).ravel()
            self._i32_parts.append(a)
            self._i32_off += a.size
            self.staged_i32_bytes += int(a.size) * 4
        return off

    def dispatch_packed(self, flat, row_off, row_len, slots, holes, b_tier) -> None:
        n = len(row_off)
        if n == 0:
            return
        self._check_block_tier(b_tier)
        L = b_tier * RATE
        if n + 1 > self._row_cap():
            # int32 scatter indices (row * L + byte) wrap past 2^31, and the
            # warm-up menu caps the batch tier — split the level by row
            # ranges (within-level order is free)
            cap = self._row_cap() - 1
            for lo in range(0, n, cap):
                hi = min(lo + cap, n)
                base = int(row_off[lo])
                end = int(row_off[hi - 1] + row_len[hi - 1])
                self.dispatch_packed(
                    flat[base:end], row_off[lo:hi] - base, row_len[lo:hi],
                    slots[lo:hi], self._filter_triples(holes, lo, hi), b_tier)
            return
        # tight staging + one explicit padding row (the hole dump target)
        row_len_p = np.zeros((n + 1,), dtype="<u2")
        row_len_p[:n] = row_len
        slots_p = np.zeros((n + 1,), dtype=np.int32)
        slots_p[:n] = slots
        h = holes.shape[1] if holes is not None else 0
        hidx = np.full((h + 1,), n * L, dtype=np.int32)
        hsrc = np.zeros((h + 1,), dtype=np.int32)
        if h:
            hidx[:h] = holes[0] * L + holes[1]
            hsrc[:h] = holes[2]
        flat_off = self._stage_u8(np.asarray(flat, dtype=np.uint8))
        len_o = self._stage_u8(row_len_p.view(np.uint8))
        slot_o = self._stage_i32(slots_p)
        hidx_o = self._stage_i32(hidx)
        hsrc_o = self._stage_i32(hsrc)
        self._plan.append(("packed", b_tier,
                           _pow2(n + 1, floor=self._ROW_FLOOR),
                           _pow2(h + 1, floor=self._HOLE_FLOOR),
                           flat_off, len_o, slot_o, hidx_o, hsrc_o,
                           n + 1, h + 1))

    def dispatch_branch(self, masks, slots, children) -> None:
        n = len(masks)
        if n == 0:
            return
        if n + 1 > self._row_cap():
            cap = self._row_cap() - 1
            for lo in range(0, n, cap):
                hi = min(lo + cap, n)
                self.dispatch_branch(masks[lo:hi], slots[lo:hi],
                                     self._filter_triples(children, lo, hi))
            return
        masks_p = np.zeros((n + 1,), dtype="<u2")
        masks_p[:n] = masks
        slots_p = np.zeros((n + 1,), dtype=np.int32)
        slots_p[:n] = slots
        c = children.shape[1] if children is not None else 0
        chidx = np.full((c + 1,), n * 16, dtype=np.int32)
        chsrc = np.zeros((c + 1,), dtype=np.int32)
        if c:
            chidx[:c] = children[0] * 16 + children[1]
            chsrc[:c] = children[2]
        mask_o = self._stage_u8(masks_p.view(np.uint8))
        slot_o = self._stage_i32(slots_p)
        chidx_o = self._stage_i32(chidx)
        chsrc_o = self._stage_i32(chsrc)
        self._plan.append(("branch",
                           _pow2(n + 1, floor=self._ROW_FLOOR),
                           _pow2(c + 1, floor=self._HOLE_FLOOR),
                           mask_o, slot_o, chidx_o, chsrc_o, n + 1, c + 1))

    def _buffer_lens(self) -> tuple[int, int]:
        """Final staged lengths: every program's dynamic_slice must fit
        in-bounds (a clamped slice start would silently misalign the level),
        then quantized so buffer-shape variety stays logarithmic."""
        u8_need = self._u8_off
        i32_need = self._i32_off
        for e in self._plan:
            if e[0] == "packed":
                (_, _b, n_pow, h_pow, _f, len_o, slot_o, hidx_o, hsrc_o,
                 _n, _h) = e
                u8_need = max(u8_need, len_o + 2 * n_pow)
                i32_need = max(i32_need, slot_o + n_pow,
                               hidx_o + h_pow, hsrc_o + h_pow)
            else:
                _, n_pow, ch_pow, mask_o, slot_o, chidx_o, chsrc_o, _n, _c = e
                u8_need = max(u8_need, mask_o + 2 * n_pow)
                i32_need = max(i32_need, slot_o + n_pow,
                               chidx_o + ch_pow, chsrc_o + ch_pow)
        return (self._step(u8_need, 1 << 16), self._step(i32_need, 1 << 12))

    def _assemble(self, u8_len: int, i32_len: int):
        """The staged parts copied into the two contiguous arrays that
        cross the wire."""
        u8 = np.zeros((u8_len,), dtype=np.uint8)
        off = 0
        for part in self._u8_parts:
            u8[off:off + part.size] = part
            off += part.size
        i32 = np.zeros((i32_len,), dtype=np.int32)
        off = 0
        for part in self._i32_parts:
            i32[off:off + part.size] = part
            off += part.size
        return u8, i32

    def _execute(self) -> None:
        from ..metrics import fused_metrics, trie_metrics

        if self._buf is not None:
            return
        s_tier = self._s_tier
        with trie_metrics.phase("assemble"):
            u8_len, i32_len = self._buffer_lens()
            u8, i32 = self._assemble(u8_len, i32_len)
        with trie_metrics.phase("upload"):
            u8d = self._device_put(u8)
            i32d = self._device_put(i32)
            buf = self._device_put(np.zeros((s_tier, 32), dtype=np.uint8))
        s32 = np.int32
        rows_dispatched = rows_needed = 0
        gather_bytes = gather_rows = 0
        branch_index_elems = branch_rows = 0
        with trie_metrics.phase("enqueue"):
            for e in self._plan:
                if e[0] == "packed":
                    (_, b_tier, n_pow, h_pow, flat_off, len_o, slot_o, hidx_o,
                     hsrc_o, n_valid, h_valid) = e
                    gather_bytes += _level_extent(n_pow, b_tier * RATE, u8_len)
                    gather_rows += n_pow
                    fn = _staged_packed(b_tier, n_pow, h_pow, u8_len, i32_len,
                                        s_tier)
                    buf = _timed_call(
                        "mega.packed",
                        (b_tier, n_pow, h_pow, u8_len, i32_len, s_tier),
                        fn, u8d, i32d, buf, s32(flat_off), s32(len_o),
                        s32(slot_o), s32(hidx_o), s32(hsrc_o),
                        s32(n_valid), s32(h_valid))
                else:
                    (_, n_pow, ch_pow, mask_o, slot_o, chidx_o, chsrc_o,
                     n_valid, c_valid) = e
                    branch_index_elems += _branch_index_elems(n_pow, ch_pow)
                    branch_rows += n_pow
                    fn = _staged_branch(n_pow, ch_pow, u8_len, i32_len,
                                        s_tier)
                    buf = _timed_call(
                        "mega.branch",
                        (n_pow, ch_pow, u8_len, i32_len, s_tier),
                        fn, u8d, i32d, buf, s32(mask_o), s32(slot_o),
                        s32(chidx_o), s32(chsrc_o), s32(n_valid),
                        s32(c_valid))
                self._count_dispatch()
                rows_dispatched += n_pow
                rows_needed += n_valid - 1  # all but the padding row
        fused_metrics.record_rows(rows_dispatched, rows_needed)
        fused_metrics.record_gather(gather_bytes, gather_rows)
        fused_metrics.record_branch_index(branch_index_elems, branch_rows)
        self._buf = buf
        self._plan, self._u8_parts, self._i32_parts = [], [], []

    def launch(self) -> None:
        """Start what is staged: assemble, upload and enqueue every level
        program, all asynchronous, and return without waiting, so the host
        can work while the device hashes until ``finish()`` waits. Idempotent;
        ``finish`` and ``fetch_slots`` start it themselves."""
        self._execute()

    def finish(self) -> np.ndarray:
        self._execute()
        return super().finish()

    def fetch_slots(self, slots: np.ndarray) -> np.ndarray:
        self._execute()
        return super().fetch_slots(slots)


class FusedMeshEngine(FusedLevelEngine):
    """Fused level commit SPMD-sharded over a 1-axis device mesh.

    Templates/counts/slots shard over the batch axis (each device hashes its
    level shard); the digest buffer is replicated — the scatter of a level's
    sharded digests into the replicated buffer makes XLA insert the
    all-gather (rides ICI on hardware), which is exactly the child-digest
    exchange a multi-chip trie commit needs. This is the committer's real
    level loop over the mesh, not a toy reduction.
    """

    def __init__(self, mesh, min_tier: int = 1024):
        from jax.sharding import NamedSharding, PartitionSpec as P

        # ``mesh``: a jax.sharding.Mesh, or a parallel/mesh.py HashMesh
        # descriptor — then the engine snapshots the LIVE sub-mesh at
        # construction (one commit = one membership; a device lost
        # mid-commit is the SupervisedBackend journal-replay's job)
        live_snapshot = getattr(mesh, "live_snapshot", None)
        if live_snapshot is not None:
            mesh, _ = live_snapshot()
            if mesh is None:
                raise RuntimeError("HashMesh has no live devices")
        # every tier must stay divisible by the device count: tiers grow by
        # x4 (batch) / x2 (holes, slots) from their floors, so rounding the
        # floor up to a device-count multiple keeps all of them shardable.
        # self.mesh must be set BEFORE super().__init__: the base class
        # hoists the ladder caps at construction, which asks for
        # _batch_multiple() — the mesh's device count here.
        mult = mesh.devices.size
        self.mesh = mesh
        axis = mesh.axis_names[0]
        self._batch_sharding = NamedSharding(mesh, P(axis))
        self._replicated = NamedSharding(mesh, P())
        super().__init__(min_tier=-(-min_tier // mult) * mult)

    def _device_put(self, arr: np.ndarray):
        return jax.device_put(_h2d(arr), self._replicated)

    def _put_batch(self, arr: np.ndarray):
        return jax.device_put(_h2d(arr), self._batch_sharding)

    def _sharding_key(self):
        return self.mesh

    def _batch_multiple(self) -> int:
        return self.mesh.devices.size


# -- whole-subtrie fused kernels (ONE dispatch per k levels) ------------------


class InjectedSubtrieWedge(RuntimeError):
    """Fault injection wedged a k-level fused chunk dispatch
    (RETH_TPU_FAULT_SUBTRIE_WEDGE) — the engine must replay the whole
    staged journal bit-identically on the per-level path."""


class InjectedSubtrieAbort(RuntimeError):
    """Fault injection poisoned the WHOLE device path for this engine
    (RETH_TPU_FAULT_SUBTRIE_ABORT): the fused chunk AND its per-level
    replay both fail, so the commit must land on the CPU twin."""


class SubtrieFaultInjector:
    """Fault policies for the whole-subtrie engine, in the style of
    ``ops/supervisor.py``'s FaultInjector.

    ``wedge_at``: the Nth fused (multi-level) chunk dispatch of the
    process raises :class:`InjectedSubtrieWedge` (one-shot) — the engine
    replays its journal on the per-level path, roots bit-identical.
    ``abort_at``: the Nth chunk dispatch raises AND every subsequent
    per-level replay dispatch raises too — drills the final rung: the
    journal replays on the CPU twin.

    Env form (:meth:`from_env`): ``RETH_TPU_FAULT_SUBTRIE_WEDGE`` /
    ``RETH_TPU_FAULT_SUBTRIE_ABORT``.
    """

    def __init__(self, wedge_at: int = 0, abort_at: int = 0):
        import threading

        self.wedge_at = wedge_at
        self.abort_at = abort_at
        self.chunks = 0
        self.wedges = 0
        self.aborts = 0
        self._abort_armed = False
        self._lock = threading.Lock()

    @classmethod
    def from_env(cls, env=None) -> "SubtrieFaultInjector | None":
        import os

        env = os.environ if env is None else env
        wedge = int(env.get("RETH_TPU_FAULT_SUBTRIE_WEDGE", "0") or 0)
        abort = int(env.get("RETH_TPU_FAULT_SUBTRIE_ABORT", "0") or 0)
        if not (wedge or abort):
            return None
        return cls(wedge_at=wedge, abort_at=abort)

    def on_chunk(self, mode: str, levels: int) -> None:
        """Called before every subtrie device dispatch. ``mode`` is
        "fused" for k-level chunks and "perlevel" for the fallback
        replay's single-level dispatches."""
        from .. import tracing

        if mode == "perlevel":
            with self._lock:
                armed = self._abort_armed
            if armed:
                tracing.fault_event("RETH_TPU_FAULT_SUBTRIE_ABORT",
                                    target="ops::fused_commit",
                                    rung="perlevel")
                raise InjectedSubtrieAbort(
                    "injected subtrie abort: per-level replay poisoned "
                    f"(RETH_TPU_FAULT_SUBTRIE_ABORT={self.abort_at})")
            return
        with self._lock:
            self.chunks += 1
            n = self.chunks
        if self.wedge_at and n == self.wedge_at:
            with self._lock:
                self.wedges += 1
            tracing.fault_event("RETH_TPU_FAULT_SUBTRIE_WEDGE",
                                target="ops::fused_commit", chunk=n,
                                levels=levels)
            raise InjectedSubtrieWedge(
                f"injected subtrie wedge on chunk #{n} "
                f"(RETH_TPU_FAULT_SUBTRIE_WEDGE={self.wedge_at})")
        if self.abort_at and n == self.abort_at:
            with self._lock:
                self.aborts += 1
                self._abort_armed = True
            tracing.fault_event("RETH_TPU_FAULT_SUBTRIE_ABORT",
                                target="ops::fused_commit", chunk=n,
                                levels=levels)
            raise InjectedSubtrieAbort(
                f"injected subtrie abort on chunk #{n} "
                f"(RETH_TPU_FAULT_SUBTRIE_ABORT={self.abort_at})")


_PARAM_W = 10  # param-table row width (i32): kind + offsets + valid counts


def _ladder_tier(n: int, floor: int, mult: int) -> int:
    """x2 ladder from the ``mult``-rounded floor (stays divisible by the
    mesh device count, mirroring ``FusedMeshEngine``'s tier discipline)."""
    t = -(-max(1, floor) // max(1, mult)) * max(1, mult)
    while t < n:
        t *= 2
    return t


@lru_cache(maxsize=128)
def _subtrie_program(b_tier: int, n_pow: int, h_pow: int, steps_pow: int,
                     u8_len: int, i32_len: int, s_tier: int, mesh=None):
    """ONE compiled program hashing up to ``steps_pow`` staged levels.

    This is the Sakura shape (arxiv 1608.00492): the depth loop runs
    INSIDE the jit — ``lax.fori_loop`` with the resident digest buffer as
    the carry, each step splicing child digests written by earlier steps
    — so a whole k-level chunk costs ONE dispatch instead of one per
    depth. The loop body is traced ONCE (a ``lax.cond`` selecting the
    packed or branch shape per step from the i32 param table), so trace
    and compile size are constant in k (the first mega variant unrolled
    every level into one graph).
    Static shapes are the chunk-wide (rows, aux, steps) tiers plus the
    staging-buffer lengths; live counts arrive via the param table and
    junk rows/holes mask to the dummy slot, exactly like the per-level
    staged programs — digests for real slots are bit-identical to the
    per-level path by construction.

    ``mesh``: a jax Mesh — the k-level SPMD variant. Staged buffers are
    replicated; the per-step row block gets a sharding constraint over
    the batch axis. The k-level packers keep each subtrie's rows
    contiguous, so row-range shards ≈ subtrie shards: parent composition
    goes through the REPLICATED digest buffer (XLA inserts the
    all-gather), never through a neighbour's row shard.
    """
    L = b_tier * RATE
    constraint = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        constraint = NamedSharding(mesh, P(mesh.axis_names[0], None))

    def _shard(rows):
        if constraint is not None:
            return jax.lax.with_sharding_constraint(rows, constraint)
        return rows

    def packed_step(u8, i32, buf, p):
        flat_off, len_o, slot_o = p[1], p[2], p[3]
        hrow_o, hbyte_o, hsrc_o = p[4], p[5], p[6]
        n_valid, h_valid = p[7], p[8]
        raw = jax.lax.dynamic_slice(u8, (len_o,), (2 * n_pow,))
        raw = raw.reshape(n_pow, 2).astype(jnp.uint32)
        ridx = jnp.arange(n_pow, dtype=jnp.int32)
        vrow = ridx < n_valid
        row_len = jnp.where(vrow, raw[:, 0] | (raw[:, 1] << 8), 0)
        row_off = (jnp.cumsum(row_len) - row_len).astype(jnp.int32)
        counts = (row_len // RATE + 1).astype(jnp.int32)
        slots = jnp.where(
            vrow, jax.lax.dynamic_slice(i32, (slot_o,), (n_pow,)), 0)
        col = jnp.arange(L, dtype=jnp.int32)[None, :]
        rows = _level_rows(u8, flat_off, row_off, row_len, L)
        rl = row_len[:, None].astype(jnp.int32)
        rows = rows ^ jnp.where(col == rl, 0x01, 0).astype(jnp.uint8)
        last = (counts * RATE - 1)[:, None]
        rows = rows ^ jnp.where(col == last, 0x80, 0).astype(jnp.uint8)
        # splice child digests; junk hole entries retarget the level's
        # always-padding row (row n_valid-1 has row_len 0, slot 0). Hole
        # targets are staged as (row, byte) pairs — NOT row*L+byte — so
        # one chunk-wide L can serve levels staged at different b_tiers.
        hv = jnp.arange(h_pow, dtype=jnp.int32) < h_valid
        hrow = jnp.where(
            hv, jax.lax.dynamic_slice(i32, (hrow_o,), (h_pow,)), n_valid - 1)
        hbyte = jnp.where(
            hv, jax.lax.dynamic_slice(i32, (hbyte_o,), (h_pow,)), 0)
        hsrc = jnp.where(
            hv, jax.lax.dynamic_slice(i32, (hsrc_o,), (h_pow,)), 0)
        dig = buf[hsrc]
        fr = rows.reshape(-1)
        sidx = (hrow * L + hbyte)[:, None] \
            + jnp.arange(32, dtype=jnp.int32)[None, :]
        rows = _shard(
            fr.at[sidx.reshape(-1)].set(dig.reshape(-1)).reshape(n_pow, L))
        d = masked_absorb_words(_bytes_to_words(rows), b_tier, counts)
        return buf.at[slots].set(_digests_to_bytes(d))

    def branch_step(u8, i32, buf, p):
        mask_o, slot_o, chidx_o, chsrc_o = p[1], p[2], p[3], p[4]
        n_valid, ch_valid = p[7], p[8]
        raw = jax.lax.dynamic_slice(u8, (mask_o,), (2 * n_pow,))
        raw = raw.reshape(n_pow, 2).astype(jnp.uint32)
        vrow = jnp.arange(n_pow, dtype=jnp.int32) < n_valid
        masks = jnp.where(vrow, raw[:, 0] | (raw[:, 1] << 8), 0)
        slots = jnp.where(
            vrow, jax.lax.dynamic_slice(i32, (slot_o,), (n_pow,)), 0)
        cv = jnp.arange(h_pow, dtype=jnp.int32) < ch_valid
        crn = jnp.where(
            cv, jax.lax.dynamic_slice(i32, (chidx_o,), (h_pow,)),
            (n_valid - 1) * 16)
        cs = jnp.where(
            cv, jax.lax.dynamic_slice(i32, (chsrc_o,), (h_pow,)), 0)
        return _branch_level(masks.astype(jnp.int32), slots, crn // 16,
                             crn % 16, cs, buf, b_tier=b_tier)

    def subtrie_chunk(u8, i32, params, buf, n_steps):
        def body(s, carry):
            p = jax.lax.dynamic_index_in_dim(params, s, axis=0,
                                             keepdims=False)
            return jax.lax.cond(
                p[0] == 0,
                lambda b: packed_step(u8, i32, b, p),
                lambda b: branch_step(u8, i32, b, p),
                carry)
        return jax.lax.fori_loop(0, n_steps, body, buf)

    return jax.jit(subtrie_chunk, donate_argnums=3)


class SubtrieFusedEngine(MegaFusedEngine):
    """Whole-subtrie k-level fused engine: ONE device dispatch per chunk
    of k staged levels, not one per depth (ROADMAP item 3).

    Staging follows :class:`MegaFusedEngine` (two H2D uploads per flush,
    tight bytes, zero mid-commit D2H), but execution goes one step
    further: instead of one small program PER level, consecutive staged
    levels group into chunks of ``k`` and each chunk runs as ONE
    :func:`_subtrie_program` dispatch whose depth loop carries the
    resident digest buffer — dispatches per commit drop from O(depth) to
    O(depth / k). ``flush_window()`` lets the rebuild pipeline execute
    each packed window eagerly (the digest buffer stays resident across
    windows), preserving the sweep/hash overlap.

    Degradation ladder (journal-replay based — staging arrays are host
    numpy, retained until the terminal fetch, so replay is exact):

      fused chunks → per-level (the same program at k=1) → CPU twin

    A failed chunk dispatch (watchdog escape, injected
    ``RETH_TPU_FAULT_SUBTRIE_WEDGE``) rebuilds the whole digest buffer by
    replaying the journal per-level; if the device path is gone entirely
    (``RETH_TPU_FAULT_SUBTRIE_ABORT``), the journal replays on the CPU
    twin. Roots are bit-identical on every rung — hashing is
    deterministic and the journal holds every staged byte. An attached
    warm-up manager routes un-warm (fused.subtrie, k, tier, mesh) shapes
    to the per-level path instead of compiling mid-commit.

    Chunking discipline: steps sharing a chunk share ONE static
    (b_tier, rows, aux) shape — the chunk b_tier is the max over its
    steps (capped at ``_CHUNK_BTIER_CAP``; bigger-block levels dispatch
    solo) and row/aux tiers are chunk-wide ladders, so program variety
    stays O(log workload) and padded rows mask to the dummy slot.
    """

    effective_kind = "device"
    _CHUNK_BTIER_CAP = 8

    def __init__(self, min_tier: int = 1024, k: int | None = None,
                 warmup=None, injector=None, row_floor: int | None = None,
                 hole_floor: int | None = None):
        import os as _os

        super().__init__(min_tier=min_tier)
        if k is None:
            k = int(_os.environ.get("RETH_TPU_SUBTRIE_LEVELS", "0") or 8)
        self.k = max(1, int(k))
        self.warmup = warmup
        self.injector = (injector if injector is not None
                         else SubtrieFaultInjector.from_env())
        if row_floor:
            self._ROW_FLOOR = int(row_floor)
        if hole_floor:
            self._HOLE_FLOOR = int(hole_floor)
        self._mode = "fused"
        self._journal: list[tuple[np.ndarray, np.ndarray, list]] = []
        self._buf_np: np.ndarray | None = None
        self.levels_staged = 0
        # delta commits (hot-state arena): the journal only covers THIS
        # epoch, so the internal replay-from-zeros ladder would silently
        # lose prior epochs' resident rows — in delta mode any device
        # fault re-raises and the OWNER (DigestArena) takes the full-
        # upload rung instead (ISSUE 19's external ladder).
        self._delta = False

    # -- mesh seam (overridden by SubtrieMeshEngine) -----------------------

    def _mesh_arg(self):
        return None

    def _mesh_size(self) -> int:
        return 1

    # -- lifecycle ---------------------------------------------------------

    def begin(self, max_slots: int) -> None:
        super().begin(max_slots)
        self._mode = "fused"
        self._journal = []
        self._buf_np = None
        self.levels_staged = 0
        self._delta = False

    def begin_delta(self, max_slots: int) -> None:
        """Open a DELTA commit: keep the resident digest buffer from the
        previous epoch and stage only this epoch's dirty rows (holes may
        splice prior-epoch slots). Preconditions — the engine must still
        be on the fused rung with a materialized buffer; anything else is
        an :class:`ArenaFault` the owner answers with a full upload."""
        if self._mode != "fused" or self._buf is None:
            raise ArenaFault(
                f"delta precondition lost (mode={self._mode}, "
                f"resident={self._buf is not None})")
        self._plan, self._u8_parts, self._i32_parts = [], [], []
        self._u8_off = self._i32_off = 0
        self.dispatches = 0
        self.staged_u8_bytes = 0
        self.staged_i32_bytes = 0
        self._journal = []
        self._buf_np = None
        self.levels_staged = 0
        self._delta = True
        self.ensure(max_slots)

    def ensure(self, max_slots: int) -> None:
        if self._mode == "cpu":
            need = max_slots + 1
            if self._buf_np is not None and self._buf_np.shape[0] >= need:
                return
            tier = _pow2(need, floor=max(self.min_tier, 2, self._s_tier))
            grown = np.zeros((tier, 32), dtype=np.uint8)
            if self._buf_np is not None:
                grown[: self._buf_np.shape[0]] = self._buf_np
            self._buf_np = grown
            self._s_tier = tier
            return
        super().ensure(max_slots)
        if self._buf is not None:
            self._s_tier = int(self._buf.shape[0])

    # -- staging (k-level layout: hole targets as (row, byte) pairs) -------

    def dispatch_packed(self, flat, row_off, row_len, slots, holes, b_tier):
        n = len(row_off)
        if n == 0:
            return
        self._check_block_tier(b_tier)
        if n + 1 > self._row_cap():
            cap = self._row_cap() - 1
            for lo in range(0, n, cap):
                hi = min(lo + cap, n)
                base = int(row_off[lo])
                end = int(row_off[hi - 1] + row_len[hi - 1])
                self.dispatch_packed(
                    flat[base:end], row_off[lo:hi] - base, row_len[lo:hi],
                    slots[lo:hi], self._filter_triples(holes, lo, hi), b_tier)
            return
        row_len_p = np.zeros((n + 1,), dtype="<u2")
        row_len_p[:n] = row_len
        slots_p = np.zeros((n + 1,), dtype=np.int32)
        slots_p[:n] = slots
        h = holes.shape[1] if holes is not None else 0
        hrow = np.full((h + 1,), n, dtype=np.int32)  # dump: the padding row
        hbyte = np.zeros((h + 1,), dtype=np.int32)
        hsrc = np.zeros((h + 1,), dtype=np.int32)
        if h:
            hrow[:h], hbyte[:h], hsrc[:h] = holes[0], holes[1], holes[2]
        flat_off = self._stage_u8(np.asarray(flat, dtype=np.uint8))
        len_o = self._stage_u8(row_len_p.view(np.uint8))
        slot_o = self._stage_i32(slots_p)
        hrow_o = self._stage_i32(hrow)
        hbyte_o = self._stage_i32(hbyte)
        hsrc_o = self._stage_i32(hsrc)
        self._plan.append(("packed", b_tier, flat_off, len_o, slot_o,
                           hrow_o, hbyte_o, hsrc_o, n + 1, h + 1))
        self.levels_staged += 1

    def dispatch_branch(self, masks, slots, children) -> None:
        n = len(masks)
        if n == 0:
            return
        if n + 1 > self._row_cap():
            cap = self._row_cap() - 1
            for lo in range(0, n, cap):
                hi = min(lo + cap, n)
                self.dispatch_branch(masks[lo:hi], slots[lo:hi],
                                     self._filter_triples(children, lo, hi))
            return
        masks_p = np.zeros((n + 1,), dtype="<u2")
        masks_p[:n] = masks
        slots_p = np.zeros((n + 1,), dtype=np.int32)
        slots_p[:n] = slots
        c = children.shape[1] if children is not None else 0
        chidx = np.full((c + 1,), n * 16, dtype=np.int32)
        chsrc = np.zeros((c + 1,), dtype=np.int32)
        if c:
            chidx[:c] = children[0] * 16 + children[1]
            chsrc[:c] = children[2]
        mask_o = self._stage_u8(masks_p.view(np.uint8))
        slot_o = self._stage_i32(slots_p)
        chidx_o = self._stage_i32(chidx)
        chsrc_o = self._stage_i32(chsrc)
        self._plan.append(("branch", mask_o, slot_o, chidx_o, chsrc_o,
                           n + 1, c + 1))
        self.levels_staged += 1

    # -- chunk planning ----------------------------------------------------

    @staticmethod
    def _step_btier(e) -> int:
        return e[1] if e[0] == "packed" else 4

    def _chunk_plan(self, plan: list, k: int) -> list[tuple]:
        """[(entries, b_tier, n_pow, h_pow)] — consecutive steps grouped
        up to ``k`` per chunk; within-a-commit order is the dependency
        order (deeper levels staged first), so consecutive grouping
        preserves parent composition exactly."""
        mult = self._batch_multiple()
        groups: list[list] = []
        cur: list = []
        cur_big = False
        for e in plan:
            big = self._step_btier(e) > self._CHUNK_BTIER_CAP
            if cur and (len(cur) >= k or big or cur_big):
                groups.append(cur)
                cur = []
            cur.append(e)
            cur_big = big
        if cur:
            groups.append(cur)
        chunks = []
        for entries in groups:
            b_tier = max(self._step_btier(e) for e in entries)
            n_pow = _ladder_tier(max(e[-2] for e in entries),
                                 self._ROW_FLOOR, mult)
            h_pow = _ladder_tier(max(e[-1] for e in entries),
                                 self._HOLE_FLOOR, mult)
            chunks.append((entries, b_tier, n_pow, h_pow))
        return chunks

    def _chunk_buffer_lens(self, chunks: list[tuple]) -> tuple[int, int]:
        """Final staged lengths covering every chunk-wide dynamic_slice
        (a clamped slice start would silently misalign a level — the
        chunk-wide row/aux tiers read PAST each level's own staging, so
        the buffers must be long enough for the widest reader)."""
        u8_need = self._u8_off
        i32_need = self._i32_off
        for entries, _b, n_pow, h_pow in chunks:
            for e in entries:
                if e[0] == "packed":
                    (_t, _bt, _f, len_o, slot_o, hrow_o, hbyte_o, hsrc_o,
                     _n, _h) = e
                    u8_need = max(u8_need, len_o + 2 * n_pow)
                    i32_need = max(i32_need, slot_o + n_pow,
                                   hrow_o + h_pow, hbyte_o + h_pow,
                                   hsrc_o + h_pow)
                else:
                    _t, mask_o, slot_o, chidx_o, chsrc_o, _n, _c = e
                    u8_need = max(u8_need, mask_o + 2 * n_pow)
                    i32_need = max(i32_need, slot_o + n_pow,
                                   chidx_o + h_pow, chsrc_o + h_pow)
        return (self._step(u8_need, 1 << 16), self._step(i32_need, 1 << 12))

    # -- execution ---------------------------------------------------------

    def flush_window(self) -> None:
        """Execute everything staged so far (the rebuild pipeline calls
        this per packed window, so device hashing overlaps the next
        window's native sweep). The digest buffer stays resident."""
        self._execute()

    def _execute(self) -> None:
        plan = self._plan
        if not plan:
            if (self._mode != "cpu" and self._buf is None
                    and self._buf_np is None):
                self._buf = self._device_put(
                    np.zeros((self._s_tier, 32), dtype=np.uint8))
            return
        from ..metrics import trie_metrics

        k_plan = 1 if self._mode == "perlevel" else self.k
        with trie_metrics.phase("assemble"):
            chunks = self._chunk_plan(plan, k_plan)
            u8_len, i32_len = self._chunk_buffer_lens(chunks)
            u8, i32 = self._assemble(u8_len, i32_len)
        self._plan, self._u8_parts, self._i32_parts = [], [], []
        self._u8_off = self._i32_off = 0
        # the journal IS the failover: replay is exact because every
        # staged byte is retained until the terminal fetch
        self._journal.append((u8, i32, plan))
        if self._mode == "cpu":
            self._run_plan_numpy(u8, i32, plan)
            return
        if self._buf is None:
            self._buf = self._device_put(
                np.zeros((self._s_tier, 32), dtype=np.uint8))
        mult = self._batch_multiple()
        route_tier = -(-self._ROW_FLOOR // mult) * mult
        if (self._mode == "fused" and self.k > 1 and self.warmup is not None
                and not self.warmup.route_bucket(
                    "fused.subtrie", self.k, route_tier,
                    self._mesh_size())):
            # degraded routing: the k-shape isn't warm — this flush runs
            # per-level (same staged bytes, k=1 chunks); the engine stays
            # on "fused" so later flushes promote once the shape warms
            from ..metrics import fused_metrics

            fused_metrics.record_fallback()
            chunks = self._chunk_plan(plan, 1)
        mode = "perlevel" if (self._mode == "perlevel"
                              or len(chunks) >= len(plan)) else "fused"
        try:
            self._run_chunks(u8, i32, chunks, u8_len, i32_len, mode)
        except BaseException as e:  # noqa: BLE001 — degraded below
            if self._delta:
                raise  # external ladder: the arena owner full-uploads
            self._degrade(e)

    def _run_chunks(self, u8: np.ndarray, i32: np.ndarray, chunks: list,
                    u8_len: int, i32_len: int, mode: str) -> None:
        from ..metrics import fused_metrics, trie_metrics

        with trie_metrics.phase("upload"):
            u8d = self._device_put(u8)
            i32d = self._device_put(i32)
        s_tier = int(self._buf.shape[0])
        with trie_metrics.phase("enqueue"):
            for entries, b_tier, n_pow, h_pow in chunks:
                steps_pow = _pow2(len(entries), floor=8)
                params = np.zeros((steps_pow, _PARAM_W), dtype=np.int32)
                for i, e in enumerate(entries):
                    if e[0] == "packed":
                        (_t, _bt, flat_off, len_o, slot_o, hrow_o, hbyte_o,
                         hsrc_o, n_valid, h_valid) = e
                        params[i] = (0, flat_off, len_o, slot_o, hrow_o,
                                     hbyte_o, hsrc_o, n_valid, h_valid, 0)
                    else:
                        (_t, mask_o, slot_o, chidx_o, chsrc_o, n_valid,
                         c_valid) = e
                        params[i] = (1, mask_o, slot_o, chidx_o, chsrc_o, 0,
                                     0, n_valid, c_valid, 0)
                if self.injector is not None:
                    self.injector.on_chunk(mode, len(entries))
                fn = _subtrie_program(b_tier, n_pow, h_pow, steps_pow, u8_len,
                                      i32_len, s_tier, self._mesh_arg())
                self._buf = _timed_call(
                    "fused.subtrie",
                    (b_tier, n_pow, h_pow, steps_pow, u8_len, i32_len,
                     s_tier, self._mesh_size()),
                    fn, u8d, i32d, self._device_put(params), self._buf,
                    np.int32(len(entries)))
                self._count_dispatch(len(entries))
                # every step runs at the chunk's row tier; e[-2] is a
                # level's n_valid, its rows and the padding row
                fused_metrics.record_rows(
                    n_pow * len(entries), sum(e[-2] - 1 for e in entries))

    # -- degradation ladder ------------------------------------------------

    def _degrade(self, err: BaseException) -> None:
        from .. import tracing
        from ..metrics import fused_metrics

        fused_metrics.record_fallback()
        if self._mode == "fused":
            tracing.fault_event("subtrie_fallback",
                                target="ops::fused_commit",
                                rung="perlevel",
                                error=f"{type(err).__name__}: {err}"[:200])
            self._mode = "perlevel"
            try:
                self._replay_journal_device()
                return
            except BaseException as e2:  # noqa: BLE001 — final rung below
                fused_metrics.record_fallback()
                err = e2
        tracing.fault_event("subtrie_fallback", target="ops::fused_commit",
                            rung="cpu",
                            error=f"{type(err).__name__}: {err}"[:200])
        self._mode = "cpu"
        self._buf = None
        self._buf_np = np.zeros((self._s_tier, 32), dtype=np.uint8)
        for u8, i32, plan in self._journal:
            self._run_plan_numpy(u8, i32, plan)

    def _replay_journal_device(self) -> None:
        """Per-level rung: rebuild the digest buffer by replaying EVERY
        journaled flush through the same program at k=1 (hashing is
        deterministic, so the rebuilt buffer is bit-identical)."""
        self._buf = self._device_put(
            np.zeros((self._s_tier, 32), dtype=np.uint8))
        for u8, i32, plan in self._journal:
            chunks = self._chunk_plan(plan, 1)
            self._run_chunks(u8, i32, chunks, u8.size, i32.size, "perlevel")

    def _run_plan_numpy(self, u8: np.ndarray, i32: np.ndarray,
                        plan: list) -> None:
        """CPU-twin rung: interpret the staged plan with the numpy
        backend's own level math (bit-identical to the device path)."""
        from ..trie.turbo import _NumpyBackend

        nb = _NumpyBackend()
        nb._buf = self._buf_np
        for e in plan:
            if e[0] == "packed":
                (_t, b_tier, flat_off, len_o, slot_o, hrow_o, hbyte_o,
                 hsrc_o, n_valid, h_valid) = e
                n = n_valid - 1
                raw = u8[len_o:len_o + 2 * n].astype(np.uint32)
                row_len = (raw[0::2] | (raw[1::2] << 8)).astype(np.uint32)
                row_off = (np.cumsum(row_len) - row_len).astype(np.uint32)
                slots = i32[slot_o:slot_o + n].astype(np.int64)
                total = int(row_off[-1] + row_len[-1]) if n else 0
                flat = u8[flat_off:flat_off + total]
                h = h_valid - 1
                holes = None
                if h:
                    holes = (i32[hrow_o:hrow_o + h],
                             i32[hbyte_o:hbyte_o + h],
                             i32[hsrc_o:hsrc_o + h])
                nb.dispatch_packed(flat, row_off, row_len, slots, holes,
                                   b_tier)
            else:
                _t, mask_o, slot_o, chidx_o, chsrc_o, n_valid, c_valid = e
                n = n_valid - 1
                raw = u8[mask_o:mask_o + 2 * n].astype(np.uint16)
                masks = (raw[0::2] | (raw[1::2] << 8)).astype(np.uint16)
                slots = i32[slot_o:slot_o + n].astype(np.int64)
                c = c_valid - 1
                crn = i32[chidx_o:chidx_o + c]
                children = np.stack([crn // 16, crn % 16,
                                     i32[chsrc_o:chsrc_o + c]])
                nb.dispatch_branch(masks, slots, children)

    # -- terminal fetches --------------------------------------------------

    def _record_commit(self) -> None:
        from ..metrics import fused_metrics

        fused_metrics.record_commit(dispatches=self.dispatches,
                                    levels=self.levels_staged, k=self.k,
                                    mode=self._mode)

    def finish(self) -> np.ndarray:
        self._execute()
        self._record_commit()
        if self._mode == "cpu":
            buf, self._buf_np = self._buf_np, None
            self._journal = []
            return buf
        self._journal = []
        return FusedLevelEngine.finish(self)

    def fetch_slots(self, slots: np.ndarray) -> np.ndarray:
        self._execute()
        self._record_commit()
        if self._mode == "cpu":
            out = self._buf_np[np.asarray(slots, dtype=np.int64)]
            self._buf_np = None
            self._journal = []
            return out
        self._journal = []
        return FusedLevelEngine.fetch_slots(self, slots)

    def peek_slots(self, slots: np.ndarray) -> np.ndarray:
        """Small D2H like :meth:`fetch_slots`, but the digest buffer stays
        RESIDENT — the terminal fetch of a delta epoch (the rows live on
        so later epochs can hole-splice them)."""
        self._execute()
        self._record_commit()
        self._journal = []
        if self._mode == "cpu":  # defensive: delta never degrades to cpu
            return self._buf_np[np.asarray(slots, dtype=np.int64)].copy()
        return self._take_slots(slots)


class SubtrieMeshEngine(SubtrieFusedEngine):
    """k-level fused commit over a device mesh: the staged buffers and
    the resident digest buffer are replicated, and each step's row block
    carries a batch-axis sharding constraint. The k-level packers keep a
    subtrie's rows contiguous (``_pack_window`` concatenates per sweep),
    so row-range shards approximate shard-by-subtrie — and parent
    composition always reads the REPLICATED digest buffer, so it never
    crosses a row shard regardless of placement (the all-gather XLA
    inserts after each step's scatter is the only communication)."""

    def __init__(self, mesh, min_tier: int = 1024, k: int | None = None,
                 warmup=None, injector=None, row_floor: int | None = None,
                 hole_floor: int | None = None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        live_snapshot = getattr(mesh, "live_snapshot", None)
        if live_snapshot is not None:
            mesh, _ = live_snapshot()
            if mesh is None:
                raise RuntimeError("HashMesh has no live devices")
        mult = mesh.devices.size
        self.mesh = mesh
        self._replicated = NamedSharding(mesh, P())
        super().__init__(min_tier=-(-min_tier // mult) * mult, k=k,
                         warmup=warmup, injector=injector,
                         row_floor=row_floor, hole_floor=hole_floor)

    def _device_put(self, arr: np.ndarray):
        return jax.device_put(_h2d(arr), self._replicated)

    def _batch_multiple(self) -> int:
        return self.mesh.devices.size

    def _mesh_arg(self):
        return self.mesh

    def _mesh_size(self) -> int:
        return self.mesh.devices.size


# -- hot-state plane, device half: the persistent digest arena ----------------


class ArenaFault(RuntimeError):
    """A delta-commit precondition or device fault under the hot-state
    arena — NEVER handled inside the engine (the journal only covers the
    current epoch, so the internal replay ladder cannot rebuild resident
    rows). The arena owner catches it, evicts, and re-runs the commit on
    the classic full-upload path (then per-level, then the CPU twin —
    the same ladder as before, entered one rung higher)."""


class DigestArena:
    """Epoch-tagged registry of digest rows resident in ONE persistent
    :class:`SubtrieFusedEngine` across blocks — the hot-state plane's
    device half (ISSUE 19; SonicDB S6's commitment-structure residency).

    The classic sparse finish builds a throwaway engine per commit: every
    block re-stages and re-uploads its whole dirty set and the buffer
    dies with ``finish()``. Under the arena the engine (and its device
    buffer) survives: slots are allocated monotonically across epochs,
    ``_slot_of`` maps node digest -> (slot, last_live_epoch), and a new
    epoch's templates hole-splice resident slots for unchanged sibling
    digests instead of treating the buffer as empty. The terminal fetch
    is :meth:`SubtrieFusedEngine.peek_slots` (this epoch's rows only),
    which keeps the buffer resident.

    Safety ladder (roots bit-identical on every rung):

    - ``begin_delta`` refuses unless the engine is still on the fused
      rung with a materialized buffer (:class:`ArenaFault`);
    - any device fault during a delta epoch re-raises out of the engine
      (``_delta`` external ladder) — :meth:`on_fault` evicts wholesale
      and the commit re-runs on the full-upload path;
    - rows idle for ``max_epoch_age`` epochs are retired at lookup, and
      the whole arena evicts when ``next_slot`` outgrows ``max_rows`` —
      so the buffer is bounded and the leak invariant
      ``leaked_rows() == 0`` (every allocated row is registered or
      retired) is checkable after every epoch (the chaos cache dimension
      asserts it post-storm).

    Single-writer: concurrent sparse finishes (speculation leg, the
    continuous producer) contend via :meth:`try_acquire`; the loser just
    takes the classic path for that block.
    """

    def __init__(self, max_rows: int = 1 << 20, max_epoch_age: int = 64):
        self.max_rows = max(1024, int(max_rows))
        self.max_epoch_age = max(1, int(max_epoch_age))
        self.engine: SubtrieFusedEngine | None = None
        self.epoch = 0
        self.next_slot = 1  # slot 0 = the engines' dummy slot
        self._slot_of: dict[bytes, tuple[int, int]] = {}
        self.retired = 0
        self._commit_lock = threading.Lock()
        # counters (mirrored into hotstate_* metrics by the committer)
        self.resident_hits = 0
        self.lookup_misses = 0
        self.evictions = 0
        self.faults = 0
        self.delta_epochs = 0
        self.full_epochs = 0
        self.contended = 0

    @classmethod
    def from_env(cls, env=None) -> "DigestArena":
        env = os.environ if env is None else env
        return cls(
            max_rows=int(env.get("RETH_TPU_HOT_ARENA_ROWS", "0")
                         or (1 << 20)),
            max_epoch_age=int(env.get("RETH_TPU_HOT_ARENA_EPOCHS", "0")
                              or 64))

    # -- single-writer seam ------------------------------------------------

    def try_acquire(self) -> bool:
        if self._commit_lock.acquire(blocking=False):
            return True
        self.contended += 1
        return False

    def release(self) -> None:
        self._commit_lock.release()

    # -- epoch lifecycle ---------------------------------------------------

    def begin_epoch(self, evict_storm: bool = False) -> bool:
        """Open a commit epoch; True = the arena is empty and this epoch
        must be a FULL upload (``engine.begin``), False = delta."""
        self.epoch += 1
        if evict_storm:
            self.evict("evict_storm")
        elif self.next_slot >= self.max_rows:
            self.evict("max_rows")
        fresh = self.next_slot == 1 or self.engine is None
        if fresh:
            self.full_epochs += 1
        else:
            self.delta_epochs += 1
        return fresh

    def evict(self, reason: str = "") -> None:
        """Wholesale eviction: drop the engine (and its device buffer)
        and forget every registered row — the next epoch full-uploads."""
        self.engine = None
        self._slot_of.clear()
        self.next_slot = 1
        self.retired = 0
        self.evictions += 1
        if reason:
            from .. import tracing

            tracing.fault_event("hotstate_arena_evict",
                                target="ops::fused_commit", reason=reason,
                                epoch=self.epoch)

    def invalidate(self, reason: str = "") -> None:
        """Tree-side wholesale invalidation (deep reorg / reorg storm):
        waits out any in-flight commit, then evicts — the same stand-down
        that parks the preserved trie and clears the node cache."""
        with self._commit_lock:
            self.evict(reason)

    def on_fault(self, err: BaseException) -> None:
        """A delta epoch died mid-flight (device fault, ArenaFault, any
        exception out of the committer's arena path): count it, evict —
        the caller re-runs the SAME commit on the full-upload path."""
        self.faults += 1
        from .. import tracing
        from ..metrics import fused_metrics

        fused_metrics.record_fallback()
        tracing.fault_event("hotstate_arena_fault",
                            target="ops::fused_commit",
                            error=f"{type(err).__name__}: {err}"[:200],
                            epoch=self.epoch)
        self.evict("fault")

    # -- row registry ------------------------------------------------------

    def alloc(self) -> int:
        slot = self.next_slot
        self.next_slot += 1
        return slot

    def lookup(self, digest: bytes) -> int:
        """Resident slot for ``digest`` (0 = not resident). Rows idle for
        ``max_epoch_age`` epochs retire here; hits refresh the tag."""
        ent = self._slot_of.get(digest)
        if ent is None:
            self.lookup_misses += 1
            return 0
        slot, last = ent
        if self.epoch - last > self.max_epoch_age:
            del self._slot_of[digest]
            self.retired += 1
            self.lookup_misses += 1
            return 0
        self._slot_of[digest] = (slot, self.epoch)
        self.resident_hits += 1
        return slot

    def note(self, digest: bytes, slot: int) -> None:
        """Register this epoch's freshly hashed row; a duplicate digest
        retires the superseded slot (the leak invariant's other half)."""
        old = self._slot_of.get(digest)
        if old is not None and old[0] != slot:
            self.retired += 1
        self._slot_of[digest] = (slot, self.epoch)

    def leaked_rows(self) -> int:
        """Allocated-but-unaccounted rows; 0 is an invariant the chaos
        cache dimension asserts after every storm."""
        return self.next_slot - 1 - len(self._slot_of) - self.retired

    # -- observability -----------------------------------------------------

    def snapshot(self) -> dict:
        return {
            "epoch": self.epoch, "resident_rows": len(self._slot_of),
            "next_slot": self.next_slot, "retired": self.retired,
            "leaked_rows": self.leaked_rows(),
            "resident_hits": self.resident_hits,
            "lookup_misses": self.lookup_misses,
            "evictions": self.evictions, "faults": self.faults,
            "delta_epochs": self.delta_epochs,
            "full_epochs": self.full_epochs, "contended": self.contended,
        }
