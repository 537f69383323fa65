"""Batched Keccak-256 as a JAX/XLA kernel — the TPU hashing data plane.

This replaces the reference's CPU keccak hot loops (`asm-keccak` sha3-asm,
rayon chunks in AccountHashingStage — reference
crates/stages/stages/src/stages/hashing_account.rs:29-32 — and the
sparse-trie `update_subtrie_hashes` keccak loop — reference
crates/trie/sparse/src/arena/mod.rs:2500-2548) with a single batched,
shape-stable device program.

TPU-first design notes:
- 64-bit lanes are emulated as (hi, lo) uint32 pairs: the TPU VPU is a
  32-bit vector ISA; all keccak ops are XOR/AND/NOT/rot so the emulation
  is exact and cheap. Rotation amounts are compile-time constants, so each
  lane's rotate lowers to static shifts.
- Lane-major layout ``(25, N)``: each lane is a contiguous vector over the
  batch; every op is elementwise over N and vectorises onto the 8x128 VPU.
  No gathers, no dynamic shapes.
- 24 rounds via ``lax.fori_loop`` (round constants indexed dynamically) —
  traced once, compiled once per (num_blocks, N-tier).
- Variable-length messages are bucketed by 136-byte rate-block count and
  padded to power-of-two batch tiers, so the number of distinct compiled
  programs is O(#block-buckets x #tiers), not O(#shapes).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..primitives.keccak import RC, ROT, pad_batch, bucketed_hash

# Round constants as (24, 2) uint32: [:, 0] = lo, [:, 1] = hi.
_RC_WORDS = np.array([[rc & 0xFFFFFFFF, rc >> 32] for rc in RC], dtype=np.uint32)


def _rotl_pair(lo, hi, r: int):
    """Rotate a 64-bit lane (as uint32 lo/hi) left by static r."""
    r %= 64
    if r == 0:
        return lo, hi
    if r == 32:
        return hi, lo
    if r > 32:
        lo, hi = hi, lo
        r -= 32
    rr = 32 - r
    new_lo = (lo << r) | (hi >> rr)
    new_hi = (hi << r) | (lo >> rr)
    return new_lo, new_hi


def keccak_f1600_jax(lo, hi):
    """keccak-f[1600] over a batch. ``lo``/``hi``: (25, N) uint32 arrays."""
    rc = jnp.asarray(_RC_WORDS)

    def round_fn(i, state):
        slo, shi = state
        alo = [slo[j] for j in range(25)]
        ahi = [shi[j] for j in range(25)]
        # theta
        clo = [alo[x] ^ alo[x + 5] ^ alo[x + 10] ^ alo[x + 15] ^ alo[x + 20] for x in range(5)]
        chi_ = [ahi[x] ^ ahi[x + 5] ^ ahi[x + 10] ^ ahi[x + 15] ^ ahi[x + 20] for x in range(5)]
        for x in range(5):
            rl, rh = _rotl_pair(clo[(x + 1) % 5], chi_[(x + 1) % 5], 1)
            dlo = clo[(x - 1) % 5] ^ rl
            dhi = chi_[(x - 1) % 5] ^ rh
            for y in range(5):
                alo[x + 5 * y] = alo[x + 5 * y] ^ dlo
                ahi[x + 5 * y] = ahi[x + 5 * y] ^ dhi
        # rho + pi
        blo = [None] * 25
        bhi = [None] * 25
        for x in range(5):
            for y in range(5):
                rl, rh = _rotl_pair(alo[x + 5 * y], ahi[x + 5 * y], ROT[x][y])
                dst = y + 5 * ((2 * x + 3 * y) % 5)
                blo[dst] = rl
                bhi[dst] = rh
        # chi
        for x in range(5):
            for y in range(5):
                i1 = (x + 1) % 5 + 5 * y
                i2 = (x + 2) % 5 + 5 * y
                alo[x + 5 * y] = blo[x + 5 * y] ^ (~blo[i1] & blo[i2])
                ahi[x + 5 * y] = bhi[x + 5 * y] ^ (~bhi[i1] & bhi[i2])
        # iota
        alo[0] = alo[0] ^ rc[i, 0]
        ahi[0] = ahi[0] ^ rc[i, 1]
        return jnp.stack(alo), jnp.stack(ahi)

    return lax.fori_loop(0, 24, round_fn, (lo, hi))


def _squeeze256(lo, hi):
    """First 4 lanes -> (N, 8) uint32 digest words [lo0,hi0,lo1,hi1,...]."""
    return jnp.stack([lo[0], hi[0], lo[1], hi[1], lo[2], hi[2], lo[3], hi[3]], axis=1)


def absorb_single_block(words):
    """Single-rate-block keccak-256: (N, 34) uint32 words → (N, 8) digests.

    The canonical one-block absorb — the mesh/sharding layer and the graft
    entry build on this exact function so the lane layout lives in one place.
    """
    n = words.shape[0]
    w = words.reshape(n, 17, 2).transpose(1, 2, 0)  # (17, 2, N)
    lo = jnp.zeros((25, n), dtype=jnp.uint32).at[:17].set(w[:, 0, :])
    hi = jnp.zeros((25, n), dtype=jnp.uint32).at[:17].set(w[:, 1, :])
    lo, hi = keccak_f1600_jax(lo, hi)
    return _squeeze256(lo, hi)


@partial(jax.jit, static_argnums=1)
def keccak256_jax_words(words, num_blocks: int):
    """Keccak-256 over pre-padded messages, all with the same block count.

    ``words``: (N, num_blocks*34) uint32 — little-endian 32-bit words of the
    padded message (as produced by ``primitives.keccak.pad_batch`` viewed as
    '<u4'); even indices are lane-lo, odd are lane-hi.
    Returns (N, 8) uint32 — the 32-byte digests as little-endian words.

    The absorb loop is a ``fori_loop`` (not a Python unroll), so trace size
    is constant in ``num_blocks``; XLA still compiles one program per
    distinct (num_blocks, N) shape — the batching front-end bounds both.
    """
    n = words.shape[0]
    w = words.reshape(n, num_blocks, 17, 2).transpose(1, 2, 3, 0)  # (B, 17, 2, N)

    def absorb(blk, state):
        lo, hi = state
        blkw = lax.dynamic_index_in_dim(w, blk, axis=0, keepdims=False)
        lo = lo.at[:17].set(lo[:17] ^ blkw[:, 0, :])
        hi = hi.at[:17].set(hi[:17] ^ blkw[:, 1, :])
        return keccak_f1600_jax(lo, hi)

    zero = jnp.zeros((25, n), dtype=jnp.uint32)
    lo, hi = lax.fori_loop(0, num_blocks, absorb, (zero, zero))
    return _squeeze256(lo, hi)


def masked_absorb_words(words, max_blocks: int, counts):
    """Non-jitted masked-absorb core shared by the batch front-end and the
    fused level committer (``ops.fused_commit``): messages of differing block
    counts in one batch, each padded at its OWN final rate block and
    zero-extended to ``max_blocks``. Blocks at index >= ``counts[i]`` leave
    message i's state untouched. Returns (N, 8) uint32 digests."""
    n = words.shape[0]
    w = words.reshape(n, max_blocks, 17, 2).transpose(1, 2, 3, 0)

    def absorb(blk, state):
        lo, hi = state
        blkw = lax.dynamic_index_in_dim(w, blk, axis=0, keepdims=False)
        nlo = lo.at[:17].set(lo[:17] ^ blkw[:, 0, :])
        nhi = hi.at[:17].set(hi[:17] ^ blkw[:, 1, :])
        nlo, nhi = keccak_f1600_jax(nlo, nhi)
        live = (blk < counts)[None, :]  # (1, N) broadcast over lanes
        return jnp.where(live, nlo, lo), jnp.where(live, nhi, hi)

    zero = jnp.zeros((25, n), dtype=jnp.uint32)
    lo, hi = lax.fori_loop(0, max_blocks, absorb, (zero, zero))
    return _squeeze256(lo, hi)


@partial(jax.jit, static_argnums=1)
def keccak256_jax_words_masked(words, max_blocks: int, counts=None):
    """Jitted wrapper over :func:`masked_absorb_words` (one program per
    (max_blocks, N) shape tier — the batching front-end bounds both)."""
    return masked_absorb_words(words, max_blocks, counts)


def _next_tier(n: int, min_tier: int = 8, max_tier: int | None = None) -> int:
    """Pow2 tier ladder from ``min_tier``; ``max_tier`` clamps growth to a
    declared ceiling (the warm-up shape menu, ops/warmup.py) — callers must
    chunk batches above it rather than minting an unbounded new tier."""
    t = min_tier
    while t < n:
        t *= 2
    if max_tier is not None and t > max_tier:
        return max_tier
    return t


# one shared sentinel bucket for messages above the declared block-tier
# ceiling: they hash on the CPU twin instead of minting a fresh program
_CPU_BUCKET = 1 << 30


def _to_u32(words: np.ndarray, batch_tier: int) -> np.ndarray:
    """(n, W) uint64 padded words → (batch_tier, 2W) uint32, zero row-padded."""
    n, w = words.shape
    if batch_tier != n:
        words = np.vstack([words, np.zeros((batch_tier - n, w), dtype=np.uint64)])
    return np.ascontiguousarray(words).view("<u4").reshape(batch_tier, 2 * w)


class KeccakDevice:
    """Host-side batching front-end for the device keccak kernel.

    This is the host↔device marshalling layer — the analogue of the
    reference's rayon worker-chunk boundary (the "NCCL boundary" of this
    single-chip design, see SURVEY.md §5). Callers hand over lists of
    byte-strings; it buckets by block count, pads batches to power-of-two
    tiers (shape-stable → bounded number of XLA compilations), runs the
    kernel, and returns digests in order.
    """

    # Block counts <= this get their own exactly-sized program; larger
    # messages (contract bytecode etc.) share masked programs at
    # power-of-two block tiers so compilation count stays bounded.
    MAX_EXACT_BLOCKS = 8
    # Declared menu ceilings (ops/warmup.py default_menu): batches above
    # MAX_BATCH_TIER are chunked; messages above MAX_BLOCK_TIER rate blocks
    # hash on the CPU twin — either way no request can mint a program shape
    # outside the warm-up menu (and trigger a fresh compile) mid-commit.
    MAX_BATCH_TIER = 16384
    MAX_BLOCK_TIER = 32

    def __init__(self, min_tier: int = 8, block_tier: int | None = None,
                 warmup=None, max_batch_tier: int | None = None,
                 max_block_tier: int | None = None):
        """``block_tier``: if set, ALL messages up to that many rate blocks
        share one masked program per batch tier (compile-count-minimal mode
        for workloads with a known size ceiling, e.g. trie nodes <= 4
        blocks); larger messages still fall back to pow2 tiers above it.
        ``warmup``: an ``ops/warmup.py`` WarmupManager — buckets whose
        (program, block_tier, batch_tier) shape is not warm yet hash on the
        CPU twin instead of compiling inside a live dispatch.
        """
        self.min_tier = min_tier
        self.block_tier = block_tier
        self.warmup = warmup
        if max_block_tier is None:
            max_block_tier = self.MAX_BLOCK_TIER
        self.max_block_tier = max_block_tier
        if max_batch_tier is None:
            max_batch_tier = self.MAX_BATCH_TIER
        # keep the ceiling ON the pow2 ladder from min_tier, so the chunk
        # cap can never round up past it inside _hash_bucket
        cap = min_tier
        while cap * 2 <= max_batch_tier:
            cap *= 2
        self.max_batch_tier = cap

    def hash_batch(self, msgs: list[bytes]) -> list[bytes]:
        cap = self.max_batch_tier
        if len(msgs) > cap:
            # one huge request never mints a tier above the menu ceiling:
            # dispatch ceiling-sized chunks (order preserved)
            out: list[bytes] = []
            for lo in range(0, len(msgs), cap):
                out.extend(bucketed_hash(msgs[lo:lo + cap],
                                         self._hash_bucket,
                                         bucket_key=self._bucket_key))
            return out
        return bucketed_hash(msgs, self._hash_bucket, bucket_key=self._bucket_key)

    def _bucket_key(self, nb: int) -> int:
        """Exact program for small block counts; shared pow2 tier above —
        clamped at the menu ceiling (over-ceiling messages share the CPU
        bucket)."""
        if nb > self.max_block_tier:
            return _CPU_BUCKET
        if self.block_tier is not None:
            if nb <= self.block_tier:
                return self.block_tier
            return _next_tier(nb, 2 * self.block_tier)
        if nb <= self.MAX_EXACT_BLOCKS:
            return nb
        return _next_tier(nb, 2 * self.MAX_EXACT_BLOCKS)

    @staticmethod
    def _cpu_bucket(sub: list[bytes], counts: np.ndarray) -> np.ndarray:
        """CPU-twin bucket: same row-viewable digest contract as the device
        paths (rows ``.tobytes()`` == the 32-byte digest)."""
        from ..primitives.keccak import keccak256_words_masked_np

        words = pad_batch(sub, counts)
        return keccak256_words_masked_np(words, int(counts.max()), counts)

    def _hash_bucket(self, sub: list[bytes], key: int, counts: np.ndarray) -> np.ndarray:
        """Hash one bucket; returns (n, 8) uint32 digests. Every dispatch
        reports its (program kind, block count, batch tier) shape and wall
        to the compile tracker: the FIRST call of a shape is its XLA
        compile, so compile storms show up split from steady-state
        dispatch instead of masquerading as slow hashing."""
        import os
        import time as _time

        from ..metrics import compile_tracker, keccak_route_metrics

        n = len(sub)
        batch_tier = _next_tier(n, self.min_tier, self.max_batch_tier)
        if key == _CPU_BUCKET:
            # over the declared block-tier ceiling: CPU twin, no new program
            keccak_route_metrics.record_cpu_bucket("over_ceiling")
            return self._cpu_bucket(sub, counts)
        if self.warmup is not None:
            kind = ("keccak.exact"
                    if self.block_tier is None and key <= self.MAX_EXACT_BLOCKS
                    else "keccak.masked")
            if not self.warmup.route_bucket(kind, key, batch_tier):
                # shape not warm yet (degraded-mode serving): hash this
                # bucket on the CPU twin; it promotes to the device the
                # moment the warm-up manager marks the shape WARM
                keccak_route_metrics.record_cpu_bucket("unwarmed")
                return self._cpu_bucket(sub, counts)
        if key == 1 and os.environ.get("RETH_TPU_PALLAS"):
            # hand-written fused kernel for the dominant single-block
            # bucket. The user asked for it: it runs or it raises — the XLA
            # program below never answers in its place. Off the TPU (CPU
            # tests and rehearsals) the same kernel runs interpreted.
            from .keccak_pallas import keccak256_pallas_words

            w32 = _to_u32(pad_batch(sub, 1), batch_tier)
            t0 = _time.perf_counter()
            out = np.asarray(keccak256_pallas_words(
                w32, interpret=jax.default_backend() != "tpu"))[:n]
            compile_tracker.record("keccak.pallas", (1, batch_tier),
                                   _time.perf_counter() - t0)
            return out
        t0 = _time.perf_counter()
        if self.block_tier is None and key <= self.MAX_EXACT_BLOCKS:
            kind = "keccak.exact"
            w32 = _to_u32(pad_batch(sub, key), batch_tier)
            digests = keccak256_jax_words(jnp.asarray(w32), key)
        else:
            kind = "keccak.masked"
            words = pad_batch(sub, counts, pad_to_blocks=key)
            w32 = _to_u32(words, batch_tier)
            cnt = np.zeros((batch_tier,), dtype=np.int32)
            cnt[:n] = counts
            digests = keccak256_jax_words_masked(jnp.asarray(w32), key, counts=jnp.asarray(cnt))
        out = np.asarray(digests)[:n]  # D2H sync point: wall is honest here
        compile_tracker.record(kind, (key, batch_tier),
                               _time.perf_counter() - t0)
        return out

    def hash_one(self, msg: bytes) -> bytes:
        return self.hash_batch([msg])[0]


def keccak256_batch_jax(msgs: list[bytes]) -> list[bytes]:
    """One-shot convenience wrapper around a default ``KeccakDevice``."""
    return _DEFAULT_DEVICE.hash_batch(msgs)


_DEFAULT_DEVICE = KeccakDevice()
