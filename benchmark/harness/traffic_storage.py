"""The generator of storage-chunk traffic (``"kind": "storage_chunks"``): the
storage phase of a clean ``MerkleStage`` rebuild. One operation is one storage
chunk as ``MerkleStage._storage_chunk`` gathers it: WHOLE storage tries taken
in hashed-address order until the chunk holds ``chunk_leaves`` slots or more,
each trie a job ``(keys (n, 32) uint8 ascending, values list[bytes])`` -- the
argument of ``TurboCommitter.commit_hashed_pipelined`` at ``start_depth`` 0.
Nothing here imports the program.

The sizes come from the PARAMETERS, never from ``--seed``: with F the
distribution of P(s) ~ s**-alpha on 1 .. max, a chunk holds T tries of sizes
F**-1((i + 1/2) / T), i = 0 .. T - 1, T the least count whose sizes sum to
``chunk_leaves`` or more; their order in operation o is a permutation keyed by
(T, o) (the stage takes tries in hashed-address order, which no size follows).
So groups, windows and tiers are the same for every seed. From ``--seed``:
each trie's distinct uniform 32-byte keys (keccak outputs in a real node) and
each value, the RLP of a non-zero integer with no leading zero byte, of an
encoded length drawn from ``rlp_len_weights`` (1 = one byte 0x01-0x7f).
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.traffic import (_rows_to_list, _weighted_lengths,
                                       make_rng)

# the stream of the order's permutation: a constant of the generator, so the
# order follows from (T, o) alone
_ORDER_STREAM = 0x53544F52


def _cdf(law: dict) -> np.ndarray:
    """F on 1 .. max: the law's cumulative distribution."""
    if law["form"] != "power":
        raise ValueError(f"unknown size law {law['form']!r}")
    s = np.arange(1, int(law["max"]) + 1, dtype=np.float64)
    cdf = np.cumsum(s ** -float(law["alpha"]))
    cdf /= cdf[-1]
    return cdf


def quantile_sizes(law: dict, t: int, cdf: np.ndarray | None = None) -> np.ndarray:
    """``t`` sizes, ascending: the quantiles F**-1((i + 1/2) / t) of the
    law's distribution (``cdf``: ``_cdf(law)``, where the caller has it)."""
    if cdf is None:
        cdf = _cdf(law)
    return (1 + np.searchsorted(cdf, (np.arange(t) + 0.5) / t)).astype(np.int64)


def power_law_sizes(law: dict, chunk_leaves: int) -> np.ndarray:
    """The trie sizes of one chunk, ascending: ``quantile_sizes`` of the
    least count whose sizes sum to ``chunk_leaves`` or more. (Counted from
    the top, the k-th largest of t + 1 quantiles is no smaller than the k-th
    largest of t, and there is one more: the total rises with the count, so
    a bisection finds the least.) The law keeps its whole tail (``max`` is
    the largest trie there is, not a cut): what bounds a chunk's largest trie
    is the sampling itself, the quantile 1 - 1 / (2 T)."""
    cdf = _cdf(law)                         # max terms: once, not a probe
    lo, hi = 1, int(chunk_leaves)           # hi tries of one slot or more
    while lo < hi:
        mid = (lo + hi) // 2
        if int(quantile_sizes(law, mid, cdf).sum()) >= chunk_leaves:
            hi = mid
        else:
            lo = mid + 1
    return quantile_sizes(law, lo, cdf)


def _order(n_tries: int, op: int) -> np.ndarray:
    """The order operation ``op`` takes a chunk's ``n_tries`` sizes in: a
    fixed permutation keyed by (T, op), never by ``--seed``."""
    return make_rng(n_tries * 1_000_003 + op, _ORDER_STREAM).permutation(
        n_tries)


def chunk_sizes(traffic: dict, op: int) -> np.ndarray:
    """Operation ``op``'s trie sizes in the order the stage would take them."""
    shape = traffic["jobs"]
    sizes = power_law_sizes(shape["size_law"], int(shape["chunk_leaves"]))
    return sizes[_order(len(sizes), op)]


def storage_values(rng, n: int, weights: dict) -> list[bytes]:
    """n storage leaf values: RLP of a trimmed non-zero u256 (what
    ``rlp_encode(encode_int(value))`` gives the stage), of an encoded length
    drawn from ``weights``: 1 is the integer itself (0x01-0x7f), k > 1 a
    length byte 0x80 + k - 1 and k - 1 big-endian bytes, the first non-zero
    (0x80 or above where it stands alone)."""
    drawn = _weighted_lengths(rng, weights, n)
    if drawn.min() < 1 or drawn.max() > 33:
        raise ValueError("a storage value's RLP is 1 to 33 bytes")
    out: list = [None] * n
    for k in np.unique(drawn):
        idx = np.nonzero(drawn == k)[0]
        m, k = len(idx), int(k)
        rows = rng.integers(0, 256, size=(m, k), dtype=np.uint8)
        if k == 1:
            rows[:, 0] = rng.integers(1, 0x80, size=m)
        else:
            rows[:, 0] = 0x80 + k - 1
            rows[:, 1] = rng.integers(0x80 if k == 2 else 1, 256, size=m)
        for i, v in zip(idx.tolist(), _rows_to_list(rows)):
            out[i] = v
    return out


def _jobs(rng, sizes: np.ndarray, weights: dict) -> list:
    """One chunk's jobs, made in bulk: every key of the chunk drawn at once,
    sorted by (job, key), so each job's keys are distinct and ascending."""
    n = int(sizes.sum())
    rows = np.empty((n, 36), dtype=np.uint8)       # job number | key
    job = np.repeat(np.arange(len(sizes), dtype=">u4"), sizes)
    rows[:, :4] = job.view(np.uint8).reshape(n, 4)
    rows[:, 4:] = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    flat = np.sort(rows.view("S36").ravel())
    if n > 1 and (flat[1:] == flat[:-1]).any():
        raise RuntimeError("key collision: draw another seed")
    keys = np.ascontiguousarray(flat.view(np.uint8).reshape(n, 36)[:, 4:])
    vals = storage_values(rng, n, weights)
    ends = np.cumsum(sizes)
    return [(keys[lo:hi], vals[lo:hi])
            for lo, hi in zip((ends - sizes).tolist(), ends.tolist())]


def storage_chunk_ops(traffic: dict, seed: int) -> list[list]:
    """The operations of a ``storage_chunks`` mix: ``distinct_ops`` chunks of
    whole storage tries, sizes and order from the parameters, keys and values
    from the seed."""
    if traffic["kind"] != "storage_chunks":
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    rng = make_rng(seed, 2)
    shape, weights = traffic["jobs"], traffic["values"]["rlp_len_weights"]
    sizes = power_law_sizes(shape["size_law"], int(shape["chunk_leaves"]))
    return [_jobs(rng, sizes[_order(len(sizes), o)], weights)
            for o in range(int(traffic["distinct_ops"]))]
