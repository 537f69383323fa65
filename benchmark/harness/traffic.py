"""The one traffic generator. A traffic mix is a data file of parameters
(``benchmark/workloads/<cell>.json``, key ``traffic``); this module turns the
parameters and ``--seed`` into the inputs of the window. Nothing here imports
the program.

Trie-build traffic (``"kind": "trie_jobs"``): a fixed list of operations, each
a list of jobs ``(keys (n, 32) uint8 sorted, values list[bytes])`` -- the
argument of ``TurboCommitter.commit_hashed_pipelined``. Every seed gets the
same job sizes (they come from the parameters, not from the seed) with other
prefixes, keys and values, so the work of a window does not depend on the
seed.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.mpt import EMPTY_ROOT

KECCAK_EMPTY = bytes.fromhex(
    "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470")


def make_rng(seed: int, stream: int = 0) -> np.random.Generator:
    """``--seed`` may exceed 2**31: SeedSequence takes any non-negative int."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _weighted_lengths(rng, weights: dict, n: int) -> np.ndarray:
    lens = np.array([int(k) for k in weights], dtype=np.int64)
    w = np.array([float(weights[k]) for k in weights])
    return lens[rng.choice(len(lens), size=n, p=w / w.sum())]


def _rows_to_list(rows: np.ndarray) -> list[bytes]:
    blob, w = rows.tobytes(), rows.shape[1]
    return [blob[i * w:(i + 1) * w] for i in range(rows.shape[0])]


def _uint_payload(rng, m: int, k: int) -> np.ndarray:
    """(m, k) big-endian integers of exactly k bytes (top byte non-zero; a
    one-byte integer is 0x80 or above, so its RLP keeps the length byte)."""
    body = rng.integers(0, 256, size=(m, k), dtype=np.uint8)
    if k:
        body[:, 0] = rng.integers(0x80 if k == 1 else 1, 256, size=m)
    return body


def account_values(rng, n: int, p: dict) -> list[bytes]:
    """RLP([nonce, balance, storage_root, code_hash]) for n accounts: nonce
    one byte, balance of a byte length drawn from ``balance_len_weights``
    (70-78 bytes in all), ``contract_share`` of them with a storage root and
    code hash of their own."""
    out: list = [None] * n
    blen = _weighted_lengths(rng, p["balance_len_weights"], n)
    contract = rng.random(n) < float(p["contract_share"])
    for k in np.unique(blen):
        idx = np.nonzero(blen == k)[0]
        m, k = len(idx), int(k)
        rows = np.empty((m, 70 + k), dtype=np.uint8)
        rows[:, 0], rows[:, 1] = 0xF8, 68 + k
        nonce = rng.integers(0, 0x80, size=m)
        rows[:, 2] = np.where(nonce == 0, 0x80, nonce)
        rows[:, 3] = 0x80 + k
        rows[:, 4:4 + k] = _uint_payload(rng, m, k)
        o = 4 + k
        rows[:, o], rows[:, o + 33] = 0xA0, 0xA0
        rows[:, o + 1:o + 33] = np.frombuffer(EMPTY_ROOT, dtype=np.uint8)
        rows[:, o + 34:o + 66] = np.frombuffer(KECCAK_EMPTY, dtype=np.uint8)
        c = contract[idx]
        rows[c, o + 1:o + 33] = rng.integers(0, 256, size=(int(c.sum()), 32))
        rows[c, o + 34:o + 66] = rng.integers(0, 256, size=(int(c.sum()), 32))
        for i, v in zip(idx.tolist(), _rows_to_list(rows)):
            out[i] = v
    return out


_VALUES = {"account": account_values}


def _jobs(rng, sizes: list[int], prefixes, values: dict) -> list:
    """One operation's jobs, made in bulk: distinct uniform 32-byte keys (in
    a real node they are keccak outputs, so uniform is the real distribution),
    job k's under ``prefixes[k]`` where given, ascending inside each job."""
    n = int(sum(sizes))
    rows = np.empty((n, 36), dtype=np.uint8)       # job number | key
    job = np.repeat(np.arange(len(sizes), dtype=">u4"), sizes)
    rows[:, :4] = job.view(np.uint8).reshape(n, 4)
    rows[:, 4:] = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    if prefixes is not None:
        rows[:, 4] = np.repeat(np.asarray(prefixes, dtype=np.uint8), sizes)
    flat = np.sort(rows.view("S36").ravel())
    if len(np.unique(flat)) != n:
        raise RuntimeError("key collision: draw another seed")
    keys = np.ascontiguousarray(flat.view(np.uint8).reshape(n, 36)[:, 4:])
    vals = _VALUES[values["kind"]](rng, n, values)
    ends = np.cumsum(sizes)
    return [(keys[lo:hi], vals[lo:hi])
            for lo, hi in zip((ends - sizes).tolist(), ends.tolist())]


def trie_job_ops(traffic: dict, seed: int) -> list[list]:
    """The operations of a ``trie_jobs`` mix: ``distinct_ops`` lists of jobs.

    ``prefix_subtries``: one operation is one account chunk as ``MerkleStage.
    _account_chunk`` gathers it -- whole two-nibble-prefix subtries of
    ``leaves_per_subtrie`` leaves, taken until the chunk holds ``chunk_leaves``
    or more -- under prefixes drawn from the seed."""
    rng = make_rng(seed, 1)
    shape, ops = traffic["jobs"], []
    n_ops = int(traffic["distinct_ops"])
    if shape["kind"] != "prefix_subtries":
        raise ValueError(f"unknown jobs kind {shape['kind']!r}")
    per_subtrie = int(shape["leaves_per_subtrie"])
    per_op = -(-int(shape["chunk_leaves"]) // per_subtrie)
    if n_ops * per_op > 256:
        raise ValueError("more subtries than two-nibble prefixes")
    prefixes = rng.permutation(256)[:n_ops * per_op]
    for o in range(n_ops):
        mine = np.sort(prefixes[o * per_op:(o + 1) * per_op])
        ops.append(_jobs(rng, [per_subtrie] * per_op, mine, traffic["values"]))
    return ops
