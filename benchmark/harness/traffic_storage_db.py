"""The generator of a storage-phase datadir (``"kind": "storage_datadir"``):
the hashed state that ``MerkleStage``'s storage phase reads, as the hashing
stages leave it on a fresh datadir, in blocks of whole storage tries.

The tries come in ``datadir["blocks"]`` blocks. Each block holds the
``harness/traffic_storage.py`` chunk's sizes (``power_law_sizes`` of the law
and ``chunk_leaves``) in the order that file gives operation b
(``_order(T, b)``): sizes and order from the PARAMETERS, the same on every
seed. The tries' hashed addresses are drawn from ``--seed`` all at once and
sorted, so the blocks follow one another in address order (block b holds
the tries b*T .. b*T + T - 1). Each block's slots and values are drawn from
``--seed`` and the block's number, so any block can be made again alone:
uniform 32-byte hashed slots, ascending within a trie, and values whose
``rlp_encode(encode_int(value))`` is ``traffic_storage.storage_values``'
law of widths (1 = 0x01-0x7f; k > 1 = k - 1 big-endian bytes, the first
non-zero, 0x80 or above where it stands alone).

Where the stage's chunks end is not where the blocks end: the stage adds
whole tries ``while leaves < chunk_leaves`` (``chunk_ends``). Nothing here
imports the program.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.traffic import _weighted_lengths, make_rng
from benchmark.harness.traffic_storage import _order, power_law_sizes

# streams of the seed's generator: the addresses, the accounts, and one a
# block (its slots and values)
_ADDR_STREAM = 0x41444452
_ACCOUNT_STREAM = 0x41434354
_BLOCK_STREAM = 0x424C4B00

EMPTY_ROOT = bytes.fromhex(
    "56e81f171bcc55a6ff8345e692c0f86e5b48e01b996cadc001622fb5e363b421")
ACCOUNT_BYTES = 79   # see account_rows


class Plan:
    """What a datadir holds, made from the parameters and the seed: every
    trie's size and hashed address, and the chunks the stage cuts."""

    def __init__(self, traffic: dict, seed: int):
        if traffic["kind"] != "storage_datadir":
            raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
        self.seed = seed
        shape = traffic["jobs"]
        self.chunk_leaves = int(shape["chunk_leaves"])
        self.weights = traffic["values"]["rlp_len_weights"]
        base = power_law_sizes(shape["size_law"], self.chunk_leaves)
        self.per_block = len(base)
        self.blocks = int(traffic["datadir"]["blocks"])
        self.sizes = np.concatenate([base[_order(len(base), b)]
                                     for b in range(self.blocks)])
        self.starts = np.concatenate([[0], np.cumsum(self.sizes)])
        n = len(self.sizes)
        raw = make_rng(seed, _ADDR_STREAM).integers(0, 256, size=(n, 32),
                                                    dtype=np.uint8)
        flat = np.sort(raw.view("V32").ravel())
        if n > 1 and (flat[1:] == flat[:-1]).any():
            raise RuntimeError("address collision: draw another seed")
        self.addrs = np.ascontiguousarray(flat.view(np.uint8).reshape(n, 32))
        self.chunks = chunk_ends(self.sizes, self.chunk_leaves)
        # the last chunk may be the phase's short tail: not an operation
        self.full_chunks = sum(int(self.starts[e] - self.starts[f])
                               >= self.chunk_leaves for f, e in self.chunks)

    @property
    def n_tries(self) -> int:
        return len(self.sizes)

    def addr(self, trie: int) -> bytes:
        return self.addrs[trie].tobytes()

    def block(self, b: int):
        """Block ``b``'s slots: ``(slots (n, 32), rlp (n, 33), widths (n,))``
        in (trie, slot) order, n the block's slots; a value's RLP is the
        last ``widths[i]`` bytes of ``rlp[i]``."""
        sizes = self.sizes[b * self.per_block:(b + 1) * self.per_block]
        rng = make_rng(self.seed, _BLOCK_STREAM + b)
        n = int(sizes.sum())
        rows = np.empty((n, 36), dtype=np.uint8)      # trie number | slot
        trie = np.repeat(np.arange(len(sizes), dtype=">u4"), sizes)
        rows[:, :4] = trie.view(np.uint8).reshape(n, 4)
        rows[:, 4:] = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
        flat = np.sort(rows.view("V36").ravel())
        if n > 1 and (flat[1:] == flat[:-1]).any():
            raise RuntimeError("slot collision: draw another seed")
        slots = np.ascontiguousarray(flat.view(np.uint8).reshape(n, 36)[:, 4:])
        widths = _weighted_lengths(rng, self.weights, n)
        if widths.min() < 1 or widths.max() > 33:
            raise ValueError("a storage value's RLP is 1 to 33 bytes")
        rlp = rng.integers(0, 256, size=(n, 33), dtype=np.uint8)
        col = np.arange(33)
        rlp[col[None, :] < (33 - widths)[:, None]] = 0
        one = widths == 1
        rlp[one, 32] = rng.integers(1, 0x80, size=int(one.sum()))
        for k in np.unique(widths[~one]).tolist():
            at = np.nonzero(widths == k)[0]
            rlp[at, 33 - k] = 0x80 + k - 1
            rlp[at, 34 - k] = rng.integers(0x80 if k == 2 else 1, 256,
                                           size=len(at))
        return slots, rlp, widths

    def entries(self, b: int):
        """Block ``b`` as ``HashedStorages`` holds it: ``(keys (n, 32),
        dups (n, 64))``, each dup the hashed slot and the value's 32
        big-endian bytes, in ascending (key, dup) order."""
        slots, rlp, widths = self.block(b)
        values = rlp[:, 1:].copy()
        inside = (widths > 1) & (widths < 33)    # the RLP's length byte
        values[inside, 32 - widths[inside]] = 0
        lo = b * self.per_block
        keys = np.repeat(self.addrs[lo:lo + self.per_block],
                         self.sizes[lo:lo + self.per_block], axis=0)
        return keys, np.concatenate([slots, values], axis=1)

    def jobs(self, first: int, end: int) -> list:
        """Tries ``first .. end - 1`` as the plain reference takes them:
        ``[(slots (n, 32), [RLP of each value])]``."""
        out = []
        for b in range(first // self.per_block, (end - 1) // self.per_block + 1):
            slots, rlp, widths = self.block(b)
            base = self.starts[b * self.per_block]
            for t in range(max(first, b * self.per_block),
                           min(end, (b + 1) * self.per_block)):
                lo = int(self.starts[t] - base)
                hi = int(self.starts[t + 1] - base)
                out.append((slots[lo:hi], [r[33 - w:].tobytes() for r, w in
                                           zip(rlp[lo:hi], widths[lo:hi])]))
        return out


def chunk_ends(sizes: np.ndarray, chunk_leaves: int) -> list[tuple[int, int]]:
    """The stage's chunks as ``(first trie, end)``: from the first trie not
    yet committed, whole tries while the chunk holds fewer than
    ``chunk_leaves`` slots (``MerkleStage._storage_chunk``)."""
    out, first, leaves = [], 0, 0
    for t, s in enumerate(sizes.tolist()):
        leaves += s
        if leaves >= chunk_leaves:
            out.append((first, t + 1))
            first, leaves = t + 1, 0
    if first < len(sizes):
        out.append((first, len(sizes)))
    return out


def account_rows(plan: Plan) -> np.ndarray:
    """One ``HashedAccounts`` value a trie, as ``AccountHashingStage`` leaves
    it: RLP([nonce, balance, storage_root, code_hash]) with nonce 1, a
    balance of 9 bytes, the empty trie's root (the storage phase has not run
    yet) and a code hash from the seed: 79 bytes, ``(n, 79)``."""
    n = plan.n_tries
    rng = make_rng(plan.seed, _ACCOUNT_STREAM)
    rows = np.empty((n, ACCOUNT_BYTES), dtype=np.uint8)
    rows[:, 0:2] = (0xF8, 77)                    # a list of 77 bytes
    rows[:, 2] = 0x01                            # nonce 1
    rows[:, 3] = 0x89                            # 9 bytes of balance
    rows[:, 4:13] = rng.integers(0, 256, size=(n, 9), dtype=np.uint8)
    rows[:, 4] = rng.integers(1, 256, size=n)
    rows[:, 13] = 0xA0
    rows[:, 14:46] = np.frombuffer(EMPTY_ROOT, np.uint8)
    rows[:, 46] = 0xA0
    rows[:, 47:79] = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    return rows
