"""What the chip has to do for a trie build, counted from the trie alone.

``trie_work`` gives, for a list of (sorted keys, values) jobs, the number of
node hashes and of Keccak-f[1600] permutations that ANY implementation of the
Merkle-Patricia-Trie build needs: one hash for each node whose RLP is 32 bytes
or longer, ``len(rlp) // 136 + 1`` permutations for each. It never looks at
what the program dispatched (padded rows, tiers). It walks the longest common
prefixes of neighbouring keys (the compacted trie's branch nodes are the LCP
intervals), so it costs a second or so for 500,000 leaves where the full
reference (``reference/mpt.py``, which it is tested against) costs ten.

``keccak_work`` turns the counts into bytes and 32-bit operations, and
``least_seconds`` into the least time a chip of the peaks table could take.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.keccak import RATE
from benchmark.reference.mpt import to_nibbles

# 32-bit logical operations for one Keccak-f[1600] permutation, with each
# 64-bit lane held as a pair of u32 (what a 32-bit vector unit must do):
#   theta: C[x] = 4 xors x 5 columns (20 lane-xors = 40 ops); D[x] = C[x-1] ^
#          rol(C[x+1], 1): a 64-bit rotate of a u32 pair by r (r not 0, 32)
#          is 4 shifts + 2 ors = 6 ops, plus the 2-op xor, x 5 = 40; A ^= D
#          over 25 lanes = 50.                                   -> 130
#   rho:   24 lanes rotate by an offset that is neither 0 nor 32 -> 144
#   pi:    a renaming of lanes                                   ->   0
#   chi:   A[x] ^= ~A[x+1] & A[x+2]: not, and, xor on 25 lanes   -> 150
#   iota:  one lane xor a constant                               ->   2
# 426 a round, 24 rounds = 10,224; absorbing a 136-byte block xors 17 lanes
# into the state = 34. Independent of how any program schedules them.
OPS_PER_ROUND = 130 + 144 + 0 + 150 + 2
OPS_PER_PERMUTATION = 24 * OPS_PER_ROUND + 34


def _enc_len(payload_len: np.ndarray) -> np.ndarray:
    """Length of an RLP string/list of ``payload_len`` bytes (header added;
    payloads under 65,536 bytes)."""
    return payload_len + 1 + (payload_len >= 56) + (payload_len >= 256)


def trie_work(jobs, start_depth: int = 0) -> tuple[int, int]:
    """``(n_hashes, n_blocks)`` over ``jobs`` = [(keys (n, 32) uint8 sorted
    ascending and unique, values list[bytes])], each built below
    ``start_depth``. Raises where a node would be shorter than 32 bytes (it
    is then embedded, not hashed, and only the full reference can count)."""
    jobs = [j for j in jobs if len(j[1])]
    if not jobs:
        return 0, 0
    keys = np.concatenate([j[0] for j in jobs])
    n = len(keys)
    nib = to_nibbles(keys)
    vlen = np.fromiter((len(v) for j in jobs for v in j[1]), dtype=np.int64,
                       count=n)
    vfirst = np.fromiter((v[0] for j in jobs for v in j[1]), dtype=np.int64,
                         count=n)
    floor = start_depth - 1
    # lcp[i]: common nibbles of rows i-1 and i; `floor` at both ends and
    # across a job boundary, which closes every open branch there
    lcp = np.full(n + 1, floor, dtype=np.int64)
    if n > 1:
        neq = nib[:-1] != nib[1:]
        lcp[1:n] = neq.argmax(axis=1)
        rows = np.arange(n - 1)
        rising = neq.any(axis=1) & (nib[rows, lcp[1:n]] < nib[rows + 1, lcp[1:n]])
        inside = np.ones(n - 1, dtype=bool)
        inside[np.cumsum([len(j[1]) for j in jobs])[:-1] - 1] = False
        if not rising[inside].all():
            raise ValueError("keys of a job are not sorted and unique")
        if (lcp[1:n][inside] < start_depth).any():
            raise ValueError("keys of a job do not share the start_depth prefix")
        lcp[1:n][~inside] = floor
    # leaves: below the deeper of the two neighbouring branch points
    attach = np.maximum(lcp[:-1], lcp[1:]) + 1
    rem = 64 - attach                       # nibbles left in the leaf's path
    hp = rem // 2 + 1                       # hex-prefix bytes
    path_enc = np.where(hp == 1, 1, hp + 1)
    val_enc = np.where((vlen == 1) & (vfirst < 0x80), 1, _enc_len(vlen))
    leaf_len = _enc_len(path_enc + val_enc)
    if (leaf_len < 32).any():
        raise NotImplementedError("a leaf under 32 bytes is embedded")
    n_hashes = n
    n_blocks = int((leaf_len // RATE + 1).sum())
    # branches: one for each LCP interval; a stack of open (depth, children)
    stack_d: list[int] = []
    stack_c: list[int] = []
    for d in lcp[1:].tolist():              # ends with `floor`: closes all
        while stack_d and stack_d[-1] > d:
            dx = stack_d.pop()
            c = stack_c.pop()
            below = stack_d[-1] if stack_d else floor
            parent = below if below > d else d
            payload = 32 * c + 17           # c hash refs, 16 - c empties, value
            blen = payload + (3 if payload > 255 else 2)
            n_hashes += 1
            n_blocks += blen // RATE + 1
            ext = dx - parent - 1
            if ext > 0:                     # an extension node above it
                ehp = ext // 2 + 1
                elen = (1 if ehp == 1 else ehp + 1) + 33 + 1
                n_hashes += 1
                n_blocks += elen // RATE + 1
        if d == floor:
            continue
        if stack_d and stack_d[-1] == d:
            stack_c[-1] += 1
        else:
            stack_d.append(d)
            stack_c.append(2)
    return n_hashes, n_blocks


def keccak_work(n_hashes: int, n_blocks: int) -> dict:
    """Bytes that must cross HBM (each block read once, each digest written
    once) and 32-bit operations, for that many hashes and permutations."""
    return {"n_hashes": n_hashes, "n_blocks": n_blocks,
            "bytes": RATE * n_blocks + 32 * n_hashes,
            "ops": OPS_PER_PERMUTATION * n_blocks}


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """Least time a chip with ``peaks`` could take, and which bound it is."""
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    t_ops = work["ops"] / peaks["u32_ops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
