"""Plain Keccak-256 (Ethereum padding 0x01), batched over rows with numpy.

The benchmark's own: it imports nothing of the program. Written from the
Keccak reference (FIPS 202 section 3, with the original 0x01 domain byte in
place of SHA-3's 0x06): 1600-bit state as 25 little-endian 64-bit lanes,
rate 136 bytes, 24 rounds of theta, rho, pi, chi, iota.
"""

from __future__ import annotations

import numpy as np

RATE = 136

_RC = np.array([
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
], dtype=np.uint64)

# rho offsets, indexed [x][y]
_ROT = ((0, 36, 3, 41, 18), (1, 44, 10, 45, 2), (62, 6, 43, 15, 61),
        (28, 55, 25, 21, 56), (27, 20, 39, 8, 14))


def _rol(v: np.ndarray, r: int) -> np.ndarray:
    if r == 0:
        return v
    return (v << np.uint64(r)) | (v >> np.uint64(64 - r))


def keccak_f1600(a: list[list[np.ndarray]]) -> list[list[np.ndarray]]:
    """One permutation; ``a[x][y]`` are uint64 arrays of one length."""
    for rnd in range(24):
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rol(c[(x + 1) % 5], 1) for x in range(5)]
        a = [[a[x][y] ^ d[x] for y in range(5)] for x in range(5)]
        b = [[None] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rol(a[x][y], _ROT[x][y])
        a = [[b[x][y] ^ (~b[(x + 1) % 5][y] & b[(x + 2) % 5][y])
              for y in range(5)] for x in range(5)]
        a[0][0] = a[0][0] ^ _RC[rnd]
    return a


def _hash_padded(buf: np.ndarray, n_blocks: int) -> np.ndarray:
    """``buf``: (n, n_blocks * 136) uint8, already padded -> (n, 32) uint8."""
    n = buf.shape[0]
    lanes = buf.view("<u8").reshape(n, n_blocks, RATE // 8)
    zero = np.zeros(n, dtype=np.uint64)
    a = [[zero for _ in range(5)] for _ in range(5)]
    for blk in range(n_blocks):
        a = [[a[x][y] ^ lanes[:, blk, x + 5 * y] if x + 5 * y < RATE // 8
              else a[x][y] for y in range(5)] for x in range(5)]
        a = keccak_f1600(a)
    out = np.stack([a[0][0], a[1][0], a[2][0], a[3][0]], axis=1)
    return np.ascontiguousarray(out.astype("<u8")).view(np.uint8).reshape(n, 32)


def blocks_of(length: int) -> int:
    """Permutations Keccak-256 needs for a message of ``length`` bytes."""
    return length // RATE + 1


def keccak256_batch(msgs: list[bytes], rows_per_pass: int = 4096) -> np.ndarray:
    """Digests of ``msgs`` as an (n, 32) uint8 array, in order."""
    n = len(msgs)
    out = np.empty((n, 32), dtype=np.uint8)
    if n == 0:
        return out
    lens = np.fromiter((len(m) for m in msgs), dtype=np.int64, count=n)
    nblk = lens // RATE + 1
    for nb in np.unique(nblk):
        idx = np.nonzero(nblk == nb)[0]
        width = int(nb) * RATE
        for lo in range(0, len(idx), rows_per_pass):
            sel = idx[lo:lo + rows_per_pass]
            ls = lens[sel]
            flat = np.frombuffer(b"".join(msgs[i] for i in sel), dtype=np.uint8)
            buf = np.zeros((len(sel), width), dtype=np.uint8)
            starts = np.cumsum(ls) - ls
            rows = np.repeat(np.arange(len(sel)), ls)
            cols = np.arange(len(flat)) - np.repeat(starts, ls)
            buf[rows, cols] = flat
            buf[np.arange(len(sel)), ls] = 0x01
            buf[:, width - 1] |= 0x80
            out[sel] = _hash_padded(buf, int(nb))
    return out


def keccak256(msg: bytes) -> bytes:
    return keccak256_batch([msg])[0].tobytes()
