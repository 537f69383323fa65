"""Plain Merkle-Patricia-Trie build: the reference the rebuild cells are
compared with, and the source of the roofline's work counts.

Follows the Yellow Paper (appendix D) directly: leaf = RLP([hp(path, 1),
value]), extension = RLP([hp(path, 0), ref]), branch = RLP([c0..c15, value]),
ref(node) = node if len(node) < 32 else keccak256(node). Imports nothing of
the program. Nodes are laid out by a recursive walk over the sorted keys and
hashed deepest depth first, one numpy keccak batch per depth.

``start_depth`` builds the subtrie below that nibble depth (all keys share
the prefix); its root is the hash of the node that sits there. Branch
records are what reth's ``BranchNodeCompact`` holds: ``state_mask`` (children
present), ``tree_mask`` (child subtree holds a branch node), ``hash_mask``
(child referenced by hash) and those hashes in nibble order, keyed by the
branch's path below ``start_depth`` (one byte per nibble).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from .keccak import blocks_of, keccak256_batch

EMPTY_ROOT = bytes.fromhex(
    "56e81f171bcc55a6ff8345e692c0f86e5b48e01b996cadc001622fb5e363b421")

_LEAF, _EXT, _BRANCH = 0, 1, 2


def rlp_str(b: bytes) -> bytes:
    if len(b) == 1 and b[0] < 0x80:
        return b
    return _header(len(b), 0x80) + b


def _header(n: int, base: int) -> bytes:
    if n < 56:
        return bytes([base + n])
    nb = n.to_bytes((n.bit_length() + 7) // 8, "big")
    return bytes([base + 55 + len(nb)]) + nb


def rlp_list(payload: bytes) -> bytes:
    return _header(len(payload), 0xC0) + payload


def hex_prefix(nibbles: bytes, leaf: bool) -> bytes:
    flag = 2 if leaf else 0
    if len(nibbles) % 2:
        head = bytes([((flag + 1) << 4) | nibbles[0]])
        rest = nibbles[1:]
    else:
        head = bytes([flag << 4])
        rest = nibbles
    return head + bytes((rest[i] << 4) | rest[i + 1]
                        for i in range(0, len(rest), 2))


@dataclass
class TrieResult:
    root: bytes
    branches: dict = field(default_factory=dict)  # path -> (sm, tm, hm, hashes)
    n_hashes: int = 0
    n_blocks: int = 0


def to_nibbles(keys: np.ndarray) -> np.ndarray:
    nib = np.empty((keys.shape[0], keys.shape[1] * 2), dtype=np.uint8)
    nib[:, 0::2] = keys >> 4
    nib[:, 1::2] = keys & 0xF
    return nib


def build_trie(keys: np.ndarray, values: list[bytes],
               start_depth: int = 0) -> TrieResult:
    """``keys``: (n, 32) uint8, unique, any order; ``values`` aligned,
    already RLP-encoded leaf values."""
    n = len(values)
    if n == 0:
        return TrieResult(root=EMPTY_ROOT)
    order = np.lexsort(keys.T[::-1])
    nib = to_nibbles(keys[order])
    rows = [r.tobytes() for r in nib]
    vals = [values[i] for i in order]
    # node table, filled by the walk: parents before children
    kind: list[int] = []
    at: list[int] = []       # nibble depth the node sits at
    first: list[int] = []    # a leaf row under the node (gives its path)
    span: list[int] = []     # ext: nibbles consumed; leaf: unused
    kids: list = []          # branch: [(nibble, node)], ext: child node

    def walk(lo: int, hi: int, depth: int) -> int:
        me = len(kind)
        kind.append(_LEAF); at.append(depth); first.append(lo)
        span.append(0); kids.append(None)
        if hi - lo == 1:
            return me
        a, b = rows[lo], rows[hi - 1]
        d = depth
        while a[d] == b[d]:
            d += 1
        if d > depth:
            kind[me] = _EXT
            span[me] = d - depth
            kids[me] = walk(lo, hi, d)
            return me
        kind[me] = _BRANCH
        col = nib[lo:hi, depth]
        cuts = lo + np.searchsorted(col, np.arange(17))
        mine = []
        for nb in range(16):
            if cuts[nb + 1] > cuts[nb]:
                mine.append((nb, walk(int(cuts[nb]), int(cuts[nb + 1]),
                                      depth + 1)))
        kids[me] = mine
        return me

    walk(0, n, start_depth)
    ref: list = [None] * len(kind)       # RLP reference as embedded in parent
    digest: list = [None] * len(kind)
    has_branch = [k == _BRANCH for k in kind]
    res = TrieResult(root=b"")
    by_depth: dict[int, list[int]] = {}
    for i, d in enumerate(at):
        by_depth.setdefault(d, []).append(i)
    for d in sorted(by_depth, reverse=True):
        ids = by_depth[d]
        rlps = []
        for i in ids:
            row = rows[first[i]]
            if kind[i] == _LEAF:
                rlp = rlp_list(rlp_str(hex_prefix(row[d:], True))
                               + rlp_str(vals[first[i]]))
            elif kind[i] == _EXT:
                child = kids[i]
                has_branch[i] = has_branch[child]
                rlp = rlp_list(rlp_str(hex_prefix(row[d:d + span[i]], False))
                               + ref[child])
            else:
                slots = [b"\x80"] * 16
                for nb, c in kids[i]:
                    slots[nb] = ref[c]
                rlp = rlp_list(b"".join(slots) + b"\x80")
            rlps.append(rlp)
        hashed = [k for k, r in enumerate(rlps)
                  if len(r) >= 32 or ids[k] == 0]
        digs = keccak256_batch([rlps[k] for k in hashed])
        for k, r in enumerate(rlps):
            ref[ids[k]] = r
        for j, k in enumerate(hashed):
            i = ids[k]
            digest[i] = digs[j].tobytes()
            if len(rlps[k]) >= 32:
                ref[i] = b"\xa0" + digest[i]
            res.n_hashes += 1
            res.n_blocks += blocks_of(len(rlps[k]))
    res.root = digest[0]
    for i, k in enumerate(kind):
        if k != _BRANCH:
            continue
        sm = tm = hm = 0
        hashes = []
        for nb, c in kids[i]:
            sm |= 1 << nb
            if has_branch[c]:
                tm |= 1 << nb
            if digest[c] is not None and len(ref[c]) == 33:
                hm |= 1 << nb
                hashes.append(digest[c])
        path = rows[first[i]][start_depth:at[i]]
        res.branches[path] = (sm, tm, hm, tuple(hashes))
    return res
