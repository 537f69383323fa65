"""The plain Merkle-Patricia-Trie build over MANY tries at once: the reference
of the storage cell, whose chunk holds thousands of tries, most of
one to three leaves.

The same Yellow-Paper construction as ``mpt.build_trie``, over its helpers
(leaf = RLP([hp(path, 1), value]), extension = RLP([hp(path, 0), ref]),
branch = RLP([c0..c15, value]), ref(node) = node if len(node) < 32 else
keccak256(node); a trie's root is hashed whatever its length). What differs
is the batching alone: every job of a batch is walked into ONE node table and
the table is hashed deepest depth first ACROSS the jobs, one
``keccak256_batch`` a depth of the batch and not of the trie (a batch of the
numpy Keccak costs milliseconds whatever it holds). Each job gets a
``TrieResult`` of its own, with its ``n_hashes`` / ``n_blocks``; held to
``build_trie`` job for job by ``benchmark/tests``. Imports nothing of the
program, and no JAX.
"""

from __future__ import annotations

import numpy as np

from .keccak import blocks_of, keccak256_batch
from .mpt import (EMPTY_ROOT, TrieResult, hex_prefix, rlp_list, rlp_str,
                  to_nibbles)

_LEAF, _EXT, _BRANCH = 0, 1, 2


def build_tries(jobs, start_depth: int = 0) -> list[TrieResult]:
    """``jobs``: [(keys (n, 32) uint8, unique, any order; values aligned,
    already RLP-encoded)]. One ``TrieResult`` a job, in the jobs' order; an
    empty job's root is the empty trie's."""
    results = [TrieResult(root=EMPTY_ROOT) for _ in jobs]
    sizes = np.array([len(values) for _, values in jobs], dtype=np.int64)
    if not sizes.sum():
        return results
    keys = np.concatenate([np.asarray(k, dtype=np.uint8).reshape(-1, 32)
                           for k, _ in jobs])
    job_of = np.repeat(np.arange(len(jobs)), sizes)
    # rows in (job, key) order: each job's leaves ascending, jobs in turn
    order = np.lexsort(tuple(keys.T[::-1]) + (job_of,))
    nib = to_nibbles(keys[order])
    rows = [r.tobytes() for r in nib]
    flat_vals = [v for _, values in jobs for v in values]
    vals = [flat_vals[i] for i in order]
    # ONE node table for the batch, filled by the walks: parents before
    # children, a job's root first among its nodes
    kind: list[int] = []
    at: list[int] = []       # nibble depth the node sits at
    first: list[int] = []    # a leaf row under the node (gives its path)
    span: list[int] = []     # ext: nibbles consumed; leaf: unused
    kids: list = []          # branch: [(nibble, node)], ext: child node
    owner: list[int] = []    # the job the node belongs to

    def walk(lo: int, hi: int, depth: int, job: int) -> int:
        me = len(kind)
        kind.append(_LEAF); at.append(depth); first.append(lo)
        span.append(0); kids.append(None); owner.append(job)
        if hi - lo == 1:
            return me
        a, b = rows[lo], rows[hi - 1]
        d = depth
        while a[d] == b[d]:
            d += 1
        if d > depth:
            kind[me] = _EXT
            span[me] = d - depth
            kids[me] = walk(lo, hi, d, job)
            return me
        kind[me] = _BRANCH
        col = nib[lo:hi, depth]
        cuts = lo + np.searchsorted(col, np.arange(17))
        mine = []
        for nb in range(16):
            if cuts[nb + 1] > cuts[nb]:
                mine.append((nb, walk(int(cuts[nb]), int(cuts[nb + 1]),
                                      depth + 1, job)))
        kids[me] = mine
        return me

    ends = np.cumsum(sizes)
    root_of = {}
    for job, (lo, hi) in enumerate(zip((ends - sizes).tolist(), ends.tolist())):
        if hi > lo:
            root_of[job] = walk(lo, hi, start_depth, job)
    roots = set(root_of.values())
    ref: list = [None] * len(kind)       # RLP reference as embedded in parent
    digest: list = [None] * len(kind)
    has_branch = [k == _BRANCH for k in kind]
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
                  if len(r) >= 32 or ids[k] in roots]
        digs = keccak256_batch([rlps[k] for k in hashed])
        for k, r in enumerate(rlps):
            ref[ids[k]] = r
        for j, k in enumerate(hashed):
            i = ids[k]
            digest[i] = digs[j].tobytes()
            if len(rlps[k]) >= 32:
                ref[i] = b"\xa0" + digest[i]
            res = results[owner[i]]
            res.n_hashes += 1
            res.n_blocks += blocks_of(len(rlps[k]))
    for job, node in root_of.items():
        results[job].root = digest[node]
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
        results[owner[i]].branches[path] = (sm, tm, hm, tuple(hashes))
    return results
