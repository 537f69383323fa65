"""Proof-revealed sparse MPT + the cross-block preserved trie cache.

Reference analogue: crates/trie/sparse (`SparseStateTrie`,
`ArenaParallelSparseTrie`, `SerialSparseTrie`) and chain-state's
`PreservedSparseTrie` (crates/chain-state/src/preserved_sparse_trie.rs:15).
The reference reveals multiproof nodes into an in-memory partial trie at
the live tip, applies the payload's state updates to it, re-hashes only
dirty subtrees (rayon keccak, arena/mod.rs:2500-2548), and preserves the
anchored trie across consecutive payloads so each block only reveals the
paths it newly touches.

TPU-first redesign: the structure walk (reveal/update/delete — pointer
work) stays on host, but re-hashing is LEVEL-BATCHED exactly like the
committer — dirty nodes are grouped by depth and each depth hashes in one
batched keccak call (device-dispatchable), instead of the reference's
per-node sequential keccak inside a rayon worker. Clean subtrees keep
their cached refs, so cross-block reuse skips both structure and hashing
work for untouched paths.

Blinded nodes: paths the proofs never revealed. Reading through or
collapsing into one raises ``BlindedNodeError`` carrying the nibble path,
so a caller holding a proof source (the engine strategy, stateless
executors) can reveal exactly that path and retry — the reference's
reveal-on-demand loop.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

from .. import tracing
from ..primitives.keccak import RATE, keccak256, keccak256_batch_np
from ..primitives.rlp import rlp_encode as _rlp_encode
from ..primitives.nibbles import (
    Nibbles,
    common_prefix_len,
    decode_path,
    unpack_nibbles,
)
from ..primitives.rlp import rlp_decode
from ..primitives.types import EMPTY_ROOT_HASH
from .node import (
    EMPTY_STRING_RLP,
    branch_node_rlp,
    encode_hash_ref,
    extension_node_rlp,
    leaf_node_rlp,
)


class BlindedNodeError(Exception):
    """Traversal hit an unrevealed subtree; ``path`` names the blinded
    node so the caller can fetch a proof for it and retry."""

    def __init__(self, path: Nibbles, msg: str = ""):
        super().__init__(msg or f"blinded node at {path.hex()}")
        self.path = path
        # hashed address of the storage trie the blind was hit in (set by
        # state-level callers); None = the account trie
        self.owner: bytes | None = None


# -- node objects -------------------------------------------------------------
# Kept as small Python objects (host pointer work); only hashing batches.


class _Blind:
    __slots__ = ("hash",)

    def __init__(self, h: bytes):
        self.hash = h


class _Leaf:
    __slots__ = ("path", "value", "_ref")

    def __init__(self, path: Nibbles, value: bytes):
        self.path = path
        self.value = value
        self._ref = None  # cached RLP ref while clean


class _Ext:
    __slots__ = ("path", "child", "_ref")

    def __init__(self, path: Nibbles, child):
        self.path = path
        self.child = child
        self._ref = None


class _Branch:
    __slots__ = ("children", "value", "_ref")

    def __init__(self, children=None, value: bytes = b""):
        self.children = children if children is not None else [None] * 16
        self.value = value
        self._ref = None


def _decode_node(rlp: bytes, by_hash: dict[bytes, bytes],
                 stamp: bool = False):
    """Materialize one RLP node, descending into children found in
    ``by_hash`` (proof set); absent hashed children stay blinded.

    ``stamp`` (the hot-state plane, trie/hot_cache.py): revealed nodes'
    hashes are already known — the proof addressed them BY hash — so
    their ``_ref`` can be stamped at decode time. A revealed-but-never-
    mutated node then stays clean through the next commit instead of
    being re-encoded, re-staged, and re-hashed; mutation clears refs
    along its path exactly as before, so roots are bit-identical."""
    items = rlp_decode(rlp)
    if len(items) == 2:
        prefix, payload = items
        nib, is_leaf = decode_path(prefix)
        if is_leaf:
            return _Leaf(nib, payload)
        # extension: payload is a child ref (raw RLP list when inline)
        return _Ext(nib, _decode_ref(payload, by_hash, stamp))
    assert len(items) == 17, "malformed MPT node"
    br = _Branch(value=items[16])
    for i in range(16):
        if items[i] != b"":
            br.children[i] = _decode_ref(items[i], by_hash, stamp)
    return br


def _decode_ref(ref, by_hash: dict[bytes, bytes], stamp: bool = False):
    """A child as it appears inside a parent's decoded RLP: a 32-byte hash
    string, or an inline (already decoded) list for <32-byte nodes."""
    if isinstance(ref, list):  # inline child: re-encode to reuse _decode_node
        from ..primitives.rlp import rlp_encode

        inline = rlp_encode(ref)
        node = _decode_node(inline, by_hash, stamp)
        if stamp:
            node._ref = inline  # inline ref IS the node's RLP
        return node
    assert isinstance(ref, bytes)
    if len(ref) == 32:
        sub = by_hash.get(ref)
        if sub is not None:
            node = _decode_node(sub, by_hash, stamp)
            if stamp:
                node._ref = encode_hash_ref(ref)
            return node
        return _Blind(ref)
    # short raw value used as a ref (shouldn't occur in secure tries)
    raise ValueError("unexpected short child reference")


class SparseTrie:
    """One partially-revealed secure MPT (account trie or one storage trie)."""

    def __init__(self, root_hash: bytes = EMPTY_ROOT_HASH):
        self.root_hash = root_hash
        self.root = None if root_hash == EMPTY_ROOT_HASH else _Blind(root_hash)
        self.updates = 0  # mutations since last root()
        # hot-state plane (trie/hot_cache.py): when set, reveals stamp
        # the (known) node hashes as clean refs so unmutated revealed
        # nodes never re-stage; ``stamped`` counts them since the last
        # commit (the delta-upload-fraction denominator)
        self.stamp_reveals = False
        self.stamped = 0

    # -- reveal ---------------------------------------------------------------

    def reveal(self, proof_nodes: list[bytes]) -> None:
        """Reveal the subtrees reachable from the current root through the
        given proof nodes (spine nodes of one or more proofs)."""
        if not proof_nodes:
            return
        stamp = self.stamp_reveals
        by_hash = {keccak256(n): n for n in proof_nodes}
        if self.root is None or isinstance(self.root, _Blind):
            top = by_hash.get(self.root_hash)
            if top is None:
                return  # proof for a different root
            self.root = _decode_node(top, by_hash, stamp)
            if stamp:
                self.root._ref = encode_hash_ref(self.root_hash)
                self.stamped += len(by_hash)
            return
        self.root = self._merge(self.root, by_hash, stamp)
        if stamp:
            self.stamped += len(by_hash)

    def _merge(self, node, by_hash, stamp: bool = False):
        if isinstance(node, _Blind):
            rlp = by_hash.get(node.hash)
            if rlp is None:
                return node
            revealed = _decode_node(rlp, by_hash, stamp)
            if stamp:
                revealed._ref = encode_hash_ref(node.hash)
            return revealed
        if isinstance(node, _Ext):
            node.child = self._merge(node.child, by_hash, stamp)
        elif isinstance(node, _Branch):
            for i, c in enumerate(node.children):
                if c is not None:
                    node.children[i] = self._merge(c, by_hash, stamp)
        return node

    # -- hot-state plane hooks (trie/hot_cache.py) ----------------------------

    def node_at(self, path: bytes):
        """The node sitting after consuming exactly ``path``'s nibbles
        (the key-nibble positions ``BlindedNodeError.path`` uses); None
        when the walk diverges, ends early, or an earlier blind blocks
        it."""
        node, depth = self.root, 0
        while node is not None:
            if depth == len(path):
                return node
            if isinstance(node, (_Blind, _Leaf)):
                return None
            if isinstance(node, _Ext):
                np_ = node.path
                if (depth + len(np_) > len(path)
                        or path[depth:depth + len(np_)] != np_):
                    return None
                depth += len(np_)
                node = node.child
                continue
            node = node.children[path[depth]]
            depth += 1
        return None

    def blind_hash_at(self, path: bytes) -> bytes | None:
        """Hash of the blinded node at ``path`` (key-nibble position), or
        None when the position isn't a blind — the hot cache's lookup key
        validator."""
        node = self.node_at(path)
        return node.hash if isinstance(node, _Blind) else None

    def reveal_at(self, path: bytes, rlp: bytes) -> bool:
        """Reveal ONE blinded node in place from a cached RLP (hot-state
        cache hit). Validates ``keccak(rlp)`` against the blind's hash —
        a poisoned/stale entry can never splice in — and stamps the
        revealed node's ref (its hash is known by construction).
        Children decode to blinds; deeper cache hits reveal them in
        turn. Returns False when the position isn't a matching blind."""
        node, depth, parent, link = self.root, 0, None, None
        while node is not None:
            if depth == len(path):
                break
            if isinstance(node, (_Blind, _Leaf)):
                return False
            if isinstance(node, _Ext):
                np_ = node.path
                if (depth + len(np_) > len(path)
                        or path[depth:depth + len(np_)] != np_):
                    return False
                depth += len(np_)
                parent, link = node, None
                node = node.child
                continue
            parent, link = node, path[depth]
            node = node.children[path[depth]]
            depth += 1
        if not isinstance(node, _Blind) or keccak256(rlp) != node.hash:
            return False
        revealed = _decode_node(rlp, {}, stamp=True)
        revealed._ref = encode_hash_ref(node.hash)
        self.stamped += 1
        if parent is None:
            self.root = revealed
        elif isinstance(parent, _Ext):
            parent.child = revealed
        else:
            parent.children[link] = revealed
        return True

    def harvest_spine(self, key: bytes, out: list, seen: set) -> None:
        """Collect ``(path, rlp)`` for every >=32 B node along ``key``'s
        path into ``out`` (hot-cache population). Paths are key-nibble
        positions (the same coordinates ``BlindedNodeError`` reports).
        Child refs must be clean where visited — the walk stops at the
        first node whose children aren't (a freshly revealed subtree
        under a clean parent before any commit), which is safe: harvest
        runs post-commit or post-reveal-with-stamping, where that never
        happens on the key path."""
        nib = unpack_nibbles(key) if len(key) == 32 else key
        node, depth = self.root, 0
        while node is not None and not isinstance(node, _Blind):
            path = bytes(nib[:depth])
            if path not in seen:
                if not _children_ready(node):
                    return
                rlp = _encode_rlp(node)
                if len(rlp) >= 32:
                    seen.add(path)
                    out.append((path, rlp))
            if isinstance(node, _Leaf):
                return
            if isinstance(node, _Ext):
                if nib[depth:depth + len(node.path)] != node.path:
                    return
                depth += len(node.path)
                node = node.child
            else:
                node = node.children[nib[depth]]
                depth += 1

    # -- read -----------------------------------------------------------------

    def get(self, key: bytes):
        """Value for a 32-byte hashed key; None when provably absent."""
        nib = unpack_nibbles(key)
        node, depth = self.root, 0
        while True:
            if node is None:
                return None
            if isinstance(node, _Blind):
                raise BlindedNodeError(nib[:depth])
            if isinstance(node, _Leaf):
                return node.value if node.path == nib[depth:] else None
            if isinstance(node, _Ext):
                if nib[depth:depth + len(node.path)] != node.path:
                    return None
                depth += len(node.path)
                node = node.child
                continue
            node = node.children[nib[depth]]
            depth += 1

    # -- write ----------------------------------------------------------------

    def update(self, key: bytes, value: bytes) -> None:
        nib = unpack_nibbles(key)
        self.root = self._insert(self.root, nib, 0, value)
        self.updates += 1

    def delete(self, key: bytes) -> None:
        nib = unpack_nibbles(key)
        self.root = self._remove(self.root, nib, 0)
        self.updates += 1

    def _insert(self, node, nib: Nibbles, depth: int, value: bytes):
        if node is None:
            return _Leaf(nib[depth:], value)
        if isinstance(node, _Blind):
            raise BlindedNodeError(nib[:depth])
        node._ref = None  # path dirties
        if isinstance(node, _Leaf):
            rem = nib[depth:]
            if node.path == rem:
                node.value = value
                return node
            return self._split(node.path, node, rem, _Leaf(b"", value))
        if isinstance(node, _Ext):
            rem = nib[depth:]
            common = _common_len(node.path, rem)
            if common == len(node.path):
                node.child = self._insert(node.child, nib, depth + common, value)
                return node
            return self._split(node.path, node, rem, _Leaf(b"", value),
                               common)
        idx = nib[depth]
        node.children[idx] = self._insert(node.children[idx], nib, depth + 1,
                                          value)
        return node

    @staticmethod
    def _strip(node, by: int):
        """Drop ``by`` leading nibbles from a leaf/ext's remaining path."""
        node.path = node.path[by:]
        return node

    def _split(self, old_path: Nibbles, old_node, new_path: Nibbles, new_leaf,
               common: int | None = None):
        """Diverge two paths into (optional ext →) branch."""
        if common is None:
            common = _common_len(old_path, new_path)
        branch = _Branch()
        old = self._strip(old_node, common + 1) if len(old_path) > common \
            else old_node
        if len(old_path) == common:
            # old path exhausted at the branch: only valid for leaf (value
            # in branch slot 16) — extensions always have a next nibble
            assert isinstance(old_node, _Leaf)
            branch.value = old_node.value
        else:
            child = old
            if isinstance(child, _Ext) and len(child.path) == 0:
                child = child.child  # ext with empty path collapses
            branch.children[old_path[common]] = child
        if len(new_path) == common:
            branch.value = new_leaf.value
        else:
            new_leaf.path = new_path[common + 1:]
            branch.children[new_path[common]] = new_leaf
        if common:
            return _Ext(old_path[:common], branch)
        return branch

    def _remove(self, node, nib: Nibbles, depth: int):
        if node is None:
            return None
        if isinstance(node, _Blind):
            raise BlindedNodeError(nib[:depth])
        node._ref = None
        if isinstance(node, _Leaf):
            return None if node.path == nib[depth:] else node
        if isinstance(node, _Ext):
            if nib[depth:depth + len(node.path)] != node.path:
                return node
            node.child = self._remove(node.child, nib, depth + len(node.path))
            if node.child is None:
                return None
            return self._collapse_ext(node, nib, depth)
        idx = nib[depth]
        node.children[idx] = self._remove(node.children[idx], nib, depth + 1)
        return self._collapse_branch(node, nib, depth)

    def _collapse_ext(self, ext: _Ext, nib: Nibbles, depth: int):
        child = ext.child
        if isinstance(child, _Ext):
            child._ref = None
            child.path = ext.path + child.path
            return child
        if isinstance(child, _Leaf):
            child._ref = None
            child.path = ext.path + child.path
            return child
        return ext

    def _collapse_branch(self, br: _Branch, nib: Nibbles, depth: int):
        live = [(i, c) for i, c in enumerate(br.children) if c is not None]
        if br.value:
            if live:
                return br
            return _Leaf(b"", br.value)
        if len(live) > 1:
            return br
        if not live:
            return None
        idx, child = live[0]
        # merging needs the child's structure: a blinded survivor must be
        # revealed first (the engine strategy reveals and retries)
        if isinstance(child, _Blind):
            raise BlindedNodeError(nib[:depth] + bytes([idx]),
                                   "collapse into blinded sibling")
        child._ref = None
        if isinstance(child, _Leaf):
            child.path = bytes([idx]) + child.path
            return child
        if isinstance(child, _Ext):
            child.path = bytes([idx]) + child.path
            return child
        return _Ext(bytes([idx]), child)

    # -- hashing --------------------------------------------------------------

    def root_hash_compute(self, hasher=keccak256_batch_np) -> bytes:
        """Level-batched rehash of dirty subtrees: one batched keccak call
        per depth level (the device dispatch seam), cached refs for clean
        subtrees (the cross-block reuse)."""
        if self.root is None:
            self.root_hash = EMPTY_ROOT_HASH
            self.updates = 0
            return self.root_hash
        if isinstance(self.root, _Blind):
            self.root_hash = self.root.hash
            return self.root_hash
        # collect dirty nodes by depth (a node is dirty iff _ref is None)
        levels: dict[int, list] = {}

        def collect(node, depth):
            if isinstance(node, _Blind) or node is None:
                return
            if getattr(node, "_ref", None) is not None:
                return  # clean subtree: ref cached
            levels.setdefault(depth, []).append(node)
            if isinstance(node, _Ext):
                collect(node.child, depth + 1)
            elif isinstance(node, _Branch):
                for c in node.children:
                    collect(c, depth + 1)

        collect(self.root, 0)
        for depth in sorted(levels, reverse=True):
            rlps, nodes = [], []
            for node in levels[depth]:
                rlp = self._encode(node)
                if len(rlp) < 32:
                    node._ref = rlp  # inline ref
                else:
                    rlps.append(rlp)
                    nodes.append(node)
            if rlps:
                digests = hasher(rlps)
                for node, d in zip(nodes, digests):
                    node._ref = encode_hash_ref(bytes(d))
        top = self._encode(self.root)
        self.root_hash = keccak256(top)
        self.updates = 0
        return self.root_hash

    def _encode(self, node) -> bytes:
        return _encode_rlp(node)

    def _child_ref(self, child) -> bytes:
        return _child_ref_of(child)

    def spine(self, key: bytes) -> list[bytes]:
        """The RLP nodes along ``key``'s path (a single-key proof). Valid
        after ``root_hash_compute`` (refs must be clean); used by witness
        generation and the collapse-retry reveal loop."""
        out = []
        nib = unpack_nibbles(key)
        node, depth = self.root, 0
        while node is not None and not isinstance(node, _Blind):
            rlp = self._encode(node)
            if len(rlp) >= 32:
                out.append(rlp)
            if isinstance(node, _Leaf):
                break
            if isinstance(node, _Ext):
                if nib[depth:depth + len(node.path)] != node.path:
                    break
                depth += len(node.path)
                node = node.child
            else:
                node = node.children[nib[depth]]
                depth += 1
        return out

    # -- introspection --------------------------------------------------------

    def revealed_count(self) -> int:
        n = 0
        stack = [self.root]
        while stack:
            node = stack.pop()
            if node is None or isinstance(node, _Blind):
                continue
            n += 1
            if isinstance(node, _Ext):
                stack.append(node.child)
            elif isinstance(node, _Branch):
                stack.extend(node.children)
        return n


_common_len = common_prefix_len


def _encode_rlp(node) -> bytes:
    """RLP-encode one node from its children's (clean) refs. Module-level
    so the parallel commit's encode pool can fan it out without touching
    any trie instance state."""
    if isinstance(node, _Leaf):
        return leaf_node_rlp(node.path, node.value)
    if isinstance(node, _Ext):
        return extension_node_rlp(node.path, _child_ref_of(node.child))
    assert isinstance(node, _Branch)
    refs = [_child_ref_of(c) if c is not None else EMPTY_STRING_RLP
            for c in node.children]
    return branch_node_rlp(refs, node.value)


def _child_ref_of(child) -> bytes:
    if isinstance(child, _Blind):
        return encode_hash_ref(child.hash)
    assert child._ref is not None, "child not hashed (collect order bug)"
    return child._ref


def _children_ready(node) -> bool:
    """True when every child carries a usable ref (blind or cached) — the
    precondition for ``_encode_rlp`` outside a commit walk."""
    if isinstance(node, _Leaf):
        return True
    if isinstance(node, _Ext):
        c = node.child
        return isinstance(c, _Blind) or c._ref is not None
    return all(c is None or isinstance(c, _Blind) or c._ref is not None
               for c in node.children)


def _child_ref_template(child, slot_of: dict[int, int],
                        resident=None) -> tuple[bytes, int]:
    """Child reference as template bytes + digest source slot (0 = no
    hole): clean/blinded/inline children contribute literal host-known
    bytes, dirty hashed children a 33-byte placeholder whose digest the
    device splices from the resident buffer. Dirty-inline children were
    finalized when their own (deeper) level was walked, so their
    ``_ref`` already holds complete hole-free bytes — the same invariant
    as ``TrieCommitter._child_ref_template``.

    ``resident`` (hot-state arena): maps a known child HASH to a digest
    slot still resident from a PRIOR epoch (0 = not resident). A hit
    turns the literal ref into a hole spliced from the persistent buffer
    — the spliced bytes are that slot's digest, which IS the hash, so
    the composed RLP is bit-identical either way."""
    from .node import HASH_REF_HOLE

    if isinstance(child, _Blind):
        if resident is not None:
            s = resident(child.hash)
            if s:
                return HASH_REF_HOLE, s
        return encode_hash_ref(child.hash), 0
    if child._ref is not None:
        r = child._ref
        if resident is not None and len(r) == 33 and r[0] == 0xA0:
            s = resident(r[1:])
            if s:
                return HASH_REF_HOLE, s
        return r, 0
    return HASH_REF_HOLE, slot_of[id(child)]


def _node_template_sparse(node, slot_of: dict[int, int], resident=None):
    """(RLP template with zero-filled holes, [(byte_off, src_slot)]) for
    one dirty sparse node — built with the SAME RLP builders the serial
    encode uses (``HASH_REF_HOLE`` is a well-formed 33-byte ref), so the
    spliced bytes are identical to ``_encode_rlp``'s output."""
    if isinstance(node, _Leaf):
        return leaf_node_rlp(node.path, node.value), []
    if isinstance(node, _Ext):
        ref, src = _child_ref_template(node.child, slot_of, resident)
        rlp = extension_node_rlp(node.path, ref)
        # the child ref is the payload's tail; +1 skips its 0xa0 marker
        return rlp, ([(len(rlp) - 32, src)] if src else [])
    assert isinstance(node, _Branch)
    refs: list[bytes] = []
    srcs: list[int] = []
    for c in node.children:
        if c is None:
            refs.append(EMPTY_STRING_RLP)
            srcs.append(0)
        else:
            r, s = _child_ref_template(c, slot_of, resident)
            refs.append(r)
            srcs.append(s)
    rlp = branch_node_rlp(refs, node.value)
    # refs sit back-to-back after the list header; the value is the tail
    val_len = len(_rlp_encode(node.value))
    off = len(rlp) - val_len - sum(len(r) for r in refs)
    holes: list[tuple[int, int]] = []
    for r, s in zip(refs, srcs):
        if s:
            holes.append((off + 1, s))
        off += len(r)
    return rlp, holes


# -- parallel cross-trie commit ----------------------------------------------


class InjectedSparseAbort(RuntimeError):
    """Fault injection killed a parallel sparse commit at a dispatch
    boundary (RETH_TPU_FAULT_SPARSE_ABORT) — drills the engine's
    ``state_root_fallback`` path without hardware."""


class SparseFaultInjector:
    """Fault policies for the parallel sparse-commit path, in the style of
    ``ops/supervisor.py``'s FaultInjector / the service injector.

    ``abort_at``: the Nth packed hash dispatch of the process raises
    :class:`InjectedSparseAbort` (one-shot) — a mid-commit abort; the
    engine must fall back to the incremental committer.
    ``proof_wedge_every``: every Nth sharded proof fetch raises — drills
    the proof-worker failure path (worker error -> SparseRootError ->
    fallback).

    Env form (:meth:`from_env`): ``RETH_TPU_FAULT_SPARSE_ABORT`` /
    ``RETH_TPU_FAULT_SPARSE_PROOF_WEDGE``.
    """

    def __init__(self, abort_at: int = 0, proof_wedge_every: int = 0):
        self.abort_at = abort_at
        self.proof_wedge_every = proof_wedge_every
        self.dispatches = 0
        self.proof_fetches = 0
        self.aborts = 0
        self.wedges = 0
        self._lock = threading.Lock()

    @classmethod
    def from_env(cls, env=None) -> "SparseFaultInjector | None":
        env = os.environ if env is None else env
        abort_at = int(env.get("RETH_TPU_FAULT_SPARSE_ABORT", "0") or 0)
        wedge = int(env.get("RETH_TPU_FAULT_SPARSE_PROOF_WEDGE", "0") or 0)
        if not (abort_at or wedge):
            return None
        return cls(abort_at=abort_at, proof_wedge_every=wedge)

    def on_dispatch(self) -> None:
        with self._lock:
            self.dispatches += 1
            n = self.dispatches
        if self.abort_at and n == self.abort_at:
            with self._lock:
                self.aborts += 1
            from .. import tracing

            tracing.fault_event("RETH_TPU_FAULT_SPARSE_ABORT",
                                target="trie::sparse", dispatch=n)
            raise InjectedSparseAbort(
                f"injected sparse-commit abort on dispatch #{n} "
                f"(RETH_TPU_FAULT_SPARSE_ABORT={self.abort_at})")

    def on_proof_fetch(self) -> None:
        with self._lock:
            self.proof_fetches += 1
            n = self.proof_fetches
        if self.proof_wedge_every and n % self.proof_wedge_every == 0:
            with self._lock:
                self.wedges += 1
            from .. import tracing

            tracing.fault_event("RETH_TPU_FAULT_SPARSE_PROOF_WEDGE",
                                target="trie::sparse", fetch=n)
            raise RuntimeError(
                f"injected sparse proof wedge on fetch #{n} "
                f"(RETH_TPU_FAULT_SPARSE_PROOF_WEDGE="
                f"{self.proof_wedge_every})")


def sparse_worker_count(workers: int | None = None) -> int:
    """Resolve the shared ``--sparse-workers`` knob: explicit value >
    ``RETH_TPU_SPARSE_WORKERS`` > cpu-derived default. 1 disables the
    pools (packed dispatch stays on)."""
    if workers is None or workers <= 0:
        workers = int(os.environ.get("RETH_TPU_SPARSE_WORKERS", "0") or 0)
    if workers <= 0:
        workers = max(2, min(4, os.cpu_count() or 1))
    return max(1, workers)


class ParallelSparseCommitter:
    """Parallel commit of MANY dirty sparse tries — the live-tip finish
    path's analogue of ``turbo._pack_window``.

    Two axes of parallelism over the serial per-trie
    ``root_hash_compute`` loop:

    (a) **Cross-trie level packing**: dirty nodes from EVERY trie (all
        dirty storage tries + the account trie) are collected into one
        global per-depth schedule and each depth issues ONE fused hasher
        dispatch (deepest first — a parent always sits at a strictly
        smaller depth, and across tries there is no ordering constraint,
        exactly the ``_pack_window`` slot-rebasing argument). A
        storage-heavy block's hundreds of tiny per-trie per-depth calls
        become ~max_depth full-rate dispatches.
    (b) **Upper/lower subtrie split with a host encode pool**: each trie
        partitions at ``split_depth`` (reth's ``ParallelSparseTrie``
        shape). RLP encoding for nodes inside independent lower subtries
        fans out across a shared thread pool (chunks never split a
        subtrie), while the short upper spine encodes serially on the
        caller thread — host pointer-chasing stops serializing behind
        the hash dispatch.

    With a lane-bound ``HashClient`` hasher (--hash-service), encoded
    chunks STREAM into the service as they finish (``submit`` futures on
    the live lane); the service's continuous batching coalesces them
    back into full-rate device dispatches, overlapping host encode with
    device hashing inside one level.

    Roots are bit-identical to the serial path by construction: the
    structure walk, inline (<32 B) rule, and ref encoding are shared
    with ``root_hash_compute``; only batching geometry changes.
    Thread-safe: per-commit state is local; the executor is shared.
    """

    POOL_MIN_NODES = 128   # below this a level encodes serially
    MIN_CHUNK = 32

    # whole-subtrie packing floors (k-level engine program tiers) — class
    # attrs so tests can shrink them for fast CPU compiles
    SUBTRIE_ROW_FLOOR = 512
    SUBTRIE_HOLE_FLOOR = 512

    def __init__(self, workers: int | None = None, split_depth: int | None = None,
                 injector: SparseFaultInjector | None = None,
                 subtrie_levels: int | None = None, arena=None):
        env = os.environ
        self.workers = sparse_worker_count(workers)
        self.split_depth = int(
            split_depth if split_depth is not None
            else env.get("RETH_TPU_SPARSE_SPLIT_DEPTH", "2"))
        # whole-subtrie fused finish (--subtrie-levels): k > 1 packs the
        # global per-depth schedule into hole-spliced level templates and
        # commits the WHOLE dirty set in one multi-level dispatch per k
        # levels (ops/fused_commit.SubtrieFusedEngine, or the hash
        # service's window lane when the hasher is a HashClient)
        self.subtrie_levels = int(
            subtrie_levels if subtrie_levels is not None
            else env.get("RETH_TPU_SUBTRIE_LEVELS", "0") or 0)
        self.injector = (injector if injector is not None
                         else SparseFaultInjector.from_env())
        # hot-state plane (--hot-state): a shared DigestArena makes each
        # commit a DELTA against the persistent cross-block engine —
        # only this block's dirty rows stage; unchanged sibling digests
        # splice from rows still resident from prior epochs. Implies the
        # whole-subtrie layout even when --subtrie-levels is unset.
        self.arena = arena
        self._arena_k = self.subtrie_levels if self.subtrie_levels > 1 else 8
        self.hot_injector = None
        if arena is not None:
            from .hot_cache import HotStateFaultInjector

            self.hot_injector = HotStateFaultInjector.from_env()
        self._pool: ThreadPoolExecutor | None = None
        self._pool_lock = threading.Lock()
        self.last: dict | None = None  # most recent commit's stats

    def _executor(self) -> ThreadPoolExecutor:
        with self._pool_lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=self.workers,
                    thread_name_prefix="sparse-encode")
            return self._pool

    def shutdown(self) -> None:
        with self._pool_lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False)

    # -- collection -----------------------------------------------------------

    def _collect(self, tries):
        """Global per-depth schedule: ``levels[depth] = [(group, node)]``
        across all tries. ``group`` identifies the lower subtrie a node
        belongs to (nodes above ``split_depth`` get the trie's own upper
        group) so encode chunks never split a subtrie."""
        levels: dict[int, list] = {}
        split = self.split_depth
        group_counter = [0]

        def collect(node, depth, group):
            if node is None or isinstance(node, _Blind):
                return
            if node._ref is not None:
                return  # clean subtree: ref cached (cross-block reuse)
            levels.setdefault(depth, []).append((group, node))
            nxt = depth + 1
            if isinstance(node, _Ext):
                cg = group
                if nxt == split:
                    group_counter[0] += 1
                    cg = group_counter[0]
                collect(node.child, nxt, cg)
            elif isinstance(node, _Branch):
                for c in node.children:
                    if c is None:
                        continue
                    cg = group
                    if nxt == split:
                        group_counter[0] += 1
                        cg = group_counter[0]
                    collect(c, nxt, cg)

        for t in tries:
            group_counter[0] += 1
            collect(t.root, 0, group_counter[0])
        return levels

    def _chunk(self, entries):
        """Group-aligned contiguous chunks sized for the pool width."""
        target = max(self.MIN_CHUNK, len(entries) // (self.workers * 2) or 1)
        chunks: list[list] = []
        cur: list = []
        cur_group = None
        for group, node in entries:
            if cur and len(cur) >= target and group != cur_group:
                chunks.append(cur)
                cur = []
            cur.append(node)
            cur_group = group
        if cur:
            chunks.append(cur)
        return chunks

    # -- commit ---------------------------------------------------------------

    def commit(self, tries: list["SparseTrie"], hasher=keccak256_batch_np) -> list[bytes]:
        """Hash every dirty subtree of ``tries`` and return their roots
        (in input order), bit-identical to calling ``root_hash_compute``
        on each. One fused hasher dispatch per global depth."""
        from ..metrics import sparse_commit_metrics

        t_wall = time.perf_counter()
        roots: list[bytes | None] = [None] * len(tries)
        live: list[tuple[int, "SparseTrie"]] = []
        for i, t in enumerate(tries):
            if t.root is None:
                t.root_hash = EMPTY_ROOT_HASH
                t.updates = 0
                roots[i] = t.root_hash
            elif isinstance(t.root, _Blind):
                t.root_hash = t.root.hash
                roots[i] = t.root_hash
            else:
                live.append((i, t))
        stats = {"tries": len(live), "levels": 0, "dispatches": 0,
                 "hashed": 0, "encode_chunks": 0, "pooled_levels": 0,
                 "streamed": 0}
        if not live:
            self.last = {**stats, "wall_s": 0.0}
            return roots

        if (self.arena is not None
                and getattr(hasher, "commit_window", None) is None):
            # hot-state delta commit; any fault inside evicts the arena
            # and falls through to the classic full-upload rungs below
            delta = self._commit_fused_arena(live, roots, hasher, stats,
                                             t_wall)
            if delta is not None:
                return delta

        if self.subtrie_levels > 1:
            fused = self._commit_fused(live, roots, hasher, stats, t_wall)
            if fused is not None:
                return fused

        levels = self._collect([t for _, t in live])
        use_streaming = hasattr(hasher, "submit")
        encode_wall = [0.0]  # summed per-chunk encode time (pool-side)

        def _encode_chunk(c):
            t0 = time.perf_counter()
            out = [_encode_rlp(n) for n in c]
            dt = time.perf_counter() - t0
            with self._pool_lock:
                encode_wall[0] += dt
            return out

        for depth in sorted(levels, reverse=True):
            entries = levels[depth]
            stats["levels"] += 1
            use_pool = (self.workers > 1
                        and len(entries) >= self.POOL_MIN_NODES)
            if self.injector is not None:
                self.injector.on_dispatch()
            if not use_pool:
                rlps = [_encode_rlp(node) for _, node in entries]
                nodes = [node for _, node in entries]
                self._apply_level(nodes, rlps, hasher, stats)
                continue
            stats["pooled_levels"] += 1
            chunks = self._chunk(entries)
            stats["encode_chunks"] += len(chunks)
            pool = self._executor()
            sparse_commit_metrics.set_encode_busy(len(chunks))
            futs = [pool.submit(_encode_chunk, c) for c in chunks]
            try:
                if use_streaming:
                    # live-lane streaming: each encoded chunk's >=32 B rows
                    # go straight to the hash service as their own request;
                    # continuous batching fuses them back into one
                    # full-rate dispatch while later chunks still encode
                    pending = []
                    for chunk, f in zip(chunks, futs):
                        rlps = f.result()
                        to_hash = [(n, r) for n, r in zip(chunk, rlps)
                                   if len(r) >= 32]
                        for n, r in zip(chunk, rlps):
                            if len(r) < 32:
                                n._ref = r
                        if to_hash:
                            stats["streamed"] += 1
                            stats["h2d_bytes"] = (
                                stats.get("h2d_bytes", 0)
                                + sum(len(r) for _, r in to_hash))
                            pending.append(
                                (to_hash,
                                 hasher.submit([r for _, r in to_hash])))
                    for to_hash, fut in pending:
                        for (n, _r), d in zip(to_hash, fut.result()):
                            n._ref = encode_hash_ref(bytes(d))
                            stats["hashed"] += 1
                    stats["dispatches"] += 1 if pending else 0
                else:
                    nodes, rlps = [], []
                    for chunk, f in zip(chunks, futs):
                        nodes.extend(chunk)
                        rlps.extend(f.result())
                    self._apply_level(nodes, rlps, hasher, stats)
            finally:
                sparse_commit_metrics.set_encode_busy(0)

        # per-trie top: the root hash is keccak of the root RLP whatever
        # its size — batch every live trie's top in one dispatch
        if self.injector is not None:
            self.injector.on_dispatch()
        tops = [_encode_rlp(t.root) for _, t in live]
        stats["dispatches"] += 1
        stats["h2d_bytes"] = (stats.get("h2d_bytes", 0)
                              + sum(len(r) for r in tops))
        with tracing.span("trie::sparse", "hash.dispatch", msgs=len(tops),
                          what="trie_tops"):
            digests = hasher(tops)
        for (i, t), d in zip(live, digests):
            t.root_hash = bytes(d)
            t.updates = 0
            roots[i] = t.root_hash
        if encode_wall[0]:
            # encode-pool attribution: summed worker-side walls (chunks run
            # concurrently, so this is work, not wall clock)
            tracing.record_span("trie::sparse", "sparse.encode",
                                time.time() - encode_wall[0], encode_wall[0],
                                ctx=tracing.current_context(),
                                fields={"chunks": stats["encode_chunks"]})
        stats["wall_s"] = round(time.perf_counter() - t_wall, 6)
        self.last = stats
        sparse_commit_metrics.record_commit(stats)
        return roots

    # -- whole-subtrie fused finish (k levels per device dispatch) ----------

    def _commit_fused(self, live, roots, hasher, stats, t_wall):
        """Pack the global per-depth schedule into hole-spliced level
        templates — the inline-vs-hashed split needs only RLP *lengths*,
        never digest values (the fused-committer invariant) — and commit
        the whole dirty set through a whole-subtrie engine: ONE device
        dispatch per ``subtrie_levels`` depths instead of one hash call
        per depth. With a service-bound ``HashClient`` the window rides
        the live lane (``commit_window``); otherwise a local
        ``SubtrieFusedEngine`` runs it. Roots are bit-identical to the
        serial path: templates come from the SAME RLP builders, with
        zero-filled holes where the device splices child digests.
        Returns None when the engine stack is unavailable (no jax) — the
        caller falls through to the classic per-depth path."""
        import numpy as np

        from ..metrics import sparse_commit_metrics

        commit_window = getattr(hasher, "commit_window", None)
        eng = None
        if commit_window is None:
            try:
                from ..ops.fused_commit import SubtrieFusedEngine

                eng = SubtrieFusedEngine(
                    min_tier=64, k=self.subtrie_levels,
                    row_floor=self.SUBTRIE_ROW_FLOOR,
                    hole_floor=self.SUBTRIE_HOLE_FLOOR)
            except ImportError:  # no jax installed: classic path
                return None

        levels = self._collect([t for _, t in live])
        slot_of: dict[int, int] = {}
        next_slot = [1]  # slot 0 = dummy (engine convention)
        schedule: list[tuple[list, list, list]] = []
        for depth in sorted(levels, reverse=True):
            if self.injector is not None:
                self.injector.on_dispatch()
            stats["levels"] += 1
            lv_nodes, lv_templates, lv_holes = [], [], []
            for _g, node in levels[depth]:
                t, holes = _node_template_sparse(node, slot_of)
                if len(t) < 32:
                    node._ref = t  # inline: complete and hole-free
                    continue
                slot = next_slot[0]
                next_slot[0] += 1
                slot_of[id(node)] = slot
                lv_nodes.append(node)
                lv_templates.append(t)
                lv_holes.append(holes)
            if lv_nodes:
                schedule.append((lv_nodes, lv_templates, lv_holes))

        window = self._pack_schedule(schedule, slot_of)

        buf = None
        if window:
            max_slots = next_slot[0] - 1
            if commit_window is not None:
                # live-lane window request: the service runs it as one
                # fused dispatch per k levels (numpy replay on failure)
                buf = commit_window(window, max_slots)
                stats["streamed"] += len(window)
                stats["dispatches"] += max(
                    1, -(-len(window) // self.subtrie_levels))
            else:
                eng.begin(max_slots)
                for w in window:
                    eng.dispatch_packed(w["flat"], w["row_off"],
                                        w["row_len"], w["slots"],
                                        w["holes"], w["b_tier"])
                buf = eng.finish()
                stats["dispatches"] += eng.dispatches
                stats["h2d_bytes"] = (eng.staged_u8_bytes
                                      + eng.staged_i32_bytes)
            for _nodes, _templates, _holess in schedule:
                for node in _nodes:
                    node._ref = encode_hash_ref(
                        bytes(buf[slot_of[id(node)]]))
                    stats["hashed"] += 1

        for i, t in live:
            root_slot = slot_of.get(id(t.root))
            if root_slot is not None:
                t.root_hash = bytes(buf[root_slot])
            else:
                # inline or clean root: the root hash is keccak of the
                # full root RLP whatever its size (serial-path rule)
                t.root_hash = keccak256(_encode_rlp(t.root))
            t.updates = 0
            roots[i] = t.root_hash
        stats["wall_s"] = round(time.perf_counter() - t_wall, 6)
        stats["subtrie_k"] = self.subtrie_levels
        self.last = stats
        sparse_commit_metrics.record_commit(stats)
        return roots

    @staticmethod
    def _pack_schedule(schedule, slot_of: dict[int, int]) -> list[dict]:
        """Level template lists -> engine window dicts (flat bytes,
        row offsets/lengths, digest slots, hole triples, block tier) —
        shared by the classic fused finish and the arena delta finish."""
        import numpy as np

        window: list[dict] = []
        for _nodes, templates, holess in schedule:
            row_len = np.array([len(t) for t in templates], dtype=np.uint32)
            row_off = (np.cumsum(row_len) - row_len).astype(np.uint32)
            flat = np.frombuffer(b"".join(templates), dtype=np.uint8)
            slots = np.array([slot_of[id(n)] for n in _nodes],
                             dtype=np.int32)
            hr: list[int] = []
            hb: list[int] = []
            hs: list[int] = []
            for i, hl in enumerate(holess):
                for off, src in hl:
                    hr.append(i)
                    hb.append(off)
                    hs.append(src)
            holes = (np.array([hr, hb, hs], dtype=np.int32) if hr else None)
            bt = 1
            maxlen = int(row_len.max())
            while bt * RATE <= maxlen:
                bt *= 2
            window.append({"flat": flat, "row_off": row_off,
                           "row_len": row_len, "slots": slots,
                           "holes": holes, "b_tier": bt})
        return window

    # -- hot-state arena delta finish (ISSUE 19 device half) ----------------

    def _commit_fused_arena(self, live, roots, hasher, stats, t_wall):
        """Delta-commit the dirty set against the persistent cross-block
        :class:`~reth_tpu.ops.fused_commit.DigestArena`: only THIS
        block's dirty rows stage onto the device; unchanged sibling
        digests (clean refs, blinds, reveal-stamped subtrees) either
        inline as literal bytes or hole-splice rows still resident from
        prior epochs. The terminal fetch is ``peek_slots`` (this epoch's
        rows only), keeping the buffer resident for the next block.

        Returns None — and the caller reruns the SAME commit on the
        classic full-upload rungs — when the arena is contended, the
        device stack is absent, or ANY fault fires mid-epoch (the arena
        evicts first, so no partial epoch is ever referenced). Roots are
        bit-identical on every rung: templates come from the same RLP
        builders and a resident splice writes the exact digest bytes the
        literal ref would have inlined."""
        import numpy as np

        from ..metrics import sparse_commit_metrics

        arena = self.arena
        if not arena.try_acquire():
            return None  # a sibling finish holds the arena: classic path
        try:
            evict_storm = (self.hot_injector is not None
                           and self.hot_injector.evict_storm)
            fresh = arena.begin_epoch(evict_storm=evict_storm)
            eng = arena.engine
            if eng is None:
                try:
                    from ..ops.fused_commit import SubtrieFusedEngine

                    eng = SubtrieFusedEngine(
                        min_tier=64, k=self._arena_k,
                        row_floor=self.SUBTRIE_ROW_FLOOR,
                        hole_floor=self.SUBTRIE_HOLE_FLOOR)
                except ImportError:  # no jax installed
                    return None
                arena.engine = eng
                fresh = True

            levels = self._collect([t for _, t in live])
            resident = None if fresh else arena.lookup
            slot_of: dict[int, int] = {}
            epoch_nodes: list = []
            epoch_slots: list[int] = []
            schedule: list[tuple[list, list, list]] = []
            for depth in sorted(levels, reverse=True):
                if self.injector is not None:
                    self.injector.on_dispatch()
                stats["levels"] += 1
                lv_nodes, lv_templates, lv_holes = [], [], []
                for _g, node in levels[depth]:
                    t, holes = _node_template_sparse(node, slot_of,
                                                     resident)
                    if len(t) < 32:
                        node._ref = t  # inline: complete and hole-free
                        continue
                    slot = arena.alloc()
                    slot_of[id(node)] = slot
                    lv_nodes.append(node)
                    lv_templates.append(t)
                    lv_holes.append(holes)
                    epoch_nodes.append(node)
                    epoch_slots.append(slot)
                if lv_nodes:
                    schedule.append((lv_nodes, lv_templates, lv_holes))

            window = self._pack_schedule(schedule, slot_of)
            h2d_bytes = 0
            if window:
                max_slots = arena.next_slot - 1
                if fresh:
                    eng.begin(max_slots)
                else:
                    eng.begin_delta(max_slots)
                for w in window:
                    eng.dispatch_packed(w["flat"], w["row_off"],
                                        w["row_len"], w["slots"],
                                        w["holes"], w["b_tier"])
                rows = eng.peek_slots(
                    np.asarray(epoch_slots, dtype=np.int64))
                for node, slot, d in zip(epoch_nodes, epoch_slots, rows):
                    dig = bytes(d)
                    node._ref = encode_hash_ref(dig)
                    arena.note(dig, slot)
                    stats["hashed"] += 1
                stats["dispatches"] += eng.dispatches
                h2d_bytes = eng.staged_u8_bytes + eng.staged_i32_bytes

            for i, t in live:
                if id(t.root) in slot_of:
                    t.root_hash = bytes(t.root._ref[1:])
                else:
                    # inline or clean root: keccak of the full root RLP
                    # whatever its size (serial-path rule)
                    t.root_hash = keccak256(_encode_rlp(t.root))
                t.updates = 0
                roots[i] = t.root_hash

            # delta-upload accounting: staged rows vs reveal-stamped
            # rows that a cold path would have re-staged (trie.stamped)
            stamped = 0
            for _i, t in live:
                stamped += t.stamped
                t.stamped = 0
            staged_rows = len(epoch_nodes)
            denom = staged_rows + stamped
            delta_fraction = (staged_rows / denom) if denom else 0.0
            stats["wall_s"] = round(time.perf_counter() - t_wall, 6)
            stats["subtrie_k"] = self._arena_k
            stats["staged_rows"] = staged_rows
            stats["stamped_rows"] = stamped
            stats["delta_fraction"] = round(delta_fraction, 4)
            stats["h2d_bytes"] = h2d_bytes
            stats["arena_fresh"] = fresh
            self.last = stats
            sparse_commit_metrics.record_commit(stats)
            try:
                from ..metrics import hotstate_metrics

                hotstate_metrics.record_arena(
                    arena.snapshot(), delta_fraction=delta_fraction,
                    staged_rows=staged_rows, stamped_rows=stamped,
                    h2d_bytes=h2d_bytes, fresh=fresh)
            except Exception:  # noqa: BLE001 — metrics never gate commits
                pass
            return roots
        except BaseException as e:  # noqa: BLE001 — external ladder
            arena.on_fault(e)
            if not isinstance(e, Exception) or isinstance(
                    e, InjectedSparseAbort):
                raise  # injected aborts / interrupts keep their semantics
            return None
        finally:
            arena.release()

    @staticmethod
    def _apply_level(nodes, rlps, hasher, stats) -> None:
        to_hash = [(n, r) for n, r in zip(nodes, rlps) if len(r) >= 32]
        for n, r in zip(nodes, rlps):
            if len(r) < 32:
                n._ref = r  # inline ref
        if to_hash:
            stats["dispatches"] += 1
            stats["h2d_bytes"] = (stats.get("h2d_bytes", 0)
                                  + sum(len(r) for _, r in to_hash))
            with tracing.span("trie::sparse", "hash.dispatch",
                              msgs=len(to_hash), what="level"):
                digests = hasher([r for _, r in to_hash])
            for (n, _r), d in zip(to_hash, digests):
                n._ref = encode_hash_ref(bytes(d))
                stats["hashed"] += 1


# -- state-level composition --------------------------------------------------


@dataclass
class SparseStateTrie:
    """Account trie + per-account storage tries, revealed from proofs.

    Reference: crates/trie/sparse/src/state.rs. Keys are HASHED (secure
    trie); callers pass keccak(address)/keccak(slot).
    """

    account_trie: SparseTrie = field(default_factory=SparseTrie)
    storage_tries: dict[bytes, SparseTrie] = field(default_factory=dict)
    # hot-state plane: propagate reveal-ref stamping to every trie
    stamp_reveals: bool = False

    @classmethod
    def anchored(cls, state_root: bytes) -> "SparseStateTrie":
        return cls(account_trie=SparseTrie(state_root))

    def set_stamping(self, on: bool) -> None:
        """Turn reveal-ref stamping on for every current and future trie
        (the hot-state plane's delta-staging precondition)."""
        self.stamp_reveals = on
        self.account_trie.stamp_reveals = on
        for t in self.storage_tries.values():
            t.stamp_reveals = on

    def reveal_account(self, proof_nodes: list[bytes]) -> None:
        self.account_trie.reveal(proof_nodes)

    def storage_trie(self, hashed_addr: bytes,
                     storage_root: bytes = EMPTY_ROOT_HASH) -> SparseTrie:
        st = self.storage_tries.get(hashed_addr)
        if st is None:
            st = SparseTrie(storage_root)
            st.stamp_reveals = self.stamp_reveals
            self.storage_tries[hashed_addr] = st
        return st

    def reveal_storage(self, hashed_addr: bytes, storage_root: bytes,
                       proof_nodes: list[bytes]) -> None:
        st = self.storage_tries.get(hashed_addr)
        if st is None or (st.root is None and st.root_hash != storage_root):
            st = SparseTrie(storage_root)
            st.stamp_reveals = self.stamp_reveals
            self.storage_tries[hashed_addr] = st
        st.reveal(proof_nodes)

    def update_account(self, hashed_addr: bytes, account_rlp: bytes) -> None:
        self.account_trie.update(hashed_addr, account_rlp)

    def remove_account(self, hashed_addr: bytes) -> None:
        self.account_trie.delete(hashed_addr)
        self.storage_tries.pop(hashed_addr, None)

    def dirty_storage_tries(self) -> list[SparseTrie]:
        return [t for t in self.storage_tries.values()
                if t.updates or (t.root is not None
                                 and not isinstance(t.root, _Blind)
                                 and t.root._ref is None)]

    def root(self, hasher=keccak256_batch_np,
             committer: "ParallelSparseCommitter | None" = None) -> bytes:
        """State root over every dirty storage trie + the account trie.

        With a :class:`ParallelSparseCommitter` the dirty storage tries
        AND the account trie share ONE global per-depth schedule (one
        fused dispatch per depth across all of them — the account trie's
        leaf values already embed their storage roots, so there is no
        ordering constraint between the tries). Without one, each trie
        runs its own level batching (the serial baseline the bench and
        differential tests compare against)."""
        dirty = self.dirty_storage_tries()
        if committer is not None:
            roots = committer.commit(dirty + [self.account_trie], hasher)
            return roots[-1]
        # serial composition: each trie's own level batching
        for t in dirty:
            t.root_hash_compute(hasher)
        return self.account_trie.root_hash_compute(hasher)


def export_branch_updates(trie: SparseTrie, changed_keys: list[bytes],
                          old_branch=None):
    """Stored-format trie updates from an updated+hashed sparse trie.

    Reference analogue: the sparse trie producing ``TrieUpdates`` for the
    engine (crates/trie/sparse — updated_nodes/removed_nodes feeding
    `TrieUpdates`), so the live-tip path never re-walks the database.

    For every prefix of every changed key path, returns
    ``{path: BranchNode}`` where the trie holds a branch, and
    ``{path: None}`` (a delete marker) where it no longer does BUT the
    pre-state did (``old_branch(path)`` resolves) — a collapsed branch may
    sit deeper than the post-update walk reaches (a delete that merges a
    long extension), so every prefix is checked against the pre-state
    rather than guessing from walk depth; prefixes that never held a
    stored branch produce nothing. Only prefixes of changed keys can have
    changed stored nodes — a branch's content changes only when a
    descendant leaf does. MUST be called after ``root_hash_compute``
    (child refs must be clean).

    ``old_branch(path)`` resolves the pre-state stored branch — also used
    to carry over ``tree_mask`` bits for blinded children (their subtrees
    are untouched by definition, so the old bit is still exact).
    """
    from .committer import BranchNode

    out: dict[bytes, BranchNode | None] = {}
    branches: dict[bytes, _Branch] = {}
    old_cache: dict[bytes, object] = {}

    def old_at(path: bytes):
        if path not in old_cache:
            old_cache[path] = old_branch(path) if old_branch is not None else None
        return old_cache[path]

    # Which prefixes can hold a STALE stored branch (needing a delete
    # marker)? Only pre-state branch paths along a changed key. Probing all
    # 64 prefixes of every key is sound but wasteful; three sound cuts:
    # (a) a stored branch whose tree_mask bit for the key's next nibble is
    #     CLEAR proves no deeper stored branch exists in that subtree;
    # (b) for a key still PRESENT post-state, pre-state branches on its
    #     path never lie deeper than its post-state walk depth — any
    #     deeper branch that collapsed did so because a sibling key was
    #     DELETED this block, and the deleted key's own (uncapped) probe
    #     walk shares that prefix and emits the marker;
    # (c) one pre-state read per distinct prefix across all keys.
    probe_caps: dict[bytes, int] = {}
    for key in changed_keys:
        nib = unpack_nibbles(key) if len(key) == 32 else key
        # walk the path, recording branches at their trie paths
        node, depth = trie.root, 0
        present = False
        while node is not None and not isinstance(node, _Blind):
            if isinstance(node, _Leaf):
                present = node.path == nib[depth:]
                break
            if isinstance(node, _Ext):
                if nib[depth:depth + len(node.path)] != node.path:
                    break
                depth += len(node.path)
                node = node.child
                continue
            branches[nib[:depth]] = node
            node = node.children[nib[depth]]
            depth += 1
        probe_caps[nib] = depth + 1 if present else 64

    # cut (a) prunes DELETE-MARKER probing only — every post-state branch
    # recorded by the walks is emitted unconditionally below, so a new
    # branch forming deeper than a collapsed (bit-clear) pre-state branch
    # is never skipped
    marker_candidates: set[bytes] = set()
    for nib, cap in probe_caps.items():
        for plen in range(0, min(cap, 64)):
            p = nib[:plen]
            if p in branches:
                continue  # post-state branch: emitted below, no marker
            ob = old_at(p)
            if ob is not None:
                marker_candidates.add(p)
                if not (ob.tree_mask >> nib[plen]) & 1:
                    break  # (a): provably nothing stored deeper pre-state

    def subtree_has_branch(child) -> bool | None:
        if isinstance(child, _Branch):
            return True
        if isinstance(child, _Ext):
            return True  # an extension's child is always a branch (MPT)
        if isinstance(child, _Leaf):
            return False
        return None  # blinded: unknown from the sparse view

    for path in marker_candidates:
        if path not in branches:
            out[path] = None  # pre-state stored a branch here; gone now
    for path, br in branches.items():
        state_mask = tree_mask = hash_mask = 0
        hashes: list[bytes] = []
        old = None
        old_resolved = False
        for nibble in range(16):
            c = br.children[nibble]
            if c is None:
                continue
            state_mask |= 1 << nibble
            has_branch = subtree_has_branch(c)
            if has_branch is None:
                # blinded child: its subtree is unchanged, so the old
                # stored node's bit is still exact
                if not old_resolved:
                    old = old_at(path)
                    old_resolved = True
                has_branch = bool(old is not None
                                  and (old.tree_mask >> nibble) & 1)
            if has_branch:
                tree_mask |= 1 << nibble
            ref = (encode_hash_ref(c.hash) if isinstance(c, _Blind)
                   else c._ref)
            if ref is not None and len(ref) == 33:
                hash_mask |= 1 << nibble
                hashes.append(ref[1:])
        out[path] = BranchNode(state_mask, tree_mask, hash_mask, tuple(hashes))
    return out


class PreservedSparseTrie:
    """Cross-block sparse-trie cache anchored at the canonical tip.

    Reference: crates/chain-state/src/preserved_sparse_trie.rs:15 — after
    a payload's state root is computed, the revealed+updated sparse trie is
    preserved keyed by that block's hash; the next payload building on it
    takes the trie and only reveals the paths it newly touches. A reorg
    (parent mismatch) drops the cache.
    """

    def __init__(self):
        self._anchor: bytes | None = None
        self._trie: SparseStateTrie | None = None
        self.hits = 0
        self.misses = 0

    def take(self, parent_hash: bytes) -> SparseStateTrie | None:
        """Claim the preserved trie if it is anchored at ``parent_hash``."""
        if self._trie is not None and self._anchor == parent_hash:
            t, self._trie, self._anchor = self._trie, None, None
            self.hits += 1
            return t
        self.misses += 1
        return None

    def preserve(self, block_hash: bytes, trie: SparseStateTrie) -> None:
        self._anchor = block_hash
        self._trie = trie

    def peek(self, block_hash: bytes) -> SparseStateTrie | None:
        """Read the preserved trie WITHOUT claiming it (the replica
        role serves reads from it between blocks; the next validate
        still takes it normally)."""
        if self._trie is not None and self._anchor == block_hash:
            return self._trie
        return None

    def invalidate(self) -> None:
        self._anchor = None
        self._trie = None
