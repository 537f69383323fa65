"""Decoders of what the stage writes into its datadir, for the comparison
with the reference: a ``StoragesTrie`` duplicate (one branch node of one
storage trie) and a ``HashedAccounts`` value. A plain RLP decoder over the
layouts the tables' comments state; imports nothing of the program.

- ``StoragesTrie``: key = hashed address; each duplicate = len(path) (one
  byte) || path (one nibble a byte, below the trie's root) || RLP([state
  mask, tree mask, hash mask, [child hashes]]), masks as RLP integers;
- ``HashedAccounts``: key = hashed address; value = RLP([nonce, balance,
  storage root, code hash]).
"""

from __future__ import annotations


def rlp_decode(data: bytes):
    """One RLP item: bytes, or a list of items. Raises ``ValueError`` on
    anything malformed or trailing."""
    item, end = _item(data, 0)
    if end != len(data):
        raise ValueError("trailing bytes after the RLP item")
    return item


def _item(data: bytes, at: int):
    if at >= len(data):
        raise ValueError("RLP item past the end")
    b = data[at]
    if b < 0x80:
        return data[at:at + 1], at + 1
    if b < 0xB8:
        n, at = b - 0x80, at + 1
    elif b < 0xC0:
        k = b - 0xB7
        n, at = int.from_bytes(data[at + 1:at + 1 + k], "big"), at + 1 + k
    else:
        if b < 0xF8:
            n, at = b - 0xC0, at + 1
        else:
            k = b - 0xF7
            n, at = int.from_bytes(data[at + 1:at + 1 + k], "big"), at + 1 + k
        end, out = at + n, []
        if end > len(data):
            raise ValueError("RLP list past the end")
        while at < end:
            item, at = _item(data, at)
            out.append(item)
        if at != end:
            raise ValueError("RLP list overruns its length")
        return out, end
    if at + n > len(data):
        raise ValueError("RLP string past the end")
    return data[at:at + n], at + n


def storage_trie_entry(dup: bytes) -> tuple[bytes, tuple]:
    """``(path, (state_mask, tree_mask, hash_mask, hashes))``: what
    ``reference/mpt.py``'s ``TrieResult.branches`` holds for that path."""
    plen = dup[0]
    sm, tm, hm, hashes = rlp_decode(dup[1 + plen:])
    return dup[1:1 + plen], (int.from_bytes(sm, "big"), int.from_bytes(tm, "big"),
                             int.from_bytes(hm, "big"), tuple(hashes))


def account_storage_root(value: bytes) -> bytes:
    return rlp_decode(value)[2]
