"""Account/Storage hashing stages: plain state → hashed tables.

Reference analogue: `AccountHashingStage` (keccak256(address), rayon
chunks + ETL — crates/stages/stages/src/stages/hashing_account.rs:37) and
`StorageHashingStage` (hashing_storage.rs:133-137). TPU-first: the keccak
work is a batched device dispatch per scan chunk instead of CPU worker
chunks — this is benchmark config #3 (BASELINE.md).

Clean path (first sync): scan the plain table in bounded chunks, batch-
hash each, collect through the ETL external-sort collector (reth_tpu/etl
— memory stays bounded for >RAM inputs) and bulk-load the hashed table
in sorted order. Incremental path: only keys in the range's changesets.
"""

from __future__ import annotations

from ..etl import Collector
from ..storage.provider import DatabaseProvider
from ..storage.tables import (Tables, decode_account, decode_storage_entry,
                              encode_storage_entry)
from ..trie.committer import TrieCommitter
from .api import ExecInput, ExecOutput, Stage, UnwindInput

_SCAN_CHUNK = 200_000  # keys hashed per device dispatch during clean scans


def _drain(pairs, tx, table: str, dupsort: bool) -> None:
    """A clean drain's sorted pairs into the table the drain cleared, by
    the store's sorted bulk append (``Tx.append``), ``_SCAN_CHUNK`` pairs
    a call."""
    keys: list[bytes] = []
    values: list[bytes] = []
    for k, v in pairs:
        keys.append(k)
        values.append(v)
        if len(keys) >= _SCAN_CHUNK:
            tx.append(table, keys, values, dupsort)
            keys, values = [], []
    if keys:
        tx.append(table, keys, values, dupsort)


class AccountHashingStage(Stage):
    id = "AccountHashing"

    def __init__(self, committer: TrieCommitter | None = None, clean_threshold: int = 100_000):
        committer = committer or TrieCommitter()
        # hashing-stage scans are rebuild work: with --hash-service their
        # chunk batches ride the rebuild lane (identity without a service)
        if hasattr(committer, "for_lane"):
            committer = committer.for_lane("rebuild")
        self.hasher = committer.hasher
        self.clean_threshold = clean_threshold

    def execute(self, provider: DatabaseProvider, inp: ExecInput) -> ExecOutput:
        if inp.checkpoint == 0 or inp.target - inp.checkpoint > self.clean_threshold:
            # clean rebuild: chunked scan -> batch hash -> ETL -> sorted load
            provider.tx.clear(Tables.HashedAccounts.name)
            with Collector() as col:
                batch: list[tuple[bytes, bytes]] = []

                def flush():
                    hashed = self.hasher([k for k, _ in batch])
                    for (_, value), haddr in zip(batch, hashed):
                        col.insert(haddr, value)
                    batch.clear()

                for entry in provider.tx.cursor(Tables.PlainAccountState.name).walk():
                    batch.append(entry)
                    if len(batch) >= _SCAN_CHUNK:
                        flush()
                if batch:
                    flush()
                _drain(col, provider.tx, Tables.HashedAccounts.name, dupsort=False)
        else:
            changed = provider.account_changes_in_range(inp.next_block, inp.target)
            addrs = sorted(changed.keys())
            hashed = self.hasher(addrs)
            for addr, haddr in zip(addrs, hashed):
                acc = provider.account(addr)
                provider.put_hashed_account(haddr, acc)
        return ExecOutput(checkpoint=inp.target)

    def unwind(self, provider: DatabaseProvider, inp: UnwindInput) -> None:
        # Restore hashed accounts from changeset PREV-IMAGES directly: plain
        # state is unwound later (ExecutionStage is after us in unwind order).
        changed = provider.account_changes_in_range(inp.unwind_to + 1, inp.checkpoint)
        addrs = sorted(changed.keys())
        hashed = self.hasher(addrs)
        for addr, haddr in zip(addrs, hashed):
            provider.put_hashed_account(haddr, changed[addr])


class StorageHashingStage(Stage):
    id = "StorageHashing"

    def __init__(self, committer: TrieCommitter | None = None, clean_threshold: int = 100_000):
        committer = committer or TrieCommitter()
        # hashing-stage scans are rebuild work: with --hash-service their
        # chunk batches ride the rebuild lane (identity without a service)
        if hasattr(committer, "for_lane"):
            committer = committer.for_lane("rebuild")
        self.hasher = committer.hasher
        self.clean_threshold = clean_threshold

    def execute(self, provider: DatabaseProvider, inp: ExecInput) -> ExecOutput:
        if inp.checkpoint == 0 or inp.target - inp.checkpoint > self.clean_threshold:
            provider.tx.clear(Tables.HashedStorages.name)
            with Collector() as col:
                batch: list[tuple[bytes, bytes, int]] = []  # (addr, slot, value)

                def flush():
                    n = len(batch)
                    digests = self.hasher(
                        [a for a, _, _ in batch] + [s for _, s, _ in batch]
                    )
                    for (_, _, value), haddr, hslot in zip(batch, digests[:n], digests[n:]):
                        col.insert(haddr + hslot, value.to_bytes(32, "big"))
                    batch.clear()

                for addr, dup in provider.tx.cursor(Tables.PlainStorageState.name).walk():
                    slot, value = decode_storage_entry(dup)
                    batch.append((addr, slot, value))
                    if len(batch) >= _SCAN_CHUNK:
                        flush()
                if batch:
                    flush()
                def entries():
                    for key, value32 in col:
                        value = int.from_bytes(value32, "big")
                        if value:  # a zero slot holds no entry
                            yield key[:32], encode_storage_entry(key[32:], value)

                _drain(entries(), provider.tx, Tables.HashedStorages.name,
                       dupsort=True)
        else:
            changed = provider.storage_changes_in_range(inp.next_block, inp.target)
            self._apply_changed(provider, changed, use_prev_images=False)
        return ExecOutput(checkpoint=inp.target)

    def _apply_changed(self, provider: DatabaseProvider, changed, use_prev_images: bool) -> None:
        pairs: list[tuple[bytes, bytes]] = [
            (addr, slot) for addr, slots in changed.items() for slot in slots
        ]
        addrs = sorted({a for a, _ in pairs})
        digests = self.hasher(addrs + [s for _, s in pairs])
        haddr_of = dict(zip(addrs, digests[: len(addrs)]))
        for (addr, slot), hslot in zip(pairs, digests[len(addrs) :]):
            value = changed[addr][slot] if use_prev_images else provider.storage(addr, slot)
            provider.put_hashed_storage(haddr_of[addr], hslot, value)

    def unwind(self, provider: DatabaseProvider, inp: UnwindInput) -> None:
        # prev-images ARE the post-unwind values (plain state unwinds later)
        changed = provider.storage_changes_in_range(inp.unwind_to + 1, inp.checkpoint)
        self._apply_changed(provider, changed, use_prev_images=True)
