"""Driver ``rebuild_storage_db``: the storage phase of ``stage run merkle`` on
its own datadir, one ``Pipeline.step`` an operation.

The node's normal path, as ``reth-tpu stage run --stage MerkleExecute
--datadir <d> --db paged --hasher device`` takes it: the arguments that
command line parses to (``cli.build_parser``), the store ``cli._open_db``
opens for them (``open_database("paged", <d>)``), the committer
``cli._make_committer`` makes for them, the ``MerkleExecute`` stage of
``default_stages(committer=...)`` and ``Pipeline(factory, [stage]).step``: one
read-write transaction, one ``execute`` (one storage chunk read from
``HashedStorages``, committed, its branch nodes and storage roots written),
the checkpoint, the synced commit. No knob is passed and no call into the
committer is made here.

Set-up makes a fresh datadir under the system's temporary directory (on a
tmpfs, where a sync costs nothing, under the checkout instead), fills it
from the seed through the store's sorted bulk append
(``harness/traffic_storage_db.py``: blocks of whole tries in hashed-address
order, one ``HashedAccounts`` entry a trie, the header of the target),
reads it once through a mapping (the page cache the hashing stages leave
behind them), then runs the rebuild's first step (the trie tables cleared, progress ``S``) and
the first ``warmup_ops`` chunks. The window takes the chunks that follow, one
step each: every operation is the first pass over its chunk, as in a sync; a
window that would run out of chunks fails the run. The driver keeps no
branch node: after the window the stage and the store are dropped, the
datadir is opened again and read back, tries and nodes, against the plain
reference (``reference/mpt_many.py``, one chunk a worker process;
``reference/db_entries.py`` decodes what the store holds). The datadir is
removed at exit.
"""

from __future__ import annotations

import atexit
import contextlib
import gc
import mmap
import multiprocessing
import os
import resource
import shutil
import tempfile
import time
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

from benchmark.drivers.rebuild import _merged, _resolved
from benchmark.harness import traffic_storage_db as gen
from benchmark.harness.work import keccak_work
from benchmark.reference import db_entries
from benchmark.reference.mpt_many import build_tries

SLICE_SPAN = "bench.rebuild.op"
STAGE = "MerkleExecute"
HASHED_NODES = ("trie_commit_nodes_total_device", "trie_commit_nodes_total_numpy")
# a step's legs and the collector's passes, each operation's own (absent
# from a program without those counters)
LEGS = (("read", "stage_merkle_read_seconds_total"),
        ("commit", "stage_merkle_commit_seconds_total"),
        ("write", "stage_merkle_write_seconds_total"),
        ("db_commit", "db_commit_seconds_total"),
        ("gc", "python_gc_seconds_total"))
_RAM_FILESYSTEMS = ("tmpfs", "ramfs")


class Driver:
    def __init__(self, config: dict, workload: dict, seed: int, rehearsal: bool):
        self.config = config
        traffic = dict(workload["traffic"])
        if rehearsal:
            traffic = _merged(traffic, workload["rehearsal"])
        self.traffic = _resolved(traffic, config)
        self.call = workload["call"]
        self.seed = seed
        self.rehearsal = rehearsal
        self.completed: list[dict] = []
        self.attempted = 0
        self.failed = 0
        self.errors: list[str] = []
        self.notes: list[str] = []
        self.commit_override = None   # the control's commit, in the stage's place
        self._refs: dict[int, list | None] = {}   # chunk -> a TrieResult a trie

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from reth_tpu import cli
        from reth_tpu.stages import Pipeline, default_stages
        from reth_tpu.storage import ProviderFactory

        self.plan = gen.Plan(self.traffic, self.seed)
        self.dir = _fresh_datadir()
        self.args = cli.build_parser().parse_args(
            ["stage", "run", "--datadir", str(self.dir), "--stage", STAGE,
             "--db", "paged", "--hasher", self.config["hasher"]])
        self.target = int(self.call["target"])
        self.db = cli._open_db(self.args)
        t0 = time.perf_counter()
        self._fill()
        self.fill_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        _warm_page_cache(self.dir)
        self.warm_s = time.perf_counter() - t0
        (self.stage,) = [s for s in default_stages(
            committer=cli._make_committer(self.args)) if s.id == STAGE]
        if self.stage.chunk_leaves != self.plan.chunk_leaves:
            # the configuration states the stage's own default; only a
            # rehearsal's small chunks set the stage's bound to theirs
            if not self.rehearsal:
                raise ValueError(
                    f"the configuration's chunk_leaves {self.plan.chunk_leaves}"
                    f" is not the stage's {self.stage.chunk_leaves}")
            self.stage.chunk_leaves = self.plan.chunk_leaves
        if self.commit_override is not None:
            self.stage._commit_subtries = self.commit_override
        self.pipe = Pipeline(ProviderFactory(self.db), [self.stage])
        # warm-up, untimed: the tables cleared, then the first chunks
        self.next_chunk = 0
        self._step()
        for _ in range(int(self.call["warmup_ops"])):
            self._step()
            self.next_chunk += 1

    def _fill(self) -> None:
        from reth_tpu.primitives.types import Header
        from reth_tpu.storage.provider import DatabaseProvider
        from reth_tpu.storage.tables import Tables

        for b in range(self.plan.blocks):     # one synced commit a block
            keys, dups = self.plan.entries(b)
            with self.db.tx_mut() as tx:
                tx.append(Tables.HashedStorages.name, keys, dups, dupsort=True)
        with DatabaseProvider(self.db.tx_mut()) as p:
            p.tx.append(Tables.HashedAccounts.name, self.plan.addrs,
                        gen.account_rows(self.plan))
            p.insert_header(Header(number=self.target))

    def _step(self):
        return self.pipe.step(self.stage, self.target)

    # -- the window -----------------------------------------------------------

    def window(self, seconds: float, tracer) -> None:
        import jax

        from benchmark.harness.tracing import registry_counters

        traced_at = int(self.call["traced_op"]) if tracer.enabled else -1
        self.t_start = time.perf_counter()
        deadline = self.t_start + seconds
        i = 0
        while time.perf_counter() < deadline:
            chunk = self.next_chunk
            if chunk >= self.plan.full_chunks:
                raise RuntimeError(
                    f"the datadir's {self.plan.full_chunks} chunks ran out "
                    f"after {i} operations of the window: every operation "
                    f"must be the first pass over its chunk, so the window "
                    f"needs more blocks (traffic datadir.blocks)")
            self.attempted += 1
            c0 = registry_counters()
            t0 = time.perf_counter()
            try:
                with (tracer.slice(SLICE_SPAN) if i == traced_at
                      else contextlib.nullcontext()):
                    with jax.profiler.TraceAnnotation(SLICE_SPAN):
                        self._step()
                    t1 = time.perf_counter()     # before the trace is written
            except Exception as e:  # noqa: BLE001 -- counted, the loop goes on
                self.failed += 1
                self.errors.append(f"op {i}: {type(e).__name__}: {e}")
                if self.failed >= 3:
                    break
                i += 1
                continue
            c1 = registry_counters()
            self.next_chunk += 1
            self.completed.append({
                "i": i, "chunk": chunk, "t0": t0, "t1": t1,
                "traced": i == traced_at,
                "hashed_nodes": sum(c1.get(k, 0.0) - c0.get(k, 0.0)
                                    for k in HASHED_NODES),
                "legs": {leg: c1[k] - c0[k] for leg, k in LEGS
                         if k in c0 and k in c1}})
            i += 1
        self.t_end = (self.completed[-1]["t1"] if self.completed
                      else time.perf_counter())

    def release(self) -> None:
        self.stage = self.pipe = None
        self.db.close()
        self.db = None
        gc.collect()

    # -- after the window -----------------------------------------------------

    def _reference(self, chunks: list[int]) -> None:
        todo = [k for k in chunks if k not in self._refs]
        self._refs.update(_reference_answers(todo, self.plan))

    def _work(self, chunk: int) -> tuple[int, int]:
        self._reference([chunk])
        ref = self._refs[chunk] or []
        return sum(r.n_hashes for r in ref), sum(r.n_blocks for r in ref)

    def check(self) -> list[tuple[str, float, float]]:
        """(name, number, limit), every limit 0, read from the datadir opened
        again: every trie of every completed operation, its storage root in
        ``HashedAccounts`` and every one of its ``StoragesTrie`` nodes
        against the reference; the progress blob against the last completed
        chunk's last address."""
        from reth_tpu.storage import open_database
        from reth_tpu.storage.tables import Tables

        self._reference(sorted({c["chunk"] for c in self.completed}))
        bad_roots = bad_branches = missing = count_gap = unchecked = 0
        tries_checked = 0
        db = open_database("paged", self.dir)
        try:
            with db.tx() as tx:
                for c in self.completed:
                    first, end = self.plan.chunks[c["chunk"]]
                    refs = self._refs[c["chunk"]]
                    if refs is None:
                        unchecked += end - first
                        continue
                    count_gap += abs(int(c["hashed_nodes"])
                                     - sum(r.n_hashes for r in refs))
                    stored = _stored_nodes(tx.cursor(Tables.StoragesTrie.name),
                                           self.plan.addr(first),
                                           self.plan.addr(end - 1))
                    for t, ref in zip(range(first, end), refs):
                        addr = self.plan.addr(t)
                        value = tx.get(Tables.HashedAccounts.name, addr)
                        tries_checked += 1
                        if value is None:
                            missing += 1
                            continue
                        bad_roots += db_entries.account_storage_root(value) != ref.root
                        got = stored.pop(addr, {})
                        bad_branches += sum(got.get(p) != ref.branches.get(p)
                                            for p in set(got) | set(ref.branches))
                    # nodes under an address no trie of the chunk has
                    bad_branches += sum(len(v) for v in stored.values())
                blob = tx.get(Tables.StageCheckpointProgresses.name, STAGE.encode())
        finally:
            db.close()
        want = None
        if self.completed:
            last = self.plan.chunks[self.completed[-1]["chunk"]][1] - 1
            want = b"S" + self.target.to_bytes(8, "big") + self.plan.addr(last)
        progress_mismatch = float(want is not None and blob != want)
        if not tries_checked:
            unchecked += 1
        self.notes = [
            f"compared {tries_checked} tries of {len(self.completed)} "
            f"operations (chunks {[c['chunk'] for c in self.completed]}) with "
            f"the reference, read back from the datadir opened again",
            f"datadir {self.dir} on {_fs_type(self.dir)}, "
            f"{_du(self.dir)} bytes on disk; {self.plan.blocks} blocks, "
            f"{self.plan.n_tries} tries, {int(self.plan.starts[-1])} slots, "
            f"{len(self.plan.chunks)} chunks; filled in {self.fill_s:.2f}s, "
            f"read once in {self.warm_s:.2f}s",
            f"host peak RSS {_peak_rss_bytes()} bytes"]
        for leg, _ in LEGS:
            got = [c["legs"][leg] for c in self.completed if leg in c["legs"]]
            if got:
                self.notes.append(f"{leg} leg of each operation, s: "
                                  + " ".join(f"{x:.3f}" for x in got))
        _remove(self.dir)
        return [("root_mismatches", float(bad_roots), 0.0),
                ("branch_node_mismatches", float(bad_branches), 0.0),
                ("answers_missing", float(missing), 0.0),
                ("hashed_nodes_gap", float(count_gap), 0.0),
                ("jobs_unchecked", float(unchecked), 0.0),
                ("progress_mismatch", progress_mismatch, 0.0)]

    def facts(self) -> dict:
        """Counts of the window, the benchmark's own: node hashes and
        permutations are the reference's counts over the chunks' tries."""
        work = [self._work(c["chunk"]) for c in self.completed]
        hashes = sum(h for h, _ in work)
        out = {"ops": len(self.completed), "hashes": hashes,
               "blocks": sum(b for _, b in work), "mhashes": hashes / 1e6,
               "seconds": self.t_end - self.t_start,
               "op_seconds": [c["t1"] - c["t0"] for c in self.completed],
               "notes": list(self.notes)}
        traced = [c for c in self.completed if c["traced"]]
        if traced:
            out["slice_work"] = keccak_work(*self._work(traced[0]["chunk"]))
            out["slice_seconds"] = traced[0]["t1"] - traced[0]["t0"]
        return out

    def end_to_end(self) -> dict:
        f = self.facts()
        if not f["ops"]:
            return {}
        return {self.call["rate_metric"]: f["hashes"] / f["seconds"]}


class ReferenceCommit:
    """The control: ``MerkleStage._commit_subtries`` by the plain reference,
    with the last slot of every trie of two or more left out (a write that
    was acknowledged and is not read back). Put in the stage's place through
    ``Driver.commit_override``; it has to come out as not correct."""

    def __call__(self, jobs, start_depth: int = 0):
        from types import SimpleNamespace

        out = []
        for ref in build_tries([(k[:-1], v[:-1]) if len(v) > 1 else (k, v)
                                for k, v in jobs], start_depth):
            out.append(SimpleNamespace(
                root=ref.root, branch_nodes={
                    p: SimpleNamespace(state_mask=sm, tree_mask=tm,
                                       hash_mask=hm, hashes=hs)
                    for p, (sm, tm, hm, hs) in ref.branches.items()}))
        return out


def _stored_nodes(cur, first_addr: bytes, last_addr: bytes) -> dict:
    """``addr -> {path: node}`` of every ``StoragesTrie`` duplicate from
    ``first_addr`` to ``last_addr``, read through the store's cursor."""
    out: dict[bytes, dict] = {}
    entry = cur.seek(first_addr)
    while entry is not None and entry[0] <= last_addr:
        path, node = db_entries.storage_trie_entry(entry[1])
        out.setdefault(entry[0], {})[path] = node
        entry = cur.next()
    return out


def _reference_answers(todo: list[int], plan) -> dict:
    """``chunk -> [TrieResult a trie]`` of the plain reference, ``None`` for
    a chunk whose worker was lost: one chunk a spawned process that imports
    nothing of the program (no JAX)."""
    workers = min(len(todo), max(1, (os.cpu_count() or 2) - 2), 8)
    if workers <= 1 or plan.chunk_leaves < 100_000:
        return {k: build_tries(plan.jobs(*plan.chunks[k])) for k in todo}
    with ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as pool:
        futs = {k: pool.submit(build_tries, plan.jobs(*plan.chunks[k]))
                for k in todo}
        out = {}
        for k, f in futs.items():
            try:
                out[k] = f.result()
            except Exception:  # noqa: BLE001 -- a lost worker: chunk unchecked
                out[k] = None
        return out


def _fs_type(path: Path) -> str:
    """The filesystem type of the mount that holds ``path``."""
    best, kind = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            parts = line.split()
            mnt = parts[1]
            if (str(path) == mnt or str(path).startswith(mnt.rstrip("/") + "/")) \
                    and len(mnt) >= len(best):
                best, kind = mnt, parts[2]
    return kind


def _fresh_datadir() -> Path:
    """A new directory under the system's temporary directory, or under the
    checkout where that is a filesystem in memory; removed at exit."""
    d = Path(tempfile.mkdtemp(prefix="reth-tpu-stage-db-"))
    if _fs_type(d) in _RAM_FILESYSTEMS:
        d.rmdir()
        root = Path(__file__).resolve().parents[2] / ".bench_datadir"
        root.mkdir(exist_ok=True)
        d = Path(tempfile.mkdtemp(prefix="stage-db-", dir=root))
    atexit.register(_remove, d)
    return d


def _warm_page_cache(d: Path) -> None:
    """Read every file of the datadir once through a mapping of its own, as
    the store reads it: the pages a node's hashing stages have just written
    are in its page cache, where a filesystem that does not keep what a
    ``pwrite`` wrote would leave them to be fetched inside a timed step."""
    step = 1 << 24
    for f in sorted(Path(d).rglob("*")):
        if not f.is_file() or f.stat().st_size == 0:
            continue
        with open(f, "rb") as fh, mmap.mmap(fh.fileno(), 0,
                                            access=mmap.ACCESS_READ) as mm:
            for at in range(0, len(mm), step):
                mm[at:at + step]


def _remove(d: Path) -> None:
    shutil.rmtree(d, ignore_errors=True)


def _du(d: Path) -> int:
    return sum(f.stat().st_blocks * 512 for f in Path(d).rglob("*") if f.is_file())


def _peak_rss_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
