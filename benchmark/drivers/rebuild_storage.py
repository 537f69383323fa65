"""Driver ``rebuild_storage``: the ``rebuild`` driver's closed loop over
STORAGE chunks of a clean ``MerkleStage`` rebuild.

Each operation is one storage chunk as ``MerkleStage._storage_chunk`` hands it
to ``_commit_subtries``: thousands of whole storage tries, most of one
to three slots, through ``commit_hashed_pipelined(jobs, collect_branches=True,
start_depth=0)`` on the one committer kept for the run. The window's loop, the
answers kept, ``release``, the facts and the rate are the parent class's. This
module brings what a chunk of that shape needs of its own:

- the operations come from ``harness/traffic_storage.py`` (sizes and order
  from the parameters, keys and values from the seed);
- the work count: ``harness/work.py::trie_work`` over the whole operation, and
  where a leaf under 32 bytes makes it raise (such a node is embedded, not
  hashed) the sum of the reference's own ``n_hashes`` / ``n_blocks`` over the
  operation's jobs: either way the count never comes from the program;
- the comparison with the reference over ``reference/mpt_many.py::
  build_tries``: one distinct CHUNK a worker process, not one job (a run's
  four chunks hold 70,424 jobs). The same comparisons under the same names as
  the parent's, every limit 0; a chunk whose worker is lost counts its jobs
  ``jobs_unchecked``.
"""

from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

from benchmark.drivers import rebuild
from benchmark.harness import traffic_storage as gen
from benchmark.harness.work import trie_work
from benchmark.reference.mpt_many import build_tries


class Driver(rebuild.Driver):
    def __init__(self, config: dict, workload: dict, seed: int, rehearsal: bool):
        super().__init__(config, workload, seed, rehearsal)
        self._refs: dict[int, list | None] = {}   # op -> a TrieResult a job

    def setup(self) -> None:
        self.ops = gen.storage_chunk_ops(self.traffic, self.seed)
        self.committer = self.make_committer()
        self.start_depth = int(self.call["start_depth"])
        # warm-up, untimed: every distinct chunk once
        for op in range(len(self.ops)):
            self._commit(op)

    def _reference(self, op: int):
        if op not in self._refs:
            self._refs.update(_reference_answers([op], self.ops,
                                                 self.start_depth))
        return self._refs[op]

    def _work(self, op: int) -> tuple[int, int]:
        if op not in self._work_cache:
            try:
                work = trie_work(self.ops[op], self.start_depth)
            except NotImplementedError:   # an embedded leaf: the reference counts
                ref = self._reference(op) or []
                work = (sum(r.n_hashes for r in ref),
                        sum(r.n_blocks for r in ref))
            self._work_cache[op] = work
        return self._work_cache[op]

    def check(self) -> list[tuple[str, float, float]]:
        """(name, number, limit), every limit 0. Every job of every completed
        operation is compared: its root always, its branch nodes in the
        window's first ``FULL_ANSWERS`` operations."""
        bad_roots = bad_branches = missing = count_gap = unchecked = 0
        jobs_checked = 0
        distinct = sorted({c["op"] for c in self.completed})
        self._refs.update(_reference_answers(
            [op for op in distinct if op not in self._refs], self.ops,
            self.start_depth))
        for c in self.completed:
            jobs, roots, refs = self.ops[c["op"]], c["roots"], self._refs[c["op"]]
            count_gap += abs(int(c["hashed_nodes"]) - self._work(c["op"])[0])
            missing += max(0, len(jobs) - len(roots))
            n = min(len(jobs), len(roots))
            if refs is None:
                unchecked += n
                continue
            jobs_checked += n
            for j in range(n):
                bad_roots += roots[j] != refs[j].root
                if c["branch_nodes"] is None:
                    continue
                got, want = rebuild._plain(c["branch_nodes"][j]), refs[j].branches
                if got != want:
                    bad_branches += sum(got.get(p) != want.get(p)
                                        for p in set(got) | set(want))
        if not jobs_checked:
            unchecked += 1
        self.notes = [f"compared {jobs_checked} jobs (all) of "
                      f"{len(self.completed)} operations, {len(distinct)} "
                      f"distinct, with the reference"]
        return [("root_mismatches", float(bad_roots), 0.0),
                ("branch_node_mismatches", float(bad_branches), 0.0),
                ("answers_missing", float(missing), 0.0),
                ("hashed_nodes_gap", float(count_gap), 0.0),
                ("jobs_unchecked", float(unchecked), 0.0)]


def _reference_answers(todo: list[int], ops: list, start_depth: int) -> dict:
    """``op -> [TrieResult a job]`` of the plain reference, ``None`` for a
    chunk whose worker was lost. Large chunks are built side by side, each in
    a process of its own that imports nothing but ``benchmark.reference`` (no
    JAX: it cannot reach for the chip); each is waited for."""
    leaves = sum(len(values) for op in todo for _, values in ops[op])
    workers = min(len(todo), max(1, (os.cpu_count() or 2) - 2), 8)
    if workers <= 1 or leaves < 200_000:
        return {op: build_tries(ops[op], start_depth) for op in todo}
    with ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as pool:
        futs = {op: pool.submit(build_tries, ops[op], start_depth)
                for op in todo}
        out = {}
        for op, f in futs.items():
            try:
                out[op] = f.result()
            except Exception:  # noqa: BLE001 -- a lost worker: chunk unchecked
                out[op] = None
        return out
