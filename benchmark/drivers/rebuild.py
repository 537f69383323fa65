"""Driver ``rebuild``: a closed loop of one caller over trie-build operations.

Each operation is one chunk of a clean ``MerkleStage`` rebuild: the call
``MerkleStage._commit_subtries`` makes, ``TurboCommitter.commit_hashed_pipelined
(jobs, collect_branches=True, start_depth=...)`` with every knob at the
program's default, on ONE committer kept for the whole run (as
``MerkleStage._turbo_committer`` keeps one, so the digest arena stays
resident). The jobs come from the cell's traffic file through the general
generator; this module holds no sizes.

Set-up runs every distinct operation once, untimed; the window cycles over
them. After the window the program's state is dropped and EVERY job of every
completed operation is built again by the plain reference
(``reference/mpt.py``, one process for each distinct job): subtrie roots and
every stored branch node must be equal, byte for byte.
"""

from __future__ import annotations

import contextlib
import gc
import multiprocessing
import os
import time
from concurrent.futures import ProcessPoolExecutor

from benchmark.harness import traffic as gen
from benchmark.harness.work import keccak_work, trie_work
from benchmark.reference.mpt import build_trie

SLICE_SPAN = "bench.rebuild.op"
# branch nodes are kept, for the comparison after the window, of this many of
# the window's first operations (roots of all): memory stays bounded however
# many operations a faster system completes; today a window holds four
FULL_ANSWERS = 8


class Driver:
    def __init__(self, config: dict, workload: dict, seed: int, rehearsal: bool):
        self.config = config
        self.traffic = dict(workload["traffic"])
        if rehearsal:
            self.traffic = _merged(self.traffic, workload["rehearsal"])
        self.traffic = _resolved(self.traffic, config)
        self.call = workload["call"]
        self.seed = seed
        self.completed: list[dict] = []
        self.attempted = 0
        self.failed = 0
        self.errors: list[str] = []
        self.notes: list[str] = []
        self._work_cache: dict[int, tuple[int, int]] = {}

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        self.ops = gen.trie_job_ops(self.traffic, self.seed)
        self.committer = self.make_committer()
        self.start_depth = int(self.call["start_depth"])
        # warm-up, untimed: every distinct operation once, so that every
        # program shape the window's operations need has been built
        for op in range(len(self.ops)):
            self._commit(op)

    def make_committer(self):
        """The system under test (the control and the fault tests under
        benchmark/tests put something else here)."""
        from reth_tpu.trie.turbo import TurboCommitter

        return TurboCommitter(backend=self.config["turbo_backend"])

    def _commit(self, op: int):
        return self.committer.commit_hashed_pipelined(
            self.ops[op], collect_branches=bool(self.call["collect_branches"]),
            start_depth=self.start_depth)

    # -- the window -----------------------------------------------------------

    def window(self, seconds: float, tracer) -> None:
        import jax

        traced_at = int(self.call["traced_op"]) if tracer.enabled else -1
        self.t_start = time.perf_counter()
        deadline = self.t_start + seconds
        i = 0
        while time.perf_counter() < deadline:
            op = i % len(self.ops)
            self.attempted += 1
            t0 = time.perf_counter()
            try:
                with (tracer.slice(SLICE_SPAN) if i == traced_at
                      else contextlib.nullcontext()):
                    with jax.profiler.TraceAnnotation(SLICE_SPAN):
                        res = self._commit(op)
                    t1 = time.perf_counter()     # before the trace is written
            except Exception as e:  # noqa: BLE001 -- counted, the loop goes on
                self.failed += 1
                self.errors.append(f"op {i}: {type(e).__name__}: {e}")
                if self.failed >= 3:
                    break
                i += 1
                continue
            self.completed.append({
                "i": i, "op": op, "t0": t0, "t1": t1, "traced": i == traced_at,
                "hashed_nodes": res[-1].hashed_nodes if res else 0,
                "roots": [r.root for r in res],
                "branch_nodes": ([r.branch_nodes for r in res]
                                 if i < FULL_ANSWERS else None),
            })
            i += 1
        self.t_end = self.completed[-1]["t1"] if self.completed else time.perf_counter()

    def release(self) -> None:
        self.committer = None
        gc.collect()

    # -- after the window -----------------------------------------------------

    def _work(self, op: int) -> tuple[int, int]:
        if op not in self._work_cache:
            self._work_cache[op] = trie_work(self.ops[op], self.start_depth)
        return self._work_cache[op]

    def facts(self) -> dict:
        """Counts of the window, the benchmark's own: node hashes are counted
        from the tries (harness/work.py), never from what the program says."""
        hashes = sum(self._work(c["op"])[0] for c in self.completed)
        blocks = sum(self._work(c["op"])[1] for c in self.completed)
        out = {"ops": len(self.completed), "hashes": hashes, "blocks": blocks,
               "mhashes": hashes / 1e6,
               "seconds": self.t_end - self.t_start,
               "op_seconds": [c["t1"] - c["t0"] for c in self.completed]}
        out["notes"] = list(self.notes)
        traced = [c for c in self.completed if c["traced"]]
        if traced:
            out["slice_work"] = keccak_work(*self._work(traced[0]["op"]))
            out["slice_seconds"] = traced[0]["t1"] - traced[0]["t0"]
        return out

    def end_to_end(self) -> dict:
        f = self.facts()
        if not f["ops"]:
            return {}
        return {self.call["rate_metric"]: f["hashes"] / f["seconds"]}

    def check(self) -> list[tuple[str, float, float]]:
        """(name, number, limit) of everything compared; all limits are 0:
        the comparison is exact. Every job of every completed operation is
        compared (its root always, its branch nodes in the window's first
        ``FULL_ANSWERS`` operations); a job the reference did
        not get to counts as unchecked."""
        bad_roots = bad_branches = missing = count_gap = unchecked = 0
        jobs_checked = 0
        distinct = sorted({c["op"] for c in self.completed})
        refs = _reference_answers(
            [(op, j) for op in distinct for j in range(len(self.ops[op]))],
            self.ops, self.start_depth)
        for c in self.completed:
            jobs, roots = self.ops[c["op"]], c["roots"]
            count_gap += abs(int(c["hashed_nodes"]) - self._work(c["op"])[0])
            missing += max(0, len(jobs) - len(roots))
            for j in range(min(len(jobs), len(roots))):
                ref = refs.get((c["op"], j))
                if ref is None:
                    unchecked += 1
                    continue
                jobs_checked += 1
                bad_roots += roots[j] != ref.root
                if c["branch_nodes"] is None:
                    continue
                got = _plain(c["branch_nodes"][j])
                if got != ref.branches:
                    bad_branches += sum(got.get(p) != ref.branches.get(p)
                                        for p in set(got) | set(ref.branches))
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


def _reference_answers(todo: list, ops: list, start_depth: int) -> dict:
    """``(op, job) -> TrieResult`` of the plain reference. The reference is
    plain Python, so large jobs are built side by side in processes of their
    own that import nothing but ``benchmark.reference`` (no JAX: they cannot
    reach for the chip); each is waited for before this returns."""
    leaves = sum(len(ops[op][j][1]) for op, j in todo)
    workers = min(len(todo), max(1, (os.cpu_count() or 2) - 2), 8)
    if workers <= 1 or leaves < 200_000:
        return {(op, j): build_trie(*ops[op][j], start_depth) for op, j in todo}
    with ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as pool:
        futs = {(op, j): pool.submit(build_trie, *ops[op][j], start_depth)
                for op, j in todo}
        out = {}
        for key, f in futs.items():
            try:
                out[key] = f.result()
            except Exception:  # noqa: BLE001 -- a lost worker: job unchecked
                out[key] = None
        return out


def _plain(branch_nodes: dict) -> dict:
    """The program's branch records as plain tuples, path -> (state_mask,
    tree_mask, hash_mask, hashes): what the reference produces."""
    return {bytes(p): (b.state_mask, b.tree_mask, b.hash_mask, tuple(b.hashes))
            for p, b in branch_nodes.items()}


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merged(base[k], v) if isinstance(v, dict) and isinstance(
            base.get(k), dict) else v
    return out


def _resolved(obj, config: dict):
    """A value "config:<key>" in a traffic file is that key of the cell's
    configuration file, so that the sizes a deployment states drive the run."""
    if isinstance(obj, dict):
        return {k: _resolved(v, config) for k, v in obj.items()}
    if isinstance(obj, str) and obj.startswith("config:"):
        return config[obj.split(":", 1)[1]]
    return obj

