"""Metrics registry with a Prometheus text exposition endpoint.

Reference analogue: crates/metrics (metrics-rs facade + derive) and
crates/node/metrics (Prometheus server/recorder,
node/metrics/src/server.rs:22). Counters/gauges/histograms register
globally; the node serves GET /metrics from its HTTP server.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from dataclasses import dataclass, field

from . import tracing


# Sub-millisecond decades for device-dispatch and gateway/service
# timings: the old 1 ms floor swallowed every dispatch (a fused keccak
# dispatch is tens of µs on a healthy device), making queue-wait vs
# dispatch attribution invisible on /metrics.
SUB_MS_BUCKETS = (0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025,
                  0.005, 0.02, 0.1, 0.5, 2, 10)


@dataclass
class Counter:
    name: str
    help: str = ""
    value: float = 0.0
    # optional constant labels, rendered as name{k="v",...} — the
    # per-replica attribution shape (fleet_routed_total{replica="r1"}):
    # one Counter per label set, registered under the labeled key,
    # sharing one TYPE line per family on /metrics
    labels: dict | None = None
    # float += is a read-modify-write: unsynchronized concurrent
    # increments lose counts (every hot path here is multi-threaded)
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    def increment(self, amount: float = 1.0):
        with self._lock:
            self.value += amount


@dataclass
class Gauge:
    name: str
    help: str = ""
    value: float = 0.0
    # optional constant labels, rendered as name{k="v",...} — the
    # Prometheus *_info convention (build_info et al: value pinned to 1,
    # the identity lives in the labels)
    labels: dict | None = None
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    def set(self, value: float):
        with self._lock:
            self.value = value


@dataclass
class Histogram:
    """Fixed-bucket histogram (Prometheus cumulative buckets)."""

    name: str
    help: str = ""
    buckets: tuple[float, ...] = (0.001, 0.01, 0.1, 0.5, 1, 5, 30, 120)
    counts: list[int] = field(default_factory=list)
    total: float = 0.0
    n: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    def __post_init__(self):
        if not self.counts:
            self.counts = [0] * (len(self.buckets) + 1)

    def record(self, value: float):
        with self._lock:
            self.total += value
            self.n += 1
            for i, b in enumerate(self.buckets):
                if value <= b:
                    self.counts[i] += 1
                    return
            self.counts[-1] += 1

    def snapshot(self) -> tuple[list[int], float, int]:
        """Consistent (counts, total, n) copy — what render() and the
        health sampler read under the per-metric lock."""
        with self._lock:
            return list(self.counts), self.total, self.n

    def quantile(self, q: float) -> float | None:
        """Estimate the q-quantile from the cumulative buckets (lifetime
        counts; windowed estimates come from the health sampler's bucket
        deltas)."""
        counts, _, n = self.snapshot()
        if not n:
            return None
        return histogram_quantile(self.buckets, counts, q)


def histogram_quantile(buckets: tuple[float, ...], counts, q: float) -> float | None:
    """Prometheus-style quantile estimate from fixed-bucket counts.

    ``buckets`` are the upper bounds; ``counts`` are PER-BUCKET (not
    cumulative) observation counts with the +Inf overflow bucket last,
    so ``len(counts) == len(buckets) + 1``. Linear interpolation inside
    the target bucket (lower bound = previous edge, 0 for the first);
    a rank landing in the overflow bucket clamps to the highest finite
    edge (the Prometheus convention — the bucket has no upper bound to
    interpolate toward). Returns None when there are no observations.

    Shared by the SLO evaluator (windowed p99s from sampler bucket
    deltas), ``Histogram.quantile`` and bench/debug tooling — ad-hoc
    percentile math grows subtle rank-vs-index bugs, so there is ONE
    implementation.
    """
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile {q} outside [0, 1]")
    total = sum(counts)
    if total <= 0:
        return None
    rank = q * total
    seen = 0.0
    for i, b in enumerate(buckets):
        prev_seen = seen
        seen += counts[i]
        if seen >= rank:
            lo = buckets[i - 1] if i else 0.0
            if counts[i] == 0:  # exact bucket-boundary rank
                return lo
            frac = (rank - prev_seen) / counts[i]
            return lo + (b - lo) * frac
    return buckets[-1]  # overflow bucket: clamp to the last finite edge


def sample_percentile(sorted_samples, pct: int):
    """Nearest-rank percentile over an already-sorted sample list (the
    gas-oracle shape: small lists, integer percentile). One shared
    implementation for every sorted-sample percentile in the repo."""
    if not sorted_samples:
        return None
    idx = min(len(sorted_samples) - 1, len(sorted_samples) * pct // 100)
    return sorted_samples[idx]


def _label_str(labels: dict) -> str:
    return ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))


class MetricsRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict[str, object] = {}

    def _register(self, name: str, kind, factory):
        with self._lock:
            existing = self._metrics.get(name)
            if existing is None:
                existing = self._metrics[name] = factory()
            elif not isinstance(existing, kind):
                raise TypeError(
                    f"metric {name!r} already registered as {type(existing).__name__}"
                )
            return existing

    def counter(self, name: str, help: str = "",
                labels: dict | None = None) -> Counter:
        # a labeled counter registers under its full labeled key so one
        # family holds many series (per-replica attribution); the bare
        # name stays available for the family's unlabeled aggregate
        key = name if not labels else f"{name}{{{_label_str(labels)}}}"
        return self._register(
            key, Counter, lambda: Counter(name, help, labels=labels))

    def gauge(self, name: str, help: str = "", labels: dict | None = None) -> Gauge:
        g = self._register(name, Gauge, lambda: Gauge(name, help, labels=labels))
        if labels is not None:
            g.labels = labels
        return g

    def histogram(self, name: str, help: str = "", **kw) -> Histogram:
        h = self._register(name, Histogram, lambda: Histogram(name, help, **kw))
        if kw.get("buckets") and h.buckets != kw["buckets"]:
            raise ValueError(f"metric {name!r} registered with different buckets")
        return h

    def items(self) -> list[tuple[str, object]]:
        """Stable (name, metric) snapshot — the health sampler's walk.
        The metric objects are live; read histograms via snapshot()."""
        with self._lock:
            return sorted(self._metrics.items())

    def render(self) -> str:
        """Prometheus text exposition format."""
        lines = []
        typed: set[str] = set()  # one TYPE line per labeled family
        with self._lock:
            for name, m in sorted(self._metrics.items()):
                if isinstance(m, Counter):
                    if m.name not in typed:
                        typed.add(m.name)
                        lines.append(f"# TYPE {m.name} counter")
                    if m.labels:
                        lines.append(
                            f"{m.name}{{{_label_str(m.labels)}}} {m.value}")
                    else:
                        lines.append(f"{name} {m.value}")
                elif isinstance(m, Gauge):
                    lines.append(f"# TYPE {name} gauge")
                    if m.labels:
                        lbl = ",".join(f'{k}="{v}"'
                                       for k, v in sorted(m.labels.items()))
                        lines.append(f"{name}{{{lbl}}} {m.value}")
                    else:
                        lines.append(f"{name} {m.value}")
                elif isinstance(m, Histogram):
                    lines.append(f"# TYPE {name} histogram")
                    with m._lock:  # consistent bucket/count/sum snapshot
                        counts, total, n = list(m.counts), m.total, m.n
                    cum = 0
                    for b, c in zip(m.buckets, counts):
                        cum += c
                        lines.append(f'{name}_bucket{{le="{b}"}} {cum}')
                    lines.append(f'{name}_bucket{{le="+Inf"}} {n}')
                    lines.append(f"{name}_sum {total}")
                    lines.append(f"{name}_count {n}")
        return "\n".join(lines) + "\n"


# the global registry (metrics-rs global recorder analogue)
REGISTRY = MetricsRegistry()

_PROC_START = None
_BUILD_INFO: dict | None = None


def build_info() -> dict:
    """Node-identity labels for the fleet: package version, git revision
    (when the repo is available), jax version, and the configured device
    backend. Computed once — subprocess + metadata probes must not tax
    every /metrics scrape or health sample."""
    global _BUILD_INFO
    if _BUILD_INFO is not None:
        return _BUILD_INFO
    import os

    from . import __version__

    info = {"version": __version__}
    try:
        import subprocess

        r = subprocess.run(
            ["git", "-C", os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5)
        if r.returncode == 0 and r.stdout.strip():
            info["git"] = r.stdout.strip()
    except Exception:  # noqa: BLE001 — identity is best-effort
        pass
    try:
        from importlib.metadata import version as _pkg_version

        info["jax"] = _pkg_version("jax")
    except Exception:  # noqa: BLE001
        pass
    info["backend"] = os.environ.get("JAX_PLATFORMS", "") or "device"
    _BUILD_INFO = info
    return info


def update_process_metrics(registry: MetricsRegistry | None = None) -> None:
    """Process-level gauges from /proc/self (reference crates/node/metrics
    process collector: RSS, CPU time, fds, threads, uptime). Called at
    scrape time by the /metrics endpoint; silently a no-op off-Linux."""
    global _PROC_START
    reg = registry or REGISTRY
    import os
    import time as _t

    if _PROC_START is None:
        _PROC_START = _t.time()
    reg.gauge("process_uptime_seconds").set(round(_t.time() - _PROC_START, 1))
    # fleet identity: which build/toolchain/backend is this node? (the
    # Prometheus *_info convention — value 1, identity in the labels)
    reg.gauge("reth_tpu_build_info",
              "node build identity: version/git/jax/backend",
              labels=build_info()).set(1)
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        reg.gauge("process_resident_memory_bytes").set(
            pages * os.sysconf("SC_PAGE_SIZE"))
        with open("/proc/self/stat") as f:
            parts = f.read().rsplit(")", 1)[1].split()
        tck = os.sysconf("SC_CLK_TCK")
        # fields (post-comm): utime=11 stime=12 num_threads=17 (0-based)
        reg.gauge("process_cpu_seconds_total").set(
            round((int(parts[11]) + int(parts[12])) / tck, 2))
        reg.gauge("process_threads").set(int(parts[17]))
        reg.gauge("process_open_fds").set(len(os.listdir("/proc/self/fd")))
    except (OSError, IndexError, ValueError):
        pass


class TrieMetrics:
    """TrieTracker analogue (reference crates/trie metrics): per-commit
    stats for the state-commitment hot path — node/leaf counts, level
    depth, host→device wire bytes, wall time, split by backend — and the
    host seconds of each phase of a turbo commit (:meth:`phase`)."""

    # a turbo commit's phases in the order a commit runs them (trie/turbo.py
    # marshal..stage, predecode and collect..decode; ops/fused_commit.py
    # assemble..enqueue and device_wait..fetch).
    # "pack" is the pipelined path's alone (RebuildPipeline: one window's
    # levels merged across subtries); there marshal, sweep and the levels'
    # extraction run on the sweep pool and their counters hold
    # thread-seconds. A backend that hashes as it is fed (the numpy twin,
    # the per-level engines) does so inside "stage". With branch nodes
    # collected, "predecode" is what needs no digest: one result a job (a
    # phase's worth for a storage chunk of tens of thousands of tries) and
    # the first half of the branch decode, run after the backend's
    # ``launch`` and before its wait; then "collect" lays the roots in from
    # the fetched arena and "decode" the child hashes. Roots alone:
    # "collect" builds the results after the fetch.
    PHASES = ("marshal", "sweep", "pack", "stage", "assemble", "upload",
              "enqueue", "predecode", "device_wait", "fetch", "collect",
              "decode")

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._phase_s = {k: reg.counter(f"trie_commit_{k}_seconds_total")
                         for k in self.PHASES}
        self._marshal_cpu = reg.counter(
            "trie_commit_marshal_cpu_seconds_total",
            "CPU seconds of the threads inside marshal: over its wall, the "
            "share not spent waiting for the interpreter or the allocator")
        self._decode_records = reg.counter("trie_commit_decode_records_total")
        self._predecode_records = reg.counter(
            "trie_commit_predecode_records_total",
            "branch records whose digest-free half of the decode ran before "
            "the wait for the device")
        self._nodes = {k: reg.counter(f"trie_commit_nodes_total_{k}")
                       for k in ("device", "numpy")}
        self._leaves = reg.counter("trie_commit_leaves_total")
        self._wire = reg.counter("trie_commit_wire_bytes_total")
        self._commits = reg.counter("trie_commits_total")
        self._seconds = reg.histogram("trie_commit_duration_seconds",
                                      buckets=SUB_MS_BUCKETS)
        self._levels = reg.histogram(
            "trie_commit_levels", buckets=(2, 4, 6, 8, 10, 12, 16))
        self.last: dict | None = None  # most recent commit, for bench triage

    def record_commit(self, backend: str, nodes: int, levels: int,
                      leaves: int, wire_bytes: int, seconds: float) -> None:
        self._nodes.get(backend, self._nodes["numpy"]).increment(nodes)
        self._leaves.increment(leaves)
        self._wire.increment(wire_bytes)
        self._commits.increment()
        self._seconds.record(seconds)
        self._levels.record(levels)
        self.last = {"backend": backend, "nodes": nodes, "levels": levels,
                     "leaves": leaves, "wire_bytes": wire_bytes,
                     "seconds": round(seconds, 4)}

    def record_decode(self, records: int) -> None:
        """Branch records turned into BranchNodes by one decode call:
        ``trie_commit_decode_seconds_total`` over this is seconds a
        record, whatever the chunk size."""
        self._decode_records.increment(records)

    def record_predecode(self, records: int) -> None:
        """Branch records whose paths, masks and digest rows one call of
        the decode's first half made, before the wait for the device."""
        self._predecode_records.increment(records)

    def phase_seconds(self) -> dict[str, float]:
        """Every phase's counter as it stands: a run's phases are the
        difference of two readings."""
        return {k: c.value for k, c in self._phase_s.items()}

    @contextlib.contextmanager
    def phase(self, name: str):
        """One phase of a commit, measured where the work happens: a
        ``trie::commit`` span (on the profiler's clock too) whose wall
        adds to ``trie_commit_<name>_seconds_total`` and whose field
        ``cpu_s`` is the thread's CPU seconds inside it; ``marshal``'s add
        to ``trie_commit_marshal_cpu_seconds_total`` too (the phase that
        holds the interpreter on the sweep pool). While it runs it is the
        thread's open phase (``tracing.current_phase``: what a collector
        pass interrupted). One per phase per commit or window, never per
        level, row or record."""
        t0 = time.perf_counter()
        c0 = time.thread_time()
        cpu = 0.0
        prev = tracing.set_phase(name)
        try:
            with tracing.span("trie::commit", name) as ctx:
                try:
                    yield
                finally:
                    cpu = time.thread_time() - c0
                    if ctx is not None:
                        ctx.fields["cpu_s"] = round(cpu, 6)
        finally:
            tracing.set_phase(prev)
            self._phase_s[name].increment(time.perf_counter() - t0)
            if name == "marshal":
                self._marshal_cpu.increment(cpu)


trie_metrics = TrieMetrics()


class PipelineMetrics:
    """Rebuild-pipeline observability (trie/turbo.py RebuildPipeline):
    the consumer's wait for the next sweep in order, swept groups parked,
    sweep pool occupancy, window/packing counts, and queue drains onto the
    CPU twin after a mid-rebuild device trip. Where a run's seconds went is
    the phases' own counters (``TrieMetrics.phase``): ``last`` holds their
    movement over the run, and the collector's seconds in it."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._wait_s = reg.counter(
            "trie_pipeline_wait_seconds_total",
            "the consumer blocked on the next sweep in submission order")
        self._runs = reg.counter("trie_pipeline_runs_total")
        self._windows = reg.counter(
            "trie_pipeline_windows_total",
            "cross-subtrie packed dispatch windows")
        self._subtries = reg.counter("trie_pipeline_subtries_total")
        self._groups = reg.counter(
            "trie_pipeline_groups_total",
            "sweep groups laid out: one native build and one decode call each")
        self._leaves = reg.counter(
            "trie_pipeline_leaves_total",
            "leaves of every sweep group laid out")
        self._largest = reg.counter(
            "trie_pipeline_largest_group_leaves_total",
            "leaves of each commit's largest sweep group: over the leaves of "
            "all, the share of a chunk that one thread marshals and extracts "
            "alone (its large jobs are swept on several)")
        self._threaded_jobs = reg.counter(
            "trie_sweep_threaded_jobs_total",
            "jobs of SWEEP_THREADS * LEAVES_PER_SWEEP leaves or more: swept "
            "on SWEEP_THREADS threads inside the native call")
        self._threaded_leaves = reg.counter(
            "trie_sweep_threaded_leaves_total",
            "leaves of those jobs: over trie_pipeline_leaves_total, the share "
            "of the leaves laid out that several threads sweep")
        self._drains = reg.counter(
            "trie_pipeline_queue_drains_total",
            "windows hashed on the CPU twin after a mid-rebuild failover")
        self._qdepth = reg.gauge(
            "trie_pipeline_queue_depth",
            "sweeps finished ahead of the consumer, parked until their turn "
            "in submission order")
        self._busy = reg.gauge(
            "trie_pipeline_pool_busy", "native sweeps currently running")
        self.last: dict | None = None  # most recent run, for events/bench

    def set_queue_depth(self, n: int) -> None:
        self._qdepth.set(n)

    def set_pool_busy(self, n: int) -> None:
        self._busy.set(n)

    def record_threaded_sweeps(self, jobs: int, leaves: int) -> None:
        self._threaded_jobs.increment(jobs)
        self._threaded_leaves.increment(leaves)

    def record_run(self, *, jobs: int, groups: int, leaves: int,
                   largest_group_leaves: int, windows: int,
                   queue_peak: int, drained_windows: int, backend,
                   wall_s: float, wait: float, phases: dict[str, float],
                   gc_s: float, gc_full_s: float) -> None:
        self._runs.increment()
        self._windows.increment(windows)
        self._subtries.increment(jobs)
        self._groups.increment(groups)
        self._leaves.increment(leaves)
        self._largest.increment(largest_group_leaves)
        self._drains.increment(drained_windows)
        self._wait_s.increment(round(wait, 6))
        self.last = {
            "jobs": jobs, "groups": groups, "leaves": leaves,
            "largest_group_leaves": largest_group_leaves, "windows": windows,
            "queue_peak": queue_peak, "drained_windows": drained_windows,
            "backend": backend, "wall_s": round(wall_s, 4),
            "wait_s": round(wait, 4), "gc_s": round(gc_s, 4),
            "gc_full_s": round(gc_full_s, 4),
            # seconds of each phase of the run; those on the sweep pool
            # (marshal, sweep, the groups' stage) are thread-seconds
            "phases": {k: round(v, 4) for k, v in phases.items()},
        }


pipeline_metrics = PipelineMetrics()


class CollectorMetrics:
    """Python's cyclic garbage collector, seen from a ``gc.callbacks`` hook:
    a pass runs inside whatever phase allocated last, so without this its
    seconds are charged to that phase. Each pass is a ``python::gc``
    annotation on the profiler's clock (the device trace then names the host
    seconds it took), adds its wall to ``python_gc_seconds_total`` (and, a
    full pass of generation 2, to ``python_gc_full_seconds_total``) and one
    to ``python_gc_passes_total_gen<n>``; with tracing on it is a ``python``
    ``gc`` span in the flight recorder, with the generation, the objects
    collected and ``during``, the phase it interrupted. The hook only looks:
    when and how the collector runs is left as it is."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._seconds = reg.counter(
            "python_gc_seconds_total",
            "wall of the cyclic collector's passes, in whatever phase")
        self._full = reg.counter(
            "python_gc_full_seconds_total",
            "of which full passes (generation 2): every long-lived object")
        self._passes = tuple(
            reg.counter(f"python_gc_passes_total_gen{g}",
                        f"collector passes of generation {g}")
            for g in range(3))
        # one pass at a time in a process: the collector does not re-enter
        self._t0 = 0.0
        self._wall0 = 0.0
        self._ann = None

    def seconds(self) -> tuple[float, float]:
        """(all passes, full passes) as the counters stand."""
        return self._seconds.value, self._full.value

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._ann = tracing.annotation("python::gc")
            self._wall0 = time.time()
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        gen = info["generation"]
        self._seconds.increment(dt)
        if gen == 2:
            self._full.increment(dt)
        self._passes[gen].increment()
        # deferred: the pass may have begun inside an exporter's lock
        tracing.record_span(
            "python", "gc", self._wall0, dt, deferred=True,
            fields={"generation": gen, "collected": info["collected"],
                    "during": tracing.current_phase()})


gc_metrics = CollectorMetrics()


def _install_gc_hook(hook: CollectorMetrics) -> None:
    """Make ``hook`` the process's one collector hook: one that this module
    put there before (an import of it again) is taken out first."""
    gc.callbacks[:] = [cb for cb in gc.callbacks
                       if type(cb).__module__ != __name__]
    gc.callbacks.append(hook)


_install_gc_hook(gc_metrics)


class MerkleStageMetrics:
    """The clean ``MerkleStage``'s storage chunk (``stages/merkle.py``
    ``_storage_chunk``), leg by leg, the way :meth:`TrieMetrics.phase`
    times a commit: ``read`` (one cursor a trie over ``HashedStorages``,
    each slot decoded and RLP-encoded), ``commit`` (the ``_commit_subtries``
    call) and ``write`` (the branch nodes into ``StoragesTrie``, the
    storage roots into ``HashedAccounts``, the progress blob). Each leg is a
    ``stages::merkle:<leg>`` span (on the profiler's clock too) whose wall
    adds to ``stage_merkle_<leg>_seconds_total``; beside them the chunk's
    counts. The transaction's commit that follows is ``DbMetrics``'."""

    LEGS = ("read", "commit", "write")

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._leg_s = {k: reg.counter(f"stage_merkle_{k}_seconds_total")
                       for k in self.LEGS}
        self._slots = reg.counter(
            "stage_merkle_slots_read_total",
            "storage slots a chunk read from HashedStorages")
        self._nodes = reg.counter(
            "stage_merkle_branch_nodes_written_total",
            "branch nodes a chunk put into StoragesTrie")
        self._accounts = reg.counter(
            "stage_merkle_accounts_written_total",
            "HashedAccounts entries a chunk gave a new storage root")

    @contextlib.contextmanager
    def leg(self, name: str):
        t0 = time.perf_counter()
        try:
            with tracing.span("stages::merkle", name):
                yield
        finally:
            self._leg_s[name].increment(time.perf_counter() - t0)

    def record_chunk(self, slots: int, nodes: int, accounts: int) -> None:
        self._slots.increment(slots)
        self._nodes.increment(nodes)
        self._accounts.increment(accounts)


merkle_stage_metrics = MerkleStageMetrics()


class DbMetrics:
    """A read-write transaction's commit (``storage/native.py``
    ``NativeTx.commit``): its wall, the engine's syncs included, is the
    annotation ``storage::commit`` on the profiler's clock (with tracing on,
    a ``storage`` ``commit`` flight-recorder span with ``pages``) and adds
    to ``db_commit_seconds_total``; ``db_commits_total`` counts the commits
    and ``db_commit_pages_written_total`` the pages they wrote, as the
    paged engine counts them (``rtpg_pages_written``: dirty pages and the
    free list's chain)."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._seconds = reg.counter(
            "db_commit_seconds_total",
            "wall of read-write commits, the engine's fdatasync calls included")
        self._commits = reg.counter("db_commits_total")
        self._pages = reg.counter(
            "db_commit_pages_written_total",
            "pages the paged engine's commits wrote")

    @contextlib.contextmanager
    def commit(self, pages_written=None):
        """Around one commit; ``pages_written()`` reads the engine's running
        count of pages written, or None where it keeps none."""
        p0 = pages_written() if pages_written is not None else None
        ann = tracing.annotation("storage::commit")
        wall0 = time.time()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            pages = (pages_written() - p0) if p0 is not None else 0
            self._seconds.increment(dt)
            self._commits.increment()
            self._pages.increment(pages)
            tracing.record_span("storage", "commit", wall0, dt,
                                fields={"pages": pages})


db_metrics = DbMetrics()


class SparseCommitMetrics:
    """Parallel sparse-commit observability (trie/sparse.py
    ParallelSparseCommitter + the proof-worker pool): packed levels and
    fused dispatches per block, encode-pool occupancy, proof-worker
    depth, and the live-tip finish wall — what an operator needs to see
    that the storage-heavy commit actually packed across tries instead
    of degrading to per-trie per-depth calls."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._commits = reg.counter(
            "sparse_commit_commits_total", "parallel packed commits run")
        self._levels = reg.counter(
            "sparse_commit_levels_packed_total",
            "global depth levels packed across tries")
        self._dispatches = reg.counter(
            "sparse_commit_dispatches_total",
            "fused hash dispatches issued (one per packed depth)")
        self._hashed = reg.counter(
            "sparse_commit_hashed_nodes_total")
        self._chunks = reg.counter(
            "sparse_commit_encode_chunks_total",
            "lower-subtrie RLP encode chunks fanned across the pool")
        self._streamed = reg.counter(
            "sparse_commit_streamed_chunks_total",
            "encode chunks streamed to the hash service's live lane")
        self._encode_busy = reg.gauge(
            "sparse_commit_encode_pool_busy",
            "encode chunks currently in flight on the pool")
        self._proof_depth = reg.gauge(
            "sparse_commit_proof_worker_depth",
            "sharded multiproof fetches currently outstanding")
        self._disp_per_block = reg.histogram(
            "sparse_commit_dispatches_per_block",
            buckets=(2, 4, 6, 8, 12, 16, 24, 32))
        self._finish = reg.histogram(
            "sparse_commit_finish_seconds",
            "live-tip sparse finish() wall clock",
            buckets=SUB_MS_BUCKETS)
        self.last: dict | None = None  # most recent commit, for events/bench

    def record_commit(self, stats: dict) -> None:
        self._commits.increment()
        self._levels.increment(stats.get("levels", 0))
        self._dispatches.increment(stats.get("dispatches", 0))
        self._hashed.increment(stats.get("hashed", 0))
        self._chunks.increment(stats.get("encode_chunks", 0))
        self._streamed.increment(stats.get("streamed", 0))
        self.last = dict(stats)

    def record_block(self, dispatches: int, finish_s: float) -> None:
        self._disp_per_block.record(dispatches)
        self._finish.record(finish_s)
        if self.last is not None:
            self.last["finish_s"] = round(finish_s, 4)

    def set_encode_busy(self, n: int) -> None:
        self._encode_busy.set(n)

    def set_proof_depth(self, n: int) -> None:
        self._proof_depth.set(n)


sparse_commit_metrics = SparseCommitMetrics()


class FusedCommitMetrics:
    """Fused-committer dispatch accounting (ops/fused_commit.py): how many
    device dispatches the commitment path actually issues, and how many
    trie levels each one carried. The whole-subtrie engine
    (``SubtrieFusedEngine``) exists to collapse O(depth) dispatches per
    block into O(1) per chunk — these are the numbers that prove (or
    disprove) it per commit, and the SLO rule in ``health.py`` pages when
    a k-level commit regresses back to per-level dispatch counts."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._dispatches = reg.counter(
            "fused_dispatches_total",
            "fused committer device dispatches issued")
        self._levels = reg.counter(
            "fused_levels_total", "trie levels carried by fused dispatches")
        self._levels_per = reg.histogram(
            "fused_levels_per_dispatch",
            "trie levels fused into one device dispatch",
            buckets=(1, 2, 4, 8, 16, 32, 64))
        self._per_block = reg.histogram(
            "fused_dispatches_per_block",
            "device dispatches one k-level fused commit issued",
            buckets=(1, 2, 4, 8, 16, 24, 32, 64, 128))
        self._fallbacks = reg.counter(
            "fused_subtrie_fallbacks_total",
            "k-level chunks degraded to the per-level or CPU path")
        self._h2d_bytes = reg.counter(
            "fused_h2d_bytes_total",
            "bytes of every host array the fused engines put on the device")
        self._d2h_bytes = reg.counter(
            "fused_d2h_bytes_total",
            "bytes the fused engines' terminal fetches brought back")
        self._rows_dispatched = reg.counter(
            "fused_rows_dispatched_total",
            "row tier of every staged level the device ran")
        self._rows_needed = reg.counter(
            "fused_rows_needed_total",
            "trie nodes among those rows (the padding row is not one)")
        self._arena_grows = reg.counter(
            "fused_arena_grows_total",
            "ensure() calls that raised the digest arena's tier")
        self._gather_operand_bytes = reg.counter(
            "fused_gather_operand_bytes_total",
            "bytes of the staging buffer each packed dispatch's row read "
            "addresses (the level's extent)")
        self._gather_rows = reg.counter(
            "fused_gather_rows_total",
            "row tier of every packed dispatch whose rows were read")
        self._branch_index_elems = reg.counter(
            "fused_branch_index_elems_total",
            "elements of every index array the branch dispatches' gathers "
            "and scatters take")
        self._branch_rows = reg.counter(
            "fused_branch_rows_total",
            "row tier of every branch dispatch")
        self.last: dict | None = None  # most recent commit, for events/bench
        self.dispatches_cum = 0  # lifetime count (bench deltas)

    def record_dispatch(self, levels: int) -> None:
        self._dispatches.increment()
        self._levels.increment(levels)
        self._levels_per.record(levels)
        self.dispatches_cum += 1

    def record_fallback(self) -> None:
        self._fallbacks.increment()

    def record_h2d(self, nbytes: int) -> None:
        self._h2d_bytes.increment(nbytes)

    def record_d2h(self, nbytes: int) -> None:
        self._d2h_bytes.increment(nbytes)

    def record_arena_grow(self) -> None:
        self._arena_grows.increment()

    def record_rows(self, dispatched: int, needed: int) -> None:
        self._rows_dispatched.increment(dispatched)
        self._rows_needed.increment(needed)

    def record_gather(self, operand_bytes: int, rows: int) -> None:
        self._gather_operand_bytes.increment(operand_bytes)
        self._gather_rows.increment(rows)

    def record_branch_index(self, index_elems: int, rows: int) -> None:
        self._branch_index_elems.increment(index_elems)
        self._branch_rows.increment(rows)

    def record_commit(self, *, dispatches: int, levels: int, k: int,
                      mode: str) -> None:
        """One k-level commit finished: ``dispatches`` device calls carried
        ``levels`` staged levels (``mode`` records which rung produced the
        digests — fused / perlevel / cpu)."""
        self._per_block.record(dispatches)
        self.last = {"k": k, "dispatches": dispatches, "levels": levels,
                     "mode": mode}


fused_metrics = FusedCommitMetrics()


class HotStateMetrics:
    """Hot-state plane observability (ISSUE 19: trie/hot_cache.py
    TrieNodeCache + ops/fused_commit.py DigestArena). Two families:

    - ``hotstate_cache_*``: cross-block node-cache hit/miss/evict
      counters and the stale/poison validation drops — hit rate is the
      signal the health SLO floor watches (a sustained collapse under
      steady import means the invalidation rules are wrong, not that
      consensus is at risk: validation-at-lookup turns staleness into
      misses, so this degrades, never pages).
    - ``hotstate_arena_*``: resident digest rows, delta-epoch vs
      full-upload counts, fault-driven evictions, and the delta-upload
      fraction histogram (staged rows over staged + reveal-stamped —
      the bench's <0.5 acceptance signal).
    """

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._hits = reg.counter(
            "hotstate_cache_hits_total",
            "node-cache lookups served (hash-validated)")
        self._misses = reg.counter(
            "hotstate_cache_misses_total",
            "node-cache lookups that paid a proof fetch")
        self._stale = reg.counter(
            "hotstate_cache_stale_drops_total",
            "entries dropped because keccak(rlp) != expected hash")
        self._poison = reg.counter(
            "hotstate_cache_poison_caught_total",
            "injected poisons caught by node-hash validation")
        self._cache_evictions = reg.counter(
            "hotstate_cache_evictions_total", "LRU bound evictions")
        self._clears = reg.counter(
            "hotstate_cache_clears_total",
            "wholesale invalidations (deep reorg / storm / injector)")
        self._entries = reg.gauge(
            "hotstate_cache_entries", "node-cache resident entries")
        self._hit_rate = reg.gauge(
            "hotstate_cache_hit_rate",
            "rolling lifetime hit rate (health SLO floor input)")
        self._rows = reg.gauge(
            "hotstate_arena_resident_rows",
            "digest rows resident in the cross-block device arena")
        self._leaked = reg.gauge(
            "hotstate_arena_leaked_rows",
            "allocated-but-unaccounted rows (invariant: 0)")
        self._delta_epochs = reg.counter(
            "hotstate_arena_delta_epochs_total",
            "commits that delta-uploaded against resident rows")
        self._full_epochs = reg.counter(
            "hotstate_arena_full_epochs_total",
            "commits that took the full-upload rung")
        self._arena_evictions = reg.counter(
            "hotstate_arena_evictions_total",
            "wholesale arena evictions (bound / fault / reorg)")
        self._faults = reg.counter(
            "hotstate_arena_faults_total",
            "delta epochs that died and fell back to full upload")
        self._delta_fraction = reg.histogram(
            "hotstate_delta_upload_fraction",
            "staged rows / (staged + reveal-stamped) per commit",
            buckets=(0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0))
        self._h2d = reg.histogram(
            "hotstate_h2d_bytes_per_commit",
            "bytes staged to the device per sparse finish",
            buckets=(1 << 12, 1 << 14, 1 << 16, 1 << 18, 1 << 20,
                     1 << 22, 1 << 24))
        self.last: dict | None = None  # most recent snapshot (events/bench)
        self._cache_prev: dict = {}
        self._arena_prev: dict = {}

    @staticmethod
    def _delta(prev: dict, cur: dict, key: str) -> int:
        """Counters arrive as lifetime totals from the cache/arena
        objects; convert to per-snapshot increments."""
        d = cur.get(key, 0) - prev.get(key, 0)
        return d if d > 0 else 0

    def record_cache(self, stats: dict) -> None:
        p = self._cache_prev
        self._hits.increment(self._delta(p, stats, "hits"))
        self._misses.increment(self._delta(p, stats, "misses"))
        self._stale.increment(self._delta(p, stats, "stale_drops"))
        self._poison.increment(self._delta(p, stats, "poison_caught"))
        self._cache_evictions.increment(self._delta(p, stats, "evictions"))
        self._clears.increment(self._delta(p, stats, "clears"))
        self._entries.set(stats.get("entries", 0))
        total = stats.get("hits", 0) + stats.get("misses", 0)
        rate = (stats.get("hits", 0) / total) if total else 0.0
        self._hit_rate.set(round(rate, 4))
        self._cache_prev = dict(stats)
        self.last = {**(self.last or {}), "cache": dict(stats),
                     "hit_rate": round(rate, 4)}

    def record_arena(self, snap: dict, *, delta_fraction: float,
                     staged_rows: int, stamped_rows: int, h2d_bytes: int,
                     fresh: bool) -> None:
        p = self._arena_prev
        self._arena_evictions.increment(self._delta(p, snap, "evictions"))
        self._faults.increment(self._delta(p, snap, "faults"))
        self._delta_epochs.increment(self._delta(p, snap, "delta_epochs"))
        self._full_epochs.increment(self._delta(p, snap, "full_epochs"))
        self._rows.set(snap.get("resident_rows", 0))
        self._leaked.set(snap.get("leaked_rows", 0))
        self._delta_fraction.record(delta_fraction)
        self._h2d.record(h2d_bytes)
        self._arena_prev = dict(snap)
        self.last = {**(self.last or {}), "arena": dict(snap),
                     "delta_fraction": round(delta_fraction, 4),
                     "staged_rows": staged_rows,
                     "stamped_rows": stamped_rows,
                     "h2d_bytes": h2d_bytes, "fresh": fresh}


hotstate_metrics = HotStateMetrics()


class ExecMetrics:
    """Parallel-execution observability: the optimistic scheduler
    (engine/optimistic.py — exec_parallel_*) and the BAL wave executor
    (engine/bal.py — exec_bal_*, previously computed but only stashed on
    ``EngineTree.last_bal_stats``). One place to compare BAL-hinted vs
    optimistic scheduling efficiency in production: how many ranks ran
    native/parallel, how many invalidated and re-ran serially, how many
    keys the async storage layer prefetched, and whether a block fell
    all the way back to the serial executor."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._blocks = reg.counter(
            "exec_parallel_blocks_total",
            "blocks executed by the optimistic scheduler")
        self._rounds = reg.counter(
            "exec_parallel_rounds_total", "native speculation rounds run")
        self._native = reg.counter(
            "exec_parallel_native_txs_total",
            "ranks committed from the native wave core")
        self._python = reg.counter(
            "exec_parallel_python_txs_total",
            "ranks committed through the Python interpreter")
        self._speculative = reg.counter(
            "exec_parallel_speculative_commits_total",
            "ranks whose validation-clean speculation committed directly")
        self._serial_rerun = reg.counter(
            "exec_parallel_serial_reruns_total",
            "invalidated ranks re-executed against the merged view")
        self._conflicts = reg.counter(
            "exec_parallel_conflicts_total",
            "native ranks demoted to an in-core serial re-run")
        self._misses = reg.counter(
            "exec_parallel_misses_total",
            "native rounds stopped by a snapshot miss")
        self._prefetched = reg.counter(
            "exec_parallel_prefetched_keys_total",
            "keys the async storage layer fetched in the background")
        self._fallbacks = reg.counter(
            "exec_parallel_fallbacks_total",
            "blocks that fell back to the serial executor")
        self._wall = reg.histogram(
            "exec_parallel_wall_seconds",
            "optimistic scheduler wall clock per block")
        self._bal_waves = reg.counter("exec_bal_waves_total")
        self._bal_parallel = reg.counter(
            "exec_bal_parallel_txs_total",
            "txs committed from conflict-free waves")
        self._bal_serial = reg.counter(
            "exec_bal_serial_txs_total",
            "txs demoted to serial re-execution")
        self._bal_native = reg.counter(
            "exec_bal_native_txs_total", "txs executed by the native core")
        self.last: dict | None = None      # optimistic, for the events line
        self.last_bal: dict | None = None  # BAL, for the events line

    def record_optimistic(self, stats: dict) -> None:
        self._blocks.increment()
        self._rounds.increment(stats.get("rounds", 0))
        self._native.increment(stats.get("native", 0))
        self._python.increment(stats.get("python", 0))
        self._speculative.increment(stats.get("speculative", 0))
        self._serial_rerun.increment(stats.get("serial_rerun", 0))
        self._conflicts.increment(stats.get("conflicts", 0))
        self._misses.increment(stats.get("misses", 0))
        self._prefetched.increment(stats.get("prefetched", 0))
        if stats.get("fallback"):
            self._fallbacks.increment()
        if "wall_s" in stats:
            self._wall.record(stats["wall_s"])
        self.last = dict(stats)

    def record_bal(self, stats: dict) -> None:
        self._bal_waves.increment(stats.get("waves", 0))
        self._bal_parallel.increment(stats.get("parallel", 0))
        self._bal_serial.increment(stats.get("serial", 0))
        self._bal_native.increment(stats.get("native", 0))
        self.last_bal = dict(stats)


exec_metrics = ExecMetrics()


class HashServiceMetrics:
    """Shared hash service observability (ops/hash_service.py): per-lane
    queue depth and request counts, coalesce factor (requests fused per
    dispatch), batch occupancy (messages over the padded tier), wait and
    service-time histograms, plus the failure-path counters (numpy-twin
    replays, backpressure rejects, lease bypasses) — what an operator
    needs to see whether small client batches actually fuse into
    full-rate dispatches and where requests spend their time."""

    _LANES = ("live", "payload", "rebuild", "proof")

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._requests = {l: reg.counter(
            f"hash_service_requests_total_{l}",
            f"requests submitted on the {l} lane") for l in self._LANES}
        self._msgs = {l: reg.counter(
            f"hash_service_msgs_total_{l}",
            f"messages submitted on the {l} lane") for l in self._LANES}
        self._qdepth = {l: reg.gauge(
            f"hash_service_queue_depth_{l}",
            f"messages waiting on the {l} lane") for l in self._LANES}
        self._rejects = {l: reg.counter(
            f"hash_service_rejects_total_{l}",
            f"backpressure rejections on the {l} lane") for l in self._LANES}
        self._dispatches = reg.counter(
            "hash_service_dispatches_total",
            "coalesced backend dispatches issued")
        self._coalesced = reg.counter(
            "hash_service_coalesced_requests_total",
            "requests fused into coalesced dispatches")
        self._hashed = reg.counter(
            "hash_service_hashed_msgs_total", "messages hashed")
        self._coalesce_factor = reg.gauge(
            "hash_service_coalesce_factor",
            "requests per dispatch, lifetime average (>1 = batching works)")
        self._occupancy = reg.gauge(
            "hash_service_batch_occupancy",
            "last dispatch: messages / padded batch tier")
        self._replays = reg.counter(
            "hash_service_replays_total",
            "coalesced batches replayed on the numpy twin after a failure")
        self._lease_bypasses = reg.counter(
            "hash_service_lease_bypass_total",
            "coalesced batches hashed on the CPU twin while leased")
        self._leases = reg.counter("hash_service_leases_total")
        self._lease_wait = reg.histogram(
            "hash_service_lease_wait_seconds",
            buckets=(0.0001, 0.001, 0.01, 0.1, 0.5, 1, 5, 30))
        self._wait = {l: reg.histogram(
            f"hash_service_wait_seconds_{l}",
            f"queue wait before dispatch, {l} lane",
            buckets=SUB_MS_BUCKETS)
            for l in self._LANES}
        self._service = reg.histogram(
            "hash_service_service_seconds",
            "coalesced dispatch wall time",
            buckets=SUB_MS_BUCKETS)

    def record_submit(self, lane: str, n_msgs: int) -> None:
        self._requests[lane].increment()
        self._msgs[lane].increment(n_msgs)

    def set_queue_depth(self, lane: str, n_msgs: int) -> None:
        self._qdepth[lane].set(n_msgs)

    def record_reject(self, lane: str) -> None:
        self._rejects[lane].increment()

    def record_wait(self, lane: str, seconds: float) -> None:
        self._wait[lane].record(seconds)

    def record_dispatch(self, *, requests: int, msgs: int, occupancy: float,
                        service_s: float, replayed: bool) -> None:
        self._dispatches.increment()
        self._coalesced.increment(requests)
        self._hashed.increment(msgs)
        self._coalesce_factor.set(
            round(self._coalesced.value / self._dispatches.value, 3))
        self._occupancy.set(round(occupancy, 4))
        self._service.record(service_s)

    def record_replay(self) -> None:
        self._replays.increment()

    def record_lease(self, wait_s: float) -> None:
        self._leases.increment()
        self._lease_wait.record(wait_s)

    def record_lease_bypass(self) -> None:
        self._lease_bypasses.increment()


class SupervisorMetrics:
    """Device hasher supervisor state on /metrics (ops/supervisor.py):
    breaker state + trips, mid-commit failovers, watchdog timeouts, and
    health-probe outcomes/latency — what an operator needs to see that the
    node degraded to the CPU hashing route and why."""

    # breaker state encoding for the gauge (alerting-friendly ordering)
    _STATES = {"closed": 0.0, "half_open": 1.0, "open": 2.0}

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._state = reg.gauge(
            "hasher_supervisor_breaker_state",
            "circuit breaker state: 0 closed, 1 half-open, 2 open")
        self._trips = reg.counter(
            "hasher_supervisor_breaker_trips_total",
            "times the breaker opened (device route disabled)")
        self._failovers = reg.counter(
            "hasher_supervisor_failovers_total",
            "mid-commit failovers replayed onto the CPU backend")
        self._timeouts = reg.counter(
            "hasher_supervisor_dispatch_timeouts_total",
            "device dispatches that exceeded the watchdog budget")
        self._probes = reg.counter("hasher_supervisor_probes_total")
        self._probe_failures = reg.counter(
            "hasher_supervisor_probe_failures_total")
        self._probe_seconds = reg.histogram(
            "hasher_supervisor_probe_duration_seconds",
            buckets=(0.1, 0.5, 1, 2, 5, 15, 60, 120))

    def set_state(self, state: str) -> None:
        self._state.set(self._STATES.get(state, 2.0))

    def record_trip(self) -> None:
        self._trips.increment()

    def record_failover(self) -> None:
        self._failovers.increment()

    def record_timeout(self) -> None:
        self._timeouts.increment()

    def record_probe(self, ok: bool, latency: float) -> None:
        self._probes.increment()
        if not ok:
            self._probe_failures.increment()
        self._probe_seconds.record(latency)


class WarmupMetrics:
    """Device warm-up manager observability (ops/warmup.py): menu progress
    (shapes warm/failed out of declared), per-shape compile walls, watchdog
    wedges and backoff retries, persistent-cache hits/misses,
    and how many dispatch buckets degraded-mode serving routed to the CPU
    twin — what an operator needs to see that the node is (still) paying
    compile cost, and whether restarts actually hit the on-disk cache."""

    _STATES = {"off": 0.0, "pending": 1.0, "warming": 2.0, "warm": 3.0,
               "degraded": 4.0}

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._state = reg.gauge(
            "warmup_state",
            "0 off, 1 pending, 2 warming, 3 warm, 4 degraded")
        self._total = reg.gauge(
            "warmup_shapes_total", "declared menu shapes")
        self._warm = reg.gauge(
            "warmup_shapes_warm", "menu shapes compiled and promoted")
        self._failed = reg.gauge(
            "warmup_shapes_failed",
            "menu shapes that exhausted their compile retries")
        self._compiles = reg.counter(
            "warmup_compiles_total", "successful AOT shape compiles")
        self._compile_s = reg.counter(
            "warmup_compile_seconds_total",
            "wall spent in successful warm-up compiles")
        self._compile_hist = reg.histogram(
            "warmup_compile_seconds", "per-shape AOT compile wall",
            buckets=(0.05, 0.25, 1, 5, 15, 60, 240, 1200))
        self._retries = reg.counter(
            "warmup_retries_total", "compile retries after a wedge/failure")
        self._wedges = reg.counter(
            "warmup_wedges_total",
            "compiles that exceeded the watchdog budget or raised")
        self._cpu_routed = reg.counter(
            "warmup_cpu_routed_total",
            "dispatch buckets served on the CPU twin while un-warm")
        self._cache_hits = reg.counter(
            "warmup_cache_hits_total",
            "shape compiles satisfied by the persistent cache")
        self._cache_misses = reg.counter(
            "warmup_cache_misses_total",
            "shape compiles that wrote new persistent-cache entries")
        self._cache_entries = reg.gauge(
            "warmup_cache_entries",
            "persistent-cache entries found when warm-up started")

    def set_state(self, state: str) -> None:
        self._state.set(self._STATES.get(state, 0.0))

    def set_progress(self, *, total: int, warm: int, failed: int) -> None:
        self._total.set(total)
        self._warm.set(warm)
        self._failed.set(failed)

    def record_compile(self, wall_s: float, cache_hit: bool | None) -> None:
        self._compiles.increment()
        self._compile_s.increment(round(wall_s, 6))
        self._compile_hist.record(wall_s)
        if cache_hit is True:
            self._cache_hits.increment()
        elif cache_hit is False:
            self._cache_misses.increment()

    def record_retry(self) -> None:
        self._retries.increment()

    def record_wedge(self) -> None:
        self._wedges.increment()

    def record_cpu_routed(self, n: int = 1) -> None:
        self._cpu_routed.increment(n)

    def set_cache_entries(self, n: int) -> None:
        self._cache_entries.set(n)


class MeshMetrics:
    """Device-mesh observability (parallel/mesh.py + the mesh-sharded
    hash service): mesh topology (total/healthy/leased devices), the
    per-device breaker degradation counters (shrinks, shrunken-mesh
    replays, recoveries), sub-mesh rebuild leases, and the partition-rule
    routing split (sharded vs unpartitioned dispatches) — what an
    operator needs to see that the mesh is serving degraded, and whether
    coalesced batches actually scatter across devices."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._total = reg.gauge(
            "mesh_devices_total", "devices in the hashing mesh roster")
        self._healthy = reg.gauge(
            "mesh_devices_healthy", "mesh devices passing their breaker")
        self._unhealthy = reg.gauge(
            "mesh_devices_unhealthy",
            "mesh devices shed by per-device breakers (SLO input)")
        self._leased = reg.gauge(
            "mesh_devices_leased",
            "devices currently claimed by a sub-mesh lease (rebuild)")
        self._shrinks = reg.counter(
            "mesh_shrinks_total",
            "times a breaker trip removed a device from the live mesh")
        self._recoveries = reg.counter(
            "mesh_recoveries_total",
            "devices re-admitted after their breaker cooldown")
        self._submesh_leases = reg.counter(
            "mesh_submesh_leases_total",
            "sub-mesh leases granted (rebuild claims k of n devices)")
        self._sharded = reg.counter(
            "mesh_sharded_dispatches_total",
            "coalesced dispatches batch-sharded across the mesh")
        self._single = reg.counter(
            "mesh_single_dispatches_total",
            "scalar/sub-threshold dispatches kept on one device")
        self._replays = reg.counter(
            "mesh_replays_total",
            "in-flight batches replayed on a shrunken mesh after a trip")

    def set_topology(self, *, total: int, healthy: int, leased: int) -> None:
        self._total.set(total)
        self._healthy.set(healthy)
        self._unhealthy.set(total - healthy)
        self._leased.set(leased)

    def record_shrink(self) -> None:
        self._shrinks.increment()

    def record_recovery(self) -> None:
        self._recoveries.increment()

    def record_submesh_lease(self) -> None:
        self._submesh_leases.increment()

    def record_sharded(self) -> None:
        self._sharded.increment()

    def record_single(self) -> None:
        self._single.increment()

    def record_replay(self) -> None:
        self._replays.increment()


class GatewayMetrics:
    """RPC serving gateway observability (rpc/gateway.py): per-class
    request counts, queue depth, running handlers, shed counts, and
    wait/service histograms, plus the coalescing/caching counters — what
    an operator needs to see that duplicate read bursts actually share
    work and where admission is queueing or shedding."""

    _CLASSES = ("engine", "read", "tx", "debug")

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._requests = {c: reg.counter(
            f"gateway_requests_total_{c}",
            f"requests admitted to the {c} class") for c in self._CLASSES}
        self._qdepth = {c: reg.gauge(
            f"gateway_queue_depth_{c}",
            f"requests waiting for a {c} slot") for c in self._CLASSES}
        self._running = {c: reg.gauge(
            f"gateway_running_{c}",
            f"handlers currently executing in the {c} class")
            for c in self._CLASSES}
        self._sheds = {c: reg.counter(
            f"gateway_sheds_total_{c}",
            f"requests shed with -32005 from the {c} class")
            for c in self._CLASSES}
        self._coalesced = {c: reg.counter(
            f"gateway_coalesced_total_{c}",
            f"{c} requests that shared an in-flight computation")
            for c in self._CLASSES}
        self._executions = reg.counter(
            "gateway_executions_total", "handler executions actually run")
        self._coalesce_factor = reg.gauge(
            "gateway_coalesce_factor",
            "coalescable requests served per execution (>1 = sharing works)")
        self._cache_hits = reg.counter("gateway_cache_hits_total")
        self._cache_misses = reg.counter("gateway_cache_misses_total")
        self._cache_hit_rate = reg.gauge(
            "gateway_cache_hit_rate", "response-cache hit fraction")
        self._invalidations = reg.counter(
            "gateway_cache_invalidations_total",
            "wholesale cache clears on canonical-head change")
        self._invalidated = reg.counter(
            "gateway_cache_invalidated_entries_total")
        self._wait = {c: reg.histogram(
            f"gateway_wait_seconds_{c}",
            f"admission wait before dispatch, {c} class",
            buckets=SUB_MS_BUCKETS)
            for c in self._CLASSES}
        self._service = {c: reg.histogram(
            f"gateway_service_seconds_{c}",
            f"handler execution wall time, {c} class",
            buckets=SUB_MS_BUCKETS)
            for c in self._CLASSES}

    def record_request(self, cls: str) -> None:
        self._requests[cls].increment()

    def set_queue_depth(self, cls: str, n: int) -> None:
        self._qdepth[cls].set(n)

    def set_running(self, cls: str, n: int) -> None:
        self._running[cls].set(n)

    def record_shed(self, cls: str) -> None:
        self._sheds[cls].increment()

    def record_coalesced(self, cls: str) -> None:
        self._coalesced[cls].increment()
        self._update_factor()

    def record_wait(self, cls: str, seconds: float) -> None:
        self._wait[cls].record(seconds)

    def record_service(self, cls: str, seconds: float) -> None:
        self._service[cls].record(seconds)
        self._executions.increment()
        self._update_factor()

    def _update_factor(self) -> None:
        ex = self._executions.value
        if ex:
            served = (ex + self._cache_hits.value
                      + sum(c.value for c in self._coalesced.values()))
            self._coalesce_factor.set(round(served / ex, 3))

    def record_cache(self, *, hit: bool) -> None:
        (self._cache_hits if hit else self._cache_misses).increment()
        total = self._cache_hits.value + self._cache_misses.value
        self._cache_hit_rate.set(round(self._cache_hits.value / total, 4))

    def record_invalidation(self, entries: int) -> None:
        self._invalidations.increment()
        self._invalidated.increment(entries)


class DeviceCompileTracker:
    """Per-shape compile-vs-execute attribution for the device kernels
    (ops/keccak_jax.py, ops/fused_commit.py): a program is built lazily on
    the first call of each (kind, shape) pair, so a "slow dispatch" is
    often a build in disguise. Every jitted call site reports here; the
    FIRST call of a shape counts as its build (its wall holds the XLA
    compile or, where the persistent compilation cache has the program,
    the load from the cache), later calls as steady-state dispatches, whose
    walls add to ``keccak_dispatch_seconds_total`` (over
    ``keccak_dispatch_total``: the mean call). Surfaced as keccak_compile_*
    / keccak_dispatch_* metrics, a flight-recorder event per first call,
    and per-shape stats for bench.py's compile_wall_s split."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._compiles = reg.counter(
            "keccak_compile_total", "distinct device program shapes compiled")
        self._compile_s = reg.counter(
            "keccak_compile_seconds_total",
            "wall spent on first-call (compiling) dispatches")
        self._dispatches = reg.counter(
            "keccak_dispatch_total", "steady-state device dispatches")
        self._dispatch_s = reg.histogram(
            "keccak_dispatch_seconds",
            "steady-state (post-compile) dispatch wall",
            buckets=SUB_MS_BUCKETS)
        self._dispatch_total_s = reg.counter(
            "keccak_dispatch_seconds_total",
            "steady-state dispatch walls: the host inside the call, waiting "
            "for the runtime's queue included")
        self._lock = threading.Lock()
        self.shapes: dict = {}  # shape key -> {compile_s, calls, execute_s}

    def record(self, kind: str, shape, seconds: float) -> bool:
        """Report one jitted call; returns True when it was the shape's
        first call (its compile or its load from the cache)."""
        key = (kind,) + tuple(shape if isinstance(shape, (tuple, list))
                              else (shape,))
        with self._lock:
            st = self.shapes.get(key)
            first = st is None
            if first:
                st = self.shapes[key] = {
                    "compile_s": round(seconds, 6), "calls": 0,
                    "execute_s": 0.0}
            else:
                st["calls"] += 1
                st["execute_s"] = round(st["execute_s"] + seconds, 6)
        if first:
            self._compiles.increment()
            self._compile_s.increment(round(seconds, 6))
            # the shape's first call: its compile, or its load from the
            # persistent cache in a process whose cache holds it
            tracing.event("ops::compile", "first_compile", kind=kind,
                          shape=str(shape), wall_s=round(seconds, 4))
        else:
            self._dispatches.increment()
            self._dispatch_s.record(seconds)
            self._dispatch_total_s.increment(seconds)
        return first

    def totals(self) -> dict:
        """Aggregate compile/execute walls (bench compile_wall_s split)."""
        with self._lock:
            return {
                "shapes": len(self.shapes),
                "compile_wall_s": round(
                    sum(s["compile_s"] for s in self.shapes.values()), 6),
                "execute_wall_s": round(
                    sum(s["execute_s"] for s in self.shapes.values()), 6),
                "execute_calls": sum(
                    s["calls"] for s in self.shapes.values()),
            }


compile_tracker = DeviceCompileTracker()


class KeccakRouteMetrics:
    """``KeccakDevice`` buckets that hashed on the CPU twin instead of the
    device (ops/keccak_jax.py), by reason: ``over_ceiling`` — messages
    above the declared block-tier ceiling (by design); ``unwarmed`` — the
    warm-up manager had not promoted the bucket's shape yet."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._buckets = {
            reason: reg.counter(
                f"keccak_cpu_bucket_total_{reason}",
                f"KeccakDevice buckets hashed on the CPU twin ({reason})")
            for reason in ("over_ceiling", "unwarmed")}

    def record_cpu_bucket(self, reason: str) -> None:
        self._buckets[reason].increment()


keccak_route_metrics = KeccakRouteMetrics()


class WalMetrics:
    """Write-ahead-log + startup-recovery observability (storage/wal.py,
    storage/recovery.py): append/checkpoint cadence, segment size, torn
    bytes discarded on replay, quarantined images/jars, and the
    recovery_status gauge the health engine's durability rule watches —
    the numbers that say whether a kill -9 right now would lose more
    than the persistence threshold."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._appends = reg.counter(
            "wal_appends_total", "fsync'd commit records appended")
        self._bytes = reg.counter(
            "wal_bytes_written_total", "record bytes appended (framed)")
        self._checkpoints = reg.counter(
            "wal_checkpoints_total", "image+manifest checkpoints taken")
        self._segment_bytes = reg.gauge(
            "wal_segment_bytes", "bytes in the live WAL segment")
        self._gen = reg.gauge("wal_generation", "current WAL generation")
        self._replayed = reg.counter(
            "recovery_wal_records_replayed_total",
            "commit records applied during startup replay")
        self._torn = reg.counter(
            "recovery_torn_bytes_total",
            "torn WAL tail bytes discarded during startup replay")
        self._quarantined = reg.counter(
            "recovery_quarantined_total",
            "corrupt images/jars quarantined aside at startup")
        self._status = reg.gauge(
            "recovery_status",
            "last startup recovery: 0 ok, 1 degraded (healed), 2 failed")
        self._problems = reg.gauge(
            "recovery_problems", "problems reported by the last recovery")
        self._mgr = None
        self.last_recovery: dict | None = None  # events line fragment

    def attach(self, manager) -> None:
        """Bind the live DurabilityManager so the sampler-facing gauges
        track it (called from storage/wal.py on attach)."""
        self._mgr = manager
        s = manager.snapshot()
        self._gen.set(s["gen"])
        self._segment_bytes.set(s["segment_bytes"])
        self._replayed.increment(s["replayed"])
        self._torn.increment(s["torn_bytes"])

    def record_append(self, nbytes: int, segment_bytes: int) -> None:
        self._appends.increment()
        self._bytes.increment(nbytes)
        self._segment_bytes.set(segment_bytes)

    def record_checkpoint(self, manager) -> None:
        self._checkpoints.increment()
        s = manager.snapshot()
        self._gen.set(s["gen"])
        self._segment_bytes.set(s["segment_bytes"])

    def record(self, report: dict) -> None:
        """Push one startup-recovery report (storage/recovery.py)."""
        level = {"ok": 0, "degraded": 1, "failed": 2}.get(
            report.get("status", "ok"), 2)
        self._status.set(level)
        self._problems.set(len(report.get("problems", ())))
        self._quarantined.increment(len(report.get("quarantined", ())))
        self.last_recovery = {
            "status": report.get("status"),
            "head": report.get("head_number"),
            "replayed": report.get("replayed_records", 0),
            "torn_bytes": report.get("torn_bytes", 0),
            "quarantined": len(report.get("quarantined", ())),
            "healed": len(report.get("healed", ())),
            "root_verified": report.get("root_verified"),
            "wall_s": report.get("wall_s"),
        }


wal_metrics = WalMetrics()
recovery_metrics = wal_metrics  # one surface: recovery_* lives beside wal_*


class EngineTreeMetrics:
    """Consensus-robustness observability for the engine tree
    (engine/tree.py + engine/block_buffer.py): invalid-header cache
    occupancy vs its bound (an invalid-payload flood must plateau, not
    grow), orphan-buffer depth and evictions, reorg cadence/depth, storm
    detections with their backoff state, and in-flight inserts cancelled
    by a competing forkchoiceUpdated — the numbers that say whether a
    hostile CL is actually hurting the node."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._invalid = reg.gauge(
            "tree_invalid_cached",
            "invalid-header cache entries (bounded LRU)")
        self._invalid_evictions = reg.counter(
            "tree_invalid_evictions_total",
            "invalid-cache entries evicted at the bound")
        self._orphans = reg.gauge(
            "tree_orphans_buffered",
            "blocks buffered awaiting an unknown parent")
        self._orphan_evictions = reg.counter(
            "tree_orphan_evictions_total",
            "buffered orphans evicted (bound or TTL)")
        self._orphan_replays = reg.counter(
            "tree_orphan_replays_total",
            "buffered children replayed when their parent arrived")
        self._reorgs = reg.counter("tree_reorgs_total")
        self._deep_reorgs = reg.counter(
            "tree_deep_reorgs_total",
            "reorgs that unwound the persisted chain")
        self._depth = reg.histogram(
            "tree_reorg_depth", "blocks abandoned per reorg",
            buckets=(1, 2, 3, 5, 8, 13, 21, 34))
        self._storms = reg.counter(
            "tree_reorg_storms_total",
            "reorg-storm detections (flight recorder dumped)")
        self._backoff = reg.gauge(
            "tree_reorg_backoff_active",
            "1 while reorg-storm backoff disables speculation")
        self._cancelled = reg.counter(
            "tree_payloads_cancelled_total",
            "in-flight inserts aborted by a forkchoice reorg")
        # events-line fragment state (node/events.py tree[...])
        self.last: dict = {}

    def set_invalid(self, n: int, cap: int) -> None:
        self._invalid.set(n)
        self.last["invalid"] = n
        self.last["invalid_cap"] = cap

    def invalid_evicted(self) -> None:
        self._invalid_evictions.increment()
        self.last["invalid_evicted"] = self.last.get("invalid_evicted", 0) + 1

    def set_orphans(self, n: int) -> None:
        self._orphans.set(n)
        self.last["orphans"] = n

    def orphan_evicted(self) -> None:
        self._orphan_evictions.increment()
        self.last["orphans_evicted"] = self.last.get("orphans_evicted", 0) + 1

    def orphans_replayed(self, n: int = 1) -> None:
        self._orphan_replays.increment(n)
        self.last["replayed"] = self.last.get("replayed", 0) + n

    def record_reorg(self, depth: int, deep: bool = False) -> None:
        self._reorgs.increment()
        if deep:
            self._deep_reorgs.increment()
        self._depth.record(depth)
        self.last["reorgs"] = self.last.get("reorgs", 0) + 1
        self.last["max_depth"] = max(self.last.get("max_depth", 0), depth)

    def storm(self) -> None:
        self._storms.increment()
        self.last["storms"] = self.last.get("storms", 0) + 1

    def set_backoff(self, active: bool) -> None:
        self._backoff.set(1 if active else 0)
        self.last["backoff"] = bool(active)

    def payload_cancelled(self) -> None:
        self._cancelled.increment()
        self.last["cancelled"] = self.last.get("cancelled", 0) + 1


tree_metrics = EngineTreeMetrics()


class BlockPipelineMetrics:
    """Cross-block import pipeline observability
    (engine/block_pipeline.py): speculations started/adopted/aborted
    (aborts labeled by ladder rung), commit-window cadence, the measured
    exec-inside-commit overlap fraction, and double-buffer sub-mesh
    leases — the numbers that say whether back-to-back import is
    actually overlapping exec with commit and why speculations die."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._reg = reg
        self._depth = reg.gauge(
            "block_pipeline_depth", "configured import pipeline depth")
        self._started = reg.counter(
            "block_pipeline_speculations_total",
            "speculative next-block executions started")
        self._adopted = reg.counter(
            "block_pipeline_committed_total",
            "speculations adopted after the parent committed VALID")
        self._aborted = reg.counter(
            "block_pipeline_aborted_total",
            "speculations discarded (any abort-ladder rung)")
        self._abort_reason: dict[str, Counter] = {}
        self._windows = reg.counter(
            "block_pipeline_commit_windows_total",
            "commit windows published by the insert path")
        self._window_wall = reg.histogram(
            "block_pipeline_commit_window_seconds",
            "commit-window wall clock (open to close)")
        self._overlap = reg.histogram(
            "block_pipeline_overlap_fraction",
            "speculative exec wall inside the parent's commit window")
        self._leases = reg.counter(
            "block_pipeline_submesh_leases_total",
            "double-buffer sub-mesh leases taken for speculation")
        # events-line fragment state (node/events.py pipe[...])
        self.last: dict = {}

    def set_depth(self, depth: int) -> None:
        self._depth.set(depth)
        self.last["depth"] = depth

    def window_opened(self) -> None:
        self._windows.increment()

    def window_closed(self, ok: bool, wall: float) -> None:
        self._window_wall.record(wall)

    def speculation_started(self) -> None:
        self._started.increment()
        self.last["spec"] = self.last.get("spec", 0) + 1

    def speculation_adopted(self, overlap_fraction: float) -> None:
        self._adopted.increment()
        self._overlap.record(overlap_fraction)
        self.last["adopted"] = self.last.get("adopted", 0) + 1
        self.last["overlap"] = overlap_fraction

    def speculation_aborted(self, reason: str) -> None:
        self._aborted.increment()
        c = self._abort_reason.get(reason)
        if c is None:
            c = self._reg.counter(
                "block_pipeline_aborted_reason_total",
                "speculations discarded, by abort-ladder rung",
                labels={"reason": reason})
            self._abort_reason[reason] = c
        c.increment()
        self.last["aborted"] = self.last.get("aborted", 0) + 1
        self.last["last_abort"] = reason

    def lease_taken(self, devices: int) -> None:
        self._leases.increment()
        self.last["lease_devices"] = devices


block_pipeline_metrics = BlockPipelineMetrics()


class FleetMetrics:
    """Replica-fleet observability (fleet/ring.py + fleet/feed.py):
    ring membership by state, per-request routing/failover counters,
    feed fanout health (witness bytes per block, subscriber count,
    generation failures), and the worst per-replica feed lag — the
    numbers that say whether the fleet is actually absorbing read
    traffic and which replica the ring shed."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._reg = reg
        # per-replica routing attribution: replica-id-labeled series
        # beside the unlabeled aggregates, created lazily per replica —
        # a hot or flappy replica is visible on /metrics without
        # log-diving (satellite contract)
        self._per_replica: dict[tuple, Counter] = {}
        self._registered = reg.gauge(
            "fleet_replicas_registered", "replicas known to the ring")
        self._healthy = reg.gauge(
            "fleet_replicas_healthy", "replicas currently in the ring")
        self._draining = reg.gauge(
            "fleet_replicas_draining",
            "replicas shed from the ring (degraded, still probed)")
        self._unreachable = reg.gauge(
            "fleet_replicas_unreachable",
            "replicas shed from the ring (transport-dead, still probed)")
        self._max_lag = reg.gauge(
            "fleet_feed_lag_heads",
            "worst per-replica feed lag behind the full node's head")
        self._routed = reg.counter(
            "fleet_routed_total", "reads served by a ring replica")
        self._failovers = reg.counter(
            "fleet_failovers_total",
            "reads that failed over to the next ring position")
        self._local = reg.counter(
            "fleet_local_fallbacks_total",
            "reads answered by the local full node (ladder's last rung)")
        self._shed = reg.counter(
            "fleet_sheds_total", "replicas shed from the ring")
        self._heals = reg.counter(
            "fleet_heals_total", "shed replicas re-admitted on recovery")
        self._subscribers = reg.gauge(
            "fleet_feed_subscribers", "replicas subscribed to the feed")
        self._witness_bytes = reg.histogram(
            "fleet_witness_bytes", "witness feed record size per block",
            buckets=(1024, 4096, 16384, 65536, 262144, 1048576, 4194304))
        self._witness_failures = reg.counter(
            "fleet_witness_failures_total",
            "blocks whose witness generation failed (record skipped)")
        self._feed_drops = reg.counter(
            "fleet_feed_dropped_blocks_total",
            "blocks dropped from a full feed queue (replicas re-anchor)")

    def set_replicas(self, *, registered: int, healthy: int, draining: int,
                     unreachable: int, max_lag: int) -> None:
        self._registered.set(registered)
        self._healthy.set(healthy)
        self._draining.set(draining)
        self._unreachable.set(unreachable)
        self._max_lag.set(max_lag)

    def _replica_counter(self, family: str, help: str, rid: str) -> Counter:
        key = (family, rid)
        c = self._per_replica.get(key)
        if c is None:
            c = self._per_replica[key] = self._reg.counter(
                family, help, labels={"replica": rid})
        return c

    def record_routed(self, rid: str | None = None) -> None:
        self._routed.increment()
        if rid:
            self._replica_counter(
                "fleet_routed_total",
                "reads served by a ring replica", rid).increment()

    def record_failover(self, rid: str | None = None) -> None:
        self._failovers.increment()
        if rid:
            self._replica_counter(
                "fleet_failovers_total",
                "reads that failed over off this replica", rid).increment()

    def record_local_fallback(self) -> None:
        self._local.increment()

    def record_shed(self) -> None:
        self._shed.increment()

    def record_heal(self) -> None:
        self._heals.increment()

    def set_subscribers(self, n: int) -> None:
        self._subscribers.set(n)

    def record_witness(self, size: int) -> None:
        self._witness_bytes.record(size)

    def record_witness_failure(self) -> None:
        self._witness_failures.increment()

    def record_feed_drop(self) -> None:
        self._feed_drops.increment()


class ReplicaMetrics:
    """Replica-process observability (fleet/replica.py): validated
    blocks + stateless-validation wall, feed lag as the replica itself
    sees it, validation failures, and reads refused because the witness
    never revealed the path (-32001 → gateway failover)."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._validated = reg.counter(
            "replica_blocks_validated_total",
            "blocks validated through StatelessChain")
        self._validate_wall = reg.histogram(
            "replica_validate_seconds",
            "stateless re-execution + root recompute wall per block",
            buckets=SUB_MS_BUCKETS)
        self._failures = reg.counter(
            "replica_validation_failures_total",
            "fed blocks that failed stateless validation (skipped)")
        self._lag = reg.gauge(
            "replica_feed_lag_heads",
            "announced head minus validated head")
        self._blinded = reg.counter(
            "replica_blinded_reads_total",
            "reads refused with -32001 (path not in the witness)")

    def record_validated(self, wall_s: float) -> None:
        self._validated.increment()
        self._validate_wall.record(wall_s)

    def record_validation_failure(self) -> None:
        self._failures.increment()

    def set_lag(self, lag: int) -> None:
        self._lag.set(lag)

    def record_blinded(self) -> None:
        self._blinded.increment()


class StandbyMetrics:
    """Hot-standby observability (fleet/standby.py): replay lag behind
    the leader's heartbeat head (the HA SLO input), applied vs rejected
    shipped records by rejection class, resync churn, the promotion
    ladder position, and time-to-promote."""

    _STATES = ("following", "catching-up", "promoting", "leading",
               "failed")

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._reg = reg
        self._lag = reg.gauge(
            "standby_replay_lag_heads",
            "leader heartbeat head minus the standby's applied head")
        self._epoch = reg.gauge(
            "standby_leader_epoch", "leader epoch the standby tracks")
        self._state = reg.gauge(
            "standby_promotion_state",
            "promotion ladder position (0=following .. 3=leading, "
            "-1=failed)")
        self._applied = reg.counter(
            "standby_records_applied_total",
            "shipped WAL records applied to the standby's store")
        self._rejected: dict[str, Counter] = {}
        self._resync_requests = reg.counter(
            "standby_resync_requests_total",
            "gap/corruption re-anchors requested from the leader")
        self._resync_applied = reg.counter(
            "standby_resyncs_applied_total",
            "full table images applied (stream re-anchored)")
        self._promote_wall = reg.histogram(
            "standby_promote_seconds",
            "heartbeat-loss/fleet_promote to feed-serving wall",
            buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0))
        self._promote_failures = reg.counter(
            "standby_promote_failures_total",
            "promotions aborted (root verification / node launch)")

    def set_lag(self, lag: int) -> None:
        self._lag.set(lag)

    def set_epoch(self, epoch: int) -> None:
        self._epoch.set(epoch)

    def set_state(self, state: str) -> None:
        self._state.set(self._STATES.index(state)
                        if state in self._STATES[:-1] else -1)

    def record_applied(self) -> None:
        self._applied.increment()

    def record_rejected(self, kind: str) -> None:
        c = self._rejected.get(kind)
        if c is None:
            c = self._rejected[kind] = self._reg.counter(
                "standby_records_rejected_total",
                "shipped records refused (crc / stale_epoch / "
                "generation / gap)", labels={"reason": kind})
        c.increment()

    def record_resync_request(self) -> None:
        self._resync_requests.increment()

    def record_resync_applied(self) -> None:
        self._resync_applied.increment()

    def record_promotion(self, wall_s: float | None = None,
                         failed: bool = False) -> None:
        if failed:
            self._promote_failures.increment()
        elif wall_s is not None:
            self._promote_wall.record(wall_s)


class PoolMetrics:
    """Write-path firehose observability (pool/pool.py +
    pool/batcher.py): pool events by kind (admissions, replacements,
    drops labeled by reason), admission-queue sheds (the -32005
    backpressure ladder firing), and the pt_* records shipped to the
    fleet — the numbers that say whether the firehose is being absorbed
    or shed, and whether replicas are hearing about it."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._reg = reg
        self._events: dict[tuple, Counter] = {}
        self._sheds = reg.counter(
            "pool_admission_sheds_total",
            "tx submissions refused -32005 (admission queue saturated)")
        self._shipped = reg.counter(
            "pool_feed_records_total",
            "pt_* pool records shipped to feed subscribers")
        self._feed_drops = reg.counter(
            "pool_feed_dropped_total",
            "pt_* records dropped at a saturated subscriber queue")
        # events-line fragment state (node/events.py pool[...])
        self.last: dict = {}

    def on_event(self, kind: str, reason: str | None = None) -> None:
        key = (kind, reason or "")
        c = self._events.get(key)
        if c is None:
            c = self._events[key] = self._reg.counter(
                "pool_events_total",
                "pool events by kind (add/replace/drop/canon) and "
                "drop reason",
                labels={"kind": kind, "reason": reason or ""})
        c.increment()
        if kind != "canon":
            self.last[kind] = self.last.get(kind, 0) + 1

    def record_shed(self) -> None:
        self._sheds.increment()
        self.last["sheds"] = self.last.get("sheds", 0) + 1

    def record_shipped(self, n: int = 1) -> None:
        self._shipped.increment(n)
        self.last["shipped"] = self.last.get("shipped", 0) + n

    def record_feed_drop(self, n: int = 1) -> None:
        self._feed_drops.increment(n)
        self.last["feed_drops"] = self.last.get("feed_drops", 0) + n

    def shed_total(self) -> int:
        return int(self.last.get("sheds", 0))


pool_metrics = PoolMetrics()


class ProducerMetrics:
    """Continuous block production observability (payload/producer.py):
    refresh cadence and wall, ranks executed fresh vs replayed from a
    checkpoint, candidate size, and staleness — the numbers that say
    whether the hot candidate is actually incremental (reexec ≪ ranks)
    and keeping up with the firehose."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry or REGISTRY
        self._refreshes = reg.counter(
            "producer_refreshes_total",
            "incremental candidate refreshes (full rebuilds included)")
        self._refresh_wall = reg.histogram(
            "producer_refresh_seconds",
            "one incremental refresh: restore + replay + greedy tail",
            buckets=SUB_MS_BUCKETS)
        self._fresh = reg.counter(
            "producer_ranks_executed_total",
            "candidate ranks executed against new stream entries")
        self._reexec = reg.counter(
            "producer_ranks_replayed_total",
            "known-good selected ranks replayed from a checkpoint")
        self._ranks = reg.gauge(
            "producer_candidate_ranks", "txs in the hot candidate")
        self._staleness = reg.gauge(
            "producer_staleness_seconds",
            "how long the hot candidate has lagged the pool (SLO input)")
        # events-line fragment state (node/events.py build[...])
        self.last: dict = {}

    def record_refresh(self, wall_s: float, ranks: int, reexec: int,
                       fresh: int) -> None:
        self._refreshes.increment()
        self._refresh_wall.record(wall_s)
        if fresh > 0:
            self._fresh.increment(fresh)
        if reexec > 0:
            self._reexec.increment(reexec)
        self._ranks.set(ranks)
        self.last["refreshes"] = self.last.get("refreshes", 0) + 1
        self.last["ranks"] = ranks
        self.last["reexec"] = self.last.get("reexec", 0) + reexec
        self.last["fresh"] = self.last.get("fresh", 0) + fresh
        self.last["wall_s"] = wall_s

    def sync_ranks(self, ranks: int) -> None:
        self._ranks.set(ranks)
        self.last["ranks"] = ranks

    def set_staleness(self, seconds: float) -> None:
        self._staleness.set(seconds)
        self.last["staleness_s"] = seconds


producer_metrics = ProducerMetrics()
