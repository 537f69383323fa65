"""Node health & SLO engine: metric time-series retention, declarative
SLO rules with burn-rate alerting, and component/node health roll-up.

Reference analogue: the reference splits raw telemetry from *judgment
about* telemetry — every subsystem exports metrics, but the node also
knows whether it is healthy (crates/node/events' status lines, the
consensus/engine health surfaces). Eight PRs of instrumentation gave
this repo the raw side (``metrics.py`` registries, ``tracing.py`` spans
+ flight recorder); this module is the layer that CONSUMES it, so a
breach pages the node itself instead of waiting for a human to stare at
the events line — and gives the coming replica fleet (ROADMAP item 4) a
machine-readable per-node health surface a gateway can route around.

Shape:

- **Time-series retention** (:class:`MetricsSampler`): a background
  sampler snapshots every counter/gauge/histogram in a
  :class:`~reth_tpu.metrics.MetricsRegistry` at a fixed interval into
  bounded ring buffers — counters delta-encoded (cumulative value +
  per-interval delta, reset-safe), gauges by value, histograms as
  per-interval bucket deltas so WINDOWED quantiles (a real p99 over the
  last N seconds, not a lifetime average) come from
  :func:`~reth_tpu.metrics.histogram_quantile` over summed deltas.
  Queryable via the ``debug_metricsHistory`` RPC and consumed by the
  evaluator below.
- **Declarative SLO rules** (:class:`SloRule`, :func:`default_rules`):
  each rule derives one value from the ring buffers — a gauge level, a
  windowed counter rate, a ratio of counter deltas, a windowed histogram
  quantile, or a callable (the block-import wall reads
  ``tracing.recent_block_summaries()``) — and compares it to a budget.
  The comparison is expressed as a *burn signal* (value/budget; inverted
  for floor rules like cache hit rate), evaluated over **fast and slow
  burn windows**: the fast window (last ``fast_n`` samples) flips a
  component to ``degraded`` within one evaluation window of a breach;
  ``failing`` needs the fast burn over ``failing_factor`` AND the slow
  window burning too (the classic multi-window burn-rate rule — a blip
  degrades, only a sustained burn escalates). An EWMA baseline of each
  rule's value rides along for drill-down (is this breach 1.1x or 20x
  normal?). Recovery has hysteresis (``recovery`` < 1).
- **Breach side effects**: a state escalation increments
  ``slo_breaches_total``, records a structured breach (surfaced on the
  events line as the ``slo[...]`` fragment and via ``debug_sloStatus``),
  and auto-dumps the flight recorder through
  :func:`tracing.fault_event` — same rate-limited path as every
  ``RETH_TPU_FAULT_*`` drill, so a breach storm cannot spray the disk.
  ``RETH_TPU_FAULT_SLO_BREACH=<rule|all>`` forces breaches for drills.
- **Health roll-up**: per-component ``ok | degraded | failing`` (worst
  rule wins), rolled into node health (worst component wins), served by
  ``GET /health`` beside ``/metrics`` (503 only when failing) and the
  ``debug_healthCheck`` RPC, with build identity from
  :func:`metrics.build_info` so a fleet can tell its nodes apart.
- **Perf-regression sentinel** (:class:`BenchBaselineStore`): a
  trailing last-N-good-runs store keyed by (metric, mode, backend,
  warmup state) that ``bench.py`` consults to stamp ``vs_prev`` /
  ``regression`` on every bench line — a real throughput regression
  fails loudly.

Wiring: ``--health`` (cli.py) / ``[node] health`` (reth.toml) builds one
engine per node over the global registry, installs it as the process
default (:func:`install`) for the ``/health`` endpoint and debug RPCs,
and starts the sampler thread at ``slo_interval`` seconds.
``interval <= 0`` disables the thread — tests drive :meth:`tick`
manually for determinism.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from pathlib import Path

from . import tracing
from .metrics import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    build_info,
    histogram_quantile,
)

STATES = ("ok", "degraded", "failing")
_SEVERITY = {"ok": 0, "degraded": 1, "failing": 2}

DEFAULT_INTERVAL_S = 1.0
DEFAULT_WINDOW = 300  # retained samples per series (5 min at 1 Hz)


# -- time-series retention ----------------------------------------------------


class MetricsSampler:
    """Bounded ring-buffer retention over a metrics registry.

    One :meth:`sample` call walks the registry and appends one point per
    metric: counters as ``(ts, cumulative, delta)`` (delta-encoded; a
    counter reset — cumulative going backwards — re-bases the delta),
    gauges as ``(ts, value)``, histograms as ``(ts, n_delta, sum_delta,
    bucket_deltas)``. Windowed derivations (rates, ratios, quantiles)
    aggregate the per-interval deltas, so they reflect the window, not
    the process lifetime.
    """

    def __init__(self, registry=None, window: int = DEFAULT_WINDOW):
        self.registry = registry or REGISTRY
        self.window = max(2, int(window))
        self._lock = threading.Lock()
        self._series: dict[str, dict] = {}
        self.samples = 0

    def sample(self, now: float | None = None) -> None:
        now = time.time() if now is None else now
        with self._lock:
            for name, m in self.registry.items():
                s = self._series.get(name)
                if isinstance(m, Counter):
                    v = m.value
                    if s is None:
                        s = self._series[name] = {
                            "kind": "counter", "last": v,
                            "points": deque(maxlen=self.window)}
                    delta = v - s["last"]
                    if delta < 0:  # counter reset: re-base
                        delta = v
                    s["points"].append((now, v, delta))
                    s["last"] = v
                elif isinstance(m, Gauge):
                    if s is None:
                        s = self._series[name] = {
                            "kind": "gauge",
                            "points": deque(maxlen=self.window)}
                    s["points"].append((now, m.value))
                elif isinstance(m, Histogram):
                    counts, total, n = m.snapshot()
                    if s is None:
                        # first sight is a BASELINE (zero delta): lifetime
                        # counts predate the retention window, and a
                        # polluted pre-engine history must not read as a
                        # one-interval burst
                        s = self._series[name] = {
                            "kind": "histogram", "buckets": m.buckets,
                            "last": (counts, total, n),
                            "points": deque(maxlen=self.window)}
                        prev = (counts, total, n)
                    else:
                        prev = s["last"]
                    if n < prev[2]:  # histogram reset
                        prev = ([0] * len(counts), 0.0, 0)
                    s["points"].append((
                        now, n - prev[2], total - prev[1],
                        tuple(c - p for c, p in zip(counts, prev[0]))))
                    s["last"] = (counts, total, n)
            self.samples += 1

    # -- queries ------------------------------------------------------------

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._series)

    def kind(self, name: str) -> str | None:
        with self._lock:
            s = self._series.get(name)
            return s["kind"] if s else None

    def points(self, name: str, n: int | None = None) -> list[dict] | None:
        """Ring-buffer tail as JSON-shaped points (debug_metricsHistory)."""
        with self._lock:
            s = self._series.get(name)
            if s is None:
                return None
            pts = list(s["points"])
            kind = s["kind"]
            buckets = s.get("buckets")
        if n:
            pts = pts[-n:]
        if kind == "counter":
            return [{"ts": round(p[0], 3), "value": p[1], "delta": p[2]}
                    for p in pts]
        if kind == "gauge":
            return [{"ts": round(p[0], 3), "value": p[1]} for p in pts]
        out = []
        for p in pts:
            entry = {"ts": round(p[0], 3), "count": p[1],
                     "sum": round(p[2], 6)}
            if p[1]:
                entry["p50"] = round(histogram_quantile(buckets, p[3], 0.5), 6)
                entry["p99"] = round(histogram_quantile(buckets, p[3], 0.99), 6)
            out.append(entry)
        return out

    def latest(self, name: str) -> float | None:
        """Most recent gauge value (or counter cumulative)."""
        with self._lock:
            s = self._series.get(name)
            if s is None or not s["points"] or s["kind"] == "histogram":
                return None
            return s["points"][-1][1]

    def delta(self, name: str, samples: int) -> float:
        """Counter increase over the last ``samples`` intervals (0 when
        the series is unknown — a subsystem that never registered)."""
        with self._lock:
            s = self._series.get(name)
            if s is None or s["kind"] != "counter":
                return 0.0
            return sum(p[2] for p in list(s["points"])[-samples:])

    def rate(self, name: str, samples: int) -> float | None:
        """Counter increase per second over the last ``samples`` points."""
        with self._lock:
            s = self._series.get(name)
            if s is None or s["kind"] != "counter" or len(s["points"]) < 2:
                return None
            pts = list(s["points"])[-(samples + 1):]
            elapsed = max(pts[-1][0] - pts[0][0], 1e-6)
            return sum(p[2] for p in pts[1:]) / elapsed

    def quantile(self, name: str, q: float,
                 samples: int) -> float | None:
        """Windowed quantile: merge the last ``samples`` intervals'
        bucket deltas, estimate via histogram_quantile. None when the
        window saw no observations (idle subsystem)."""
        with self._lock:
            s = self._series.get(name)
            if s is None or s["kind"] != "histogram":
                return None
            pts = list(s["points"])[-samples:]
            buckets = s["buckets"]
        if not pts:
            return None
        merged = [0] * (len(buckets) + 1)
        for p in pts:
            for i, d in enumerate(p[3]):
                merged[i] += d
        return histogram_quantile(buckets, merged, q)


# -- declarative SLO rules ----------------------------------------------------


@dataclass
class SloRule:
    """One budgeted judgment over the ring buffers.

    ``kind``: ``gauge`` (latest level of ``metric``) | ``rate``
    (windowed counter increase/s) | ``ratio`` (sum of ``metrics_num``
    deltas over sum of ``metrics_den`` deltas, guarded by ``min_den``
    activity) | ``quantile`` (windowed ``q`` over ``metric``'s buckets)
    | ``callable`` (``source(engine, rule)`` — non-metric inputs like
    tracing block summaries).

    ``op``: ``>`` budgets a ceiling (burn = value/budget), ``<`` a floor
    (burn = budget/value) — burn > 1 means violating either way.
    """

    name: str
    component: str
    kind: str
    budget: float
    metric: str | None = None
    metrics_num: tuple = ()
    metrics_den: tuple = ()
    q: float = 0.99
    op: str = ">"
    window: int = 10          # samples aggregated per evaluation
    # fast burn window: 1 by default — rule values are already aggregated
    # over ``window`` samples, so one evaluation flips to degraded (the
    # acceptance contract); raise it for noisy instantaneous gauges
    fast_n: int = 1
    slow_n: int = 30          # slow burn window (samples)
    failing_factor: float = 2.0  # fast burn needed to escalate to failing
    recovery: float = 0.9     # fast burn under this recovers (hysteresis)
    min_den: float = 0.0      # ratio rules: required denominator activity
    ewma_alpha: float = 0.1
    source: object = None     # kind == "callable"
    unit: str = ""
    help: str = ""


def _block_wall_ms(engine: "HealthEngine", rule: SloRule) -> float | None:
    """Mean closed-block import wall over the rule window (needs
    --trace-blocks: the summaries come from tracing's block roots)."""
    window_s = rule.window * (engine.interval or 1.0)
    now = time.time()
    walls = [s["total_ms"] for s in tracing.recent_block_summaries()
             if now - s.get("ts", 0.0) <= window_s]
    return sum(walls) / len(walls) if walls else None


def _fleet_unhealthy(engine: "HealthEngine", rule: SloRule) -> float | None:
    """Replicas shed from the gateway ring: draining + unreachable.
    None (rule idle) when the node never registered fleet gauges —
    fleet mode off."""
    draining = engine.sampler.latest("fleet_replicas_draining")
    unreachable = engine.sampler.latest("fleet_replicas_unreachable")
    if draining is None and unreachable is None:
        return None
    return (draining or 0) + (unreachable or 0)


def _federation(with_replicas: bool = True):
    """The installed metrics federation, or None (fleet obs off / no
    replicas pulled yet — the rules stay idle rather than paging on an
    empty fleet)."""
    from .obs import federation as federation_mod

    fed = federation_mod.get_federation()
    if fed is None:
        return None
    if with_replicas and not fed.snapshot()["replicas"]:
        return None
    return fed


def _fleet_read_p99(engine: "HealthEngine", rule: SloRule) -> float | None:
    """Fleet-wide read-serving p99 across every replica's gateway
    (obs/federation.py bucket-wise merge): the latency the fleet's
    users actually see, windowed over the federation's pull rings."""
    fed = _federation()
    if fed is None:
        return None
    return fed.fleet_quantile("gateway_service_seconds_read", 0.99,
                              samples=rule.window)


def _fleet_lag_worst(engine: "HealthEngine", rule: SloRule) -> float | None:
    """Worst replica feed lag AS THE REPLICAS REPORT IT (the federated
    replica_feed_lag_heads gauge) — the distribution's max; the ring
    prober sees the same number, but this one survives the prober being
    wedged."""
    fed = _federation()
    if fed is None:
        return None
    return fed.replica_gauge_max("replica_feed_lag_heads")


def _fleet_stale(engine: "HealthEngine", rule: SloRule) -> float | None:
    """Replicas whose federated metrics are stale (pulls failing):
    per-replica staleness is the federation's own degradation signal —
    the fleet view is partially blind, even if serving is fine."""
    fed = _federation()
    if fed is None:
        return None
    return fed.snapshot()["stale"]


def default_rules() -> list[SloRule]:
    """The default rule table over the hot paths the repo instruments.
    Budgets are deliberately loose — SLOs page on pathology (a stall, a
    shed storm, a breaker trip), not on a busy-but-healthy node."""
    from .ops.hash_service import (
        DEFAULT_DISPATCH_BUDGET_S,
        DEFAULT_WAIT_BUDGETS,
        LANES,
    )

    gw_classes = ("engine", "read", "tx", "debug")
    rules = [
        # block import: the whole-pipeline wall budget (tracing summaries)
        SloRule("block_import_wall", "engine", "callable", 2000.0,
                source=_block_wall_ms, unit="ms",
                help="mean closed-block import wall vs the 2s budget "
                     "(needs --trace-blocks)"),
        # hash service: one coalesced dispatch's wall — a stalled backend
        # (wedge drill, compile storm, saturated device) shows here first
        SloRule("hash_service_dispatch_p99", "hash_service", "quantile",
                DEFAULT_DISPATCH_BUDGET_S,
                metric="hash_service_service_seconds", q=0.99, unit="s",
                help="p99 coalesced dispatch wall"),
    ]
    # per-lane queue wait: the live lane is the block-import critical
    # path; background lanes tolerate queueing by design
    rules += [
        SloRule(f"hash_service_{lane}_wait_p99", "hash_service",
                "quantile", DEFAULT_WAIT_BUDGETS[lane],
                metric=f"hash_service_wait_seconds_{lane}", q=0.99,
                unit="s", help=f"p99 queue wait on the {lane} lane")
        for lane in LANES
    ]
    rules += [
        SloRule("gateway_shed_rate", "gateway", "ratio", 0.05,
                metrics_num=tuple(f"gateway_sheds_total_{c}"
                                  for c in gw_classes),
                metrics_den=tuple(f"gateway_requests_total_{c}"
                                  for c in gw_classes),
                min_den=4.0,
                help="fraction of requests shed with -32005"),
        SloRule("gateway_cache_hit_rate", "gateway", "ratio", 0.02,
                metrics_num=("gateway_cache_hits_total",),
                metrics_den=("gateway_cache_hits_total",
                             "gateway_cache_misses_total"),
                op="<", min_den=50.0, failing_factor=1e9,
                help="response-cache hit rate collapsing under real "
                     "lookup traffic"),
        SloRule("sparse_finish_p99", "sparse_commit", "quantile", 0.5,
                metric="sparse_commit_finish_seconds", q=0.99, unit="s",
                help="p99 live-tip sparse finish() wall"),
        # whole-subtrie fused commits: the histogram is recorded ONLY by
        # the k-level engines, so a healthy k=8 commit sits at ~depth/8
        # dispatches — a median above the budget means k-level commits
        # are degrading back to per-level dispatch counts (un-warm
        # k-shapes, chunk wedges, or a packing regression); degraded
        # only, never failing (roots stay correct on every rung)
        SloRule("fused_dispatches_per_block", "fused_commit", "quantile",
                16.0, metric="fused_dispatches_per_block", q=0.5,
                failing_factor=1e9,
                help="median device dispatches per k-level fused commit "
                     "above the k-level baseline (per-level regression)"),
        # hot-state node cache: a SUSTAINED hit-rate collapse under
        # steady import traffic means the invalidation rules are eating
        # the cache (an invalidation bug), not a consensus risk —
        # validation-at-lookup turns staleness into misses. Floor rule,
        # gated on real lookup volume; degrade only, never page.
        SloRule("hotstate_hit_rate", "hot_state", "ratio", 0.10,
                metrics_num=("hotstate_cache_hits_total",),
                metrics_den=("hotstate_cache_hits_total",
                             "hotstate_cache_misses_total"),
                op="<", min_den=50.0, failing_factor=1e9,
                help="cross-block node-cache hit rate collapsing under "
                     "steady import (invalidation bug — degrade, don't "
                     "page)"),
        SloRule("exec_conflict_rate", "exec", "ratio", 0.5,
                metrics_num=("exec_parallel_conflicts_total",
                             "exec_parallel_serial_reruns_total"),
                metrics_den=("exec_parallel_native_txs_total",
                             "exec_parallel_python_txs_total"),
                min_den=8.0, failing_factor=1e9,
                help="optimistic scheduling losing to conflicts "
                     "(Reddio-style conflict-rate visibility)"),
        SloRule("exec_fallbacks", "exec", "rate", 0.01,
                metric="exec_parallel_fallbacks_total", unit="/s",
                help="blocks falling back to the serial executor"),
        SloRule("warmup_failed_shapes", "warmup", "gauge", 0.5,
                metric="warmup_shapes_failed", failing_factor=1e9,
                help="menu shapes that exhausted compile retries "
                     "(serving degraded on the CPU twin)"),
        # one shed device degrades within a window (budget 0.5 → burn 2);
        # a full-mesh outage pages through hasher_breaker/CPU-rung rules,
        # so this one never self-escalates to failing
        SloRule("mesh_degraded_devices", "mesh", "gauge", 0.5,
                metric="mesh_devices_unhealthy", failing_factor=1e9,
                help="devices shed from the hashing mesh by per-device "
                     "breakers (serving on a shrunken mesh)"),
        # breaker open (2) degrades within one window; sustained open
        # escalates to failing once the slow window burns too
        SloRule("hasher_breaker", "hasher_supervisor", "gauge", 1.5,
                metric="hasher_supervisor_breaker_state",
                failing_factor=1.3,
                help="supervisor circuit breaker half-open/open"),
        # crash-recovery verdict (storage/recovery.py): 0 ok, 1 degraded
        # (healed a torn tail / quarantine — the node is consistent NOW,
        # so no breach), 2 failed — the recovered state is provably wrong
        # (root mismatch), which must page immediately and sustain
        SloRule("recovery_failed", "durability", "gauge", 1.5,
                metric="recovery_status", failing_factor=1.2,
                help="startup recovery provably failed (recovered state "
                     "root mismatch / unhealable chain)"),
        # reorg-storm backoff engaged (engine/block_buffer.py
        # ReorgTracker): the tree is absorbing pathological forkchoice
        # churn with speculation disabled — degraded while it lasts,
        # never self-escalating (the node still imports correctly)
        SloRule("tree_reorg_backoff", "consensus", "gauge", 0.5,
                metric="tree_reorg_backoff_active", failing_factor=1e9,
                help="reorg-storm backoff active (speculative paths "
                     "stood down while forkchoice churns)"),
        # replica fleet (fleet/ring.py): one shed replica degrades the
        # fleet component within a window (the ring already routed
        # around it — reads fail over to neighbors / the local node, so
        # this never self-escalates to failing); a whole-fleet outage
        # just means every read serves locally, which is yesterday's
        # single-node behavior, not an incident
        SloRule("fleet_unhealthy_replicas", "fleet", "callable", 0.5,
                source=_fleet_unhealthy, failing_factor=1e9,
                help="replicas shed from the gateway ring (draining or "
                     "unreachable; reads failing over)"),
        # fleet observability plane (obs/federation.py): fleet-wide
        # read p99 over the bucket-wise federated histograms — the
        # number single-process /metrics could never compute
        SloRule("fleet_read_p99", "fleet", "callable", 0.5,
                source=_fleet_read_p99, unit="s", failing_factor=4.0,
                help="fleet-wide p99 read service wall across replica "
                     "gateways (federated bucket-wise merge)"),
        # replica-lag distribution: the worst federated
        # replica_feed_lag_heads — degrades when any replica trails
        # beyond the ring's shed bound; never self-escalates (the ring
        # sheds it, reads fail over)
        # budget mirrors fleet/ring.py DEFAULT_MAX_LAG
        SloRule("fleet_replica_lag", "fleet", "callable", 4.0,
                source=_fleet_lag_worst,
                unit="heads", failing_factor=1e9,
                help="worst federated replica feed lag (heads behind "
                     "the announced head)"),
        # per-replica staleness: the federation itself degrading — a
        # replica whose metrics can't be pulled leaves the fleet view
        # partially blind even while serving continues
        SloRule("fleet_federation_stale", "fleet", "callable", 0.5,
                source=_fleet_stale, failing_factor=1e9,
                help="replicas whose federated metrics are stale "
                     "(fleet_metricsSnapshot pulls failing)"),
        # HA hot standby (fleet/standby.py): replay lag in heads behind
        # the leader's heartbeat head. A trailing standby still promotes
        # correctly (it finishes the durable tail first) but widens the
        # failover's data-loss window toward the persistence threshold —
        # degraded while it trails, failing when it has effectively
        # stopped replaying (wedged feed thread / resync loop)
        SloRule("standby_replay_lag", "ha", "gauge", 4.0,
                metric="standby_replay_lag_heads", unit="heads",
                failing_factor=8.0,
                help="hot-standby replay lag (heads behind the leader "
                     "heartbeat; bounds the failover loss window)"),
        # write-path firehose (pool/batcher.py): sustained -32005
        # admission shedding means the insert worker has fallen behind
        # the submit rate for a whole window — clients are being told to
        # back off faster than the pool absorbs. Bursty sheds within a
        # window are the backpressure ladder WORKING, so the budget is a
        # sustained rate, not a single-burst count
        SloRule("pool_shed_rate", "pool", "rate", 10.0,
                metric="pool_admission_sheds_total", unit="/s",
                help="sustained tx-admission shed rate (-32005 "
                     "backpressure saturating for whole windows)"),
        # continuous producer (payload/producer.py): staleness is how
        # long the hot candidate has lagged the pool. A stale candidate
        # silently degrades continuous build back to build-on-demand;
        # sustained staleness means the refresh loop is wedged or
        # drowning — failing once it exceeds a block interval
        SloRule("producer_staleness", "producer", "gauge", 1.0,
                metric="producer_staleness_seconds", unit="s",
                failing_factor=12.0,
                help="hot-candidate staleness behind the pool (refresh "
                     "loop wedged or outpaced)"),
    ]
    return rules


class _RuleState:
    __slots__ = ("state", "signals", "values", "ts", "ewma", "breaches",
                 "last_value", "last_change", "last_breach", "last_dump")

    def __init__(self, rule: SloRule):
        self.state = "ok"
        self.signals: deque = deque(maxlen=max(rule.slow_n, rule.fast_n))
        self.values: deque = deque(maxlen=max(rule.slow_n, rule.fast_n))
        self.ts: deque = deque(maxlen=max(rule.slow_n, rule.fast_n))
        self.ewma: float | None = None
        self.breaches = 0
        self.last_value: float | None = None
        self.last_change: float | None = None
        self.last_breach: dict | None = None
        self.last_dump: str | None = None


def _worst(a: str, b: str) -> str:
    return a if _SEVERITY[a] >= _SEVERITY[b] else b


# -- the engine ---------------------------------------------------------------


class HealthEngine:
    """Sampler + evaluator + health roll-up. One per node (installed as
    the process default); standalone instances over private registries
    are the test harness."""

    def __init__(self, registry=None, rules: list[SloRule] | None = None, *,
                 interval: float | None = None, window: int | None = None):
        env = os.environ
        self.registry = registry or REGISTRY
        self.interval = float(
            interval if interval is not None
            else env.get("RETH_TPU_SLO_INTERVAL", DEFAULT_INTERVAL_S))
        window = int(window or env.get("RETH_TPU_SLO_WINDOW", 0)
                     or DEFAULT_WINDOW)
        self.sampler = MetricsSampler(self.registry, window)
        self.rules = list(rules) if rules is not None else default_rules()
        self._states = {r.name: _RuleState(r) for r in self.rules}
        self._lock = threading.Lock()
        self.breaches_total = 0
        self.recent_breaches: deque = deque(maxlen=64)
        self.started_at = time.time()
        self.ticks = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # the engine's own health surface rides in the same registry it
        # samples — scrapers and the sampler see the judgment too
        self._m_state = self.registry.gauge(
            "node_health_state", "rolled-up node health: "
                                 "0 ok, 1 degraded, 2 failing")
        self._m_breaches = self.registry.counter(
            "slo_breaches_total", "SLO state escalations")
        self._m_ticks = self.registry.counter(
            "health_ticks_total", "sampler+evaluator passes")
        self._comp_gauges: dict[str, Gauge] = {}

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        """Start the background sampler thread (no-op when interval<=0:
        manual :meth:`tick` mode, the deterministic test path)."""
        if self.interval <= 0 or self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="health-slo")
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — health must never kill the node
                pass

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None

    # -- evaluation ---------------------------------------------------------

    def tick(self, now: float | None = None) -> None:
        """One sample + evaluate pass (the thread body; tests call it
        directly)."""
        now = time.time() if now is None else now
        self.sampler.sample(now)
        forced = os.environ.get("RETH_TPU_FAULT_SLO_BREACH", "")
        forced_rules = (set(r.strip() for r in forced.split(","))
                        if forced else set())
        with self._lock:
            for rule in self.rules:
                self._evaluate(rule, self._states[rule.name], now,
                               forced_rules)
            self.ticks += 1
        self._m_ticks.increment()
        self._publish()

    def _value(self, rule: SloRule) -> float | None:
        s = self.sampler
        if rule.kind == "callable":
            return rule.source(self, rule)
        if rule.kind == "gauge":
            return s.latest(rule.metric)
        if rule.kind == "rate":
            return s.rate(rule.metric, rule.window)
        if rule.kind == "quantile":
            return s.quantile(rule.metric, rule.q, rule.window)
        if rule.kind == "ratio":
            den = sum(s.delta(m, rule.window) for m in rule.metrics_den)
            if den <= 0 or den < rule.min_den:
                return None  # no meaningful activity in the window
            num = sum(s.delta(m, rule.window) for m in rule.metrics_num)
            return num / den
        raise ValueError(f"unknown rule kind {rule.kind!r}")

    @staticmethod
    def _signal(rule: SloRule, value: float | None) -> float:
        """Burn signal: >1 means the budget is being violated."""
        if value is None:
            return 0.0
        if rule.op == "<":
            return rule.budget / max(value, 1e-9)
        return value / rule.budget if rule.budget else float(value > 0)

    def _evaluate(self, rule: SloRule, st: _RuleState, now: float,
                  forced: set) -> None:
        value = self._value(rule)
        signal = self._signal(rule, value)
        drilled = forced and (forced & {"1", "all", rule.name,
                                        rule.component})
        if drilled:
            signal = max(signal, rule.failing_factor + 1.0)
        st.values.append(value)
        st.signals.append(signal)
        st.ts.append(now)
        st.last_value = value
        if value is not None:
            st.ewma = (value if st.ewma is None
                       else rule.ewma_alpha * value
                       + (1 - rule.ewma_alpha) * st.ewma)
        fast_sig = list(st.signals)[-rule.fast_n:]
        fast = sum(fast_sig) / len(fast_sig)
        slow = sum(st.signals) / len(st.signals)
        new = st.state
        if st.state == "ok":
            if fast >= 1.0:
                new = "degraded"
        else:
            if fast >= rule.failing_factor and slow >= 1.0:
                new = "failing"
            elif fast < rule.recovery:
                new = "ok"
            elif st.state == "failing" and fast < rule.failing_factor:
                new = "degraded"
        if new != st.state:
            self._transition(rule, st, new, now, value, fast, slow,
                             bool(drilled))

    def _transition(self, rule: SloRule, st: _RuleState, new: str,
                    now: float, value, fast: float, slow: float,
                    drilled: bool) -> None:
        old, st.state = st.state, new
        st.last_change = now
        if _SEVERITY[new] > _SEVERITY[old]:
            st.breaches += 1
            self.breaches_total += 1
            self._m_breaches.increment()
            info = {
                "rule": rule.name, "component": rule.component,
                "state": new, "from": old,
                "value": value if value is None else round(value, 6),
                "budget": rule.budget, "unit": rule.unit,
                "burn_fast": round(min(fast, 1e9), 3),
                "burn_slow": round(min(slow, 1e9), 3),
                "ewma": None if st.ewma is None else round(st.ewma, 6),
                "drill": drilled, "ts": round(now, 3),
            }
            # flight dump via the fault path: rate-limited per rule so a
            # flapping rule can't spray the disk — the postmortem trail
            # every breach deserves (and the BENCH zeros never had)
            # ("drill" collides with fault_event's own first parameter —
            # passed as "forced" on the event, kept as "drill" in info)
            dump = tracing.fault_event(
                f"slo_breach_{rule.name}", target="health",
                forced=drilled,
                **{k: v for k, v in info.items()
                   if k not in ("ts", "drill")})
            info["flight_dump"] = dump
            st.last_breach = info
            if dump:
                st.last_dump = dump
            self.recent_breaches.append(info)
        else:
            tracing.event("health", "slo_recovered", rule=rule.name,
                          component=rule.component, state=new,
                          burn_fast=round(min(fast, 1e9), 3))

    def _publish(self) -> None:
        comps = self.components()
        status = "ok"
        for c, s in comps.items():
            status = _worst(status, s)
            g = self._comp_gauges.get(c)
            if g is None:
                g = self._comp_gauges[c] = self.registry.gauge(
                    f"health_component_state_{c}",
                    "0 ok, 1 degraded, 2 failing")
            g.set(_SEVERITY[s])
        self._m_state.set(_SEVERITY[status])

    # -- surfaces -----------------------------------------------------------

    def components(self) -> dict[str, str]:
        comps: dict[str, str] = {}
        for rule in self.rules:
            st = self._states[rule.name].state
            comps[rule.component] = _worst(comps.get(rule.component, "ok"),
                                           st)
        return comps

    def status(self) -> str:
        s = "ok"
        for c in self.components().values():
            s = _worst(s, c)
        return s

    def health(self) -> dict:
        """The /health + debug_healthCheck body: roll-up first, detail
        after."""
        comps = self.components()
        status = "ok"
        for s in comps.values():
            status = _worst(status, s)
        breaching = {r.name: self._states[r.name].state
                     for r in self.rules
                     if self._states[r.name].state != "ok"}
        return {
            "status": status,
            "components": comps,
            "breaching_rules": breaching,
            "breaches_total": self.breaches_total,
            "recent_breaches": list(self.recent_breaches)[-8:],
            "ticks": self.ticks,
            "interval_s": self.interval,
            "uptime_s": round(time.time() - self.started_at, 1),
        }

    def slo_status(self) -> dict:
        """debug_sloStatus: every rule's state, burn, baseline, and the
        triggering value series (ts/value tail from the burn window)."""
        rules = []
        with self._lock:
            for rule in self.rules:
                st = self._states[rule.name]
                sigs = list(st.signals)
                fast_sig = sigs[-rule.fast_n:]
                series = [{"ts": round(t, 3),
                           "value": None if v is None else round(v, 6)}
                          for t, v in zip(st.ts, st.values)]
                rules.append({
                    "rule": rule.name,
                    "component": rule.component,
                    "state": st.state,
                    "kind": rule.kind,
                    "metric": rule.metric,
                    "budget": rule.budget,
                    "op": rule.op,
                    "unit": rule.unit,
                    "value": (None if st.last_value is None
                              else round(st.last_value, 6)),
                    "ewma": None if st.ewma is None else round(st.ewma, 6),
                    "burn_fast": (round(sum(fast_sig) / len(fast_sig), 3)
                                  if fast_sig else 0.0),
                    "burn_slow": (round(sum(sigs) / len(sigs), 3)
                                  if sigs else 0.0),
                    "windows": {"fast_n": rule.fast_n, "slow_n": rule.slow_n,
                                "agg": rule.window},
                    "breaches": st.breaches,
                    "last_breach": st.last_breach,
                    "flight_dump": st.last_dump,
                    "series": series,
                    "help": rule.help,
                })
        return {"status": self.status(), "rules": rules}

    def metrics_history(self, name: str | None = None,
                        samples: int | None = None) -> dict:
        """debug_metricsHistory: retained series names, or one series'
        ring-buffer tail."""
        if name is None:
            return {"series": self.sampler.names(),
                    "window": self.sampler.window,
                    "samples": self.sampler.samples,
                    "interval_s": self.interval}
        pts = self.sampler.points(name, samples)
        if pts is None:
            raise KeyError(f"no retained series named {name!r}")
        return {"name": name, "kind": self.sampler.kind(name),
                "points": pts}


# -- process-default engine (the /health and debug-RPC seam) ------------------

_ENGINE: HealthEngine | None = None


def install(engine: HealthEngine) -> None:
    """Make ``engine`` the process default served by ``/health`` and the
    debug RPCs (node/node.py; last installed wins, like REGISTRY)."""
    global _ENGINE
    _ENGINE = engine


def uninstall(engine: HealthEngine | None = None) -> None:
    """Clear the default (only if it is still ``engine`` when given)."""
    global _ENGINE
    if engine is None or _ENGINE is engine:
        _ENGINE = None


def get_engine() -> HealthEngine | None:
    return _ENGINE


def health_response() -> tuple[int, dict]:
    """(HTTP status, JSON body) for ``GET /health``. Without an engine
    the endpoint still answers — liveness + build identity — so fleet
    probes work against nodes launched without ``--health``. 503 only
    when the roll-up is ``failing`` (degraded still serves)."""
    body: dict = {"build": build_info()}
    eng = get_engine()
    if eng is None:
        body.update({"status": "unknown", "health_engine": "off"})
        return 200, body
    body.update(eng.health())
    return (503 if body["status"] == "failing" else 200), body


# -- perf-regression sentinel -------------------------------------------------


class BenchBaselineStore:
    """Trailing-baseline store for bench.py: the last N good runs per
    ``(metric, mode, backend, warmup_state)`` key, persisted as JSON.

    ``assess`` computes ``vs_prev`` = value / median(previous good runs)
    and flags ``regression`` when it drops under the threshold;
    ``record`` appends a good run and trims. Key on mode+backend+warmup
    so a numpy fallback never compares against a device number and a
    cold-compile run never drags the steady-state baseline down. A
    corrupt store is moved aside (``<path>.corrupt``) and rebuilt — the
    sentinel must never fail a bench."""

    def __init__(self, path: str | Path | None = None, keep: int = 8):
        if path is None:
            path = (os.environ.get("RETH_TPU_BENCH_BASELINE_STORE")
                    or Path(__file__).resolve().parent.parent
                    / ".bench_baselines.json")
        self.path = Path(path)
        self.keep = keep
        self._data = self._load()

    def _load(self) -> dict:
        try:
            if self.path.exists():
                data = json.loads(self.path.read_text())
                if isinstance(data, dict):
                    return data
                raise ValueError("store root is not an object")
        except Exception:  # noqa: BLE001 — quarantine, never fail the bench
            try:
                self.path.replace(self.path.with_suffix(
                    self.path.suffix + ".corrupt"))
            except OSError:
                pass
        return {}

    @staticmethod
    def key(metric: str, mode: str, backend: str, warmup_state) -> str:
        # warmup_state arrives as the bench line's field: a dict snapshot
        # ({"state": "warm", ...}) or a plain string ("off")
        if isinstance(warmup_state, dict):
            warmup_state = warmup_state.get("state", "unknown")
        return f"{metric}|{mode}|{backend}|{warmup_state}"

    def runs(self, metric: str, mode: str, backend: str,
             warmup_state) -> list[dict]:
        return list(self._data.get(
            self.key(metric, mode, backend, warmup_state), []))

    def assess(self, metric: str, mode: str, backend: str, warmup_state,
               value: float, threshold: float = 0.8) -> dict:
        """``vs_prev``/``regression`` for one run vs the trailing
        baseline. No prior runs -> vs_prev 1.0 (nothing to regress
        against), never a regression."""
        prev = [r["value"] for r in
                self.runs(metric, mode, backend, warmup_state)
                if r.get("value", 0) > 0]
        if not prev or value <= 0:
            return {"vs_prev": 1.0 if value > 0 else 0.0,
                    "regression": False, "baseline_n": len(prev),
                    "baseline": None}
        prev.sort()
        mid = len(prev) // 2
        median = (prev[mid] if len(prev) % 2
                  else (prev[mid - 1] + prev[mid]) / 2)
        vs_prev = value / median if median else 1.0
        return {"vs_prev": round(vs_prev, 3),
                "regression": vs_prev < threshold,
                "baseline_n": len(prev),
                "baseline": round(median, 1)}

    def record(self, metric: str, mode: str, backend: str, warmup_state,
               value: float, **extra) -> None:
        """Append one GOOD run (caller filters errors/zeros) and persist
        atomically."""
        key = self.key(metric, mode, backend, warmup_state)
        runs = self._data.setdefault(key, [])
        runs.append({"value": value, "ts": time.time(), **extra})
        del runs[:-self.keep]
        try:
            tmp = self.path.with_suffix(".tmp")
            tmp.write_text(json.dumps(self._data, indent=1) + "\n")
            tmp.replace(self.path)
        except OSError:
            pass
