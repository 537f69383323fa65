"""Node health & SLO engine (health.py): histogram-quantile helpers,
metric time-series retention, burn-rate SLO evaluation with breach
flight dumps + the RETH_TPU_FAULT_SLO_BREACH drill, /health and the
debug health RPCs end-to-end on a dev node with a hash-service stall,
the bench perf-regression sentinel (the rebuild mode without its device
-> non-zero exit, no number; the exec mode -> a real CPU number +
vs_prev), and the sampler/evaluator overhead
guard.

Reference analogue: the reference wires metrics through every layer so
the node itself knows when it is sick (PAPER.md §1); these tests pin
this repo's judgment layer end to end (ISSUE 9)."""

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from reth_tpu import health, tracing
from reth_tpu.health import (
    BenchBaselineStore,
    HealthEngine,
    MetricsSampler,
    SloRule,
    default_rules,
)
from reth_tpu.metrics import (
    REGISTRY,
    Histogram,
    MetricsRegistry,
    histogram_quantile,
    sample_percentile,
    update_process_metrics,
)
from reth_tpu.primitives import Account
from reth_tpu.primitives.keccak import keccak256_batch_np
from reth_tpu.testing import ChainBuilder, Wallet
from reth_tpu.trie import TrieCommitter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _health_env(tmp_path, monkeypatch):
    """Isolate flight dumps + dump rate limits + the default engine."""
    monkeypatch.setenv("RETH_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.delenv("RETH_TPU_FAULT_SLO_BREACH", raising=False)
    rec = tracing.flight_recorder()
    rec.directory = None
    rec.dumps.clear()
    tracing.reset_fault_dump_limits()
    yield
    health.uninstall()
    rec.directory = None


# -- satellite: histogram_quantile / sample_percentile ------------------------


def test_histogram_quantile_known_distributions():
    buckets = (1.0, 2.0, 3.0, 4.0)
    # uniform: 10 observations per bucket -> median at the 2nd edge
    assert histogram_quantile(buckets, [10, 10, 10, 10, 0], 0.5) == \
        pytest.approx(2.0)
    # linear interpolation inside a bucket: rank 5 of 10 in (1, 2]
    assert histogram_quantile(buckets, [0, 10, 0, 0, 0], 0.5) == \
        pytest.approx(1.5)
    # skewed mass: 90 in the first bucket -> p50 well inside it
    assert histogram_quantile(buckets, [90, 5, 3, 1, 1], 0.5) == \
        pytest.approx(0.5 * 100 / 90, rel=1e-6)
    # overflow rank clamps to the last finite edge (Prometheus rule)
    assert histogram_quantile(buckets, [1, 0, 0, 0, 99], 0.99) == 4.0
    # first bucket interpolates from 0
    assert histogram_quantile(buckets, [4, 0, 0, 0, 0], 0.25) == \
        pytest.approx(0.25)
    # no observations
    assert histogram_quantile(buckets, [0, 0, 0, 0, 0], 0.5) is None
    with pytest.raises(ValueError):
        histogram_quantile(buckets, [1, 0, 0, 0, 0], 1.5)


def test_histogram_quantile_vs_empirical():
    """Against a known sample set pushed through a real Histogram: the
    bucketed estimate brackets the empirical percentile."""
    h = Histogram("q_test", buckets=(0.001, 0.01, 0.1, 0.5, 1.0))
    values = [0.0005] * 50 + [0.05] * 40 + [0.75] * 10
    for v in values:
        h.record(v)
    p50 = h.quantile(0.5)
    assert 0.001 <= p50 <= 0.1  # true p50 = 0.0005..0.05 boundary region
    p99 = h.quantile(0.99)
    assert 0.5 < p99 <= 1.0    # true p99 = 0.75
    assert Histogram("empty").quantile(0.5) is None


def test_sample_percentile_nearest_rank():
    samples = list(range(1, 11))
    assert sample_percentile(samples, 0) == 1
    assert sample_percentile(samples, 60) == 7  # the gas-oracle shape
    assert sample_percentile(samples, 100) == 10
    assert sample_percentile([], 50) is None
    assert sample_percentile([7], 99) == 7


# -- satellite: build-info / uptime gauges ------------------------------------


def test_build_info_and_uptime_gauges():
    reg = MetricsRegistry()
    update_process_metrics(reg)
    text = reg.render()
    assert "# TYPE reth_tpu_build_info gauge" in text
    # identity in the labels, value pinned to 1
    line = next(ln for ln in text.splitlines()
                if ln.startswith("reth_tpu_build_info{"))
    assert line.endswith(" 1.0") or line.endswith(" 1")
    assert 'version="' in line and 'backend="' in line
    assert "process_uptime_seconds" in text
    # label rendering keeps the exposition parseable: TYPE name is bare
    assert "# TYPE reth_tpu_build_info{" not in text


# -- time-series retention ----------------------------------------------------


def test_sampler_counter_delta_encoding_and_reset():
    reg = MetricsRegistry()
    c = reg.counter("work_total")
    s = MetricsSampler(reg, window=8)
    c.increment(5)
    s.sample(now=1.0)   # first sight: baseline, delta 0
    c.increment(3)
    s.sample(now=2.0)
    c.increment(2)
    s.sample(now=3.0)
    pts = s.points("work_total")
    assert [p["delta"] for p in pts] == [0, 3, 2]
    assert [p["value"] for p in pts] == [5, 8, 10]
    assert s.delta("work_total", 2) == 5
    assert s.rate("work_total", 2) == pytest.approx(5 / 2.0)
    # counter reset (restart): delta re-bases instead of going negative
    c.value = 1.0
    s.sample(now=4.0)
    assert s.points("work_total")[-1]["delta"] == 1.0


def test_sampler_gauge_and_windowed_histogram_quantile():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    s = MetricsSampler(reg, window=16)
    # pre-engine history must NOT count as a burst (baseline sample)
    for _ in range(50):
        h.record(5.0)
    g.set(3)
    s.sample(now=1.0)
    assert s.quantile("lat_seconds", 0.99, 1) is None  # empty window
    # a window of fast observations
    for _ in range(100):
        h.record(0.005)
    s.sample(now=2.0)
    assert s.quantile("lat_seconds", 0.99, 1) <= 0.01
    # then a slow interval: the one-sample window sees only the stall
    for _ in range(10):
        h.record(0.5)
    g.set(7)
    s.sample(now=3.0)
    assert s.quantile("lat_seconds", 0.99, 1) > 0.1
    # ...while the two-sample window still averages both
    assert s.quantile("lat_seconds", 0.5, 2) <= 0.01
    assert s.latest("depth") == 7
    pts = s.points("lat_seconds")
    assert pts[1]["count"] == 100 and "p99" in pts[1]


def test_sampler_window_bounded():
    reg = MetricsRegistry()
    reg.gauge("g").set(1)
    s = MetricsSampler(reg, window=4)
    for i in range(20):
        s.sample(now=float(i))
    assert len(s.points("g")) == 4
    assert s.samples == 20


# -- burn-rate evaluation -----------------------------------------------------


def _gauge_rule(**kw):
    defaults = dict(kind="gauge", budget=10.0, metric="probe_ms",
                    fast_n=2, slow_n=4, failing_factor=2.0, recovery=0.9,
                    window=2)
    defaults.update(kw)
    return SloRule("probe_latency", "probe", **defaults)


def test_slo_degraded_failing_recovery_cycle(tmp_path):
    reg = MetricsRegistry()
    g = reg.gauge("probe_ms")
    eng = HealthEngine(reg, [_gauge_rule()], interval=0)
    g.set(5.0)
    for _ in range(4):
        eng.tick()
    assert eng.status() == "ok"
    assert eng.components() == {"probe": "ok"}
    # breach: flips to degraded within ONE evaluation window
    g.set(15.0)
    eng.tick()
    assert eng.components()["probe"] == "degraded"
    assert eng.breaches_total == 1
    st = eng.slo_status()["rules"][0]
    assert st["state"] == "degraded" and st["value"] == 15.0
    assert st["series"][-1]["value"] == 15.0  # the triggering series
    # the breach dumped the flight recorder (fault_event path)
    assert st["last_breach"]["flight_dump"]
    assert os.path.exists(st["last_breach"]["flight_dump"])
    # sustained hard burn (>= failing_factor x budget, slow window too)
    g.set(25.0)
    for _ in range(4):
        eng.tick()
    assert eng.components()["probe"] == "failing"
    assert eng.status() == "failing"
    # recovery has hysteresis: back under budget -> ok
    g.set(5.0)
    for _ in range(4):
        eng.tick()
    assert eng.components()["probe"] == "ok"
    h = eng.health()
    assert h["status"] == "ok" and h["breaches_total"] >= 2
    assert h["recent_breaches"][-1]["rule"] == "probe_latency"


def test_slo_ewma_baseline_tracks_value():
    reg = MetricsRegistry()
    g = reg.gauge("probe_ms")
    eng = HealthEngine(reg, [_gauge_rule(ewma_alpha=0.5)], interval=0)
    g.set(4.0)
    eng.tick()
    g.set(8.0)
    eng.tick()
    st = eng.slo_status()["rules"][0]
    assert st["ewma"] == pytest.approx(6.0)  # 0.5*8 + 0.5*4


def test_slo_floor_rule_breaches_below_budget():
    """op='<' rules budget a floor (cache hit rate shape)."""
    reg = MetricsRegistry()
    hits = reg.counter("hits_total")
    total = reg.counter("lookups_total")
    rule = SloRule("hit_rate", "cache", "ratio", 0.5,
                   metrics_num=("hits_total",),
                   metrics_den=("lookups_total",),
                   op="<", min_den=10.0, fast_n=1, slow_n=4, window=2)
    eng = HealthEngine(reg, [rule], interval=0)
    eng.tick()  # baseline
    hits.increment(90)
    total.increment(100)
    eng.tick()
    assert eng.components()["cache"] == "ok"
    total.increment(100)  # 0 hits this window -> rate 0 < 0.5 floor
    eng.tick()
    assert eng.components()["cache"] == "degraded"


def test_slo_ratio_min_den_guards_idle_subsystems():
    reg = MetricsRegistry()
    reg.counter("errs_total").increment(5)
    reg.counter("reqs_total")
    rule = SloRule("err_rate", "svc", "ratio", 0.01,
                   metrics_num=("errs_total",), metrics_den=("reqs_total",),
                   min_den=10.0, fast_n=1, window=4)
    eng = HealthEngine(reg, [rule], interval=0)
    for _ in range(3):
        eng.tick()
    # no denominator activity: the rule must idle at ok, not divide by 0
    assert eng.components()["svc"] == "ok"
    assert eng.slo_status()["rules"][0]["value"] is None


def test_slo_breach_drill_env(monkeypatch, tmp_path):
    """RETH_TPU_FAULT_SLO_BREACH forces the named rule to breach."""
    reg = MetricsRegistry()
    reg.gauge("probe_ms").set(1.0)
    eng = HealthEngine(reg, [_gauge_rule()], interval=0)
    eng.tick()
    assert eng.status() == "ok"
    monkeypatch.setenv("RETH_TPU_FAULT_SLO_BREACH", "probe_latency")
    eng.tick()
    assert eng.components()["probe"] == "degraded"
    breach = eng.slo_status()["rules"][0]["last_breach"]
    assert breach["drill"] is True and breach["flight_dump"]
    monkeypatch.delenv("RETH_TPU_FAULT_SLO_BREACH")
    for _ in range(4):
        eng.tick()
    assert eng.status() == "ok"


def test_block_wall_rule_reads_tracing_summaries():
    reg = MetricsRegistry()
    rule = next(r for r in default_rules() if r.name == "block_import_wall")
    rule.budget = 0.001  # ms: any real block breaches
    rule.fast_n = 1
    eng = HealthEngine(reg, [rule], interval=0)
    tracing.set_trace_enabled(True)
    try:
        # a unique trace id: timelines are keyed globally, and reusing
        # another suite's id would merge the two blocks' records
        with tracing.trace_block("9e" * 32, number=7):
            with tracing.span("engine::block", "execute"):
                time.sleep(0.002)
    finally:
        tracing.set_trace_enabled(False)
    eng.tick()
    st = eng.slo_status()["rules"][0]
    assert st["value"] is not None and st["value"] > 0
    assert eng.components()["engine"] == "degraded"


def test_health_engine_metrics_published():
    reg = MetricsRegistry()
    g = reg.gauge("probe_ms")
    eng = HealthEngine(reg, [_gauge_rule()], interval=0)
    g.set(20.0)
    eng.tick()
    lines = reg.render().splitlines()
    assert "node_health_state 1" in lines        # degraded
    assert "slo_breaches_total 1.0" in lines
    assert "health_component_state_probe 1" in lines
    assert "health_ticks_total 1.0" in lines


def test_metrics_history_query():
    reg = MetricsRegistry()
    c = reg.counter("x_total")
    eng = HealthEngine(reg, [], interval=0)
    eng.tick()
    c.increment(4)
    eng.tick()
    listing = eng.metrics_history()
    assert "x_total" in listing["series"]
    series = eng.metrics_history("x_total", samples=1)
    assert series["kind"] == "counter"
    assert series["points"][-1]["delta"] == 4
    with pytest.raises(KeyError):
        eng.metrics_history("no_such_metric")


# -- gateway shed storm degrades its component --------------------------------


def test_gateway_shed_storm_degrades_component():
    from reth_tpu.rpc.gateway import GatewayFaultInjector, RpcGateway
    from reth_tpu.rpc.server import RpcError

    reg = MetricsRegistry()
    rules = [r for r in default_rules() if r.name == "gateway_shed_rate"]
    eng = HealthEngine(reg, rules, interval=0)
    gw = RpcGateway(head_supplier=lambda: b"h", registry=reg,
                    injector=GatewayFaultInjector(shed_every=2),
                    cache_size=0)
    eng.tick()  # baseline
    sheds = 0
    for i in range(40):
        try:
            gw.call("eth_blockNumber", [], lambda: "0x1")
        except RpcError as e:
            assert e.code == -32005
            sheds += 1
    assert sheds >= 19  # the storm: every 2nd admission shed
    eng.tick()
    assert eng.components()["gateway"] == "degraded"
    st = next(r for r in eng.slo_status()["rules"]
              if r["rule"] == "gateway_shed_rate")
    assert st["value"] >= 0.4
    assert st["last_breach"]["flight_dump"]  # breach dumped the recorder
    # monitoring probes classify as reads — never starved in the 2-slot
    # debug class behind a trace re-execution
    from reth_tpu.rpc.gateway import classify

    assert classify("debug_healthCheck") == "read"
    assert classify("debug_sloStatus") == "read"
    assert classify("debug_metricsHistory") == "read"
    assert classify("debug_traceTransaction") == "debug"


# -- node e2e: /health + debug RPCs + hash-service stall drill ----------------


@pytest.fixture()
def health_node():
    from reth_tpu.node import Node, NodeConfig
    from reth_tpu.ops.hash_service import HashService

    cpu = TrieCommitter(hasher=keccak256_batch_np)
    svc = HashService(backend=cpu.hasher, min_tier=256)
    cpu.hash_service = svc
    cpu.hasher = svc.client("live")
    alice = Wallet(0xA11CE)
    builder = ChainBuilder({alice.address: Account(balance=10**21)},
                           committer=cpu)
    # other suites may have left global-registry gauges non-zero (the
    # engine samples REGISTRY); pin the gauge-kind rule inputs healthy
    REGISTRY.gauge("warmup_shapes_failed").set(0)
    REGISTRY.gauge("hasher_supervisor_breaker_state").set(0)
    cfg = NodeConfig(dev=True, health=True, slo_interval=0,
                     genesis_header=builder.genesis,
                     genesis_alloc=builder.accounts_at_genesis)
    n = Node(cfg, committer=cpu)
    n.start_rpc()
    yield n, svc
    n.stop()
    svc.stop()


def _rpc(port, method, *params):
    req = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                      "params": list(params)})
    out = json.loads(urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/", req.encode(),
        {"Content-Type": "application/json"}), timeout=30).read())
    if "error" in out:
        raise RuntimeError(f"{method}: {out['error']}")
    return out["result"]


def _get_health(port):
    try:
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=10)
        return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:  # 503 when failing
        return e.code, json.loads(e.read())


def test_node_health_e2e_stall_degrade_recover(health_node):
    """The acceptance drill: healthy -> RETH_TPU_FAULT_SERVICE_STALL
    shape stall -> hash_service degrades and node health flips within
    one evaluation window, slo breach event + flight dump recorded,
    /health + debug_healthCheck + debug_sloStatus report it with the
    triggering series -> recovery returns to ok."""
    from reth_tpu.ops.hash_service import ServiceFaultInjector

    n, svc = health_node
    port = n.rpc.port
    eng = n.health
    assert eng is not None and health.get_engine() is eng

    # healthy baseline: mine a block (live-lane traffic), then evaluate
    n.miner.mine_block(timestamp=1_900_000_000)
    eng.tick()
    eng.tick()
    code, body = _get_health(port)
    assert code == 200
    assert body["components"]["hash_service"] == "ok"
    assert body["build"]["version"]
    assert _rpc(port, "debug_healthCheck")["components"][
        "hash_service"] == "ok"

    # inject the stall drill (the ServiceFaultInjector the env knob
    # builds): every coalesced dispatch sleeps, breaching the p99
    # dispatch budget
    dumps_before = len(tracing.flight_recorder().dumps)
    svc.injector = ServiceFaultInjector(stall=0.2)
    try:
        n.miner.mine_block(timestamp=1_900_000_001)
    finally:
        svc.injector = None
    eng.tick()  # one evaluation window
    assert eng.components()["hash_service"] == "degraded"
    code, body = _get_health(port)
    assert code == 200  # degraded still serves
    assert body["status"] in ("degraded", "failing")
    assert body["components"]["hash_service"] == "degraded"
    assert any(b["component"] == "hash_service"
               for b in body["recent_breaches"])
    # flight dumps: the drill's own fault_event AND the slo breach
    assert len(tracing.flight_recorder().dumps) > dumps_before
    slo = _rpc(port, "debug_sloStatus")
    breached = [r for r in slo["rules"]
                if r["component"] == "hash_service" and r["state"] != "ok"]
    assert breached
    assert any(p["value"] and p["value"] > 0.15
               for r in breached for p in r["series"])  # triggering series
    assert any(r["last_breach"] and r["last_breach"]["flight_dump"]
               for r in breached)
    # the events line carries the slo fragment
    n.event_reporter.on_canon_change([])  # ensure reporter is wired
    line = None
    n.miner.mine_block(timestamp=1_900_000_002)
    line = n.event_reporter.report_once()
    assert line is not None and "slo[" in line

    # recovery: clean traffic + enough windows for the stall deltas to
    # leave the aggregation window
    n.miner.mine_block(timestamp=1_900_000_003)
    for _ in range(14):
        eng.tick()
    assert eng.components()["hash_service"] == "ok"
    code, body = _get_health(port)
    assert body["components"]["hash_service"] == "ok"


def test_debug_metrics_history_rpc(health_node):
    n, _svc = health_node
    port = n.rpc.port
    n.miner.mine_block(timestamp=1_900_000_000)
    n.health.tick()
    n.health.tick()
    listing = _rpc(port, "debug_metricsHistory")
    assert "hash_service_dispatches_total" in listing["series"]
    series = _rpc(port, "debug_metricsHistory",
                  "hash_service_dispatches_total", 4)
    assert series["kind"] == "counter"
    assert len(series["points"]) <= 4
    assert series["points"][-1]["value"] > 0
    with pytest.raises(RuntimeError, match="no retained series"):
        _rpc(port, "debug_metricsHistory", "bogus_metric")


def test_health_endpoint_without_engine():
    """/health answers liveness + build identity even without --health."""
    from reth_tpu.rpc.server import RpcServer

    assert health.get_engine() is None
    srv = RpcServer()
    port = srv.start()
    try:
        code, body = _get_health(port)
        assert code == 200
        assert body["status"] == "unknown"
        assert body["health_engine"] == "off"
        assert body["build"]["version"]
    finally:
        srv.stop()


def test_debug_health_rpcs_error_without_engine():
    from reth_tpu.rpc.debug import DebugApi
    from reth_tpu.rpc.server import RpcError

    assert health.get_engine() is None
    api = DebugApi(eth_api=None)
    for fn in (api.debug_healthCheck, api.debug_sloStatus,
               api.debug_metricsHistory):
        with pytest.raises(RpcError, match="health engine disabled"):
            fn()


# -- perf-regression sentinel -------------------------------------------------


def test_bench_baseline_store_roundtrip(tmp_path):
    path = tmp_path / "baselines.json"
    store = BenchBaselineStore(path, keep=3)
    # no history: vs_prev pins to 1.0, never a regression
    v = store.assess("m", "exec", "cpu", "off", 100.0)
    assert v == {"vs_prev": 1.0, "regression": False, "baseline_n": 0,
                 "baseline": None}
    for x in (100.0, 110.0, 90.0):
        store.record("m", "exec", "cpu", "off", x)
    # reload from disk: median of trailing goods = 100
    store2 = BenchBaselineStore(path, keep=3)
    v = store2.assess("m", "exec", "cpu", "off", 95.0)
    assert v["vs_prev"] == pytest.approx(0.95)
    assert v["regression"] is False and v["baseline_n"] == 3
    v = store2.assess("m", "exec", "cpu", "off", 50.0)
    assert v["regression"] is True and v["vs_prev"] == pytest.approx(0.5)
    # keyed by backend/warmup: a numpy fallback never compares against
    # the device baseline
    v = store2.assess("m", "exec", "numpy", "off", 50.0)
    assert v["baseline_n"] == 0 and v["regression"] is False
    v = store2.assess("m", "exec", "cpu", {"state": "warming"}, 50.0)
    assert v["baseline_n"] == 0
    # keep=3 trims
    store2.record("m", "exec", "cpu", "off", 120.0)
    assert len(store2.runs("m", "exec", "cpu", "off")) == 3


def test_bench_baseline_store_corrupt_file_quarantined(tmp_path):
    path = tmp_path / "baselines.json"
    path.write_text("{not json")
    store = BenchBaselineStore(path)
    assert store.assess("m", "exec", "cpu", "off", 10.0)["baseline_n"] == 0
    store.record("m", "exec", "cpu", "off", 10.0)
    assert (tmp_path / "baselines.json.corrupt").exists()
    assert BenchBaselineStore(path).runs("m", "exec", "cpu",
                                         "off")[0]["value"] == 10.0


def _run_bench(tmp_path, extra_env, timeout=420):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "RETH_TPU_BENCH_BASELINE_STORE": str(tmp_path / "baselines.json"),
        "RETH_TPU_FLIGHT_DIR": str(tmp_path / "flight"),
    })
    env.update(extra_env)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, timeout=timeout,
                       cwd=REPO, env=env)
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.startswith("{")]
    assert lines, f"no JSON line: rc={r.returncode} stderr={r.stderr[-500:]}"
    return r.returncode, json.loads(lines[-1])


@pytest.mark.slow  # ~10s subprocess (jax import); `make test-health` runs it
def test_bench_rebuild_without_device_exits_nonzero(tmp_path):
    """Satellite: the rebuild mode is a DEVICE measurement. With no TPU
    and JAX_PLATFORMS not naming the cpu, it exits non-zero with value 0
    and says why — it never prints a numpy number under the device
    metric's name (was: the CPU-fallback measurement with rc=0). The
    tier-1-fast twin below covers the DEFAULT (exec) mode."""
    env = {
        "RETH_TPU_BENCH_MODE": "rebuild",
        "RETH_TPU_BENCH_ACCOUNTS": "2000",
        "RETH_TPU_BENCH_SLOTS": "800",
        "RETH_TPU_BENCH_TIMEOUT": "360",
    }
    # "" = not naming the cpu: the child is entitled to the TPU
    rc, line = _run_bench(tmp_path, {**env, "JAX_PLATFORMS": ""})
    assert rc != 0
    assert line["value"] == 0 and line["vs_baseline"] == 0
    assert "no device" in line["error"] and "'tpu'" in line["error"]
    assert "device_unavailable" not in line
    assert line["warmup_state"] is not None
    # named CPU (a rehearsal): runs, and the line says what it ran on
    rc, line = _run_bench(tmp_path, {**env, "RETH_TPU_BENCH_TIER": "1024"})
    assert rc == 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": line["device"]["count"]}
    assert line["value"] > 0 and line["backend"] == "device"


def test_bench_default_exec_mode_needs_no_device(tmp_path):
    """The DEFAULT bench (exec, PR 7) records a real CPU number with the
    sentinel fields even when every device probe would fail — it never
    touches the device."""
    rc, line = _run_bench(tmp_path, {
        "RETH_TPU_FAULT_PROBE_FAIL": "1",
        "RETH_TPU_BENCH_EXEC_TXS": "24",
        "RETH_TPU_BENCH_EXEC_WORKERS": "2",
        "RETH_TPU_BENCH_EXEC_REPS": "30",
        "RETH_TPU_BENCH_TIMEOUT": "360",
    })
    assert rc == 0
    assert line["metric"] == "exec_parallel_txs_per_sec"
    assert line["value"] > 0
    assert line["backend"] in ("cpu", "native-cpu")
    assert line["receipts_identical"] is True
    assert line["vs_prev"] == 1.0 and line["regression"] is False
    assert "warmup_state" in line and "compile_cache" in line
    # the store recorded the run for the next round's vs_prev
    store = BenchBaselineStore(tmp_path / "baselines.json")
    assert store.runs("exec_parallel_txs_per_sec", "exec",
                      line["backend"], "off")


# -- overhead guard -----------------------------------------------------------


def test_sampler_evaluator_overhead_guard():
    """Satellite: the health engine's steady-state cost — one sampler +
    evaluator pass per interval on its own thread — steals under 1% of a
    concurrent sparse-commit wall at the default 1 Hz cadence (mirrors
    PR 6's tracing-off guard)."""
    import numpy as np

    from reth_tpu.health import DEFAULT_INTERVAL_S
    from reth_tpu.trie.sparse import ParallelSparseCommitter, SparseStateTrie

    # a representative sparse-commit wall (the hot path being guarded)
    rng = np.random.default_rng(5)
    st = SparseStateTrie()
    for _ in range(24):
        ha = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        t = st.storage_trie(ha)
        for _ in range(24):
            t.update(bytes(rng.integers(0, 256, 32, dtype=np.uint8)),
                     bytes(rng.integers(1, 256, 8, dtype=np.uint8)))
        st.update_account(ha, b"leaf-" + ha)
    committer = ParallelSparseCommitter(workers=2)
    t0 = time.perf_counter()
    st.root(keccak256_batch_np, committer=committer)
    wall = time.perf_counter() - t0
    committer.shutdown()

    # steady-state tick cost over the FULL global registry (every metric
    # the node registers) with the default rule table
    eng = HealthEngine(REGISTRY, default_rules(), interval=0)
    eng.tick()  # baselines + lazy series allocation out of the measure
    reps = 30
    t0 = time.perf_counter()
    for _ in range(reps):
        eng.tick()
    per_tick = (time.perf_counter() - t0) / reps
    # the sampler thread steals per_tick seconds out of every interval
    stolen_fraction = per_tick / DEFAULT_INTERVAL_S
    assert stolen_fraction < 0.01, (
        f"health tick costs {per_tick * 1e3:.2f}ms per {DEFAULT_INTERVAL_S}s "
        f"interval ({stolen_fraction:.2%} of a concurrent "
        f"{wall * 1e3:.1f}ms sparse commit's cpu)")


def test_health_engine_thread_lifecycle():
    reg = MetricsRegistry()
    reg.gauge("probe_ms").set(1.0)
    eng = HealthEngine(reg, [_gauge_rule()], interval=0.02)
    eng.start()
    try:
        deadline = time.time() + 5
        while eng.ticks < 3 and time.time() < deadline:
            time.sleep(0.02)
        assert eng.ticks >= 3
    finally:
        eng.stop()
    ticks = eng.ticks
    time.sleep(0.08)
    assert eng.ticks == ticks  # thread actually stopped
