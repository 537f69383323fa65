"""Block-lifecycle observability: trace-context propagation, per-block
timelines, the flight recorder + fault-drill dumps, Chrome/OTLP span-file
validation, /metrics exposition-format checks, metrics thread safety, and
the tracing-disabled overhead guard.

Reference analogue: crates/tracing + crates/node/events — the reference
treats tracing as a first-class layer; these tests pin this repo's
equivalent end to end (ISSUE 6)."""

import json
import threading
import time

import pytest

from reth_tpu import tracing
from reth_tpu.metrics import (
    SUB_MS_BUCKETS,
    Counter,
    DeviceCompileTracker,
    Gauge,
    Histogram,
    HashServiceMetrics,
    MetricsRegistry,
)
from reth_tpu.primitives import Account
from reth_tpu.primitives.keccak import keccak256_batch_np
from reth_tpu.testing import ChainBuilder, Wallet
from reth_tpu.trie import TrieCommitter


@pytest.fixture(autouse=True)
def _trace_env(tmp_path, monkeypatch):
    """Isolate tracing state per test: flight dumps under tmp, fault-dump
    rate limits cleared, exporters and the enable switch reset after."""
    monkeypatch.setenv("RETH_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    rec = tracing.flight_recorder()
    rec.directory = None
    rec.dumps.clear()
    tracing.reset_fault_dump_limits()
    tracing.set_trace_enabled(False)
    yield
    tracing.shutdown_block_tracing()
    tracing.set_trace_enabled(False)
    rec.directory = None


# -- satellite: metrics thread safety ----------------------------------------


def test_metrics_thread_safety_hammer():
    """Counter.increment / Gauge.set / Histogram.record are unsynchronized
    read-modify-writes no more: N threads x M operations lose nothing."""
    c = Counter("hammer_total")
    g = Gauge("hammer_gauge")
    h = Histogram("hammer_seconds", buckets=(0.5, 1.0))
    threads, per = 8, 5000

    def worker(i):
        for k in range(per):
            c.increment()
            g.set(float(k))
            h.record(0.25 if k % 2 == 0 else 0.75)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == threads * per
    assert h.n == threads * per
    assert h.counts[0] + h.counts[1] == threads * per  # no lost bucket inc
    assert h.total == pytest.approx(threads * per * 0.5)


def test_submillisecond_buckets():
    """Device-dispatch/service histograms resolve 50µs-1ms timings instead
    of dumping everything into a 1ms-floor first bucket."""
    assert SUB_MS_BUCKETS[0] == pytest.approx(5e-5)
    reg = MetricsRegistry()
    m = HashServiceMetrics(reg)
    m.record_dispatch(requests=1, msgs=4, occupancy=1.0,
                      service_s=2e-4, replayed=False)
    m.record_wait("live", 8e-5)
    svc = reg._metrics["hash_service_service_seconds"]
    assert svc.buckets[0] < 1e-4 < svc.buckets[-1]
    # a 200µs dispatch lands in a real bucket, not just +Inf
    idx = next(i for i, b in enumerate(svc.buckets) if 2e-4 <= b)
    assert sum(svc.counts[: idx + 1]) == 1
    wait = reg._metrics["hash_service_wait_seconds_live"]
    assert wait.counts[1] == 1  # 80µs <= 100µs bucket


# -- satellite: exposition-format validation ----------------------------------


def _parse_exposition(text: str):
    types, samples = {}, {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            types[name] = kind
        else:
            name, _, value = line.rpartition(" ")
            samples[name] = float(value)
    return types, samples


def test_metrics_exposition_format():
    reg = MetricsRegistry()
    reg.counter("blocks_total", "help").increment(3)
    reg.gauge("head").set(9)
    h = reg.histogram("lat_seconds", buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.05, 5.0):
        h.record(v)
    text = reg.render()
    types, samples = _parse_exposition(text)
    assert types == {"blocks_total": "counter", "head": "gauge",
                     "lat_seconds": "histogram"}
    # cumulative le buckets, nondecreasing, +Inf == _count, _sum present
    les = [k for k in samples if k.startswith('lat_seconds_bucket{le="')
           and "+Inf" not in k]
    counts = [samples[k] for k in les]
    assert counts == sorted(counts) == [1, 2, 3]
    assert samples['lat_seconds_bucket{le="+Inf"}'] == samples["lat_seconds_count"] == 4
    assert samples["lat_seconds_sum"] == pytest.approx(5.0555)


def test_global_metrics_exposition_valid():
    """The real /metrics surface (every registered subsystem) stays
    format-valid: TYPE lines precede samples, histogram invariants hold."""
    from reth_tpu.metrics import REGISTRY, update_process_metrics

    update_process_metrics()
    text = REGISTRY.render()
    types, samples = _parse_exposition(text)
    for name, kind in types.items():
        if kind == "histogram":
            inf = samples[f'{name}_bucket{{le="+Inf"}}']
            assert inf == samples[f"{name}_count"]
            assert f"{name}_sum" in samples
            les = [v for k, v in samples.items()
                   if k.startswith(f'{name}_bucket{{le="') and "+Inf" not in k]
            assert les == sorted(les)  # cumulative
        else:
            # labeled gauges (the *_info convention, e.g. build_info)
            # render as name{k="v"} value under a bare TYPE line
            assert name in samples or any(
                k.startswith(name + "{") for k in samples)


# -- trace context ------------------------------------------------------------


def test_span_context_propagation():
    tracing.set_trace_enabled(True)
    rec = tracing.flight_recorder()
    before = rec.recorded
    with tracing.trace_block("aa" * 32, number=1) as root:
        assert root.trace_id == "aa" * 32
        with tracing.span("t", "child") as c1:
            assert c1.trace_id == "aa" * 32
            captured = tracing.current_context()

            # explicit handoff into a worker thread
            def worker():
                with tracing.use_context(captured):
                    with tracing.span("t", "grandchild"):
                        pass

            t = threading.Thread(target=worker)
            t.start()
            t.join()
        tracing.record_span("t", "attributed", time.time() - 0.01, 0.01,
                            ctx=captured, fields={"wait_ms": 4.0})
    tl = tracing.block_timeline("aa" * 32)
    by_name = {r["name"]: r for r in tl}
    assert by_name["grandchild"]["parent"] == by_name["child"]["span"]
    assert by_name["attributed"]["parent"] == by_name["child"]["span"]
    assert by_name["child"]["parent"] == by_name["block"]["span"]
    assert by_name["block"]["parent"] is None
    assert all(r["trace"] == "aa" * 32 for r in tl)
    assert rec.recorded > before  # spans landed in the flight recorder
    assert tracing.block_summary("aa" * 32)["total_ms"] >= 0


def test_span_disabled_is_contextless():
    assert not tracing.trace_enabled()
    with tracing.span("t", "x") as ctx:
        assert ctx is None
        assert tracing.current_context() is None


# -- end-to-end: engine block timeline ----------------------------------------


def _make_traced_env(n_txs=6, with_service=False):
    from reth_tpu.engine import EngineTree
    from reth_tpu.storage import MemDb, ProviderFactory
    from reth_tpu.storage.genesis import init_genesis

    cpu = TrieCommitter(hasher=keccak256_batch_np)
    alice = Wallet(0xA11CE)
    bob = Wallet(0xB0B)
    builder = ChainBuilder(
        {alice.address: Account(balance=10**21),
         bob.address: Account(balance=10**20)}, committer=cpu)
    builder.build_block([alice.transfer(bob.address, 10**15 + i)
                         for i in range(n_txs)])
    factory = ProviderFactory(MemDb())
    init_genesis(factory, builder.genesis, builder.accounts_at_genesis,
                 committer=cpu)
    svc = None
    committer = cpu
    if with_service:
        from reth_tpu.ops.hash_service import HashService

        committer = TrieCommitter(hasher=keccak256_batch_np)
        svc = HashService(backend=keccak256_batch_np,
                          registry=MetricsRegistry())
        committer.hash_service = svc
        committer.hasher = svc.client("live")
    tree = EngineTree(factory, committer=committer, persistence_threshold=2)
    return builder, tree, svc


def test_block_timeline_coverage_and_attribution():
    """Acceptance: tracing a block yields a timeline whose direct phase
    spans account for >=95% of the block's wall, with hash-service
    queue-wait vs dispatch attribution visible."""
    from reth_tpu.engine.tree import PayloadStatusKind

    tracing.set_trace_enabled(True)
    builder, tree, svc = _make_traced_env(n_txs=6, with_service=True)
    try:
        blk = builder.blocks[1]
        st = tree.on_new_payload(blk)
        assert st.status is PayloadStatusKind.VALID, st.validation_error
        trace_id = blk.hash.hex()
        tl = tracing.block_timeline(trace_id)
        assert tl, "no timeline recorded"
        names = {r["name"] for r in tl}
        # the lifecycle phases are all present
        assert {"block", "validate", "prepare", "recover_senders",
                "execute", "state_root", "finalize"} <= names
        assert "prewarm" in names  # 6 txs >= prewarm threshold
        # hash-service attribution: per-request queue-wait vs dispatch
        reqs = [r for r in tl if r["name"] == "hashsvc.request"]
        assert reqs, "no hash-service request spans in the block timeline"
        for r in reqs:
            assert "wait_ms" in r["fields"] and "service_ms" in r["fields"]
        summary = tracing.block_summary(trace_id)
        assert summary["coverage"] >= 0.95, summary
        assert summary["total_ms"] > 0
        assert summary["exec_ms"] > 0 and summary["root_ms"] > 0
        # parent ids resolve within the timeline
        ids = {r["span"] for r in tl if r["span"] is not None}
        root_id = next(r["span"] for r in tl if r["parent"] is None
                       and r["kind"] == "span")
        for r in tl:
            if r["parent"] is not None:
                assert r["parent"] in ids
        # nesting monotonic: every direct child sits inside the root span
        root = next(r for r in tl if r["span"] == root_id)
        lo, hi = root["ts"], root["ts"] + root["dur_ms"] / 1e3
        for r in tl:
            if r["kind"] == "span" and r["parent"] == root_id:
                assert r["ts"] >= lo - 0.002
                assert r["ts"] + r["dur_ms"] / 1e3 <= hi + 0.002
    finally:
        if svc is not None:
            svc.stop()


def test_chrome_and_otlp_span_files(tmp_path):
    """Exporter files: valid JSON lines, parent ids resolve, children
    nest inside their parents."""
    from reth_tpu.engine.tree import PayloadStatusKind

    chrome = tmp_path / "blocks.trace.json"
    otlp = tmp_path / "spans.otlp.jsonl"
    tracing.init_block_tracing(chrome_path=chrome, otlp_path=otlp)
    builder, tree, _ = _make_traced_env(n_txs=5)
    st = tree.on_new_payload(builder.blocks[1])
    assert st.status is PayloadStatusKind.VALID
    tracing.shutdown_block_tracing()

    # chrome file: strictly valid JSON array once closed, AND one event
    # per line for the JSONL view
    events = json.loads(chrome.read_text())
    assert tracing.read_chrome_trace(chrome) == events
    spans = [e for e in events if e.get("ph") == "X"]
    assert spans
    by_id = {e["args"]["span_id"]: e for e in spans if "span_id" in e["args"]}
    root = next(e for e in spans if e["name"] == "block")
    checked = 0
    for e in spans:
        pid = e["args"].get("parent_id")
        if pid is None:
            continue
        assert pid in by_id, f"dangling parent {pid}"
        # nesting monotonic for the block's phase spans (µs timestamps;
        # small slack — worker-attributed spans overlap phases by design)
        if pid == root["args"]["span_id"]:
            assert e["ts"] >= root["ts"] - 2e3
            assert (e["ts"] + e.get("dur", 0)
                    <= root["ts"] + root.get("dur", 0) + 2e3)
            checked += 1
    assert checked > 3

    # OTLP file: one valid JSON object per line, ids resolve
    lines = [json.loads(line) for line in otlp.read_text().splitlines()]
    assert lines
    osp = [line["scopeSpans"][0]["spans"][0] for line in lines]
    ids = {s["spanId"] for s in osp if "spanId" in s}
    for s in osp:
        assert int(s["endTimeUnixNano"]) >= int(s["startTimeUnixNano"])
        if "parentSpanId" in s:
            assert s["parentSpanId"] in ids
    assert any("traceId" in s for s in osp)


# -- flight recorder + fault drills -------------------------------------------


def test_flight_recorder_dump_roundtrip(tmp_path):
    tracing.set_trace_enabled(True)
    with tracing.span("t", "work", leaves=3):
        tracing.event("t", "checkpoint", at="mid")
    path = tracing.flight_dump("unit_test", tmp_path / "dump.jsonl")
    header, records = tracing.load_flight_dump(path)
    assert header["reason"] == "unit_test" and header["records"] == len(records)
    names = [r["name"] for r in records]
    assert "work" in names and "checkpoint" in names


def test_service_wedge_drill_dumps_flight_recorder():
    """Acceptance: a RETH_TPU_FAULT_SERVICE_WEDGE drill emits a JSONL
    dump a test can parse to locate the failing dispatch."""
    from reth_tpu.ops.hash_service import HashService, ServiceFaultInjector

    svc = HashService(backend=keccak256_batch_np,
                      injector=ServiceFaultInjector(wedge_every=1),
                      registry=MetricsRegistry())
    try:
        out = svc.hash("live", [b"abc"])  # completes via numpy-twin replay
        assert out == keccak256_batch_np([b"abc"])
        assert svc.replays == 1
    finally:
        svc.stop()
    dumps = tracing.flight_recorder().dumps
    assert dumps, "wedge drill wrote no flight dump"
    header, records = tracing.load_flight_dump(dumps[-1])
    assert "SERVICE_WEDGE" in header["reason"]
    fault = next(r for r in records
                 if r["name"] == "RETH_TPU_FAULT_SERVICE_WEDGE_EVERY")
    assert fault["target"] == "ops::hash_service"
    assert fault["fields"]["dispatch"] == 1


def test_gateway_stall_drill_dumps_flight_recorder():
    from reth_tpu.rpc.gateway import GatewayFaultInjector, RpcGateway

    gw = RpcGateway(head_supplier=lambda: b"h",
                    injector=GatewayFaultInjector(stall=0.001),
                    registry=MetricsRegistry())
    assert gw.call("eth_blockNumber", [], lambda: "0x1") == "0x1"
    dumps = tracing.flight_recorder().dumps
    assert dumps
    header, records = tracing.load_flight_dump(dumps[-1])
    assert "GATEWAY_STALL" in header["reason"]
    assert any(r["name"] == "RETH_TPU_FAULT_GATEWAY_STALL"
               and r["target"] == "rpc::gateway" for r in records)


def test_breaker_open_dumps_flight_recorder():
    from reth_tpu.ops.supervisor import CircuitBreaker

    br = CircuitBreaker(failure_threshold=1)
    assert br.record_failure()  # opens
    dumps = tracing.flight_recorder().dumps
    assert dumps
    header, records = tracing.load_flight_dump(dumps[-1])
    assert header["reason"] == "breaker_open"
    ev = next(r for r in records if r["name"] == "breaker_open")
    assert ev["fields"]["state"] == "open"


def test_sparse_abort_drill_dumps():
    from reth_tpu.trie.sparse import (
        InjectedSparseAbort,
        ParallelSparseCommitter,
        SparseFaultInjector,
        SparseTrie,
    )

    t = SparseTrie()
    t.update(b"\x11" * 32, b"v1")
    committer = ParallelSparseCommitter(
        workers=1, injector=SparseFaultInjector(abort_at=1))
    with pytest.raises(InjectedSparseAbort):
        committer.commit([t], keccak256_batch_np)
    dumps = tracing.flight_recorder().dumps
    assert dumps and "SPARSE_ABORT" in dumps[-1]


# -- debug RPCs ---------------------------------------------------------------


def test_debug_rpc_methods():
    from reth_tpu.rpc.debug import DebugApi
    from reth_tpu.rpc.server import RpcError

    api = DebugApi(None)  # tracing surfaces need no eth backend
    with pytest.raises(RpcError):
        api.debug_blockTimeline("0x" + "ee" * 32)  # tracing disabled

    tracing.set_trace_enabled(True)
    with tracing.trace_block("cd" * 32, number=12):
        with tracing.span("t", "phase"):
            pass
    out = api.debug_blockTimeline("0x" + "cd" * 32)
    assert out["traceId"] == "cd" * 32
    assert out["summary"]["number"] == 12
    assert any(r["name"] == "phase" for r in out["spans"])
    # None = most recent trace
    assert api.debug_blockTimeline(None)["traceId"] == "cd" * 32
    with pytest.raises(RpcError):
        api.debug_blockTimeline("0x" + "00" * 32)

    fr = api.debug_flightRecorder()
    assert fr["recorded"] >= 1 and fr["records"]
    dumped = api.debug_flightRecorder("dump")
    assert dumped["path"] and dumped["path"] in dumped["dumps"]
    header, _ = tracing.load_flight_dump(dumped["path"])
    assert header["reason"] == "rpc_request"
    with pytest.raises(RpcError):
        api.debug_flightRecorder("bogus")


def test_events_dashboard_wall_budget_line():
    from types import SimpleNamespace

    from reth_tpu.node.events import NodeEventReporter

    tracing.set_trace_enabled(True)
    with tracing.trace_block("ab" * 32, number=7):
        with tracing.span("engine::prewarm", "prewarm"):
            pass
        with tracing.span("engine::execute", "execute"):
            pass
        with tracing.span("engine::tree", "state_root"):
            pass
    s = tracing.last_block_summary()
    assert s is not None and s["number"] == 7
    budget = tracing.format_wall_budget(s)
    assert budget.startswith("block 7 total=")
    assert "prewarm" in budget and "dispatch" in budget

    cpu = TrieCommitter(hasher=keccak256_batch_np)
    alice = Wallet(0xA11CE)
    builder = ChainBuilder({alice.address: Account(balance=10**21)},
                           committer=cpu)
    builder.build_block([alice.transfer(b"\x0b" * 20, 5)])
    rep = NodeEventReporter(SimpleNamespace(pool=None, network=None),
                            interval=999)
    rep.on_canon_change([SimpleNamespace(block=builder.blocks[1])])
    line = rep.report_once()
    assert "block 7 total=" in line


# -- compile tracker ----------------------------------------------------------


def test_compile_tracker_splits_first_call():
    reg = MetricsRegistry()
    tr = DeviceCompileTracker(reg)
    assert tr.record("keccak.exact", (1, 1024), 0.5) is True  # compile
    assert tr.record("keccak.exact", (1, 1024), 0.001) is False
    assert tr.record("keccak.exact", (2, 1024), 0.3) is True  # new shape
    t = tr.totals()
    assert t["shapes"] == 2
    assert t["compile_wall_s"] == pytest.approx(0.8)
    assert t["execute_wall_s"] == pytest.approx(0.001)
    assert reg._metrics["keccak_compile_total"].value == 2
    assert reg._metrics["keccak_dispatch_total"].value == 1


def test_keccak_device_reports_shapes():
    jax = pytest.importorskip("jax")  # noqa: F841
    from reth_tpu.metrics import compile_tracker
    from reth_tpu.ops.keccak_jax import KeccakDevice

    # the tracker is process-global: earlier tests may already have
    # compiled these shapes, so assert on deltas (new shape OR new
    # steady-state calls), not on absolute shape counts
    before = compile_tracker.totals()
    dev = KeccakDevice(min_tier=8)
    out = dev.hash_batch([b"x" * 5, b"y" * 200])
    assert out == keccak256_batch_np([b"x" * 5, b"y" * 200])
    after = compile_tracker.totals()
    assert (after["shapes"] > before["shapes"]
            or after["execute_calls"] > before["execute_calls"])


# -- overhead guard -----------------------------------------------------------


def _sparse_workload(n_tries=24, slots=24, dirty=6, seed=5):
    import numpy as np

    from reth_tpu.trie.sparse import SparseStateTrie

    rng = np.random.default_rng(seed)
    st = SparseStateTrie()
    for _ in range(n_tries):
        ha = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        t = st.storage_trie(ha)
        for _ in range(slots):
            t.update(bytes(rng.integers(0, 256, 32, dtype=np.uint8)),
                     bytes(rng.integers(1, 256, 8, dtype=np.uint8)))
        st.update_account(ha, b"leaf-" + ha)
    return st


def test_tracing_disabled_overhead_guard():
    """Satellite: with tracing off, the instrumentation's cost (span
    count x per-span disabled cost) stays under 1% of the sparse-commit
    wall — the hot path pays for observability only when asked to."""
    from reth_tpu.trie.sparse import ParallelSparseCommitter

    # (1) wall of the instrumented workload with tracing disabled
    assert not tracing.trace_enabled()
    st = _sparse_workload()
    committer = ParallelSparseCommitter(workers=2)
    t0 = time.perf_counter()
    st.root(keccak256_batch_np, committer=committer)
    wall = time.perf_counter() - t0
    committer.shutdown()

    # (2) spans the same workload emits when tracing is ON
    tracing.set_trace_enabled(True)
    rec = tracing.flight_recorder()
    before = rec.recorded
    st2 = _sparse_workload()
    committer2 = ParallelSparseCommitter(workers=2)
    st2.root(keccak256_batch_np, committer=committer2)
    committer2.shutdown()
    n_spans = rec.recorded - before
    tracing.set_trace_enabled(False)

    # (3) per-span cost with tracing disabled
    reps = 20_000
    t0 = time.perf_counter()
    for _ in range(reps):
        with tracing.span("trie::sparse", "overhead.probe"):
            pass
    per_span = (time.perf_counter() - t0) / reps

    overhead = n_spans * per_span
    assert overhead < 0.01 * wall, (
        f"disabled tracing would cost {overhead * 1e3:.3f}ms on a "
        f"{wall * 1e3:.1f}ms commit ({n_spans} spans x "
        f"{per_span * 1e6:.2f}µs)")


# -- bench: device-unavailable reporting --------------------------------------


@pytest.mark.slow
def test_bench_device_unavailable_exits_nonzero_with_flight_excerpt(tmp_path):
    """Satellite: the rebuild mode without its device yields a NON-ZERO
    exit, an error that names the platform found, the compile/steady
    split and the flight-recorder field — and no number (was: rc=0 with a
    numpy measurement)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    env = {**os.environ,
           "JAX_PLATFORMS": "",  # not naming the cpu: entitled to the TPU
           "RETH_TPU_BENCH_MODE": "rebuild",
           "RETH_TPU_BENCH_ACCOUNTS": "1500", "RETH_TPU_BENCH_SLOTS": "400",
           "RETH_TPU_BENCH_TIMEOUT": "300",
           "RETH_TPU_BENCH_BASELINE_STORE": str(tmp_path / "baselines.json"),
           "RETH_TPU_FLIGHT_DIR": str(tmp_path)}
    root = Path(__file__).resolve().parents[1]
    r = subprocess.run([sys.executable, str(root / "bench.py")],
                       capture_output=True, text=True, timeout=280,
                       cwd=root, env=env)
    assert r.returncode != 0, r.stdout + r.stderr
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["value"] == 0
    assert "no device" in line["error"]
    assert "device_unavailable" not in line
    assert "compile_wall_s" in line
    assert "flight_recorder" in line
