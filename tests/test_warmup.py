"""Device warm-up manager drills (reth_tpu/ops/warmup.py).

The acceptance drills: with RETH_TPU_FAULT_COMPILE_WEDGE forcing shape
compiles past their watchdog budget, the node serves DEGRADED on the CPU
twin (bit-identical digests), compiles retry with exponential backoff, the
circuit breaker trips instead of startup freezing, and shapes promote to
the device once the fault clears. The persistent compilation cache is
configured in ONE function (ops/device.configure_compile_cache) at one
fixed place; CompileCache only reports on it.
Everything runs CPU-only (JAX_PLATFORMS=cpu via conftest) — the injector
stands in for a stuck compile, which is the point: the compile lifecycle
must be testable without hardware.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from reth_tpu.metrics import MetricsRegistry, compile_tracker
from reth_tpu.ops.fused_commit import FusedLevelEngine, _Bucket
from reth_tpu.ops.keccak_jax import _CPU_BUCKET, KeccakDevice, _next_tier
from reth_tpu.ops.supervisor import (
    CLOSED,
    OPEN,
    CircuitBreaker,
    DeviceSupervisor,
    FaultInjector,
    ProbeResult,
)
from reth_tpu.ops.warmup import (
    COLD,
    FAILED,
    WARM,
    CompileCache,
    MenuShape,
    WarmupManager,
    build_warmup,
    default_menu,
)
from reth_tpu.primitives.keccak import keccak256, keccak256_batch_np
from reth_tpu.trie.committer import TrieCommitter


def _ok_probe(budget, injector=None, **kw):
    return ProbeResult(True, 0.001)


def _supervisor(**kw):
    kw.setdefault("dispatch_budget", 120.0)
    kw.setdefault("probe_fn", _ok_probe)
    kw.setdefault("registry", MetricsRegistry())
    return DeviceSupervisor(**kw)


def _mgr(menu=None, builder=None, **kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("budget", 0.25)
    kw.setdefault("attempts", 2)
    kw.setdefault("backoff", 0.01)
    if menu is None:
        menu = [MenuShape("keccak.masked", 4, 8),
                MenuShape("keccak.masked", 8, 8)]
    if builder is None:
        builder = lambda shape: None  # noqa: E731
    return WarmupManager(menu=menu, builder=builder, **kw)


def _msgs(n, size=40, seed=0):
    rng = np.random.default_rng(seed)
    return [bytes(rng.integers(0, 256, size, dtype=np.uint8))
            for _ in range(n)]


# -- shape menu ---------------------------------------------------------------


def test_default_menu_grid():
    menu = default_menu(min_tier=1024, block_tier=4, max_batch_tier=16384,
                        max_block_tier=32)
    keys = [s.key() for s in menu]
    assert len(keys) == len(set(keys))
    # batch ladder for trie-node-sized messages
    for t in (1024, 2048, 4096, 8192, 16384):
        assert ("keccak.masked", 4, t, 1) in keys
    # block ladder for large messages at the base tier
    for bt in (8, 16, 32):
        assert ("keccak.masked", bt, 1024, 1) in keys
    # fused level-commit programs
    assert ("fused.plain", 4, 1024, 1) in keys
    assert ("fused.splice", 4, 1024, 1) in keys
    # ceilings respected
    assert all(s.batch_tier <= 16384 and s.block_tier <= 32 for s in menu)
    assert default_menu(include_fused=False) == [
        s for s in menu if not s.program.startswith("fused")]


def test_default_menu_mesh_variants():
    """mesh_sizes adds SPMD menu slots whose tiers sit on the
    device-count-multiple ladder (what MeshKeccak/FusedMeshEngine mint)."""
    menu = default_menu(min_tier=1024, mesh_sizes=(8,))
    keys = [s.key() for s in menu]
    assert len(keys) == len(set(keys))
    for t in (1024, 2048, 4096, 8192, 16384):
        assert ("keccak.masked", 4, t, 8) in keys
    assert ("fused.plain", 4, 1024, 8) in keys
    assert ("fused.splice", 4, 1024, 8) in keys
    # a non-pow2 mesh rounds the floor up to a device-count multiple
    menu6 = default_menu(min_tier=1024, mesh_sizes=(6,))
    mesh6 = [s for s in menu6 if s.mesh_size == 6]
    assert mesh6 and all(s.batch_tier % 6 == 0 for s in mesh6)
    assert ("fused.plain", 4, 1026, 6) in [s.key() for s in mesh6]
    assert str(mesh6[0]).endswith("@m6")


def test_next_tier_clamps_to_menu_ceiling():
    assert _next_tier(5, 8) == 8
    assert _next_tier(100, 8) == 128
    assert _next_tier(100_000, 8, max_tier=1024) == 1024
    assert _next_tier(100, 8, max_tier=1024) == 128


# -- persistent compilation cache ---------------------------------------------


class _ConfigRecorder:
    """Stands in for ``jax.config.update`` so a test can see what the
    cache function WOULD set without touching the worker's jax config."""

    def __init__(self):
        self.calls = []

    def __call__(self, name, value):
        self.calls.append((name, value))


def test_compile_cache_dir_is_one_fixed_path_in_the_checkout(monkeypatch):
    """Was: the digest-versioned ``xla-<digest>`` directory. Now the path
    is fixed — not datadir-, mesh-, source- or pid-derived — because the
    path is part of the cache key and JAX's own key covers the program."""
    from reth_tpu.ops import device

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = Path(__file__).resolve().parent.parent
    assert device.compile_cache_dir() == repo / ".jax_cache"
    assert device.DEFAULT_COMPILE_CACHE_DIR == repo / ".jax_cache"
    assert CompileCache().dir == repo / ".jax_cache"
    # the mesh size adds menu shapes, never a different cache directory
    assert (build_warmup(registry=MetricsRegistry(), mesh_size=8,
                         builder=lambda s: None).cache.dir
            == repo / ".jax_cache")
    ignored = (repo / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_compile_cache_reports_entries(tmp_path):
    cc = CompileCache(tmp_path)
    assert cc.entry_count() == 0 and cc.summary()["mode"] == "cold"
    (tmp_path / "jit_f-abc-cache").write_bytes(b"x" * 64)
    (tmp_path / "jit_f-abc-atime").write_bytes(b"t")  # JAX's access stamp
    (tmp_path / "jit_g-def-cache").write_bytes(b"y" * 64)
    assert cc.entry_count() == 2
    assert cc.summary() == {"mode": "cold", "dir": str(tmp_path),
                            "entries": 2}
    assert CompileCache(tmp_path).summary()["mode"] == "warm"  # restart
    assert CompileCache(tmp_path / "missing").entry_count() == 0


def test_configure_compile_cache_sets_nothing_when_env_names_the_dir(
        tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself; no code path
    calls jax.config.update("jax_compilation_cache_dir", ...)."""
    import jax

    from reth_tpu.ops import device

    rec = _ConfigRecorder()
    monkeypatch.setattr(jax.config, "update", rec)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device.configure_compile_cache() == tmp_path
    assert device.configure_compile_cache() == tmp_path  # idempotent
    assert rec.calls == []
    assert CompileCache().dir == tmp_path


def test_configure_compile_cache_fixed_path_when_env_unset(monkeypatch):
    import jax

    from reth_tpu.ops import device

    rec = _ConfigRecorder()
    monkeypatch.setattr(jax.config, "update", rec)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert device.configure_compile_cache() == device.DEFAULT_COMPILE_CACHE_DIR
    want = ("jax_compilation_cache_dir", str(device.DEFAULT_COMPILE_CACHE_DIR))
    if jax.config.jax_compilation_cache_dir != want[1]:
        assert rec.calls == [want]
    # only ever the directory, and never a reset to None
    assert all(c == want for c in rec.calls)


def test_compile_cache_never_touches_jax_config(tmp_path, monkeypatch):
    """Was: the enable()/disable() round trip. The reporter has neither —
    a warm-up pass with a cache attached leaves the jax config alone."""
    import jax

    rec = _ConfigRecorder()
    monkeypatch.setattr(jax.config, "update", rec)
    cc = CompileCache(tmp_path)
    assert not hasattr(cc, "enable") and not hasattr(cc, "disable")
    assert not hasattr(cc, "probe") and not hasattr(cc, "validate")
    _mgr(cache=cc).run()
    assert rec.calls == []


# -- manager lifecycle --------------------------------------------------------


def test_happy_path_all_shapes_warm():
    built = []
    mgr = _mgr(builder=built.append)
    assert mgr.overall_state() == "off"
    snap = mgr.run()
    assert [s.key() for s in built] == [s.key() for s in mgr.menu]
    assert snap["state"] == "warm"
    assert snap["warm"] == snap["total"] == 2 and snap["failed"] == 0
    assert mgr.device_ready()
    assert mgr.route_bucket("keccak.masked", 4, 8)
    # fully warm: off-menu stragglers are allowed (watchdog covers them)
    assert mgr.route_bucket("keccak.masked", 64, 8)
    assert mgr.cpu_routed == 0
    assert all(s == WARM for s in mgr.states.values())


def test_no_gating_before_start():
    mgr = _mgr()
    assert mgr.device_ready()
    assert mgr.route_bucket("keccak.masked", 4, 8)
    assert mgr.route_bucket("anything", 1, 1)
    assert mgr.cpu_routed == 0


def test_degraded_routing_while_warming():
    mgr = _mgr()
    mgr._active = True  # mid-warm-up: nothing compiled yet
    assert not mgr.device_ready()
    assert not mgr.route_bucket("keccak.masked", 4, 8)
    assert mgr.cpu_routed == 1
    # per-shape promotion: ONE shape warming routes ITS buckets to the
    # device while the sibling still serves on the CPU twin
    mgr.states[("keccak.masked", 4, 8, 1)] = WARM
    assert mgr.route_bucket("keccak.masked", 4, 8)
    assert not mgr.route_bucket("keccak.masked", 8, 8)
    assert mgr.cpu_routed == 2
    assert mgr.overall_state() == "warming"


def test_background_start_and_wait():
    slow = threading.Event()

    def builder(shape):
        slow.wait(2.0)

    mgr = _mgr(builder=builder)
    mgr.start()
    assert not mgr.device_ready()  # warming in the background
    slow.set()
    assert mgr.wait(5.0)
    assert mgr.device_ready()
    mgr.start()  # idempotent once done (thread not alive)
    assert mgr.device_ready()


def test_compile_wedge_drill_budget_retry_then_warm():
    """RETH_TPU_FAULT_COMPILE_WEDGE=1: the first compile wedges PAST the
    watchdog budget (real join-timeout path), the retry succeeds."""
    inj = FaultInjector(compile_wedge=1)
    mgr = _mgr(menu=[MenuShape("keccak.masked", 4, 8)], injector=inj,
               budget=0.1, attempts=3, backoff=0.01)
    t0 = time.monotonic()
    snap = mgr.run()
    assert snap["state"] == "warm"
    assert mgr.wedges == 1 and mgr.retries == 1
    assert inj.compiles_wedged == 1 and inj.compile_wedge == 0
    # the wedged attempt burned ~the budget, not the injected sleep
    assert time.monotonic() - t0 < 1.5


def test_compile_wedge_forever_trips_breaker_and_degrades():
    """The full drill: every compile wedges -> shapes FAIL after bounded
    retries, the supervisor's breaker OPENS (startup never freezes), and
    serving is degraded to the CPU twin."""
    inj = FaultInjector(compile_wedge=-1)
    breaker = CircuitBreaker(failure_threshold=2, reset_timeout=0.05)
    sup = _supervisor(breaker=breaker, injector=inj)
    mgr = _mgr(menu=[MenuShape("keccak.masked", 4, 8)], supervisor=sup,
               injector=inj, budget=0.05, attempts=2, backoff=0.01)
    assert sup.warmup is mgr  # attached at construction
    snap = mgr.run()
    assert snap["state"] == "degraded" and snap["failed"] == 1
    assert mgr.states[("keccak.masked", 4, 8, 1)] == FAILED
    assert breaker.state == OPEN  # wedges fed the breaker
    assert not mgr.device_ready()
    assert not sup.warmup_allows_device()
    assert not mgr.route_bucket("keccak.masked", 4, 8)


def test_promotion_after_fault_clears_via_half_open_probe():
    """Recovery: the fault clears, the breaker's half-open probe succeeds,
    and on_device_recovered promotes the FAILED shapes."""
    inj = FaultInjector(compile_wedge=-1)
    breaker = CircuitBreaker(failure_threshold=2, reset_timeout=0.05)
    sup = _supervisor(breaker=breaker, injector=inj)
    mgr = _mgr(menu=[MenuShape("keccak.masked", 4, 8)], supervisor=sup,
               injector=inj, budget=0.05, attempts=2, backoff=0.01)
    mgr.run()
    assert breaker.state == OPEN and not mgr.device_ready()
    with inj._lock:
        inj.compile_wedge = 0  # the wedge clears
    time.sleep(0.06)  # past the breaker cooldown -> next route half-opens
    assert sup.allows_device()  # half-open probe ok -> closes + promotes
    for _ in range(200):
        if mgr.device_ready():
            break
        time.sleep(0.01)
    assert mgr.device_ready()
    assert mgr.states[("keccak.masked", 4, 8, 1)] == WARM
    assert breaker.state == CLOSED
    assert sup.warmup_allows_device()


def test_breaker_open_defers_without_burning_attempts():
    sup = _supervisor()
    sup.breaker.force_open()

    def builder(shape):  # pragma: no cover - must not run
        raise AssertionError("compile attempted while breaker open")

    mgr = _mgr(menu=[MenuShape("keccak.masked", 4, 8)], supervisor=sup,
               builder=builder)
    snap = mgr.run()
    assert snap["state"] == "degraded"
    assert mgr.states[("keccak.masked", 4, 8, 1)] == FAILED
    assert mgr.wedges == 0  # deferred, not wedged


def test_retry_failed_reentrancy_guard():
    calls = []
    mgr = _mgr(menu=[MenuShape("keccak.masked", 4, 8)],
               builder=calls.append, attempts=1)
    mgr._active = True
    mgr.states[("keccak.masked", 4, 8, 1)] = FAILED
    with mgr._lock:
        mgr._retrying = True
    assert mgr.retry_failed() == 0  # guarded
    with mgr._lock:
        mgr._retrying = False
    assert mgr.retry_failed() == 1
    assert len(calls) == 1


def test_fault_injector_env_and_active():
    inj = FaultInjector.from_env({"RETH_TPU_FAULT_COMPILE_WEDGE": "2"})
    assert inj is not None and inj.compile_wedge == 2 and inj.active()
    t0 = time.monotonic()
    inj.on_compile(0.01)
    inj.on_compile(0.01)
    assert inj.compiles_wedged == 2 and inj.compile_wedge == 0
    inj.on_compile(0.01)  # exhausted: no wedge
    assert inj.compiles_wedged == 2
    assert time.monotonic() - t0 < 5
    assert FaultInjector.from_env({}) is None


# -- degraded-mode serving through the real dispatch front-ends ---------------


def test_keccak_device_degraded_buckets_bit_identical():
    msgs = _msgs(5)
    expect = keccak256_batch_np(msgs)
    mgr = _mgr(menu=[MenuShape("keccak.masked", 4, 8)])
    dev = KeccakDevice(min_tier=8, block_tier=4, warmup=mgr)
    assert dev.hash_batch(msgs) == expect  # not started: device route
    mgr._active = True  # warming: CPU twin, same digests
    assert dev.hash_batch(msgs) == expect
    assert mgr.cpu_routed >= 1
    routed = mgr.cpu_routed
    mgr.states[("keccak.masked", 4, 8, 1)] = WARM  # promoted mid-warm-up
    assert dev.hash_batch(msgs) == expect
    assert mgr.cpu_routed == routed  # warm shape went to the device


def test_supervised_hasher_picks_up_attached_warmup():
    sup = _supervisor()
    mgr = _mgr(supervisor=sup)
    committer = TrieCommitter(supervisor=sup)
    committer.attach_warmup(mgr)
    assert committer.warmup is mgr
    assert committer.hasher._warmup is mgr
    msgs = _msgs(4)
    mgr._active = True  # degraded: buckets on the CPU twin
    assert committer.hasher(msgs) == keccak256_batch_np(msgs)
    assert mgr.cpu_routed >= 1


def test_attach_warmup_reaches_plain_keccak_device():
    committer = TrieCommitter(min_tier=8)
    mgr = _mgr()
    committer.attach_warmup(mgr)
    assert committer.hasher.__self__.warmup is mgr


def test_supervised_backend_fused_commit_gated_until_warm():
    from reth_tpu.primitives.nibbles import unpack_nibbles
    from reth_tpu.primitives.rlp import rlp_encode

    leaves = [(unpack_nibbles(keccak256(bytes([i]))),
               rlp_encode(b"v%d" % i)) for i in range(40)]
    expect = TrieCommitter(hasher=keccak256_batch_np).commit(leaves).root

    sup = _supervisor()
    mgr = _mgr(supervisor=sup)
    mgr._active = True  # warming
    committer = TrieCommitter(fused=True, min_tier=16, supervisor=sup)
    res = committer.commit(leaves)
    assert res.root == expect
    assert committer._engine.effective_kind == "numpy"  # degraded commit
    mgr.run()  # everything warms
    res = committer.commit(leaves)
    assert res.root == expect
    assert committer._engine.effective_kind == "device"


# -- tier clamps (keccak_jax + fused_commit mirrors) --------------------------


def test_oversized_batch_chunked_at_menu_ceiling():
    before = set(compile_tracker.shapes)
    dev = KeccakDevice(min_tier=8, max_batch_tier=16)
    assert dev.max_batch_tier == 16
    msgs = _msgs(50)
    assert dev.hash_batch(msgs) == keccak256_batch_np(msgs)
    minted = set(compile_tracker.shapes) - before
    assert all(shape[-1] <= 16 for shape in minted)  # no tier above ceiling


def test_max_batch_tier_normalized_onto_ladder():
    dev = KeccakDevice(min_tier=8, max_batch_tier=100)
    assert dev.max_batch_tier == 64  # largest pow2 ladder step <= 100


def test_block_ceiling_routes_to_cpu_twin_no_new_program():
    before = set(compile_tracker.shapes)
    dev = KeccakDevice(min_tier=8, block_tier=4, max_block_tier=8)
    big = bytes(range(256)) * 8  # 2048 B = 16 rate blocks > ceiling 8
    small = _msgs(3)
    msgs = [small[0], big, small[1], big + b"!", small[2]]
    assert dev._bucket_key(16) == _CPU_BUCKET
    assert dev.hash_batch(msgs) == keccak256_batch_np(msgs)
    assert dev.hash_batch([big])[0] == keccak256(big)
    minted = set(compile_tracker.shapes) - before
    assert all(shape[1] <= 8 for shape in minted)  # no over-ceiling program


def test_fused_block_tier_ceiling_raises():
    eng = FusedLevelEngine(min_tier=8)
    eng.begin(4)
    bucket = _Bucket()
    giant = bytes(70 * 136 - 10)  # 70 rate blocks > MAX_BLOCK_TIER=64
    bucket.add(giant, 70, 1, [])
    with pytest.raises(ValueError, match="block-tier ceiling"):
        eng.dispatch_level(bucket)
    with pytest.raises(ValueError, match="block-tier ceiling"):
        eng.dispatch_packed(np.zeros(16, np.uint8),
                            np.zeros(1, np.uint32), np.full(1, 8, np.uint32),
                            np.ones(1, np.int32), None, 128)


def test_fused_row_cap_splits_level_bit_identical():
    from reth_tpu.primitives.nibbles import unpack_nibbles
    from reth_tpu.primitives.rlp import rlp_encode

    leaves = [(unpack_nibbles(keccak256(b"k%d" % i)),
               rlp_encode(b"value-%d" % i)) for i in range(120)]
    expect = TrieCommitter(hasher=keccak256_batch_np).commit(leaves).root
    committer = TrieCommitter(fused=True, min_tier=16)
    committer._engine.MAX_BATCH_ROWS = 16  # force menu-cap splitting
    assert committer._engine._row_cap() == 16
    assert committer.commit(leaves).root == expect


# -- observability ------------------------------------------------------------


def test_metrics_and_snapshot_surface(tmp_path):
    reg = MetricsRegistry()
    cc = CompileCache(tmp_path)
    mgr = _mgr(registry=reg, cache=cc)
    snap = mgr.run()
    out = reg.render()
    assert "# TYPE warmup_state gauge" in out
    assert "warmup_shapes_total 2" in out
    assert "warmup_shapes_warm 2" in out
    assert "warmup_compiles_total 2.0" in out
    assert "warmup_compile_seconds_bucket" in out
    assert snap["cache"]["mode"] == "cold"  # attached, nothing on disk yet
    assert snap["compile_wall_s"] >= 0
    assert snap["shapes"] == {"keccak.masked:4x8": WARM,
                              "keccak.masked:8x8": WARM}
    assert snap["compiling"] is None


def test_supervisor_snapshot_carries_warmup_state():
    sup = _supervisor()
    assert sup.snapshot()["warmup"] is None
    mgr = _mgr(supervisor=sup)
    assert sup.snapshot()["warmup"] == "off"
    mgr.run()
    assert sup.snapshot()["warmup"] == "warm"


def test_events_line_has_warmup_fragment():
    from reth_tpu.node.events import CanonUpdate, NodeEventReporter

    class _Stub:
        pool = None
        network = None
        hasher_supervisor = None
        hash_service = None
        gateway = None
        warmup = None

    node = _Stub()
    node.warmup = _mgr()
    node.warmup._active = True
    rep = NodeEventReporter(node)
    rep._tip = CanonUpdate(1, b"\x11" * 32, 0, 0)
    rep._blocks = 1
    line = rep.report_once()
    assert "warmup[warming 0/2" in line
    node.warmup.run()
    rep._tip = CanonUpdate(2, b"\x22" * 32, 0, 0)
    rep._blocks = 1
    line = rep.report_once()
    assert "warmup[warm 2/2 cache=off" in line


def test_build_warmup_constructor(tmp_path):
    sup = _supervisor()
    from reth_tpu.ops.device import compile_cache_dir

    mgr = build_warmup(supervisor=sup, registry=MetricsRegistry(),
                       menu=[MenuShape("keccak.masked", 4, 8)],
                       builder=lambda s: None)
    assert mgr.sup is sup and sup.warmup is mgr
    # always the process's one cache directory, never a per-node one
    assert mgr.cache is not None and mgr.cache.dir == compile_cache_dir()


# -- kill-and-restart drill ---------------------------------------------------


def test_restart_with_populated_cache_reports_hits(tmp_path):
    """Second 'node start' against the same persistent cache dir: every
    shape compile finds its entry already on disk and the warmup line
    reports cache hits with a near-zero marginal entry count."""
    cc = CompileCache(tmp_path)
    entries = {"n": 0}

    def builder(shape):
        # first run writes one cache entry per shape; the restart writes
        # nothing (the loader served it) — modelled via the entry counter
        # the manager samples around each compile
        if entries["n"] < 2:
            (cc.dir / f"entry-{entries['n']}-cache").write_bytes(b"x" * 32)
            entries["n"] += 1

    menu = [MenuShape("keccak.masked", 4, 8), MenuShape("keccak.masked", 8, 8)]
    mgr1 = WarmupManager(menu=menu, cache=cc, builder=builder,
                         registry=MetricsRegistry(),
                         budget=1, attempts=1, backoff=0.01)
    snap1 = mgr1.run()
    assert snap1["state"] == "warm"
    assert snap1["cache_misses"] == 2 and snap1["cache_hits"] == 0
    assert snap1["cache"]["mode"] == "cold"

    cc2 = CompileCache(tmp_path)
    assert cc2.entry_count() == 2  # survived the "restart"
    mgr2 = WarmupManager(menu=menu, cache=cc2, builder=lambda s: None,
                         registry=MetricsRegistry(),
                         budget=1, attempts=1, backoff=0.01)
    snap2 = mgr2.run()
    assert snap2["state"] == "warm"
    assert snap2["cache_hits"] == 2 and snap2["cache_misses"] == 0
    assert snap2["cache"]["mode"] == "warm"


# -- bench integration --------------------------------------------------------


def test_bench_emits_warmup_state_and_cache_fields(tmp_path):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               RETH_TPU_BENCH_MODE="gateway",
               RETH_TPU_BENCH_GW_CLIENTS="2",
               RETH_TPU_BENCH_GW_REQS="4",
               RETH_TPU_BENCH_GW_KEYS="2",
               RETH_TPU_BENCH_GW_WORK="4",
               RETH_TPU_BENCH_TIMEOUT="300",
               # keep the repo's trailing perf-baseline store out of
               # test runs (tiny workloads would poison real vs_prev)
               RETH_TPU_BENCH_BASELINE_STORE=str(
                   tmp_path / "baselines.json"))
    env.pop("RETH_TPU_WARMUP", None)
    repo = Path(__file__).resolve().parent.parent
    r = subprocess.run([sys.executable, str(repo / "bench.py")],
                       capture_output=True, text=True, timeout=280,
                       cwd=str(repo), env=env)
    assert r.returncode == 0, r.stderr[-800:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert "warmup_state" in line and "compile_cache" in line
    assert "compile_wall_s" in line and "compiled_shapes" in line
    assert line["value"] > 0
