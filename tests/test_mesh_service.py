"""Mesh-sharded hash service drills (parallel/mesh.py + ops/hash_service.py
mesh integration + ops/supervisor.py DeviceBreakerBoard).

The acceptance drills, all on the virtual 8-device CPU mesh (conftest):

- randomized differential sweep: the mesh-sharded committers
  (FusedMeshEngine under TurboCommitter/TrieCommitter) produce roots and
  branch nodes bit-identical to the single-device/numpy committers,
  including non-power-of-two meshes whose tier ladders leave the pow2
  grid (uneven tiers — the satellite clamp fix);
- sub-mesh rebuild lease: a pipelined rebuild claims k of n devices
  while live-lane dispatches KEEP COMPLETING on the remaining devices
  (no pause, no CPU bypass), roots bit-identical;
- per-device breaker drill: one injected device wedge
  (FaultInjector.device_wedge / RETH_TPU_FAULT_DEVICE_WEDGE) sheds that
  device, the in-flight batch REPLAYS on the shrunken mesh with
  bit-identical digests, and the numpy-twin replay only fires once
  every device has tripped (the final rung).
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from reth_tpu.metrics import MetricsRegistry
from reth_tpu.ops.fused_commit import FusedLevelEngine, FusedMeshEngine
from reth_tpu.ops.hash_service import HashService
from reth_tpu.ops.supervisor import (
    DeviceBreakerBoard,
    FaultInjector,
    InjectedDeviceWedge,
)
from reth_tpu.parallel.mesh import (
    DEFAULT_PARTITION_RULES,
    HashMesh,
    MeshKeccak,
    match_partition_rule,
    mesh_tier,
)
from reth_tpu.primitives.keccak import keccak256, keccak256_batch_np
from reth_tpu.primitives.rlp import rlp_encode


def _mesh(n: int = 8) -> HashMesh:
    import jax

    return HashMesh(jax.devices()[:n], registry=MetricsRegistry())


def _svc(hm: HashMesh, **kw) -> HashService:
    kw.setdefault("backend", keccak256_batch_np)
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("min_tier", 16)
    return HashService(mesh=hm, **kw)


def _msgs(seed: int, n: int, lo: int = 1, hi: int = 300) -> list[bytes]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=int(rng.integers(lo, hi)),
                         dtype=np.uint8).tobytes() for _ in range(n)]


def _job(n: int, seed: int):
    r = np.random.default_rng(seed)
    keys = r.integers(0, 256, (n, 32), dtype=np.uint8)
    vals = [rlp_encode(bytes(r.integers(0, 256, size=int(r.integers(1, 60)),
                                        dtype=np.uint8))) for _ in range(n)]
    return keys, vals


# -- partition-rule table ------------------------------------------------------


def test_partition_rule_table_decisions():
    # fused rebuild windows always shard; scalars never do; coalesced
    # keccak batches shard once every device gets a real shard
    assert match_partition_rule(DEFAULT_PARTITION_RULES,
                                "rebuild/fused.packed", 8, 8) == "batch"
    assert match_partition_rule(DEFAULT_PARTITION_RULES,
                                "live/keccak.scalar", 1, 8) == "single"
    assert match_partition_rule(DEFAULT_PARTITION_RULES,
                                "live/keccak.masked", 1024, 8) == "batch"
    assert match_partition_rule(DEFAULT_PARTITION_RULES,
                                "proof/keccak.masked", 8, 8) == "single"
    assert match_partition_rule(DEFAULT_PARTITION_RULES,
                                "live/keccak.masked", 1024, 1) == "single"


def test_spec_for_shards_large_keeps_scalar_single():
    hm = _mesh(8)
    spec, mesh = hm.spec_for("live", "keccak.masked", 2048)
    assert len(spec) == 1 and mesh.devices.size == 8
    spec, mesh = hm.spec_for("proof", "keccak.scalar", 1)
    assert len(spec) == 0 and mesh.devices.size == 1
    # every device dead -> (None, None): the caller takes the CPU rung
    for i in range(8):
        hm.mark_unhealthy(i)
    assert hm.spec_for("live", "keccak.masked", 2048) == (None, None)


# -- tier ladder / satellite clamp fix ----------------------------------------


def test_mesh_tier_divisible_and_clamped():
    # rounded floor, x2 growth, divisibility by the device count
    assert mesh_tier(100, 1024, 6) == 1026
    assert mesh_tier(2000, 1024, 6) == 2052
    assert mesh_tier(100, 1024, 8) == 1024
    # the clamp lands ON the ladder, never at the raw ceiling
    assert mesh_tier(70000, 1024, 6, 65536) == 32832
    assert mesh_tier(70000, 1024, 8, 65536) == 65536
    for mult in (2, 3, 5, 6, 7, 8):
        t = mesh_tier(12345, 1024, mult, 65536)
        assert t % mult == 0 and t <= 65536


def test_fused_mesh_row_cap_stays_on_ladder():
    """The satellite fix: the row-range split threshold is the largest
    LADDER tier under the ceilings, so a chunk split can never mint a
    tier above MAX_BATCH_ROWS or off the device-count-multiple grid
    (6 devices: 1026 -> 4104 -> 16416; the old raw-ceiling cap of 65536
    would have minted 65664 > MAX_BATCH_ROWS)."""
    import jax
    from jax.sharding import Mesh

    mesh6 = Mesh(np.array(jax.devices()[:6]), ("data",))
    eng = FusedMeshEngine(mesh6, min_tier=1024)
    assert eng.min_tier == 1026
    cap = eng._row_cap()
    assert cap == 16416  # 1026 * 4 * 4: the next rung (65664) > 65536
    assert cap % 6 == 0 and cap <= eng.MAX_BATCH_ROWS
    # the guard itself: an off-ladder tier is an assertion, not silence
    with pytest.raises(AssertionError):
        eng._check_batch_tier(1028)
    # single-device engines keep the old pow2 cap exactly
    assert FusedLevelEngine(min_tier=1024)._row_cap() == 65536


def test_row_range_split_parity_on_shrunk_ceiling():
    """dispatch_packed across a row-range split (rows > row cap) on a
    6-device mesh with a shrunken MAX_BATCH_ROWS: every minted tier obeys
    the clamp (asserted inside the engine) and digests stay bit-identical
    to the reference keccak."""
    import jax
    from jax.sharding import Mesh

    mesh6 = Mesh(np.array(jax.devices()[:6]), ("data",))
    eng = FusedMeshEngine(mesh6, min_tier=18)
    eng.MAX_BATCH_ROWS = 100  # ladder: 18 -> 72; cap 72 < 100
    assert eng._row_cap() == 72
    rng = np.random.default_rng(9)
    rows = [rng.integers(0, 256, size=int(rng.integers(1, 120)),
                         dtype=np.uint8).tobytes() for _ in range(150)]
    eng.begin(len(rows) + 1)
    slots = np.array([eng.alloc_slot() for _ in rows], dtype=np.int32)
    flat = np.frombuffer(b"".join(rows), dtype=np.uint8)
    row_len = np.array([len(r) for r in rows], dtype=np.uint32)
    row_off = (np.cumsum(row_len) - row_len).astype(np.uint32)
    eng.dispatch_packed(flat, row_off, row_len, slots, None, b_tier=1)
    digests = eng.finish()
    for s, r in zip(slots, rows):
        assert digests[s].tobytes() == keccak256(r)


# -- randomized differential sweep (mesh vs single-device) --------------------


def _differential(n_dev: int, min_tier: int, seeds) -> None:
    import jax
    from jax.sharding import Mesh

    from reth_tpu.trie.turbo import TurboCommitter

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    dev = TurboCommitter(backend="device", min_tier=min_tier, mesh=mesh)
    cpu = TurboCommitter(backend="numpy")
    for seed in seeds:
        jobs = [_job(int(n), seed * 10 + i)
                for i, n in enumerate((130, 50, 9, 1))]
        got = dev.commit_hashed_many(jobs, collect_branches=True)
        want = cpu.commit_hashed_many(jobs, collect_branches=True)
        assert [r.root for r in got] == [r.root for r in want]
        assert [r.branch_nodes for r in got] == [r.branch_nodes for r in want]
        # pipelined path (the rebuild's shape) too
        got_p = dev.commit_hashed_pipelined(jobs)
        assert [r.root for r in got_p] == [r.root for r in want]


@pytest.mark.slow
def test_turbo_mesh_randomized_differential():
    """The production level loop (packed + branch dispatches) sharded over
    the full 8-device mesh vs the numpy committer: roots and TrieUpdates
    branch nodes bit-identical across randomized job mixes. (Tier-1
    already pins single-shot mesh parity via test_fused_commit /
    test_turbo_commit; this randomized sweep rides make test-mesh.)"""
    _differential(8, 16, seeds=(1,))


@pytest.mark.slow
def test_turbo_mesh_differential_uneven_meshes():
    """Extended sweep (make test-mesh): non-power-of-two meshes whose tier
    ladders leave the pow2 grid, plus extra randomized seeds."""
    _differential(8, 16, seeds=(2,))
    _differential(6, 20, seeds=(1, 2))
    _differential(3, 8, seeds=(1, 2))


@pytest.mark.slow
def test_trie_committer_fused_mesh_accepts_hashmesh():
    """TrieCommitter's fused path (template/splice dispatches) over a
    HashMesh descriptor — FusedMeshEngine snapshots the live sub-mesh."""
    from reth_tpu.trie.committer import TrieCommitter

    hm = _mesh(8)
    hm.mark_unhealthy(7)  # engine must form over the 7 live devices
    sharded = TrieCommitter(fused=True, min_tier=14, mesh=hm)
    baseline = TrieCommitter(hasher=keccak256_batch_np)
    rng = np.random.default_rng(4)
    leaves = [(bytes(rng.integers(0, 16, 64, dtype=np.uint8)),
               rlp_encode(bytes(rng.integers(0, 256, 40, dtype=np.uint8))))
              for _ in range(120)]
    got = sharded.commit(leaves)
    want = baseline.commit(leaves)
    assert got.root == want.root
    assert got.branch_nodes == want.branch_nodes


# -- mesh-sharded service ------------------------------------------------------


def test_service_mesh_sharded_parity_and_routing():
    hm = _mesh(8)
    svc = _svc(hm)
    try:
        big = _msgs(1, 120)
        assert svc.client("live")(big) == [keccak256(m) for m in big]
        assert svc.client("proof")([b"k"]) == [keccak256(b"k")]
        assert svc.mesh_sharded >= 1 and svc.mesh_single >= 1
        snap = svc.snapshot()["mesh"]
        assert snap["total"] == 8 and snap["healthy"] == 8
    finally:
        svc.stop()


def test_service_mesh_streaming_chunks_fuse():
    """map_chunks streaming (the parallel sparse commit's encode-pool
    shape) over the meshed service: digests in order, bit-identical."""
    hm = _mesh(8)
    svc = _svc(hm, window_s=0.01)
    try:
        msgs = _msgs(2, 96)
        chunks = [msgs[i:i + 8] for i in range(0, len(msgs), 8)]
        out = svc.client("live").map_chunks(chunks)
        assert out == [keccak256(m) for m in msgs]
    finally:
        svc.stop()


def test_submesh_lease_live_lane_continues():
    """Acceptance drill: a rebuild holds k=4 of 8 devices; live-lane
    dispatches complete ON THE REMAINING DEVICES while the lease is held
    — verified by joining the live worker inside the lease — with zero
    CPU lease-bypasses and correct digests."""
    hm = _mesh(8)
    svc = _svc(hm)
    try:
        msgs = _msgs(3, 128)
        want = [keccak256(m) for m in msgs]
        results = []

        def live_worker():
            for _ in range(4):
                results.append(svc.client("live")(msgs) == want)

        with svc.lease(what="rebuild", devices=4):
            assert svc.rebuild_mesh().devices.size == 4
            assert svc.snapshot()["mesh"]["leased"] == 4
            t = threading.Thread(target=live_worker)
            t.start()
            t.join(60)
            assert not t.is_alive()
        assert results == [True] * 4
        assert svc.lease_bypasses == 0 and svc.submesh_leases == 1
        assert svc.snapshot()["mesh"]["leased"] == 0  # released
    finally:
        svc.stop()


def _turbo_lease_drill(commit) -> None:
    """Shared body: a turbo commit through a meshed hash service takes the
    sub-mesh lease (engine sharded over the leased k devices) while a
    live-lane client keeps hashing — roots bit-identical to numpy, no CPU
    bypasses."""
    from reth_tpu.trie.turbo import TurboCommitter

    hm = _mesh(8)
    svc = _svc(hm)
    try:
        jobs = [_job(120, 2), _job(60, 3)]
        # one batch tier for every level (min_tier pads them all to 256):
        # the drill is about the LEASE, not tier variety — tier sweeps
        # live in the differential tests, so keep the compile count here
        # at one program per (kind, topology)
        dev = TurboCommitter(backend="device", min_tier=256,
                             hash_service=svc)
        cpu = TurboCommitter(backend="numpy")
        stop = threading.Event()
        ok: list[bool] = []
        msgs = _msgs(5, 48)
        want = [keccak256(m) for m in msgs]

        def live():
            while not stop.is_set():
                ok.append(svc.client("live")(msgs) == want)

        t = threading.Thread(target=live)
        t.start()
        try:
            got = commit(dev, jobs)
        finally:
            stop.set()
            t.join(30)
        want_roots = [r.root for r in commit(cpu, jobs)]
        assert [r.root for r in got] == want_roots
        assert svc.submesh_leases == 1 and svc.lease_bypasses == 0
        assert ok and all(ok)
        assert svc.snapshot()["mesh"]["leased"] == 0
    finally:
        svc.stop()


@pytest.mark.slow
def test_turbo_commit_submesh_lease_roots_and_live_traffic():
    """(make test-mesh: mesh-program compile cost keeps this out of the
    tier-1 budget; the lease semantics themselves are pinned fast by
    test_submesh_lease_live_lane_continues above.)"""
    _turbo_lease_drill(lambda c, jobs: c.commit_hashed_many(jobs))


@pytest.mark.slow
def test_turbo_pipelined_rebuild_submesh_lease():
    """Extended (make test-mesh): the overlapped RebuildPipeline variant —
    many packed windows stream through the leased sub-mesh engine."""
    _turbo_lease_drill(lambda c, jobs: c.commit_hashed_pipelined(jobs))


# -- per-device breaker degradation -------------------------------------------


def test_device_wedge_shrinks_mesh_and_replays_batch():
    """Acceptance drill: one injected device wedge sheds that device and
    the in-flight batch replays on the 7 survivors — digests
    bit-identical, every future completes exactly once, and the CPU twin
    is NOT involved."""
    hm = _mesh(8)
    svc = _svc(hm,
               breaker_board=DeviceBreakerBoard(hm, failure_threshold=1),
               device_injector=FaultInjector(device_wedge=(3,)))
    try:
        msgs = _msgs(6, 100)
        fut = svc.submit("live", msgs)
        assert fut.result(60) == [keccak256(m) for m in msgs]
        assert fut.completions == 1
        snap = svc.snapshot()["mesh"]
        assert snap["healthy"] == 7 and snap["unhealthy"] == 1
        assert snap["mesh_replays"] == 1
        assert svc.replays == 0  # the final rung never fired
        # subsequent dispatches run on the shrunken mesh without replay
        assert svc.client("payload")(msgs) == [keccak256(m) for m in msgs]
        assert svc.mesh_replays == 1
    finally:
        svc.stop()


def test_all_devices_trip_then_cpu_final_rung():
    """Wedging every device walks the whole ladder: shrink, shrink, ...,
    exhausted -> the numpy-twin replay completes the batch (the FINAL
    rung, exactly once) with correct digests."""
    hm = _mesh(4)
    svc = _svc(hm,
               breaker_board=DeviceBreakerBoard(hm, failure_threshold=1),
               device_injector=FaultInjector(device_wedge=(0, 1, 2, 3)))
    try:
        msgs = _msgs(7, 60)
        assert svc.client("live")(msgs) == [keccak256(m) for m in msgs]
        snap = svc.snapshot()["mesh"]
        assert snap["healthy"] == 0 and snap["unhealthy"] == 4
        assert svc.replays == 1  # CPU twin, once
        assert svc.breaker_board.exhausted()
    finally:
        svc.stop()


def test_breaker_cooldown_readmits_device():
    """Trial-by-fire recovery: a shed device rejoins once its breaker
    cooldown elapses (poll -> HALF_OPEN), and a clean dispatch closes the
    breaker for good."""
    clock = [0.0]
    hm = _mesh(8)
    board = DeviceBreakerBoard(hm, failure_threshold=1, reset_timeout=10.0,
                               clock=lambda: clock[0])
    board.record_failure(2, attributed=True)
    assert not hm.is_healthy(2)
    assert board.poll() == 0  # cooldown not elapsed
    clock[0] = 11.0
    assert board.poll() == 1
    assert hm.is_healthy(2)
    board.record_success((2,))
    assert board.breakers[2].state == "closed"


def test_unattributed_failures_need_threshold():
    hm = _mesh(8)
    board = DeviceBreakerBoard(hm, failure_threshold=2)
    assert not board.record_failure(5)
    assert hm.is_healthy(5)
    assert board.record_failure(5)  # second strike sheds it
    assert not hm.is_healthy(5)


def test_device_wedge_injector_from_env(monkeypatch):
    monkeypatch.setenv("RETH_TPU_FAULT_DEVICE_WEDGE", "1,5")
    inj = FaultInjector.from_env()
    assert inj is not None and inj.device_wedge == frozenset((1, 5))
    with pytest.raises(InjectedDeviceWedge) as ei:
        inj.on_mesh_dispatch((0, 1, 2))
    assert ei.value.device_index == 1
    inj.on_mesh_dispatch((0, 2, 3))  # no wedged device participates


# -- warm-up integration -------------------------------------------------------


@pytest.mark.slow
def test_warmup_builds_mesh_shapes_and_routes():
    """Real sharded AOT builds over the 8-device mesh: the SPMD menu
    variants compile to WARM, route_bucket answers per mesh size, and the
    compile cache key carries the mesh size."""
    from reth_tpu.ops.warmup import MenuShape, WarmupManager

    menu = [MenuShape("keccak.masked", 4, 16, 8),
            MenuShape("fused.plain", 4, 16, 8),
            MenuShape("fused.splice", 4, 16, 8)]
    mgr = WarmupManager(menu=menu, registry=MetricsRegistry(), budget=120,
                        attempts=1)
    snap = mgr.run()
    assert snap["state"] == "warm" and snap["warm"] == 3
    assert mgr.route_bucket("keccak.masked", 4, 16, 8)
    assert "keccak.masked:4x16@m8" in snap["shapes"]


def test_compile_cache_dir_does_not_depend_on_mesh_size():
    """Was: the ``-m8`` directory suffix. JAX's own cache key already
    covers the topology, and a directory that moves never hits — the mesh
    size only adds the SPMD shapes to the menu."""
    from reth_tpu.ops.warmup import build_warmup

    single = build_warmup(registry=MetricsRegistry(), builder=lambda s: None)
    meshed = build_warmup(registry=MetricsRegistry(), mesh_size=8,
                          builder=lambda s: None)
    assert single.cache.dir == meshed.cache.dir
    assert any(sh.mesh_size == 8 for sh in meshed.menu)
    assert all(sh.mesh_size == 1 for sh in single.menu)


@pytest.mark.slow
def test_bench_mesh_mode_end_to_end(tmp_path):
    """RETH_TPU_BENCH_MODE=mesh at test size: one JSON line with
    per-mesh-size throughput + compile wall, roots verified identical,
    n_devices + mesh_degraded fields present,
    rc=0."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               RETH_TPU_BENCH_MODE="mesh",
               RETH_TPU_BENCH_MESH_DEVICES="1,2",
               RETH_TPU_BENCH_MESH_ACCOUNTS="800",
               RETH_TPU_BENCH_MESH_SLOTS="300",
               RETH_TPU_BENCH_MESH_TIER="256",
               RETH_TPU_BENCH_TIMEOUT="240",
               RETH_TPU_BENCH_BASELINE_STORE=str(tmp_path / "store.json"))
    r = subprocess.run([sys.executable, str(repo / "bench.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=repo)
    assert r.returncode == 0, r.stderr[-500:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["metric"] == "mesh_rebuild_hashes_per_sec"
    assert line["value"] > 0 and "error" not in line
    assert line["roots_identical"] is True
    assert line["n_devices"] == 2 and line["mesh_degraded"] == 0
    per = line["per_mesh"]
    assert set(per) == {"1", "2"}
    for stats in per.values():
        assert stats["hashes_per_sec"] > 0
        assert stats["compile_wall_s"] >= 0


def test_mesh_keccak_unwarm_shape_routes_to_cpu():
    """Degraded-mode serving holds on the mesh path too: an un-warm
    (program, block, batch, mesh) shape hashes on the CPU twin with
    bit-identical digests — never a fresh compile mid-commit."""
    from reth_tpu.ops.warmup import MenuShape, WarmupManager

    hm = _mesh(8)
    mgr = WarmupManager(menu=[MenuShape("keccak.masked", 4, 16, 8)],
                        registry=MetricsRegistry(), builder=lambda s: None)
    mgr._active = True  # mid-warm-up, nothing compiled
    mk = MeshKeccak(hm, min_tier=16, block_tier=4, warmup=mgr)
    msgs = _msgs(8, 40)
    mesh, _ = hm.live_snapshot()
    assert mk.hash_sharded(msgs, mesh) == [keccak256(m) for m in msgs]
    assert mgr.cpu_routed > 0
