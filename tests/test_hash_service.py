"""Shared device hash service (reth_tpu/ops/hash_service.py).

The acceptance drill: N concurrent clients (live-tip + payload + rebuild
+ proof lanes) get digests bit-identical to direct backend calls, with a
measured coalesce factor > 1 reported through the ``hash_service_*``
metrics; a mid-dispatch device trip (supervisor wedge or injected
service fault) fails over to the numpy twin completing EVERY in-flight
future exactly once — no request lost, none double-completed. Everything
here runs CPU-only (JAX_PLATFORMS=cpu via conftest); injectors stand in
for a stuck device.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from reth_tpu.metrics import MetricsRegistry
from reth_tpu.ops.hash_service import (
    LANES,
    HashService,
    LaneOverloaded,
    ServiceFaultInjector,
)
from reth_tpu.primitives.keccak import keccak256, keccak256_batch_np
from reth_tpu.primitives.rlp import rlp_encode


def _svc(**kw):
    kw.setdefault("backend", keccak256_batch_np)
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("min_tier", 8)
    return HashService(**kw)


def _msgs(seed: int, n: int, lo: int = 1, hi: int = 300) -> list[bytes]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=int(rng.integers(lo, hi)),
                         dtype=np.uint8).tobytes() for _ in range(n)]


@pytest.fixture
def svc():
    s = _svc()
    yield s
    s.stop()


# -- core correctness --------------------------------------------------------


def test_single_request_roundtrip(svc):
    msgs = _msgs(1, 10)
    assert svc.client("live")(msgs) == [keccak256(m) for m in msgs]


def test_lone_request_skips_coalescing_window():
    """A single pending request dispatches immediately — the synchronous
    latency path must never pay the full coalescing window."""
    svc = _svc(window_s=0.25)  # pathological window: eager path must win
    t0 = time.monotonic()
    svc.client("live")([b"solo"])
    elapsed = time.monotonic() - t0
    svc.stop()
    assert elapsed < 0.2, f"lone request waited the window ({elapsed:.3f}s)"


def test_empty_request_fast_path(svc):
    assert svc.client("proof")([]) == []
    assert svc.dispatches == 0  # no backend call for an empty batch


def test_lane_names_validated(svc):
    with pytest.raises(ValueError):
        svc.client("turbo-boost")
    with pytest.raises(ValueError):
        svc.submit("nope", [b"x"])


def test_multithreaded_stress_bit_identical_and_coalesced():
    """THE acceptance drill: concurrent live-tip + payload + rebuild +
    proof clients, many small batches each, digests bit-identical to
    direct hashing, coalesce factor > 1 on the service metrics."""
    reg = MetricsRegistry()
    svc = _svc(registry=reg, window_s=0.004, fill_target=512)
    results: dict[int, tuple[list[bytes], list[bytes]]] = {}
    errors: list[BaseException] = []

    def client_thread(i: int):
        lane = LANES[i % len(LANES)]
        client = svc.client(lane)
        try:
            for j in range(6):
                msgs = _msgs(100 * i + j, 7)
                results[(i, j)] = (msgs, client(msgs))
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client_thread, args=(i,))
               for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    svc.stop()
    assert not errors
    assert len(results) == 16 * 6
    for msgs, digests in results.values():
        assert digests == [keccak256(m) for m in msgs]
    # 96 requests must have fused into far fewer dispatches
    assert svc.dispatches < 96
    assert svc.coalesce_factor() > 1.0
    rendered = reg.render()
    assert "hash_service_dispatches_total" in rendered

    def sample(name: str) -> float:
        line = next(l for l in rendered.splitlines()
                    if l.startswith(name + " "))
        return float(line.split()[1])

    assert sample("hash_service_coalesce_factor") > 1.0
    assert 0.0 < sample("hash_service_batch_occupancy") <= 1.0
    for lane in LANES:
        assert f"hash_service_queue_depth_{lane} 0" in rendered
    assert "hash_service_wait_seconds_live_count" in rendered
    assert "hash_service_service_seconds_count" in rendered


def test_mixed_lane_burst_single_dispatch():
    """Requests queued while the dispatcher is held by a lease drain as
    ONE coalesced dispatch on release, ordered live > payload > rebuild >
    proof (priority) within the fused batch."""
    seen: list[list[bytes]] = []

    def backend(msgs):
        seen.append(list(msgs))
        return keccak256_batch_np(msgs)

    svc = _svc(backend=backend, window_s=0.01, lease_bypass_s=10.0)
    futs = {}
    with svc.lease("hold"):
        for lane, payload in (("proof", b"p"), ("live", b"l"),
                              ("rebuild", b"r"), ("payload", b"b")):
            futs[lane] = svc.submit(lane, [payload])
            time.sleep(0.002)  # deterministic enqueue order
    out = {lane: f.result(5.0) for lane, f in futs.items()}
    svc.stop()
    assert out == {"proof": [keccak256(b"p")], "live": [keccak256(b"l")],
                   "rebuild": [keccak256(b"r")], "payload": [keccak256(b"b")]}
    assert len(seen) == 1  # everything fused into one dispatch
    # priority order inside the fused batch, not arrival order
    assert seen[0] == [b"l", b"b", b"r", b"p"]


def test_aging_promotes_starved_lane():
    """A proof request older than age_promote_s is drained FIRST even
    though live requests are queued ahead of it in priority."""
    seen: list[list[bytes]] = []

    def backend(msgs):
        seen.append(list(msgs))
        return keccak256_batch_np(msgs)

    svc = _svc(backend=backend, window_s=0.05, age_promote_s=0.01,
               lease_bypass_s=10.0)
    with svc.lease("hold"):
        f_proof = svc.submit("proof", [b"old"])
        time.sleep(0.03)  # let the proof request age past the threshold
        f_live = svc.submit("live", [b"new"])
    f_proof.result(5.0), f_live.result(5.0)
    svc.stop()
    assert seen[0][0] == b"old"  # aged request leads the fused batch


# -- backpressure ------------------------------------------------------------


def test_backpressure_rejects_when_asked_not_to_block():
    svc = _svc(lane_capacity=4, window_s=0.5, lease_bypass_s=10.0)
    with svc.lease("hold"):  # dispatcher paused: the queue can only grow
        svc.submit("proof", [b"a"] * 4)
        with pytest.raises(LaneOverloaded):
            svc.submit("proof", [b"b"], block=False)
        # other lanes are unaffected (per-lane bounds)
        f = svc.submit("live", [b"c"], block=False)
    assert f.result(5.0) == [keccak256(b"c")]
    svc.stop()
    assert svc.rejects == 1


def test_backpressure_blocks_then_completes():
    """A blocked submitter resumes as soon as the dispatcher drains the
    lane — bounded memory, zero lost requests."""
    svc = _svc(lane_capacity=8, window_s=0.001)
    done: list[list[bytes]] = []

    def submitter():
        for i in range(30):
            done.append(svc.client("rebuild")([b"%d" % i] * 4))

    t = threading.Thread(target=submitter)
    t.start()
    t.join(timeout=30)
    alive = t.is_alive()
    svc.stop()
    assert not alive
    assert done == [[keccak256(b"%d" % i)] * 4 for i in range(30)]


def test_backpressure_timeout():
    svc = _svc(lane_capacity=2, window_s=0.5, lease_bypass_s=10.0)
    with svc.lease("hold"):
        svc.submit("proof", [b"a", b"b"])
        with pytest.raises(LaneOverloaded):
            svc.submit("proof", [b"c"], timeout=0.05)
    svc.stop()


def test_oversized_request_admitted_alone():
    svc = _svc(lane_capacity=4, window_s=0.001)
    msgs = [b"%d" % i for i in range(64)]  # 16x the lane bound
    assert svc.client("rebuild")(msgs) == [keccak256(m) for m in msgs]
    svc.stop()


# -- exclusive lease ---------------------------------------------------------


def test_lease_pauses_device_dispatch_and_bypasses_aged():
    device_calls: list[int] = []

    def backend(msgs):
        device_calls.append(len(msgs))
        return keccak256_batch_np(msgs)

    svc = _svc(backend=backend, window_s=0.002, lease_bypass_s=0.01)
    with svc.lease("rebuild"):
        f = svc.submit("live", [b"tip"])
        out = f.result(5.0)  # completes WHILE leased, via the CPU twin
        assert out == [keccak256(b"tip")]
        assert device_calls == []  # the device was never touched
    svc.stop()
    assert svc.lease_bypasses == 1
    assert svc.leases == 1


def test_lease_backend_wraps_turbo_commit(rebuild_layout):
    """TurboCommitter(hash_service=...) holds the exclusive lease for each
    commit; roots stay bit-identical to the unleased committer, and an
    aborted commit releases the lease (no wedged service)."""
    from reth_tpu.ops.supervisor import FaultInjector, InjectedPipelineAbort
    from reth_tpu.trie.turbo import TurboCommitter

    rng = np.random.default_rng(7)
    keys = rng.integers(0, 256, size=(400, 32), dtype=np.uint8)
    keys = np.unique(keys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
    vals = [rlp_encode(bytes(rng.integers(0, 256, size=1 + i % 29,
                                          dtype=np.uint8)))
            for i in range(len(keys))]
    jobs = [(keys[: len(keys) // 2], vals[: len(keys) // 2]),
            (keys[len(keys) // 2:], vals[len(keys) // 2:])]

    base = TurboCommitter(backend="numpy")
    want = [r.root for r in base.commit_hashed_many(jobs)]

    svc = _svc(window_s=0.001)
    leased = TurboCommitter(backend="numpy", hash_service=svc)
    # numpy backend takes no lease (it never touches the device)
    assert [r.root for r in leased.commit_hashed_many(jobs)] == want
    assert svc.leases == 0

    # a device-kind committer DOES lease; fake the engine with the numpy
    # twin so the lease path runs hardware-free
    from reth_tpu.trie.turbo import _NumpyBackend

    dev = TurboCommitter(backend="device", hash_service=svc)
    dev._device_engine = lambda: _NumpyBackend(arena=dev.arena)
    assert [r.root for r in dev.commit_hashed_many(jobs)] == want
    assert svc.leases == 1
    with svc._cond:
        assert not svc._leased  # released at the terminal fetch

    # aborted pipelined commit: the finally-path must drop the lease
    dev.supervisor = type("S", (), {"injector": FaultInjector(pipeline_abort=1)})()
    with pytest.raises(InjectedPipelineAbort):
        rebuild_layout(PACK_WINDOW=1, SWEEP_THREADS=1, LEAVES_PER_SWEEP=64)
        dev.commit_hashed_pipelined(jobs)
    with svc._cond:
        assert not svc._leased
    # and the service still works afterwards
    assert svc.client("live")([b"post"]) == [keccak256(b"post")]
    svc.stop()


# -- failover / fault injection ----------------------------------------------


def test_injected_wedge_replays_on_twin_every_future_completes():
    """RETH_TPU_FAULT_SERVICE_WEDGE_EVERY=1: every coalesced dispatch
    wedges before touching the backend; the numpy-twin replay completes
    every in-flight future exactly once with correct digests."""
    device_calls: list[int] = []

    def backend(msgs):  # pragma: no cover - must never run
        device_calls.append(len(msgs))
        return keccak256_batch_np(msgs)

    inj = ServiceFaultInjector(wedge_every=1)
    svc = _svc(backend=backend, injector=inj, window_s=0.002)
    futs = [svc.submit(LANES[i % 4], [b"w%d" % i, b"v%d" % i])
            for i in range(12)]
    outs = [f.result(10.0) for f in futs]
    svc.stop()
    assert outs == [[keccak256(b"w%d" % i), keccak256(b"v%d" % i)]
                    for i in range(12)]
    assert [f.completions for f in futs] == [1] * 12  # no double-complete
    assert device_calls == []
    assert svc.replays >= 1
    assert inj.wedged >= 1


def test_supervised_backend_mid_dispatch_trip_fails_over():
    """The service composed with the SUPERVISOR: a wedge injected inside
    the supervised hasher trips the watchdog path; the breaker sees the
    failure and the batch still completes on the CPU (either via the
    supervisor's own fallback or the service replay) — the acceptance
    criterion's mid-dispatch device trip."""
    from reth_tpu.ops.supervisor import (
        DeviceSupervisor,
        FaultInjector,
        ProbeResult,
        SupervisedHasher,
    )

    sup = DeviceSupervisor(
        dispatch_budget=30.0,
        injector=FaultInjector(wedge_every=1),
        probe_fn=lambda budget, injector=None: ProbeResult(True, 0.001),
        registry=MetricsRegistry(),
    )
    hasher = SupervisedHasher(sup, device_hasher=keccak256_batch_np)
    svc = _svc(backend=hasher, supervisor=sup, window_s=0.002)
    msgs = _msgs(3, 40)
    futs = [svc.submit("live", msgs[i:i + 4]) for i in range(0, 40, 4)]
    outs = [f.result(15.0) for f in futs]
    svc.stop()
    flat = [d for out in outs for d in out]
    assert flat == [keccak256(m) for m in msgs]
    assert [f.completions for f in futs] == [1] * 10
    assert sup.dispatch_errors >= 1  # the trip really happened mid-dispatch


def test_fault_injector_from_env(monkeypatch):
    monkeypatch.setenv("RETH_TPU_FAULT_SERVICE_WEDGE_EVERY", "3")
    monkeypatch.setenv("RETH_TPU_FAULT_SERVICE_STALL", "0.001")
    monkeypatch.setenv("RETH_TPU_FAULT_SERVICE_QUEUE_CAP", "16")
    inj = ServiceFaultInjector.from_env()
    assert inj is not None and inj.active()
    assert (inj.wedge_every, inj.stall, inj.queue_cap) == (3, 0.001, 16)
    svc = _svc(injector=inj)
    assert svc.lane_capacity == 16  # overload drill shrinks the lanes
    out = svc.client("proof")([b"a", b"b", b"c"])
    assert out == [keccak256(b"a"), keccak256(b"b"), keccak256(b"c")]
    svc.stop()
    monkeypatch.delenv("RETH_TPU_FAULT_SERVICE_WEDGE_EVERY")
    monkeypatch.delenv("RETH_TPU_FAULT_SERVICE_STALL")
    monkeypatch.delenv("RETH_TPU_FAULT_SERVICE_QUEUE_CAP")
    assert ServiceFaultInjector.from_env() is None


def test_overload_stall_drill_backs_up_then_drains():
    """RETH_TPU_FAULT_SERVICE_STALL: slow dispatches back requests up
    into the bounded lanes; everything still completes, in order, and
    the queue-depth gauge returns to zero."""
    reg = MetricsRegistry()
    inj = ServiceFaultInjector(stall=0.01)
    svc = _svc(registry=reg, injector=inj, window_s=0.001, lane_capacity=64)
    futs = [svc.submit("payload", [b"s%d" % i]) for i in range(20)]
    outs = [f.result(30.0) for f in futs]
    svc.stop()
    assert outs == [[keccak256(b"s%d" % i)] for i in range(20)]
    assert "hash_service_queue_depth_payload 0" in reg.render()


# -- lifecycle ---------------------------------------------------------------


def test_stop_drains_pending_requests():
    svc = _svc(window_s=0.2, lease_bypass_s=10.0)
    with svc.lease("hold"):
        futs = [svc.submit("proof", [b"d%d" % i]) for i in range(5)]
    svc.stop(drain=True)
    assert [f.result(1.0) for f in futs] == [[keccak256(b"d%d" % i)]
                                             for i in range(5)]


def test_stop_without_drain_fails_pending():
    from reth_tpu.ops.hash_service import ServiceStopped

    svc = _svc(window_s=10.0, lease_bypass_s=30.0)
    with svc.lease("hold"):
        fut = svc.submit("proof", [b"x"])
        svc.stop(drain=False)
    with pytest.raises(ServiceStopped):
        fut.result(1.0)


def test_snapshot_shape(svc):
    svc.client("live")([b"x"])
    s = svc.snapshot()
    assert s["dispatches"] >= 1
    assert s["queued_total"] == 0
    assert set(s["queued"]) == set(LANES)
    assert s["fault_injection"] is False


# -- client integration ------------------------------------------------------


def test_for_lane_binds_committer_clients():
    from reth_tpu.trie.committer import TrieCommitter

    svc = _svc()
    committer = TrieCommitter(hasher=keccak256_batch_np)
    committer.hash_service = svc
    committer.hasher = svc.client("live")
    proof = committer.for_lane("proof")
    assert proof is not committer
    assert proof.hasher.lane == "proof"
    assert proof.hash_service is svc
    # no service -> identity
    plain = TrieCommitter(hasher=keccak256_batch_np)
    assert plain.for_lane("proof") is plain
    # lane-bound committers produce identical roots
    leaves = [(bytes([i]) * 64, rlp_encode(b"v%d" % i)) for i in range(16)]
    assert (committer.commit(leaves).root
            == proof.commit(leaves).root
            == plain.commit(leaves).root)
    svc.stop()


def test_proof_calculator_and_sparse_use_service_lanes():
    """End-to-end: a ChainBuilder-backed multiproof through a service-lane
    committer matches the direct committer bit-for-bit."""
    from reth_tpu.consensus import EthBeaconConsensus
    from reth_tpu.primitives import Account
    from reth_tpu.stages import Pipeline, default_stages
    from reth_tpu.storage import MemDb, ProviderFactory
    from reth_tpu.storage.genesis import import_chain, init_genesis
    from reth_tpu.testing import ChainBuilder, Wallet
    from reth_tpu.trie import TrieCommitter
    from reth_tpu.trie.proof import ProofCalculator, verify_account_proof

    direct = TrieCommitter(hasher=keccak256_batch_np)
    svc = _svc()
    via = TrieCommitter(hasher=keccak256_batch_np)
    via.hash_service = svc
    via.hasher = svc.client("live")

    a, b = Wallet(0xAA), Wallet(0xBB)
    builder = ChainBuilder({a.address: Account(balance=10**18),
                            b.address: Account(balance=10**18)})
    builder.build_block([a.transfer(b.address, 1000)])
    factory = ProviderFactory(MemDb())
    init_genesis(factory, builder.genesis, builder.accounts_at_genesis,
                 committer=direct)
    import_chain(factory, builder.blocks[1:], EthBeaconConsensus(direct))
    Pipeline(factory, default_stages(committer=direct)).run(1)

    with factory.provider() as provider:
        want = ProofCalculator(provider, direct).account_proof(a.address)
        got = ProofCalculator(provider, via).account_proof(a.address)
    assert got.proof == want.proof
    assert got.storage_root == want.storage_root
    root = builder.blocks[1].header.state_root
    assert verify_account_proof(root, a.address, got)
    assert svc.dispatches >= 1  # the proof work really rode the service
    svc.stop()
