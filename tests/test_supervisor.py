"""Device hasher supervisor: health probes, circuit breaker, watchdog-bounded
dispatch, and mid-commit CPU failover (reth_tpu/ops/supervisor.py).

The acceptance drill: with fault injection wedging EVERY device dispatch, a
multi-commit run still produces correct state roots — each commit completes
on the CPU twin via journal replay, the breaker opens, and a subsequent
healthy half-open probe restores the device route. Roots are pinned against
the numpy oracle throughout. Everything here runs CPU-only
(JAX_PLATFORMS=cpu via conftest) — the injector stands in for a stuck
device, which is the point: the failover machinery must be testable
without hardware.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from reth_tpu.metrics import MetricsRegistry
from reth_tpu.ops.supervisor import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    DeviceDispatchError,
    DeviceSupervisor,
    FaultInjector,
    InjectedWedge,
    ProbeResult,
    SupervisedHasher,
    probe_device,
)
from reth_tpu.primitives.keccak import keccak256_batch_np
from reth_tpu.primitives.rlp import rlp_encode
from reth_tpu.trie.committer import TrieCommitter
from reth_tpu.trie.turbo import TurboCommitter


def _fake_probe(outcomes=()):
    """Probe stub: pops from ``outcomes``, then always healthy. Still
    consults the injector so RETH_TPU_FAULT_PROBE_FAIL keeps working."""
    remaining = list(outcomes)

    def probe(budget, injector=None):
        ok = remaining.pop(0) if remaining else True
        if injector is not None and not injector.on_probe():
            ok = False
        return ProbeResult(ok, 0.001, None if ok else "fake probe failure")

    return probe


def _supervisor(**kw):
    kw.setdefault("dispatch_budget", 120.0)
    kw.setdefault("probe_fn", _fake_probe())
    kw.setdefault("registry", MetricsRegistry())
    return DeviceSupervisor(**kw)


def _jobs(seed: int, n: int = 150):
    """One commit's worth of turbo jobs: a storage trie + an account trie."""
    rng = np.random.default_rng(seed)
    jobs = []
    for j in range(2):
        keys = rng.integers(0, 256, size=(n // (j + 1), 32), dtype=np.uint8)
        keys = np.unique(keys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
        vals = [rlp_encode(bytes(rng.integers(0, 256, size=1 + i % 37,
                                              dtype=np.uint8)))
                for i in range(len(keys))]
        jobs.append((keys, vals))
    return jobs


# -- circuit breaker ---------------------------------------------------------


def test_breaker_transitions_and_backoff():
    now = [0.0]
    br = CircuitBreaker(failure_threshold=2, reset_timeout=10.0,
                        clock=lambda: now[0])
    assert br.state == CLOSED and br.allow()
    assert not br.record_failure()        # 1/2
    assert br.record_failure()            # 2/2 -> OPEN
    assert br.state == OPEN and br.trips == 1
    assert not br.allow()
    now[0] = 9.9
    assert not br.allow()
    now[0] = 10.0                         # cooldown elapsed -> HALF_OPEN
    assert br.allow() and br.state == HALF_OPEN
    assert br.record_failure()            # trial failed -> reopen, 2x backoff
    assert br.state == OPEN and br.trips == 2
    now[0] = 10.0 + 19.9
    assert not br.allow()                 # doubled cooldown still running
    now[0] = 10.0 + 20.0
    assert br.allow() and br.state == HALF_OPEN
    br.record_success()                   # trial succeeded -> CLOSED, reset
    assert br.state == CLOSED and br.failures == 0
    # backoff reset: next trip waits the base timeout again
    br.record_failure()
    br.record_failure()
    assert br.state == OPEN
    now[0] += 10.0
    assert br.allow() and br.state == HALF_OPEN
    assert br.transitions[0] == CLOSED and OPEN in br.transitions


def test_breaker_closed_success_resets_failure_count():
    br = CircuitBreaker(failure_threshold=3)
    br.record_failure()
    br.record_failure()
    br.record_success()
    assert br.failures == 0 and br.state == CLOSED


# -- fault injection ---------------------------------------------------------


def test_fault_injector_from_env():
    assert FaultInjector.from_env({}) is None
    inj = FaultInjector.from_env({"RETH_TPU_FAULT_WEDGE_EVERY": "2",
                                  "RETH_TPU_FAULT_DELAY": "0.5",
                                  "RETH_TPU_FAULT_PROBE_FAIL": "1"})
    assert inj is not None and inj.active()
    assert (inj.wedge_every, inj.delay, inj.probe_fail) == (2, 0.5, 1)


def test_fault_injector_wedges_every_nth():
    inj = FaultInjector(wedge_every=2)
    inj.on_dispatch()                      # 1: passes
    with pytest.raises(InjectedWedge):
        inj.on_dispatch()                  # 2: wedged
    inj.on_dispatch()                      # 3: passes
    assert inj.wedged == 1


def test_fault_injector_probe_failures():
    inj = FaultInjector(probe_fail=2)
    assert not inj.on_probe()
    assert not inj.on_probe()
    assert inj.on_probe()                  # budget spent
    forever = FaultInjector(probe_fail=-1)
    assert not forever.on_probe() and not forever.on_probe()


# -- health probe ------------------------------------------------------------


def test_probe_device_in_process_healthy():
    import subprocess

    spawned = []
    real = subprocess.Popen

    class _Spy(real):
        def __init__(self, *a, **kw):
            spawned.append(a)
            super().__init__(*a, **kw)

    subprocess.Popen = _Spy
    try:
        r = probe_device(budget=300)
    finally:
        subprocess.Popen = real
    assert r.ok, r.diag
    assert r.latency > 0
    assert spawned == []  # one process per chip: the probe starts no child


def test_probe_device_failure_modes():
    def boom():
        raise RuntimeError("device fell over")

    bad = probe_device(budget=60, program=boom)
    assert not bad.ok and "device fell over" in bad.diag
    stuck = probe_device(budget=0.5, program=lambda: time.sleep(30))
    assert not stuck.ok and "exceeded" in stuck.diag
    assert stuck.latency < 5  # the caller never blocks past the budget


def test_probe_on_unentitled_platform_is_a_failed_probe(monkeypatch):
    """PROBE_OK on the CPU backend is only OK when the environment names
    the CPU itself; otherwise the process is entitled to the TPU and a
    probe that landed anywhere else failed."""
    assert probe_device(budget=300).ok  # conftest: JAX_PLATFORMS=cpu
    monkeypatch.delenv("JAX_PLATFORMS")
    r = probe_device(budget=300)
    assert not r.ok
    assert "DeviceUnavailable" in r.diag and "'cpu'" in r.diag
    sup = _supervisor(probe_fn=probe_device)
    assert not sup.startup()  # --hasher auto boots on the CPU route
    assert sup.route() == "numpy"


def test_probe_injected_failure_skips_the_program():
    inj = FaultInjector(probe_fail=1)
    ran = []
    r = probe_device(budget=60, injector=inj, program=lambda: ran.append(1))
    assert not r.ok and "injected" in r.diag
    assert ran == []                       # the probe program never ran


# -- watchdog-bounded dispatch ----------------------------------------------


def test_watchdog_trips_on_real_timeout():
    sup = _supervisor()
    with pytest.raises(DeviceDispatchError, match="watchdog"):
        sup.run_guarded(time.sleep, 2.0, what="sleepy", budget=0.05)
    assert sup.dispatch_timeouts == 1
    assert sup.breaker.failures == 1


def test_watchdog_wraps_exceptions_and_feeds_breaker():
    sup = _supervisor(breaker=CircuitBreaker(failure_threshold=2))

    def boom():
        raise RuntimeError("device reset")

    with pytest.raises(DeviceDispatchError, match="device reset"):
        sup.run_guarded(boom)
    with pytest.raises(DeviceDispatchError):
        sup.run_guarded(boom)
    assert sup.breaker.state == OPEN
    assert sup.route() == "numpy"


def test_injected_delay_exercises_real_timeout_path():
    inj = FaultInjector(delay=0.3)
    sup = _supervisor(injector=inj, dispatch_budget=0.05)
    with pytest.raises(DeviceDispatchError, match="watchdog"):
        sup.run_guarded(lambda: "never", what="delayed")
    assert sup.dispatch_timeouts == 1


# -- supervised turbo commits: the acceptance drill --------------------------


def test_wedged_run_fails_over_then_recovers():
    """Wedge EVERY device dispatch across a multi-commit run: every commit
    still lands the oracle root on the CPU twin, the breaker opens, and a
    healthy half-open probe restores the device route."""
    all_jobs = [_jobs(seed) for seed in range(4)]
    oracle = TurboCommitter(backend="numpy")
    want = [[r.root for r in oracle.commit_hashed_many(jobs)]
            for jobs in all_jobs]

    now = [0.0]                            # breaker time under test control
    inj = FaultInjector(wedge_every=1)     # every dispatch wedges
    sup = _supervisor(
        injector=inj,
        breaker=CircuitBreaker(failure_threshold=2, reset_timeout=30.0,
                               clock=lambda: now[0]))
    auto = TurboCommitter(backend="auto", min_tier=64, supervisor=sup)

    for jobs, roots in zip(all_jobs, want):
        got = auto.commit_hashed_many(jobs)
        assert [r.root for r in got] == roots   # per-commit completion
    assert sup.breaker.state == OPEN
    assert sup.breaker.trips == 1
    assert sup.failovers >= 1                   # at least one mid-run failover
    assert CLOSED == sup.breaker.transitions[0]
    assert OPEN in sup.breaker.transitions

    # device heals; the open cooldown elapses; the half-open probe (healthy)
    # closes the breaker and the device route returns
    inj.wedge_every = 0
    now[0] = 30.0
    assert sup.route() == "device"
    assert sup.breaker.state == CLOSED
    assert sup.breaker.transitions[-3:] == [OPEN, HALF_OPEN, CLOSED]
    got = auto.commit_hashed_many(all_jobs[0])
    assert [r.root for r in got] == want[0]     # device commit post-recovery


def test_failed_half_open_probe_reopens_with_backoff():
    now = [0.0]
    inj = FaultInjector(wedge_every=1, probe_fail=1)
    sup = _supervisor(
        injector=inj,
        breaker=CircuitBreaker(failure_threshold=1, reset_timeout=30.0,
                               clock=lambda: now[0]))
    jobs = _jobs(7)
    want = [r.root for r in TurboCommitter(backend="numpy")
            .commit_hashed_many(jobs)]
    auto = TurboCommitter(backend="auto", min_tier=64, supervisor=sup)
    got = auto.commit_hashed_many(jobs)
    assert [r.root for r in got] == want
    assert sup.breaker.state == OPEN
    now[0] = 30.0
    assert sup.route() == "numpy"              # injected probe failure
    assert sup.breaker.state == OPEN and sup.breaker.trips == 2
    now[0] = 30.0 + 59.9
    assert sup.route() == "numpy"              # doubled cooldown not elapsed
    now[0] = 30.0 + 60.0
    assert sup.route() == "device"             # healthy probe closes it
    assert sup.breaker.state == CLOSED


def test_mid_commit_failover_at_the_sync_point():
    """Let every level dispatch 'succeed' and wedge only the terminal
    fetch — the async-dispatch reality, where a stuck device is first
    OBSERVED at the sync point. The journal must replay the whole commit
    on the CPU twin."""
    jobs = _jobs(11)
    want = [r.root for r in TurboCommitter(backend="numpy")
            .commit_hashed_many(jobs)]
    # count the guarded calls of a clean supervised device commit
    counter = _supervisor()
    auto = TurboCommitter(backend="auto", min_tier=64, supervisor=counter)
    counter.injector = FaultInjector()     # counting only
    got = auto.commit_hashed_many(jobs)
    assert [r.root for r in got] == want
    n_calls = counter.injector.dispatch_count
    assert n_calls >= 3                    # init + begin + dispatches + fetch

    inj = FaultInjector(wedge_every=n_calls)   # trips exactly at the fetch
    sup = _supervisor(injector=inj,
                      breaker=CircuitBreaker(failure_threshold=3))
    auto2 = TurboCommitter(backend="auto", min_tier=64, supervisor=sup)
    got2 = auto2.commit_hashed_many(jobs)
    assert [r.root for r in got2] == want
    assert sup.failovers == 1
    assert inj.wedged == 1
    assert sup.breaker.state == CLOSED     # one trip < threshold


def test_open_breaker_routes_commits_to_cpu_without_failover():
    sup = _supervisor(breaker=CircuitBreaker(failure_threshold=1,
                                             reset_timeout=300.0))
    sup.breaker.force_open()
    jobs = _jobs(13)
    want = [r.root for r in TurboCommitter(backend="numpy")
            .commit_hashed_many(jobs)]
    auto = TurboCommitter(backend="auto", min_tier=64, supervisor=sup)
    got = auto.commit_hashed_many(jobs)
    assert [r.root for r in got] == want
    assert sup.failovers == 0              # routed, not failed over


def test_supervised_fused_committer_bucket_protocol():
    """TrieCommitter(fused=True) through the supervisor: the CPU twin's
    alloc_slot/dispatch_level replay must land the oracle root."""
    from reth_tpu.primitives.nibbles import unpack_nibbles

    rng = np.random.default_rng(3)
    keys = rng.integers(0, 256, size=(120, 32), dtype=np.uint8)
    keys = np.unique(keys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
    leaves = [(unpack_nibbles(k.tobytes()),
               rlp_encode(bytes(rng.integers(0, 256, size=1 + i % 50,
                                             dtype=np.uint8))))
              for i, k in enumerate(keys)]
    want = TrieCommitter(hasher=keccak256_batch_np).commit(leaves)
    sup = _supervisor(injector=FaultInjector(wedge_every=1),
                      breaker=CircuitBreaker(failure_threshold=100))
    fused = TrieCommitter(fused=True, min_tier=8, supervisor=sup)
    got = fused.commit(leaves)
    assert got.root == want.root
    assert got.branch_nodes == want.branch_nodes
    assert sup.failovers >= 1


# -- supervised hasher + EngineTree multi-block run --------------------------


def test_engine_tree_follows_chain_with_wedged_hasher():
    """EngineTree harness: with every device hash batch wedged, the node
    still validates a multi-block chain — every block's state root lands
    via the CPU fallback and the breaker opens."""
    from reth_tpu.engine import EngineTree
    from reth_tpu.engine.tree import PayloadStatusKind
    from reth_tpu.primitives import Account
    from reth_tpu.storage import MemDb, ProviderFactory
    from reth_tpu.storage.genesis import init_genesis
    from reth_tpu.testing import ChainBuilder, Wallet

    cpu = TrieCommitter(hasher=keccak256_batch_np)
    alice, bob = Wallet(0xA11CE), Wallet(0xB0B)
    builder = ChainBuilder(
        {alice.address: Account(balance=10**21),
         bob.address: Account(balance=10**20)},
        committer=cpu,
    )
    for i in range(5):
        builder.build_block([alice.transfer(bob.address, 10**15 + i)])

    factory = ProviderFactory(MemDb())
    init_genesis(factory, builder.genesis, builder.accounts_at_genesis,
                 committer=cpu)
    sup = _supervisor(
        injector=FaultInjector(wedge_every=1),
        breaker=CircuitBreaker(failure_threshold=2, reset_timeout=300.0))
    supervised = TrieCommitter(supervisor=sup)
    supervised.turbo_backend = "auto"
    tree = EngineTree(factory, committer=supervised, persistence_threshold=2)

    for blk in builder.blocks[1:]:
        st = tree.on_new_payload(blk)
        assert st.status is PayloadStatusKind.VALID, st.validation_error
        assert tree.on_forkchoice_updated(blk.hash).status is \
            PayloadStatusKind.VALID
    assert tree.overlay_provider().last_block_number() == 5
    assert sup.breaker.state == OPEN           # the wedges tripped it
    assert sup.dispatch_errors >= 2
    # a healthy probe at the next half-open window restores the device
    sup.injector.wedge_every = 0
    sup.breaker._open_until = 0.0              # fast-forward the cooldown
    assert sup.route() == "device"
    assert sup.breaker.state == CLOSED


def test_supervised_hasher_matches_cpu_hasher():
    msgs = [bytes([i]) * (1 + i % 200) for i in range(64)]
    want = keccak256_batch_np(msgs)
    wedged = SupervisedHasher(
        _supervisor(injector=FaultInjector(wedge_every=1),
                    breaker=CircuitBreaker(failure_threshold=10)))
    assert list(wedged(msgs)) == list(want)
    healthy = SupervisedHasher(_supervisor())
    assert [bytes(d) for d in healthy(msgs)] == [bytes(d) for d in want]


# -- observability -----------------------------------------------------------


def test_supervisor_metrics_and_snapshot():
    reg = MetricsRegistry()
    sup = _supervisor(registry=reg,
                      injector=FaultInjector(wedge_every=1),
                      breaker=CircuitBreaker(failure_threshold=1))
    with pytest.raises(DeviceDispatchError):
        sup.run_guarded(lambda: None)
    snap = sup.snapshot()
    assert snap["breaker"] == OPEN
    assert snap["trips"] == 1
    assert snap["fault_injection"] is True
    text = reg.render()
    assert "hasher_supervisor_breaker_state 2.0" in text
    assert "hasher_supervisor_breaker_trips_total 1.0" in text
    # probes feed the histogram
    sup.startup()
    assert "hasher_supervisor_probe_duration_seconds_count 1" in reg.render()


def test_trie_metrics_attribute_failover_to_numpy():
    from reth_tpu.metrics import trie_metrics

    sup = _supervisor(injector=FaultInjector(wedge_every=1),
                      breaker=CircuitBreaker(failure_threshold=100))
    auto = TurboCommitter(backend="auto", min_tier=64, supervisor=sup)
    auto.commit_hashed_many(_jobs(17))
    assert trie_metrics.last["backend"] == "numpy"  # the twin did the work
