"""Device hasher supervisor: health probes, circuit breaker, watchdog-bounded
dispatch, and mid-commit CPU failover for the state-commitment path.

Device flakiness is a first-class failure mode here, the way production
accelerator stacks treat it (cf. the bounded-queue backend isolation of
arxiv 2503.04595):

- **Health probe** (:func:`probe_device`): device discovery plus one tiny
  jit round trip, run IN-PROCESS on a watchdog thread under a hard
  wall-clock budget. A chip belongs to one process: a child that probes it
  while the parent holds it fails or hangs, so no JAX child is ever
  started. A probe that lands on a platform the process is not entitled to
  (``ops/device.py``) is a failed probe.
- **Circuit breaker** (:class:`CircuitBreaker`): closed → open → half-open
  with exponential backoff. After ``failure_threshold`` watchdog trips all
  hashing routes to the numpy twin (``trie/turbo._NumpyBackend`` /
  ``keccak256_batch_np``) until a half-open probe succeeds.
- **Watchdog-bounded dispatch** (:meth:`DeviceSupervisor.run_guarded`):
  every device call gets a wall-clock budget in a worker thread; a trip
  abandons the stuck thread and fails over. Because the committer is
  level-batched and every dispatch's inputs are host numpy arrays, the
  :class:`SupervisedBackend` journals them and REPLAYS the same commit on
  the CPU twin from the current level boundary — no block is lost, the
  state root is still produced.
- **Fault injection** (:class:`FaultInjector`): env/CLI-configurable
  wedge-every-Nth-dispatch / fixed-delay / probe-failure policies in the
  style of ``engine/util.py``'s EngineSkip, so every failover path is
  testable without real hardware.
- **Observability**: breaker state, trips, failovers, and probe latency on
  ``/metrics`` (``metrics.SupervisorMetrics``) and the ``node/events.py``
  dashboard line.

Wiring: ``--hasher auto`` (cli.py) runs the startup probe and installs the
supervised committer; ``TurboCommitter(backend="auto")`` routes through
:class:`SupervisedBackend`.
"""

from __future__ import annotations

import os
import threading
import time

from .. import tracing

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"


class DeviceDispatchError(RuntimeError):
    """A supervised device call failed or exceeded its watchdog budget."""


class InjectedWedge(DeviceDispatchError):
    """Fault injection wedged this dispatch (RETH_TPU_FAULT_WEDGE_EVERY)."""


class InjectedDeviceWedge(DeviceDispatchError):
    """Fault injection wedged ONE SPECIFIC mesh device
    (RETH_TPU_FAULT_DEVICE_WEDGE) — carries the device index so the
    per-device breaker can attribute the failure and shrink the mesh
    around it instead of tripping the whole-device route."""

    def __init__(self, device_index: int, msg: str):
        super().__init__(msg)
        self.device_index = device_index


class InjectedPipelineAbort(RuntimeError):
    """Fault injection killed the rebuild pipeline at a window boundary
    (RETH_TPU_FAULT_PIPELINE_ABORT) — the in-process analogue of a crash
    mid-queue. Deliberately NOT a DeviceDispatchError: it must abort the
    whole chunk (so resume-from-progress is exercised), not fail over."""


class ProbeResult:
    __slots__ = ("ok", "latency", "diag")

    def __init__(self, ok: bool, latency: float, diag: str | None = None):
        self.ok = ok
        self.latency = latency
        self.diag = diag

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        state = "ok" if self.ok else f"FAIL ({self.diag})"
        return f"ProbeResult({state}, {self.latency:.3f}s)"


def _probe_program() -> str:
    """Device discovery plus one trivial jit round trip; returns the
    platform it ran on. Raises ``DeviceUnavailable`` on a platform the
    process is not entitled to."""
    import jax
    import jax.numpy as jnp

    from .device import require_device

    platform = require_device()[0]
    y = jax.jit(lambda a: a ^ (a << 1))(jnp.arange(256, dtype=jnp.uint32))
    y.block_until_ready()
    return platform


def probe_device(budget: float | None = None, *,
                 injector: "FaultInjector | None" = None,
                 program=_probe_program) -> ProbeResult:
    """One fail-fast health probe: run ``program`` on a watchdog thread in
    THIS process under a hard wall-clock ``budget``. Returns a
    :class:`ProbeResult`; never raises and never blocks past the budget —
    a stuck device call is abandoned with its (daemon) thread."""
    if budget is None:
        budget = float(os.environ.get("RETH_TPU_PROBE_TIMEOUT", "120"))
    t0 = time.monotonic()
    if injector is not None and not injector.on_probe():
        tracing.fault_event("RETH_TPU_FAULT_PROBE_FAIL",
                            target="ops::supervisor")
        return ProbeResult(False, time.monotonic() - t0,
                           "injected probe failure (RETH_TPU_FAULT_PROBE_FAIL)")
    box: list = [None, None]  # [platform, exception]

    def _call():
        try:
            box[0] = program()
        except BaseException as e:  # noqa: BLE001 — reported below
            box[1] = e

    t = threading.Thread(target=_call, daemon=True, name="device-probe")
    t.start()
    t.join(budget)
    latency = time.monotonic() - t0
    if t.is_alive():
        diag = f"device probe exceeded {budget}s"
    elif box[1] is not None:
        diag = (f"device probe failed: {type(box[1]).__name__}: "
                f"{str(box[1])[:300]}")
    else:
        tracing.event("ops::supervisor", "probe", ok=True,
                      latency_s=round(latency, 3), platform=box[0])
        return ProbeResult(True, latency)
    tracing.event("ops::supervisor", "probe", ok=False,
                  latency_s=round(latency, 3), diag=diag)
    return ProbeResult(False, latency, diag)


class FaultInjector:
    """Dispatch/probe fault policies (``engine/util.py`` EngineSkip style).

    ``wedge_every``: every Nth supervised device dispatch raises
    :class:`InjectedWedge` (counts as a watchdog trip). ``wedge_every=1``
    wedges EVERY dispatch — the full-failover drill.
    ``delay``: fixed seconds added to every dispatch — with a delay above
    the watchdog budget this exercises the REAL timeout path.
    ``probe_fail``: the first N health probes report failure (negative =
    all probes fail forever), so breaker recovery is testable.
    ``pipeline_abort``: the Nth rebuild-pipeline window raises
    :class:`InjectedPipelineAbort` — kills the chunk mid-queue so the
    chunked rebuild's resume-from-progress path is testable in-process.
    ``compile_wedge``: the first N warm-up shape compiles wedge past their
    watchdog budget (negative = every compile, until the field is cleared)
    — the ``ops/warmup.py`` degraded-serving / backoff-retry drill.
    ``device_wedge``: a set of MESH DEVICE indices — any sharded dispatch
    whose live mesh still contains one of them raises
    :class:`InjectedDeviceWedge` (attributed), so the per-device breaker
    + shrunken-mesh replay ladder is testable without hardware. Wedging
    every index drills the final CPU rung.

    Env form (read by :meth:`from_env`, also settable via CLI):
    ``RETH_TPU_FAULT_WEDGE_EVERY`` / ``RETH_TPU_FAULT_DELAY`` /
    ``RETH_TPU_FAULT_PROBE_FAIL`` / ``RETH_TPU_FAULT_PIPELINE_ABORT`` /
    ``RETH_TPU_FAULT_COMPILE_WEDGE`` / ``RETH_TPU_FAULT_DEVICE_WEDGE``
    (comma-separated device indices, e.g. ``"2"`` or ``"0,3,5"``).
    """

    def __init__(self, wedge_every: int = 0, delay: float = 0.0,
                 probe_fail: int = 0, pipeline_abort: int = 0,
                 compile_wedge: int = 0, device_wedge=()):
        self.wedge_every = wedge_every
        self.delay = delay
        self.probe_fail = probe_fail
        self.pipeline_abort = pipeline_abort
        self.compile_wedge = compile_wedge
        self.device_wedge = frozenset(int(i) for i in device_wedge)
        self.dispatch_count = 0
        self.wedged = 0
        self.probes_failed = 0
        self.windows = 0
        self.compiles_wedged = 0
        self.devices_wedged = 0
        self._lock = threading.Lock()

    @classmethod
    def from_env(cls, env=None) -> "FaultInjector | None":
        """Build from env knobs; None when no fault policy is set."""
        env = os.environ if env is None else env
        wedge = int(env.get("RETH_TPU_FAULT_WEDGE_EVERY", "0") or 0)
        delay = float(env.get("RETH_TPU_FAULT_DELAY", "0") or 0)
        probe = int(env.get("RETH_TPU_FAULT_PROBE_FAIL", "0") or 0)
        pabort = int(env.get("RETH_TPU_FAULT_PIPELINE_ABORT", "0") or 0)
        cwedge = int(env.get("RETH_TPU_FAULT_COMPILE_WEDGE", "0") or 0)
        raw = env.get("RETH_TPU_FAULT_DEVICE_WEDGE", "") or ""
        dwedge = tuple(int(x) for x in raw.split(",") if x.strip())
        if not (wedge or delay or probe or pabort or cwedge or dwedge):
            return None
        return cls(wedge_every=wedge, delay=delay, probe_fail=probe,
                   pipeline_abort=pabort, compile_wedge=cwedge,
                   device_wedge=dwedge)

    def active(self) -> bool:
        return bool(self.wedge_every or self.delay or self.probe_fail
                    or self.pipeline_abort or self.compile_wedge
                    or self.device_wedge)

    def on_mesh_dispatch(self, device_indices) -> None:
        """Called before every mesh-sharded dispatch with the live device
        indices. If a wedged device still participates, the dispatch
        fails ATTRIBUTED to that device — exactly the failure shape a
        per-device breaker needs to shrink the mesh around it."""
        if not self.device_wedge:
            return
        hit = sorted(self.device_wedge.intersection(device_indices))
        if not hit:
            return
        with self._lock:
            self.devices_wedged += 1
        tracing.fault_event("RETH_TPU_FAULT_DEVICE_WEDGE",
                            target="parallel::mesh", device=hit[0],
                            live=list(device_indices))
        raise InjectedDeviceWedge(
            hit[0], f"injected wedge on mesh device {hit[0]} "
                    f"(live mesh {list(device_indices)})")

    def on_compile(self, budget: float) -> None:
        """Called inside every warm-up compile worker. A wedged "compile"
        sleeps well past the caller's watchdog ``budget`` in the (abandoned)
        worker thread, so the REAL join-timeout path is exercised."""
        with self._lock:
            if self.compile_wedge == 0:
                return
            if self.compile_wedge > 0:
                self.compile_wedge -= 1
            self.compiles_wedged += 1
        tracing.fault_event("RETH_TPU_FAULT_COMPILE_WEDGE",
                            target="ops::warmup",
                            compile=self.compiles_wedged)
        time.sleep(min(budget * 3 + 1, budget + 60))

    def on_pipeline_window(self) -> None:
        """Called by the rebuild pipeline before dispatching each packed
        window; the Nth call aborts the commit."""
        if not self.pipeline_abort:
            return
        with self._lock:
            self.windows += 1
            n = self.windows
        if n == self.pipeline_abort:
            tracing.fault_event("RETH_TPU_FAULT_PIPELINE_ABORT",
                                target="trie::pipeline", window=n)
            raise InjectedPipelineAbort(
                f"injected pipeline abort at window #{n} "
                f"(RETH_TPU_FAULT_PIPELINE_ABORT={self.pipeline_abort})")

    def on_dispatch(self) -> None:
        """Called before every supervised device call."""
        with self._lock:
            self.dispatch_count += 1
            n = self.dispatch_count
        if self.delay:
            time.sleep(self.delay)
        if self.wedge_every and n % self.wedge_every == 0:
            with self._lock:
                self.wedged += 1
            tracing.fault_event("RETH_TPU_FAULT_WEDGE_EVERY",
                                target="ops::supervisor", dispatch=n)
            raise InjectedWedge(
                f"injected wedge on dispatch #{n} "
                f"(every {self.wedge_every})")

    def on_probe(self) -> bool:
        """True = let the probe run; False = injected probe failure."""
        with self._lock:
            if self.probe_fail < 0:
                self.probes_failed += 1
                return False
            if self.probes_failed < self.probe_fail:
                self.probes_failed += 1
                return False
        return True


class CircuitBreaker:
    """closed → open → half-open breaker with exponential backoff.

    While CLOSED, failures accumulate; at ``failure_threshold`` consecutive
    failures the breaker OPENS for ``reset_timeout`` seconds (doubling per
    re-trip up to ``max_reset_timeout``). Once the cooldown elapses the
    breaker is HALF_OPEN: one trial (a health probe) decides — success
    closes and resets the backoff, failure re-opens with doubled backoff.
    """

    def __init__(self, failure_threshold: int = 3, reset_timeout: float = 30.0,
                 max_reset_timeout: float = 600.0, clock=time.monotonic):
        self.failure_threshold = max(1, failure_threshold)
        self.base_reset_timeout = reset_timeout
        self.max_reset_timeout = max_reset_timeout
        self._clock = clock
        self._lock = threading.Lock()
        self.state = CLOSED
        self.failures = 0          # consecutive, while closed
        self.trips = 0             # times the breaker opened
        self._timeout = reset_timeout
        self._open_until = 0.0
        self.transitions: list[str] = [CLOSED]  # state history (tests/events)

    def _set_state(self, state: str) -> None:
        if state != self.state:
            prev, self.state = self.state, state
            self.transitions.append(state)
            if state == OPEN:
                # the device route just went dark: this is exactly the
                # moment a postmortem needs the recent span history
                # (fault_event = event + rate-limited JSONL snapshot)
                tracing.fault_event("breaker_open", target="ops::supervisor",
                                    state=state, previous=prev,
                                    trips=self.trips)
            else:
                tracing.event("ops::supervisor", "breaker",
                              state=state, previous=prev, trips=self.trips)

    def allow(self) -> bool:
        """May a device call proceed right now? OPEN past its cooldown
        moves to HALF_OPEN (the caller should then run a trial probe)."""
        with self._lock:
            if self.state == CLOSED:
                return True
            if self.state == OPEN and self._clock() >= self._open_until:
                self._set_state(HALF_OPEN)
            return self.state == HALF_OPEN

    def record_failure(self) -> bool:
        """Count one failure; returns True when this call opened the
        breaker (HALF_OPEN failure re-opens with doubled backoff)."""
        with self._lock:
            if self.state == HALF_OPEN:
                self.trips += 1
                self._timeout = min(self._timeout * 2, self.max_reset_timeout)
                self._open_until = self._clock() + self._timeout
                self._set_state(OPEN)
                return True
            self.failures += 1
            if self.state == CLOSED and self.failures >= self.failure_threshold:
                self.trips += 1
                self._open_until = self._clock() + self._timeout
                self._set_state(OPEN)
                return True
            return False

    def record_success(self) -> None:
        with self._lock:
            self.failures = 0
            if self.state != CLOSED:
                self._timeout = self.base_reset_timeout
                self._set_state(CLOSED)

    def force_open(self) -> None:
        """Open immediately (startup probe failed: no point counting)."""
        with self._lock:
            if self.state != OPEN:
                self.trips += 1
                self._open_until = self._clock() + self._timeout
                self._set_state(OPEN)


class DeviceBreakerBoard:
    """Per-device circuit breakers over a ``parallel/mesh.py`` HashMesh —
    the MIDDLE rung of the degradation ladder (device → sub-mesh → CPU
    twin). One :class:`CircuitBreaker` per mesh device; a trip sheds that
    device from the mesh's health mask (shardings re-form over the
    survivors, the in-flight batch replays there) instead of routing the
    whole node to the CPU twin. The full CPU failover — the supervisor's
    existing all-or-nothing breaker — only fires once EVERY device has
    tripped (:meth:`exhausted`).

    Recovery is trial-by-fire: :meth:`poll` re-admits a device whose open
    cooldown elapsed (the breaker's HALF_OPEN transition); the next
    successful dispatch that includes it closes the breaker, the next
    attributed failure re-opens it with doubled backoff. There is no
    per-virtual-device probe — a mesh device's only meaningful
    health signal is a dispatch that includes it.
    """

    def __init__(self, mesh, failure_threshold: int | None = None,
                 reset_timeout: float | None = None, clock=time.monotonic):
        if failure_threshold is None:
            failure_threshold = int(
                os.environ.get("RETH_TPU_DEVICE_BREAKER_TRIPS", "3"))
        if reset_timeout is None:
            reset_timeout = float(
                os.environ.get("RETH_TPU_DEVICE_BREAKER_RESET", "30"))
        self.mesh = mesh
        self.breakers = [
            CircuitBreaker(failure_threshold=failure_threshold,
                           reset_timeout=reset_timeout, clock=clock)
            for _ in range(mesh.n_devices)
        ]
        self.trips = 0

    def record_failure(self, idx: int, attributed: bool = False) -> bool:
        """Count one failure against device ``idx``; an ATTRIBUTED failure
        (the error names the device — injected wedge, per-device XLA
        diagnostic) opens immediately, an unattributed one counts toward
        the threshold like any collective-participant suspicion. Returns
        True when this call shed the device from the mesh."""
        b = self.breakers[idx]
        if attributed:
            b.force_open()
        else:
            b.record_failure()
        if b.state == OPEN and self.mesh.is_healthy(idx):
            self.trips += 1
            return self.mesh.mark_unhealthy(
                idx, reason="attributed wedge" if attributed
                else "unattributed dispatch failures")
        return False

    def record_success(self, indices) -> None:
        """A dispatch over ``indices`` completed: clear their failure
        counts (and close any HALF_OPEN breaker that just survived its
        trial dispatch)."""
        for i in indices:
            self.breakers[i].record_success()

    def poll(self) -> int:
        """Re-admit devices whose open cooldown elapsed (``allow()`` moves
        OPEN past its deadline to HALF_OPEN). Returns how many devices
        rejoined the mesh; call before each mesh dispatch so recovery
        needs no extra thread."""
        rejoined = 0
        for i, b in enumerate(self.breakers):
            if not self.mesh.is_healthy(i) and b.allow():
                if self.mesh.mark_healthy(i):
                    rejoined += 1
        return rejoined

    def exhausted(self) -> bool:
        """True when no device remains healthy — the caller must take the
        final rung (CPU twin)."""
        return self.mesh.healthy_count == 0

    def snapshot(self) -> dict:
        states = [b.state for b in self.breakers]
        return {
            "devices": len(states),
            "open": sum(1 for s in states if s == OPEN),
            "half_open": sum(1 for s in states if s == HALF_OPEN),
            "trips": self.trips,
            "states": states,
        }


class DeviceSupervisor:
    """Owns every device dispatch on the state-commitment path.

    ``route()`` answers "device or numpy, right now" — consulting the
    breaker and, when the open-state cooldown has elapsed, running ONE
    half-open health probe whose outcome closes or re-opens it.
    ``run_guarded(fn, *args)`` executes a device call in a worker thread
    under ``dispatch_budget`` seconds; a timeout abandons the (wedged)
    thread and raises :class:`DeviceDispatchError` after informing the
    breaker. The supervisor never raises out of ``route()``: a sick device
    degrades to the CPU route, it does not take the node down.
    """

    def __init__(self, dispatch_budget: float | None = None,
                 probe_budget: float | None = None,
                 breaker: CircuitBreaker | None = None,
                 injector: FaultInjector | None = None,
                 probe_fn=None, registry=None):
        if dispatch_budget is None:
            dispatch_budget = float(
                os.environ.get("RETH_TPU_DISPATCH_BUDGET", "120"))
        self.dispatch_budget = dispatch_budget
        self.probe_budget = probe_budget
        self.breaker = breaker or CircuitBreaker(
            failure_threshold=int(os.environ.get("RETH_TPU_BREAKER_TRIPS", "3")),
            reset_timeout=float(os.environ.get("RETH_TPU_BREAKER_RESET", "30")),
        )
        self.injector = injector if injector is not None else FaultInjector.from_env()
        self._probe_fn = probe_fn or probe_device
        from ..metrics import SupervisorMetrics

        self.metrics = SupervisorMetrics(registry)
        self.failovers = 0
        self.dispatch_timeouts = 0
        self.dispatch_errors = 0
        self.last_probe: ProbeResult | None = None
        self._probe_lock = threading.Lock()
        # warm-up manager attachment (ops/warmup.py): per-shape readiness
        # states ride here so committers/bench/events reach them through
        # the supervisor they already hold
        self.warmup = None
        self._publish()

    # -- shared instance (one supervisor per process, like REGISTRY) -------

    _shared: "DeviceSupervisor | None" = None
    _shared_lock = threading.Lock()

    @classmethod
    def shared(cls) -> "DeviceSupervisor":
        with cls._shared_lock:
            if cls._shared is None:
                cls._shared = cls()
            return cls._shared

    @classmethod
    def reset_shared(cls) -> None:
        with cls._shared_lock:
            cls._shared = None

    # -- probes ------------------------------------------------------------

    def _probe(self) -> ProbeResult:
        result = self._probe_fn(self.probe_budget, injector=self.injector)
        self.last_probe = result
        self.metrics.record_probe(result.ok, result.latency)
        return result

    def startup(self) -> bool:
        """Startup health probe (``--hasher auto``): an unhealthy device
        opens the breaker immediately, so the node boots on the CPU route
        instead of wedging on its first commit."""
        result = self._probe()
        if result.ok:
            self.breaker.record_success()
        else:
            self.breaker.force_open()
            self.metrics.record_trip()
        self._publish()
        return result.ok

    # -- routing -----------------------------------------------------------

    def route(self) -> str:
        """"device" | "numpy" — where hashing should run right now. A
        HALF_OPEN breaker runs one trial probe inline; its outcome decides
        the route AND the breaker's next state."""
        if not self.breaker.allow():
            self._publish()
            return "numpy"
        if self.breaker.state == HALF_OPEN:
            with self._probe_lock:
                # re-check under the lock: another thread's probe may have
                # already closed or re-opened the breaker
                if self.breaker.state == HALF_OPEN:
                    if self._probe().ok:
                        self.breaker.record_success()
                        if self.warmup is not None:
                            # the device just came back: promote any
                            # compile-FAILED shapes in the background
                            self.warmup.on_device_recovered()
                    else:
                        self.breaker.record_failure()
                        self.metrics.record_trip()
            self._publish()
            return "device" if self.breaker.state == CLOSED else "numpy"
        return "device"

    def allows_device(self) -> bool:
        return self.route() == "device"

    def warmup_allows_device(self) -> bool:
        """Commit-level warm-up gate (fused path): a fused commit's
        resident digest buffer can't hop backends at a shape boundary, so
        the whole commit stays on the CPU twin until every menu shape is
        warm. True when no warm-up manager is attached."""
        return self.warmup is None or self.warmup.device_ready()

    # -- watchdog-bounded dispatch ----------------------------------------

    def run_guarded(self, fn, *args, what: str = "dispatch",
                    budget: float | None = None):
        """Run ``fn(*args)`` under the wall-clock ``budget`` in a worker
        thread. On timeout the wedged thread is abandoned (a stuck device
        call cannot be cancelled — the breaker keeps further work away
        from it) and :class:`DeviceDispatchError` is raised; any exception
        from ``fn`` is re-raised wrapped. Both count as breaker failures."""
        if budget is None:
            budget = self.dispatch_budget
        try:
            box: list = [None, None]  # [result, exception]
            injector = self.injector

            def _call():
                try:
                    if injector is not None:
                        # inside the worker so an injected DELAY above the
                        # budget exercises the REAL join-timeout path
                        injector.on_dispatch()
                    box[0] = fn(*args)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    box[1] = e

            t = threading.Thread(target=_call, daemon=True,
                                 name=f"supervised-{what}")
            t.start()
            t.join(budget)
            if t.is_alive():
                self.dispatch_timeouts += 1
                self.metrics.record_timeout()
                tracing.fault_event("watchdog_timeout",
                                    target="ops::supervisor",
                                    what=what, budget_s=budget)
                raise DeviceDispatchError(
                    f"device {what} exceeded {budget}s watchdog budget")
            if box[1] is not None:
                raise DeviceDispatchError(
                    f"device {what} failed: {box[1]}") from box[1]
        except DeviceDispatchError:
            self.dispatch_errors += 1
            if self.breaker.record_failure():
                self.metrics.record_trip()
            self._publish()
            raise
        self.breaker.record_success()
        return box[0]

    def record_failover(self) -> None:
        self.failovers += 1
        self.metrics.record_failover()
        self._publish()

    # -- observability ------------------------------------------------------

    def snapshot(self) -> dict:
        """State for the events dashboard and bench triage."""
        lp = self.last_probe
        return {
            "breaker": self.breaker.state,
            "trips": self.breaker.trips,
            "failures": self.breaker.failures,
            "failovers": self.failovers,
            "dispatch_timeouts": self.dispatch_timeouts,
            "dispatch_errors": self.dispatch_errors,
            "probe_ok": None if lp is None else lp.ok,
            "probe_latency": None if lp is None else round(lp.latency, 3),
            "fault_injection": (self.injector.active()
                                if self.injector is not None else False),
            "warmup": (None if self.warmup is None
                       else self.warmup.overall_state()),
        }

    def _publish(self) -> None:
        self.metrics.set_state(self.breaker.state)


class SupervisedBackend:
    """Turbo array-protocol backend: device engine under the watchdog with
    journaled mid-commit CPU failover.

    Every dispatch's inputs are host numpy arrays (the committer is
    level-batched), so the backend journals ``(method, args)`` as it
    forwards them. When a device call trips the watchdog — or the device
    route is already broken — a fresh ``_NumpyBackend`` replays the journal
    and the commit RESUMES at the current level boundary on the CPU: the
    same commit, the same state root, no block lost. Terminal calls
    (``finish`` / ``fetch_slots``) are guarded too, since an async-dispatch
    engine often only blocks at its sync point.
    """

    def __init__(self, supervisor: DeviceSupervisor, device_factory,
                 arena=None):
        self.sup = supervisor
        self._factory = device_factory
        self._arena = arena  # resident DigestArena for the CPU twin
        self._journal: list[tuple[str, tuple]] = []
        self._device = None
        self._cpu = None
        self.failed_over = False

    @property
    def effective_kind(self) -> str:
        return "numpy" if self._cpu is not None else "device"

    def _failover(self, mid_commit: bool) -> None:
        from ..trie.turbo import _NumpyBackend

        self._device = None
        self._cpu = _NumpyBackend(arena=self._arena)
        if mid_commit and not self.failed_over:
            self.failed_over = True
            self.sup.record_failover()
        for name, args in self._journal:
            getattr(self._cpu, name)(*args)

    def _call(self, name: str, *args):
        if self._device is not None:
            try:
                out = self.sup.run_guarded(
                    getattr(self._device, name), *args, what=name)
                self._journal.append((name, args))
                return out
            except DeviceDispatchError:
                # replays the journal: the commit resumes HERE, at the
                # current level boundary, on the CPU twin
                self._failover(mid_commit=True)
        elif self._cpu is None:
            # breaker already open before the commit started: plain CPU
            # routing, not a mid-commit failover
            self._failover(mid_commit=False)
        self._journal.append((name, args))
        return getattr(self._cpu, name)(*args)

    # -- array protocol (turbo backends + FusedLevelEngine callers) --------

    def begin(self, max_slots: int) -> None:
        self._journal = []
        self._device, self._cpu = None, None
        self.failed_over = False
        # warm-up gate first (cheap, no probe): a commit started during
        # warm-up serves on the CPU twin — degraded mode, not a failover
        if self.sup.warmup_allows_device() and self.sup.route() == "device":
            try:
                self._device = self.sup.run_guarded(
                    self._factory, what="engine init")
            except DeviceDispatchError:
                # the commit was headed for the device and fell over —
                # counts as a failover even though no level ran yet
                self._failover(mid_commit=True)
        self._call("begin", max_slots)

    def alloc_slot(self) -> int:
        """Host-side counter on whichever twin is live; journaled so a
        replayed CPU twin's counter stays in sync (no watchdog — this
        never touches the device)."""
        self._journal.append(("alloc_slot", ()))
        live = self._device if self._device is not None else self._cpu
        return live.alloc_slot()

    def ensure(self, max_slots: int) -> None:
        """Arena-growth protocol (pipelined rebuild): guarded on the device
        and journaled, so a replayed CPU twin re-grows to the same capacity
        before the journal's later dispatches land."""
        self._call("ensure", max_slots)

    def dispatch_level(self, bucket):
        """Committer bucket protocol (TrieCommitter fused hash phase)."""
        self._call("dispatch_level", bucket)

    def dispatch_packed(self, flat, row_off, row_len, slots, holes, b_tier):
        self._call("dispatch_packed", flat, row_off, row_len, slots, holes,
                   b_tier)

    def dispatch_branch(self, masks, slots, children):
        self._call("dispatch_branch", masks, slots, children)

    def flush_window(self):
        """Window-boundary hook: a whole-subtrie engine executes its
        staged k-level chunks here (guarded + journaled like any device
        call — a wedge mid-window replays the journal on the CPU twin);
        per-level engines don't expose it and defer to finish."""
        if self._device is not None and not hasattr(self._device,
                                                    "flush_window"):
            return
        self._call("flush_window")

    def fetch_slots(self, slots):
        return self._call("fetch_slots", slots)

    def finish(self):
        return self._call("finish")


class SupervisedHasher:
    """``hash_batch``-protocol wrapper: device keccak under the watchdog,
    numpy fallback. Hashing is stateless, so failover is simply re-running
    the batch on the CPU — no journal needed. This is what the live-tip
    paths (``TrieCommitter``, ``engine/sparse_root.py``,
    ``engine/pipelined_root.py``) call, so a stuck device mid-block
    degrades the block's root job to the CPU instead of hanging the node.
    """

    def __init__(self, supervisor: DeviceSupervisor, device_hasher=None,
                 cpu_hasher=None, min_tier: int = 1024, warmup=None):
        self.sup = supervisor
        self._device = device_hasher
        self._min_tier = min_tier
        self._warmup = warmup
        if cpu_hasher is None:
            from ..primitives.keccak import keccak256_batch_np

            cpu_hasher = keccak256_batch_np
        self._cpu = cpu_hasher

    def _device_hasher(self):
        if self._device is None:
            from .keccak_jax import KeccakDevice

            # the warm-up manager (explicit, or attached to the supervisor
            # after construction) gates each bucket: un-warm shapes hash on
            # the CPU twin instead of compiling mid-commit
            warmup = self._warmup if self._warmup is not None else self.sup.warmup
            self._device = KeccakDevice(
                min_tier=self._min_tier, block_tier=4,
                warmup=warmup).hash_batch
        return self._device

    def __call__(self, msgs):
        if self.sup.route() == "device":
            try:
                return self.sup.run_guarded(
                    self._device_hasher(), msgs, what="hash_batch")
            except DeviceDispatchError:
                pass
        return self._cpu(msgs)
