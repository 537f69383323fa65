"""Shared device hash service: continuous batching, priority lanes, and
backpressure for every keccak client.

Until now every hashing client owned the device alone: ``RebuildPipeline``
monopolized the backend during a rebuild, ``SparseRootTask`` dispatched
tiny synchronous batches (single keys, even), and ``ProofCalculator`` /
witness hashing never touched the device at all. This module is the
missing scheduling layer between them — one background service owns one
(supervised) backend and multiplexes every client over it, the way the
parallel-hashing literature (Sakura tree hashing, arxiv 1608.00492) and
the async-storage parallel-EVM work (Reddio, arxiv 2503.04595) keep an
accelerator saturated: decouple request arrival from dispatch.

Shape:

- **Priority lanes** (:data:`LANES`): ``live`` (live-tip state root) >
  ``payload`` (payload build) > ``rebuild`` (Merkle rebuild) > ``proof``
  (proof/RPC). Clients submit async requests (:meth:`HashService.submit`
  → :class:`HashFuture`) or call synchronously through a lane-bound
  :class:`HashClient` that satisfies the repo-wide ``hasher`` protocol
  (``list[bytes] -> list[bytes]``).
- **Continuous batching**: a dispatcher thread gathers requests until a
  fused tier fills (``fill_target`` messages) or a coalescing deadline
  (``window_s``) expires, concatenates them into ONE backend dispatch,
  and scatters the digests back through the futures. Many tiny client
  batches become one full-rate device batch. A LONE request dispatches
  immediately — the synchronous latency path never pays the window; the
  window only gathers once a second request is pending, so under load
  the previous dispatch's wall time is the natural gather period.
- **Backpressure**: per-lane queues are bounded in *messages*; a full
  lane blocks the submitter (or raises :class:`LaneOverloaded` with
  ``block=False``) instead of growing without bound.
- **Anti-starvation aging**: drain order is priority lanes first, but any
  request older than ``age_promote_s`` is taken FIRST (FIFO), so a
  saturating live-tip stream cannot starve proof/RPC traffic forever.
- **Exclusive lease** (:meth:`HashService.lease`): ``RebuildPipeline``
  streams pre-packed windows through the array-protocol engine without
  per-call service overhead; the lease pauses coalesced dispatching.
  Requests that age past ``lease_bypass_s`` while a (long) lease is held
  are dispatched on the CPU twin, so a multi-second rebuild window never
  blocks the live tip.
- **Device mesh** (``mesh=``, a ``parallel/mesh.py`` :class:`HashMesh`):
  the service owns a device MESH instead of one backend. A
  partition-rule table (``HashMesh.spec_for``) decides how each
  coalesced dispatch shards: large batches scatter over the live mesh
  (``P(axis)``, one keccak shard per device), scalar and sub-threshold
  requests stay unpartitioned on one device (``P()``) — hash throughput
  only scales with lanes when batching is explicit (arxiv 1608.00492,
  2501.18780). The exclusive lease generalizes to a **sub-mesh lease**:
  a rebuild claims k of n devices (``lease(devices=k)``) while the
  live/payload/proof lanes keep dispatching on the rest — no pause, no
  CPU bypass. Per-device circuit breakers
  (``ops/supervisor.py DeviceBreakerBoard``) give partial-mesh
  degradation: a wedged device SHRINKS the mesh (shardings re-form on
  the survivors and the in-flight batch replays there, bit-identical —
  hashing is stateless); the numpy-twin replay below remains the FINAL
  rung, taken only once every device has tripped.
- **Failover**: the backend is typically an ``ops/supervisor.py``
  :class:`~reth_tpu.ops.supervisor.SupervisedHasher` — circuit-breaker
  trips and watchdog timeouts apply to the shared service. Hashing is
  stateless, so if a dispatch still raises (or service fault injection
  wedges it), the WHOLE in-flight batch is replayed on the numpy twin:
  every future completes exactly once, no request is lost.
- **Fault injection** (:class:`ServiceFaultInjector`):
  ``RETH_TPU_FAULT_SERVICE_WEDGE_EVERY`` / ``RETH_TPU_FAULT_SERVICE_STALL``
  / ``RETH_TPU_FAULT_SERVICE_QUEUE_CAP`` drill the replay, overload, and
  backpressure paths without hardware.
- **Observability**: ``hash_service_*`` metrics (per-lane queue depth,
  coalesce factor, batch occupancy, wait/service-time histograms) plus a
  ``node/events.py`` dashboard fragment via :meth:`snapshot`.

Wiring: ``--hash-service`` (cli.py) hangs a service off the committer;
``TrieCommitter.for_lane`` hands lane-bound clients to ``SparseRootTask``
("live"), the payload builder ("payload"), the hashing/Merkle stages
("rebuild"), and ``ProofCalculator`` ("proof"); ``TurboCommitter``
("auto"/"device") takes the exclusive lease around each rebuild commit.
The parallel sparse commit (``trie/sparse.py``) STREAMS its encode-pool
chunks onto the live lane (``HashClient.submit`` / ``map_chunks``): each
per-depth level arrives as many small requests that the coalescing
window fuses back into one device dispatch while the host keeps
encoding the rest of the level.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager

from .. import tracing

# priority order, highest first — index IS the priority
LANES = ("live", "payload", "rebuild", "proof")
_LANE_INDEX = {name: i for i, name in enumerate(LANES)}

# per-lane p99 queue-wait SLO budgets (seconds) — the live lane sits on
# the block-import critical path, the background lanes tolerate queueing
# by design. Kept here, next to the lane definitions, so a new lane must
# declare its budget; consumed by health.py's default SLO rule table.
DEFAULT_WAIT_BUDGETS = {"live": 0.25, "payload": 0.5,
                        "rebuild": 2.0, "proof": 1.0}
# p99 budget for one coalesced dispatch's wall (service time): a healthy
# dispatch is sub-ms..tens of ms; sustained 150ms+ means a stalling
# backend (wedge drill, compile storm, saturated device)
DEFAULT_DISPATCH_BUDGET_S = 0.15


class HashServiceError(RuntimeError):
    """Base class for service-level failures."""


class LaneOverloaded(HashServiceError):
    """Bounded lane queue is full and the submitter asked not to block."""


class ServiceStopped(HashServiceError):
    """The service was stopped while this request was queued."""


class InjectedServiceWedge(HashServiceError):
    """Service fault injection wedged this coalesced dispatch
    (RETH_TPU_FAULT_SERVICE_WEDGE_EVERY) — exercises the replay path."""


class ServiceFaultInjector:
    """Overload/stall fault policies for the shared service, in the style
    of ``ops/supervisor.py``'s FaultInjector.

    ``wedge_every``: every Nth coalesced dispatch raises
    :class:`InjectedServiceWedge` BEFORE touching the backend; the batch
    must complete via the numpy-twin replay (``wedge_every=1`` = every
    dispatch, the full-failover drill).
    ``stall``: fixed seconds added to every coalesced dispatch — an
    overload drill that backs requests up into the bounded lanes.
    ``queue_cap``: overrides every lane's message capacity (small values
    drill backpressure blocking/rejection).

    Env form (:meth:`from_env`): ``RETH_TPU_FAULT_SERVICE_WEDGE_EVERY`` /
    ``RETH_TPU_FAULT_SERVICE_STALL`` / ``RETH_TPU_FAULT_SERVICE_QUEUE_CAP``.
    """

    def __init__(self, wedge_every: int = 0, stall: float = 0.0,
                 queue_cap: int = 0):
        self.wedge_every = wedge_every
        self.stall = stall
        self.queue_cap = queue_cap
        self.dispatches = 0
        self.wedged = 0
        self._lock = threading.Lock()

    @classmethod
    def from_env(cls, env=None) -> "ServiceFaultInjector | None":
        env = os.environ if env is None else env
        wedge = int(env.get("RETH_TPU_FAULT_SERVICE_WEDGE_EVERY", "0") or 0)
        stall = float(env.get("RETH_TPU_FAULT_SERVICE_STALL", "0") or 0)
        cap = int(env.get("RETH_TPU_FAULT_SERVICE_QUEUE_CAP", "0") or 0)
        if not (wedge or stall or cap):
            return None
        return cls(wedge_every=wedge, stall=stall, queue_cap=cap)

    def active(self) -> bool:
        return bool(self.wedge_every or self.stall or self.queue_cap)

    def on_dispatch(self) -> None:
        """Called before every coalesced dispatch touches the backend."""
        with self._lock:
            self.dispatches += 1
            n = self.dispatches
        if self.stall:
            tracing.fault_event("RETH_TPU_FAULT_SERVICE_STALL",
                                target="ops::hash_service",
                                dispatch=n, stall_s=self.stall)
            time.sleep(self.stall)
        if self.wedge_every and n % self.wedge_every == 0:
            with self._lock:
                self.wedged += 1
            tracing.fault_event("RETH_TPU_FAULT_SERVICE_WEDGE_EVERY",
                                target="ops::hash_service", dispatch=n)
            raise InjectedServiceWedge(
                f"injected service wedge on dispatch #{n} "
                f"(every {self.wedge_every})")


class HashFuture:
    """Completion handle for one submitted request. Completes exactly once
    — either with the digest list or with an exception."""

    __slots__ = ("_event", "_result", "_error", "completions")

    def __init__(self):
        self._event = threading.Event()
        self._result: list[bytes] | None = None
        self._error: BaseException | None = None
        self.completions = 0  # must end at exactly 1 (drill assertion)

    def _complete(self, result=None, error=None) -> None:
        self.completions += 1
        if self.completions > 1:  # pragma: no cover - invariant guard
            raise AssertionError("HashFuture completed twice")
        self._result, self._error = result, error
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> list[bytes]:
        if not self._event.wait(timeout):
            raise TimeoutError("hash service request timed out")
        if self._error is not None:
            raise self._error
        return self._result


class _Request:
    __slots__ = ("lane", "msgs", "future", "enqueued_at", "ctx", "wall_at")

    window = None  # plain hash request (multi-level requests override)

    def __init__(self, lane: str, msgs: list[bytes]):
        self.lane = lane
        self.msgs = msgs
        self.future = HashFuture()
        self.enqueued_at = time.monotonic()
        # explicit trace handoff across the queue: the dispatcher thread
        # serves many traces per coalesced batch, so each request carries
        # its submitter's context and gets a per-request span on completion
        self.ctx = tracing.current_context()
        self.wall_at = time.time()


class _WindowRequest:
    """One multi-level request: a pre-packed k-level window (per-depth
    packed/branch level arrays, the ``dispatch_packed``/``dispatch_branch``
    wire shape) that the dispatcher runs as ONE whole-subtrie fused
    dispatch instead of one hash call per depth. Completes with the
    fetched digest rows (``fetch`` slots, or the whole buffer)."""

    __slots__ = ("lane", "window", "max_slots", "fetch", "rows", "future",
                 "enqueued_at", "ctx", "wall_at")

    def __init__(self, lane: str, window: list[dict], max_slots: int,
                 fetch=None):
        self.lane = lane
        self.window = window
        self.max_slots = max_slots
        self.fetch = fetch
        self.rows = sum(len(lv["slots"]) for lv in window)
        self.future = HashFuture()
        self.enqueued_at = time.monotonic()
        self.ctx = tracing.current_context()
        self.wall_at = time.time()


def _req_msgs(r) -> int:
    """Queue-accounting size of one request (messages, or window rows)."""
    return r.rows if r.window is not None else len(r.msgs)


class HashClient:
    """Lane-bound callable satisfying the repo-wide ``hasher`` protocol
    (``list[bytes] -> list[bytes]``) — drop-in for ``KeccakDevice
    .hash_batch`` / ``keccak256_batch_np`` / ``SupervisedHasher``."""

    __slots__ = ("service", "lane")

    def __init__(self, service: "HashService", lane: str):
        if lane not in _LANE_INDEX:
            raise ValueError(f"unknown lane {lane!r} (have {LANES})")
        self.service = service
        self.lane = lane

    def __call__(self, msgs: list[bytes]) -> list[bytes]:
        return self.service.hash(self.lane, list(msgs))

    def submit(self, msgs: list[bytes]) -> HashFuture:
        return self.service.submit(self.lane, list(msgs))

    def commit_window(self, window: list[dict], max_slots: int,
                      fetch=None):
        """Multi-level request: hand the service a pre-packed k-level
        window (one dict per level in deepest-first order — the
        ``dispatch_packed``/``dispatch_branch`` array shape) and get the
        digest buffer (or the ``fetch`` slots) back from ONE fused
        dispatch. This is how the live sparse finish and the rebuild
        lanes collapse their per-depth hash calls."""
        return self.service.submit_window(self.lane, window,
                                          max_slots, fetch=fetch).result()

    def map_chunks(self, chunks) -> list[bytes]:
        """Live-lane streaming: submit every chunk as its own request —
        a producer (e.g. the parallel sparse commit's encode pool) keeps
        encoding while earlier chunks already sit in the dispatcher,
        whose continuous batching fuses them back into ONE full-rate
        dispatch — then gather digests in submission order."""
        futs = [self.submit(list(c)) for c in chunks]
        out: list[bytes] = []
        for f in futs:
            out.extend(f.result())
        return out

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"HashClient(lane={self.lane!r})"


class PipelineLease:
    """A double-buffered sub-mesh held by the cross-block import
    pipeline: the speculative block's key-prehash batches dispatch on
    the leased devices (via the service's sharded hasher) while the
    committing block's lanes re-form over the rest. Release is
    idempotent — the pipeline's abort ladder releases on every exit
    path, and the chaos drills assert zero leaked leases."""

    def __init__(self, service: "HashService", sub):
        self._service = service
        self._sub = sub
        self.devices = len(sub.indices)
        self.released = False

    def hash(self, msgs: list[bytes]) -> list[bytes]:
        if self.released:  # late straggler batch: CPU twin, never racy
            return self._service._cpu(msgs)
        return self._service._mesh_hasher.hash_sharded(msgs, self._sub.mesh)

    def release(self) -> None:
        if self.released:
            return
        self.released = True
        self._sub.release()


class LeasedTurboBackend:
    """Array-protocol backend proxy that holds the service's exclusive
    lease for the duration of one turbo commit (``begin`` → terminal
    ``finish``/``fetch_slots``). The RebuildPipeline keeps streaming its
    pre-packed windows straight at the inner engine — zero per-dispatch
    service overhead — while coalesced lanes pause (aged requests bypass
    onto the CPU twin, see :meth:`HashService.lease`)."""

    def __init__(self, service: "HashService", inner=None, factory=None):
        if inner is None and factory is None:
            raise ValueError("LeasedTurboBackend needs inner or factory")
        self._service = service
        self._inner = inner
        self._factory = factory
        self._lease = None

    @property
    def effective_kind(self) -> str:
        return getattr(self._inner, "effective_kind", "device")

    @property
    def failed_over(self) -> bool:
        return getattr(self._inner, "failed_over", False)

    def begin(self, max_slots: int) -> None:
        if self._lease is None:
            self._lease = self._service.lease(what="rebuild")
            self._lease.__enter__()
        if self._inner is None:
            # deferred construction: on a meshed service the engine's
            # shardings must form over the sub-mesh the lease carved out,
            # which only exists once the lease is held
            self._inner = self._factory()
        self._inner.begin(max_slots)

    def release(self) -> None:
        """Drop the lease. Idempotent — the terminal fetch calls this, and
        committers also call it from a ``finally`` so an aborted commit
        (pipeline fault drill, sweep rejection) can never wedge the
        service's coalesced lanes."""
        if self._lease is not None:
            lease, self._lease = self._lease, None
            lease.__exit__(None, None, None)

    _release = release

    def ensure(self, max_slots: int) -> None:
        self._inner.ensure(max_slots)

    def alloc_slot(self) -> int:
        return self._inner.alloc_slot()

    def dispatch_level(self, bucket) -> None:
        self._inner.dispatch_level(bucket)

    def dispatch_packed(self, flat, row_off, row_len, slots, holes, b_tier):
        self._inner.dispatch_packed(flat, row_off, row_len, slots, holes,
                                    b_tier)

    def dispatch_branch(self, masks, slots, children) -> None:
        self._inner.dispatch_branch(masks, slots, children)

    def flush_window(self) -> None:
        flush = getattr(self._inner, "flush_window", None)
        if flush is not None:
            flush()

    def fetch_slots(self, slots):
        try:
            return self._inner.fetch_slots(slots)
        finally:
            self._release()

    def finish(self):
        try:
            return self._inner.finish()
        finally:
            self._release()


def _next_tier(n: int, min_tier: int) -> int:
    t = max(1, min_tier)
    while t < n:
        t *= 2
    return t


class HashService:
    """Background device hash service: one (supervised) backend, many
    clients, continuous batching. See the module docstring for semantics.

    ``backend``: the batch hasher (``list[bytes] -> list[bytes]``); when
    None, built from ``supervisor`` (a ``SupervisedHasher``) or, with no
    supervisor either, the plain device front-end.
    ``cpu_hasher``: the replay twin (default ``keccak256_batch_np``).
    ``mesh``: a ``parallel/mesh.py`` HashMesh — coalesced dispatches then
    route through the partition-rule table (sharded over the live mesh or
    unpartitioned on one device) instead of ``backend``; per-device
    breakers shrink the mesh before the CPU twin is ever considered.
    ``rebuild_devices``: sub-mesh lease width (k of n devices for the
    rebuild; default ``RETH_TPU_MESH_REBUILD_DEVICES`` or half the mesh).
    """

    def __init__(self, backend=None, supervisor=None, *,
                 cpu_hasher=None,
                 window_s: float | None = None,
                 fill_target: int | None = None,
                 max_batch: int | None = None,
                 lane_capacity: int | None = None,
                 age_promote_s: float | None = None,
                 lease_bypass_s: float | None = None,
                 min_tier: int = 1024,
                 injector: ServiceFaultInjector | None = None,
                 mesh=None, breaker_board=None, device_injector=None,
                 rebuild_devices: int | None = None, warmup=None,
                 subtrie_levels: int | None = None, registry=None):
        env = os.environ
        # multi-level window requests (submit_window): k levels per fused
        # dispatch; RETH_TPU_SUBTRIE_LEVELS=0 keeps the default of 8 here
        # because a window request is an EXPLICIT multi-level ask
        if subtrie_levels is None:
            subtrie_levels = int(
                env.get("RETH_TPU_SUBTRIE_LEVELS", "0") or 8)
        self.subtrie_levels = max(1, int(subtrie_levels))
        self.warmup = warmup
        self.window_dispatches = 0
        self.supervisor = supervisor
        if backend is None:
            if supervisor is not None:
                from .supervisor import SupervisedHasher

                backend = SupervisedHasher(supervisor, min_tier=min_tier)
            else:
                from .keccak_jax import KeccakDevice

                backend = KeccakDevice(min_tier=min_tier,
                                       block_tier=4).hash_batch
        self._backend = backend
        if cpu_hasher is None:
            from ..primitives.keccak import keccak256_batch_np

            cpu_hasher = keccak256_batch_np
        self._cpu = cpu_hasher
        self.window_s = float(window_s if window_s is not None
                              else env.get("RETH_TPU_SERVICE_WINDOW", "0.002"))
        self.fill_target = int(fill_target or
                               env.get("RETH_TPU_SERVICE_FILL", 0) or min_tier)
        self.max_batch = int(max_batch or 8 * self.fill_target)
        self.injector = (injector if injector is not None
                         else ServiceFaultInjector.from_env())
        cap = int(lane_capacity or
                  env.get("RETH_TPU_SERVICE_LANE_CAP", 0) or 262144)
        if self.injector is not None and self.injector.queue_cap:
            cap = self.injector.queue_cap
        self.lane_capacity = cap
        self.age_promote_s = float(
            age_promote_s if age_promote_s is not None
            else env.get("RETH_TPU_SERVICE_AGE_PROMOTE", "0.05"))
        self.lease_bypass_s = float(
            lease_bypass_s if lease_bypass_s is not None
            else env.get("RETH_TPU_SERVICE_LEASE_BYPASS", "0.02"))
        self.min_tier = min_tier

        from ..metrics import HashServiceMetrics

        self.metrics = HashServiceMetrics(registry)
        # -- device mesh (tentpole): partition-rule routed sharded dispatch,
        # per-device breakers, sub-mesh rebuild leases
        self.mesh = mesh
        self._mesh_hasher = None
        self.breaker_board = breaker_board
        self.device_injector = device_injector
        self.rebuild_devices = rebuild_devices
        if mesh is not None:
            from ..parallel.mesh import MeshKeccak

            self._mesh_hasher = MeshKeccak(mesh, min_tier=min_tier,
                                           block_tier=4, warmup=warmup)
            if breaker_board is None:
                from .supervisor import DeviceBreakerBoard

                self.breaker_board = DeviceBreakerBoard(mesh)
            if device_injector is None:
                from .supervisor import FaultInjector

                self.device_injector = FaultInjector.from_env()
            if rebuild_devices is None:
                self.rebuild_devices = int(
                    env.get("RETH_TPU_MESH_REBUILD_DEVICES", 0)
                    or max(1, mesh.n_devices // 2))
        self._cond = threading.Condition()
        self._queues: dict[str, list[_Request]] = {l: [] for l in LANES}
        self._queued_msgs: dict[str, int] = {l: 0 for l in LANES}
        self._stopping = False
        self._leased = False
        self._lease_what: str | None = None
        self._submesh = None  # active _SubMeshLease (rebuild holds k devices)
        self._dispatching = False
        # counters surfaced via snapshot() (metrics hold the full detail)
        self.dispatches = 0
        self.coalesced_requests = 0
        self.hashed_msgs = 0
        self.replays = 0
        self.rejects = 0
        self.leases = 0
        self.lease_bypasses = 0
        self.submesh_leases = 0
        self.pipeline_leases = 0
        self.mesh_sharded = 0
        self.mesh_single = 0
        self.mesh_replays = 0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="hash-service")
        self._thread.start()

    # -- shared instance (one service per process, like DeviceSupervisor) --

    _shared: "HashService | None" = None
    _shared_lock = threading.Lock()

    @classmethod
    def shared(cls, **kw) -> "HashService":
        with cls._shared_lock:
            if cls._shared is None:
                cls._shared = cls(**kw)
            return cls._shared

    @classmethod
    def reset_shared(cls) -> None:
        with cls._shared_lock:
            svc, cls._shared = cls._shared, None
        if svc is not None:
            svc.stop()

    # -- client API --------------------------------------------------------

    def client(self, lane: str) -> HashClient:
        return HashClient(self, lane)

    def submit(self, lane: str, msgs: list[bytes], *,
               block: bool = True, timeout: float | None = None) -> HashFuture:
        """Enqueue one request on ``lane``. A full lane blocks the caller
        (bounded-queue backpressure) unless ``block=False``, which raises
        :class:`LaneOverloaded` instead. Oversized single requests (more
        messages than the lane holds) are admitted alone — they could
        never fit otherwise."""
        if lane not in _LANE_INDEX:
            raise ValueError(f"unknown lane {lane!r} (have {LANES})")
        req = _Request(lane, msgs)
        if not msgs:
            req.future._complete(result=[])
            return req.future
        n = len(msgs)
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while True:
                if self._stopping:
                    raise ServiceStopped("hash service is stopping")
                room = self.lane_capacity - self._queued_msgs[lane]
                if n <= room or not self._queues[lane]:
                    break
                if not block:
                    self.rejects += 1
                    self.metrics.record_reject(lane)
                    raise LaneOverloaded(
                        f"lane {lane!r} is full "
                        f"({self._queued_msgs[lane]}/{self.lane_capacity} msgs)")
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    self.rejects += 1
                    self.metrics.record_reject(lane)
                    raise LaneOverloaded(
                        f"lane {lane!r} still full after {timeout}s")
                self._cond.wait(remaining)
            self._queues[lane].append(req)
            self._queued_msgs[lane] += n
            self.metrics.record_submit(lane, n)
            self.metrics.set_queue_depth(lane, self._queued_msgs[lane])
            self._cond.notify_all()
        return req.future

    def hash(self, lane: str, msgs: list[bytes]) -> list[bytes]:
        """Synchronous submit-and-wait — the ``hasher``-protocol path."""
        return self.submit(lane, msgs).result()

    def submit_window(self, lane: str, window: list[dict], max_slots: int,
                      *, fetch=None, block: bool = True,
                      timeout: float | None = None) -> HashFuture:
        """Enqueue one multi-level window request on ``lane``: a list of
        level dicts in deepest-first order (``{"flat", "row_off",
        "row_len", "slots", "holes", "b_tier"}`` or ``{"kind": "branch",
        "masks", "slots", "children"}``). The dispatcher runs the whole
        window through a whole-subtrie fused engine — ONE device dispatch
        per k levels — and completes the future with the digest buffer
        (or the requested ``fetch`` slots). Windows never coalesce with
        plain hash requests; they occupy ``rows`` messages of the lane's
        bounded capacity."""
        if lane not in _LANE_INDEX:
            raise ValueError(f"unknown lane {lane!r} (have {LANES})")
        req = _WindowRequest(lane, window, max_slots, fetch=fetch)
        if not window:
            req.future._complete(result=[])
            return req.future
        n = req.rows
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while True:
                if self._stopping:
                    raise ServiceStopped("hash service is stopping")
                room = self.lane_capacity - self._queued_msgs[lane]
                if n <= room or not self._queues[lane]:
                    break
                if not block:
                    self.rejects += 1
                    self.metrics.record_reject(lane)
                    raise LaneOverloaded(
                        f"lane {lane!r} is full "
                        f"({self._queued_msgs[lane]}/{self.lane_capacity} msgs)")
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    self.rejects += 1
                    self.metrics.record_reject(lane)
                    raise LaneOverloaded(
                        f"lane {lane!r} still full after {timeout}s")
                self._cond.wait(remaining)
            self._queues[lane].append(req)
            self._queued_msgs[lane] += n
            self.metrics.record_submit(lane, n)
            self.metrics.set_queue_depth(lane, self._queued_msgs[lane])
            self._cond.notify_all()
        return req.future

    # -- exclusive lease ----------------------------------------------------

    @contextmanager
    def lease(self, what: str = "rebuild", devices: int | None = None):
        """Device lease for a turbo commit.

        **Exclusive** (no mesh, or ``devices`` covers the mesh): coalesced
        dispatching pauses until release (in-flight dispatch first
        drains); queued requests that age past ``lease_bypass_s`` are
        hashed on the CPU twin meanwhile, so a long-held lease cannot
        stall the live tip.

        **Sub-mesh** (mesh present, ``devices=k`` leaves >= 1 live
        device): the rebuild claims k devices (``rebuild_mesh()`` exposes
        them to the engine factory) while coalesced dispatching CONTINUES
        on the remaining live sub-mesh — shardings re-form over the
        survivors, nothing pauses and nothing bypasses to the CPU.
        """
        if devices is None and self.mesh is not None:
            devices = self.rebuild_devices
        if self.mesh is not None and devices:
            from ..parallel.mesh import MeshExhausted

            t0 = time.monotonic()
            sub = None
            with self._cond:
                while self._leased or self._submesh is not None:
                    self._cond.wait()
                try:
                    sub = self.mesh.lease_submesh(devices, what=what)
                except MeshExhausted:
                    pass  # not enough live devices: exclusive lease below
                else:
                    self._submesh = sub
                    self._lease_what = what
                    self.leases += 1
                    self.submesh_leases += 1
            if sub is not None:
                self.metrics.record_lease(time.monotonic() - t0)
                try:
                    yield self
                finally:
                    with self._cond:
                        sub.release()
                        self._submesh = None
                        self._lease_what = None
                        self._cond.notify_all()
                return
        t0 = time.monotonic()
        with self._cond:
            while self._leased or self._submesh is not None \
                    or self._dispatching:
                self._cond.wait()
            self._leased = True
            self._lease_what = what
            self.leases += 1
        self.metrics.record_lease(time.monotonic() - t0)
        try:
            yield self
        finally:
            with self._cond:
                self._leased = False
                self._lease_what = None
                self._cond.notify_all()

    def rebuild_mesh(self):
        """The jax Mesh currently leased to the rebuild (``None`` outside
        a sub-mesh lease) — what ``TurboCommitter``'s engine factory
        builds its ``FusedMeshEngine`` over."""
        sub = self._submesh
        return sub.mesh if sub is not None else None

    def lease_backend(self, inner=None, *, factory=None) -> LeasedTurboBackend:
        """Wrap an array-protocol turbo engine so one commit holds the
        lease from ``begin()`` to its terminal fetch. Pass ``factory``
        instead of a built engine to defer construction until AFTER the
        lease is acquired — the mesh path needs this so the engine forms
        its shardings over the sub-mesh the lease just carved out."""
        return LeasedTurboBackend(self, inner, factory=factory)

    def pipeline_lease(self, devices: int | None = None):
        """Double-buffer sub-mesh for the cross-block import pipeline
        (engine/block_pipeline.py): carve ``devices`` (default half the
        mesh) for the speculative block's key prehash while the
        in-commit block's lane dispatches re-form over the remainder —
        the PR 10 rebuild lease generalized to two concurrent users.

        Unlike :meth:`lease` this never pauses coalesced dispatching and
        never waits: the speculation either gets its own devices
        immediately or runs without (``None`` — no mesh, or not enough
        live devices to leave the commit side at least one)."""
        if self.mesh is None or self._mesh_hasher is None:
            return None
        from ..parallel.mesh import MeshExhausted

        k = int(devices) if devices else max(1, self.mesh.n_devices // 2)
        try:
            sub = self.mesh.lease_submesh(k, what="pipeline")
        except MeshExhausted:
            return None
        self.pipeline_leases += 1
        return PipelineLease(self, sub)

    # -- dispatcher ---------------------------------------------------------

    def _total_queued(self) -> int:
        return sum(self._queued_msgs.values())

    def _drain_locked(self, now: float) -> list[_Request]:
        """Pick the next coalesced batch (caller holds the lock): aged
        requests first (FIFO — the anti-starvation rule), then lanes in
        priority order, whole requests, up to ``max_batch`` messages
        (always at least one request)."""
        aged = [r for lane in LANES for r in self._queues[lane]
                if now - r.enqueued_at >= self.age_promote_s]
        aged.sort(key=lambda r: r.enqueued_at)
        aged_ids = {id(r) for r in aged}
        order = aged + [r for lane in LANES for r in self._queues[lane]
                        if id(r) not in aged_ids]
        batch: list[_Request] = []
        total = 0
        if order and order[0].window is not None:
            # multi-level windows dispatch ALONE (one fused engine run,
            # never concatenated with plain hash messages)
            batch = [order[0]]
        else:
            for r in order:
                if r.window is not None:
                    continue  # next round leads with it
                if batch and total + len(r.msgs) > self.max_batch:
                    break
                batch.append(r)
                total += len(r.msgs)
        taken = {id(r) for r in batch}
        for lane in LANES:
            kept = [r for r in self._queues[lane] if id(r) not in taken]
            if len(kept) != len(self._queues[lane]):
                removed = sum(_req_msgs(r) for r in self._queues[lane]
                              if id(r) in taken)
                self._queues[lane] = kept
                self._queued_msgs[lane] -= removed
                self.metrics.set_queue_depth(lane, self._queued_msgs[lane])
        if batch:
            self._cond.notify_all()  # wake submitters blocked on capacity
        return batch

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._stopping and (self._total_queued() == 0):
                    self._cond.wait()
                if self._stopping and self._total_queued() == 0:
                    return
                # coalescing window: gather until the fused tier fills or
                # the oldest request's deadline expires
                while not self._stopping:
                    now = time.monotonic()
                    oldest = min(r.enqueued_at for lane in LANES
                                 for r in self._queues[lane])
                    if self._leased:
                        # lease held: the device is busy — requests that
                        # outwait the grace window go to the CPU twin
                        wait = (oldest + self.lease_bypass_s) - now
                        if wait <= 0:
                            batch = self._drain_locked(now)
                            bypass = True
                            break
                        self._cond.wait(wait)
                        continue
                    deadline = oldest + self.window_s
                    pending = sum(len(q) for q in self._queues.values())
                    # a LONE request dispatches immediately — the sync
                    # latency path pays no window; the window only gathers
                    # once a second request is pending (under load the
                    # previous dispatch's wall time is the gather period,
                    # continuous-batching style)
                    if (pending == 1
                            or self._total_queued() >= self.fill_target
                            or now >= deadline):
                        batch = self._drain_locked(now)
                        bypass = False
                        break
                    self._cond.wait(deadline - now)
                else:
                    # stopping: drain what's left (onto the twin if the
                    # device is still leased out)
                    batch = self._drain_locked(time.monotonic())
                    bypass = self._leased
                if not batch:
                    continue
                self._dispatching = not bypass
            try:
                self._dispatch(batch, bypass)
            finally:
                if not bypass:
                    with self._cond:
                        self._dispatching = False
                        self._cond.notify_all()

    def _mesh_dispatch(self, msgs: list[bytes], lane: str) -> list[bytes]:
        """One coalesced batch over the device mesh, with partial-mesh
        degradation. The partition-rule table decides sharded (``P(axis)``
        over the live mesh) vs unpartitioned (``P()`` on one device); a
        failed dispatch feeds the per-device breakers — attributed wedges
        shed their device immediately — and the SAME batch replays on the
        shrunken mesh, shardings re-formed over the survivors (hashing is
        stateless, so the replay is bit-identical). Raises only when no
        device is left: the caller's numpy-twin replay is the final rung.
        """
        from ..parallel.mesh import MeshExhausted

        board = self.breaker_board
        program = "keccak.scalar" if len(msgs) == 1 else "keccak.masked"
        attempts = 0
        while True:
            if board is not None:
                board.poll()  # cooled-down devices rejoin (trial by fire)
            spec, mesh = self.mesh.spec_for(lane, program, len(msgs))
            if mesh is None:
                raise MeshExhausted(
                    "no live mesh device (all breakers open or leased)")
            indices = tuple(self.mesh.devices.index(d)
                            for d in mesh.devices.flat)
            try:
                if self.device_injector is not None:
                    self.device_injector.on_mesh_dispatch(indices)
                out = self._mesh_hasher.hash_sharded(msgs, mesh)
            except BaseException as e:  # noqa: BLE001 — degraded below
                attempts += 1
                idx = getattr(e, "device_index", None)
                if board is None or attempts > self.mesh.n_devices + 1:
                    raise
                if idx is not None:
                    board.record_failure(idx, attributed=True)
                else:
                    # a collective failure with no device attribution:
                    # every participant is suspect (thresholded, so one
                    # flaky dispatch does not shed the whole mesh)
                    for i in indices:
                        board.record_failure(i)
                self.mesh_replays += 1
                self.mesh.metrics.record_replay()
                tracing.event("ops::hash_service", "mesh_replay",
                              msgs=len(msgs), shed=idx,
                              error=type(e).__name__,
                              live=self.mesh.healthy_count)
                continue  # replay the in-flight batch on the survivors
            if board is not None:
                board.record_success(indices)
            if len(spec) and len(indices) > 1:
                self.mesh_sharded += 1
                self.mesh.metrics.record_sharded()
            else:
                self.mesh_single += 1
                self.mesh.metrics.record_single()
            return out

    def _window_engine(self, lane: str, rows: int):
        """Whole-subtrie engine for ONE multi-level window dispatch. With
        a mesh, the partition-rule table routes ``fused.subtrie`` like
        any other program — sharded over the live mesh when every device
        gets a real row shard, a 1-device mesh otherwise; shard-by-
        subtrie holds because the packers keep each subtrie's rows
        contiguous and parent composition reads the replicated buffer."""
        from .fused_commit import SubtrieFusedEngine, SubtrieMeshEngine

        floors = dict(row_floor=max(64, 2 * self.min_tier),
                      hole_floor=max(64, 2 * self.min_tier))
        if self.mesh is not None:
            from ..parallel.mesh import MeshExhausted

            if self.breaker_board is not None:
                self.breaker_board.poll()
            _spec, mesh = self.mesh.spec_for(lane, "fused.subtrie", rows)
            if mesh is None:
                raise MeshExhausted(
                    "no live mesh device (all breakers open or leased)")
            return SubtrieMeshEngine(mesh, min_tier=self.min_tier,
                                     k=self.subtrie_levels,
                                     warmup=self.warmup, **floors)
        return SubtrieFusedEngine(min_tier=self.min_tier,
                                  k=self.subtrie_levels,
                                  warmup=self.warmup, **floors)

    @staticmethod
    def _run_window_on(engine, req: _WindowRequest):
        engine.begin(req.max_slots)
        for lv in req.window:
            if lv.get("kind") == "branch":
                engine.dispatch_branch(lv["masks"], lv["slots"],
                                       lv["children"])
            else:
                engine.dispatch_packed(lv["flat"], lv["row_off"],
                                       lv["row_len"], lv["slots"],
                                       lv.get("holes"), lv["b_tier"])
        if req.fetch is not None:
            import numpy as _np

            return engine.fetch_slots(_np.asarray(req.fetch,
                                                  dtype=_np.int64))
        return engine.finish()

    def _dispatch_window(self, req: _WindowRequest, bypass: bool) -> None:
        """Run one multi-level window as a whole-subtrie fused dispatch.
        Bypass (exclusive lease held) and any device failure land on the
        numpy twin — level replay is exact, the future completes once."""
        t0 = time.monotonic()
        self.metrics.record_wait(req.lane, t0 - req.enqueued_at)
        replayed = False
        replay_err = None
        digests = None
        if not bypass:
            try:
                if self.injector is not None:
                    self.injector.on_dispatch()
                digests = self._run_window_on(
                    self._window_engine(req.lane, req.rows), req)
            except BaseException as e:  # noqa: BLE001 — replayed below
                replayed = True
                replay_err = type(e).__name__
                self.replays += 1
                self.metrics.record_replay()
        else:
            self.lease_bypasses += 1
            self.metrics.record_lease_bypass()
        if digests is None:
            from ..trie.turbo import _NumpyBackend

            try:
                digests = self._run_window_on(_NumpyBackend(), req)
            except BaseException as e:  # pragma: no cover - twin failure
                req.future._complete(error=e)
                raise
        service_s = time.monotonic() - t0
        req.future._complete(result=digests)
        if replayed:
            tracing.event("ops::hash_service", "window_replay",
                          levels=len(req.window), rows=req.rows,
                          error=replay_err)
        self.dispatches += 1
        self.window_dispatches += 1
        self.coalesced_requests += 1
        self.hashed_msgs += req.rows
        now_wall = time.time()
        if req.ctx is not None:
            tracing.record_span(
                "ops::hash_service", "hashsvc.window",
                req.wall_at, now_wall - req.wall_at, ctx=req.ctx,
                fields={"lane": req.lane, "levels": len(req.window),
                        "rows": req.rows,
                        "service_ms": round(service_s * 1e3, 3),
                        "replayed": replayed, "bypass": bypass})
        tracing.record_span(
            "ops::hash_service",
            "hashsvc.replay" if replayed
            else ("hashsvc.bypass" if bypass else "hashsvc.dispatch"),
            now_wall - service_s, service_s,
            fields={"requests": 1, "msgs": req.rows,
                    "levels": len(req.window)})
        self.metrics.record_dispatch(
            requests=1, msgs=req.rows, occupancy=1.0,
            service_s=service_s, replayed=replayed)

    def _dispatch(self, batch: list[_Request], bypass: bool) -> None:
        """ONE backend call for the whole coalesced batch; scatter digests
        back through the futures. Any backend failure (watchdog trip that
        escaped the supervisor, injected service wedge, ...) replays the
        ENTIRE batch on the numpy twin — hashing is stateless, so replay
        is exact and every future completes exactly once."""
        if len(batch) == 1 and batch[0].window is not None:
            self._dispatch_window(batch[0], bypass)
            return
        msgs: list[bytes] = []
        for r in batch:
            msgs.extend(r.msgs)
        t0 = time.monotonic()
        for r in batch:
            self.metrics.record_wait(r.lane, t0 - r.enqueued_at)
        replayed = False
        replay_err = None
        try:
            if bypass:
                self.lease_bypasses += 1
                self.metrics.record_lease_bypass()
                digests = self._cpu(msgs)
            else:
                if self.injector is not None:
                    self.injector.on_dispatch()
                if self.mesh is not None:
                    digests = self._mesh_dispatch(msgs, batch[0].lane)
                else:
                    digests = self._backend(msgs)
        except BaseException as first_error:  # noqa: BLE001 — replayed below
            replayed = True
            replay_err = type(first_error).__name__
            self.replays += 1
            self.metrics.record_replay()
            try:
                digests = self._cpu(msgs)
            except BaseException as e:  # pragma: no cover - twin failure
                for r in batch:
                    r.future._complete(error=e)
                raise first_error
        service_s = time.monotonic() - t0
        if replayed:
            tracing.event("ops::hash_service", "replay",
                          requests=len(batch), msgs=len(msgs),
                          error=replay_err)
        off = 0
        now_wall = time.time()
        for r in batch:
            r.future._complete(result=digests[off:off + len(r.msgs)])
            off += len(r.msgs)
            # per-request attribution under the SUBMITTER's trace: queue
            # wait vs coalesce vs device dispatch vs replay, the split
            # the block wall-budget line prints
            if r.ctx is not None:
                wait_s = t0 - r.enqueued_at
                tracing.record_span(
                    "ops::hash_service", "hashsvc.request",
                    r.wall_at, now_wall - r.wall_at, ctx=r.ctx,
                    fields={"lane": r.lane, "msgs": len(r.msgs),
                            "wait_ms": round(wait_s * 1e3, 3),
                            "service_ms": round(service_s * 1e3, 3),
                            "coalesced_with": len(batch),
                            "replayed": replayed, "bypass": bypass})
        self.dispatches += 1
        self.coalesced_requests += len(batch)
        self.hashed_msgs += len(msgs)
        occupancy = len(msgs) / _next_tier(len(msgs), self.min_tier)
        tracing.record_span(
            "ops::hash_service",
            "hashsvc.replay" if replayed
            else ("hashsvc.bypass" if bypass else "hashsvc.dispatch"),
            now_wall - service_s, service_s,
            fields={"requests": len(batch), "msgs": len(msgs),
                    "occupancy": round(occupancy, 4)})
        self.metrics.record_dispatch(
            requests=len(batch), msgs=len(msgs), occupancy=occupancy,
            service_s=service_s, replayed=replayed)

    # -- lifecycle / observability ------------------------------------------

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop the dispatcher. ``drain=True`` completes everything still
        queued first; ``drain=False`` fails pending futures with
        :class:`ServiceStopped`."""
        with self._cond:
            self._stopping = True
            if not drain:
                for lane in LANES:
                    for r in self._queues[lane]:
                        r.future._complete(
                            error=ServiceStopped("hash service stopped"))
                    self._queues[lane].clear()
                    self._queued_msgs[lane] = 0
                    self.metrics.set_queue_depth(lane, 0)
            self._cond.notify_all()
        self._thread.join(timeout)

    def coalesce_factor(self) -> float:
        """Requests per coalesced dispatch (lifetime average) — the
        headline number: >1 means small client batches actually fused."""
        return (self.coalesced_requests / self.dispatches
                if self.dispatches else 0.0)

    def snapshot(self) -> dict:
        """State for the events dashboard line and bench/test triage."""
        with self._cond:
            queued = dict(self._queued_msgs)
            leased = self._lease_what
            sub = self._submesh
        out = {
            "queued": queued,
            "queued_total": sum(queued.values()),
            "dispatches": self.dispatches,
            "window_dispatches": self.window_dispatches,
            "coalesce_factor": round(self.coalesce_factor(), 2),
            "hashed_msgs": self.hashed_msgs,
            "replays": self.replays,
            "rejects": self.rejects,
            "leases": self.leases,
            "lease_bypasses": self.lease_bypasses,
            "leased_by": leased,
            "fault_injection": (self.injector.active()
                                if self.injector is not None else False),
        }
        if self.mesh is not None:
            out["mesh"] = {
                **self.mesh.snapshot(),
                "sharded_dispatches": self.mesh_sharded,
                "single_dispatches": self.mesh_single,
                "mesh_replays": self.mesh_replays,
                "submesh_leases": self.submesh_leases,
                "submesh_held": (list(sub.indices)
                                 if sub is not None else None),
                "pipeline_leases": self.pipeline_leases,
            }
            if self.device_injector is not None:
                out["fault_injection"] = (out["fault_injection"]
                                          or self.device_injector.active())
        return out
