"""Device warm-up manager: supervised AOT compile lifecycle and
degraded-mode serving.

Compilation is a managed lifecycle instead of an ambush on the first live
dispatch:

- **Shape menu** (:func:`default_menu`): the bucketed
  ``(program, block_tier, batch_tier)`` grid already implicit in
  ``keccak_jax.py`` / ``fused_commit.py``, declared explicitly. At node
  start the manager AOT-compiles each menu shape ONE AT A TIME, each
  compile under a per-shape watchdog budget with retry + exponential
  backoff (``RETH_TPU_WARMUP_BUDGET`` / ``_ATTEMPTS`` / ``_BACKOFF``), and
  sequenced behind the supervisor's health probe — a stuck compile trips
  the circuit breaker (``ops/supervisor.py``) instead of freezing startup.
  ``RETH_TPU_FAULT_COMPILE_WEDGE`` drills the wedge path without hardware.
- **Persistent compilation cache**: configured once per process by
  ``ops/device.configure_compile_cache`` (``JAX_COMPILATION_CACHE_DIR`` or
  one fixed in-checkout path), never here. :class:`CompileCache` only
  REPORTS on it — entry counts around each shape compile, so the warm-up
  dashboard can tell a cache hit from a fresh compile.
- **Degraded-mode serving**: while warm-up is in progress the hash service
  and the committers run on the CPU twin; individual shapes are promoted
  to the device as each finishes compiling (per-shape
  cold/compiling/warm/failed states, consulted by
  ``KeccakDevice.route_bucket`` per dispatch and by ``SupervisedBackend``
  per fused commit). An un-warmed shape encountered mid-commit routes that
  bucket to the CPU — never a blocking fresh compile inside a commit.
- **Observability**: ``warmup_*`` metrics (``metrics.WarmupMetrics``), a
  ``warmup[...]`` events-dashboard fragment, per-shape ``ops::warmup``
  trace events, and the bench's ``warmup_state`` field.

Wiring: ``--warmup off|background|block`` on the CLI (``[node] warmup`` in
reth.toml); :func:`build_warmup` is the shared constructor the CLI and
``node/node.py`` use.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from pathlib import Path

from .. import tracing

COLD = "cold"
COMPILING = "compiling"
WARM = "warm"
FAILED = "failed"

# Declared ceilings shared with the dispatch front-ends: KeccakDevice chunks
# batches above the batch ceiling and routes messages above the block
# ceiling to the CPU twin, so no request can mint an off-menu program.
DEFAULT_MIN_TIER = 1024
DEFAULT_BLOCK_TIER = 4
DEFAULT_MAX_BATCH_TIER = 16384
DEFAULT_MAX_BLOCK_TIER = 32
# whole-subtrie k-level programs: the row tier the engines route against
# (mirrors ops/fused_commit.MegaFusedEngine._ROW_FLOOR — kept literal here
# so importing the menu never pulls jax in)
DEFAULT_SUBTRIE_TIER = 2048
# default k ladder declared for the k-level programs (--subtrie-levels)
DEFAULT_SUBTRIE_KS: tuple[int, ...] = (8,)


@dataclass(frozen=True)
class MenuShape:
    """One declared device program shape.

    ``program``: "keccak.masked" | "keccak.exact" | "fused.plain" |
    "fused.splice" — the same kind strings the dispatch sites report to the
    compile tracker, so menu states and dispatch attribution line up.
    ``mesh_size``: 1 = single-device; >1 = the SPMD variant sharded over
    that many devices (a sharded dispatch compiles a DIFFERENT executable
    than its single-device twin, so it needs its own menu slot — otherwise
    the first mesh-sharded dispatch ambushes a live commit with a fresh
    compile).
    """

    program: str
    block_tier: int
    batch_tier: int
    mesh_size: int = 1

    def key(self) -> tuple:
        return (self.program, self.block_tier, self.batch_tier,
                self.mesh_size)

    def __str__(self) -> str:  # events/log form
        base = f"{self.program}:{self.block_tier}x{self.batch_tier}"
        return base if self.mesh_size == 1 else f"{base}@m{self.mesh_size}"


def default_menu(min_tier: int = DEFAULT_MIN_TIER,
                 block_tier: int = DEFAULT_BLOCK_TIER,
                 max_batch_tier: int = DEFAULT_MAX_BATCH_TIER,
                 max_block_tier: int = DEFAULT_MAX_BLOCK_TIER,
                 include_fused: bool = True,
                 mesh_sizes: tuple[int, ...] = (),
                 subtrie_ks: tuple[int, ...] = DEFAULT_SUBTRIE_KS) -> list[MenuShape]:
    """The grid the runtime actually dispatches (see ``TrieCommitter``:
    ``KeccakDevice(min_tier=1024, block_tier=4)``): one masked program per
    pow2 batch tier for trie-node-sized messages (<= ``block_tier`` rate
    blocks), plus the pow2 block-tier ladder at the base batch tier for
    large messages (contract code), clamped at the declared ceilings —
    everything beyond the menu is served by the CPU twin, never a fresh
    mid-commit compile. ``include_fused`` adds the fused level-commit
    programs at the base tier (the live-tip sparse/turbo commit shapes).
    ``mesh_sizes`` adds the SPMD variants for each mesh size: the batch
    ladder rounded up to device-count multiples (the tiers the mesh
    front-ends actually mint — ``parallel/mesh.py mesh_tier`` /
    ``FusedMeshEngine``'s rounded floor), so a mesh-sharded dispatch
    never triggers a fresh compile mid-commit either."""
    shapes: list[MenuShape] = []
    t = min_tier
    while t <= max_batch_tier:
        shapes.append(MenuShape("keccak.masked", block_tier, t))
        t *= 2
    bt = 2 * block_tier
    while bt <= max_block_tier:
        shapes.append(MenuShape("keccak.masked", bt, min_tier))
        bt *= 2
    if include_fused:
        shapes.append(MenuShape("fused.plain", block_tier, min_tier))
        shapes.append(MenuShape("fused.splice", block_tier, min_tier))
        # whole-subtrie k-level programs (fused.subtrie): block_tier slot
        # carries k — the levels-per-dispatch the engine was built with;
        # an un-warm (k, tier, mesh) shape routes the commit to the
        # per-level path instead of compiling mid-commit
        for k in subtrie_ks:
            if k > 1:
                shapes.append(
                    MenuShape("fused.subtrie", k, DEFAULT_SUBTRIE_TIER))
    for m in mesh_sizes:
        if m <= 1:
            continue
        floor = -(-min_tier // m) * m  # device-count-multiple rounding
        t = floor
        while t <= max_batch_tier:
            shapes.append(MenuShape("keccak.masked", block_tier, t, m))
            t *= 2
        if include_fused:
            shapes.append(MenuShape("fused.plain", block_tier, floor, m))
            shapes.append(MenuShape("fused.splice", block_tier, floor, m))
            for k in subtrie_ks:
                if k > 1:
                    # device-count-multiple rounding, like every mesh tier
                    sub_t = -(-DEFAULT_SUBTRIE_TIER // m) * m
                    shapes.append(MenuShape("fused.subtrie", k, sub_t, m))
    return shapes


def _mesh_for_shape(mesh_size: int):
    """(Mesh, batch sharding, replicated sharding) for an SPMD menu shape.
    jax interns ``Mesh`` per (devices, axes), so the warm-up's sharded
    dummy dispatch hits the SAME jit cache entries the runtime's
    ``MeshKeccak`` / ``FusedMeshEngine`` use."""
    import numpy as np

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    if len(devices) < mesh_size:
        raise ValueError(
            f"menu shape needs {mesh_size} devices, found {len(devices)}")
    mesh = Mesh(np.array(devices[:mesh_size]), ("data",))
    return mesh, NamedSharding(mesh, P("data")), NamedSharding(mesh, P())


def _build_shape(shape: MenuShape) -> None:
    """Compile ``shape``'s program by dispatching a dummy batch of exactly
    that shape through the SAME jitted callables the runtime uses — the
    in-process jit cache (and, when enabled, the persistent cache) is keyed
    by function + shapes + shardings, so the runtime's first real dispatch
    of the shape is steady-state. The result sync (`np.asarray`) makes the
    wall honest. ``mesh_size > 1`` dispatches the dummy batch SHARDED over
    the first ``mesh_size`` devices — the mesh variant is a different
    executable than its single-device twin."""
    import numpy as np

    import jax

    put_batch = None
    sharding_key = None
    if shape.mesh_size > 1:
        mesh, batch_sh, rep_sh = _mesh_for_shape(shape.mesh_size)
        if shape.batch_tier % shape.mesh_size:
            raise ValueError(
                f"mesh menu tier {shape.batch_tier} not divisible by "
                f"mesh size {shape.mesh_size}")
        put_batch = lambda a: jax.device_put(a, batch_sh)  # noqa: E731
        put_rep = lambda a: jax.device_put(a, rep_sh)      # noqa: E731
        sharding_key = mesh
    else:
        import jax.numpy as jnp

        put_batch = put_rep = jnp.asarray
    if shape.program in ("keccak.masked", "keccak.exact"):
        from .keccak_jax import keccak256_jax_words, keccak256_jax_words_masked

        words = np.zeros((shape.batch_tier, shape.block_tier * 34),
                         dtype=np.uint32)
        if shape.program == "keccak.exact":
            np.asarray(keccak256_jax_words(put_batch(words),
                                           shape.block_tier))
        else:
            counts = np.ones((shape.batch_tier,), dtype=np.int32)
            np.asarray(keccak256_jax_words_masked(
                put_batch(words), shape.block_tier,
                counts=put_batch(counts)))
        return
    if shape.program in ("fused.plain", "fused.splice"):
        from ..primitives.keccak import RATE
        from .fused_commit import _jitted

        n, b = shape.batch_tier, shape.block_tier
        templates = put_batch(np.zeros((n, b * RATE), dtype=np.uint8))
        counts = put_batch(np.ones((n,), dtype=np.int32))
        slots = put_batch(np.zeros((n,), dtype=np.int32))
        buf = put_rep(np.zeros((n, 32), dtype=np.uint8))
        if shape.program == "fused.plain":
            fn = _jitted("plain", b, sharding_key)
            np.asarray(fn(templates, counts, slots, buf))
        else:
            # hole tier mirrors FusedLevelEngine: _HOLE_FACTOR * min batch
            h = 4 * n
            zeros_h = put_batch(np.zeros((h,), dtype=np.int32))
            fn = _jitted("splice", b, sharding_key)
            np.asarray(fn(templates, counts, zeros_h, zeros_h, zeros_h,
                          slots, buf))
        return
    if shape.program == "fused.subtrie":
        # k-level program: stage one packed + one branch level through the
        # REAL engine (so chunk planning mints the exact (b_tier=4,
        # row-floor, hole-floor) key the runtime's first chunk hits) and
        # execute — the loop body compiles BOTH step kinds via its cond
        from .fused_commit import SubtrieFusedEngine, SubtrieMeshEngine

        k = shape.block_tier
        if shape.mesh_size > 1:
            mesh, _batch_sh, _rep_sh = _mesh_for_shape(shape.mesh_size)
            eng = SubtrieMeshEngine(mesh, min_tier=64, k=k,
                                    row_floor=shape.batch_tier,
                                    hole_floor=shape.batch_tier)
        else:
            eng = SubtrieFusedEngine(min_tier=64, k=k,
                                     row_floor=shape.batch_tier,
                                     hole_floor=shape.batch_tier)
        eng.begin(4)
        s1, s2 = eng.alloc_slot(), eng.alloc_slot()
        row = b"\x01" * 40
        eng.dispatch_packed(np.frombuffer(row, dtype=np.uint8),
                            np.zeros((1,), dtype=np.uint32),
                            np.array([len(row)], dtype=np.uint32),
                            np.array([s1], dtype=np.int32), None, 4)
        eng.dispatch_branch(np.array([0x0001], dtype=np.uint16),
                            np.array([s2], dtype=np.int32),
                            np.array([[0], [0], [s1]], dtype=np.int32))
        np.asarray(eng.finish())
        return
    raise ValueError(f"unknown menu program {shape.program!r}")


class CompileCache:
    """Thin reporter over the process's persistent XLA compilation cache.

    The cache itself is configured once per process by
    ``ops/device.configure_compile_cache`` (``JAX_COMPILATION_CACHE_DIR``,
    else one fixed path inside the checkout); this class never touches
    the jax config. It counts entries so the warm-up manager can tell, per
    shape, whether a compile was served from disk (entry count unchanged)
    or written fresh."""

    def __init__(self, cache_dir: str | Path | None = None):
        from .device import compile_cache_dir

        self.dir = Path(cache_dir) if cache_dir else compile_cache_dir()
        self.entries_at_start = self.entry_count()

    def entry_count(self) -> int:
        try:
            return sum(1 for p in self.dir.iterdir()
                       if p.is_file() and not p.name.endswith("-atime"))
        except OSError:
            return 0

    def summary(self) -> dict:
        return {"mode": "warm" if self.entries_at_start else "cold",
                "dir": str(self.dir), "entries": self.entry_count()}


class WarmupManager:
    """Owns the compile lifecycle for the device keccak/fused kernels.

    ``run()`` (or ``start()`` for a background thread) walks the menu one
    shape at a time: each compile runs in a worker thread under ``budget``
    seconds; a timeout abandons the wedged thread, counts a breaker failure
    on the attached supervisor, and retries with exponential backoff.
    Shapes settle in WARM or FAILED; the routing queries
    (:meth:`route_bucket`, :meth:`device_ready`) implement degraded-mode
    serving until everything is warm. ``on_device_recovered()`` (called by
    the supervisor's half-open probe success) re-queues FAILED shapes, so
    shapes promote once a fault clears."""

    def __init__(self, menu: list[MenuShape] | None = None, *,
                 supervisor=None, cache: CompileCache | None = None,
                 budget: float | None = None, attempts: int | None = None,
                 backoff: float | None = None, builder=None, injector=None,
                 registry=None):
        from ..metrics import WarmupMetrics

        self.menu = list(menu if menu is not None else default_menu())
        self.sup = supervisor
        self.cache = cache
        if budget is None:
            budget = float(os.environ.get("RETH_TPU_WARMUP_BUDGET", "240"))
        self.budget = budget
        if attempts is None:
            attempts = int(os.environ.get("RETH_TPU_WARMUP_ATTEMPTS", "3"))
        self.attempts = max(1, attempts)
        if backoff is None:
            backoff = float(os.environ.get("RETH_TPU_WARMUP_BACKOFF", "2"))
        self.backoff = backoff
        self._builder = builder or _build_shape
        if injector is None and supervisor is not None:
            injector = supervisor.injector
        if injector is None:
            from .supervisor import FaultInjector

            injector = FaultInjector.from_env()
        self.injector = injector
        self.metrics = WarmupMetrics(registry)
        self._lock = threading.Lock()
        self.states: dict[tuple, str] = {s.key(): COLD for s in self.menu}
        self.compile_walls: dict[tuple, float] = {}
        self.retries = 0
        self.wedges = 0
        self.cpu_routed = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._current: MenuShape | None = None
        self._active = False      # gating applies from start() onward
        self._retrying = False
        self._done = threading.Event()
        self._thread: threading.Thread | None = None
        if supervisor is not None:
            supervisor.warmup = self
        self._publish()

    # -- routing queries (hot path) -----------------------------------------

    def device_ready(self) -> bool:
        """May a whole fused commit claim the device? True before warm-up
        ever starts (no gating), and once every menu shape is WARM. While
        warming — or degraded with FAILED shapes — commits stay on the CPU
        twin (a fused commit's digest buffer can't switch backends at a
        shape boundary)."""
        if not self._active:
            return True
        return self._done.is_set() and all(
            s == WARM for s in self.states.values())

    def route_bucket(self, program: str, block_tier: int,
                     batch_tier: int, mesh_size: int = 1) -> bool:
        """Per-dispatch routing: True = device, False = CPU twin. A WARM
        shape always gets the device; during warm-up (or degraded) an
        un-warm or off-menu shape routes to the CPU — never a blocking
        fresh compile inside a commit. ``mesh_size`` selects the SPMD
        variant's menu slot."""
        if not self._active:
            return True
        if self.states.get((program, block_tier, batch_tier,
                            mesh_size)) == WARM:
            return True
        if self.device_ready():
            return True  # fully warm: off-menu stragglers ride the watchdog
        with self._lock:
            self.cpu_routed += 1
        self.metrics.record_cpu_routed()
        return False

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        """Run warm-up on a background thread (the node serves degraded on
        the CPU twin meanwhile; shapes promote as they finish)."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._thread = threading.Thread(target=self.run, daemon=True,
                                            name="device-warmup")
        self._thread.start()

    def wait(self, timeout: float | None = None) -> bool:
        return self._done.wait(timeout)

    def run(self) -> dict:
        """Blocking warm-up pass: the menu one shape at a time. Returns
        the final snapshot."""
        self._active = True
        self._done.clear()
        t0 = time.monotonic()
        self._publish()
        if self.cache is not None:
            self.metrics.set_cache_entries(self.cache.entry_count())
        for shape in self.menu:
            if self.states.get(shape.key()) != WARM:
                self._compile_shape(shape)
        self._done.set()
        self._publish()
        snap = self.snapshot()
        tracing.event("ops::warmup", "warmup_done", state=snap["state"],
                      warm=snap["warm"], failed=snap["failed"],
                      total=snap["total"],
                      wall_s=round(time.monotonic() - t0, 3),
                      compile_wall_s=snap["compile_wall_s"],
                      cache=snap["cache"]["mode"])
        return snap

    def retry_failed(self) -> int:
        """Re-run FAILED shapes (promotion path after a fault clears);
        returns how many became WARM. Reentrancy-guarded: the supervisor's
        half-open probe success fires mid-retry too."""
        with self._lock:
            if self._retrying:
                return 0
            self._retrying = True
        try:
            failed = [s for s in self.menu
                      if self.states.get(s.key()) == FAILED]
            if not failed:
                return 0
            self._done.clear()
            self._publish()
            promoted = 0
            for shape in failed:
                if self._compile_shape(shape):
                    promoted += 1
            self._done.set()
            self._publish()
            return promoted
        finally:
            with self._lock:
                self._retrying = False

    def on_device_recovered(self) -> None:
        """Supervisor hook: a half-open probe just closed the breaker —
        promote FAILED shapes in the background."""
        if not self._active or self.device_ready():
            return
        if not any(s == FAILED for s in self.states.values()):
            return
        threading.Thread(target=self.retry_failed, daemon=True,
                         name="device-warmup-retry").start()

    # -- internals -----------------------------------------------------------

    def _set_state(self, shape: MenuShape, state: str) -> None:
        with self._lock:
            self.states[shape.key()] = state
            self._current = shape if state == COMPILING else None
        self._publish()

    def _compile_shape(self, shape: MenuShape) -> bool:
        for attempt in range(1, self.attempts + 1):
            if self.sup is not None and not self.sup.allows_device():
                # breaker open: serving stays on the CPU twin; the shape
                # parks FAILED until the supervisor's half-open probe
                # succeeds and on_device_recovered() re-queues it
                self._set_state(shape, FAILED)
                tracing.event("ops::warmup", "shape_deferred",
                              shape=str(shape), reason="breaker open")
                return False
            self._set_state(shape, COMPILING)
            before = (self.cache.entry_count()
                      if self.cache is not None else None)
            t0 = time.perf_counter()
            ok, err = self._guarded_build(shape)
            wall = time.perf_counter() - t0
            if ok:
                hit = None
                if before is not None:
                    hit = self.cache.entry_count() == before
                    with self._lock:
                        if hit:
                            self.cache_hits += 1
                        else:
                            self.cache_misses += 1
                with self._lock:
                    self.compile_walls[shape.key()] = round(wall, 6)
                self._set_state(shape, WARM)
                self.metrics.record_compile(wall, cache_hit=hit)
                if self.sup is not None:
                    self.sup.breaker.record_success()
                tracing.event("ops::warmup", "shape_warm", shape=str(shape),
                              wall_s=round(wall, 4), attempt=attempt,
                              cache_hit=hit)
                return True
            with self._lock:
                self.wedges += 1
            self.metrics.record_wedge()
            if self.sup is not None:
                # a wedged compile is a device failure like any other: it
                # feeds the breaker so repeated wedges trip it and the node
                # keeps serving degraded instead of freezing startup
                if self.sup.breaker.record_failure():
                    self.sup.metrics.record_trip()
                self.sup._publish()
            tracing.event("ops::warmup", "shape_wedged", shape=str(shape),
                          attempt=attempt, budget_s=self.budget,
                          error=str(err)[:200])
            if attempt < self.attempts:
                with self._lock:
                    self.retries += 1
                self.metrics.record_retry()
                time.sleep(self.backoff * (2 ** (attempt - 1)))
        self._set_state(shape, FAILED)
        return False

    def _guarded_build(self, shape: MenuShape) -> tuple[bool, object]:
        """One compile attempt in a worker thread under the watchdog budget
        (a wedged XLA compile cannot be cancelled — the thread is abandoned
        and the shape retried/failed, exactly like a supervised dispatch)."""
        box: list = [False, None]
        injector = self.injector

        def _call():
            try:
                if injector is not None:
                    injector.on_compile(self.budget)
                self._builder(shape)
                box[0] = True
            except BaseException as e:  # noqa: BLE001 — reported below
                box[1] = e

        t = threading.Thread(target=_call, daemon=True,
                             name=f"warmup-{shape.program}")
        t.start()
        t.join(self.budget)
        if t.is_alive():
            tracing.fault_event("warmup_compile_timeout",
                                target="ops::warmup", shape=str(shape),
                                budget_s=self.budget)
            return False, f"compile exceeded {self.budget}s watchdog budget"
        if not box[0]:
            return False, box[1]
        return True, None

    # -- observability -------------------------------------------------------

    def _counts(self) -> tuple[int, int, int]:
        vals = list(self.states.values())
        return (sum(1 for s in vals if s == WARM),
                sum(1 for s in vals if s == FAILED), len(vals))

    def overall_state(self) -> str:
        if not self._active:
            return "off"
        warm, failed, total = self._counts()
        if not self._done.is_set():
            return "warming"
        if warm == total:
            return "warm"
        return "degraded"

    def snapshot(self) -> dict:
        with self._lock:
            states = dict(self.states)
            walls = dict(self.compile_walls)
            current = self._current
        warm = sum(1 for s in states.values() if s == WARM)
        failed = sum(1 for s in states.values() if s == FAILED)
        return {
            "state": self.overall_state(),
            "warm": warm,
            "failed": failed,
            "total": len(states),
            "compiling": str(current) if current is not None else None,
            "compile_wall_s": round(sum(walls.values()), 4),
            "retries": self.retries,
            "wedges": self.wedges,
            "cpu_routed": self.cpu_routed,
            "cache": (self.cache.summary() if self.cache is not None
                      else {"mode": "off", "entries": 0}),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "shapes": {(f"{k[0]}:{k[1]}x{k[2]}"
                        + (f"@m{k[3]}" if k[3] != 1 else "")): v
                       for k, v in states.items()},
        }

    def _publish(self) -> None:
        warm, failed, total = self._counts()
        self.metrics.set_progress(total=total, warm=warm, failed=failed)
        self.metrics.set_state(self.overall_state())


def build_warmup(supervisor=None, menu: list[MenuShape] | None = None,
                 registry=None, mesh_size: int = 1, **kw) -> WarmupManager:
    """Shared constructor for the CLI and ``node/node.py``: a manager over
    the default menu, reporting on the process's persistent compile cache.
    ``mesh_size > 1`` (the ``--mesh`` wiring) adds the SPMD menu
    variants."""
    if menu is None and mesh_size > 1:
        menu = default_menu(mesh_sizes=(mesh_size,))
    return WarmupManager(menu=menu, supervisor=supervisor,
                         cache=CompileCache(), registry=registry, **kw)
