"""What the device is, where compiled programs are kept, and which counters
say that work left the device.

Three small facts every entry point (``cli.py``, ``bench.py``,
``chip_smoke.py``) needs, kept in one place so they cannot drift:

- :func:`require_device` — the ONE read of ``jax.devices()``. A process is
  entitled to the TPU unless its environment names the CPU itself
  (``JAX_PLATFORMS=cpu``, how the tests and rehearsals run); on any other
  platform it raises rather than let JAX's CPU backend hash under the name
  "device".
- :func:`configure_compile_cache` — the ONE place the persistent XLA
  compilation cache is configured. ``JAX_COMPILATION_CACHE_DIR`` set: JAX
  reads it itself and nothing is set in code. Unset: one fixed path inside
  the checkout. The path is part of the cache key, so it never moves with
  the datadir, the mesh, the pid or the kernel sources — JAX's own key
  already covers program and topology.
- :func:`cpu_route_counters` — every counter a route from a device path
  onto the CPU moves. A measurement reads them before and after and fails
  when one moved (:func:`moved_cpu_routes`).
"""

from __future__ import annotations

import os
from pathlib import Path

# the fixed in-checkout cache path used when JAX_COMPILATION_CACHE_DIR is unset
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


class DeviceUnavailable(Exception):
    """JAX initialised on a platform this process is not entitled to."""


_DEVICES: tuple[str, str, int] | None = None  # (platform, device_kind, count)


def entitled_platform() -> str:
    """"cpu" only when the environment names the CPU itself; else "tpu"."""
    named = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    return "cpu" if named == "cpu" else "tpu"


def require_device() -> tuple[str, str, int]:
    """``(platform, device_kind, count)`` as JAX reports them, read once.
    Raises :class:`DeviceUnavailable` when the platform is not the one the
    process is entitled to — a missing chip is an error, never a silent
    CPU run."""
    global _DEVICES
    if _DEVICES is None:
        import jax

        devs = jax.devices()
        _DEVICES = (devs[0].platform, devs[0].device_kind, len(devs))
    want = entitled_platform()
    if _DEVICES[0] != want:
        raise DeviceUnavailable(
            f"JAX initialised on platform {_DEVICES[0]!r} "
            f"({_DEVICES[1]} x{_DEVICES[2]}), but this process is entitled "
            f"to {want!r}: no TPU was found and JAX_PLATFORMS does not name "
            f"the cpu. Use --hasher cpu, or set JAX_PLATFORMS=cpu for a "
            f"CPU rehearsal.")
    return _DEVICES


def compile_cache_dir() -> Path:
    """Where the persistent compile cache lives for this process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return Path(env) if env else DEFAULT_COMPILE_CACHE_DIR


def configure_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its one directory.
    Idempotent and safe from several processes (JAX creates the directory
    and writes entries itself). Never resets or moves the directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        want = str(DEFAULT_COMPILE_CACHE_DIR)
        if jax.config.jax_compilation_cache_dir != want:
            jax.config.update("jax_compilation_cache_dir", want)
    return compile_cache_dir()


# Every route from a device path onto the CPU moves one of these (names as
# registered in metrics.py). ``keccak_cpu_bucket_total_over_ceiling`` is
# the declared exception: messages above KeccakDevice.MAX_BLOCK_TIER rate
# blocks hash on the CPU twin by design and are reported separately.
CPU_ROUTE_COUNTERS = (
    "trie_commit_nodes_total_numpy",
    "fused_subtrie_fallbacks_total",
    "keccak_cpu_bucket_total_unwarmed",
    "warmup_cpu_routed_total",
    "hasher_supervisor_failovers_total",
    "hasher_supervisor_breaker_trips_total",
    "hasher_supervisor_dispatch_timeouts_total",
    "hash_service_replays_total",
    "hash_service_lease_bypass_total",
    "mesh_replays_total",
    "mesh_shrinks_total",
)
OVER_CEILING_COUNTER = "keccak_cpu_bucket_total_over_ceiling"


def cpu_route_counters(registry=None) -> dict[str, float]:
    """Current values of :data:`CPU_ROUTE_COUNTERS` (+ the over-ceiling
    bucket) in ``registry`` (default: the process registry); a counter
    nothing registered yet reads 0."""
    from ..metrics import REGISTRY

    live = dict((registry or REGISTRY).items())
    return {name: float(getattr(live.get(name), "value", 0.0))
            for name in CPU_ROUTE_COUNTERS + (OVER_CEILING_COUNTER,)}


def moved_cpu_routes(before: dict[str, float],
                     after: dict[str, float] | None = None) -> dict[str, float]:
    """Counters (over-ceiling bucket excepted) that grew since ``before``:
    empty means everything asked of the device ran on the device."""
    if after is None:
        after = cpu_route_counters()
    return {k: after[k] - before.get(k, 0.0) for k in CPU_ROUTE_COUNTERS
            if after[k] != before.get(k, 0.0)}
