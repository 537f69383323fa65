"""Device kernels (JAX/XLA/Pallas) — the TPU data plane.

Reference analogue: the `asm-keccak` native fast path and rayon-parallel
keccak loops of the reference (bin/reth/Cargo.toml:94,
crates/stages/stages/src/stages/hashing_account.rs:29-32,
crates/trie/sparse/src/arena/mod.rs:2500-2548). Here those become batched,
shape-stable XLA programs.
"""

# NOTE: nothing here configures the persistent compilation cache at import
# time — ops/device.configure_compile_cache is the one place, called once by
# each entry point (cli.py, bench.py, chip_smoke.py).

from .keccak_jax import (
    keccak_f1600_jax,
    keccak256_jax_words,
    keccak256_batch_jax,
    KeccakDevice,
)
from .device import (
    DeviceUnavailable,
    configure_compile_cache,
    cpu_route_counters,
    moved_cpu_routes,
    require_device,
)
from .supervisor import (
    CircuitBreaker,
    DeviceSupervisor,
    FaultInjector,
    SupervisedBackend,
    SupervisedHasher,
    probe_device,
)
from .hash_service import (
    HashClient,
    HashFuture,
    HashService,
    LaneOverloaded,
    ServiceFaultInjector,
)
from .warmup import (
    CompileCache,
    MenuShape,
    WarmupManager,
    build_warmup,
    default_menu,
)

__all__ = [
    "DeviceUnavailable",
    "configure_compile_cache",
    "cpu_route_counters",
    "moved_cpu_routes",
    "require_device",
    "CompileCache",
    "MenuShape",
    "WarmupManager",
    "build_warmup",
    "default_menu",
    "keccak_f1600_jax",
    "keccak256_jax_words",
    "keccak256_batch_jax",
    "KeccakDevice",
    "CircuitBreaker",
    "DeviceSupervisor",
    "FaultInjector",
    "SupervisedBackend",
    "SupervisedHasher",
    "probe_device",
    "HashClient",
    "HashFuture",
    "HashService",
    "LaneOverloaded",
    "ServiceFaultInjector",
]
