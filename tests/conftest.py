"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding/collective code is
validated on host CPU with 8 virtual devices (the driver separately
dry-run-compiles the multi-chip path via `__graft_entry__.dryrun_multichip`).
Must run before the first `import jax` anywhere in the test session.
"""

import os

# FORCE cpu — a setdefault would let an inherited JAX_PLATFORMS (or a chip
# attached to the host) run the tests on a device. Tests must be hermetic
# on the virtual CPU mesh; naming the cpu here is also what entitles
# `--hasher device` code paths to run on it (ops/device.require_device).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()


def pytest_configure(config):
    # tier-1 deselects these via `-m 'not slow'`; `make test-sanitizers`
    # style targets opt back in with `-m slow`
    config.addinivalue_line(
        "markers", "slow: sanitizer builds / stress runs excluded from tier-1")
