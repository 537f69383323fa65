"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding/collective code is
validated on host CPU with 8 virtual devices (the driver separately
dry-run-compiles the multi-chip path via `__graft_entry__.dryrun_multichip`).
Must run before the first `import jax` anywhere in the test session.
"""

import os

import pytest

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


@pytest.fixture
def rebuild_layout(monkeypatch):
    """Set the rebuild's layout constants (``reth_tpu/trie/turbo.py``:
    ``SWEEP_THREADS``, ``PACK_WINDOW``, ``LEAVES_PER_SWEEP``) for one test,
    so that a tiny chunk is laid out as many sweep groups and windows:
    ``rebuild_layout(LEAVES_PER_SWEEP=1)`` is a group a job (a group closes
    at the job that brings it to the bound)."""
    from reth_tpu.trie import turbo

    def set_layout(**constants):
        for name, value in constants.items():
            monkeypatch.setattr(turbo, name, value, raising=True)

    return set_layout


@pytest.fixture
def seen_plans(monkeypatch):
    """What MegaFusedEngine is about to execute, copied before it runs."""
    from reth_tpu.ops import fused_commit as fc

    seen = []
    orig = fc.MegaFusedEngine._execute

    def spy(self):
        if self._buf is None:
            seen.append({"plan": list(self._plan), "s_tier": self._s_tier,
                         "lens": self._buffer_lens()})
        return orig(self)

    monkeypatch.setattr(fc.MegaFusedEngine, "_execute", spy)
    return seen
