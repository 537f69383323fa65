"""The device-honesty rules (ISSUE 24), on CPU.

- ``require_device()`` is the one read of ``jax.devices()``; it raises when
  the platform is not ``tpu`` unless ``JAX_PLATFORMS`` names the cpu.
- ``--hasher device`` / ``TurboCommitter(backend="device")`` refuse to run
  on a platform the process is not entitled to.
- ``RETH_TPU_PALLAS=1`` runs the Pallas kernel or raises — the XLA program
  never answers in its place.
- Every route from ``KeccakDevice`` onto the CPU twin moves a counter, and
  ``moved_cpu_routes`` reports the ones that disqualify a device run.
- ``chip_smoke.py`` at tiny size under ``JAX_PLATFORMS=cpu`` runs every
  phase and fails only at the platform check.

The steering happens here (env / monkeypatch), never through an option of
the program.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from reth_tpu.metrics import MetricsRegistry
from reth_tpu.ops import device
from reth_tpu.ops.keccak_jax import KeccakDevice
from reth_tpu.primitives.keccak import RATE, keccak256

REPO = Path(__file__).resolve().parent.parent


def _msgs(n, lo, hi, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.bytes(int(k)) for k in rng.integers(lo, hi + 1, size=n)]


# -- require_device -----------------------------------------------------------


def test_require_device_reports_what_jax_reports():
    import jax

    platform, kind, count = device.require_device()  # conftest names cpu
    assert (platform, kind, count) == (
        jax.devices()[0].platform, jax.devices()[0].device_kind,
        len(jax.devices()))
    assert platform == "cpu" and device.entitled_platform() == "cpu"


@pytest.mark.parametrize("named", [None, "", "tpu", "cpu,tpu", "cuda"])
def test_require_device_raises_off_tpu_unless_cpu_is_named(monkeypatch, named):
    if named is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", named)
    assert device.entitled_platform() == "tpu"
    with pytest.raises(device.DeviceUnavailable) as ei:
        device.require_device()
    assert "'cpu'" in str(ei.value) and "'tpu'" in str(ei.value)
    monkeypatch.setenv("JAX_PLATFORMS", " CPU ")
    assert device.require_device()[0] == "cpu"


def test_require_device_reads_jax_devices_once(monkeypatch):
    import jax

    calls = []
    real = jax.devices

    def counting(*a, **kw):
        calls.append(a)
        return real(*a, **kw)

    monkeypatch.setattr(device, "_DEVICES", None)
    monkeypatch.setattr(jax, "devices", counting)
    first = device.require_device()
    assert device.require_device() == first
    assert len(calls) == 1


def test_a_tpu_is_accepted_whatever_its_kind(monkeypatch):
    """No peak rate or device name is assumed for a kind it does not know:
    the tuple is passed through as JAX reports it."""
    monkeypatch.setattr(device, "_DEVICES", ("tpu", "TPU v9 imaginary", 1))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert device.require_device() == ("tpu", "TPU v9 imaginary", 1)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(device.DeviceUnavailable):
        device.require_device()  # named the cpu, got a tpu: not that either


def test_hasher_device_refuses_to_pretend(monkeypatch, tmp_path, capsys):
    """`--hasher device` with no TPU and no explicit cpu: an error at
    start-up, never JAX's CPU backend hashing under the name "device"."""
    from reth_tpu.cli import main
    from reth_tpu.trie.turbo import TurboCommitter

    genesis = tmp_path / "g.json"
    genesis.write_text(json.dumps({
        "config": {"chainId": 1},
        "alloc": {"0x" + "11" * 20: {"balance": "0x1"}}}))
    argv = ["init", "--datadir", str(tmp_path / "d"), "--genesis",
            str(genesis)]
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(device.DeviceUnavailable):
        main(argv + ["--hasher", "device"])
    with pytest.raises(device.DeviceUnavailable):
        TurboCommitter(backend="device")._device_engine()
    # --hasher cpu never asks; --hasher auto probes, fails, serves on cpu
    assert main(argv + ["--hasher", "cpu"]) == 0
    from reth_tpu.ops.supervisor import DeviceSupervisor

    DeviceSupervisor.reset_shared()
    try:
        assert main(["init", "--datadir", str(tmp_path / "d2"), "--genesis",
                     str(genesis), "--hasher", "auto"]) == 0
        assert "device unhealthy at startup" in capsys.readouterr().err
        assert DeviceSupervisor.shared().route() == "numpy"
    finally:
        DeviceSupervisor.reset_shared()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert main(["init", "--datadir", str(tmp_path / "d3"), "--genesis",
                 str(genesis), "--hasher", "device"]) == 0


# -- the Pallas route ---------------------------------------------------------


def test_pallas_route_runs_the_kernel(monkeypatch):
    from reth_tpu.metrics import compile_tracker
    from reth_tpu.ops import keccak_pallas

    calls = []
    real = keccak_pallas.keccak256_pallas_words

    def spy(words, interpret=False):
        calls.append(interpret)
        return real(words, interpret)

    monkeypatch.setattr(keccak_pallas, "keccak256_pallas_words", spy)
    monkeypatch.setenv("RETH_TPU_PALLAS", "1")
    msgs = _msgs(40, 0, RATE - 1)
    assert KeccakDevice(min_tier=128).hash_batch(msgs) == [
        keccak256(m) for m in msgs]
    assert calls == [True]  # off the TPU the same kernel runs interpreted
    assert ("keccak.pallas", 1, 128) in compile_tracker.shapes


def test_pallas_route_raises_instead_of_answering_from_xla(monkeypatch):
    from reth_tpu.ops import keccak_jax, keccak_pallas

    def broken(words, interpret=False):
        raise RuntimeError("Mosaic refused the kernel")

    def xla_must_not_answer(*a, **kw):
        raise AssertionError("the XLA program answered for the Pallas kernel")

    monkeypatch.setattr(keccak_pallas, "keccak256_pallas_words", broken)
    monkeypatch.setattr(keccak_jax, "keccak256_jax_words", xla_must_not_answer)
    monkeypatch.setenv("RETH_TPU_PALLAS", "1")
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        KeccakDevice(min_tier=128).hash_batch(_msgs(8, 0, RATE - 1))
    # without the variable the XLA program is what was asked for
    monkeypatch.delenv("RETH_TPU_PALLAS")
    monkeypatch.undo()
    msgs = _msgs(8, 0, RATE - 1)
    assert KeccakDevice(min_tier=128).hash_batch(msgs) == [
        keccak256(m) for m in msgs]


# -- CPU routes move counters -------------------------------------------------


def test_over_ceiling_bucket_moves_its_counter():
    before = device.cpu_route_counters()
    kd = KeccakDevice(min_tier=8, max_block_tier=2)
    msgs = _msgs(3, 2 * RATE, 3 * RATE - 1)  # 3 blocks > ceiling of 2
    assert kd.hash_batch(msgs) == [keccak256(m) for m in msgs]
    after = device.cpu_route_counters()
    assert after[device.OVER_CEILING_COUNTER] \
        == before[device.OVER_CEILING_COUNTER] + 1
    # the declared exception: it does not disqualify a device run
    assert device.OVER_CEILING_COUNTER not in device.CPU_ROUTE_COUNTERS
    assert "keccak_cpu_bucket_total_unwarmed" not in \
        device.moved_cpu_routes(before, after)


def test_unwarmed_shape_twin_moves_its_counter():
    from reth_tpu.ops.warmup import MenuShape, WarmupManager

    mgr = WarmupManager(menu=[MenuShape("keccak.masked", 4, 8)],
                        registry=MetricsRegistry(), builder=lambda s: None)
    mgr._active = True  # warm-up started, nothing warm yet
    before = device.cpu_route_counters()
    kd = KeccakDevice(min_tier=8, block_tier=4, warmup=mgr)
    msgs = _msgs(5, 0, RATE - 1)
    assert kd.hash_batch(msgs) == [keccak256(m) for m in msgs]
    moved = device.moved_cpu_routes(before)
    assert moved.get("keccak_cpu_bucket_total_unwarmed") == 1
    mgr.run()  # every shape warm: the device answers, the counter rests
    before = device.cpu_route_counters()
    assert kd.hash_batch(msgs) == [keccak256(m) for m in msgs]
    assert device.moved_cpu_routes(before) == {}


def test_numpy_commit_and_fused_fallback_count_as_cpu_routes():
    from reth_tpu.metrics import fused_metrics, trie_metrics

    before = device.cpu_route_counters()
    assert device.moved_cpu_routes(before) == {}
    trie_metrics.record_commit(backend="device", nodes=7, levels=1,
                               leaves=1, wire_bytes=0, seconds=0.0)
    assert device.moved_cpu_routes(before) == {}
    trie_metrics.record_commit(backend="numpy", nodes=7, levels=1,
                               leaves=1, wire_bytes=0, seconds=0.0)
    fused_metrics.record_fallback()
    assert device.moved_cpu_routes(before) == {
        "trie_commit_nodes_total_numpy": 7.0,
        "fused_subtrie_fallbacks_total": 1.0}
    # a registry nothing registered in reads all zeros
    assert set(device.cpu_route_counters(MetricsRegistry()).values()) == {0.0}


# -- sparse "no device stack" returns catch ImportError only ------------------


def test_sparse_engine_errors_reach_the_ladder(monkeypatch):
    """Building the k-level engine may fail with ImportError (no jax: the
    classic path answers); any other error is a device error and must
    reach the caller instead of vanishing behind `return None`."""
    import inspect

    from reth_tpu.trie import sparse

    src = inspect.getsource(sparse)
    assert src.count("except ImportError:  # no jax installed") == 2
    assert "no device stack" not in src


# -- chip_smoke.py ------------------------------------------------------------


def _smoke(args, env_extra, cwd=REPO, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "RETH_TPU_PALLAS",
                        "JAX_COMPILATION_CACHE_DIR")}
    env.update(env_extra)
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          capture_output=True, text=True, timeout=timeout,
                          cwd=str(cwd), env=env)


def test_chip_smoke_tiny_runs_every_phase_and_fails_only_at_the_platform():
    r = _smoke(["--tiny", "--seed", "3"], {"JAX_PLATFORMS": "cpu"})
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    phases = [ln.get("phase") for ln in lines]
    assert phases == ["start", "kernels", "init", "import", "node",
                      "rebuild", "done"], (phases, r.stderr[-2000:])
    assert r.returncode == 3, r.stderr[-2000:]
    assert not any("ok" in ln for ln in lines)  # no result line off the TPU
    assert "rehearsal, not a chip run" in r.stderr
    for ln in lines[1:-1]:
        assert ln["cpu_routes_moved"] == {}
        assert ln["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
        assert any(v > 0 for v in ln["device_work"].values())
    by = {ln["phase"]: ln for ln in lines}
    assert by["kernels"]["pallas"] == {
        "direct": True, "via_keccak_device": True, "interpret": True}
    assert by["import"]["device_work"]["turbo_nodes"] > 0
    assert by["rebuild"]["cut"].startswith("--tiny")
    assert by["rebuild"]["device_work"]["fused_dispatches"] > 0
    assert by["done"]["compile_cache"]["dir"] == str(REPO / ".jax_cache")


def test_chip_smoke_chips4_rehearsal_on_four_virtual_devices():
    r = _smoke(["--tiny", "--chips", "4"], {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    assert [ln.get("phase") for ln in lines] == ["start", "mesh", "done"], \
        r.stderr[-2000:]
    assert r.returncode == 3
    mesh = lines[1]
    assert mesh["device"]["count"] == 4 and mesh["chips"] == 4
    assert mesh["sharded_level_inputs"] > 0
    assert mesh["cpu_routes_moved"] == {}


def test_chip_smoke_without_accelerator_fails_before_any_phase(tmp_path):
    """No TPU and no explicit JAX_PLATFORMS=cpu: non-zero exit at the
    platform check, no phase, no result line."""
    r = _smoke(["--tiny"], {}, timeout=300)
    assert r.returncode != 0
    assert "DeviceUnavailable" in r.stderr
    assert r.stdout.strip() == ""
    # alone in a directory, without the program: fails too
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    r = _smoke([], {"JAX_PLATFORMS": "cpu"}, cwd=tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "No module named 'reth_tpu'" in r.stderr
