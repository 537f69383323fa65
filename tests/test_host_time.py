"""The host's seconds that no phase owns, named where they happen (ISSUE 38):
the cyclic collector's passes (a ``gc.callbacks`` hook: span ``python::gc``,
``python_gc_*`` counters, the phase each pass interrupted), the phases' time
on the CPU (``cpu_s``, ``trie_commit_marshal_cpu_seconds_total``), the
consumer's wait as a span, and each program's dispatch call (span
``ops::dispatch:<kind>``, ``keccak_dispatch_seconds_total``); the pipeline's
second set of stage timers is gone."""

import gc
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reth_tpu import tracing
from reth_tpu.metrics import (
    REGISTRY, TrieMetrics, gc_metrics, pipeline_metrics, trie_metrics)
from reth_tpu.ops import fused_commit as fc
from reth_tpu.trie.turbo import TurboCommitter

ROOT = Path(__file__).resolve().parents[1]
GC = ["python_gc_seconds_total", "python_gc_full_seconds_total"] + [
    f"python_gc_passes_total_gen{g}" for g in range(3)]
MARSHAL = ["trie_commit_marshal_seconds_total",
           "trie_commit_marshal_cpu_seconds_total"]
DISPATCH = ["keccak_dispatch_total", "keccak_dispatch_seconds_total"]


def _counters(names):
    return {n: REGISTRY.counter(n).value for n in names}


def _moved(before):
    after = _counters(list(before))
    return {n: after[n] - before[n] for n in before}


def _job(n, seed, prefix=None):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    if prefix is not None:
        keys[:, 0] = prefix
    vals = [bytes(rng.integers(1, 256, 70 + i % 9, dtype=np.uint8))
            for i in range(n)]
    return keys, vals


def _recorded(fn):
    """Run ``fn`` with span recording on; returns (its result, the records
    it left in the flight recorder)."""
    tracing.set_trace_enabled(True)
    try:
        rec = tracing.flight_recorder()
        n0 = rec.recorded
        out = fn()
        snap = rec.snapshot()  # a collector pass's span lands here at last
        got = snap[len(snap) - (rec.recorded - n0):]
    finally:
        tracing.set_trace_enabled(False)
    return out, got


def _spans(records, target, name=None):
    return [r for r in records if r["kind"] == "span" and r["target"] == target
            and (name is None or r["name"] == name)]


# -- the collector's passes ----------------------------------------------------


@pytest.mark.parametrize("phase", ["marshal", None])
def test_a_full_pass_is_counted_and_names_the_phase_it_interrupted(phase):
    before = _counters(GC + MARSHAL)

    def run():
        if phase is None:
            gc.collect(2)
            return
        with trie_metrics.phase(phase):
            gc.collect(2)

    _, records = _recorded(run)
    moved = _moved(before)
    assert moved["python_gc_passes_total_gen2"] >= 1
    assert 0 < moved["python_gc_full_seconds_total"] <= moved[
        "python_gc_seconds_total"]
    passes = [s for s in _spans(records, "python", "gc")
              if s["fields"]["generation"] == 2]
    assert passes and all(s["fields"]["during"] == phase for s in passes)
    assert all(s["fields"]["collected"] >= 0 for s in passes)
    if phase is not None:
        # the pass lies inside the phase's span, and inside its counter
        (outer,) = _spans(records, "trie::commit", phase)
        for s in passes:
            assert outer["ts"] <= s["ts"] + 1e-3
            assert s["ts"] + s["dur_ms"] / 1e3 <= (
                outer["ts"] + outer["dur_ms"] / 1e3 + 1e-3)
        assert moved["python_gc_full_seconds_total"] <= moved[
            "trie_commit_marshal_seconds_total"]
    # the phase is the thread's only while it is open
    assert tracing.current_phase() is None


def test_a_pass_is_no_record_with_tracing_off_but_still_counted():
    before = _counters(GC)
    rec = tracing.flight_recorder()
    n0 = rec.recorded
    assert not tracing.trace_enabled()
    with trie_metrics.phase("decode"):
        gc.collect(1)
    assert rec.recorded == n0
    moved = _moved(before)
    assert moved["python_gc_passes_total_gen1"] >= 1
    assert moved["python_gc_seconds_total"] > 0


def test_a_collector_pass_lands_in_a_profiler_trace_as_a_host_event(tmp_path):
    from benchmark.harness import trace

    assert not tracing.trace_enabled()  # the annotation does not need it
    opts = jax.profiler.ProfileOptions()  # as benchmark/harness/tracing.py
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tracing.span("trie::commit", "probe_gc"):
            jnp.arange(8).sum().block_until_ready()
            time.sleep(0.002)
            gc.collect(2)
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    files = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert files
    events = trace.read_xplane(files[-1])
    (line,) = [evs for evs in events["host"].values()
               if any(e[0] == "trie::commit:probe_gc" for e in evs)]
    outer = next(e for e in line if e[0] == "trie::commit:probe_gc")
    passes = [e for e in line if e[0] == "python::gc"
              and outer[1] <= e[1] and e[1] + e[2] <= outer[1] + outer[2]]
    assert passes
    # the harness names an instant inside the pass by the pass
    host = trace._HostLine(line)
    mid = passes[0][1] + passes[0][2] / 2
    assert host.innermost(mid)[0] == "python::gc"


_HOOK_ONCE = """
import gc, importlib
before = (gc.get_threshold(), gc.isenabled(), gc.get_freeze_count())
import reth_tpu.metrics as m
import reth_tpu.metrics
m = importlib.reload(m)
hooks = [cb for cb in gc.callbacks if type(cb).__module__ == m.__name__]
assert len(hooks) == 1 and hooks[0] is m.gc_metrics, gc.callbacks
# the hook only looks: the collector's thresholds, state and frozen objects
# are what they were before the module was imported
assert (gc.get_threshold(), gc.isenabled(), gc.get_freeze_count()) == before
gc.collect()
assert m.REGISTRY.counter("python_gc_passes_total_gen2").value == 1
print("ok")
"""


def test_the_hook_is_installed_once_however_often_metrics_is_imported():
    hooks = [cb for cb in gc.callbacks
             if type(cb).__module__ == "reth_tpu.metrics"]
    assert hooks == [gc_metrics]
    out = subprocess.run([sys.executable, "-c", _HOOK_ONCE], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


# -- the phases' time on the CPU, the consumer's wait ---------------------------


def test_marshal_cpu_moves_by_no_more_than_its_wall(rebuild_layout):
    committer = TurboCommitter(backend="numpy")
    jobs = [_job(1500, 30 + i, prefix=0x20 + i) for i in range(4)]
    rebuild_layout(LEAVES_PER_SWEEP=1)  # four groups: marshal on the pool
    before = _counters(MARSHAL)
    _, records = _recorded(lambda: committer.commit_hashed_pipelined(
        jobs, collect_branches=True, start_depth=2))
    moved = _moved(before)
    cpu, wall = (moved["trie_commit_marshal_cpu_seconds_total"],
                 moved["trie_commit_marshal_seconds_total"])
    assert 0 < cpu <= wall
    phases = _spans(records, "trie::commit")
    assert {s["name"] for s in phases} >= {"marshal", "sweep", "decode"}
    for s in phases:
        assert 0 <= s["fields"]["cpu_s"] <= s["dur_ms"] / 1e3 + 1e-3
    # the consumer's blocking wait for each sweep in order is a span
    waits = _spans(records, "trie::pipeline", "wait")
    assert len(waits) == 4
    (run,) = _spans(records, "trie::pipeline", "rebuild")
    assert run["fields"]["wait"] == pytest.approx(
        sum(w["dur_ms"] for w in waits) / 1e3, abs=5e-3)
    assert 0 <= run["fields"]["gc_full_s"] <= run["fields"]["gc_s"]


def test_the_stage_timers_are_retired_and_last_holds_the_phases(
        rebuild_layout):
    committer = TurboCommitter(backend="numpy")
    jobs = [_job(400, 50 + i) for i in range(3)]
    rebuild_layout(LEAVES_PER_SWEEP=1)
    names = dict(REGISTRY.items())
    committer.commit_hashed_pipelined(jobs)
    after = dict(REGISTRY.items())
    for k in ("sweep", "pack", "dispatch", "fetch"):
        assert f"trie_pipeline_{k}_seconds_total" not in names
        assert f"trie_pipeline_{k}_seconds_total" not in after
    assert "trie_pipeline_wait_seconds_total" in after
    last = pipeline_metrics.last
    assert set(last["phases"]) == set(TrieMetrics.PHASES)
    # the run's phases are its counters' movement: decode did not run
    assert last["phases"]["decode"] == 0.0 and last["phases"]["marshal"] > 0
    assert {"wait_s", "gc_s", "gc_full_s"} <= set(last)


def test_the_events_line_prints_the_phases_under_their_names(rebuild_layout):
    from reth_tpu.node.events import NodeEventReporter
    from reth_tpu.primitives import Account
    from reth_tpu.primitives.keccak import keccak256_batch_np
    from reth_tpu.testing import ChainBuilder, Wallet
    from reth_tpu.trie import TrieCommitter

    rebuild_layout(LEAVES_PER_SWEEP=1)
    TurboCommitter(backend="numpy").commit_hashed_pipelined(
        [_job(300, 60), _job(300, 61)], collect_branches=True)
    alice = Wallet(0xA11CE)
    builder = ChainBuilder({alice.address: Account(balance=10**21)},
                           committer=TrieCommitter(hasher=keccak256_batch_np))
    builder.build_block([alice.transfer(b"\x0b" * 20, 5)])
    rep = NodeEventReporter(SimpleNamespace(pool=None, network=None),
                            interval=999)
    rep.on_canon_change([SimpleNamespace(block=builder.blocks[1])])
    line = rep.report_once()
    frag = line[line.index(" rebuild["):]
    frag = frag[:frag.index("]") + 1]
    for name in ("wait=", "marshal+sweep=", "stage..enqueue=", "predecode=",
                 "device_wait=", "decode=", "gc="):
        assert f" {name}" in frag, frag
    for retired in (" sweep=", " pack=", " disp=", " fetch="):
        assert retired not in frag, frag


# -- each program's dispatch call ----------------------------------------------


def _mega_shapes():
    from reth_tpu.metrics import compile_tracker

    return {k: v for k, v in compile_tracker.shapes.items()
            if k[0].startswith("mega.")}


def test_a_mega_commit_times_each_steady_call_and_spans_it():
    committer = TurboCommitter(backend="device", min_tier=8)
    jobs = [_job(900, 70), _job(700, 71)]
    committer.commit_hashed_many(jobs, collect_branches=True)  # builds
    before = _counters(DISPATCH)
    shapes0 = {k: dict(v) for k, v in _mega_shapes().items()}
    t0 = time.perf_counter()
    _, records = _recorded(lambda: committer.commit_hashed_many(
        jobs, collect_branches=True))
    wall = time.perf_counter() - t0
    moved = _moved(before)
    calls = _spans(records, "ops::dispatch")
    assert calls and {s["name"] for s in calls} == {"mega.packed",
                                                    "mega.branch"}
    # one steady call, one count and its wall; the span holds the shape key
    assert moved["keccak_dispatch_total"] == len(calls)
    assert 0 < moved["keccak_dispatch_seconds_total"] <= wall
    steady = sum(v["execute_s"] - shapes0.get(k, {"execute_s": 0.0})["execute_s"]
                 for k, v in _mega_shapes().items())
    assert moved["keccak_dispatch_seconds_total"] == pytest.approx(
        steady, abs=1e-4)
    keys = {str(k[1:]) for k in _mega_shapes()}
    assert {s["fields"]["shape"] for s in calls} <= keys


def test_a_dispatch_call_lands_in_a_profiler_trace_as_a_host_event(tmp_path):
    from benchmark.harness import trace

    eng = fc.MegaFusedEngine(min_tier=8)
    row = np.frombuffer(bytes(range(40)), dtype=np.uint8)

    def commit():
        eng.begin(100)
        eng.dispatch_packed(np.concatenate([row, row]),
                            np.array([0, 40], np.uint32),
                            np.array([40, 40], np.uint32),
                            np.array([1, 2], np.int32), None, 1)
        eng.dispatch_branch(np.array([0b11], np.uint16),
                            np.array([3], np.int32),
                            np.array([[0, 0], [0, 1], [1, 2]], np.int32))
        return eng.finish()

    commit()  # builds both programs outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        commit()
    finally:
        jax.profiler.stop_trace()
    files = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    events = trace.read_xplane(files[-1])
    names = {e[0] for evs in events["host"].values() for e in evs}
    assert {"ops::dispatch:mega.packed", "ops::dispatch:mega.branch"} <= names
