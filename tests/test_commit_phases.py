"""The commit path seen from inside (ISSUE 26): each phase of a turbo commit
as a ``trie::commit`` span and a ``trie_commit_<phase>_seconds_total``
counter; ``tracing.span()`` on the profiler's clock; the fused engines' byte
and row counters against the plan's own arithmetic; every jitted program of
the commit path under its own module name; the compile tracker's shape key
with the digest tier in it."""

import gc
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reth_tpu import tracing
from reth_tpu.metrics import REGISTRY, TrieMetrics, compile_tracker
from reth_tpu.ops import fused_commit as fc
from reth_tpu.ops import keccak_jax
from reth_tpu.primitives.keccak import RATE
from reth_tpu.trie.turbo import TurboCommitter

ROOT = Path(__file__).resolve().parents[1]
PHASES = [f"trie_commit_{p}_seconds_total" for p in TrieMetrics.PHASES]
DECODE = ["trie_commit_decode_seconds_total",
          "trie_commit_decode_records_total"]
FUSED = ["fused_h2d_bytes_total", "fused_d2h_bytes_total",
         "fused_rows_dispatched_total", "fused_rows_needed_total"]


def _counters(names):
    return {n: REGISTRY.counter(n).value for n in names}


def _moved(before, names):
    after = _counters(names)
    return {n: after[n] - before[n] for n in names}


def _job(n, seed, prefix=None):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    if prefix is not None:
        keys[:, 0] = prefix
    vals = [bytes(rng.integers(1, 256, 70 + i % 9, dtype=np.uint8))
            for i in range(n)]
    return keys, vals


# -- phases -------------------------------------------------------------------


def test_every_phase_moves_on_the_serial_path_and_sums_within_the_wall():
    committer = TurboCommitter(backend="device", min_tier=8)
    jobs = [_job(3000, 1, prefix=0x5A)]
    committer.commit_hashed_many(jobs, collect_branches=True, start_depth=2)
    before = _counters(PHASES)
    t0 = time.perf_counter()
    committer.commit_hashed_many(jobs, collect_branches=True, start_depth=2)
    wall = time.perf_counter() - t0
    moved = _moved(before, PHASES)
    # "pack" too: one job is a window of one group, which merges nothing
    # and copies nothing but is still walked
    assert all(v > 0 for v in moved.values()), moved
    # one group is swept by the caller, so the phases follow one another on
    # one thread: no second is counted twice
    assert sum(moved.values()) <= wall


def test_every_phase_moves_on_the_pipelined_path_with_two_jobs(rebuild_layout):
    committer = TurboCommitter(backend="device", min_tier=8)
    jobs = [_job(2000, 2, prefix=0x10), _job(2000, 3, prefix=0x11)]
    rebuild_layout(LEAVES_PER_SWEEP=1)  # two groups: the sweep pool
    before = _counters(PHASES)
    t0 = time.perf_counter()
    res = committer.commit_hashed_pipelined(jobs, collect_branches=True,
                                            start_depth=2)
    wall = time.perf_counter() - t0
    moved = _moved(before, PHASES)
    assert all(v > 0 for v in moved.values()), moved
    # thread-seconds here: the sweeps overlap the consumer, so the bound is
    # the wall times the threads that can run a phase
    assert sum(moved.values()) <= wall * 5
    serial = committer.commit_hashed_many(jobs, collect_branches=True,
                                          start_depth=2)
    assert [r.root for r in res] == [r.root for r in serial]


def test_the_pipelined_decode_gives_the_serial_paths_branch_nodes(
        monkeypatch, rebuild_layout):
    """The chunk as ONE sweep group (what the serial path was: one decode
    call, ``slot_base`` 0), then as three, so two of the decode's calls
    rebase their records' slots into the shared arena (``slot_base`` above
    0)."""
    from reth_tpu.trie import turbo

    bases = []
    real = turbo._collect_meta_records

    def spy(*args, slot_base=0):
        bases.append(slot_base)
        return real(*args, slot_base=slot_base)

    monkeypatch.setattr(turbo, "_collect_meta_records", spy)
    committer = TurboCommitter(backend="numpy")
    jobs = [_job(n, 20 + i, prefix=0x40 + i)
            for i, n in enumerate((900, 1, 300, 1500, 40))]
    # the collector is held off over the two measured commits: its first
    # pass after the decode's own pause (``_collect_meta_records`` turns it
    # back on) would land between the span's clock and the counter's, and in
    # a worker that has run hundreds of tests that pass takes tens of ms
    gc.collect()
    gc.disable()
    tracing.set_trace_enabled(True)
    try:
        rec = tracing.flight_recorder()

        def commit(fn):
            before, n0 = _counters(DECODE), rec.recorded
            out = fn(jobs, collect_branches=True, start_depth=2)
            spans = [s for s in rec.snapshot()[-(rec.recorded - n0):]
                     if (s["target"], s["name"]) == ("trie::commit", "decode")]
            return out, _moved(before, DECODE), spans

        serial, s_moved, s_spans = commit(committer.commit_hashed_many)
        assert bases == [0]
        rebuild_layout(LEAVES_PER_SWEEP=901)  # (900, 1) (300, 1500) (40)
        piped, p_moved, p_spans = commit(committer.commit_hashed_pipelined)
    finally:
        tracing.set_trace_enabled(False)
        gc.enable()
    assert len(bases) == 4 and sorted(bases[1:])[0] == 0 < sorted(bases[1:])[1]
    n_records = sum(len(r.branch_nodes) for r in serial)
    assert n_records > 500
    for got, want in zip(piped, serial):
        assert got.root == want.root
        assert type(got.branch_nodes) is dict
        assert list(got.branch_nodes.items()) == list(want.branch_nodes.items())
    # one decode phase a commit, however many sweep groups it decodes
    for moved, spans in ((s_moved, s_spans), (p_moved, p_spans)):
        assert moved["trie_commit_decode_records_total"] == n_records
        assert len(spans) == 1
        assert moved["trie_commit_decode_seconds_total"] == pytest.approx(
            spans[0]["dur_ms"] / 1e3, rel=0.2, abs=2e-3)


def test_phase_is_a_span_and_counts_when_its_body_raises():
    tm = TrieMetrics()
    before = _counters(["trie_commit_decode_seconds_total"])
    tracing.set_trace_enabled(True)
    try:
        rec = tracing.flight_recorder()
        n0 = rec.recorded
        with pytest.raises(KeyError):
            with tm.phase("decode"):
                time.sleep(0.002)
                raise KeyError("x")
        assert rec.recorded == n0 + 1
        last = rec.snapshot()[-1]
        assert (last["target"], last["name"]) == ("trie::commit", "decode")
        assert last["error"] == "KeyError"
    finally:
        tracing.set_trace_enabled(False)
    assert _moved(before, list(before))[
        "trie_commit_decode_seconds_total"] >= 0.002


# -- span() on the profiler's clock -------------------------------------------


def test_span_lands_in_a_profiler_trace_as_a_host_event(tmp_path):
    from benchmark.harness import trace

    assert not tracing.trace_enabled()  # the annotation does not need it
    opts = jax.profiler.ProfileOptions()  # as benchmark/harness/tracing.py
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tracing.span("trie::commit", "probe_outer"):
            time.sleep(0.004)
            with tracing.span("engine::tree", "probe_inner"):
                jnp.arange(8).sum().block_until_ready()
                time.sleep(0.004)
    finally:
        jax.profiler.stop_trace()
    files = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert files
    events = trace.read_xplane(files[-1])
    (line,) = [evs for evs in events["host"].values()
               if any(e[0] == "trie::commit:probe_outer" for e in evs)]
    by_name = {e[0]: e for e in line}
    outer, inner = by_name["trie::commit:probe_outer"], by_name[
        "engine::tree:probe_inner"]
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]
    # the harness names an instant by the innermost span around it: 2 ms
    # into the outer span's sleep, 2 ms before the inner span's end
    host = trace._HostLine(line)
    assert host.innermost(outer[1] + 2e6)[0] == "trie::commit:probe_outer"
    assert host.innermost(inner[1] + inner[2] - 2e6)[0] == (
        "engine::tree:probe_inner")


def test_span_works_where_jax_was_never_imported():
    code = ("import sys\n"
            "from reth_tpu import tracing\n"
            "with tracing.span('trie::commit', 'probe') as ctx:\n"
            "    pass\n"
            "assert 'jax' not in sys.modules\n"
            "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


# -- bytes and rows -----------------------------------------------------------


@pytest.mark.parametrize("collect_branches", [True, False])
def test_bytes_and_rows_equal_the_plans_own_arithmetic(seen_plans,
                                                       collect_branches):
    committer = TurboCommitter(backend="device", min_tier=8)
    jobs = [_job(700, 7), _job(900, 8), _job(5, 9)]
    before = _counters(FUSED)
    committer.commit_hashed_many(jobs, collect_branches=collect_branches)
    moved = _moved(before, FUSED)
    (p,) = seen_plans
    u8_len, i32_len = p["lens"]
    arena = p["s_tier"] * 32
    staged = u8_len + 4 * i32_len + arena
    if collect_branches:     # the whole arena comes back
        assert moved["fused_h2d_bytes_total"] == staged
        assert moved["fused_d2h_bytes_total"] == arena
    else:                    # the roots alone: 3 slot ids in a tier of 8
        assert moved["fused_h2d_bytes_total"] == staged + 8 * 4
        assert moved["fused_d2h_bytes_total"] == 8 * 32
    # entry = (kind, [b_tier,] row tier, hole tier, ..., rows + 1, holes + 1)
    n_pow = [e[2] if e[0] == "packed" else e[1] for e in p["plan"]]
    assert moved["fused_rows_dispatched_total"] == sum(n_pow)
    assert moved["fused_rows_needed_total"] == sum(
        e[-2] - 1 for e in p["plan"])
    hashed = REGISTRY.counter("trie_commit_nodes_total_device").value
    assert moved["fused_rows_needed_total"] <= hashed
    assert 0 < moved["fused_rows_needed_total"] < sum(n_pow)


def test_subtrie_engine_counts_its_chunk_wide_row_tier():
    committer = TurboCommitter(backend="device", min_tier=8, subtrie_levels=4)
    jobs = [_job(600, 11)]
    before = _counters(FUSED + ["fused_dispatches_total", "fused_levels_total"])
    res = committer.commit_hashed_many(jobs, collect_branches=True)
    moved = _moved(before, list(before))
    assert moved["fused_rows_needed_total"] == res[-1].hashed_nodes
    # every staged level runs at its chunk's row tier, at least the floor
    assert (moved["fused_rows_dispatched_total"]
            >= fc.MegaFusedEngine._ROW_FLOOR * moved["fused_levels_total"])
    assert moved["fused_h2d_bytes_total"] > moved["fused_d2h_bytes_total"] > 0


# -- names --------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _module_name(fn, *args):
    text = fn.lower(*args).as_text()
    return text.split("module @", 1)[1].split()[0]


def _level_args(kind, n=16, b_tier=2, holes=8):
    i32 = _sds((n,), jnp.int32)
    h = _sds((holes,), jnp.int32)
    buf = _sds((64, 32), jnp.uint8)
    rows = _sds((n, b_tier * RATE), jnp.uint8)
    return {
        "plain": (rows, i32, i32, buf),
        "splice": (rows, i32, h, h, h, i32, buf),
        "packed": (_sds((4096,), jnp.uint8), _sds((n,), jnp.uint32),
                   _sds((n,), jnp.uint32), i32, h, h, h, i32, buf),
        "branch": (i32, i32, h, h, h, buf),
    }[kind]


def _staged(n_scalars, u8_len=1 << 16, i32_len=1 << 12, s_tier=1024):
    return ((_sds((u8_len,), jnp.uint8), _sds((i32_len,), jnp.int32),
             _sds((s_tier, 32), jnp.uint8))
            + (_sds((), jnp.int32),) * n_scalars)


PROGRAMS = {
    "level_plain": lambda: (fc._jitted("plain", 2), _level_args("plain")),
    "level_splice": lambda: (fc._jitted("splice", 2), _level_args("splice")),
    "level_packed": lambda: (fc._jitted("packed", 2), _level_args("packed")),
    "level_branch": lambda: (fc._jitted("branch", 4),
                             _level_args("branch", b_tier=4)),
    "mega_packed": lambda: (
        fc._staged_packed(1, 2048, 2048, 1 << 16, 1 << 12, 1024), _staged(7)),
    "mega_branch": lambda: (
        fc._staged_branch(2048, 2048, 1 << 16, 1 << 12, 1024), _staged(6)),
    "subtrie_chunk": lambda: (
        fc._subtrie_program(4, 2048, 2048, 8, 1 << 16, 1 << 12, 1024, None),
        (_sds((1 << 16,), jnp.uint8), _sds((1 << 12,), jnp.int32),
         _sds((8, fc._PARAM_W), jnp.int32), _sds((1024, 32), jnp.uint8),
         _sds((), jnp.int32))),
    "keccak256_jax_words": lambda: (
        keccak_jax.keccak256_jax_words, (_sds((8, 68), jnp.uint32), 2)),
    "keccak256_jax_words_masked": lambda: (
        keccak_jax.keccak256_jax_words_masked,
        (_sds((8, 68), jnp.uint32), 2, _sds((8,), jnp.int32))),
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_each_program_lowers_to_a_module_of_its_own_name(name):
    fn, args = PROGRAMS[name]()
    assert _module_name(fn, *args) == f"jit_{name}"


# -- the compile tracker sees the digest tier ---------------------------------


def test_a_commit_in_another_digest_tier_counts_new_shapes():
    def commit(max_slots):
        eng = fc.MegaFusedEngine(min_tier=8)
        eng.begin(max_slots)
        row = np.frombuffer(bytes(range(40)), dtype=np.uint8)
        eng.dispatch_packed(np.concatenate([row, row]),
                            np.array([0, 40], np.uint32),
                            np.array([40, 40], np.uint32),
                            np.array([1, 2], np.int32), None, 1)
        eng.dispatch_branch(np.array([0b11], np.uint16),
                            np.array([3], np.int32),
                            np.array([[0, 0], [0, 1], [1, 2]], np.int32))
        return eng.finish()

    small = commit(100)           # arena tier 128
    before = set(compile_tracker.shapes)
    again = commit(100)
    assert set(compile_tracker.shapes) == before
    large = commit(5000)          # the same plan, arena tier 8192
    minted = set(compile_tracker.shapes) - before
    assert {k[0] for k in minted} == {"mega.packed", "mega.branch"}
    assert all(k[-1] == 8192 for k in minted)
    assert small.shape == (128, 32) and large.shape == (8192, 32)
    assert (small[1:4] == again[1:4]).all() and (small[1:4] == large[1:4]).all()
