"""The rebuild pipeline (trie/turbo.py RebuildPipeline), the one path of
every turbo commit: parity, packing, arena residency, fault drills, the
threaded native sweep, and the one-group chunk.

However a chunk is laid out, as many sweep groups and windows or as ONE
group swept by the caller, the answers are bit-identical: pooled
`native/triebuild.cpp` sweeps + cross-subtrie level packing + resident
digest arena may change WHEN rows hash, never WHAT they hash. Roots and
TrieUpdates branch metadata are pinned against the same chunk as one group
(the layout of every chunk at the program's constants below 32,768
leaves; ``commit_hashed_many`` is the same call by its older name)
and against the plain reference (``benchmark/reference/mpt.py``).
"""

from __future__ import annotations

import itertools
import os
import subprocess
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import pytest

from reth_tpu.ops import fused_commit as fc
from reth_tpu.primitives.rlp import rlp_encode
from reth_tpu.trie import turbo
from reth_tpu.trie.turbo import (
    DigestArena,
    RebuildPipeline,
    TurboCommitter,
    _group_jobs,
    _NumpyBackend,
    _pack_window,
    _sweep_group,
)

NATIVE = Path(__file__).resolve().parent.parent / "native"


def _job(n, seed, val_len=(1, 100)):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    keys = np.unique(keys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
    rng.shuffle(keys)
    values = [
        rlp_encode(bytes(rng.integers(0, 256, size=int(rng.integers(*val_len)),
                                      dtype=np.uint8)))
        for _ in range(len(keys))
    ]
    return keys, values


def _prefix_jobs(n, seed):
    """Merkle-chunk-shaped jobs: the account trie split into two-nibble
    prefix subtries, committed at start_depth=2 (_account_chunk's shape)."""
    keys, values = _job(n, seed)
    jobs = []
    for pfx in np.unique(keys[:, 0]):
        sel = np.nonzero(keys[:, 0] == pfx)[0]
        jobs.append((keys[sel], [values[i] for i in sel]))
    return jobs


@pytest.fixture(scope="module")
def turbo_np():
    return TurboCommitter(backend="numpy")


# -- parity ------------------------------------------------------------------


@pytest.mark.parametrize("layout", [
    dict(LEAVES_PER_SWEEP=1, PACK_WINDOW=1),     # a group a job, no packing
    dict(LEAVES_PER_SWEEP=400, PACK_WINDOW=16),  # grouped sweeps, wide packs
    dict(LEAVES_PER_SWEEP=200, PACK_WINDOW=2),   # smaller groups, windows
])
def test_pipelined_root_and_branch_parity(turbo_np, rebuild_layout, layout):
    jobs = [_job(30 + 17 * i, seed=i) for i in range(12)]
    want = turbo_np.commit_hashed_many(jobs, collect_branches=True)  # 1 group
    rebuild_layout(**layout)
    got = turbo_np.commit_hashed_pipelined(jobs, collect_branches=True)
    assert [r.root for r in got] == [r.root for r in want]
    for g, w in zip(got, want):
        assert g.branch_nodes == w.branch_nodes


def test_pipelined_subtrie_start_depth_parity(turbo_np, rebuild_layout):
    """The chunked Merkle rebuild's exact call shape: prefix subtries at
    start_depth=2, branch paths subtrie-relative."""
    jobs = _prefix_jobs(600, seed=7)
    want = [turbo_np.commit_hashed_many([j], collect_branches=True,
                                        start_depth=2)[0] for j in jobs]
    rebuild_layout(LEAVES_PER_SWEEP=20)      # ~8 prefix subtries a group
    got = turbo_np.commit_hashed_pipelined(jobs, collect_branches=True,
                                           start_depth=2)
    assert [r.root for r in got] == [r.root for r in want]
    for g, w in zip(got, want):
        assert g.branch_nodes == w.branch_nodes


def test_pipelined_empty_and_single(turbo_np):
    from reth_tpu.primitives.types import EMPTY_ROOT_HASH

    assert turbo_np.commit_hashed_pipelined([]) == []
    # one job: one group, swept by the caller
    one = turbo_np.commit_hashed_pipelined([_job(40, seed=3)])
    assert one[0].root == turbo_np.commit_hashed_many([_job(40, seed=3)])[0].root
    mixed = turbo_np.commit_hashed_pipelined(
        [(np.zeros((0, 32), dtype=np.uint8), []), _job(5, seed=1)])
    assert mixed[0].root == EMPTY_ROOT_HASH


def test_pipelined_rejects_like_serial(turbo_np, rebuild_layout):
    """Oversized leaf values reject in the sweep — the same ValueError the
    MerkleStage catches to fall back to the general committer — whether
    the caller swept the group or a pool thread did."""
    keys, values = _job(8, seed=2)
    values[3] = b"\xb9\xff\xff" + bytes(65535)  # > native leaf cap
    with pytest.raises(ValueError, match="oversized"):
        turbo_np.commit_hashed_pipelined([(keys, values)])
    rebuild_layout(LEAVES_PER_SWEEP=1)
    with pytest.raises(ValueError, match="oversized"):
        turbo_np.commit_hashed_pipelined([(keys, values), _job(10, seed=4)])


# -- a group marshalled in one piece ------------------------------------------
#
# A group of several jobs is sorted once, by (job number, key), and its values
# are passed over once. What the native sweep is handed is what the sort a job
# gave it: the keys, the value blob, ``val_off`` and ``job_off``.


def _per_job_marshal(jobs):
    """The marshal as it was before a group was marshalled in one piece: one
    stable sort and one value reorder a job, kept here as the reference."""
    key_arrays, values, job_off = [], [], [0]
    for keys, vals in jobs:
        keys = np.ascontiguousarray(keys, dtype=np.uint8).reshape(-1, 32)
        order = np.argsort(keys.view("S32").ravel(), kind="stable")
        key_arrays.append(keys[order])
        values.extend(vals[i] for i in order)
        job_off.append(job_off[-1] + len(keys))
    return np.concatenate(key_arrays), values, job_off


def _blob_and_offsets(values):
    return b"".join(values), np.cumsum([0] + [len(v) for v in values])


def _sorted_job(job):
    keys, values = job
    order = np.argsort(keys.view("S32").ravel())
    return keys[order], [values[i] for i in order]


def _marshal_cases():
    jobs = [_job(n, seed=500 + n) for n in (40, 1, 7, 300, 2)]
    shared = [(jobs[0][0][:5].copy(), [b"\x05"] * 5), jobs[0], jobs[2]]
    empty = (np.zeros((0, 32), dtype=np.uint8), [])
    return {
        "unsorted": jobs,
        "sorted": [_sorted_job(j) for j in jobs],
        "sorted_then_not": [_sorted_job(j) for j in jobs[:3]] + jobs[3:],
        "an_empty_job_inside": jobs[:2] + [empty] + jobs[2:] + [empty],
        "only_empty_jobs": [empty, empty],
        "one_key_in_two_jobs": shared,
    }


@pytest.mark.parametrize("case", list(_marshal_cases()))
def test_the_group_marshal_equals_the_per_job_marshal(case):
    jobs = _marshal_cases()[case]
    want_keys, want_values, want_off = _per_job_marshal(jobs)
    keys, values, counts = turbo._marshal_group(jobs)
    assert keys.dtype == np.uint8 and keys.flags.c_contiguous
    assert np.array_equal(keys, want_keys)
    assert [0] + np.cumsum(counts).tolist() == want_off
    assert values == want_values and type(values) is list
    blob, val_off = _blob_and_offsets(values)
    want_blob, want_val_off = _blob_and_offsets(want_values)
    assert blob == want_blob and np.array_equal(val_off, want_val_off)
    if case == "one_key_in_two_jobs":
        # job 0's five keys are all in job 1 too: no duplicate
        assert ({bytes(k) for k in keys[:5]}
                < {bytes(k) for k in keys[5:45]})


@pytest.mark.parametrize("case", ["unsorted", "sorted", "an_empty_job_inside",
                                  "one_key_in_two_jobs"])
def test_a_group_in_one_piece_commits_to_the_plain_reference(turbo_np, case):
    from reth_tpu.metrics import pipeline_metrics

    jobs = _marshal_cases()[case]
    results = turbo_np.commit_hashed_pipelined(jobs, collect_branches=True)
    assert pipeline_metrics.last["groups"] == 1
    _assert_equals_the_plain_reference(jobs, results, 0)


@pytest.fixture
def marshal_spy(monkeypatch):
    """How many jobs each marshal was handed."""
    seen = {"one": 0, "group": []}
    real_one, real_group = turbo._marshal_one, turbo._marshal_group

    def one(keys, values):
        seen["one"] += 1
        return real_one(keys, values)

    def group(jobs):
        seen["group"].append(len(jobs))
        return real_group(jobs)

    monkeypatch.setattr(turbo, "_marshal_one", one)
    monkeypatch.setattr(turbo, "_marshal_group", group)
    return seen


def test_a_group_of_one_job_takes_the_path_it_took(turbo_np, marshal_spy,
                                                   rebuild_layout):
    """Chosen by the number of jobs in the group, which the code sees in its
    input: one job is sorted by its own ``argsort`` of ``S32`` with no job
    column (the account cells' chunks), several are one piece."""
    jobs = [_job(50, seed=700 + i) for i in range(3)]
    turbo_np.commit_hashed_pipelined(jobs[:1], collect_branches=True)
    assert marshal_spy == {"one": 1, "group": []}
    turbo_np.commit_hashed_pipelined(jobs)           # one group of three
    assert marshal_spy == {"one": 1, "group": [3]}
    rebuild_layout(LEAVES_PER_SWEEP=1)               # a group a job
    turbo_np.commit_hashed_pipelined(jobs)
    assert marshal_spy == {"one": 4, "group": [3]}
    rebuild_layout(LEAVES_PER_SWEEP=100)             # (50, 50) (50)
    turbo_np.commit_hashed_pipelined(jobs)
    assert marshal_spy == {"one": 5, "group": [3, 2]}


def _with_a_duplicate(job):
    keys, values = job
    return np.concatenate([keys, keys[3:4]]), values + [b"\x01"]


@pytest.mark.parametrize("fault,match", [
    (_with_a_duplicate, "duplicate keys"),
    (lambda job: (job[0], job[1][:-1]), "length mismatch"),
    (lambda job: (job[0], [b"\xb9\xff\xff" + bytes(65535)] + job[1][1:]),
     "oversized"),
])
@pytest.mark.parametrize("where", [0, 1, 2])
def test_a_group_rejects_what_a_job_rejects(turbo_np, fault, match, where):
    """A duplicate inside ONE job of a group, a keys/values mismatch and an
    oversized value are the ``ValueError`` they were, wherever in the group
    the job sits."""
    jobs = [_job(12, seed=800 + i) for i in range(3)]
    jobs[where] = fault(jobs[where])
    with pytest.raises(ValueError, match=match):
        turbo_np.commit_hashed_pipelined(jobs)
    with pytest.raises(ValueError, match=match):
        turbo_np.commit_hashed_pipelined([jobs[where]])


# -- grouping / packing ------------------------------------------------------


def test_group_jobs_bounds():
    jobs = [(None, [b""] * n) for n in (10, 10, 10, 50, 5, 5)]
    # the leaf bound closes a group at the job that reaches it
    assert _group_jobs(jobs, max_leaves=20) == [(0, 2), (2, 4), (4, 6)]
    assert _group_jobs(jobs, max_leaves=60) == [(0, 4), (4, 6)]
    # a bound of 1 is a job a group; an empty job goes with the next one
    assert _group_jobs(jobs, max_leaves=1) == [(i, i + 1) for i in range(6)]
    assert _group_jobs([(None, [])] + jobs[:2], max_leaves=1) == [
        (0, 2), (2, 3)]
    assert _group_jobs(jobs, max_leaves=10**9) == [(0, 6)]
    assert _group_jobs([], 100) == []
    # and nothing else closes one: the number of tries costs nothing
    tiny = [(None, [b""])] * 40000
    assert _group_jobs(tiny, turbo.LEAVES_PER_SWEEP) == [
        (0, 32768), (32768, 40000)]


def test_pipeline_metrics_recorded(turbo_np, rebuild_layout):
    from reth_tpu.metrics import pipeline_metrics

    jobs = [_job(30, seed=40 + i) for i in range(8)]
    rebuild_layout(LEAVES_PER_SWEEP=60)      # two jobs of 30 a group
    turbo_np.commit_hashed_pipelined(jobs)
    last = pipeline_metrics.last
    assert last is not None
    assert last["jobs"] == 8 and last["groups"] == 4
    assert last["windows"] >= 1 and last["backend"] == "numpy"
    assert last["queue_peak"] >= 1 and last["drained_windows"] == 0
    for k in ("sweep_s", "pack_s", "dispatch_s", "fetch_s"):
        assert last[k] >= 0.0


# -- resident digest arena ---------------------------------------------------


def test_arena_resident_across_commits():
    arena = DigestArena()
    b = _NumpyBackend(arena=arena)
    b.begin(100)
    first = b._buf
    assert first is arena.digest_buf(1)      # backend writes the arena buf
    b.ensure(50)
    assert b._buf is first                   # within capacity: no realloc
    b.ensure(5000)
    grown = b._buf
    assert grown.shape[0] >= 5001 and arena.grows == 1
    b2 = _NumpyBackend(arena=arena)          # next commit, same arena
    b2.begin(100)
    assert b2._buf is grown                  # resident: reused, not realloc'd


def test_arena_growth_preserves_digests():
    arena = DigestArena()
    b = _NumpyBackend(arena=arena)
    b.begin(10)
    s = b.alloc_slot()
    b._buf[s] = 0xAB
    b.ensure(100_000)
    assert bytes(b._buf[s]) == b"\xab" * 32


def test_arena_rows_thread_local():
    import threading

    arena = DigestArena()
    bufs = {}

    def grab(name):
        r = arena.rows(4, 16)
        r[:] = 1
        bufs[name] = arena.rows(4, 16)

    t = threading.Thread(target=grab, args=("worker",))
    t.start(); t.join()
    grab("main")
    assert bufs["main"].base is not bufs["worker"].base  # never shared


# -- fault drills ------------------------------------------------------------


def test_injected_pipeline_abort(turbo_np, monkeypatch, rebuild_layout):
    """RETH_TPU_FAULT_PIPELINE_ABORT kills the commit at a window boundary
    — the in-process crash-mid-queue drill the resume test builds on."""
    from reth_tpu.ops.supervisor import InjectedPipelineAbort

    jobs = [_job(20, seed=60 + i) for i in range(8)]
    want = turbo_np.commit_hashed_many(jobs)    # one group, one window
    monkeypatch.setenv("RETH_TPU_FAULT_PIPELINE_ABORT", "2")
    rebuild_layout(LEAVES_PER_SWEEP=1, PACK_WINDOW=1)
    with pytest.raises(InjectedPipelineAbort, match="window #2"):
        turbo_np.commit_hashed_pipelined(jobs)
    # the wounded committer must still complete the next (clean) commit
    monkeypatch.delenv("RETH_TPU_FAULT_PIPELINE_ABORT")
    rebuild_layout(PACK_WINDOW=16)
    got = turbo_np.commit_hashed_pipelined(jobs)
    assert [r.root for r in got] == [r.root for r in want]


def test_mid_pipeline_failover_drains_onto_cpu(rebuild_layout):
    """Wedge every device dispatch under the supervised ('auto') route: the
    pipeline keeps feeding the failed-over backend, the queue drains onto
    the numpy twin, and the roots still match the oracle."""
    from reth_tpu.metrics import MetricsRegistry, pipeline_metrics
    from reth_tpu.ops.supervisor import DeviceSupervisor, FaultInjector, ProbeResult

    def probe(budget, injector=None):
        return ProbeResult(True, 0.001, None)

    sup = DeviceSupervisor(dispatch_budget=120.0, probe_fn=probe,
                           registry=MetricsRegistry(),
                           injector=FaultInjector(wedge_every=1))
    auto = TurboCommitter(backend="auto", min_tier=64, supervisor=sup)
    jobs = [_job(40, seed=80 + i) for i in range(10)]
    want = TurboCommitter(backend="numpy").commit_hashed_many(jobs)
    rebuild_layout(LEAVES_PER_SWEEP=80)      # two jobs of 40 a group
    got = auto.commit_hashed_pipelined(jobs)
    assert [r.root for r in got] == [r.root for r in want]
    assert sup.failovers >= 1
    last = pipeline_metrics.last
    assert last["backend"] == "numpy"        # effective plane after the trip
    assert last["drained_windows"] >= 1      # windows hashed post-failover


# -- threaded native sweep under a sanitizer ---------------------------------


def _probe_tsan(tmp: Path) -> bool:
    """gcc-12's libtsan SEGVs on 6.18+ kernels; probe before trusting it."""
    probe = tmp / "probe.cpp"
    probe.write_text("#include <thread>\nint main(){std::thread t([]{});"
                     "t.join();return 0;}\n")
    exe = tmp / "probe"
    r = subprocess.run(["g++", "-std=c++17", "-fsanitize=thread",
                        str(probe), "-o", str(exe)], capture_output=True)
    if r.returncode != 0:
        return False
    r = subprocess.run([str(exe)], capture_output=True, timeout=60)
    return r.returncode == 0


@pytest.mark.slow
def test_triebuild_threaded_stress(tmp_path):
    """The pipeline calls rtb_build from a thread pool, and rtb_build
    sweeps a large job on threads of its own: run the real access pattern
    (shared read-only arrays, concurrent handles, threads inside threads)
    under TSAN (ASan+UBSan where libtsan breaks on the running kernel) and
    require every round's arrays to be the one-thread sweep's —
    native/triebuild_tsan.cpp."""
    use_tsan = _probe_tsan(tmp_path)
    san = "thread" if use_tsan else "address,undefined"
    exe = tmp_path / "triebuild_stress"
    r = subprocess.run(
        ["g++", "-std=c++17", "-O1", "-g", f"-fsanitize={san}", "-pthread",
         str(NATIVE / "triebuild.cpp"), str(NATIVE / "triebuild_tsan.cpp"),
         "-o", str(exe)],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    env = {"TSAN_OPTIONS": "halt_on_error=1",
           "ASAN_OPTIONS": "halt_on_error=1", "PATH": "/usr/bin:/bin"}
    r = subprocess.run([str(exe)], capture_output=True, text=True,
                       timeout=300, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    assert "STRESS_OK" in r.stdout


def test_pipeline_concurrent_sweeps_deterministic(turbo_np, rebuild_layout):
    """Python-level rerun determinism: many small groups racing through the
    pool must always produce the same roots."""
    jobs = [_job(15, seed=200 + i) for i in range(16)]
    rebuild_layout(LEAVES_PER_SWEEP=1, PACK_WINDOW=2)
    runs = [
        [r.root for r in turbo_np.commit_hashed_pipelined(jobs)]
        for _ in range(3)
    ]
    assert runs[0] == runs[1] == runs[2]


# -- steady program shapes: a function of the chunk, not of thread timing ----
#
# For a given chunk (the job list and start_depth) the compile-tracker keys
# the commit asks for, the arena's tier, the staged buffer lengths and the
# number of windows are the same whatever order the sweep threads finish in
# and whatever chunk came before.

# one sweep group a job (as 125,000-leaf subtries are at the program's
# LEAVES_PER_SWEEP), every sweep in flight at once
_STEADY_LAYOUT = dict(LEAVES_PER_SWEEP=200, SWEEP_THREADS=4)
_CHUNK_A = (700, 260, 420, 330)
_CHUNK_B = (1500, 240, 900)
_ORDERS = list(itertools.permutations(range(4)))


def _chunk(sizes, seed, prefix0=0x30):
    """Account-chunk-shaped jobs: job k's keys under the prefix byte
    ``prefix0 + k`` (committed at start_depth=2)."""
    jobs = []
    for k, n in enumerate(sizes):
        keys, values = _job(n, seed + k, val_len=(60, 80))
        keys[:, 0] = prefix0 + k
        jobs.append((keys, values))
    return jobs


def _assert_equals_the_plain_reference(jobs, results, start_depth):
    from benchmark.reference.mpt import build_trie

    for (keys, values), got in zip(jobs, results):
        order = np.argsort(keys.view("S32").ravel())
        ref = build_trie(keys[order], [values[i] for i in order], start_depth)
        assert got.root == ref.root
        plain = {bytes(p): (b.state_mask, b.tree_mask, b.hash_mask,
                            tuple(b.hashes))
                 for p, b in got.branch_nodes.items()}
        assert plain == ref.branches


def _plant_completion_order(monkeypatch, order, gap=0.01):
    """Make the sweep groups FINISH in ``order`` (a permutation of group
    numbers, one job a group): each waits for its predecessor's return."""
    real = turbo._sweep_group
    done = [threading.Event() for _ in order]
    rank = {g: r for r, g in enumerate(order)}

    def ordered(lib, jobs, job_ids, *rest):
        out = real(lib, jobs, job_ids, *rest)
        r = rank[job_ids[0]]
        if r:
            assert done[r - 1].wait(30)
            time.sleep(gap)
        done[r].set()
        return out

    monkeypatch.setattr(turbo, "_sweep_group", ordered)


def _commit_signature(monkeypatch, committer, jobs, order=None):
    """What one commit, laid out as ``_STEADY_LAYOUT`` says, asked of the
    device and of the arena."""
    from reth_tpu.metrics import compile_tracker, pipeline_metrics

    keys = set()
    real = compile_tracker.record

    def spy(kind, shape, seconds):
        keys.add((kind,) + tuple(shape))
        return real(kind, shape, seconds)

    with monkeypatch.context() as mp:
        mp.setattr(compile_tracker, "record", spy)
        for name, value in _STEADY_LAYOUT.items():
            mp.setattr(turbo, name, value)
        if order is not None:
            _plant_completion_order(mp, order)
        results = committer.commit_hashed_pipelined(
            jobs, collect_branches=True, start_depth=2)
    mega = [k for k in keys if k[0].startswith("mega.")]
    sig = {
        "keys": frozenset(keys),
        # (u8_len, i32_len) and s_tier close every level program's key
        "buffer_lens": {k[-3:-1] for k in mega},
        "s_tier": ({k[-1] for k in mega} if mega
                   else {committer.arena.digest_buf(1).shape[0]}),
        "windows": pipeline_metrics.last["windows"],
    }
    return sig, results


def _steady_committer(backend):
    return TurboCommitter(backend=backend, min_tier=8)


@pytest.fixture(scope="module")
def steady_baseline():
    """Chunk A committed once by each backend, its sweeps left to finish as
    they will, and the answers of the same chunk as ONE group."""
    jobs = _chunk(_CHUNK_A, seed=900)
    mp = pytest.MonkeyPatch()
    try:
        out = {b: _commit_signature(mp, _steady_committer(b), jobs)
               for b in ("numpy", "device")}
    finally:
        mp.undo()
    serial = TurboCommitter(backend="numpy").commit_hashed_many(
        jobs, collect_branches=True, start_depth=2)
    return jobs, out, serial


@pytest.mark.parametrize("order", _ORDERS, ids=lambda o: "".join(map(str, o)))
@pytest.mark.parametrize("backend", ["numpy", "device"])
def test_shapes_do_not_follow_sweep_completion_order(
        monkeypatch, steady_baseline, backend, order):
    jobs, baseline, serial = steady_baseline
    want, _ = baseline[backend]
    got, results = _commit_signature(
        monkeypatch, _steady_committer(backend), jobs, order)
    assert got["windows"] == want["windows"] == 1   # 4 groups, PACK_WINDOW 16
    assert got["s_tier"] == want["s_tier"] and len(got["s_tier"]) == 1
    assert got["buffer_lens"] == want["buffer_lens"]
    assert got["keys"] == want["keys"]
    if backend == "device":
        assert len(got["buffer_lens"]) == 1 and len(got["keys"]) >= 2
    assert [r.root for r in results] == [r.root for r in serial]
    for g, w in zip(results, serial):
        assert g.branch_nodes == w.branch_nodes


@pytest.mark.parametrize("backend", ["numpy", "device"])
def test_a_chunk_after_a_different_chunk_asks_for_nothing_new(
        monkeypatch, backend):
    committer = _steady_committer(backend)
    a, b = _chunk(_CHUNK_A, seed=910), _chunk(_CHUNK_B, seed=920, prefix0=0x80)
    first, res_first = _commit_signature(monkeypatch, committer, a)
    other, _ = _commit_signature(monkeypatch, committer, b, order=(2, 0, 1))
    grows = committer.arena.grows
    again, res_again = _commit_signature(monkeypatch, committer, a,
                                         order=(3, 2, 1, 0))
    assert again["keys"] == first["keys"]
    assert again["buffer_lens"] == first["buffer_lens"]
    assert again["windows"] == first["windows"] == other["windows"] == 1
    if backend == "device":
        assert again["s_tier"] == first["s_tier"]
        assert other["keys"] != first["keys"]        # B is another chunk
    else:  # the twin's arena is resident: B grew it, A's return does not
        assert committer.arena.grows == grows
    assert [r.root for r in res_again] == [r.root for r in res_first]


def _mixed_sizes(n_jobs):
    """1-3 leaves mixed with thousands, the same for a given job count."""
    rng = np.random.default_rng(n_jobs)
    sizes = rng.integers(1, 4, size=n_jobs)
    big = rng.choice(n_jobs, size=max(1, n_jobs // 8), replace=False)
    sizes[big] = rng.integers(1000, 3000, size=len(big))
    return [int(s) for s in sizes]


@pytest.mark.parametrize("backend,n_jobs", [
    ("numpy", 2), ("numpy", 5), ("numpy", 17), ("numpy", 64), ("device", 5)])
@pytest.mark.parametrize("start_depth", [0, 2])
def test_pipelined_equals_serial_and_the_plain_reference(
        rebuild_layout, backend, n_jobs, start_depth):
    """"Serial" is the chunk as ONE group in one window (what the serial
    path was); "pipelined" the same chunk as several groups and windows."""
    jobs = _chunk(_mixed_sizes(n_jobs), seed=1000 + n_jobs, prefix0=0x10)
    committer = _steady_committer(backend)
    rebuild_layout(LEAVES_PER_SWEEP=10**9)
    serial = committer.commit_hashed_many(jobs, collect_branches=True,
                                          start_depth=start_depth)
    # several sweep groups and several windows, so slots are rebased: two
    # or three of the tiny jobs a group, a group each of the large ones
    rebuild_layout(LEAVES_PER_SWEEP=4, PACK_WINDOW=2)
    piped = committer.commit_hashed_pipelined(
        jobs, collect_branches=True, start_depth=start_depth)
    for got, want in zip(piped, serial):
        assert got.root == want.root
        assert got.branch_nodes == want.branch_nodes
    _assert_equals_the_plain_reference(jobs, piped, start_depth)


def test_pack_phase_and_arena_grows_move(rebuild_layout):
    from reth_tpu import tracing
    from reth_tpu.metrics import REGISTRY

    names = ["trie_commit_pack_seconds_total", "fused_arena_grows_total",
             "trie_pipeline_wait_seconds_total", "trie_pipeline_windows_total"]
    before = {n: REGISTRY.counter(n).value for n in names}
    tracing.set_trace_enabled(True)
    try:
        rec = tracing.flight_recorder()
        n0 = rec.recorded
        rebuild_layout(**_STEADY_LAYOUT)
        t0 = time.perf_counter()
        _steady_committer("device").commit_hashed_pipelined(
            _chunk(_CHUNK_A, seed=930), collect_branches=True, start_depth=2)
        wall = time.perf_counter() - t0
        spans = [s for s in rec.snapshot()[-(rec.recorded - n0):]
                 if (s["target"], s["name"]) == ("trie::commit", "pack")]
    finally:
        tracing.set_trace_enabled(False)
    moved = {n: REGISTRY.counter(n).value - before[n] for n in names}
    assert len(spans) == 1                      # one window, one pack span
    # the counter's clock brackets the span's
    assert (spans[0]["dur_ms"] / 1e3 * 0.8 - 2e-3
            <= moved["trie_commit_pack_seconds_total"] <= wall)
    assert moved["trie_commit_pack_seconds_total"] > 0
    assert moved["fused_arena_grows_total"] == 1   # begin(0), then one ensure
    assert moved["trie_pipeline_windows_total"] == 1
    assert moved["trie_pipeline_wait_seconds_total"] >= 0
    rendered = REGISTRY.render()
    assert "trie_commit_pack_seconds_total" in rendered
    assert "fused_arena_grows_total" in rendered


def test_the_arena_rises_a_tier_at_a_time_however_many_windows(
        turbo_np, rebuild_layout):
    """48 windows ask the backend for room O(log) times, each time for a
    whole power-of-two tier."""
    jobs = [_job(40 + 3 * i, seed=400 + i) for i in range(48)]
    want = turbo_np.commit_hashed_many(jobs, collect_branches=True)
    asks = []

    class Spy(_NumpyBackend):
        def ensure(self, max_slots):
            asks.append(max_slots)
            super().ensure(max_slots)

    rebuild_layout(LEAVES_PER_SWEEP=1, PACK_WINDOW=1)
    pipe = RebuildPipeline(Spy())
    got = pipe.run(jobs, collect_branches=True)
    assert pipe.windows == 48
    slots = got[-1].hashed_nodes            # the commit's slot high-water mark
    assert asks == sorted(set(asks)) and 1 < len(asks) <= slots.bit_length()
    assert all((a + 1) & a == 0 for a in asks)      # capacity a + 1: 2**k
    assert asks[-1] == (1 << slots.bit_length()) - 1
    assert [r.root for r in got] == [r.root for r in want]
    for g, w in zip(got, want):
        assert g.branch_nodes == w.branch_nodes


# -- a chunk of one job: the pipeline's one-group case ------------------------
#
# ``rebuild.accounts`` commits one 1,171,875-leaf subtrie a chunk and the live
# tip one small trie a commit: one sweep group, swept by the caller, one
# window, slot base 0, the arena tier ``begin(max_slot)`` would have given.

# what ``MegaFusedEngine._execute`` was about to run for ``_one_job_chunk()``
# under ``small_tiers`` at commit c1fa191 (the parent of the PR that took the
# serial path out), through its ``commit_hashed_many`` → ``_run_inner``:
# entry = (kind, [b_tier,] row tier, hole tier, offsets..., rows + 1,
# holes + 1), deepest level first, a level over the row cap split in order.
# ``start_depth=2`` stops short of the last entry (the extension over the
# shared first byte).
_GOLDEN_S_TIER = 4096
_GOLDEN_LENS = (327680, 12288)
_GOLDEN_PLAN = [
    ("packed", 1, 64, 64, 0, 646, 0, 7, 8, 7, 1),
    ("packed", 1, 128, 64, 660, 9923, 9, 98, 99, 89, 1),
    ("branch", 64, 64, 10101, 100, 104, 111, 4, 7),
    ("packed", 1, 1024, 64, 10109, 119169, 118, 1142, 1144, 1024, 2),
    ("packed", 1, 64, 64, 121217, 124218, 1146, 1175, 1176, 29, 1),
    ("branch", 64, 128, 124276, 1177, 1222, 1312, 45, 90),
    ("packed", 1, 1024, 64, 124366, 231865, 1402, 2426, 2448, 1024, 22),
    ("packed", 1, 512, 64, 233913, 271807, 2470, 2831, 2838, 361, 7),
    ("branch", 512, 2048, 272529, 2845, 3335, 4405, 490, 1070),
    ("branch", 512, 2048, 273509, 5475, 5732, 7605, 257, 1873),
    ("branch", 64, 512, 274023, 9478, 9495, 9752, 17, 257),
    ("branch", 64, 64, 274057, 10009, 10011, 10028, 2, 17),
    ("packed", 1, 64, 64, 274061, 274098, 10045, 10047, 10049, 2, 2),
]


def _one_job_chunk():
    return _chunk((2500,), seed=3100, prefix0=0x5A)


@pytest.fixture
def small_tiers(monkeypatch):
    """Row tiers from 64 and a row cap of 1024, so that a 2,500-leaf trie
    asks for several tiers and splits a level."""
    monkeypatch.setattr(fc.MegaFusedEngine, "_ROW_FLOOR", 64)
    monkeypatch.setattr(fc.MegaFusedEngine, "_HOLE_FLOOR", 64)
    monkeypatch.setattr(fc.FusedLevelEngine, "_row_cap", lambda self: 1024)


@pytest.fixture
def pipeline_spy(monkeypatch, seen_plans):
    """What a commit did: ``ensure`` asks, plans about to execute, the
    names of the threads started, the widths of the sweep pools made."""
    seen = {"ensure": [], "plans": seen_plans, "threads": [], "pools": []}

    def pool(*args, max_workers=None, **kwargs):
        seen["pools"].append(max_workers)
        return ThreadPoolExecutor(*args, max_workers=max_workers, **kwargs)

    monkeypatch.setattr(turbo, "ThreadPoolExecutor", pool)
    for cls in (_NumpyBackend, fc.MegaFusedEngine):
        def ensure(self, max_slots, _real=cls.ensure):
            seen["ensure"].append(max_slots)
            return _real(self, max_slots)
        monkeypatch.setattr(cls, "ensure", ensure)
    start = threading.Thread.start

    def spy_start(self):
        seen["threads"].append(self.name)
        return start(self)

    monkeypatch.setattr(threading.Thread, "start", spy_start)
    return seen


@pytest.mark.parametrize("start_depth", [0, 2])
@pytest.mark.parametrize("backend", ["numpy", "device"])
def test_a_chunk_of_one_job_is_one_group_one_window(
        small_tiers, pipeline_spy, backend, start_depth):
    from reth_tpu.metrics import pipeline_metrics

    jobs = _one_job_chunk()
    committer = _steady_committer(backend)
    results = committer.commit_hashed_pipelined(
        jobs, collect_branches=True, start_depth=start_depth)
    _assert_equals_the_plain_reference(jobs, results, start_depth)
    last = pipeline_metrics.last
    assert (last["jobs"], last["groups"], last["windows"]) == (1, 1, 1)
    max_slot = results[-1].hashed_nodes     # one group: its slots are 1..n
    s_tier = fc._pow2(max_slot + 1)         # what begin(max_slot) gave
    assert pipeline_spy["ensure"] == [s_tier - 1]
    if backend == "numpy":
        assert committer.arena.digest_buf(1).shape[0] == s_tier
        return
    (p,) = pipeline_spy["plans"]
    assert p["s_tier"] == s_tier == _GOLDEN_S_TIER
    assert p["lens"] == _GOLDEN_LENS
    assert p["plan"] == (_GOLDEN_PLAN if start_depth == 0
                         else _GOLDEN_PLAN[:-1])


def test_a_window_of_one_sweep_copies_nothing():
    jobs = _chunk((900, 40), seed=3200)
    sw = _sweep_group(turbo.load_library(), jobs, range(2), True, 2)
    own = [(lv.depth, lv.flat, lv.row_off, lv.row_len, lv.row_slot, lv.holes,
            lv.masks, lv.bmp_slot, lv.children) for lv in sw.levels]
    merged = _pack_window([(0, sw)])
    assert [m.depth for m in merged] == [lv[0] for lv in own]
    assert [m.depth for m in merged] == sorted(
        (m.depth for m in merged), reverse=True)
    shared = 0
    for m, (_, flat, row_off, row_len, row_slot, holes, masks, bmp_slot,
            children) in zip(merged, own):
        if len(row_slot):
            pairs = [(m.flat, flat), (m.row_off, row_off),
                     (m.row_len, row_len), (m.row_slot, row_slot)]
            if holes is not None:
                pairs.append((m.holes, holes))
            else:
                assert m.holes is None
        else:
            pairs = []
            assert len(m.row_slot) == 0
        if len(bmp_slot):
            pairs += [(m.masks, masks), (m.bmp_slot, bmp_slot),
                      (m.children, children)]
        else:
            assert len(m.bmp_slot) == 0
        for mine, theirs in pairs:
            assert mine is theirs or np.shares_memory(mine, theirs)
            shared += 1
    assert shared >= 4 * len(merged)


def test_a_one_group_chunk_makes_no_thread(pipeline_spy, turbo_np):
    one = _chunk((300, 200), seed=3300)          # two jobs, one group
    assert _group_jobs(one, turbo.LEAVES_PER_SWEEP) == [(0, 2)]
    turbo_np.commit_hashed_pipelined(one, collect_branches=True,
                                     start_depth=2)
    turbo_np.commit_hashed_many(one[:1])
    assert pipeline_spy["threads"] == []
    two = _chunk((40000, 300), seed=3310)        # the first fills a group
    assert len(_group_jobs(two, turbo.LEAVES_PER_SWEEP)) == 2
    turbo_np.commit_hashed_pipelined(two, start_depth=2)
    assert pipeline_spy["threads"]
    assert all(n.startswith("trie-sweep") for n in pipeline_spy["threads"])
    assert pipeline_spy["pools"] == [turbo.SWEEP_THREADS]


@pytest.mark.parametrize("name,hostile", [
    ("RETH_TPU_PIPELINE", "0"),
    ("RETH_TPU_PIPELINE_SWEEPERS", "1"),
    ("RETH_TPU_PIPELINE_HASHERS", "999"),
    ("RETH_TPU_PIPELINE_WINDOW", "1"),
    ("RETH_TPU_PIPELINE_SWEEP_LEAVES", "1"),
])
def test_deleted_names_are_not_read(monkeypatch, pipeline_spy, name, hostile):
    """The five environment names of the rebuild are gone: a value that
    once forced the serial path, one sweep thread, a hash pool, a window a
    group or a group a job changes neither the layout nor the answers."""
    from reth_tpu.metrics import pipeline_metrics

    jobs = _chunk((40000, 300, 200, 100), seed=3400)

    def commit():
        del pipeline_spy["threads"][:], pipeline_spy["pools"][:]
        pipeline_metrics.last = None
        got = TurboCommitter(backend="numpy").commit_hashed_pipelined(
            jobs, collect_branches=True, start_depth=2)
        last = pipeline_metrics.last
        assert all(t.startswith("trie-sweep") for t in pipeline_spy["threads"])
        return got, (last["groups"], last["windows"], pipeline_spy["pools"][:])

    want, layout = commit()
    assert layout == (2, 1, [turbo.SWEEP_THREADS])
    monkeypatch.setenv(name, hostile)
    got, layout_hostile = commit()
    assert layout_hostile == layout
    assert [r.root for r in got] == [r.root for r in want]
    for g, w in zip(got, want):
        assert g.branch_nodes == w.branch_nodes
