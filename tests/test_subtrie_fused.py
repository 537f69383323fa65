"""Whole-subtrie fused tree-hash kernels (ops/fused_commit.py
SubtrieFusedEngine / SubtrieMeshEngine): ONE device dispatch per chunk of
k staged levels, not one per depth.

The acceptance drills, on the virtual 8-device CPU mesh (conftest):

- randomized k-level differential sweep: k x depth x mesh-size grid
  (including the non-pow2 6/3-device meshes) vs the per-level engines and
  the numpy twin — roots and TrieUpdates bit-identical (the compile-heavy
  full grid rides ``make test-subtrie`` via @slow; tier-1 pins the small
  corners);
- fault drills: RETH_TPU_FAULT_SUBTRIE_WEDGE proves a mid-kernel chunk
  failure replays the staged journal bit-identically on the per-level
  path; RETH_TPU_FAULT_SUBTRIE_ABORT poisons the device path entirely and
  proves the CPU-twin rung;
- the hoisted ladder-caps fix: a 64-level window with branch-heavy
  (hole-dense) near-root levels never mints an off-menu batch tier
  (extends the PR 10 ladder-clamp tests), and the memoized caps stay
  exact when tests mutate the ceilings post-init;
- warm-up integration: the menu declares (fused.subtrie, k, tier, mesh)
  shapes, and an un-warm k-shape routes the commit to the per-level path
  instead of compiling mid-commit;
- hash-service window requests: a pre-packed multi-level window runs as
  one fused dispatch on the live lane, with numpy replay on a wedge.
"""

from __future__ import annotations

import numpy as np
import pytest

from reth_tpu.metrics import MetricsRegistry, fused_metrics
from reth_tpu.ops.fused_commit import (
    FusedLevelEngine,
    SubtrieFaultInjector,
    SubtrieFusedEngine,
    SubtrieMeshEngine,
)
from reth_tpu.primitives.keccak import keccak256, keccak256_batch_np
from reth_tpu.primitives.rlp import rlp_encode


def _job(n: int, seed: int):
    r = np.random.default_rng(seed)
    keys = r.integers(0, 256, (n, 32), dtype=np.uint8)
    vals = [rlp_encode(bytes(r.integers(0, 256, size=int(r.integers(1, 60)),
                                        dtype=np.uint8))) for _ in range(n)]
    return keys, vals


def _leaf_rows(seed: int, n: int = 24, lo: int = 1, hi: int = 130):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=int(rng.integers(lo, hi)),
                         dtype=np.uint8).tobytes() for _ in range(n)]


def _run_leaf_levels(eng, rows, per_level: int = 8):
    """Drive ``rows`` through the engine as hole-free packed levels of
    ``per_level`` rows each; returns (digest buffer, slots)."""
    eng.begin(len(rows) + 1)
    slots = np.array([eng.alloc_slot() for _ in rows], dtype=np.int32)
    flat = np.frombuffer(b"".join(rows), dtype=np.uint8)
    row_len = np.array([len(r) for r in rows], dtype=np.uint32)
    row_off = (np.cumsum(row_len) - row_len).astype(np.uint32)
    for lo in range(0, len(rows), per_level):
        hi = min(lo + per_level, len(rows))
        base = int(row_off[lo])
        end = int(row_off[hi - 1] + row_len[hi - 1])
        eng.dispatch_packed(flat[base:end], row_off[lo:hi] - base,
                            row_len[lo:hi], slots[lo:hi], None, b_tier=1)
    return eng.finish(), slots


def _small_engine(**kw):
    kw.setdefault("min_tier", 8)
    kw.setdefault("row_floor", 32)
    kw.setdefault("hole_floor", 32)
    return SubtrieFusedEngine(**kw)


# -- engine-level parity -------------------------------------------------------


def test_subtrie_leaf_levels_match_reference():
    rows = _leaf_rows(1)
    eng = _small_engine(k=8)
    d, slots = _run_leaf_levels(eng, rows)
    for s, r in zip(slots, rows):
        assert d[s].tobytes() == keccak256(r)
    # 3 staged levels fused into one dispatch at k=8
    assert eng.levels_staged == 3
    assert eng.dispatches == 1


def test_subtrie_parent_composition_across_chunks():
    """Holes reference digests written by EARLIER steps of the same fused
    program (the in-kernel carry) and by earlier chunks/windows (the
    resident buffer)."""
    child = b"\x55" * 44
    eng = _small_engine(k=2)
    eng.begin(8)
    s_child = eng.alloc_slot()
    eng.dispatch_packed(np.frombuffer(child, np.uint8),
                        np.zeros((1,), np.uint32),
                        np.array([len(child)], np.uint32),
                        np.array([s_child], np.int32), None, 1)
    eng.flush_window()  # child lands in the resident buffer
    prefix = b"\xc0" * 7
    tmpl = prefix + b"\xa0" + b"\x00" * 32
    s_mid = eng.alloc_slot()
    eng.dispatch_packed(np.frombuffer(tmpl, np.uint8),
                        np.zeros((1,), np.uint32),
                        np.array([len(tmpl)], np.uint32),
                        np.array([s_mid], np.int32),
                        np.array([[0], [len(prefix) + 1], [s_child]],
                                 np.int32), 1)
    s_top = eng.alloc_slot()
    eng.dispatch_packed(np.frombuffer(tmpl, np.uint8),
                        np.zeros((1,), np.uint32),
                        np.array([len(tmpl)], np.uint32),
                        np.array([s_top], np.int32),
                        np.array([[0], [len(prefix) + 1], [s_mid]],
                                 np.int32), 1)
    d = eng.finish()
    mid = keccak256(prefix + b"\xa0" + keccak256(child))
    assert d[s_mid].tobytes() == mid
    assert d[s_top].tobytes() == keccak256(prefix + b"\xa0" + mid)


def test_subtrie_branch_step_matches_numpy_twin():
    from reth_tpu.trie.turbo import _NumpyBackend

    rows = _leaf_rows(3, n=4, lo=40, hi=60)
    masks = np.array([0x0013, 0x8001], dtype=np.uint16)
    children = np.array([[0, 0, 0, 1, 1],
                         [0, 1, 4, 0, 15],
                         [1, 2, 3, 4, 2]], dtype=np.int32)

    def drive(eng):
        eng.begin(8)
        slots = np.array([eng.alloc_slot() for _ in rows], np.int32)
        flat = np.frombuffer(b"".join(rows), np.uint8)
        rl = np.array([len(r) for r in rows], np.uint32)
        ro = (np.cumsum(rl) - rl).astype(np.uint32)
        eng.dispatch_packed(flat, ro, rl, slots, None, 1)
        bslots = np.array([eng.alloc_slot(), eng.alloc_slot()], np.int32)
        eng.dispatch_branch(masks, bslots, children)
        return eng.finish()

    want = drive(_NumpyBackend())
    got = drive(_small_engine(k=8))
    # slot 0 is the dummy padding target (engine-private garbage);
    # every REAL slot must match the numpy twin bit-for-bit
    assert got[1:want.shape[0]].tobytes() == want[1:].tobytes()


# -- k x depth x mesh differential grid ---------------------------------------


def _turbo_differential(k: int, mesh_n: int, seeds, min_tier: int = 16):
    import jax
    from jax.sharding import Mesh

    from reth_tpu.trie.turbo import TurboCommitter

    mesh = (Mesh(np.array(jax.devices()[:mesh_n]), ("data",))
            if mesh_n > 1 else None)
    dev = TurboCommitter(backend="device", min_tier=min_tier, mesh=mesh,
                         subtrie_levels=k)
    cpu = TurboCommitter(backend="numpy")
    for seed in seeds:
        jobs = [_job(int(n), seed * 10 + i)
                for i, n in enumerate((130, 50, 9, 1))]
        got = dev.commit_hashed_many(jobs, collect_branches=True)
        want = cpu.commit_hashed_many(jobs, collect_branches=True)
        assert [r.root for r in got] == [r.root for r in want]
        assert [r.branch_nodes for r in got] == [r.branch_nodes for r in want]
        got_p = dev.commit_hashed_pipelined(jobs)
        assert [r.root for r in got_p] == [r.root for r in want]


def test_turbo_subtrie_differential_single_device():
    """Tier-1 corner of the grid: k=4 on one device, roots + TrieUpdates
    bit-identical to the numpy twin, and the commit's dispatch count
    lands in the fused histogram."""
    _turbo_differential(4, 1, seeds=(1,))
    last = fused_metrics.last
    assert last is not None and last["k"] == 4 and last["mode"] == "fused"
    assert last["dispatches"] < last["levels"]


@pytest.mark.slow
def test_turbo_subtrie_differential_grid():
    """The full randomized k x mesh grid, incl. the non-pow2 6/3-device
    meshes whose tier ladders leave the pow2 grid (make test-subtrie —
    compile-heavy)."""
    for k in (1, 2, 8):
        _turbo_differential(k, 1, seeds=(k,))
    for mesh_n in (2, 3, 6, 8):
        _turbo_differential(8, mesh_n, seeds=(mesh_n,), min_tier=18)
    _turbo_differential(2, 6, seeds=(3,), min_tier=18)


def test_subtrie_mesh_engine_parity_small():
    """Fast mesh corner: the k-level SPMD variant on 2 and 3 devices is
    bit-identical to the single-device engine."""
    import jax
    from jax.sharding import Mesh

    rows = _leaf_rows(7)
    d0, s0 = _run_leaf_levels(_small_engine(k=4), rows)
    for n in (2, 3):
        mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
        eng = SubtrieMeshEngine(mesh, min_tier=8, k=4, row_floor=32,
                                hole_floor=32)
        d, s = _run_leaf_levels(eng, rows)
        assert all(d[a].tobytes() == d0[b].tobytes()
                   for a, b in zip(s, s0))


# -- fault drills --------------------------------------------------------------


def test_subtrie_wedge_replays_per_level(monkeypatch):
    """RETH_TPU_FAULT_SUBTRIE_WEDGE: the wedged chunk replays the staged
    journal on the per-level path, digests bit-identical."""
    monkeypatch.setenv("RETH_TPU_FAULT_SUBTRIE_WEDGE", "1")
    inj = SubtrieFaultInjector.from_env()
    assert inj is not None and inj.wedge_at == 1
    rows = _leaf_rows(11)
    d0, s0 = _run_leaf_levels(_small_engine(k=8), rows)
    eng = _small_engine(k=8, injector=inj)
    d1, s1 = _run_leaf_levels(eng, rows)
    assert all(d1[a].tobytes() == d0[b].tobytes() for a, b in zip(s1, s0))
    assert eng._mode == "perlevel" and inj.wedges == 1
    assert eng.dispatches == eng.levels_staged  # one per level on replay


def test_subtrie_abort_lands_on_cpu_twin(monkeypatch):
    """RETH_TPU_FAULT_SUBTRIE_ABORT: fused AND per-level replays fail —
    the journal replays on the CPU twin, digests bit-identical."""
    monkeypatch.setenv("RETH_TPU_FAULT_SUBTRIE_ABORT", "1")
    inj = SubtrieFaultInjector.from_env()
    rows = _leaf_rows(13)
    d0, s0 = _run_leaf_levels(_small_engine(k=8), rows)
    eng = _small_engine(k=8, injector=inj)
    d1, s1 = _run_leaf_levels(eng, rows)
    assert all(d1[a].tobytes() == d0[b].tobytes() for a, b in zip(s1, s0))
    assert eng._mode == "cpu" and inj.aborts == 1


def test_subtrie_wedge_mid_pipeline_turbo():
    """The wedge drill through the REAL consumer: a pipelined turbo
    rebuild whose k-level backend wedges mid-commit still produces roots
    bit-identical to the numpy committer."""
    from reth_tpu.trie.turbo import TurboCommitter

    jobs = [_job(60, 77), _job(25, 78)]
    cpu = TurboCommitter(backend="numpy")
    want = [r.root for r in cpu.commit_hashed_many(jobs)]
    dev = TurboCommitter(backend="device", min_tier=16, subtrie_levels=4)
    orig = dev._device_engine

    def wedged_engine():
        eng = orig()
        eng.injector = SubtrieFaultInjector(wedge_at=1)
        return eng

    dev._device_engine = wedged_engine
    got = [r.root for r in dev.commit_hashed_pipelined(jobs)]
    assert got == want
    assert fused_metrics.last["mode"] == "perlevel"


# -- hoisted ladder caps (PR 10 ladder-clamp extension) ------------------------


def test_row_cap_memo_tracks_ceiling_mutation():
    assert FusedLevelEngine(min_tier=1024)._row_cap() == 65536  # at __init__
    eng = FusedLevelEngine(min_tier=18)
    assert eng._row_cap() == 18432  # ladder 18→72→…→18432 under 65536
    eng.MAX_BATCH_ROWS = 100  # tests mutate ceilings post-init: memo keys
    assert eng._row_cap() == 72
    assert eng._hole_budget(65) == 4 * 72  # ladder lookup, not a walk
    assert eng._hole_budget(1) == 4 * 18


def test_64_level_branch_heavy_window_stays_on_menu():
    """A 64-level window with hole-dense near-root levels never mints an
    off-menu batch tier: every split lands ON the hoisted ladder (the
    in-engine _check_batch_tier assertion is the guard) and digests stay
    bit-identical to the reference keccak across the splits."""
    rng = np.random.default_rng(5)
    eng = _small_engine(k=8)
    eng.MAX_BATCH_ROWS = 16  # row cap 8: every 12-row level splits
    assert eng._row_cap() == 8
    eng.begin(64 * 12 + 1)
    prev_slots: list[int] = []
    expected: dict[int, bytes] = {}
    prev_hashes: list[bytes] = []
    for depth in range(64):
        rows, holes_r, holes_b, holes_s = [], [], [], []
        slots = []
        hashes = []
        for i in range(12):
            s = eng.alloc_slot()
            slots.append(s)
            if depth and i < 10:  # branch-heavy: most rows splice a child
                prefix = bytes(rng.integers(0, 256, 8, dtype=np.uint8))
                child = (depth - 1) * 12 + i
                rows.append(prefix + b"\xa0" + b"\x00" * 32)
                holes_r.append(i)
                holes_b.append(len(prefix) + 1)
                holes_s.append(prev_slots[i])
                real = prefix + b"\xa0" + prev_hashes[i]
                del child
            else:
                real = bytes(rng.integers(0, 256,
                                          int(rng.integers(33, 100)),
                                          dtype=np.uint8))
                rows.append(real)
            hashes.append(keccak256(real))
            expected[s] = hashes[-1]
        flat = np.frombuffer(b"".join(rows), np.uint8)
        rl = np.array([len(r) for r in rows], np.uint32)
        ro = (np.cumsum(rl) - rl).astype(np.uint32)
        holes = (np.array([holes_r, holes_b, holes_s], np.int32)
                 if holes_r else None)
        eng.dispatch_packed(flat, ro, rl, np.array(slots, np.int32),
                            holes, 1)
        prev_slots, prev_hashes = slots, hashes
    d = eng.finish()
    for s, h in expected.items():
        assert d[s].tobytes() == h
    assert eng.levels_staged >= 64  # row-cap splits multiplied the steps
    assert eng.dispatches < eng.levels_staged  # ...and chunks still fused


# -- warm-up integration -------------------------------------------------------


def test_menu_declares_subtrie_shapes():
    from reth_tpu.ops.warmup import default_menu

    menu = default_menu(subtrie_ks=(8,), mesh_sizes=(4,))
    keys = [s.key() for s in menu]
    assert ("fused.subtrie", 8, 2048, 1) in keys
    assert ("fused.subtrie", 8, 2048, 4) in keys
    assert str([s for s in menu if s.program == "fused.subtrie"][0]) \
        == "fused.subtrie:8x2048"


def test_unwarm_k_shape_routes_per_level():
    from reth_tpu.ops.warmup import MenuShape, WarmupManager

    mgr = WarmupManager(menu=[MenuShape("fused.subtrie", 8, 32, 1)],
                        registry=MetricsRegistry())
    mgr._active = True  # warm-up started, nothing warm yet
    rows = _leaf_rows(21)
    eng = _small_engine(k=8, warmup=mgr)
    d, s = _run_leaf_levels(eng, rows)
    for a, r in zip(s, rows):
        assert d[a].tobytes() == keccak256(r)
    assert eng.dispatches == eng.levels_staged  # degraded: one per level
    assert eng._mode == "fused"  # degraded ROUTING, not a failover
    # promote the shape: the same engine shape fuses again
    mgr.states[("fused.subtrie", 8, 32, 1)] = "warm"
    mgr._done.set()
    eng2 = _small_engine(k=8, warmup=mgr)
    d2, s2 = _run_leaf_levels(eng2, rows)
    assert all(d2[a].tobytes() == d[b].tobytes() for a, b in zip(s2, s))
    assert eng2.dispatches < eng2.levels_staged


@pytest.mark.slow
def test_warmup_builds_subtrie_shape():
    from reth_tpu.ops.warmup import MenuShape, _build_shape

    _build_shape(MenuShape("fused.subtrie", 8, 32, 1))
    _build_shape(MenuShape("fused.subtrie", 4, 32, 2))


# -- sparse finish (multi-level dispatch per finish) --------------------------


def _sparse_state(seed: int, tries: int = 10, slots: int = 24):
    from reth_tpu.trie.sparse import SparseStateTrie

    rng = np.random.default_rng(seed)
    st = SparseStateTrie()
    for _ in range(tries):
        ha = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        t = st.storage_trie(ha)
        keys = [bytes(rng.integers(0, 256, 32, dtype=np.uint8))
                for _ in range(slots)]
        for k in keys:
            t.update(k, bytes(rng.integers(1, 256, 8, dtype=np.uint8)))
        t.delete(keys[0])
        st.update_account(ha, b"account-leaf-" + ha)
    return st


def _sparse_committer(k: int = 8):
    from reth_tpu.trie.sparse import ParallelSparseCommitter

    c = ParallelSparseCommitter(subtrie_levels=k)
    c.SUBTRIE_ROW_FLOOR = 64
    c.SUBTRIE_HOLE_FLOOR = 64
    return c


@pytest.mark.parametrize("seed", [4, 5])
def test_sparse_fused_finish_parity(seed):
    st_serial = _sparse_state(seed)
    st_fused = _sparse_state(seed)
    want = st_serial.root(keccak256_batch_np)
    c = _sparse_committer()
    got = st_fused.root(keccak256_batch_np, committer=c)
    assert got == want
    assert c.last["subtrie_k"] == 8
    assert c.last["dispatches"] <= -(-c.last["levels"] // 8) + 1
    # second block: dirty subset + cross-block clean-ref reuse
    for st in (st_serial, st_fused):
        r = np.random.default_rng(seed + 100)
        for ha, t in list(st.storage_tries.items())[:3]:
            for _ in range(4):
                t.update(bytes(r.integers(0, 256, 32, dtype=np.uint8)),
                         b"\x07\x08")
            st.update_account(ha, b"post-" + ha)
    assert st_fused.root(keccak256_batch_np, committer=c) \
        == st_serial.root(keccak256_batch_np)


def test_sparse_fused_preserves_abort_drill():
    """RETH_TPU_FAULT_SPARSE_ABORT still fires on the fused path (the
    engine-strategy fallback contract is unchanged)."""
    from reth_tpu.trie.sparse import InjectedSparseAbort, SparseFaultInjector

    st = _sparse_state(6)
    c = _sparse_committer()
    c.injector = SparseFaultInjector(abort_at=1)
    with pytest.raises(InjectedSparseAbort):
        st.root(keccak256_batch_np, committer=c)


# -- hash-service multi-level windows -----------------------------------------


def _window_levels():
    rows = [b"\x11" * 45, b"\x22" * 50]
    lv1 = {"flat": np.frombuffer(b"".join(rows), np.uint8),
           "row_off": np.array([0, 45], np.uint32),
           "row_len": np.array([45, 50], np.uint32),
           "slots": np.array([1, 2], np.int32),
           "holes": None, "b_tier": 1}
    parent = b"\xc1" * 6 + b"\xa0" + b"\x00" * 32
    lv2 = {"flat": np.frombuffer(parent, np.uint8),
           "row_off": np.array([0], np.uint32),
           "row_len": np.array([len(parent)], np.uint32),
           "slots": np.array([3], np.int32),
           "holes": np.array([[0], [7], [2]], np.int32), "b_tier": 1}
    want = {1: keccak256(rows[0]), 2: keccak256(rows[1]),
            3: keccak256(parent[:7] + keccak256(rows[1]))}
    return [lv1, lv2], want


def test_service_window_one_fused_dispatch():
    from reth_tpu.ops.hash_service import HashService

    svc = HashService(backend=keccak256_batch_np,
                      registry=MetricsRegistry(), min_tier=16,
                      subtrie_levels=8)
    try:
        window, want = _window_levels()
        buf = svc.client("live").commit_window(window, 3)
        for s, h in want.items():
            assert buf[s].tobytes() == h
        assert svc.window_dispatches == 1
        # plain traffic still coalesces beside windows
        assert svc.client("proof")([b"abc"])[0] == keccak256(b"abc")
    finally:
        svc.stop()


def test_service_window_wedge_replays_on_numpy():
    from reth_tpu.ops.hash_service import HashService, ServiceFaultInjector

    svc = HashService(backend=keccak256_batch_np,
                      registry=MetricsRegistry(), min_tier=16,
                      subtrie_levels=8,
                      injector=ServiceFaultInjector(wedge_every=1))
    try:
        window, want = _window_levels()
        fut = svc.submit_window("live", window, 3)
        buf = fut.result(timeout=30)
        for s, h in want.items():
            assert buf[s].tobytes() == h
        assert fut.completions == 1
        assert svc.replays == 1
    finally:
        svc.stop()


def test_sparse_fused_streams_through_service_window():
    """The live-tip finish with a lane-bound HashClient hasher rides the
    service's window lane — one fused dispatch per finish."""
    from reth_tpu.ops.hash_service import HashService

    st_serial = _sparse_state(8, tries=6, slots=16)
    st_fused = _sparse_state(8, tries=6, slots=16)
    want = st_serial.root(keccak256_batch_np)
    svc = HashService(backend=keccak256_batch_np,
                      registry=MetricsRegistry(), min_tier=16,
                      subtrie_levels=8)
    try:
        got = st_fused.root(svc.client("live"),
                            committer=_sparse_committer())
        assert got == want
        assert svc.window_dispatches == 1
    finally:
        svc.stop()
