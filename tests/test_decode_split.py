"""The branch decode split at the digest boundary: what needs no digest (the
results, the paths, masks and nodes of the branch records, the arena rows of
their child hashes) runs between the backend's ``launch`` and its ``finish``;
the child hashes and the roots are laid in after the fetch. Held here to the
record-by-record oracle and to the decode run in one piece after the fetch,
on the numpy twin and on ``MegaFusedEngine`` under JAX's CPU backend."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest
from branch_decode_oracle import collect_meta_records_loop

from reth_tpu import tracing
from reth_tpu.metrics import REGISTRY
from reth_tpu.primitives.rlp import rlp_encode
from reth_tpu.trie import turbo
from reth_tpu.trie.committer import (
    BranchNode,
    TrieBuildResult,
    branch_nodes_hashed_later,
)
from reth_tpu.trie.turbo import TurboCommitter

THREADED = "trie_sweep_threaded_jobs_total"
PREDECODE = ["trie_commit_predecode_seconds_total",
             "trie_commit_predecode_records_total",
             "trie_commit_decode_records_total"]


def _trie(rng, n, value_len=(1, 40), prefix=None, shared=0):
    """One job: ``n`` distinct keys (the first ``shared`` bytes of each the
    same, so their leaves sit deep and, with short values, are embedded in
    their parents), values of ``value_len`` bytes, RLP-encoded."""
    keys = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    keys[:, :shared] = rng.integers(0, 256, shared, dtype=np.uint8)
    if prefix is not None:
        keys[:, 0] = prefix
    keys = np.unique(keys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
    rng.shuffle(keys)
    return keys, [rlp_encode(bytes(rng.integers(0, 256, int(k), dtype=np.uint8)))
                  for k in rng.integers(*value_len, len(keys))]


def _case(name):
    """(jobs, start_depth, layout) of one shape of chunk."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "one_threaded_job":
        # over SWEEP_THREADS * LEAVES_PER_SWEEP leaves: swept on threads
        return [_trie(rng, 131_072, (60, 80), prefix=0x3C)], 2, {}
    if name == "small_tries_then_a_large_one":
        jobs = [_trie(rng, int(n), (1, 4), shared=29 if i % 3 == 0 else 0)
                for i, n in enumerate(rng.integers(1, 40, 300))]
        return jobs + [_trie(rng, 20_000)], 0, {}
    assert name == "rebased_groups"
    jobs = [_trie(rng, n, prefix=0x40 + i)
            for i, n in enumerate((900, 1, 300, 1500, 40, 700))]
    return jobs, 2, {"LEAVES_PER_SWEEP": 901}


CASES = ["one_threaded_job", "small_tries_then_a_large_one", "rebased_groups"]


class _Recording:
    """A backend as the pipeline sees it, with its ``launch``, ``finish``
    and ``fetch_slots`` calls written to ``events`` and what ``finish``
    returned kept; ``hide_launch`` takes ``launch`` away."""

    def __init__(self, inner, events, hide_launch=False):
        self._inner, self._events, self._hide = inner, events, hide_launch
        self.digests = None

    def __getattr__(self, name):
        if name == "launch" and self._hide:
            raise AttributeError(name)
        attr = getattr(self._inner, name)
        if name not in ("launch", "finish", "fetch_slots"):
            return attr

        def call(*args):
            self._events.append(name)
            out = attr(*args)
            if name == "finish":
                self.digests = out
            return out

        return call


class _Run:
    """One commit, seen from inside: the events in order (``half1`` a
    group's first half, ``half2`` its second), the first half's arguments
    and its group results, the digests ``finish`` returned; ``half1`` is
    the first half itself, unrecorded."""

    def __init__(self, monkeypatch, committer, hide_launch=False):
        self.events, self.groups, self.backends = [], [], []
        self.half1 = real_half1 = turbo._collect_meta_records
        real_half2 = turbo._PendingBranches.lay_in
        make = committer._make_backend

        def half1(meta_rec, keys, results, start_depth=0, slot_base=0):
            self.events.append("half1")
            self.groups.append((meta_rec.copy(), keys, results, start_depth,
                                slot_base))
            return real_half1(meta_rec, keys, results, start_depth, slot_base)

        def half2(pending, digests):
            self.events.append("half2")
            return real_half2(pending, digests)

        def backend():
            self.backends.append(_Recording(make(), self.events, hide_launch))
            return self.backends[-1]

        monkeypatch.setattr(turbo, "_collect_meta_records", half1)
        monkeypatch.setattr(turbo._PendingBranches, "lay_in", half2)
        monkeypatch.setattr(committer, "_make_backend", backend)


def _committer(kind):
    if kind == "numpy":
        return TurboCommitter(backend="numpy")
    return TurboCommitter(backend="device", min_tier=8)


_ANCHORS: dict = {}


def _anchor_roots(name):
    """The numpy twin's roots of a case, roots alone."""
    if name not in _ANCHORS:
        jobs, start_depth, _ = _case(name)
        _ANCHORS[name] = [r.root for r in TurboCommitter(
            backend="numpy").commit_hashed_pipelined(jobs, False, start_depth)]
    return _ANCHORS[name]


def _oracle(run):
    """Each group's records decoded by the record-by-record oracle and, in
    one piece after the fetch, by the split decode itself: both as fresh
    results, next to the group's own."""
    out, digests = [], run.backends[-1].digests
    for meta_rec, keys, got, start_depth, slot_base in run.groups:
        n = len(got)
        loop = collect_meta_records_loop(
            meta_rec, [keys] * n, [0] * n, digests,
            [TrieBuildResult(root=b"") for _ in range(n)], start_depth,
            slot_base)
        whole = run.half1(
            meta_rec, keys, [TrieBuildResult(root=b"") for _ in range(n)],
            start_depth, slot_base).lay_in(digests)
        out.append((got, loop, whole))
    return out


def _same_nodes(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        # order too: the stage writes the nodes in iteration order
        assert list(g.branch_nodes.items()) == list(w.branch_nodes.items())
        for node in g.branch_nodes.values():
            assert type(node) is BranchNode and type(node.hashes) is tuple
            assert all(type(h) is bytes and len(h) == 32 for h in node.hashes)


@pytest.mark.parametrize("collect", [True, False], ids=["branches", "roots"])
@pytest.mark.parametrize("kind", ["numpy", "mega"])
@pytest.mark.parametrize("name", CASES)
def test_the_split_decode_is_bit_identical(monkeypatch, rebuild_layout, name,
                                           kind, collect):
    jobs, start_depth, layout = _case(name)
    rebuild_layout(**layout)
    want = _anchor_roots(name)
    committer = _committer(kind)
    run = _Run(monkeypatch, committer)
    threaded = REGISTRY.counter(THREADED).value
    got = committer.commit_hashed_pipelined(jobs, collect, start_depth)
    assert [r.root for r in got] == want
    if name == "one_threaded_job":
        assert REGISTRY.counter(THREADED).value - threaded == 1
    if not collect:
        assert run.events == ["fetch_slots"] and not run.groups
        assert all(r.branch_nodes == {} for r in got)
        return
    assert len(run.backends) == 1
    n_groups = len(turbo._group_jobs(jobs, turbo.LEAVES_PER_SWEEP))
    assert len(run.groups) == n_groups
    if name == "rebased_groups":
        assert n_groups == 3 and run.groups[-1][-1] > 0  # slot_base
    records = 0
    for group, loop, whole in _oracle(run):
        _same_nodes(group, loop)
        _same_nodes(group, whole)
        records += sum(len(r.branch_nodes) for r in group)
    assert records == sum(len(g[0]) for g in run.groups) > 0


@pytest.mark.parametrize("kind", ["numpy", "mega"])
def test_launch_then_the_first_half_then_finish_then_the_second(
        monkeypatch, rebuild_layout, kind):
    """The mega engine has a ``launch``; the numpy twin has none, and runs
    the same halves in the same order with ``finish`` between them."""
    jobs, start_depth, layout = _case("rebased_groups")
    rebuild_layout(**layout)
    committer = _committer(kind)
    run = _Run(monkeypatch, committer)
    committer.commit_hashed_pipelined(jobs, True, start_depth)
    halves = ["half1"] * 3 + ["finish"] + ["half2"] * 3
    assert run.events == (["launch"] if kind == "mega" else []) + halves


def test_backends_without_launch_give_the_same_results(monkeypatch,
                                                       rebuild_layout):
    jobs, start_depth, layout = _case("small_tries_then_a_large_one")
    rebuild_layout(**layout)
    out = {}
    for hide in (False, True):
        committer = _committer("mega")
        run = _Run(monkeypatch, committer, hide_launch=hide)
        out[hide] = committer.commit_hashed_pipelined(jobs, True, start_depth)
        assert ("launch" in run.events) is not hide
    twin = _committer("numpy").commit_hashed_pipelined(jobs, True, start_depth)
    for results in (out[True], twin):
        assert [r.root for r in results] == [r.root for r in out[False]]
        _same_nodes(results, out[False])


class _Tripwire:
    """Digests that raise at any touch."""

    def __getattr__(self, name):
        raise AssertionError(f"a digest was read: {name}")

    def __getitem__(self, key):
        raise AssertionError("a digest was read")


class _LaunchingTwin(turbo._NumpyBackend):
    """The numpy twin with a ``launch``: from it until ``finish`` its
    digest buffer is a tripwire, so the first half can read none."""

    def launch(self):
        self._kept, self._buf = self._buf, _Tripwire()

    def finish(self):
        self._buf = self._kept
        return super().finish()


def test_the_first_half_reads_no_digest(monkeypatch, rebuild_layout):
    jobs, start_depth, layout = _case("rebased_groups")
    rebuild_layout(**layout)
    committer = TurboCommitter(backend="numpy")
    monkeypatch.setattr(committer, "_make_backend",
                        lambda: _LaunchingTwin(arena=committer.arena))
    got = committer.commit_hashed_pipelined(jobs, True, start_depth)
    want = TurboCommitter(backend="numpy").commit_hashed_pipelined(
        jobs, True, start_depth)
    assert [r.root for r in got] == [r.root for r in want]
    _same_nodes(got, want)
    # and the second half is where they are read
    lib = turbo.load_library()
    sw = turbo._sweep_group(lib, jobs[:1], range(1), True, start_depth)
    pending = turbo._collect_meta_records(
        sw.meta_rec, sw.keys, [TrieBuildResult(root=b"")], start_depth)
    with pytest.raises(AssertionError, match="a digest was read"):
        pending.lay_in(_Tripwire())


class _FailingFinish(turbo._NumpyBackend):
    def finish(self):
        raise RuntimeError("the device was lost")


@pytest.mark.parametrize("where", ["window", "finish"])
def test_an_aborted_commit_leaks_no_lease_and_no_half_made_node(
        monkeypatch, rebuild_layout, where):
    """Aborted at a window boundary (the pipeline-abort injector: before
    either half) or in ``finish`` (after the first half): the lease is
    dropped, and no result the first half made holds a node."""
    from reth_tpu.metrics import MetricsRegistry
    from reth_tpu.ops.hash_service import HashService
    from reth_tpu.ops.supervisor import FaultInjector, InjectedPipelineAbort
    from reth_tpu.primitives.keccak import keccak256_batch_np

    jobs, start_depth, layout = _case("rebased_groups")
    rebuild_layout(**layout)
    svc = HashService(backend=keccak256_batch_np, registry=MetricsRegistry(),
                      min_tier=8, window_s=0.001)
    committer = TurboCommitter(backend="device", hash_service=svc)
    if where == "window":
        committer.supervisor = type(
            "S", (), {"injector": FaultInjector(pipeline_abort=1)})()
        fails, engine = InjectedPipelineAbort, turbo._NumpyBackend
    else:
        fails, engine = RuntimeError, _FailingFinish
    committer._device_engine = lambda: engine(arena=committer.arena)
    run = _Run(monkeypatch, committer)
    try:
        with pytest.raises(fails):
            committer.commit_hashed_pipelined(jobs, True, start_depth)
        with svc._cond:
            assert not svc._leased
        assert len(run.groups) == (0 if where == "window" else 3)
        assert "half2" not in run.events
        for *_, results, _sd, _base in run.groups:
            assert all(r.branch_nodes == {} for r in results)
    finally:
        svc.stop()


@pytest.mark.parametrize("kind", ["numpy", "mega"])
def test_the_predecode_phase_and_records_move_once_a_commit(kind):
    jobs, start_depth, _ = _case("small_tries_then_a_large_one")
    committer = _committer(kind)
    before = {n: REGISTRY.counter(n).value for n in PREDECODE}
    tracing.set_trace_enabled(True)
    try:
        rec = tracing.flight_recorder()
        n0 = rec.recorded
        got = committer.commit_hashed_pipelined(jobs, True, start_depth)
        spans = rec.snapshot()[-(rec.recorded - n0):]
    finally:
        tracing.set_trace_enabled(False)
    moved = {n: REGISTRY.counter(n).value - before[n] for n in PREDECODE}
    n_records = sum(len(r.branch_nodes) for r in got)
    assert (moved["trie_commit_predecode_records_total"]
            == moved["trie_commit_decode_records_total"] == n_records > 0)
    predecode = [s for s in spans
                 if (s["target"], s["name"]) == ("trie::commit", "predecode")]
    assert len(predecode) == 1
    assert moved["trie_commit_predecode_seconds_total"] > 0
    (run,) = [s for s in spans
              if (s["target"], s["name"]) == ("trie::pipeline", "rebuild")]
    assert run["fields"]["overlapped"] is (kind == "mega")
    assert run["fields"]["predecode_s"] == pytest.approx(
        moved["trie_commit_predecode_seconds_total"], abs=1e-4)


def test_mega_launch_starts_the_programs_once():
    from reth_tpu.ops.fused_commit import MegaFusedEngine

    lib = turbo.load_library()
    jobs, start_depth, _ = _case("rebased_groups")
    sw = turbo._sweep_group(lib, jobs, range(len(jobs)), False, start_depth)

    def staged():
        engine = MegaFusedEngine(min_tier=8)
        engine.begin(sw.max_slot)
        for m in turbo._pack_window([(0, sw)]):
            engine.dispatch_packed(m.flat, m.row_off, m.row_len, m.row_slot,
                                   m.holes, m.b_tier)
            engine.dispatch_branch(m.masks, m.bmp_slot, m.children)
        return engine

    engine = staged()
    assert engine.dispatches == 0
    engine.launch()
    launched = engine.dispatches
    assert launched > 0 and not engine._plan
    engine.launch()
    assert engine.dispatches == launched
    assert np.array_equal(engine.finish(), staged().finish())


def test_nodes_whose_hashes_come_later_are_plain_branch_nodes():
    masks = [(0x8421, 0x0001, 0x8001), (0x00F0, 0, 0), (0xFFFF, 0x0F0F, 0x0003)]
    hashes = [(b"\x01" * 32, b"\x02" * 32), (), (b"\x03" * 32, b"\x04" * 32)]
    lay_in = branch_nodes_hashed_later(*map(list, zip(*masks)))
    nodes = lay_in(iter(hashes))
    want = [BranchNode(*m, h) for m, h in zip(masks, hashes)]
    assert nodes == want
    assert list(map(hash, nodes)) == list(map(hash, want))
    assert nodes[0].child_hash(15) == b"\x02" * 32
    with pytest.raises(dataclasses.FrozenInstanceError):
        nodes[0].hashes = ()
    assert not hasattr(nodes[0], "__dict__")
