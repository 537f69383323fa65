"""ETL collector + chunked resumable MerkleStage rebuild.

Covers VERDICT round-1 next-round #5: kill -9 mid-rebuild, restart, same
root (real SIGKILL over the durable native KV engine), plus in-process
chunk-boundary resume and >buffer ETL spills.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time

import pytest

from reth_tpu.etl import Collector
from reth_tpu.primitives.keccak import keccak256_batch_np
from reth_tpu.primitives.types import Account
from reth_tpu.stages import default_stages
from reth_tpu.stages.api import ExecInput, Pipeline
from reth_tpu.stages.merkle import MerkleStage
from reth_tpu.storage.genesis import import_chain, init_genesis
from reth_tpu.storage.kv import MemDb
from reth_tpu.storage.provider import ProviderFactory
from reth_tpu.testing import ChainBuilder, Wallet
from reth_tpu.trie.committer import TrieCommitter

CPU = TrieCommitter(hasher=keccak256_batch_np)
CPU.turbo_backend = "numpy"


# -- ETL ---------------------------------------------------------------------


def test_etl_sorted_iteration_with_spills():
    col = Collector(buffer_bytes=512)  # force many spill files
    items = [(os.urandom(8), os.urandom(16)) for _ in range(500)]
    for k, v in items:
        col.insert(k, v)
    got = list(col)
    assert got == sorted(items, key=lambda kv: kv[0])
    assert len(col._files) > 1, "expected disk spills"
    col.close()


def test_etl_duplicate_keys_stable_order():
    with Collector(buffer_bytes=64) as col:
        for i in range(50):
            col.insert(b"same", bytes([i]))
        assert [v for _, v in col] == [bytes([i]) for i in range(50)]


def test_etl_empty():
    with Collector() as col:
        assert list(col) == []


# -- chunked rebuild ---------------------------------------------------------

STORE = bytes.fromhex("5f355f5500")


def _initcode(runtime):
    n = len(runtime)
    return bytes([0x60, n, 0x60, 0x0B, 0x5F, 0x39, 0x60, n, 0x5F, 0xF3]) + b"\x00" + runtime


def _build_chain():
    a = Wallet(0xAAA1)
    bld = ChainBuilder({a.address: Account(balance=10**21)}, committer=CPU)
    bld.build_block([a.deploy(_initcode(STORE))])
    contract = next(
        addr for addr, acc in bld.accounts.items()
        if bld.codes.get(acc.code_hash) == STORE
    )
    bld.build_block(
        [a.transfer(bytes([i + 1] * 20), 10**10 + i) for i in range(10)]
        + [a.call(contract, (0xAB01).to_bytes(32, "big"))]
    )
    bld.build_block([a.transfer(bytes([i + 11] * 20), 10**10 + i) for i in range(10)])
    return bld


def _synced_factory(bld):
    factory = ProviderFactory(MemDb())
    init_genesis(factory, bld.genesis, dict(bld.accounts_at_genesis),
                 dict(bld.storage_at_genesis), dict(bld.codes_at_genesis),
                 committer=CPU)
    import_chain(factory, bld.blocks[1:])
    return factory


def test_chunked_rebuild_matches_header_root():
    bld = _build_chain()
    factory = _synced_factory(bld)
    stages = default_stages(committer=CPU)
    for s in stages:
        if isinstance(s, MerkleStage):
            s.chunk_leaves = 4  # force many chunks
    Pipeline(factory, stages).run(bld.tip.number)  # raises on root mismatch
    with factory.provider() as p:
        assert p.stage_progress(MerkleStage.id) is None  # progress cleared


def test_chunked_rebuild_resumes_after_interruption():
    """Drive the chunked stage to a mid-rebuild progress blob, then finish
    with a FRESH stage instance (all context from the persisted blob)."""
    bld = _build_chain()
    factory = _synced_factory(bld)
    # run the earlier stages so hashed tables exist
    stages = default_stages(committer=CPU)
    pre = [s for s in stages if not isinstance(s, MerkleStage)]
    merkle_idx = next(i for i, s in enumerate(stages) if isinstance(s, MerkleStage))
    Pipeline(factory, stages[:merkle_idx]).run(bld.tip.number)

    stage = MerkleStage(CPU, chunk_leaves=4)
    target = bld.tip.number
    for _ in range(3):  # a few chunks, committing each
        with factory.provider_rw() as p:
            out = stage.execute(p, ExecInput(target, 0))
        assert not out.done
    with factory.provider() as p:
        blob = p.stage_progress(MerkleStage.id)
        assert blob is not None, "expected mid-rebuild progress"

    # "crash": new stage object, resume purely from the blob
    resumed = MerkleStage(CPU, chunk_leaves=4)
    for _ in range(500):
        with factory.provider_rw() as p:
            out = resumed.execute(p, ExecInput(target, 0))
        if out.done:
            break
    assert out.done and out.checkpoint == target
    with factory.provider() as p:
        assert p.stage_progress(MerkleStage.id) is None
    # and the trie tables it left behind satisfy the full verifier
    from reth_tpu.trie.incremental import verify_state_root

    with factory.provider_rw() as p:
        root, problems = verify_state_root(p, CPU)
    assert problems == []
    assert root == bld.tip.state_root


def test_stale_target_progress_restarts_rebuild():
    """Progress bound to an older sync target is discarded, not stitched
    into a mixed-state root (review finding)."""
    bld = _build_chain()
    factory = _synced_factory(bld)
    stages = default_stages(committer=CPU)
    merkle_idx = next(i for i, s in enumerate(stages) if isinstance(s, MerkleStage))
    Pipeline(factory, stages[:merkle_idx]).run(bld.tip.number)

    stage = MerkleStage(CPU, chunk_leaves=4)
    old_target = bld.tip.number - 1
    for _ in range(2):  # leave stale progress behind for old_target
        with factory.provider_rw() as p:
            stage.execute(p, ExecInput(old_target, 0))
    with factory.provider() as p:
        assert p.stage_progress(MerkleStage.id) is not None

    # full pipeline to the REAL tip must restart the rebuild and succeed
    run_stages = default_stages(committer=CPU)
    for s in run_stages:
        if isinstance(s, MerkleStage):
            s.chunk_leaves = 4
    Pipeline(factory, run_stages).run(bld.tip.number)
    with factory.provider() as p:
        assert p.stage_progress(MerkleStage.id) is None


def test_storage_chunk_hands_whole_tries_in_hashed_address_order():
    """What ``_storage_chunk`` gives ``_commit_subtries``: WHOLE storage tries
    taken in hashed-address order until the chunk holds ``chunk_leaves``
    slots, each a job of its slots' keys ascending with RLP values of 1-33
    bytes, committed at ``start_depth`` 0. The benchmark's storage cell
    copies this shape (``benchmark/harness/traffic_storage.py``)."""
    import numpy as np

    from reth_tpu.primitives.rlp import encode_int, rlp_encode
    from reth_tpu.primitives.types import EMPTY_ROOT_HASH

    rng = np.random.default_rng(33)
    sizes = [1, 3, 1, 6, 2, 1, 4]                 # in hashed-address order
    addrs = sorted(bytes(rng.integers(0, 256, 32, dtype=np.uint8))
                   for _ in sizes)
    table = {}
    factory = ProviderFactory(MemDb())
    with factory.provider_rw() as p:
        for addr, n in zip(addrs, sizes):
            slots = sorted(bytes(rng.integers(0, 256, 32, dtype=np.uint8))
                           for _ in range(n))
            # values of every width: a flag, a short integer, a full word
            values = [int(v) for v in rng.choice(
                [1, 0x7F, 0x80, 0x1234, 2**160 - 3, 2**256 - 1], size=n)]
            table[addr] = list(zip(slots, values))
            for slot, value in reversed(table[addr]):   # written unordered
                p.put_hashed_storage(addr, slot, value)
    stage = MerkleStage(CPU, chunk_leaves=5)
    calls = []

    def spy(jobs, start_depth=0):
        calls.append((jobs, start_depth))
        return [type("R", (), {"root": EMPTY_ROOT_HASH, "branch_nodes": {}})()
                for _ in jobs]

    stage._commit_subtries = spy
    tb = (7).to_bytes(8, "big")
    with factory.provider_rw() as p:
        p.save_stage_progress(stage.id, b"S" + tb)
        for _ in range(4):
            stage._storage_chunk(p, tb, p.stage_progress(stage.id)[9:])
        assert p.stage_progress(stage.id) == b"A" + tb   # the phase is over
    # 1 + 3 + 1 = 5 closes the first chunk; 6; 2 + 1 + 4 = 7 (a trie is
    # never split: the chunk that reaches chunk_leaves keeps its last whole)
    assert [[len(v) for _, v in jobs] for jobs, _ in calls] == [
        [1, 3, 1], [6], [2, 1, 4]]
    assert all(depth == 0 for _, depth in calls)
    handed = [job for jobs, _ in calls for job in jobs]
    for addr, (keys, values) in zip(addrs, handed):
        assert keys.dtype == np.uint8 and keys.shape == (len(values), 32)
        assert [bytes(k) for k in keys] == [s for s, _ in table[addr]]
        assert values == [rlp_encode(encode_int(v)) for _, v in table[addr]]
        assert all(1 <= len(v) <= 33 for v in values)


def test_pipeline_abort_mid_queue_resumes_bit_identical(monkeypatch):
    """Kill the OVERLAPPED rebuild pipeline mid-queue (fault injection via
    RETH_TPU_FAULT_PIPELINE_ABORT): the aborted chunk's transaction rolls
    back, earlier committed chunks survive, and a fresh stage instance
    resumes from the persisted progress to the bit-identical root."""
    from reth_tpu.ops.supervisor import InjectedPipelineAbort

    bld = _build_chain()
    factory = _synced_factory(bld)
    stages = default_stages(committer=CPU)
    merkle_idx = next(i for i, s in enumerate(stages) if isinstance(s, MerkleStage))
    Pipeline(factory, stages[:merkle_idx]).run(bld.tip.number)

    stage = MerkleStage(CPU, chunk_leaves=4)
    target = bld.tip.number
    for _ in range(2):  # committed chunks that the abort must NOT lose
        with factory.provider_rw() as p:
            out = stage.execute(p, ExecInput(target, 0))
        assert not out.done
    with factory.provider() as p:
        before = p.stage_progress(MerkleStage.id)
    assert before is not None, "expected mid-rebuild progress"

    # every pipelined (multi-subtrie) commit now dies at its first packed
    # window — the in-process analogue of a crash while the sweep queue is
    # full. Single-job chunks take the serial path and still commit, so
    # snapshot progress before each attempt: the abort must roll back to
    # EXACTLY the last committed chunk, losing nothing else.
    monkeypatch.setenv("RETH_TPU_FAULT_PIPELINE_ABORT", "1")
    aborted = False
    snap = before
    for _ in range(300):
        with factory.provider() as p:
            snap = p.stage_progress(MerkleStage.id)
        try:
            with factory.provider_rw() as p:
                out = stage.execute(p, ExecInput(target, 0))
        except InjectedPipelineAbort:
            aborted = True
            break
        if out.done:
            break
    assert aborted, "injected pipeline abort never fired"
    with factory.provider() as p:
        # the dying chunk rolled back; the committed prefix set is intact
        assert p.stage_progress(MerkleStage.id) == snap

    monkeypatch.delenv("RETH_TPU_FAULT_PIPELINE_ABORT")
    resumed = MerkleStage(CPU, chunk_leaves=4)  # fresh instance: blob only
    for _ in range(500):
        with factory.provider_rw() as p:
            out = resumed.execute(p, ExecInput(target, 0))
        if out.done:
            break
    assert out.done and out.checkpoint == target
    with factory.provider() as p:
        assert p.stage_progress(MerkleStage.id) is None
    from reth_tpu.trie.incremental import verify_state_root

    with factory.provider_rw() as p:
        root, problems = verify_state_root(p, CPU)
    assert problems == []
    assert root == bld.tip.state_root


_KILL_SCRIPT = "tests/helpers/merkle_resume_child.py"


def test_kill9_mid_rebuild_then_restart(tmp_path):
    """Real SIGKILL over the durable native KV engine: first run is killed
    mid-rebuild; the rerun must resume from the persisted chunk progress
    and finish with the correct root."""
    datadir = str(tmp_path / "db")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    def spawn(mode, slow=False):
        e = dict(env)
        if slow:
            e["MERKLE_CHILD_SLOW"] = "1"
        return subprocess.Popen(
            [sys.executable, _KILL_SCRIPT, datadir, mode],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=e, text=True,
        )

    p = spawn("init")
    out, _ = p.communicate(timeout=120)
    assert p.returncode == 0, out

    p = spawn("rebuild", slow=True)
    # wait for the CHUNK progress marker, then kill: guarantees the kill
    # lands AFTER a committed chunk regardless of machine load (a fixed
    # sleep killed during interpreter startup under parallel test runs)
    killed_mid_run = False
    # readline() blocks: a watchdog kills a wedged child so the test
    # stays bounded no matter what
    import threading

    watchdog = threading.Timer(120, p.kill)
    watchdog.start()
    try:
        while True:
            line = p.stdout.readline()
            if not line:  # child finished before any chunk boundary
                break
            if "CHUNK" in line:
                killed_mid_run = p.poll() is None
                if killed_mid_run:
                    p.send_signal(signal.SIGKILL)
                break
    finally:
        watchdog.cancel()
    p.wait(timeout=60)

    p = spawn("rebuild")
    out, _ = p.communicate(timeout=300)
    assert p.returncode == 0, out
    assert "REBUILD_OK" in out
    if killed_mid_run:
        assert "RESUMED_FROM_PROGRESS" in out, out
