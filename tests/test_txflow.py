"""Production write path: firehose -> continuous block production.

Covers the PR-18 surfaces end to end:

- ``BlockProducer`` differential correctness: at pool-sequence parity the
  standing hot candidate must be **bit-identical** to a from-scratch serial
  greedy build over a clone of the pool (same selection, same order), under
  randomized submission mixes, nonce-gap promotion, blob-fee gating, and
  same-slot replacement races.
- ``TxBatcher`` bounded backpressure: synchronous shedding with
  ``PoolOverloaded`` carrying ``retry_after_s``, surfaced over RPC as
  ``-32005`` with structured ``error.data``.
- ``ReplicaPoolView``: the ``pt_*`` feed record family (snapshot anchor,
  incremental add/replace/drop/canon, gap detection -> resubscribe).
- Pool event plane: monotonic ``seq`` and the add/replace/drop/canon kinds
  the feed publisher relies on.
- Node wiring for ``continuous_build`` plus the chaos ``pool`` domain and
  the ``txflow`` bench mode (slow drills).
"""

import json
import os
import random
import subprocess
import sys
import time
from pathlib import Path

import pytest

from reth_tpu.engine import EngineTree
from reth_tpu.engine.local import LocalMiner
from reth_tpu.payload import build_payload
from reth_tpu.payload.producer import BlockProducer
from reth_tpu.pool import PoolError, PoolOverloaded, TransactionPool, TxBatcher
from reth_tpu.primitives import Account
from reth_tpu.primitives.keccak import keccak256_batch_np
from reth_tpu.primitives.types import Transaction
from reth_tpu.storage import MemDb, ProviderFactory
from reth_tpu.storage.genesis import init_genesis
from reth_tpu.testing import ChainBuilder, Wallet
from reth_tpu.trie import TrieCommitter

CPU = TrieCommitter(hasher=keccak256_batch_np)

SINK = b"\x0f" * 20


def make_env(n_wallets=3, cancun=False):
    wallets = [Wallet(0x7F000 + i) for i in range(n_wallets)]
    builder = ChainBuilder(
        {w.address: Account(balance=10**21) for w in wallets},
        committer=CPU, cancun=cancun,
    )
    factory = ProviderFactory(MemDb())
    init_genesis(factory, builder.genesis, builder.accounts_at_genesis, committer=CPU)
    tree = EngineTree(factory, committer=CPU, persistence_threshold=2)
    pool = TransactionPool(lambda: tree.overlay_provider())
    pool.base_fee = 10**9
    return tree, pool, wallets


@pytest.fixture
def producer_env():
    tree, pool, wallets = make_env()
    prod = BlockProducer(tree, pool, interval=0.01)
    prod.start()
    try:
        yield tree, pool, wallets, prod
    finally:
        prod.stop()


def wait_parity(prod, pool, tree, timeout=10.0):
    """Wait until the hot candidate has caught up with every pool event,
    then return (selected_hashes, parent_hash, attrs) as one atomic read."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with prod._lock:
            cand = prod.candidate
            if (cand is not None and cand.window is None
                    and cand.parent_hash == tree.head_hash
                    and cand.pool_seq == pool.event_seq):
                return ([t.hash for t in cand.selected], cand.parent_hash,
                        cand.attrs)
        time.sleep(0.005)
    raise AssertionError(
        f"producer never reached pool parity: {prod.snapshot()}")


def clone_pool(pool):
    """Fresh pool with identical contents, replayed in submission order so
    the selection heap's tie-breaks (submission_id) match the original."""
    clone = TransactionPool(pool.state_reader, config=pool.config)
    clone.base_fee = pool.base_fee
    clone.blob_base_fee = pool.blob_base_fee
    with pool._lock:
        pooled = sorted(pool.by_hash.values(), key=lambda p: p.submission_id)
        for p in pooled:
            if p.tx.tx_type == 3:
                clone.add_blob_transaction(p.tx, pool.get_blob_sidecar(p.tx.hash))
            else:
                clone.add_transaction(p.tx, sender=p.sender)
    return clone


def serial_selection(tree, pool, parent, attrs):
    """From-scratch greedy build over a pool clone — the reference the
    incremental producer must match bit-for-bit."""
    block, _fees = build_payload(tree, clone_pool(pool), parent, attrs)
    return [t.hash for t in block.transactions]


# -- producer differential correctness ---------------------------------------


def test_producer_matches_serial_greedy_randomized(producer_env):
    tree, pool, wallets, prod = producer_env
    rng = random.Random(0x7AF10)
    miner = LocalMiner(tree, pool, producer=prod)
    for rnd in range(4):
        for _ in range(rng.randint(4, 10)):
            w = rng.choice(wallets)
            tip = rng.choice([10**9, 2 * 10**9, 5 * 10**9])
            tx = w.transfer(SINK, rng.randint(1, 10**6),
                            max_priority_fee_per_gas=tip)
            pool.add_transaction(tx)
            roll = rng.random()
            if roll < 0.25:
                repl = w.sign_tx(Transaction(
                    tx_type=2, chain_id=1, nonce=tx.nonce,
                    max_fee_per_gas=tx.max_fee_per_gas * 2,
                    max_priority_fee_per_gas=tip * 2,
                    gas_limit=21_000, to=SINK, value=7), bump_nonce=False)
                pool.add_transaction(repl)
            elif roll < 0.40:
                with pytest.raises(PoolError, match="already known"):
                    pool.add_transaction(tx)
        got, parent, attrs = wait_parity(prod, pool, tree)
        want = serial_selection(tree, pool, parent, attrs)
        assert got == want, f"round {rnd}: producer diverged from serial greedy"
        blk = miner.mine_block()
        assert [t.hash for t in blk.transactions] == got
    assert miner.producer_seals == 4 and miner.serial_builds == 0
    snap = prod.snapshot()
    assert snap["sealed"] == 4 and snap["errors"] == 0
    assert prod.hits >= 1


def test_producer_nonce_gap_promotion_is_incremental(producer_env):
    tree, pool, wallets, prod = producer_env
    w = wallets[0]
    t0 = w.transfer(SINK, 1)                       # nonce 0
    w.nonce = 2
    t2 = w.transfer(SINK, 3)                       # nonce 2 (gapped)
    w.nonce = 1
    t1 = w.transfer(SINK, 2)                       # the gap filler
    pool.add_transaction(t0)
    pool.add_transaction(t2)
    got, _, _ = wait_parity(prod, pool, tree)
    assert got == [t0.hash]                        # t2 queued behind the gap
    rebuilds = prod.full_rebuilds
    ranks = prod.exec_ranks
    pool.add_transaction(t1)                       # promotes t1 AND t2
    got, parent, attrs = wait_parity(prod, pool, tree)
    assert got == [t0.hash, t1.hash, t2.hash]
    # the promotion extends the candidate from the considered-trace suffix:
    # new execution happened, but never a from-scratch rebuild
    assert prod.full_rebuilds == rebuilds
    assert prod.exec_ranks >= ranks + 2
    assert got == serial_selection(tree, pool, parent, attrs)


def test_producer_replacement_race_and_single_slot_mined(producer_env):
    tree, pool, wallets, prod = producer_env
    w = wallets[0]
    base = w.transfer(SINK, 10)
    pool.add_transaction(base)
    got, _, _ = wait_parity(prod, pool, tree)
    assert got == [base.hash]
    repl = w.sign_tx(Transaction(
        tx_type=2, chain_id=1, nonce=base.nonce,
        max_fee_per_gas=base.max_fee_per_gas * 2,
        max_priority_fee_per_gas=base.max_priority_fee_per_gas * 2,
        gas_limit=21_000, to=SINK, value=11), bump_nonce=False)
    pool.add_transaction(repl)
    # +5% on the *original* fees is far below the 10% bump over the live
    # occupant (already at 2x) -> rejected, candidate untouched
    under = w.sign_tx(Transaction(
        tx_type=2, chain_id=1, nonce=base.nonce,
        max_fee_per_gas=base.max_fee_per_gas * 105 // 100,
        max_priority_fee_per_gas=base.max_priority_fee_per_gas * 105 // 100,
        gas_limit=21_000, to=SINK, value=12), bump_nonce=False)
    with pytest.raises(PoolError, match="underpriced"):
        pool.add_transaction(under)
    got, parent, attrs = wait_parity(prod, pool, tree)
    assert got == [repl.hash]                      # slot raced, winner only
    assert got == serial_selection(tree, pool, parent, attrs)
    blk = LocalMiner(tree, pool, producer=prod).mine_block()
    assert [t.hash for t in blk.transactions] == [repl.hash]
    # the slot is spent: even a 10x late replacement is nonce-too-low now
    late = w.sign_tx(Transaction(
        tx_type=2, chain_id=1, nonce=base.nonce,
        max_fee_per_gas=base.max_fee_per_gas * 10,
        max_priority_fee_per_gas=base.max_priority_fee_per_gas * 10,
        gas_limit=21_000, to=SINK, value=13), bump_nonce=False)
    with pytest.raises(PoolError, match="nonce too low"):
        pool.add_transaction(late)


def test_producer_blob_fee_gating():
    from tests.test_blob_pool import make_sidecar

    tree, pool, wallets = make_env(cancun=True)
    w = wallets[0]
    sidecar = make_sidecar(n_blobs=1, seed=7)
    blob_tx = w.sign_tx(Transaction(
        tx_type=3, chain_id=1, nonce=0, max_fee_per_gas=10**10,
        max_priority_fee_per_gas=10**9, gas_limit=21_000, to=SINK,
        max_fee_per_blob_gas=5,
        blob_versioned_hashes=sidecar.versioned_hashes()))
    plain = wallets[1].transfer(SINK, 1)
    prod = BlockProducer(tree, pool, interval=0.01)
    prod.start()
    try:
        pool.add_blob_transaction(blob_tx, sidecar)
        pool.add_transaction(plain)
        # blob market spikes above the tx's cap: the candidate must shed
        # the blob tx while keeping the plain one
        pool.on_canonical_state_change(10**9, blob_base_fee=50)
        got, _, _ = wait_parity(prod, pool, tree)
        assert got == [plain.hash]
        # market cools below the cap: blob tx flows back in, and the hot
        # candidate still matches a from-scratch build over a pool clone
        pool.on_canonical_state_change(10**9, blob_base_fee=3)
        got, parent, attrs = wait_parity(prod, pool, tree)
        assert blob_tx.hash in got and plain.hash in got
        assert got == serial_selection(tree, pool, parent, attrs)
    finally:
        prod.stop()


# -- firehose backpressure ---------------------------------------------------


def test_batcher_sheds_with_retry_after_when_saturated():
    tree, pool, wallets = make_env(1)
    w = wallets[0]
    batcher = TxBatcher(pool, max_batch=1, max_queue=4, retry_after_s=0.25)
    try:
        futs = []
        shed = None
        with pool._lock:                 # wedge the insert worker mid-batch
            for i in range(64):
                f = batcher.submit(w.transfer(SINK, i + 1))
                futs.append(f)
                if f.done():             # only sheds fail synchronously
                    shed = f
                    break
                time.sleep(0.005)
            assert shed is not None, "queue never saturated"
            err = shed.exception()
            assert isinstance(err, PoolOverloaded)
            assert isinstance(err, PoolError)
            assert err.retry_after_s == 0.25
            assert batcher.sheds >= 1
        # lock released: the queued (non-shed) futures must all resolve
        for f in futs[:-1]:
            assert isinstance(f.result(timeout=10), bytes)
        assert batcher.processed == len(futs) - 1
        assert batcher.batches >= 1
    finally:
        batcher.close()


def test_rpc_send_raw_transaction_sheds_as_32005():
    from reth_tpu.rpc.eth import EthApi
    from reth_tpu.rpc.server import RpcError

    tree, pool, wallets = make_env(1)
    w = wallets[0]
    batcher = TxBatcher(pool, max_batch=1, max_queue=1, retry_after_s=0.7)
    api = EthApi(tree, pool=pool, tx_batcher=batcher)
    try:
        with pool._lock:                 # wedge the worker; saturate the queue
            saturated = False
            for i in range(64):
                f = batcher.submit(w.transfer(SINK, i + 1))
                if f.done():
                    saturated = True
                    break
                time.sleep(0.005)
            assert saturated
            raw = "0x" + w.transfer(SINK, 999).encode().hex()
            with pytest.raises(RpcError) as ei:
                api.eth_sendRawTransaction(raw)
        assert ei.value.code == -32005
        assert ei.value.data["class"] == "tx"
        assert ei.value.data["retry_after"] == 0.7
    finally:
        batcher.close()


# -- pool event plane + pt_* replica view ------------------------------------


def test_pool_event_plane_kinds_and_sequencing():
    tree, pool, wallets = make_env(1)
    w = wallets[0]
    events = []
    pool.add_listener(events.append)
    t0 = w.transfer(SINK, 1)
    pool.add_transaction(t0)
    w.nonce = 0
    repl = w.transfer(SINK, 2, max_fee_per_gas=200 * 10**9,
                      max_priority_fee_per_gas=2 * 10**9)
    pool.add_transaction(repl)
    t1 = w.transfer(SINK, 3)                       # nonce 1
    pool.add_transaction(t1)
    pool.remove_invalid(t1.hash)
    pool.on_canonical_state_change(2 * 10**9)
    assert [e["kind"] for e in events] == [
        "add", "replace", "add", "drop", "canon"]
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert events[1]["old_hash"] == t0.hash
    assert events[1]["tx"].hash == repl.hash
    assert events[3]["reason"] == "invalid"
    assert events[4]["base_fee"] == 2 * 10**9
    pool.remove_listener(events.append)


def test_replica_pool_view_pt_record_family():
    from reth_tpu.fleet.replica import ReplicaPoolView

    w = Wallet(0xB10B)
    t0 = w.transfer(SINK, 1)
    t1 = w.transfer(SINK, 2)
    w.nonce = 1
    t1b = w.transfer(SINK, 3, max_fee_per_gas=200 * 10**9,
                     max_priority_fee_per_gas=2 * 10**9)
    view = ReplicaPoolView()
    # incremental records are ignored until a snapshot anchors the view
    assert view.apply({"type": "pt_add", "seq": 1, "tx": t0.encode(),
                       "sender": w.address}) == "ok"
    assert view.seq == -1 and not view.txs
    assert view.apply({"type": "pt_snapshot", "seq": 4, "base_fee": 10**9,
                       "blob_base_fee": 1,
                       "txs": [(t0.encode(), w.address)]}) == "ok"
    assert view.seq == 4 and t0.hash in view.txs
    # records at or below the snapshot seq are already folded in
    assert view.apply({"type": "pt_add", "seq": 4, "tx": t1.encode(),
                       "sender": w.address}) == "ok"
    assert t1.hash not in view.txs
    assert view.apply({"type": "pt_add", "seq": 5, "tx": t1.encode(),
                       "sender": w.address}) == "ok"
    assert view.by_sender[w.address][1] == t1.hash
    # replacement evicts the old hash and takes the (sender, nonce) slot
    assert view.apply({"type": "pt_replace", "seq": 6, "tx": t1b.encode(),
                       "old_hash": t1.hash, "sender": w.address}) == "ok"
    assert t1.hash not in view.txs
    assert view.by_sender[w.address][1] == t1b.hash
    assert view.apply({"type": "pt_canon", "seq": 7, "base_fee": 2 * 10**9,
                       "blob_base_fee": 3}) == "ok"
    assert view.base_fee == 2 * 10**9 and view.blob_base_fee == 3
    assert view.apply({"type": "pt_drop", "seq": 8, "hash": t1b.hash}) == "ok"
    assert t1b.hash not in view.txs
    # a seq gap means lost records: reset to unsynced and ask to resubscribe
    assert view.apply({"type": "pt_drop", "seq": 10, "hash": t0.hash}) == "gap"
    assert view.seq == -1
    assert view.records >= 4 and view.snapshots == 1


# -- node wiring + chaos matrix ----------------------------------------------


def test_node_continuous_build_wiring():
    from reth_tpu.node import Node, NodeConfig

    w = Wallet(0xA11CE)
    builder = ChainBuilder({w.address: Account(balance=10**21)}, committer=CPU)
    cfg = NodeConfig(dev=True, genesis_header=builder.genesis,
                     genesis_alloc=builder.accounts_at_genesis,
                     continuous_build=True, http_port=0, authrpc_port=0)
    node = Node(cfg, committer=CPU)
    try:
        node.start_rpc()
        assert node.producer is not None
        assert node.miner.producer is node.producer
        assert node.payload_service.producer is node.producer
        # firehose -> hot candidate -> sealed through the producer
        node.tx_batcher.add_sync(w.transfer(SINK, 123))
        blk = node.miner.mine_block()
        assert len(blk.transactions) == 1
        assert node.miner.producer_seals == 1
        assert node.miner.serial_builds == 0
        snap = node.producer.snapshot()
        assert snap["sealed"] == 1 and snap["errors"] == 0
        # the ranks gauge re-anchors to 0 once the mined txs leave the
        # pool, even though the rebuild-to-empty is not a stream-changing
        # refresh
        from reth_tpu.metrics import producer_metrics
        deadline = time.monotonic() + 10
        while (time.monotonic() < deadline
               and producer_metrics.last.get("ranks") != 0):
            time.sleep(0.01)
        assert producer_metrics.last.get("ranks") == 0
        # producer_status rides the normal RPC dispatch
        resp = json.loads(node.rpc.handle(json.dumps(
            {"jsonrpc": "2.0", "id": 1, "method": "producer_status",
             "params": []}).encode()))
        assert resp["result"]["sealed"] == 1
    finally:
        node.stop()


def test_pool_scenario_deterministic_and_isolated():
    from reth_tpu.chaos import (
        make_fleet_scenario,
        make_ha_scenario,
        make_pool_scenario,
        make_scenario,
    )

    for seed in (1, 5, 9):
        a, b = make_pool_scenario(seed), make_pool_scenario(seed)
        assert a == b
        assert a["domain"] == "pool" and a["mode"] == "kill"
        assert 4 <= a["kill_after"] <= 7
    # own rng stream: drawing other domains' scenarios must not perturb it
    before = make_pool_scenario(3)
    make_scenario(3), make_fleet_scenario(3), make_ha_scenario(3)
    assert make_pool_scenario(3) == before
    # the seed actually varies the matrix
    assert any(make_pool_scenario(s) != make_pool_scenario(1)
               for s in range(2, 6))


@pytest.mark.slow
def test_pool_chaos_single_seed(tmp_path):
    from reth_tpu.chaos import make_pool_scenario, run_pool_scenario

    scn = make_pool_scenario(1)
    res = run_pool_scenario(scn, tmp_path, timeout=420)
    assert res.get("ok") is True, res
    inv = res.get("invariants", {})
    for k in ("head_consistent", "loss_bound", "no_stuck_candidate",
              "liveness", "replacement_semantics", "replacement_mined",
              "replica_pending_view", "no_leaked_lease"):
        assert inv.get(k) is True, (k, res)


@pytest.mark.slow
def test_pool_chaos_campaign_ten_seeds(tmp_path):
    from reth_tpu.chaos import run_campaign

    results = run_campaign(range(1, 11), tmp_path, domain="pool")
    assert len(results) == 10
    bad = [r for r in results if not r.get("ok")]
    assert not bad, bad


@pytest.mark.slow
def test_bench_txflow_mode_end_to_end():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RETH_TPU_FAULT_")}
    env.update(JAX_PLATFORMS="cpu", RETH_TPU_BENCH_MODE="txflow",
               RETH_TPU_BENCH_TXFLOW_RATES="800",
               RETH_TPU_BENCH_TXFLOW_WALLETS="6",
               RETH_TPU_BENCH_TXFLOW_TXS="4")
    repo = Path(__file__).resolve().parent.parent
    r = subprocess.run([sys.executable, str(repo / "bench.py")],
                       capture_output=True, text=True, timeout=560,
                       env=env, cwd=repo)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["metric"] == "txflow_inclusion_p99_ms"
    assert line.get("error") is None, line
    assert line["value"] > 0
