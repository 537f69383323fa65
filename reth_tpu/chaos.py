"""Chaos drill engine: crash points, composed fault scenarios, invariants.

Reference analogue: reth proves its persistence thread + startup
invariants with kill-and-restart integration drills; the Reddio paper's
pipelined-execution failure modes (arxiv 2503.04595) arrive as
*compositions* — a stalled service AND a shed storm AND a process kill
— never one injector at a time. Ten PRs of this repo built fault
injectors (``RETH_TPU_FAULT_*``) that had each only ever been drilled
alone. This module is the harness that composes them and adds the one
fault no injector could express: ungraceful death.

Two layers:

- **Crash points** (:func:`crash_point`): named ``os._exit`` sites in
  the durability-critical windows — ``RETH_TPU_FAULT_CRASH_AT=
  <point>[:nth]`` kills the process the *nth* time that point is
  reached. Declared points (:data:`CRASH_POINTS`): after a WAL record
  is fsync'd but before the in-memory publish (``wal-append``), between
  the checkpoint's image swap and its manifest/truncation
  (``checkpoint-swap``), between the persistence commit and the
  in-memory bookkeeping (``advance-persistence``), mid-unwind between
  the pipeline unwind and the canonical-header surgery (``unwind``),
  and before a static-file jar's atomic rename (``jar-rename``).
- **Scenario orchestrator**: seeded compositions of the existing
  injectors + a kill (crash point or external ``SIGKILL``) against a
  subprocess dev node, then a restart that must satisfy the declared
  invariant suite: recovered head consistent and at most
  ``persistence_threshold`` blocks behind the last mined block, the
  recovered state root bit-identical both to recomputation through the
  committer and to a fault-free twin replaying the same recorded
  blocks, ``/health`` back to ``ok`` within the SLO window, and the
  node live (mines again, no leaked hash-service lease). Every scenario
  prints its seed; ``python -m reth_tpu.chaos scenario --seed N``
  replays one exactly.
- **Consensus domain** (``--domain consensus``): the same orchestrator
  over an Engine-API adversarial victim
  (:func:`child_consensus_victim`) — seeded reorg storms driven through
  ``newPayload``/``forkchoiceUpdated`` by a
  :class:`~reth_tpu.testing_actions.ForkBuilder` whose shadow tree is
  the fault-free twin: side forks at random depths, deep reorgs across
  the persistence threshold, orphan/duplicate/out-of-order payloads,
  invalid payloads and floods, hostile forkchoice targets — under the
  same composed injectors and crash points, with the same restart
  invariant suite afterwards. Half the seeds storm a hot-state-cached
  tree (trie/hot_cache.py) against the uncached twin — some with the
  ``HOTSTATE_POISON``/``HOTSTATE_EVICT_STORM`` injectors underneath —
  so every VALID is a bit-identical-root agreement across cache state,
  and the arena must end the storm with zero leaked rows.
- **Fleet domain** (``--domain fleet``): a dev full node in replica-
  fleet mode (fleet/) with replica subprocesses fed over the witness
  socket, read load through the consistent-hash gateway ring while
  blocks keep mining, and one replica SIGKILLed / wedged / lagged
  mid-load (:func:`child_fleet_victim`). Invariants: zero failed
  reads, responses bit-identical to an ungated dispatch on the full
  node, the ring converges around the lost replica, and the survivor's
  validated head catches back up.

The module stays import-light: storage (wal.py, kv.py, nippyjar.py) and
the engine tree import :func:`crash_point` at module load; everything
heavy is imported inside the child/orchestrator entry points.
"""

from __future__ import annotations

import json
import os
import signal
import struct
import subprocess
import sys
import time
import zlib
from pathlib import Path

CRASH_POINTS = (
    "wal-append",          # record fsync'd, publish pending (storage/wal.py)
    "checkpoint-swap",     # image swapped, manifest/truncate pending
    "advance-persistence", # persistence committed, tree bookkeeping pending
    "unwind",              # pipeline unwound, canonical surgery pending
    "jar-rename",          # jar bytes fsync'd, atomic rename pending
)

_hits: dict[str, int] = {}


def reset_crash_counts() -> None:
    """Test hook: forget per-point hit counters (they are process-wide)."""
    _hits.clear()


def crash_spec() -> tuple[str, int] | None:
    """Parse ``RETH_TPU_FAULT_CRASH_AT=<point>[:nth]`` (nth default 1)."""
    spec = os.environ.get("RETH_TPU_FAULT_CRASH_AT", "")
    if not spec:
        return None
    name, _, nth = spec.partition(":")
    try:
        return name, max(1, int(nth or 1))
    except ValueError:
        return name, 1


def crash_point(point: str) -> None:
    """Die here (``os._exit(137)``) when the drill says so.

    A real crash flushes nothing and runs no handlers — ``os._exit``
    is the honest simulation of ``kill -9`` at an exact code location.
    """
    spec = crash_spec()
    if spec is None or spec[0] != point:
        return
    _hits[point] = _hits.get(point, 0) + 1
    if _hits[point] != spec[1]:
        return
    try:  # flight-record the drill like every other injector, best-effort
        from . import tracing

        tracing.fault_event("RETH_TPU_FAULT_CRASH_AT", target="chaos",
                            point=point, nth=spec[1])
    except Exception:  # noqa: BLE001 - dying is the point
        pass
    sys.stderr.write(f"chaos: crash point {point!r} firing (os._exit)\n")
    sys.stderr.flush()
    os._exit(137)


# -- scenario vocabulary ------------------------------------------------------

# injector menu: every env-driven fault the repo ships that is
# meaningful on a CPU dev node (device/compile wedges need the device
# supervisor path and are drilled by test_supervisor/test_warmup).
# Values are deliberately mild — the node must LIMP, not halt, so the
# kill lands on a degraded-but-serving process, which is how real
# incidents arrive.
FAULT_MENU: tuple[dict, ...] = (
    {"RETH_TPU_FAULT_SPARSE_ABORT": "2"},        # sparse finish -> fallback
    {"RETH_TPU_FAULT_SPARSE_PROOF_WEDGE": "1"},  # proof shard wedge
    {"RETH_TPU_FAULT_GATEWAY_STALL": "0.02"},    # slow every admission
    {"RETH_TPU_FAULT_GATEWAY_SHED": "5"},        # shed every 5th request
    {"RETH_TPU_FAULT_EXEC_CONFLICT_STORM": "1"}, # all-conflict scheduling
    {"RETH_TPU_FAULT_SERVICE_STALL": "0.02"},    # hash-service dispatch stall
    {"RETH_TPU_FAULT_SLO_BREACH": "all"},        # force every SLO rule red
)

# hot-state injectors ride only on cached consensus seeds (drawn after
# the hot_state coin in make_consensus_scenario), never sampled from
# FAULT_MENU — keeping them out preserves every pre-existing seed's
# fault schedule bit-for-bit.
HOTSTATE_FAULTS: tuple[str, ...] = (
    "RETH_TPU_FAULT_HOTSTATE_POISON",
    "RETH_TPU_FAULT_HOTSTATE_EVICT_STORM",
)


def make_scenario(seed: int) -> dict:
    """Deterministic scenario from one seed: a fault composition plus a
    kill (crash point or external SIGKILL mid-mining)."""
    import random

    rng = random.Random(seed)
    faults: dict[str, str] = {}
    for f in rng.sample(FAULT_MENU, k=rng.randint(1, 3)):
        faults.update(f)
    blocks = rng.randint(8, 13)
    if rng.random() < 0.5:
        point = rng.choice(CRASH_POINTS)
        nth = {
            # every commit appends: land the crash mid-chain, not at genesis
            "wal-append": rng.randint(6, 3 * blocks),
            "checkpoint-swap": rng.randint(1, 3),
            "advance-persistence": rng.randint(2, blocks - 2),
            "unwind": 1,
            "jar-rename": rng.randint(1, 3),
        }[point]
        scn = {"mode": "point", "point": point, "nth": nth}
    else:
        scn = {"mode": "kill", "kill_after": rng.randint(4, blocks - 1)}
    scn.update({
        "seed": seed,
        "faults": faults,
        "blocks": blocks,
        # the unwind point needs a deep reorg to reach _unwind_persisted_to
        "reorg_at": (rng.randint(5, blocks - 1)
                     if scn.get("point") == "unwind" or rng.random() < 0.25
                     else 0),
        "threshold": 2,
        # hash service on for some scenarios so SERVICE_* faults bite
        "hash_service": rng.random() < 0.5
        or "RETH_TPU_FAULT_SERVICE_STALL" in faults,
    })
    return scn


def make_consensus_scenario(seed: int) -> dict:
    """Deterministic Engine-API adversarial scenario: a seeded
    reorg-storm schedule (side-chain forks, deep reorgs across the
    persistence threshold, orphan/duplicate/out-of-order payloads,
    invalid floods, hostile forkchoice targets) composed with a fault
    sample and, for some seeds, a kill (crash point or SIGKILL) mid-
    storm. Uses its own rng stream so storage-domain seeds stay stable."""
    import random

    rng = random.Random(0xC0DE0000 + seed)
    faults: dict[str, str] = {}
    for f in rng.sample(FAULT_MENU, k=rng.randint(1, 2)):
        faults.update(f)
    rounds = rng.randint(16, 26)
    r = rng.random()
    if r < 0.25:
        scn: dict = {"mode": "kill", "kill_after": rng.randint(5, 10)}
    elif r < 0.55:
        point = rng.choice(("wal-append", "advance-persistence",
                            "checkpoint-swap", "unwind"))
        nth = {
            "wal-append": rng.randint(6, 20),
            "advance-persistence": rng.randint(2, 6),
            "checkpoint-swap": rng.randint(1, 2),
            "unwind": 1,
        }[point]
        scn = {"mode": "point", "point": point, "nth": nth}
    else:
        # run the whole storm: the victim's own fault-free-twin checks
        # must hold live, and the restart invariants still run after
        scn = {"mode": "complete"}
    scn.update({
        "domain": "consensus",
        "seed": seed,
        "faults": faults,
        "rounds": rounds,
        "threshold": 2,
        # the unwind crash point only fires inside a persisted-chain
        # unwind, so those seeds guarantee a deep reorg
        "force_deep_reorg": (scn.get("point") == "unwind"
                             or rng.random() < 0.3),
        "hash_service": rng.random() < 0.4
        or "RETH_TPU_FAULT_SERVICE_STALL" in faults,
        # cross-block import pipeline (engine/block_pipeline.py): half
        # the seeds storm a depth-2 tree — two-deep payload bursts, fcU
        # reorgs landing mid-speculation, tampered-root parents whose
        # speculating children must abort cleanly. Drawn after the base
        # schedule so existing seeds' schedules stay bit-stable.
        "pipeline": rng.random() < 0.5,
        # hot-state plane (trie/hot_cache.py): half the seeds storm a
        # cache-enabled tree while the twin stays cache-disabled, so
        # every VALID the storm already demands is a bit-identical-root
        # agreement with the uncached twin across every reorg/unwind.
        # Drawn LAST (after "pipeline") so existing seeds stay stable.
        "hot_state": rng.random() < 0.5,
    })
    if scn["hot_state"]:
        # hot-state injectors ride along on some cached seeds: poison
        # must be CAUGHT by node-hash validation (a served poison flips
        # a root and the twin checks fail), an evict storm may only
        # cost performance — never a wrong status. Drawn after the
        # hot_state coin so every earlier seed schedule stays put.
        if rng.random() < 0.5:
            faults["RETH_TPU_FAULT_HOTSTATE_POISON"] = str(
                rng.randint(3, 9))
        if rng.random() < 0.3:
            faults["RETH_TPU_FAULT_HOTSTATE_EVICT_STORM"] = "1"
    return scn


def make_fleet_scenario(seed: int) -> dict:
    """Deterministic replica-fleet scenario: a dev full node in fleet
    mode + N replica subprocesses under load, one replica degraded or
    killed mid-load, composed with full-node injectors that slow (never
    legitimately fail) requests. Invariant suite runs in-victim: zero
    failed reads, responses bit-identical to the ungated full node, and
    the ring converges around the lost replica. Own rng stream so
    storage/consensus seeds stay stable."""
    import random

    rng = random.Random(0xF1EE7000 + seed)
    # only injectors that SLOW the node: a shed drill (-32005) would
    # fail requests by design, which is exactly what this suite asserts
    # cannot happen from fleet membership churn
    fault_menu = (
        {"RETH_TPU_FAULT_GATEWAY_STALL": "0.01"},
        {"RETH_TPU_FAULT_EXEC_CONFLICT_STORM": "1"},
        {"RETH_TPU_FAULT_SLO_BREACH": "all"},
    )
    faults: dict[str, str] = {}
    for f in rng.sample(fault_menu, k=rng.randint(0, 2)):
        faults.update(f)
    blocks = rng.randint(3, 5)
    return {
        "domain": "fleet",
        "seed": seed,
        "faults": faults,
        "replicas": 2,
        "blocks": blocks,
        "requests": rng.randint(120, 200),
        # how the fleet loses a replica mid-load
        "mode": rng.choice(("sigkill", "wedge", "lag")),
        # wedge replicas validate the initial chain and serve the first
        # part of the load, then wedge MID-load (deferred injector) —
        # so the stitched-trace invariant sees all three processes
        # before the fleet degrades
        "wedge_after": blocks + 1,
        "kill_frac": 0.4,
        "max_lag": 2,
    }


def make_ha_scenario(seed: int) -> dict:
    """Deterministic leader-kill HA scenario: a dev full node in
    fleet+WAL mode (the leader) shipping its durable stream to a hot
    standby subprocess, two replicas anchored on the leader's feed with
    the standby's takeover feed as failover — then SIGKILL the leader
    mid-load. Invariant suite runs in the orchestrator child: the
    standby promotes, its recovered head is within the persistence
    threshold of the recorded chain with a root bit-identical to a
    fault-free twin replay, the replicas re-register with the new
    leader's ring and reads keep succeeding, and the restarted OLD
    leader fences on the standby's higher epoch. Own rng stream so
    other domains' seeds stay stable."""
    import random

    rng = random.Random(0xF1EEB000 + seed)
    # leader-side injectors: only ones the stream must absorb without
    # an invariant lawfully failing — a stalled gateway slows reads, a
    # bounded feed partition forces the standby through the
    # gap-detect → resync ladder before the kill even happens
    leader_menu = (
        {"RETH_TPU_FAULT_GATEWAY_STALL": "0.01"},
        {"RETH_TPU_FAULT_LEADER_PARTITION": "0.4:1.5"},
    )
    faults: dict[str, str] = {}
    for f in rng.sample(leader_menu, k=rng.randint(0, 2)):
        faults.update(f)
    # standby-side: a per-record replay delay small enough to catch
    # back up before the kill gate (which requires lag <= 2)
    standby_faults: dict[str, str] = {}
    if rng.random() < 0.5:
        standby_faults["RETH_TPU_FAULT_STANDBY_LAG"] = "0.002"
    return {
        "domain": "ha",
        "seed": seed,
        "faults": faults,
        "standby_faults": standby_faults,
        "replicas": 2,
        "threshold": 2,
        # blocks the leader must have recorded before the SIGKILL
        "kill_after": rng.randint(6, 10),
        # > the partition window, so a mid-partition silence never
        # triggers a premature promotion
        "heartbeat_timeout": 2.0,
        # the negative drill flips this: fencing disabled, the
        # old-leader invariant MUST fail (proves the suite can)
        "no_fence": False,
    }


def make_pool_scenario(seed: int) -> dict:
    """Deterministic write-path scenario (``--domain pool``): a dev full
    node in fleet mode with the continuous producer on, flooded with a
    seeded adversarial submission mix (per-sender nonce chains plus
    duplicates, valid 2x replacements, underpriced +5% replacements, and
    a fee-capped-below-base-fee straggler) while blocks keep mining off
    the hot candidate — some seeds throw a mid-storm reorg — then
    SIGKILLed mid-build. The recover child restarts the datadir and
    audits the write path: no stuck candidate slot, replacement
    semantics intact, a replica converging on the leader's exact pending
    view, and zero leaked leases. Own rng stream so other domains'
    seeds stay stable."""
    import random

    rng = random.Random(0xF001ED00 + seed)
    # slow-only injectors: the write-path invariants assert semantics,
    # not latency, so nothing here may legitimately fail a submission
    fault_menu = (
        {"RETH_TPU_FAULT_GATEWAY_STALL": "0.01"},
        {"RETH_TPU_FAULT_SLO_BREACH": "all"},
    )
    faults: dict[str, str] = {}
    for f in rng.sample(fault_menu, k=rng.randint(0, 1)):
        faults.update(f)
    return {
        "domain": "pool",
        "seed": seed,
        "faults": faults,
        "mode": "kill",
        "threshold": 2,
        "wallets": rng.randint(4, 6),
        "txs_per_wallet": rng.randint(3, 5),
        # recorded blocks before the SIGKILL lands (mid-flood, so the
        # kill interleaves arbitrarily with refresh/seal/commit legs)
        "kill_after": rng.randint(4, 7),
        "reorg_storm": rng.random() < 0.4,
        "reorg_at": rng.randint(3, 4),
    }


# -- child processes ----------------------------------------------------------


def _cpu_committer():
    from .primitives.keccak import keccak256_batch_np
    from .trie.committer import TrieCommitter

    committer = TrieCommitter(hasher=keccak256_batch_np)
    committer.turbo_backend = "numpy"
    return committer


def _build_node(datadir: Path, seed: int, threshold: int,
                hash_service: bool, fresh: bool, fleet: bool = False,
                ha_peer_feeds: tuple = (), continuous: bool = False):
    """A dev node over memdb+WAL, deterministic genesis derived from the
    seed — victim and recover children build the identical config."""
    from .node import Node, NodeConfig
    from .primitives.types import Account
    from .testing import ChainBuilder, Wallet

    committer = _cpu_committer()
    if hash_service:
        from .ops.hash_service import HashService

        committer.hash_service = HashService(backend=committer.hasher)
        committer.hasher = committer.hash_service.client("live")
    wallet = Wallet(0xA11CE + seed)
    builder = ChainBuilder({wallet.address: Account(balance=10**21)},
                           committer=committer)
    cfg = NodeConfig(
        dev=True, datadir=datadir, db_backend="memdb",
        genesis_header=builder.genesis if fresh else None,
        genesis_alloc=builder.accounts_at_genesis if fresh else {},
        persistence_threshold=threshold,
        wal=True, wal_checkpoint_blocks=3,
        static_file_distance=2,
        rpc_gateway=True,
        fleet=fleet, feed_port=0,
        continuous_build=continuous,
        ha_peer_feeds=tuple(ha_peer_feeds),
        health=True, slo_interval=0.2, slo_window=120,
        http_port=0, authrpc_port=0,
    )
    return Node(cfg, committer=committer), wallet, builder


def _record_path(datadir: Path) -> Path:
    return Path(datadir) / "chaos_blocks.jsonl"


def child_victim(datadir: str, seed: int, blocks: int, threshold: int = 2,
                 reorg_at: int = 0, hash_service: bool = False) -> int:
    """Mine deterministic blocks until done (or until a crash point /
    the parent's SIGKILL ends us), recording every sealed block's RLP so
    the recover child can bound the loss and replay a fault-free twin."""
    datadir = Path(datadir)
    node, wallet, _ = _build_node(datadir, seed, threshold,
                                  hash_service, fresh=True)
    http_port, _ = node.start_rpc()
    rec = open(_record_path(datadir), "a")
    sink = b"\x0b" * 20
    i = 0
    while blocks <= 0 or i < blocks:
        i += 1
        if reorg_at and i == reorg_at:
            # deep reorg: FCU to a persisted ancestor -> the persisted
            # chain unwinds (crash point "unwind" lives in that window).
            # Record the INTENT first — a crash mid-unwind legitimately
            # recovers to the reorg target, and the invariant suite can
            # only allow that if the record file says it was coming.
            with node.factory.provider() as p:
                target = max(0, node.tree.persisted_number - 1)
                old = p.canonical_hash(target)
            rec.write(json.dumps({"reorg_to": target}) + "\n")
            rec.flush()
            node.tree.on_forkchoice_updated(old)
        node.pool.add_transaction(wallet.transfer(sink, 100 + i))
        blk = node.miner.mine_block(timestamp=1_700_000_000 + i * 12)
        rec.write(json.dumps({
            "n": blk.header.number, "hash": blk.hash.hex(),
            "root": blk.header.state_root.hex(), "rlp": blk.encode().hex(),
        }) + "\n")
        rec.flush()
        # a little read traffic so gateway-class injectors actually fire
        try:
            import urllib.request

            req = urllib.request.Request(
                f"http://127.0.0.1:{http_port}/",
                data=json.dumps({"jsonrpc": "2.0", "id": 1,
                                 "method": "eth_blockNumber",
                                 "params": []}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=5).read()
        except Exception:  # noqa: BLE001 - shed drills reply -32005/queue full
            pass
    node.stop()
    return 0


def child_consensus_victim(datadir: str, seed: int, rounds: int = 20,
                           threshold: int = 2, hash_service: bool = False,
                           force_deep_reorg: bool = False,
                           pipeline: bool = False,
                           hot_state: bool = False) -> int:
    """Drive the dev node's engine tree as a hostile CL: seeded
    randomized interleavings of newPayload/forkchoiceUpdated — side
    forks at random depths, deep reorgs across the persistence
    threshold, orphan/out-of-order/duplicate payloads, invalid payloads
    (bad root/gas/receipts + invalid-ancestor chains + floods), fcU to
    stale/unknown/invalid heads — while the composed ``RETH_TPU_FAULT_*``
    injectors (and any armed crash point) fire underneath.

    Every block is minted by a :class:`~reth_tpu.testing_actions.ForkBuilder`
    whose shadow tree executes it fault-free first, so each VALID the
    node returns is already a bit-identical-root agreement with the
    twin. Canonical commits are recorded in ``child_victim``'s format
    (reorg intents included), so :func:`child_recover` applies the full
    restart invariant suite unchanged. ``rounds <= 0`` storms forever
    (the kill-mode orchestrator ends us)."""
    import random

    from .engine.tree import PayloadStatusKind
    from .testing_actions import ForkBuilder, tampered_block

    datadir = Path(datadir)
    if pipeline:
        # EngineTree resolves the pipeline depth from the env at
        # construction; set it before the node is built
        os.environ["RETH_TPU_PIPELINE_DEPTH"] = "2"
    if hot_state:
        # same construction-time env resolution as the pipeline; popped
        # again below so the fault-free ForkBuilder twin is built
        # CACHE-DISABLED — every VALID the storm demands is then a
        # bit-identical-root agreement between the cached node and an
        # uncached twin, across every fork switch, unwind, and storm
        os.environ["RETH_TPU_HOT_STATE"] = "1"
    node, wallet, builder = _build_node(datadir, seed, threshold,
                                        hash_service, fresh=True)
    if hot_state:
        os.environ.pop("RETH_TPU_HOT_STATE", None)
        if node.tree.hot_cache is None:
            raise AssertionError("hot-state storm requested but tree "
                                 "has no cache")
    if pipeline and node.tree.pipeline is None:
        raise AssertionError("pipeline storm requested but tree has none")
    if pipeline:
        # slow-device injector: stretch the commit leg so the storm's
        # two-deep bursts reliably land INSIDE the parent's commit
        # window (CPU roots on 1-2 tx blocks close in ~ms, faster than
        # a payload round-trip — a real device dispatch does not)
        _orig_root = node.tree._sparse_root_or_fallback

        def _slow_root(*a, **kw):
            time.sleep(0.08)
            return _orig_root(*a, **kw)

        node.tree._sparse_root_or_fallback = _slow_root
    http_port, _ = node.start_rpc()
    fb = ForkBuilder(builder.genesis, builder.accounts_at_genesis,
                     wallet=wallet, committer=_cpu_committer())
    rng = random.Random(0xAD0E0000 + seed)
    rec = open(_record_path(datadir), "a")
    recorded: set[bytes] = set()
    head = builder.genesis.hash
    VALID, SYNCING, INVALID = (PayloadStatusKind.VALID,
                               PayloadStatusKind.SYNCING,
                               PayloadStatusKind.INVALID)

    def expect(st, *allowed, op=""):
        if st.status not in allowed:
            raise AssertionError(
                f"consensus storm: {op} returned {st.status.name} "
                f"({st.validation_error}), wanted "
                f"{'/'.join(a.name for a in allowed)}")
        return st

    def record_canonical(new_head):
        chain = []
        h = new_head
        while h != fb.genesis_hash and h not in recorded:
            blk = fb.blocks[h]
            chain.append(blk)
            h = blk.header.parent_hash
        for blk in reversed(chain):
            rec.write(json.dumps({
                "n": blk.header.number, "hash": blk.hash.hex(),
                "root": blk.header.state_root.hex(),
                "rlp": blk.encode().hex(),
            }) + "\n")
            recorded.add(blk.hash)
        rec.flush()

    def fcu(target, *allowed, op=""):
        nonlocal head
        # reorg-intent marker BEFORE a non-extending fcU: a crash inside
        # the unwind legitimately recovers to the branch point, and the
        # invariant suite only allows that if the record says it was
        # coming
        branch = fb.branch_point(head, target)
        if branch is not None and branch[0] < fb.number_of(head):
            rec.write(json.dumps({"reorg_to": branch[0]}) + "\n")
            rec.flush()
        st = expect(node.tree.on_forkchoice_updated(target), *allowed, op=op)
        if st.status is VALID and target in fb.blocks:
            head = target
            record_canonical(target)
        return st

    def op_extend():
        blk = fb.block_on(head, txs=rng.randint(0, 2),
                          salt=rng.randint(0, 3))
        expect(node.tree.on_new_payload(blk), VALID, op="extend.newPayload")
        fcu(blk.hash, VALID, op="extend.fcu")

    def op_side_fork():
        hn = fb.number_of(head)
        if hn < 2:
            return op_extend()
        depth = rng.randint(1, min(4, hn))
        anc = fb.ancestor(head, depth)
        tip = anc
        for i in range(rng.randint(1, depth + 1)):
            blk = fb.block_on(tip, txs=rng.randint(0, 1),
                              salt=rng.randint(4, 9))
            # VALID when the parent is in the tree, SYNCING (buffered)
            # when it sits below the persisted tip — never INVALID
            expect(node.tree.on_new_payload(blk), VALID, SYNCING,
                   op="fork.newPayload")
            tip = blk.hash
        if rng.random() < 0.6:
            fcu(tip, VALID, op="fork.fcu")

    def op_deep_reorg():
        # branch BELOW the node's persisted tip with a strictly longer
        # fork: forces the pipeline unwind + buffered replay path (and
        # the 'unwind' crash window)
        pn = node.tree.persisted_number
        hn = fb.number_of(head)
        if pn < 1 or hn <= pn:
            return op_extend()
        anc = fb.ancestor(head, hn - max(0, pn - 1))
        tip = anc
        for _ in range(hn - fb.number_of(anc) + 1):
            blk = fb.block_on(tip, txs=1, salt=rng.randint(10, 14))
            expect(node.tree.on_new_payload(blk), VALID, SYNCING,
                   op="deep.newPayload")
            tip = blk.hash
        fcu(tip, VALID, op="deep.fcu")

    def op_rewind():
        hn = fb.number_of(head)
        if hn < 2:
            return op_extend()
        anc = fb.ancestor(head, rng.randint(1, min(3, hn)))
        fcu(anc, VALID, op="rewind.fcu")

    def op_orphan():
        # child before parent: SYNCING + buffered, then the parent's
        # arrival must replay the child (reference BlockBuffer shape)
        a = fb.block_on(head, txs=1, salt=rng.randint(15, 17))
        b = fb.block_on(a.hash, txs=0, salt=0)
        expect(node.tree.on_new_payload(b), SYNCING, op="orphan.child")
        expect(node.tree.on_new_payload(a), VALID, op="orphan.parent")
        if b.hash not in node.tree.blocks:
            raise AssertionError(
                "consensus storm: buffered child not replayed when its "
                "parent arrived")
        fcu(b.hash, VALID, op="orphan.fcu")

    def op_duplicate():
        if fb.number_of(head) == 0:
            return op_extend()
        expect(node.tree.on_new_payload(fb.blocks[head]), VALID,
               op="duplicate.newPayload")

    def op_unknown_orphan():
        salt = rng.getrandbits(64).to_bytes(8, "big")
        blk = tampered_block(fb.blocks[head], "unknown_parent", salt=salt)
        expect(node.tree.on_new_payload(blk), SYNCING, op="orphan.unknown")

    def op_invalid():
        kind = rng.choice(("state_root", "gas_used", "receipts_root",
                           "gas_limit"))
        base = fb.block_on(head, txs=1, salt=rng.randint(18, 21))
        bad = tampered_block(base, kind)
        expect(node.tree.on_new_payload(bad), INVALID,
               op=f"invalid.{kind}")
        # descendants of a known-invalid block: invalid ancestor, and an
        # fcU to the invalid head is refused
        child = tampered_block(base, "reparent", salt=bad.hash)
        expect(node.tree.on_new_payload(child), INVALID,
               op="invalid.ancestor")
        expect(node.tree.on_forkchoice_updated(bad.hash), INVALID,
               op="invalid.fcu")

    def op_fcu_unknown():
        fake = rng.getrandbits(256).to_bytes(32, "big")
        expect(node.tree.on_forkchoice_updated(fake), SYNCING,
               op="fcu.unknown")

    def op_invalid_flood():
        base = fb.block_on(head, txs=0, salt=22)
        bad = tampered_block(base, "state_root")
        expect(node.tree.on_new_payload(bad), INVALID, op="flood.seed")
        for i in range(120):
            child = tampered_block(base, "reparent",
                                   salt=bad.hash + i.to_bytes(4, "big"))
            expect(node.tree.on_new_payload(child), INVALID, op="flood")
        cap = node.tree.invalid.capacity
        if len(node.tree.invalid) > cap:
            raise AssertionError(
                f"invalid cache exceeded its bound: "
                f"{len(node.tree.invalid)} > {cap}")

    # -- cross-block pipeline ops (depth-2 trees only): two payloads in
    # flight at once, so block N+1 speculates over N's open commit
    # window while the storm's faults fire underneath. Every outcome the
    # pipeline can produce is legal here EXCEPT an unclean one: a leaked
    # lease, a stuck speculation slot, or a root the fault-free twin
    # disagrees with (the expect() on VALID already certifies roots).
    import threading as _threading

    def _two_deep(a, b):
        """Submit ``a`` then ``b`` with ``b`` landing while ``a`` is
        (likely) mid-commit; returns (status_a, status_b)."""
        res = {}
        ta = _threading.Thread(
            target=lambda: res.setdefault("a", node.tree.on_new_payload(a)))
        ta.start()
        node.tree.pipeline.wait_commit_open(a.hash, timeout=30)
        res.setdefault("b", node.tree.on_new_payload(b))
        ta.join(timeout=120)
        if ta.is_alive():
            raise AssertionError("pipeline storm: parent insert hung")
        return res["a"], res["b"]

    def op_pipe_extend():
        a = fb.block_on(head, txs=rng.randint(1, 2), salt=rng.randint(23, 25))
        b = fb.block_on(a.hash, txs=rng.randint(0, 2), salt=0)
        st_a, st_b = _two_deep(a, b)
        expect(st_a, VALID, op="pipe.parent")
        expect(st_b, VALID, SYNCING, op="pipe.child")
        if st_b.status is not VALID:
            expect(node.tree.on_new_payload(b), VALID, op="pipe.child.retry")
        fcu(b.hash, VALID, op="pipe.fcu")

    def op_pipe_reorg():
        # a known side fork, then an fcU to it lands mid-speculation:
        # the speculative child must abort (or already have adopted) and
        # the chain must remain importable either way
        fork = fb.block_on(head, txs=0, salt=26)
        expect(node.tree.on_new_payload(fork), VALID, SYNCING,
               op="pipe.fork")
        a = fb.block_on(head, txs=1, salt=27)
        b = fb.block_on(a.hash, txs=1, salt=0)
        res = {}
        ta = _threading.Thread(
            target=lambda: res.setdefault("a", node.tree.on_new_payload(a)))
        ta.start()
        node.tree.pipeline.wait_commit_open(a.hash, timeout=30)
        tb = _threading.Thread(
            target=lambda: res.setdefault("b", node.tree.on_new_payload(b)))
        tb.start()
        fcu(fork.hash, VALID, SYNCING, op="pipe.reorg.fcu")
        ta.join(timeout=120)
        tb.join(timeout=120)
        if ta.is_alive() or tb.is_alive():
            raise AssertionError("pipeline storm: reorged insert hung")
        # the racing fcU may have cancelled either insert (SYNCING, the
        # CL re-sends) — never INVALID, the payloads are valid
        expect(res["a"], VALID, SYNCING, op="pipe.reorg.parent")
        expect(res["b"], VALID, SYNCING, op="pipe.reorg.child")
        if res["a"].status is not VALID or a.hash not in node.tree.blocks:
            expect(node.tree.on_new_payload(a), VALID, op="pipe.reorg.a2")
        if res["b"].status is not VALID or b.hash not in node.tree.blocks:
            expect(node.tree.on_new_payload(b), VALID, op="pipe.reorg.b2")
        fcu(b.hash, VALID, op="pipe.reorg.back")

    def op_pipe_invalid():
        # a tampered-root parent with its child speculating over the
        # doomed commit window: the abort ladder must fire, the child
        # must never be adopted, and both must end INVALID
        base = fb.block_on(head, txs=1, salt=28)
        bad = tampered_block(base, "state_root")
        child = tampered_block(base, "reparent", salt=bad.hash)
        res = {}
        ta = _threading.Thread(
            target=lambda: res.setdefault("a", node.tree.on_new_payload(bad)))
        ta.start()
        node.tree.pipeline.wait_commit_open(bad.hash, timeout=30)
        res.setdefault("b", node.tree.on_new_payload(child))
        ta.join(timeout=120)
        if ta.is_alive():
            raise AssertionError("pipeline storm: invalid insert hung")
        expect(res["a"], INVALID, op="pipe.invalid.parent")
        # mid-flight the child may only buffer (SYNCING); once the
        # parent is known-invalid a re-send must say INVALID
        expect(res["b"], INVALID, SYNCING, op="pipe.invalid.child")
        if res["b"].status is SYNCING:
            expect(node.tree.on_new_payload(child), INVALID,
                   op="pipe.invalid.child2")
        if child.hash in node.tree.blocks:
            raise AssertionError(
                "pipeline storm: child adopted off an invalid parent")

    ops = [(op_extend, 4), (op_side_fork, 3), (op_deep_reorg, 1),
           (op_rewind, 1), (op_orphan, 2), (op_duplicate, 1),
           (op_unknown_orphan, 1), (op_invalid, 2), (op_fcu_unknown, 1),
           (op_invalid_flood, 1)]
    if node.tree.pipeline is not None:
        ops += [(op_pipe_extend, 3), (op_pipe_reorg, 2),
                (op_pipe_invalid, 2)]
    weights = [w for _, w in ops]
    i = 0
    while rounds <= 0 or i < rounds:
        i += 1
        if i <= 3:
            op_extend()  # establish a chain before the storm proper
        elif force_deep_reorg and i == 6:
            op_deep_reorg()
        else:
            rng.choices([f for f, _ in ops], weights=weights, k=1)[0]()
        if i % 3 == 0:
            # a little read traffic so gateway-class injectors fire
            try:
                import urllib.request

                req = urllib.request.Request(
                    f"http://127.0.0.1:{http_port}/",
                    data=json.dumps({"jsonrpc": "2.0", "id": 1,
                                     "method": "eth_blockNumber",
                                     "params": []}).encode(),
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=5).read()
            except Exception:  # noqa: BLE001 - shed drills reply -32005
                pass

    # storm over: in-process invariants against the fault-free twin.
    # (Every VALID above already certified a bit-identical root — both
    # trees checked the same header.state_root — so what is left is the
    # head agreement, live state equivalence, and leak checks.)
    if node.tree.head_hash != head:
        raise AssertionError("node head diverged from the storm schedule")
    if fb.number_of(head) > 0:
        a_node = node.tree.overlay_provider(head).account(wallet.address)
        a_twin = fb.tree.overlay_provider(head).account(wallet.address)
        if (a_node is None) != (a_twin is None) or (
                a_node is not None
                and (a_node.nonce, a_node.balance)
                != (a_twin.nonce, a_twin.balance)):
            raise AssertionError("live state diverged from fault-free twin")
    svc = getattr(node.committer, "hash_service", None)
    if svc is not None and svc.snapshot().get("leased_by"):
        raise AssertionError("leaked hash-service lease after the storm")
    if getattr(node.factory.db, "_writer_thread", None) is not None:
        raise AssertionError("leaked store writer lock after the storm")
    if len(node.tree.invalid) > node.tree.invalid.capacity:
        raise AssertionError("invalid cache over its bound after the storm")
    pipe_stats = {}
    if node.tree.pipeline is not None:
        pipe_stats = node.tree.pipeline.stats_snapshot()
        if pipe_stats["leases_active"]:
            raise AssertionError(
                f"leaked pipeline sub-mesh lease after the storm: "
                f"{pipe_stats}")
        if node.tree.pipeline._spec is not None:
            raise AssertionError(
                "stuck speculation slot after the storm")
    hot_stats = {}
    if hot_state:
        # stale-node leaks already fail above (a stale cache entry
        # surviving an unwind would flip a root and the VALID/twin
        # checks catch it); what is left is resource reclamation
        hot_stats = node.tree.hot_cache.stats()
        arena = node.tree.hot_arena
        if arena is not None:
            leaked = arena.leaked_rows()
            if leaked:
                raise AssertionError(
                    f"hot-state arena leaked {leaked} rows after the "
                    f"storm: {arena.snapshot()}")
            hot_stats.update(arena.snapshot())
    print(f"STORM ok seed={seed} rounds={i} head={fb.number_of(head)} "
          f"reorgs={node.tree.reorgs.reorgs} "
          f"deep={node.tree.reorgs.max_depth} "
          f"invalid_cached={len(node.tree.invalid)} "
          f"orphans={len(node.tree.buffered)}"
          + (f" pipe_spec={pipe_stats['speculations']}"
             f" pipe_adopt={pipe_stats['adopted']}"
             f" pipe_abort={pipe_stats['aborted']}"
             if pipe_stats else "")
          + (f" hot_hits={hot_stats.get('hits', 0)}"
             f" hot_clears={hot_stats.get('clears', 0)}"
             f" arena_delta={hot_stats.get('delta_epochs', 0)}"
             f" arena_evict={hot_stats.get('evictions', 0)}"
             if hot_state else ""), flush=True)
    node.stop()
    return 0


def _parse_prom(text: str) -> dict:
    """Exposition text -> {series_key: value} (comments skipped)."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out


def _fleet_metrics_bucket_exact(fleet_text: str, own_text: str,
                                rid: str, family: str) -> bool:
    """The /metrics?scope=fleet acceptance check for one histogram
    family: the replica-labeled series equal the replica's OWN
    /metrics bucket-exactly, and the ``_fleet`` merge equals the
    bucket-wise sum of every per-replica series in the same scrape."""
    import re

    fleet = _parse_prom(fleet_text)
    own = _parse_prom(own_text)
    own_buckets = {k: v for k, v in own.items()
                   if k.startswith(family + '_bucket{')}
    if not own_buckets:
        return False
    for k, v in own_buckets.items():
        m = re.search(r'le="([^"]+)"', k)
        if m is None:
            return False
        fk = f'{family}_bucket{{replica="{rid}",le="{m.group(1)}"}}'
        if fleet.get(fk) != v:
            return False
    if (fleet.get(f'{family}_count{{replica="{rid}"}}')
            != own.get(f"{family}_count")):
        return False
    # bucket-wise merge: _fleet == sum over per-replica series
    sums: dict[str, float] = {}
    pat = re.compile(
        re.escape(family) + r'_bucket\{replica="([^"]+)",le="([^"]+)"\}')
    for k, v in fleet.items():
        m = pat.fullmatch(k)
        if m is None:
            continue
        rep, le = m.group(1), m.group(2)
        if rep == "_fleet":
            continue
        sums[le] = sums.get(le, 0.0) + v
    for le, total in sums.items():
        fk = f'{family}_bucket{{replica="_fleet",le="{le}"}}'
        if fleet.get(fk) != total:
            return False
    return bool(sums)


def child_fleet_victim(datadir: str, seed: int) -> int:
    """Replica-fleet drill (``--domain fleet``): a dev full node in
    fleet mode, two replica subprocesses fed over the witness socket,
    duplicate-heavy + long-tail read load through the fleet gateway
    while blocks keep mining — and one replica SIGKILLed (or wedged /
    lagged via ``RETH_TPU_FAULT_REPLICA_*``) mid-load.

    Invariant suite (prints one ``RESULT {...}`` line; exit 0 iff all
    hold): every load response succeeded (zero failed reads — the
    ladder replica → ring neighbor → local node absorbed the loss),
    a post-load sample of every distinct request is bit-identical
    between the fleet path and a direct ungated dispatch, the ring
    converged (exactly one replica shed, requests still routing), and
    the surviving replica's validated head caught back up to the node.

    Observability invariants (PR 14, the fleet-obs acceptance): the
    merged Chrome traces from the node + both replicas form ONE
    stitched trace (every cross-process parent id resolves, ≥3 pids);
    ``/metrics?scope=fleet`` matches the survivor's own registry
    bucket-exactly and its ``_fleet`` merge is the bucket-wise sum; and
    a node-side fault event produces flight dumps from every reachable
    process under ONE correlation id, merged time-ordered.
    """
    import random
    import threading
    import urllib.request

    from . import tracing
    from .node import Node, NodeConfig
    from .primitives.types import Account
    from .rpc.server import RpcServer
    from .testing import ChainBuilder, Wallet

    scn = make_fleet_scenario(seed)
    datadir = Path(datadir)
    rng = random.Random(0xF1EE8000 + seed)
    # fleet observability plane: one shared flight dir (correlated
    # dumps from every process land together) + per-process Chrome
    # traces (stitched-trace invariant)
    obs_dir = datadir / "obs"
    obs_dir.mkdir(parents=True, exist_ok=True)
    os.environ["RETH_TPU_FLIGHT_DIR"] = str(obs_dir)
    tracing.init_block_tracing(chrome_path=obs_dir / "node.trace.json",
                               flight_dir=obs_dir)
    committer = _cpu_committer()
    wallet = Wallet(0xA11CE + seed)
    builder = ChainBuilder({wallet.address: Account(balance=10**21)},
                           committer=committer)
    cfg = NodeConfig(
        dev=True, datadir=None, db_backend="memdb",
        genesis_header=builder.genesis,
        genesis_alloc=builder.accounts_at_genesis,
        fleet=True, fleet_max_lag=scn["max_lag"],
        health=True, slo_interval=0.2, slo_window=120,
        http_port=0, authrpc_port=0,
    )
    node = Node(cfg, committer=committer)
    node.start_rpc()
    router = node.fleet_router
    router.probe_interval = 0.2
    fport = node.feed_server.port
    inv: dict[str, object] = {}
    result: dict[str, object] = {"seed": seed, "scenario": scn,
                                 "invariants": inv}
    t0 = time.time()
    procs: list = []
    try:
        # spawn the replica subprocesses; the degraded one (wedge/lag
        # modes) carries its injector env from birth
        ports = []
        for i in range(scn["replicas"]):
            env = _child_env()
            # the replicas share the node's flight dir (correlated
            # dumps) and each writes its half of the stitched trace
            env["RETH_TPU_FLIGHT_DIR"] = str(obs_dir)
            if i == 0 and scn["mode"] == "wedge":
                # deferred: validate the initial chain + serve the
                # first part of the load, THEN wedge mid-load
                env["RETH_TPU_FAULT_REPLICA_WEDGE"] = \
                    str(scn.get("wedge_after", 1))
            elif i == 0 and scn["mode"] == "lag":
                # heavy per-block delay: validation falls behind the
                # mining cadence, so probed lag crosses max_lag
                env["RETH_TPU_FAULT_REPLICA_LAG"] = "5"
            port_file = datadir / f"replica-{i}.port"
            log = open(datadir / f"replica-{i}.log", "w")
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "reth_tpu.fleet", "replica",
                 "--feed", f"127.0.0.1:{fport}",
                 "--port-file", str(port_file), "--id", f"r{i}",
                 "--trace-file",
                 str(obs_dir / f"replica-{i}.trace.json")],
                env=env, stdout=log, stderr=log))
            ports.append(port_file)
        deadline = time.time() + 60
        rports = []
        for pf in ports:
            while not pf.exists() and time.time() < deadline:
                time.sleep(0.05)
            if not pf.exists():
                raise RuntimeError(f"replica port file {pf} never appeared")
            rports.append(json.loads(pf.read_text())["http_port"])
        rids = [router.register(f"http://127.0.0.1:{p}") for p in rports]

        # establish a chain, then let the replicas catch up
        sink = b"\x0b" * 20
        mined = 0

        def mine_one():
            nonlocal mined
            mined += 1
            node.pool.add_transaction(wallet.transfer(sink, 100 + mined))
            node.miner.mine_block(timestamp=1_700_000_000 + mined * 12)

        for _ in range(scn["blocks"]):
            mine_one()
        deadline = time.time() + 60
        while time.time() < deadline:
            router.probe_once()
            snap = router.snapshot()
            healthy = snap["healthy"]
            # a deferred wedge stays healthy until mid-load, so only
            # the born-lagging replica is expected shed before the load
            want = (scn["replicas"] - 1 if scn["mode"] == "lag"
                    else scn["replicas"])
            if healthy >= want and snap["max_lag"] == 0:
                break
            time.sleep(0.1)

        # the request mix: duplicate-heavy pool + a long tail of
        # distinct calls, all pure reads the replicas can answer
        def call_body(i):
            return json.dumps({
                "jsonrpc": "2.0", "id": 1, "method": "eth_call",
                "params": [{"from": "0x" + wallet.address.hex(),
                            "to": "0x" + sink.hex(),
                            "value": hex(i)}, "latest"],
            }).encode()

        dup_pool = [call_body(i) for i in range(6)]
        dup_pool.append(json.dumps({
            "jsonrpc": "2.0", "id": 1, "method": "eth_getBlockByNumber",
            "params": [hex(scn["blocks"]), False]}).encode())
        dup_pool.append(json.dumps({
            "jsonrpc": "2.0", "id": 1, "method": "eth_getLogs",
            "params": [{"fromBlock": "0x1",
                        "toBlock": hex(scn["blocks"])}]}).encode())
        failures: list = []
        responses = 0
        kill_at = int(scn["requests"] * scn["kill_frac"])
        lock = threading.Lock()

        def one_request(i):
            nonlocal responses
            body = (dup_pool[rng.randrange(len(dup_pool))]
                    if rng.random() < 0.6 else call_body(1000 + i))
            resp = json.loads(node.rpc.handle(body))
            with lock:
                responses += 1
                if "error" in resp:
                    failures.append(resp["error"])

        for i in range(scn["requests"]):
            one_request(i)
            if i == kill_at and scn["mode"] == "sigkill":
                os.kill(procs[0].pid, signal.SIGKILL)
                procs[0].wait()
            if i % 25 == 24:
                mine_one()  # the fleet serves while the chain advances
                router.probe_once()
        # drain: give the prober a moment to converge the ring. For the
        # lag mode the replica is slow, not dead — keep mining so its
        # lag stays visible until the prober sheds it (it may lawfully
        # HEAL later once it catches up; the shed is what we assert)
        deadline = time.time() + 30
        while time.time() < deadline:
            router.probe_once()
            snap = router.snapshot()
            if scn["mode"] == "lag":
                if snap["sheds"] >= 1:
                    break
                mine_one()
            elif snap["healthy"] == scn["replicas"] - 1:
                break
            time.sleep(0.2)
        snap = router.snapshot()

        # 1. zero failed reads across the whole storm
        inv["no_failed_reads"] = not failures
        if failures:
            result["failures"] = failures[:5]

        # 2. ring converged around the degraded replica: sigkill/wedge
        # replicas stay shed (dead transport / wedged flag); a lagging
        # replica must have been shed while it trailed — healing after
        # it catches up is the designed hysteresis, not a failure
        lost = [r for r in snap["replicas"] if r["state"] != "healthy"]
        if scn["mode"] == "lag":
            inv["ring_converged"] = snap["sheds"] >= 1
        else:
            inv["ring_converged"] = (snap["healthy"] == scn["replicas"] - 1
                                     and len(lost) == 1
                                     and lost[0]["id"] == rids[0])

        # 3. reads still route to the survivor after the loss
        pre_routed = snap["routed"]
        for i in range(16):
            resp = json.loads(node.rpc.handle(call_body(9000 + i)))
            if "error" in resp:
                inv["no_failed_reads"] = False
        router.probe_once()
        inv["still_routing"] = (router.snapshot()["routed"] > pre_routed)

        # 4. bit-identical: every distinct request answered through the
        # fleet equals a direct ungated dispatch (mining stopped, head
        # frozen; the fleet cache is cleared so replicas answer live)
        naked = RpcServer(lock=node.rpc.lock)
        naked.methods = node.rpc.methods
        node.gateway.on_head_change()
        mismatches = 0
        for body in dup_pool + [call_body(1000 + i)
                                for i in range(0, scn["requests"], 7)]:
            via_fleet = json.loads(node.rpc.handle(body))
            direct = json.loads(naked.handle(body))
            if via_fleet != direct:
                mismatches += 1
        inv["bit_identical"] = mismatches == 0
        result["mismatches"] = mismatches

        # 5. the survivor caught up to the node's head (feed liveness;
        # mining stopped above, so a live feed converges to lag 0)
        deadline = time.time() + 15
        caught_up = False
        while time.time() < deadline and not caught_up:
            router.probe_once()
            reps = {r["id"]: r for r in router.snapshot()["replicas"]}
            caught_up = reps.get(rids[1], {}).get("lag") == 0
            if not caught_up:
                time.sleep(0.2)
        inv["survivor_caught_up"] = caught_up

        # -- fleet observability invariants (PR 14) -------------------

        # 6. ONE stitched trace across the fleet: a few more routed
        # reads (tracing is on), then merge every process's Chrome
        # trace — every cross-process parent id must resolve and ≥3
        # pids must appear (node + both replicas; the dead replica's
        # pre-kill spans still count, its torn file reads tolerantly)
        for i in range(8):
            node.rpc.handle(call_body(12000 + i))
        trace_files = ([obs_dir / "node.trace.json"]
                       + sorted(obs_dir.glob("replica-*.trace.json")))
        stitched = tracing.stitch_chrome_traces(trace_files)
        inv["trace_stitched"] = (stitched["stitched"]
                                 and len(stitched["pids"]) >= 3)
        result["trace"] = {
            "pids": stitched["pids"],
            "cross_refs": stitched["cross_refs"],
            "unresolved_cross": stitched["unresolved_cross"][:5],
            "events": len(stitched["events"]),
        }

        # 7. /metrics?scope=fleet matches the survivor's own registry
        # bucket-exactly, and the _fleet merge is the bucket-wise sum
        # of every per-replica series in the same scrape (the degraded
        # replica's series ride stale-marked, never blocking the pull)
        node.fleet_federation.pull_once()
        fleet_text = urllib.request.urlopen(
            f"http://127.0.0.1:{node.rpc.port}/metrics?scope=fleet",
            timeout=10).read().decode()
        survivor_text = urllib.request.urlopen(
            f"http://127.0.0.1:{rports[1]}/metrics",
            timeout=10).read().decode()
        inv["fleet_metrics"] = _fleet_metrics_bucket_exact(
            fleet_text, survivor_text, rids[1],
            "replica_validate_seconds")
        if scn["mode"] != "sigkill":
            # the degraded replica is alive: the federation must keep
            # pulling (wedge) or at least retain stale-marked data
            inv["fleet_metrics_degraded_visible"] = (
                f'replica="{rids[0]}"' in fleet_text)

        # 8. correlated flight dumps: a node-side fault event fans the
        # dump request over the feed; every reachable process dumps
        # under ONE correlation id (the lagging replica's feed thread
        # may be minutes behind its record queue, so lag mode only
        # requires the node + survivor)
        tracing.reset_fault_dump_limits()
        tracing.fault_event("fleet_chaos_obs_drill", target="chaos",
                            seed=seed, mode=scn["mode"])
        cid = tracing.flight_recorder().last_correlation_id
        want_pids = 3 if scn["mode"] == "wedge" else 2
        merged = {}
        deadline = time.time() + 20
        while time.time() < deadline:
            merged = tracing.merge_correlated(cid, obs_dir)
            if len(merged.get("pids", ())) >= want_pids:
                break
            time.sleep(0.25)
        inv["correlated_dump"] = (len(merged.get("pids", ())) >= want_pids
                                  and bool(merged.get("records")))
        ts = [r.get("ts", 0.0) for r in merged.get("records", ())]
        inv["correlated_time_ordered"] = ts == sorted(ts)
        result["correlated"] = {
            "correlation_id": cid,
            "pids": merged.get("pids"),
            "dumps": len(merged.get("dumps", ())),
            "records": len(merged.get("records", ())),
        }

        result["router"] = {k: snap[k] for k in
                            ("routed", "failovers", "local_fallbacks",
                             "sheds", "healthy", "registered")}
        result["responses"] = responses
        result["blocks"] = mined
    except Exception as e:  # noqa: BLE001 — a crashed drill fails the suite
        result["ok"] = False
        result["error"] = f"{type(e).__name__}: {e}"
        print("RESULT " + json.dumps(result, default=str))
        return 1
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        try:
            node.stop()
        except Exception:  # noqa: BLE001 - verdict beats a clean exit
            pass
    result["ok"] = all(v is True for v in inv.values())
    result["wall_s"] = round(time.time() - t0, 2)
    print("RESULT " + json.dumps(result, default=str))
    return 0 if result["ok"] else 1


def run_fleet_scenario(scn: dict, base_dir: str | Path,
                       timeout: float = 240.0) -> dict:
    """One fleet drill: the victim IS the whole drill (it owns the
    replica subprocesses and runs the invariant suite in-process);
    full-node injectors land in its env."""
    datadir = Path(base_dir) / f"fleet-{scn['seed']}"
    datadir.mkdir(parents=True, exist_ok=True)
    result = dict(scn)
    cmd = [sys.executable, "-m", "reth_tpu.chaos", "fleet-victim",
           "--datadir", str(datadir), "--seed", str(scn["seed"])]
    try:
        proc = subprocess.run(cmd, env=_child_env(scn["faults"]),
                              capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired:
        result.update(ok=False, error="fleet victim timeout")
        return result
    verdict = None
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            verdict = json.loads(line[len("RESULT "):])
    if verdict is None:
        result.update(ok=False,
                      error=f"fleet victim emitted no verdict "
                            f"(rc={proc.returncode}): {proc.stderr[-400:]}")
        return result
    result.update(verdict)
    return result


def _ha_rpc(port: int, method: str, params=None, timeout: float = 10.0):
    """One JSON-RPC call against a drill child; raises on transport
    errors (the caller's deadline loop absorbs them)."""
    import urllib.request

    body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                       "params": params or []}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/", data=body,
        headers={"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req, timeout=timeout).read())


def child_ha_leader(datadir: str, seed: int, threshold: int = 2,
                    port_file: str | None = None) -> int:
    """(child) the HA leader: a dev full node in fleet+WAL mode mining
    continuously under light read load until SIGKILLed, recording every
    sealed block — the durable-loss ledger the promoted standby is
    audited against."""
    datadir = Path(datadir)
    node, wallet, _ = _build_node(datadir, seed, threshold,
                                  hash_service=False, fresh=True,
                                  fleet=True)
    ports = node.start_rpc()
    if port_file:
        Path(port_file).write_text(json.dumps({
            "http_port": ports[0], "feed_port": node.feed_server.port,
            "pid": os.getpid()}))
    rec = open(_record_path(datadir), "a")
    sink = b"\x0b" * 20
    i = 0
    while True:  # until the orchestrator's SIGKILL
        i += 1
        node.pool.add_transaction(wallet.transfer(sink, 100 + i))
        blk = node.miner.mine_block(timestamp=1_700_000_000 + i * 12)
        rec.write(json.dumps({
            "n": blk.header.number, "hash": blk.hash.hex(),
            "root": blk.header.state_root.hex(), "rlp": blk.encode().hex(),
        }) + "\n")
        rec.flush()
        try:
            _ha_rpc(ports[0], "eth_blockNumber", timeout=5)
        except Exception:  # noqa: BLE001 - stall injectors slow, not gate
            pass
        time.sleep(0.05)


def child_ha_fence_probe(datadir: str, seed: int, threshold: int = 2,
                         peer: str = "") -> int:
    """(child) restart the SIGKILLed old leader's datadir with the
    standby's takeover feed as an HA peer: startup must fence — report
    a superseding epoch and refuse engine writes. Prints one
    ``RESULT {...}`` line; the ORCHESTRATOR judges fenced/unfenced (the
    no-fence negative drill needs the unfenced report, not a crash)."""
    from .engine.tree import PayloadStatusKind

    datadir = Path(datadir)
    try:
        node, _, _ = _build_node(datadir, seed, threshold,
                                 hash_service=False, fresh=True,
                                 fleet=True,
                                 ha_peer_feeds=(peer,) if peer else ())
    except Exception as e:  # noqa: BLE001 - a refused restart is a verdict
        print("RESULT " + json.dumps(
            {"error": f"restart refused: {type(e).__name__}: {e}"}))
        return 1
    try:
        fenced = bool(node.tree.fenced)
        write_refused = None
        if fenced:
            # a fenced tree must refuse engine writes outright
            st = node.tree.on_forkchoice_updated(b"\x00" * 32)
            write_refused = st.status is PayloadStatusKind.INVALID
        result = {
            "fenced": fenced, "write_refused": write_refused,
            "fence_report": node.fence_report,
            "own_epoch": (node.durability.epoch
                          if node.durability is not None else None),
            "recovered": node.tree.persisted_number,
        }
    finally:
        node.stop()
    print("RESULT " + json.dumps(result, default=str))
    return 0


def child_ha_victim(datadir: str, seed: int, no_fence: bool = False) -> int:
    """Leader-kill HA drill (``--domain ha``): leader + hot standby +
    two replicas as subprocesses, SIGKILL the leader mid-load, then
    audit the failover end to end.

    Invariant suite (prints one ``RESULT {...}`` line; exit 0 iff all
    hold): the standby promotes to ``leading`` with its recovered head
    root verified by recomputation; zero durable-commit loss — the
    promoted head is within the persistence threshold of the recorded
    chain and its state root is bit-identical to a fault-free twin
    replay of the recorded blocks; both replicas re-register with the
    promoted leader's ring and reads through the new gateway keep
    succeeding; and the restarted OLD leader fences on the standby's
    higher epoch (with ``no_fence`` the fencing check is disabled and
    this invariant MUST fail — the negative drill)."""
    import socket as socket_mod

    scn = make_ha_scenario(seed)
    if no_fence:
        scn["no_fence"] = True
    datadir = Path(datadir)
    leader_dir = datadir / "leader"
    standby_dir = datadir / "standby"
    leader_dir.mkdir(parents=True, exist_ok=True)
    standby_dir.mkdir(parents=True, exist_ok=True)
    inv: dict[str, object] = {}
    result: dict[str, object] = {"seed": seed, "scenario": scn,
                                 "invariants": inv}
    t0 = time.time()
    procs: list = []
    logs: list = []

    def _spawn(cmd, env, log_name):
        log = open(datadir / log_name, "w")
        logs.append(log)
        p = subprocess.Popen(cmd, env=env, stdout=log, stderr=log)
        procs.append(p)
        return p

    def _wait_port_file(pf, what, deadline_s=60):
        deadline = time.time() + deadline_s
        while not pf.exists() and time.time() < deadline:
            time.sleep(0.05)
        if not pf.exists():
            raise RuntimeError(f"{what} port file {pf} never appeared")
        return json.loads(pf.read_text())

    try:
        # the takeover feed port is pinned up front so the replicas can
        # carry it as a failover endpoint from birth
        with socket_mod.socket() as s:
            s.bind(("127.0.0.1", 0))
            tport = s.getsockname()[1]

        lpf = datadir / "leader.port"
        leader = _spawn(
            [sys.executable, "-m", "reth_tpu.chaos", "ha-leader",
             "--datadir", str(leader_dir), "--seed", str(seed),
             "--threshold", str(scn["threshold"]),
             "--port-file", str(lpf)],
            _child_env(scn["faults"]), "leader.log")
        lports = _wait_port_file(lpf, "leader")
        lhttp, lfeed = lports["http_port"], lports["feed_port"]

        spf = datadir / "standby.port"
        _spawn(
            [sys.executable, "-m", "reth_tpu.fleet", "standby",
             "--feed", f"127.0.0.1:{lfeed}",
             "--datadir", str(standby_dir),
             "--takeover-feed-port", str(tport),
             "--heartbeat-timeout", str(scn["heartbeat_timeout"]),
             "--id", f"sb{seed}", "--port-file", str(spf)],
            _child_env(scn["standby_faults"]), "standby.log")
        shttp = _wait_port_file(spf, "standby")["http_port"]

        for i in range(scn["replicas"]):
            rpf = datadir / f"replica-{i}.port"
            _spawn(
                [sys.executable, "-m", "reth_tpu.fleet", "replica",
                 "--feed", f"127.0.0.1:{lfeed}",
                 "--failover-feed", f"127.0.0.1:{tport}",
                 "--auto-register",
                 "--register", f"http://127.0.0.1:{lhttp}",
                 "--id", f"r{i}", "--port-file", str(rpf)],
                _child_env(), f"replica-{i}.log")
            _wait_port_file(rpf, f"replica {i}")

        # load gate: enough recorded blocks AND a caught-up standby —
        # killing a leader whose stream never anchored proves nothing
        deadline = time.time() + 120
        status: dict = {}
        while time.time() < deadline:
            recorded = [l for l in _read_record(leader_dir) if "hash" in l]
            try:
                status = _ha_rpc(shttp, "fleet_standbyStatus")["result"]
            except Exception:  # noqa: BLE001 - standby still booting
                status = {}
            if (len(recorded) >= scn["kill_after"]
                    and status.get("records_applied", 0) > 0
                    and not status.get("awaiting_resync", True)
                    and status.get("lag_heads", 99) <= 2):
                break
            if leader.poll() is not None:
                raise RuntimeError(
                    f"leader died early rc={leader.returncode}")
            time.sleep(0.1)
        else:
            raise RuntimeError(
                f"standby never caught up: {json.dumps(status)[:300]}")
        result["pre_kill"] = {
            "blocks_recorded": len(recorded),
            "standby_applied": status.get("records_applied"),
            "resyncs": status.get("resyncs_applied"),
        }

        # the actual fault: SIGKILL the leader mid-load
        os.kill(leader.pid, signal.SIGKILL)
        leader.wait()
        killed_at = time.time()
        recorded = _read_record(leader_dir)
        mined = [l for l in recorded if "hash" in l]
        max_n = max(l["n"] for l in mined)
        by_height: dict[int, set] = {}
        for l in mined:
            by_height.setdefault(l["n"], set()).add(l["hash"])

        # 1. the standby promotes itself (heartbeat loss) and its
        # recovered head root verifies by recomputation
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                status = _ha_rpc(shttp, "fleet_standbyStatus")["result"]
            except Exception:  # noqa: BLE001 - admin RPC mid-promotion
                status = {}
            if status.get("state") in ("leading", "failed"):
                break
            time.sleep(0.1)
        inv["promoted"] = status.get("state") == "leading"
        result["standby"] = {k: status.get(k) for k in
                             ("state", "leader_epoch", "promote_ms",
                              "promote_error", "records_applied",
                              "resyncs_applied", "gap_detected",
                              "history")}
        result["failover_wall_s"] = round(time.time() - killed_at, 2)
        if not inv["promoted"]:
            raise RuntimeError(
                f"standby never reached leading: "
                f"{json.dumps(status, default=str)[:400]}")
        pnode = status["node"] or {}
        phttp, pfeed = pnode["http_port"], pnode["feed_port"]
        inv["root_verified"] = (
            pnode.get("recovery", {}).get("root_verified") is True)

        # 2. zero durable-commit loss: the promoted head is within the
        # persistence threshold of the recorded chain, IS a recorded
        # block, and its state root is bit-identical to a fault-free
        # twin replay of the record
        head_n = int(_ha_rpc(phttp, "eth_blockNumber")["result"], 16)
        blk = _ha_rpc(phttp, "eth_getBlockByNumber",
                      [hex(head_n), False])["result"]
        head_hash = blk["hash"][2:]
        floor = max_n - scn["threshold"]
        inv["loss_bound"] = (head_n >= floor
                             and head_hash in by_height.get(head_n, ()))
        twin_root, _ = _twin_root(recorded, bytes.fromhex(head_hash), seed)
        inv["root_twin_identical"] = (
            twin_root is not None
            and "0x" + twin_root.hex() == blk["stateRoot"])
        result["recovered"] = {"number": head_n, "hash": head_hash,
                               "recorded_max": max_n}

        # 3. the fleet re-anchors: both replicas rotate to the takeover
        # feed, see the bumped epoch in its hello, and re-register with
        # the promoted leader's ring
        deadline = time.time() + 90
        fs: dict = {}
        while time.time() < deadline:
            try:
                fs = _ha_rpc(phttp, "fleet_status")["result"]
            except Exception:  # noqa: BLE001
                fs = {}
            if fs.get("registered", 0) >= scn["replicas"]:
                break
            time.sleep(0.2)
        inv["replicas_reanchored"] = (
            fs.get("registered", 0) >= scn["replicas"])
        result["ring"] = {k: fs.get(k) for k in
                          ("registered", "healthy", "routed")}

        # 4. zero failed reads through the promoted leader's gateway
        failures = []
        for i in range(16):
            for method, params in (
                    ("eth_blockNumber", []),
                    ("eth_getBlockByNumber", [hex(head_n), False])):
                resp = _ha_rpc(phttp, method, params)
                if "error" in resp:
                    failures.append(resp["error"])
        inv["no_failed_reads"] = not failures
        if failures:
            result["failures"] = failures[:5]

        # 5. the restarted old leader fences on the standby's higher
        # epoch and refuses engine writes (the no-fence negative drill
        # disables the check — this invariant is HOW it fails)
        probe_env = _child_env(
            {"RETH_TPU_FAULT_HA_NO_FENCE": "1"} if scn["no_fence"]
            else None)
        proc = subprocess.run(
            [sys.executable, "-m", "reth_tpu.chaos", "ha-fence-probe",
             "--datadir", str(leader_dir), "--seed", str(seed),
             "--threshold", str(scn["threshold"]),
             "--peer", f"127.0.0.1:{pfeed}"],
            env=probe_env, capture_output=True, text=True, timeout=120)
        probe = None
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT "):
                probe = json.loads(line[len("RESULT "):])
        inv["old_leader_fenced"] = (
            probe is not None and probe.get("fenced") is True
            and probe.get("write_refused") is True)
        result["fence_probe"] = probe if probe is not None else {
            "error": f"no verdict rc={proc.returncode}: "
                     f"{proc.stderr[-300:]}"}
    except Exception as e:  # noqa: BLE001 - a crashed drill fails the suite
        result["ok"] = False
        result["error"] = f"{type(e).__name__}: {e}"
        print("RESULT " + json.dumps(result, default=str))
        return 1
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for log in logs:
            log.close()
    result["ok"] = all(v is True for v in inv.values())
    result["wall_s"] = round(time.time() - t0, 2)
    print("RESULT " + json.dumps(result, default=str))
    return 0 if result["ok"] else 1


def run_ha_scenario(scn: dict, base_dir: str | Path,
                    timeout: float = 360.0) -> dict:
    """One HA drill: the orchestrator child owns the leader/standby/
    replica subprocesses and runs the invariant suite in-process;
    injector env lands per-process inside (the scenario carries it)."""
    datadir = Path(base_dir) / f"ha-{scn['seed']}"
    datadir.mkdir(parents=True, exist_ok=True)
    result = dict(scn)
    cmd = [sys.executable, "-m", "reth_tpu.chaos", "ha-victim",
           "--datadir", str(datadir), "--seed", str(scn["seed"])]
    if scn.get("no_fence"):
        cmd.append("--no-fence")
    try:
        proc = subprocess.run(cmd, env=_child_env(), capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        result.update(ok=False, error="ha victim timeout")
        return result
    verdict = None
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            verdict = json.loads(line[len("RESULT "):])
    if verdict is None:
        result.update(ok=False,
                      error=f"ha victim emitted no verdict "
                            f"(rc={proc.returncode}): {proc.stderr[-400:]}")
        return result
    result.update(verdict)
    return result


def _read_record(datadir: Path) -> list[dict]:
    path = _record_path(datadir)
    if not path.exists():
        return []
    out = []
    for line in path.read_text().splitlines():
        try:
            out.append(json.loads(line))
        except json.JSONDecodeError:  # torn tail of the record file itself
            break
    return out


def _twin_root(recorded: list[dict], head_hash: bytes, seed: int):
    """Replay the recorded chain (fault-free, ephemeral) up to exactly
    ``head_hash``; returns (state_root, head_number) recomputed from the
    twin's own persisted tables."""
    from .engine import EngineTree
    from .primitives.types import Account, Block
    from .storage import MemDb, ProviderFactory
    from .storage.genesis import init_genesis
    from .testing import ChainBuilder, Wallet
    from .trie.incremental import verify_state_root

    committer = _cpu_committer()
    wallet = Wallet(0xA11CE + seed)
    builder = ChainBuilder({wallet.address: Account(balance=10**21)},
                           committer=committer)
    by_hash = {}
    for line in recorded:
        if "hash" in line:
            by_hash[bytes.fromhex(line["hash"])] = \
                Block.decode(bytes.fromhex(line["rlp"]))
    chain = []
    h = head_hash
    while h != builder.genesis.hash:
        blk = by_hash.get(h)
        if blk is None:
            return None, None  # recovered head not on the recorded chain
        chain.append(blk)
        h = blk.header.parent_hash
    chain.reverse()
    factory = ProviderFactory(MemDb())
    init_genesis(factory, builder.genesis, builder.accounts_at_genesis,
                 committer=committer)
    tree = EngineTree(factory, committer=committer, persistence_threshold=0)
    for blk in chain:
        st = tree.on_new_payload(blk)
        if st.status.value != "VALID":
            return None, None
        tree.on_forkchoice_updated(blk.hash)
    root, problems = verify_state_root(factory.provider(), committer)
    return (root if not problems else None), tree.persisted_number


def child_recover(datadir: str, seed: int, threshold: int = 2,
                  hash_service: bool = False,
                  health_window_s: float = 15.0) -> int:
    """Restart over the crashed datadir and check the invariant suite.

    Prints one ``RESULT {...}`` JSON line; exit 0 iff every invariant
    held.
    """
    import urllib.request

    from .trie.incremental import verify_state_root

    datadir = Path(datadir)
    recorded = _read_record(datadir)
    mined = [l for l in recorded if "hash" in l]
    t0 = time.time()
    inv: dict[str, object] = {}
    result: dict[str, object] = {"seed": seed, "invariants": inv}
    try:
        node, wallet, _ = _build_node(datadir, seed, threshold,
                                      hash_service, fresh=True)
    except Exception as e:  # noqa: BLE001 - a refused startup fails the suite
        result["ok"] = False
        result["error"] = f"restart refused: {type(e).__name__}: {e}"
        print("RESULT " + json.dumps(result))
        return 1
    try:
        result["recovery_report"] = node.recovery
        head_n = node.tree.persisted_number
        head_h = node.tree.persisted_hash
        result["recovered"] = {"number": head_n,
                               "hash": head_h.hex() if head_h else None}
        with node.factory.provider() as p:
            head_header = p.header_by_number(head_n)

        # 1. consistent head: startup recovery itself reported ok-or-
        # degraded (degraded = it healed something), never failed
        rep = node.recovery or {}
        inv["head_consistent"] = (rep.get("status") in ("ok", "degraded")
                                  and head_header is not None
                                  and head_header.hash == head_h)

        # 2. bounded loss: at most `threshold` blocks behind the last
        # RECORDED block (each record line is written only after its FCU
        # returned, so its persistence boundary had advanced; a recorded
        # deep reorg legitimately lowers the floor), and the recovered
        # head must BE a recorded block at that height
        if mined:
            by_height: dict[int, set] = {}
            floor = 0
            for l in recorded:
                if "reorg_to" in l:
                    floor = min(floor, l["reorg_to"])
                elif "hash" in l:
                    by_height.setdefault(l["n"], set()).add(l["hash"])
                    floor = max(floor, l["n"] - threshold)
            inv["loss_bound"] = (head_n >= floor
                                 and (head_n == 0
                                      or head_h.hex() in by_height.get(head_n, ())))
        else:
            inv["loss_bound"] = head_n == 0

        # 3. recovered state root bit-identical to recomputation through
        # the committer (READ-ONLY full verify over the hashed tables);
        # a verifier CRASH on corrupt rows is a failed invariant, not a
        # failed harness
        try:
            root, problems = verify_state_root(node.factory.provider(),
                                               node.committer)
            inv["root_recomputed"] = (head_header is not None
                                      and root == head_header.state_root
                                      and not problems)
            if problems:
                result["root_problems"] = problems[:5]
        except Exception as e:  # noqa: BLE001
            inv["root_recomputed"] = False
            result["root_problems"] = [f"verifier crashed: {e}"]

        # 4. bit-identical to a fault-free twin replaying the same blocks
        try:
            if head_n > 0:
                twin_root, twin_n = _twin_root(recorded, head_h, seed)
                inv["twin_root"] = (twin_root == head_header.state_root
                                    and twin_n == head_n)
            else:
                inv["twin_root"] = True
        except Exception as e:  # noqa: BLE001
            inv["twin_root"] = False
            result["twin_error"] = str(e)

        # 5. /health returns to ok within the SLO window
        http_port, _ = node.start_rpc()
        deadline = time.time() + health_window_s
        status = None
        while time.time() < deadline:
            try:
                raw = urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/health", timeout=5).read()
                status = json.loads(raw).get("status")
                if status == "ok":
                    break
            except Exception:  # noqa: BLE001 - 503 while failing
                pass
            time.sleep(0.25)
        inv["health_ok"] = status == "ok"
        result["health_status"] = status

        # 6. liveness: the node mines again on top of the recovered head
        # (wallet nonce continues from recovered state), and no lease
        # leaked across the crash
        try:
            with node.factory.provider() as p:
                acct = p.account(wallet.address)
            wallet.nonce = acct.nonce if acct is not None else 0
            node.pool.add_transaction(wallet.transfer(b"\x0c" * 20, 7))
            blk = node.miner.mine_block(timestamp=1_800_000_000)
            inv["liveness"] = blk.header.number == head_n + 1
        except Exception as e:  # noqa: BLE001 - a wedged node fails here
            inv["liveness"] = False
            result["liveness_error"] = str(e)
        svc = getattr(node.committer, "hash_service", None)
        inv["no_leaked_lease"] = (svc is None
                                  or not svc.snapshot().get("leased_by"))
    finally:
        try:
            node.stop()
        except Exception:  # noqa: BLE001 - verdict beats a clean exit
            pass
    result["ok"] = all(v is True for v in inv.values())
    result["wall_s"] = round(time.time() - t0, 2)
    print("RESULT " + json.dumps(result))
    return 0 if result["ok"] else 1


def _pool_burst(wallets, under_wallet, txs_per_wallet: int, rng, tag: int):
    """One adversarial submission round, per-sender order preserved by a
    round-robin interleave: fresh nonce-chain bases, one duplicate per
    wallet, alternating valid (2x, >= the 10% bump) and underpriced
    (+5%, below it) same-nonce replacements, plus one fee-capped-below-
    base-fee straggler. Yields ``(tx, must_admit)`` pairs."""
    from itertools import zip_longest

    from .primitives.types import Transaction

    sink = b"\x0f" * 20
    per_wallet = []
    for wi, w in enumerate(wallets):
        bases = [w.transfer(sink, 10**6 + tag * 10_000 + wi * 100 + k)
                 for k in range(txs_per_wallet)]
        seq = [(tx, True) for tx in bases]
        seq.append((bases[rng.randrange(len(bases))], False))  # duplicate
        tgt = bases[rng.randrange(len(bases))]
        if wi % 2 == 0:
            seq.append((w.sign_tx(Transaction(
                tx_type=2, chain_id=1, nonce=tgt.nonce,
                max_fee_per_gas=tgt.max_fee_per_gas * 2,
                max_priority_fee_per_gas=tgt.max_priority_fee_per_gas * 2,
                gas_limit=21_000, to=sink, value=tgt.value + 1,
            ), bump_nonce=False), True))
        else:
            seq.append((w.sign_tx(Transaction(
                tx_type=2, chain_id=1, nonce=tgt.nonce,
                max_fee_per_gas=tgt.max_fee_per_gas * 105 // 100,
                max_priority_fee_per_gas=tgt.max_priority_fee_per_gas,
                gas_limit=21_000, to=sink, value=tgt.value + 1,
            ), bump_nonce=False), False))
        per_wallet.append(seq)
    out = [e for rnd in zip_longest(*per_wallet) for e in rnd
           if e is not None]
    # admitted (funded, gapless) but effective tip < 0: a permanent
    # basefee-bucket straggler the producer must keep skipping
    out.insert(rng.randrange(len(out) + 1),
               (under_wallet.transfer(sink, 1, max_fee_per_gas=1,
                                      max_priority_fee_per_gas=0), True))
    return out


def child_pool_victim(datadir: str, seed: int) -> int:
    """(child) write-path drill victim: continuous-build fleet node
    mining off the hot candidate under a seeded adversarial pool flood
    (duplicates / replacements / underpriced), optionally rewound by a
    mid-storm reorg, recording every sealed block until the
    orchestrator's SIGKILL lands mid-build."""
    import random

    from .pool.pool import PoolError
    from .testing import Wallet

    scn = make_pool_scenario(seed)
    datadir = Path(datadir)
    node, wallet, _ = _build_node(datadir, seed, scn["threshold"],
                                  hash_service=False, fresh=True,
                                  fleet=True, continuous=True)
    node.start_rpc()
    rec = open(_record_path(datadir), "a")

    def record(blk):
        rec.write(json.dumps({
            "n": blk.header.number, "hash": blk.hash.hex(),
            "root": blk.header.state_root.hex(), "rlp": blk.encode().hex(),
        }) + "\n")
        rec.flush()

    # funding block: the flood wallets (and the underpriced straggler's)
    # get their balances on-chain first, so admission sees them funded
    wallets = [Wallet(0xF001E000 + seed * 64 + i)
               for i in range(scn["wallets"])]
    under_wallet = Wallet(0xF001E000 + seed * 64 + 63)
    for w in wallets + [under_wallet]:
        node.pool.add_transaction(wallet.transfer(w.address, 10**18))
    record(node.miner.mine_block())
    rng = random.Random(0xF001EE00 + seed)
    i = 1
    while True:  # until the orchestrator's SIGKILL
        i += 1
        for tx, must_admit in _pool_burst(wallets, under_wallet,
                                          scn["txs_per_wallet"], rng, i):
            try:
                node.pool.add_transaction(tx)
            except PoolError:
                if must_admit:
                    raise
        if scn["reorg_storm"] and i == scn["reorg_at"]:
            # rewind to a persisted ancestor ABOVE the funding block;
            # record the INTENT first (a crash mid-unwind legitimately
            # recovers to the reorg target). Unwound senders' local
            # nonces now lead the chain — their tail gaps and queues,
            # which is exactly the post-reorg pool shape to survive
            with node.factory.provider() as p:
                target = max(1, node.tree.persisted_number - 1)
                old = p.canonical_hash(target)
            rec.write(json.dumps({"reorg_to": target}) + "\n")
            rec.flush()
            node.tree.on_forkchoice_updated(old)
        record(node.miner.mine_block())


def child_pool_recover(datadir: str, seed: int) -> int:
    """Restart over the killed write-path victim's datadir and audit the
    producer/pool invariant suite. Prints one ``RESULT {...}`` line;
    exit 0 iff every invariant held:

    - consistent recovered head with bounded durable loss (as the
      storage suite defines them);
    - **no stuck candidate slot**: fresh load lands in a hot candidate
      that reaches pool-sequence parity on the recovered head, seals
      through the producer, and advances the chain;
    - **replacement semantics hold after restart**: a 2x same-nonce
      replacement wins the slot, a +5% one is refused, and the winner
      (never the base) is mined;
    - **replicas converge on the pending view**: a replica subscribed to
      the restarted feed serves ``txpool_content`` bit-identical to the
      leader's (``pt_*`` snapshot + live records);
    - **zero leaked leases**: no hash-service lease held and the
      candidate's commit-window lease released at rest."""
    import urllib.request  # noqa: F401 - _ha_rpc pulls it lazily

    from .pool.pool import PoolError
    from .primitives.types import Transaction
    from .testing import Wallet

    scn = make_pool_scenario(seed)
    datadir = Path(datadir)
    recorded = _read_record(datadir)
    mined = [l for l in recorded if "hash" in l]
    t0 = time.time()
    inv: dict[str, object] = {}
    result: dict[str, object] = {"seed": seed, "invariants": inv}
    try:
        node, wallet, _ = _build_node(datadir, seed, scn["threshold"],
                                      hash_service=False, fresh=True,
                                      fleet=True, continuous=True)
    except Exception as e:  # noqa: BLE001 - a refused startup fails the suite
        result["ok"] = False
        result["error"] = f"restart refused: {type(e).__name__}: {e}"
        print("RESULT " + json.dumps(result))
        return 1
    rproc = None
    try:
        result["recovery_report"] = node.recovery
        head_n = node.tree.persisted_number
        head_h = node.tree.persisted_hash
        result["recovered"] = {"number": head_n,
                               "hash": head_h.hex() if head_h else None}
        with node.factory.provider() as p:
            head_header = p.header_by_number(head_n)
        rep = node.recovery or {}
        inv["head_consistent"] = (rep.get("status") in ("ok", "degraded")
                                  and head_header is not None
                                  and head_header.hash == head_h)

        # bounded durable loss, exactly as the storage suite bounds it
        if mined:
            by_height: dict[int, set] = {}
            floor = 0
            for l in recorded:
                if "reorg_to" in l:
                    floor = min(floor, l["reorg_to"])
                elif "hash" in l:
                    by_height.setdefault(l["n"], set()).add(l["hash"])
                    floor = max(floor, l["n"] - scn["threshold"])
            inv["loss_bound"] = (head_n >= floor
                                 and (head_n == 0
                                      or head_h.hex() in by_height.get(head_n, ())))
        else:
            inv["loss_bound"] = head_n == 0

        http_port, _ = node.start_rpc()
        prod = node.producer

        # -- no stuck candidate slot: fresh load -> hot candidate at
        # pool parity on the recovered head, sealed by the producer
        with node.factory.provider() as p:
            acct = p.account(wallet.address)
        wallet.nonce = acct.nonce if acct is not None else 0
        fresh_w = Wallet(0xF001F000 + seed)
        node.pool.add_transaction(wallet.transfer(fresh_w.address, 10**18))
        for k in range(3):
            node.pool.add_transaction(wallet.transfer(b"\x0d" * 20, 50 + k))
        deadline = time.time() + 20
        parity = False
        while time.time() < deadline and not parity:
            with prod._lock:
                cand = prod.candidate
                with node.pool._lock:
                    parity = (cand is not None and cand.window is None
                              and cand.parent_hash == node.tree.head_hash
                              and cand.pool_seq == node.pool.event_seq
                              and len(cand.selected) == 4)
            if not parity:
                time.sleep(0.05)
        snap = prod.snapshot()
        inv["no_stuck_candidate"] = parity and snap["errors"] == 0
        result["producer"] = {k: snap[k] for k in
                              ("refreshes", "full_rebuilds", "hits",
                               "misses", "sealed", "errors")}
        blk = node.miner.mine_block()
        inv["liveness"] = (blk.header.number == head_n + 1
                           and len(blk.transactions) == 4
                           and node.miner.producer_seals >= 1)

        # -- replacement semantics after restart: 2x wins the slot, +5%
        # against the NEW occupant is refused, the winner gets mined
        sink = b"\x0e" * 20
        base = fresh_w.transfer(sink, 77)
        node.pool.add_transaction(base)
        repl = fresh_w.sign_tx(Transaction(
            tx_type=2, chain_id=1, nonce=base.nonce,
            max_fee_per_gas=base.max_fee_per_gas * 2,
            max_priority_fee_per_gas=base.max_priority_fee_per_gas * 2,
            gas_limit=21_000, to=sink, value=78), bump_nonce=False)
        node.pool.add_transaction(repl)
        under = fresh_w.sign_tx(Transaction(
            tx_type=2, chain_id=1, nonce=base.nonce,
            max_fee_per_gas=base.max_fee_per_gas * 105 // 100,
            max_priority_fee_per_gas=base.max_priority_fee_per_gas,
            gas_limit=21_000, to=sink, value=79), bump_nonce=False)
        under_refused = False
        try:
            node.pool.add_transaction(under)
        except PoolError:
            under_refused = True
        inv["replacement_semantics"] = (under_refused
                                        and repl.hash in node.pool.by_hash
                                        and base.hash not in node.pool.by_hash)
        blk2 = node.miner.mine_block()
        hashes = {t.hash for t in blk2.transactions}
        inv["replacement_mined"] = (repl.hash in hashes
                                    and base.hash not in hashes)

        # -- replica pending-view convergence: subscribe a replica to
        # the restarted feed (pt_snapshot anchors it), then push live
        # pending load incl. a replacement; its txpool_content must go
        # bit-identical to the leader's
        port_file = datadir / "replica.port"
        rlog = open(datadir / "replica.log", "w")
        rproc = subprocess.Popen(
            [sys.executable, "-m", "reth_tpu.fleet", "replica",
             "--feed", f"127.0.0.1:{node.feed_server.port}",
             "--port-file", str(port_file), "--id", "r0"],
            env=_child_env(), stdout=rlog, stderr=rlog)
        deadline = time.time() + 60
        while not port_file.exists() and time.time() < deadline:
            time.sleep(0.05)
        if not port_file.exists():
            raise RuntimeError("replica port file never appeared")
        rport = json.loads(port_file.read_text())["http_port"]
        pend = [fresh_w.transfer(b"\x0d" * 20, 200 + k) for k in range(3)]
        for tx in pend:
            node.pool.add_transaction(tx)
        repl2 = fresh_w.sign_tx(Transaction(
            tx_type=2, chain_id=1, nonce=pend[-1].nonce,
            max_fee_per_gas=pend[-1].max_fee_per_gas * 2,
            max_priority_fee_per_gas=pend[-1].max_priority_fee_per_gas * 2,
            gas_limit=21_000, to=b"\x0d" * 20, value=299), bump_nonce=False)
        node.pool.add_transaction(repl2)

        def buckets(content):
            return {b: {h["hash"] for by_nonce in content.get(b, {}).values()
                        for h in by_nonce.values()}
                    for b in ("pending", "queued")}

        deadline = time.time() + 30
        converged = False
        own = rep_view = None
        while time.time() < deadline and not converged:
            own = _ha_rpc(http_port, "txpool_content").get("result")
            try:
                rep_view = _ha_rpc(rport, "txpool_content").get("result")
            except Exception:  # noqa: BLE001 - replica still syncing
                rep_view = None
            converged = (own is not None and rep_view is not None
                         and buckets(own) == buckets(rep_view))
            if not converged:
                time.sleep(0.2)
        inv["replica_pending_view"] = converged
        if not converged and own is not None:
            result["pending_diff"] = {
                "leader": sorted(h for s in buckets(own).values() for h in s),
                "replica": (sorted(h for s in buckets(rep_view).values()
                                   for h in s)
                            if rep_view is not None else None)}

        # -- zero leaked leases: no hash-service lease held, and the
        # candidate's commit-window lease released once at rest
        deadline = time.time() + 10
        window_free = False
        while time.time() < deadline and not window_free:
            with prod._lock:
                cand = prod.candidate
                window_free = cand is None or cand.window is None
            if not window_free:
                time.sleep(0.05)
        svc = getattr(node.committer, "hash_service", None)
        inv["no_leaked_lease"] = (window_free
                                  and (svc is None
                                       or not svc.snapshot().get("leased_by")))
    except Exception as e:  # noqa: BLE001 — a crashed suite fails the drill
        result["ok"] = False
        result["error"] = f"{type(e).__name__}: {e}"
        print("RESULT " + json.dumps(result, default=str))
        return 1
    finally:
        if rproc is not None and rproc.poll() is None:
            rproc.kill()
            rproc.wait()
        try:
            node.stop()
        except Exception:  # noqa: BLE001 - verdict beats a clean exit
            pass
    result["ok"] = all(v is True for v in inv.values())
    result["wall_s"] = round(time.time() - t0, 2)
    print("RESULT " + json.dumps(result, default=str))
    return 0 if result["ok"] else 1


# -- orchestrator -------------------------------------------------------------


def _child_cmd(mode: str, datadir: Path, scn: dict) -> list[str]:
    if mode == "victim" and scn.get("domain") == "consensus":
        mode = "consensus"
    cmd = [sys.executable, "-m", "reth_tpu.chaos", mode,
           "--datadir", str(datadir), "--seed", str(scn["seed"]),
           "--threshold", str(scn["threshold"])]
    if scn.get("hash_service"):
        cmd.append("--hash-service")
    if mode == "consensus":
        cmd += ["--rounds", str(scn["rounds"])]
        if scn.get("force_deep_reorg"):
            cmd.append("--force-deep-reorg")
        if scn.get("pipeline"):
            cmd.append("--pipeline")
        if scn.get("hot_state"):
            cmd.append("--hot-state")
    elif mode == "victim":
        cmd += ["--blocks", str(scn["blocks"]),
                "--reorg-at", str(scn.get("reorg_at", 0))]
    return cmd


def _child_env(extra: dict | None = None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RETH_TPU_FAULT_")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra or {})
    return env


def run_scenario(scn: dict, base_dir: str | Path,
                 timeout: float = 240.0) -> dict:
    """One drill: victim under composed faults + kill, then recover."""
    datadir = Path(base_dir) / f"scn-{scn['seed']}"
    datadir.mkdir(parents=True, exist_ok=True)
    result = dict(scn)
    env = _child_env(scn["faults"])
    cmd = _child_cmd("victim", datadir, scn)
    log_path = datadir / "victim.log"

    def _log_tail() -> str:
        try:
            return log_path.read_text()[-400:]
        except OSError:
            return ""

    # consensus-domain victims count storm rounds, storage victims blocks
    count_flag = "--rounds" if scn.get("domain") == "consensus" else "--blocks"
    count_key = "rounds" if scn.get("domain") == "consensus" else "blocks"
    log = open(log_path, "w")
    try:
        if scn["mode"] == "point":
            env["RETH_TPU_FAULT_CRASH_AT"] = f"{scn['point']}:{scn['nth']}"
            # run until the point fires; cap so a mis-aimed nth still ends
            cmd[cmd.index(count_flag) + 1] = str(scn[count_key] + 20)
            proc = subprocess.Popen(cmd, env=env, stdout=log, stderr=log)
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                result.update(ok=False, error="victim timeout")
                return result
            result["victim_rc"] = proc.returncode
            if proc.returncode != 137:
                result.update(ok=False,
                              error=f"crash point never fired "
                                    f"(rc={proc.returncode}): {_log_tail()}")
                return result
        elif scn["mode"] == "complete":
            # the full storm runs to the end: the victim's own in-process
            # twin/leak invariants must hold (rc 0) before the restart
            # invariant suite runs below
            proc = subprocess.Popen(cmd, env=env, stdout=log, stderr=log)
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                result.update(ok=False, error="victim timeout")
                return result
            result["victim_rc"] = proc.returncode
            if proc.returncode != 0:
                result.update(ok=False,
                              error=f"storm failed its live invariants "
                                    f"(rc={proc.returncode}): {_log_tail()}")
                return result
        else:
            cmd[cmd.index(count_flag) + 1] = "0"  # run until killed
            proc = subprocess.Popen(cmd, env=env, stdout=log, stderr=log)
            rec = _record_path(datadir)
            deadline = time.time() + timeout
            while time.time() < deadline:
                if proc.poll() is not None:
                    result.update(ok=False,
                                  error=f"victim died early "
                                        f"rc={proc.returncode}: {_log_tail()}")
                    return result
                lines = len(_read_record(datadir)) if rec.exists() else 0
                if lines >= scn["kill_after"]:
                    break
                time.sleep(0.1)
            else:
                proc.kill()
                proc.wait()
                result.update(ok=False,
                              error="victim never reached kill depth")
                return result
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait()
            result["victim_rc"] = -9
    finally:
        log.close()
    result["blocks_recorded"] = len([l for l in _read_record(datadir)
                                     if "hash" in l])
    rproc = subprocess.run(_child_cmd("recover", datadir, scn),
                           env=_child_env(), capture_output=True, text=True,
                           timeout=timeout)
    verdict = None
    for line in rproc.stdout.splitlines():
        if line.startswith("RESULT "):
            verdict = json.loads(line[len("RESULT "):])
    if verdict is None:
        result.update(ok=False,
                      error=f"recover child emitted no verdict "
                            f"(rc={rproc.returncode}): {rproc.stderr[-400:]}")
        return result
    result.update(verdict)
    return result


def run_pool_scenario(scn: dict, base_dir: str | Path,
                      timeout: float = 240.0) -> dict:
    """One write-path drill: continuous-build victim under the seeded
    flood until it has recorded ``kill_after`` blocks, SIGKILL mid-build,
    then the pool recover child's invariant suite over the datadir."""
    datadir = Path(base_dir) / f"pool-{scn['seed']}"
    datadir.mkdir(parents=True, exist_ok=True)
    result = dict(scn)
    cmd = [sys.executable, "-m", "reth_tpu.chaos", "pool-victim",
           "--datadir", str(datadir), "--seed", str(scn["seed"])]
    log_path = datadir / "victim.log"

    def _log_tail() -> str:
        try:
            return log_path.read_text()[-400:]
        except OSError:
            return ""

    log = open(log_path, "w")
    try:
        proc = subprocess.Popen(cmd, env=_child_env(scn["faults"]),
                                stdout=log, stderr=log)
        rec = _record_path(datadir)
        deadline = time.time() + timeout
        while time.time() < deadline:
            if proc.poll() is not None:
                result.update(ok=False,
                              error=f"victim died early "
                                    f"rc={proc.returncode}: {_log_tail()}")
                return result
            lines = (len([l for l in _read_record(datadir) if "hash" in l])
                     if rec.exists() else 0)
            if lines >= scn["kill_after"]:
                break
            time.sleep(0.1)
        else:
            proc.kill()
            proc.wait()
            result.update(ok=False, error="victim never reached kill depth")
            return result
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait()
        result["victim_rc"] = -9
    finally:
        log.close()
    result["blocks_recorded"] = len([l for l in _read_record(datadir)
                                     if "hash" in l])
    rproc = subprocess.run(
        [sys.executable, "-m", "reth_tpu.chaos", "pool-recover",
         "--datadir", str(datadir), "--seed", str(scn["seed"])],
        env=_child_env(), capture_output=True, text=True, timeout=timeout)
    verdict = None
    for line in rproc.stdout.splitlines():
        if line.startswith("RESULT "):
            verdict = json.loads(line[len("RESULT "):])
    if verdict is None:
        result.update(ok=False,
                      error=f"pool recover emitted no verdict "
                            f"(rc={rproc.returncode}): {rproc.stderr[-400:]}")
        return result
    result.update(verdict)
    return result


_DOMAIN_MAKERS = {
    "storage": (make_scenario, run_scenario),
    "consensus": (make_consensus_scenario, run_scenario),
    "fleet": (make_fleet_scenario, run_fleet_scenario),
    "ha": (make_ha_scenario, run_ha_scenario),
    "pool": (make_pool_scenario, run_pool_scenario),
}


def run_campaign(seeds, base_dir: str | Path,
                 domain: str = "storage") -> list[dict]:
    make, run = _DOMAIN_MAKERS[domain]
    results = []
    for seed in seeds:
        scn = make(int(seed))
        t0 = time.time()
        res = run(scn, base_dir)
        res["scenario_wall_s"] = round(time.time() - t0, 1)
        tag = "ok" if res.get("ok") else "FAIL"
        mode = scn.get("mode", "sigkill-leader")
        if mode == "point":
            kill = f"point={scn.get('point')}:{scn.get('nth')}"
        elif mode == "kill" or domain == "ha":
            kill = f"kill_after={scn['kill_after']}"
        else:
            kill = mode
        print(f"chaos[{domain}] seed={seed} {tag} {kill} "
              f"faults={sorted(scn['faults'])} "
              f"blocks={res.get('blocks_recorded')} "
              f"recovered={res.get('recovered', {}).get('number')} "
              f"wall={res['scenario_wall_s']}s", flush=True)
        if not res.get("ok"):
            print(f"  replay: python -m reth_tpu.chaos scenario "
                  f"--domain {domain} --seed {seed}"
                  f"  ({res.get('error') or res.get('invariants')})",
                  flush=True)
        results.append(res)
    return results


# -- WAL corruption helper (negative drill + tests) ---------------------------


def inject_bad_crc_record(wal_dir: str | Path, delta: dict) -> None:
    """Append a record whose CRC is deliberately wrong to the newest WAL
    segment — the bit-rot shape. A correct reader discards it as a torn
    tail; the ``RETH_TPU_FAULT_WAL_ACCEPT_TORN`` broken reader applies
    it, and the chaos invariant suite must then catch the corruption
    (proving the harness can fail)."""
    import pickle

    segs = sorted(Path(wal_dir).glob("*.wal"))
    if not segs:
        raise FileNotFoundError(f"no WAL segments under {wal_dir}")
    payload = pickle.dumps({"seq": 1 << 40, "tables": delta},
                           protocol=pickle.HIGHEST_PROTOCOL)
    bad_crc = (zlib.crc32(payload) ^ 0xDEADBEEF) & 0xFFFFFFFF
    with open(segs[-1], "ab") as f:
        f.write(struct.pack("<II", len(payload), bad_crc) + payload)
        f.flush()
        os.fsync(f.fileno())


# -- CLI ----------------------------------------------------------------------


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m reth_tpu.chaos",
        description="chaos drill engine: crash points + composed fault "
                    "scenarios over subprocess dev nodes")
    sub = parser.add_subparsers(dest="command", required=True)

    pv = sub.add_parser("victim", help="(child) mine under faults until "
                                       "crashed or killed")
    pv.add_argument("--datadir", required=True)
    pv.add_argument("--seed", type=int, required=True)
    pv.add_argument("--blocks", type=int, default=10,
                    help="0 = mine until killed")
    pv.add_argument("--threshold", type=int, default=2)
    pv.add_argument("--reorg-at", dest="reorg_at", type=int, default=0)
    pv.add_argument("--hash-service", dest="hash_service",
                    action="store_true")

    pk = sub.add_parser("consensus",
                        help="(child) Engine-API adversarial storm until "
                             "done, crashed, or killed")
    pk.add_argument("--datadir", required=True)
    pk.add_argument("--seed", type=int, required=True)
    pk.add_argument("--rounds", type=int, default=20,
                    help="0 = storm until killed")
    pk.add_argument("--threshold", type=int, default=2)
    pk.add_argument("--hash-service", dest="hash_service",
                    action="store_true")
    pk.add_argument("--force-deep-reorg", dest="force_deep_reorg",
                    action="store_true")
    pk.add_argument("--pipeline", action="store_true",
                    help="storm a depth-2 cross-block import pipeline")
    pk.add_argument("--hot-state", dest="hot_state", action="store_true",
                    help="storm a hot-state-cached tree against an "
                         "uncached fault-free twin")

    pr = sub.add_parser("recover", help="(child) restart + invariant suite")
    pr.add_argument("--datadir", required=True)
    pr.add_argument("--seed", type=int, required=True)
    pr.add_argument("--threshold", type=int, default=2)
    pr.add_argument("--hash-service", dest="hash_service",
                    action="store_true")

    pf = sub.add_parser("fleet-victim",
                        help="(child) replica-fleet drill: load through "
                             "the ring while a replica dies mid-load")
    pf.add_argument("--datadir", required=True)
    pf.add_argument("--seed", type=int, required=True)

    ph = sub.add_parser("ha-victim",
                        help="(child) leader-kill HA drill: SIGKILL the "
                             "leader mid-load, audit the standby failover")
    ph.add_argument("--datadir", required=True)
    ph.add_argument("--seed", type=int, required=True)
    ph.add_argument("--no-fence", dest="no_fence", action="store_true",
                    help="negative drill: disable epoch fencing — the "
                         "old-leader invariant must fail")

    pl = sub.add_parser("ha-leader",
                        help="(child) HA leader: fleet+WAL dev node "
                             "mining until killed")
    pl.add_argument("--datadir", required=True)
    pl.add_argument("--seed", type=int, required=True)
    pl.add_argument("--threshold", type=int, default=2)
    pl.add_argument("--port-file", dest="port_file", default=None)

    pp = sub.add_parser("ha-fence-probe",
                        help="(child) restart the old leader against a "
                             "takeover feed peer; report fenced/unfenced")
    pp.add_argument("--datadir", required=True)
    pp.add_argument("--seed", type=int, required=True)
    pp.add_argument("--threshold", type=int, default=2)
    pp.add_argument("--peer", default="",
                    help="HOST:PORT of the promoted standby's feed")

    pw = sub.add_parser("pool-victim",
                        help="(child) write-path drill: continuous-build "
                             "node under adversarial pool flood until "
                             "SIGKILLed mid-build")
    pw.add_argument("--datadir", required=True)
    pw.add_argument("--seed", type=int, required=True)

    pq = sub.add_parser("pool-recover",
                        help="(child) restart the killed write-path "
                             "victim + producer/pool invariant suite")
    pq.add_argument("--datadir", required=True)
    pq.add_argument("--seed", type=int, required=True)

    ps = sub.add_parser("scenario", help="run one seeded scenario")
    ps.add_argument("--seed", type=int, required=True)
    ps.add_argument("--domain",
                    choices=("storage", "consensus", "fleet", "ha", "pool"),
                    default="storage")
    ps.add_argument("--base", default=None)

    pc = sub.add_parser("campaign", help="run a seeded scenario matrix")
    pc.add_argument("--seeds", default="1,2,3,4,5,6,7,8,9,10",
                    help="comma list, or N for range(1, N+1)")
    pc.add_argument("--domain",
                    choices=("storage", "consensus", "fleet", "ha", "pool"),
                    default="storage")
    pc.add_argument("--base", default=None)

    args = parser.parse_args(argv)
    if args.command == "victim":
        return child_victim(args.datadir, args.seed, args.blocks,
                            args.threshold, args.reorg_at, args.hash_service)
    if args.command == "consensus":
        return child_consensus_victim(args.datadir, args.seed, args.rounds,
                                      args.threshold, args.hash_service,
                                      args.force_deep_reorg, args.pipeline,
                                      args.hot_state)
    if args.command == "recover":
        return child_recover(args.datadir, args.seed, args.threshold,
                             args.hash_service)
    if args.command == "fleet-victim":
        return child_fleet_victim(args.datadir, args.seed)
    if args.command == "ha-victim":
        return child_ha_victim(args.datadir, args.seed, args.no_fence)
    if args.command == "ha-leader":
        return child_ha_leader(args.datadir, args.seed, args.threshold,
                               args.port_file)
    if args.command == "ha-fence-probe":
        return child_ha_fence_probe(args.datadir, args.seed,
                                    args.threshold, args.peer)
    if args.command == "pool-victim":
        return child_pool_victim(args.datadir, args.seed)
    if args.command == "pool-recover":
        return child_pool_recover(args.datadir, args.seed)
    import tempfile

    base = args.base or tempfile.mkdtemp(prefix="reth-tpu-chaos-")
    if args.command == "scenario":
        make, run = _DOMAIN_MAKERS[args.domain]
        res = run(make(args.seed), base)
        print(json.dumps(res, indent=2, default=str))
        return 0 if res.get("ok") else 1
    seeds = ([int(s) for s in args.seeds.split(",")]
             if "," in args.seeds else list(range(1, int(args.seeds) + 1)))
    results = run_campaign(seeds, base, domain=args.domain)
    bad = [r for r in results if not r.get("ok")]
    print(f"chaos campaign[{args.domain}]: "
          f"{len(results) - len(bad)}/{len(results)} passed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
