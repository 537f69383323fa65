"""Leader/standby high availability (reth_tpu/fleet/standby.py +
election.py): RTST1 wire vetting with the on-disk WAL discipline, the
promotion ladder, heartbeat-loss failover, epoch fencing, feed-client
reconnect hardening, and the leader-kill chaos drills."""

import json
import os
import pickle
import socket
import subprocess
import sys
import threading
import time
import urllib.request
import zlib
from pathlib import Path

import pytest

from reth_tpu.fleet.election import (
    STATES,
    HeartbeatMonitor,
    PromotionStateMachine,
    fence_check,
    fencing_disabled,
    probe_feed_hello,
)
from reth_tpu.fleet.feed import (
    FEED_MAGIC,
    ST_MAGIC,
    WitnessFeedClient,
    WitnessFeedServer,
    send_frame,
    recv_frame,
)
from reth_tpu.fleet.standby import StandbyFaultInjector, StandbyNode
from reth_tpu.rpc.gateway import classify
from reth_tpu.storage.kv import MemDb
from reth_tpu.storage.wal import WalStore

H1 = b"\x11" * 32
H2 = b"\x22" * 32


def _rpc(port, method, params):
    body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                       "params": params}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/", data=body,
        headers={"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req, timeout=15).read())


# -- promotion state machine --------------------------------------------------


def test_promotion_ladder_is_monotonic():
    seen = []
    sm = PromotionStateMachine(on_transition=lambda s, w: seen.append(s))
    assert sm.state == "following"
    assert not sm.advance("following")           # no self-loop
    assert sm.advance("catching-up", "hb loss")
    assert not sm.advance("following")           # never demotes
    assert sm.advance("promoting")
    assert sm.advance("leading")
    assert sm.is_leading()
    assert not sm.advance("catching-up")         # terminal forwardness
    assert not sm.advance("emperor")             # unknown state refused
    assert seen == ["catching-up", "promoting", "leading"]
    hist = [h["state"] for h in sm.snapshot()["history"]]
    assert hist == list(STATES)
    assert all(h["at"] > 0 for h in sm.snapshot()["history"])


def test_promotion_failed_is_terminal():
    sm = PromotionStateMachine()
    sm.advance("catching-up")
    assert sm.advance("failed", "root mismatch")
    assert sm.state == "failed"
    assert not sm.advance("promoting")
    assert not sm.advance("leading")
    assert not sm.is_leading()


def test_heartbeat_monitor_fires_once_per_arm_then_rearms_on_beat():
    losses = []
    mon = HeartbeatMonitor(timeout_s=0.1, on_loss=losses.append,
                           interval_s=0.02)
    mon.start()
    try:
        deadline = time.time() + 10
        while not losses and time.time() < deadline:
            time.sleep(0.01)
        assert len(losses) == 1
        time.sleep(0.3)
        assert len(losses) == 1                  # fired once per arm
        mon.note()                               # a beat re-arms the deadline
        deadline = time.time() + 10
        while len(losses) < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert len(losses) == 2
        assert mon.beats == 1 and mon.losses == 2
    finally:
        mon.stop()


# -- epoch fencing ------------------------------------------------------------


def _feed_server(epoch, rpc_port=12345):
    srv = WitnessFeedServer(None, chain_id=1)
    srv.epoch = epoch
    srv.rpc_port = rpc_port
    port = srv.start()
    return srv, port


def _dead_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_probe_feed_hello_carries_epoch_and_rpc_port():
    srv, port = _feed_server(3)
    try:
        hello = probe_feed_hello("127.0.0.1", port, timeout_s=5)
        assert hello["type"] == "hello"
        assert hello["epoch"] == 3
        assert hello["rpc_port"] == 12345        # replica re-anchor target
    finally:
        srv.stop()


def test_fence_check_detects_superseding_epoch():
    srv, port = _feed_server(3)
    try:
        rep = fence_check(2, [("127.0.0.1", port)], timeout_s=5)
        assert rep["fenced"] and rep["peer_epoch"] == 3
        assert rep["probed"] == 1
        assert rep["peer"] == f"127.0.0.1:{port}"
        # equal epoch does not fence (a node is never behind itself)
        rep = fence_check(3, [("127.0.0.1", port)], timeout_s=5)
        assert not rep["fenced"] and rep["peer_epoch"] is None
    finally:
        srv.stop()


def test_fence_check_unreachable_peer_is_not_fencing():
    rep = fence_check(1, [("127.0.0.1", _dead_port())], timeout_s=0.5)
    assert not rep["fenced"] and rep["probed"] == 0


def test_fence_check_no_fence_fault_reports_but_does_not_fence(monkeypatch):
    monkeypatch.setenv("RETH_TPU_FAULT_HA_NO_FENCE", "1")
    assert fencing_disabled()
    srv, port = _feed_server(9)
    try:
        rep = fence_check(1, [("127.0.0.1", port)], timeout_s=5)
        assert rep["disabled"] and not rep["fenced"]
        assert rep["peer_epoch"] == 9            # the fact is still reported
    finally:
        srv.stop()


# -- admission-class pinning (fleet_promote must never queue behind debug) ----


def test_ha_admin_methods_ride_engine_admission_class():
    assert classify("fleet_promote") == "engine"
    assert classify("fleet_standbyStatus") == "engine"
    assert classify("engine_forkchoiceUpdatedV3") == "engine"
    assert classify("debug_traceBlockByNumber") == "debug"  # the contrast


# -- RTST1 wire vetting: corruption handled exactly like on-disk replay -------


def _frame(kind, **kw):
    f = {"type": kind, "st": ST_MAGIC, "epoch": 1}
    f.update(kw)
    return f


def _wal_frame(gen, seq, delta, *, epoch=1, store=0, corrupt=False):
    payload = pickle.dumps({"seq": seq, "tables": delta},
                           protocol=pickle.HIGHEST_PROTOCOL)
    crc = zlib.crc32(payload)
    if corrupt:
        payload = payload[:-1] + bytes([payload[-1] ^ 0xFF])
    return _frame("st_wal", epoch=epoch, store=store, gen=gen, seq=seq,
                  payload=payload, crc=crc)


def _rows(table, rows):
    return {table: {"rows": rows}}


def _anchor(sb, *, gen=1, seq=0, epoch=1, tables=None, head=None):
    """In-stream image: the anchor every wire-vetting case starts from."""
    sb._on_record(_frame(
        "st_resync", epoch=epoch, store=0,
        tables=tables if tables is not None else {"accounts": {}},
        gen=gen, seq=seq, head=head))


@pytest.fixture
def standby(tmp_path):
    sb = StandbyNode("127.0.0.1", 1, datadir=tmp_path / "sb",
                     auto_promote=False, heartbeat_timeout_s=60,
                     standby_id="t-standby")
    yield sb
    for st in sb.stores.values():
        try:
            st.wal.close()
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass


def test_standby_resync_anchors_then_stream_applies(standby):
    _anchor(standby, tables={"accounts": {b"a": b"1"}}, head=(1, H1))
    assert standby.resyncs_applied == 1
    st = standby.stores[0]
    assert not st.awaiting_resync and st.pos == (1, 0)
    assert standby.applied_head == (1, H1)
    assert st.db._tables["accounts"][b"a"] == b"1"
    standby._on_record(_wal_frame(1, 1, _rows("accounts", {b"b": b"2"})))
    assert standby.records_applied == 1 and st.pos == (1, 1)
    assert st.db._tables["accounts"][b"b"] == b"2"
    # the standby re-appended the shipped record into its OWN WAL
    assert st.wal.appends == 1


def test_standby_rejects_corrupt_payload_and_reanchors(standby):
    _anchor(standby)
    standby._on_record(
        _wal_frame(1, 1, _rows("t", {b"k": b"v"}), corrupt=True))
    assert standby.crc_rejected == 1
    assert standby.records_applied == 0
    assert standby.stores[0].awaiting_resync
    assert standby.resyncs_requested == 1
    # records streaming while the image is pending are not applied
    standby._on_record(_wal_frame(1, 1, _rows("t", {b"k": b"v"})))
    assert standby.records_applied == 0
    # the fresh image re-anchors and the stream continues
    _anchor(standby, gen=1, seq=1)
    standby._on_record(_wal_frame(1, 2, _rows("t", {b"k": b"v"})))
    assert standby.records_applied == 1


def test_standby_rejects_undecodable_payload_as_torn(standby):
    _anchor(standby)
    garbage = b"\x80\x05 not a pickle"
    standby._on_record(_frame("st_wal", store=0, gen=1, seq=1,
                              payload=garbage, crc=zlib.crc32(garbage)))
    assert standby.crc_rejected == 1 and standby.records_applied == 0


def test_standby_epoch_ladder_stale_refused_higher_adopted(standby):
    _anchor(standby)
    st = standby.stores[0]
    # a HIGHER epoch in-stream is a new leader lineage: adopt + re-anchor
    standby._on_record(_wal_frame(1, 1, _rows("t", {}), epoch=2))
    assert standby.leader_epoch == 2
    assert st.awaiting_resync and standby.resyncs_requested == 1
    assert standby.records_applied == 0
    _anchor(standby, epoch=2)
    assert not st.awaiting_resync
    # a STALE epoch is a fenced old leader still talking: refused
    standby._on_record(_wal_frame(1, 1, _rows("t", {b"k": b"v"}), epoch=1))
    assert standby.stale_epoch_rejected == 1
    assert standby.records_applied == 0
    assert b"k" not in st.db._tables.get("t", {})


def test_standby_rejects_out_of_order_generation(standby):
    _anchor(standby, gen=3, seq=5)
    standby._on_record(_wal_frame(2, 6, _rows("t", {})))
    assert standby.gen_rejected == 1
    assert standby.records_applied == 0
    assert standby.stores[0].awaiting_resync


def test_standby_duplicate_skipped_gap_reanchors(standby):
    _anchor(standby)
    standby._on_record(_wal_frame(1, 1, _rows("t", {b"a": b"1"})))
    standby._on_record(_wal_frame(1, 1, _rows("t", {b"a": b"X"})))
    assert standby.records_duplicate == 1
    assert standby.stores[0].db._tables["t"][b"a"] == b"1"  # first wins
    standby._on_record(_wal_frame(1, 3, _rows("t", {b"c": b"3"})))  # skips 2
    assert standby.gap_detected == 1
    assert standby.stores[0].awaiting_resync
    assert standby.records_applied == 1


def test_standby_heartbeat_tracks_leader_head_and_lag(standby):
    _anchor(standby, head=(3, H1))
    standby._on_record(_frame("st_heartbeat", head=(7, H2)))
    assert standby.monitor.beats == 1
    assert standby.leader_head == (7, H2)
    assert standby.lag_heads() == 4
    s = standby.status()
    assert s["lag_heads"] == 4 and s["state"] == "following"
    assert s["applied_head"]["number"] == 3


def test_standby_manifest_checkpoints_own_wal(standby):
    _anchor(standby)
    standby._on_record(_wal_frame(1, 1, _rows("t", {b"a": b"1"})))
    ck0 = standby.stores[0].wal.checkpoints
    standby._on_record(_frame(
        "st_manifest", store=0,
        manifest={"gen": 2, "head_number": 4, "head_hash": "ab" * 32}))
    assert standby.manifests_applied == 1
    assert standby.persisted_head == (4, "ab" * 32)
    assert standby.stores[0].wal.checkpoints == ck0 + 1
    assert standby.stores[0].pos == (2, 1)  # gen tracks the leader's


def test_standby_datadir_survives_restart(tmp_path):
    d = tmp_path / "sb"
    sb = StandbyNode("127.0.0.1", 1, datadir=d, auto_promote=False)
    _anchor(sb, tables={"accounts": {b"a": b"1"}})
    sb._on_record(_wal_frame(1, 1, _rows("accounts", {b"b": b"2"})))
    sb._on_record(
        _wal_frame(1, 2, {"accounts": {"del": [b"a"]}}))
    for st in sb.stores.values():
        st.wal.close()
    # a killed-and-restarted standby replays its OWN WAL back to the
    # last complete shipped commit
    sb2 = StandbyNode("127.0.0.1", 1, datadir=d, auto_promote=False)
    t = sb2.stores[0].db._tables["accounts"]
    assert t.get(b"b") == b"2" and b"a" not in t
    for st in sb2.stores.values():
        st.wal.close()


def test_wal_manifest_persists_leader_epoch(tmp_path):
    db = MemDb(tmp_path / "db.bin")
    wal = WalStore.open(db, tmp_path / "wal")
    wal.append(_rows("t", {b"k": b"v"}))
    wal.epoch = 7
    wal.checkpoint(head=(3, b"\xaa" * 32))
    wal.close()
    db2 = MemDb(tmp_path / "db.bin")
    wal2 = WalStore.open(db2, tmp_path / "wal")
    assert wal2.epoch == 7                       # the fencing token survives
    wal2.close()


def test_wal_observer_ships_exact_on_disk_payload(tmp_path, standby):
    """The leader's post-fsync observer ships the RAW record payload; a
    standby anchored at the same position applies it bit-for-bit."""
    db = MemDb(tmp_path / "leader.bin")
    wal = WalStore.open(db, tmp_path / "leader-wal")
    shipped = []
    wal.observer = lambda gen, seq, payload: shipped.append(
        (gen, seq, payload))
    wal.append(_rows("t", {b"k": b"v"}))
    wal.close()
    assert len(shipped) == 1
    gen, seq, payload = shipped[0]
    _anchor(standby, gen=gen, seq=seq - 1)
    standby._on_record(_frame("st_wal", store=0, gen=gen, seq=seq,
                              payload=payload, crc=zlib.crc32(payload)))
    assert standby.records_applied == 1
    assert standby.stores[0].db._tables["t"][b"k"] == b"v"


# -- fault injectors ----------------------------------------------------------


def test_standby_fault_injector_from_env():
    assert StandbyFaultInjector.from_env({}) is None
    inj = StandbyFaultInjector.from_env({"RETH_TPU_FAULT_STANDBY_WEDGE": "3"})
    assert inj.wedge and inj.wedge_after == 3
    assert not inj.on_record("st_wal")
    assert not inj.on_record("st_wal")
    assert inj.on_record("st_wal")               # 3rd record onward dropped
    assert inj.on_record("st_fcu")
    assert inj.dropped == 2
    inj = StandbyFaultInjector.from_env(
        {"RETH_TPU_FAULT_STANDBY_LAG": "0.001"})
    assert inj.lag_s == 0.001 and not inj.wedge
    assert not inj.on_record("st_wal")
    assert inj.lagged == 1


def test_standby_wedge_freezes_replication_not_heartbeats(tmp_path):
    inj = StandbyFaultInjector(wedge=True, wedge_after=2)
    sb = StandbyNode("127.0.0.1", 1, datadir=tmp_path / "sb",
                     auto_promote=False, injector=inj)
    try:
        _anchor(sb)                              # 1st record: passes
        sb._on_record(_wal_frame(1, 1, _rows("t", {b"a": b"1"})))
        assert sb.records_applied == 0 and inj.dropped == 1
        sb._on_record(_frame("st_heartbeat", head=(5, H1)))
        assert sb.monitor.beats == 1             # a live but stuck standby
        assert sb.status()["wedged"]
    finally:
        for st in sb.stores.values():
            st.wal.close()


def test_standby_never_promotes_before_seeing_a_leader(tmp_path):
    """A standby that starts first (leader still booting) must not fire
    heartbeat-loss promotion over an empty datadir."""
    sb = StandbyNode("127.0.0.1", 1, datadir=tmp_path / "sb",
                     auto_promote=True, heartbeat_timeout_s=60)
    try:
        sb._on_heartbeat_loss(99.0)
        time.sleep(0.2)
        assert sb.promotion.state == "following"
    finally:
        for st in sb.stores.values():
            st.wal.close()


# -- admin RPC surface --------------------------------------------------------


def test_fleet_standby_status_rpc(tmp_path):
    sb = StandbyNode("127.0.0.1", 1, datadir=tmp_path / "sb",
                     auto_promote=False, standby_id="t-status")
    port = sb.rpc.start()
    try:
        _anchor(sb)
        res = _rpc(port, "fleet_standbyStatus", [])["result"]
        assert res["state"] == "following"
        assert res["resyncs_applied"] == 1
        assert res["id"] == "t-status"
        assert res["leader_epoch"] == 1
        assert res["node"] is None
    finally:
        sb.rpc.stop()
        for st in sb.stores.values():
            st.wal.close()


# -- feed-client reconnect hardening ------------------------------------------


class _FlakyFeed:
    """A feed endpoint that refuses the first ``flaps`` connections
    (accept-then-close mid-handshake), then serves real sessions and
    captures upstream frames."""

    def __init__(self, flaps=3, head=None, epoch=1):
        self.flaps = flaps
        self.head = head
        self.epoch = epoch
        self.upstream = []
        self.attempts = 0
        self.sessions = 0
        self._srv = socket.socket()
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(16)
        self.port = self._srv.getsockname()[1]
        self._stop = threading.Event()
        self._conns = []
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while not self._stop.is_set():
            try:
                sock, _ = self._srv.accept()
            except OSError:
                return
            self.attempts += 1
            if self.attempts <= self.flaps:
                sock.close()
                continue
            self.sessions += 1
            self._conns.append(sock)
            threading.Thread(target=self._serve, args=(sock,),
                             daemon=True).start()

    def _serve(self, sock):
        try:
            sock.sendall(FEED_MAGIC)
            send_frame(sock, {"type": "hello", "chain_id": 1,
                              "head": self.head, "epoch": self.epoch,
                              "rpc_port": None, "spec": None})
            if self.head is not None:
                send_frame(sock, {"type": "head", "number": self.head[0],
                                  "hash": self.head[1]})
            while not self._stop.is_set():
                self.upstream.append(recv_frame(sock))
        except Exception:  # noqa: BLE001 - session death ends the serve
            pass

    def drop_all(self):
        for s in self._conns:
            # shutdown (not just close): the serve thread blocked in
            # recv holds the fd open, so close alone never sends FIN
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass
        self._conns.clear()

    def stop(self):
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
        self.drop_all()


def test_client_reconnects_through_flapping_server():
    srv = _FlakyFeed(flaps=3)
    hellos = []
    cli = WitnessFeedClient("127.0.0.1", srv.port, on_hello=hellos.append,
                            backoff_s=0.02, backoff_max_s=0.2)
    cli.start()
    try:
        assert cli.connected.wait(30)
        assert srv.attempts >= 4                 # 3 refused + the real one
        assert cli.connections == 1              # only real sessions count
        assert hellos and hellos[0]["epoch"] == 1
        assert cli.endpoint == ("127.0.0.1", srv.port)
    finally:
        cli.stop()
        srv.stop()


def test_client_resubscribes_from_last_seen_head():
    srv = _FlakyFeed(flaps=0, head=(5, b"\x55" * 32))
    cli = WitnessFeedClient("127.0.0.1", srv.port,
                            backoff_s=0.02, backoff_max_s=0.2)
    cli.start()
    try:
        assert cli.connected.wait(15)
        deadline = time.time() + 15
        while cli.last_seen_head is None and time.time() < deadline:
            time.sleep(0.01)
        assert cli.last_seen_head == (5, b"\x55" * 32)
        assert cli.resubscribes == 0             # nothing seen pre-session
        srv.drop_all()                           # transport dies mid-stream
        deadline = time.time() + 30
        while cli.resubscribes == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert cli.resubscribes >= 1
        assert cli.connections >= 2
        deadline = time.time() + 15
        while not any(f.get("type") == "resubscribe" for f in srv.upstream) \
                and time.time() < deadline:
            time.sleep(0.01)
        subs = [f for f in srv.upstream if f.get("type") == "resubscribe"]
        assert subs and subs[0]["number"] == 5   # from the LAST SEEN head
    finally:
        cli.stop()
        srv.stop()


def test_client_rotates_to_failover_endpoint():
    """The HA failover ladder: the primary feed is dead, the standby's
    takeover endpoint serves — the client rotates onto it."""
    srv = _FlakyFeed(flaps=0, epoch=2)
    hellos = []
    cli = WitnessFeedClient("127.0.0.1", _dead_port(),
                            on_hello=hellos.append,
                            backoff_s=0.02, backoff_max_s=0.2,
                            endpoints=[("127.0.0.1", srv.port)])
    cli.start()
    try:
        assert cli.connected.wait(30)
        assert cli.endpoint == ("127.0.0.1", srv.port)
        assert hellos[0]["epoch"] == 2           # the promoted lineage
    finally:
        cli.stop()
        srv.stop()


# -- live replication + promotion + fencing (in-process) ----------------------


def _mk_node(datadir, wallet, *, ha_peer_feeds=(), start_rpc=True):
    from reth_tpu.node import Node, NodeConfig
    from reth_tpu.primitives.keccak import keccak256_batch_np
    from reth_tpu.primitives.types import Account
    from reth_tpu.testing import ChainBuilder
    from reth_tpu.trie.committer import TrieCommitter

    committer = TrieCommitter(hasher=keccak256_batch_np)
    committer.turbo_backend = "numpy"
    builder = ChainBuilder({wallet.address: Account(balance=10**21)},
                           committer=committer)
    node = Node(NodeConfig(dev=True, genesis_header=builder.genesis,
                           genesis_alloc=builder.accounts_at_genesis,
                           fleet=True, wal=True, datadir=str(datadir),
                           db_backend="memdb", persistence_threshold=1,
                           http_port=0, authrpc_port=0,
                           ha_peer_feeds=tuple(ha_peer_feeds)),
                committer=committer)
    if start_rpc:
        node.start_rpc()
    return node


def test_leader_standby_replication_promotion_and_fencing(tmp_path):
    """The tentpole, in-process: WAL-shipped replication into the
    standby's own datadir, promotion with root verification over the
    recovered head, a bumped epoch on the takeover feed, and the old
    leader fencing itself on restart."""
    from reth_tpu.engine.tree import PayloadStatusKind
    from reth_tpu.testing import Wallet

    wallet = Wallet(0xAB5B)
    leader = _mk_node(tmp_path / "leader", wallet)
    leader_alive = True
    sb = old = None
    try:
        fport = leader.feed_server.port
        sb = StandbyNode("127.0.0.1", fport, datadir=tmp_path / "standby",
                         auto_promote=False, heartbeat_timeout_s=60,
                         standby_id="t-ha")
        sb.start()
        sink = b"\x0c" * 20
        for i in range(4):
            leader.pool.add_transaction(wallet.transfer(sink, 1000 + i))
            leader.miner.mine_block(timestamp=1_700_000_000 + i * 12)
        deadline = time.time() + 90
        while time.time() < deadline:
            if (sb.applied_head and sb.applied_head[0] == 4
                    and sb.records_applied > 0
                    and not any(st.awaiting_resync
                                for st in sb.stores.values())):
                break
            time.sleep(0.05)
        assert sb.applied_head and sb.applied_head[0] == 4, sb.status()
        assert sb.resyncs_applied >= 1           # first connect = image
        assert sb.lag_heads() == 0

        leader.stop()                            # the leader dies
        leader_alive = False
        old_epoch = sb.leader_epoch
        assert sb.promote("drill") is True, sb.status()
        assert sb.promotion.is_leading()
        st = sb.status()
        assert st["state"] == "leading"
        assert st["leader_epoch"] == old_epoch + 1
        rec = st["node"]["recovery"]
        assert rec["root_verified"] is True      # recomputed at takeover
        assert rec["status"] != "failed"
        assert st["promote_ms"] and st["promote_ms"] > 0
        # the takeover feed advertises the bumped epoch (fencing token)
        hello = probe_feed_hello("127.0.0.1", st["node"]["feed_port"],
                                 timeout_s=5)
        assert hello["epoch"] == old_epoch + 1
        # the promoted node serves the replicated chain (threshold=1:
        # at most the last in-flight block is shed)
        res = _rpc(st["node"]["http_port"], "eth_blockNumber", [])
        assert int(res["result"], 16) >= 3

        # a restarted old leader probes the takeover feed and fences
        old = _mk_node(
            tmp_path / "leader", wallet, start_rpc=False,
            ha_peer_feeds=(f"127.0.0.1:{st['node']['feed_port']}",))
        assert old.fence_report and old.fence_report["fenced"], \
            old.fence_report
        assert old.tree.fenced
        r = old.tree.on_forkchoice_updated(b"\x00" * 32)
        assert r.status is PayloadStatusKind.INVALID
        assert "fenced" in (r.validation_error or "")
    finally:
        if old is not None:
            old.stop()
        if sb is not None:
            sb.stop()
        if leader_alive:
            leader.stop()


# -- chaos drills + bench (multi-process, slow) -------------------------------

_HA_INVARIANTS = ("promoted", "root_verified", "loss_bound",
                  "root_twin_identical", "replicas_reanchored",
                  "no_failed_reads", "old_leader_fenced")


@pytest.mark.slow
def test_ha_chaos_leader_kill_single_seed(tmp_path):
    from reth_tpu.chaos import make_ha_scenario, run_ha_scenario

    scn = make_ha_scenario(1)
    assert scn["domain"] == "ha" and scn["replicas"] == 2
    res = run_ha_scenario(scn, tmp_path, timeout=420)
    assert res.get("ok") is True, res
    inv = res.get("invariants", {})
    for k in _HA_INVARIANTS:
        assert inv.get(k) is True, (k, res)


@pytest.mark.slow
def test_ha_chaos_campaign_ten_seeds(tmp_path):
    from reth_tpu.chaos import run_campaign

    results = run_campaign(range(1, 11), tmp_path, domain="ha")
    assert len(results) == 10
    bad = [r for r in results if not r.get("ok")]
    assert not bad, bad


@pytest.mark.slow
def test_ha_chaos_negative_no_fence_drill_fails(tmp_path):
    """RETH_TPU_FAULT_HA_NO_FENCE disables the old leader's fencing
    probe; the invariant suite must notice the split brain — proof the
    drills can fail."""
    from reth_tpu.chaos import make_ha_scenario, run_ha_scenario

    scn = make_ha_scenario(2)
    scn["no_fence"] = True
    res = run_ha_scenario(scn, tmp_path, timeout=420)
    assert res.get("invariants", {}).get("old_leader_fenced") is False, res
    assert res.get("ok") is not True, res


@pytest.mark.slow
def test_bench_ha_mode_end_to_end(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RETH_TPU_FAULT_")}
    env.update(JAX_PLATFORMS="cpu", RETH_TPU_BENCH_MODE="ha",
               RETH_TPU_BENCH_HA_BLOCKS="4")
    repo = Path(__file__).resolve().parent.parent
    r = subprocess.run([sys.executable, str(repo / "bench.py")],
                       capture_output=True, text=True, timeout=560,
                       env=env, cwd=repo)
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["metric"] == "ha_promote_ms"
    assert line.get("error") is None, line
    assert line["value"] > 0
    assert line["reads_failed"] == 0
    assert line["promoted_reads_failed"] == 0
    assert line["replicas_reanchored"] is True
    assert line["leader_epoch"] == 2
    assert r.returncode == 0, (line, r.stderr[-800:])
