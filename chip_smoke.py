#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that reth-tpu's state-commitment path
still starts, and is right, on the chip.

One process (so the chip has one owner and the metrics registry can be read
after each phase) drives the normal entry points — ``reth_tpu.cli.main`` —
through five phases on ONE TPU chip::

    kernels  KeccakDevice at the menu ceiling (block tiers 1/4/32) + the
             Pallas kernel, digests vs the pure-Python keccak256
    init     `init --hasher device` on a generated ~20k-account genesis;
             genesis hash equals `init --hasher cpu`
    import   a ChainBuilder chain written as RLP -> `import --hasher
             device` (hashing stages + MerkleStage's clean turbo path),
             then `db verify-trie`
    node     `node --dev --block-time 0 --hasher device` on a thread;
             transactions over HTTP JSON-RPC, each mined; block / balance /
             proof answered, the proof verified against the state root
    rebuild  TurboCommitter(backend="device").commit_hashed_pipelined — the
             call MerkleStage._account_chunk makes — over 1,000,000 hashed
             accounts as 256 two-nibble-prefix subtries + 1,000,000 slots
             over 100,000 storage tries; roots equal the numpy twin's; run
             twice so cold compile and steady state are told apart

After every phase the registry must show that the device did the work and
that NO route onto the CPU moved a counter (ops/device.CPU_ROUTE_COUNTERS;
the over-32-block bucket is reported separately). Any failed phase raises:
no exception is caught and carried past. The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and is printed only on a TPU. With no accelerator the script exits non-zero
at once and prints no result; with ``JAX_PLATFORMS=cpu`` (a rehearsal — see
``--tiny``) every phase runs and the script then exits non-zero at the
platform check, again without a result line.

``--chips 4`` runs ONLY the four-chip path and what it is compared with:
the same rebuild state through ``HashMesh.build(4)`` +
``TurboCommitter(backend="device", mesh=...)`` against the single-device
commit, roots equal, level inputs and digest arena on four distinct
devices.

Everything is generated from ``--seed``; nothing is read from outside the
checkout; datadirs are fresh temporary directories (the compile cache is
not in them: ops/device.configure_compile_cache).
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np

# full = what the driver runs; tiny = the CPU rehearsal / test size
SIZES = {
    "full": dict(kernel_rows=16384, init_accounts=20_000, init_contracts=100,
                 init_slots=100, import_accounts=1_000, import_blocks=24,
                 import_txs=8, node_txs=6, rebuild_accounts=1_000_000,
                 rebuild_slots=1_000_000, ref_sample=256),
    "tiny": dict(kernel_rows=256, init_accounts=300, init_contracts=4,
                 init_slots=5, import_accounts=40, import_blocks=3,
                 import_txs=3, node_txs=3, rebuild_accounts=6_000,
                 rebuild_slots=6_000, ref_sample=32),
}

# sstore(calldata[0:32], calldata[32:64])
STORE_CODE = bytes.fromhex("6020355f355500")
STORE_INITCODE = bytes([0x60, len(STORE_CODE), 0x60, 0x0B, 0x5F, 0x39,
                        0x60, len(STORE_CODE), 0x5F, 0xF3, 0x00]) + STORE_CODE

DEVICE: dict = {}
CACHE_EVENTS = {"hits": 0, "misses": 0}


def _host_rss_mb() -> dict:
    """Resident and peak resident host memory of this process (MiB)."""
    out = {}
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(("VmRSS:", "VmHWM:")):
                key = "rss" if line.startswith("VmRSS") else "peak_rss"
                out[key] = int(line.split()[1]) // 1024
    return out


def emit(phase: str, wall: float, **checked) -> None:
    print(json.dumps({"phase": phase, "wall_s": round(wall, 3),
                      "device": DEVICE, "host_mib": _host_rss_mb(),
                      **checked}), flush=True)


def _device_work() -> dict[str, float]:
    from reth_tpu.metrics import REGISTRY

    live = dict(REGISTRY.items())

    def counter(name: str) -> float:
        return float(getattr(live.get(name), "value", 0.0))

    return {
        "turbo_nodes": counter("trie_commit_nodes_total_device"),
        "keccak_calls": (counter("keccak_dispatch_total")
                         + counter("keccak_compile_total")),
        "fused_dispatches": counter("fused_dispatches_total"),
    }


@contextlib.contextmanager
def on_device(phase: str, need: tuple[str, ...]):
    """Bracket the part of a phase that was asked of the device: on exit
    the ``need`` work counters must have moved, no CPU-route counter may
    have, and the breaker (where a supervisor exists) must be closed."""
    from reth_tpu.ops.device import (OVER_CEILING_COUNTER,
                                     cpu_route_counters, moved_cpu_routes)
    from reth_tpu.ops.supervisor import CLOSED, DeviceSupervisor

    routes0, work0 = cpu_route_counters(), _device_work()
    report: dict = {}
    yield report
    moved = moved_cpu_routes(routes0)
    if moved:
        raise AssertionError(
            f"{phase}: work left the device — CPU-route counters moved: "
            f"{moved}")
    work = {k: v - work0[k] for k, v in _device_work().items()}
    idle = [k for k in need if work[k] <= 0]
    if idle:
        raise AssertionError(
            f"{phase}: the device did no {idle} work (deltas {work})")
    sup = DeviceSupervisor._shared
    if sup is not None and sup.breaker.state != CLOSED:
        raise AssertionError(f"{phase}: breaker is {sup.breaker.state}")
    over = cpu_route_counters()[OVER_CEILING_COUNTER]
    report.update(device_work=work, cpu_routes_moved={},
                  over_ceiling_buckets=over - routes0[OVER_CEILING_COUNTER])


def cli(argv: list[str]) -> str:
    """``reth_tpu.cli.main(argv)`` in-process; returns what it printed
    (echoed to stderr so stdout keeps to one JSON line per phase)."""
    from reth_tpu.cli import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    sys.stderr.write(buf.getvalue())
    if rc != 0:
        raise RuntimeError(f"reth-tpu {' '.join(argv[:2])} exited {rc}")
    return buf.getvalue()


def _messages(rng, n: int, lo: int, hi: int) -> list[bytes]:
    lens = rng.integers(lo, hi + 1, size=n)
    blob = rng.bytes(int(lens.sum()))
    offs = np.concatenate([[0], np.cumsum(lens)])
    return [blob[offs[i]:offs[i + 1]] for i in range(n)]


# -- phase: kernels -----------------------------------------------------------


def phase_kernels(sz: dict, rng) -> None:
    from reth_tpu.metrics import compile_tracker
    from reth_tpu.ops.keccak_jax import KeccakDevice, _to_u32
    from reth_tpu.ops.keccak_pallas import keccak256_pallas_words
    from reth_tpu.primitives.keccak import (RATE, keccak256,
                                            keccak256_batch_np, pad_batch)

    t0 = time.time()
    rows = sz["kernel_rows"]
    msgs = {1: _messages(rng, rows, 0, RATE - 1),
            4: _messages(rng, rows, 0, 4 * RATE - 1),
            32: _messages(rng, rows, 16 * RATE, 32 * RATE - 1)}
    got: dict = {}
    with on_device("kernels", ("keccak_calls",)) as rep:
        got[1] = KeccakDevice(min_tier=1024).hash_batch(msgs[1])
        masked = KeccakDevice(min_tier=1024, block_tier=4)
        got[4] = masked.hash_batch(msgs[4])
        got[32] = masked.hash_batch(msgs[32])
        w32 = _to_u32(pad_batch(msgs[1], 1), rows)
        direct = np.ascontiguousarray(keccak256_pallas_words(
            w32, interpret=DEVICE["platform"] != "tpu"))  # (rows, 8) u32
        got["pallas"] = [r.tobytes() for r in direct]
        os.environ["RETH_TPU_PALLAS"] = "1"
        try:
            got["pallas_route"] = KeccakDevice(min_tier=1024).hash_batch(msgs[1])
        finally:
            del os.environ["RETH_TPU_PALLAS"]
    shapes = {k[0]: k[1:] for k in compile_tracker.shapes}
    for kind in ("keccak.exact", "keccak.masked", "keccak.pallas"):
        assert kind in shapes, f"{kind} never dispatched: {sorted(shapes)}"
    # reference: the pure-Python keccak256, row for row on the single-block
    # tier (which the Pallas kernel shares); on the 4- and 32-block tiers
    # (16 ms/row in pure Python) every row against the independent numpy
    # twin plus a seeded sample against keccak256
    ref1 = [keccak256(m) for m in msgs[1]]
    for name in (1, "pallas", "pallas_route"):
        assert got[name] == ref1, f"kernels: {name} digests differ"
    sample = rng.choice(rows, size=min(rows, sz["ref_sample"]), replace=False)
    for tier in (4, 32):
        assert got[tier] == keccak256_batch_np(msgs[tier]), (
            f"kernels: tier {tier} differs from the numpy twin")
        for i in sample[: len(sample) if tier == 4 else len(sample) // 8]:
            assert got[tier][i] == keccak256(msgs[tier][i]), (
                f"kernels: tier {tier} row {i} differs from keccak256")
    emit("kernels", time.time() - t0, rows=rows, block_tiers=[1, 4, 32],
         pallas={"direct": True, "via_keccak_device": True,
                 "interpret": DEVICE["platform"] != "tpu"},
         checked=f"tier 1 + pallas: all {rows} rows == keccak256; tiers "
                 f"4/32: all rows == numpy twin, {len(sample)}/"
                 f"{len(sample) // 8} sampled rows == keccak256",
         **rep)


# -- phase: init --------------------------------------------------------------


def _genesis(rng, n_accounts: int, n_contracts: int, n_slots: int,
             funded: list[bytes], chain_id: int) -> dict:
    addrs = rng.integers(0, 256, size=(n_accounts, 20), dtype=np.uint8)
    balances = rng.integers(1, 1 << 62, size=n_accounts)
    alloc = {}
    for i in range(n_accounts):
        entry = {"balance": hex(int(balances[i]))}
        if i < n_contracts:
            # a few contracts carry multi-block code, all carry storage
            entry["code"] = "0x" + (STORE_CODE + rng.bytes(
                int(rng.integers(0, 600)))).hex()
            vals = rng.integers(1, 1 << 62, size=n_slots)
            entry["storage"] = {hex(s): hex(int(vals[s]))
                                for s in range(n_slots)}
        alloc["0x" + addrs[i].tobytes().hex()] = entry
    for a in funded:
        alloc["0x" + a.hex()] = {"balance": hex(10**24)}
    return {"config": {"chainId": chain_id}, "gasLimit": hex(30_000_000),
            "baseFeePerGas": hex(10**9), "alloc": alloc}


def _genesis_hash(out: str) -> str:
    return out.split("hash=0x", 1)[1].split()[0]


def phase_init(sz: dict, rng, tmp: Path) -> None:
    t0 = time.time()
    spec = _genesis(rng, sz["init_accounts"], sz["init_contracts"],
                    sz["init_slots"], [], chain_id=1337)
    gpath = tmp / "init-genesis.json"
    gpath.write_text(json.dumps(spec))
    with on_device("init", ("keccak_calls",)) as rep:
        t_dev = time.time()
        dev = _genesis_hash(cli(["init", "--datadir", str(tmp / "init-dev"),
                                 "--genesis", str(gpath),
                                 "--hasher", "device"]))
        dev_wall = time.time() - t_dev
    t_cpu = time.time()
    cpu = _genesis_hash(cli(["init", "--datadir", str(tmp / "init-cpu"),
                             "--genesis", str(gpath), "--hasher", "cpu"]))
    cpu_wall = time.time() - t_cpu
    assert dev == cpu, f"init: device genesis {dev} != cpu genesis {cpu}"
    emit("init", time.time() - t0, accounts=len(spec["alloc"]),
         contracts=sz["init_contracts"], slots_each=sz["init_slots"],
         device_wall_s=round(dev_wall, 3), cpu_wall_s=round(cpu_wall, 3),
         genesis_hash="0x" + dev,
         checked="genesis hash of init --hasher device == init --hasher cpu",
         **rep)


# -- phase: import ------------------------------------------------------------


def phase_import(sz: dict, rng, tmp: Path) -> None:
    from reth_tpu.primitives import Account
    from reth_tpu.primitives.keccak import keccak256_batch_np
    from reth_tpu.testing import ChainBuilder, Wallet
    from reth_tpu.trie import TrieCommitter

    t0 = time.time()
    chain_id = 1337
    wallets = [Wallet(int(rng.integers(1 << 20, 1 << 62))) for _ in range(4)]
    spec = _genesis(rng, sz["import_accounts"], 0, 0,
                    [w.address for w in wallets], chain_id)
    alloc = {bytes.fromhex(a[2:]): Account(balance=int(e["balance"], 16))
             for a, e in spec["alloc"].items()}
    # the reference chain is sealed on the numpy twin, independent of the
    # device: every header carries the state root the import must reproduce
    builder = ChainBuilder(alloc, chain_id=chain_id,
                           committer=TrieCommitter(hasher=keccak256_batch_np))
    builder.build_block([wallets[0].deploy(STORE_INITCODE, chain_id=chain_id)])
    contract = next(a for a, acc in builder.accounts.items()
                    if a not in alloc and acc.code_hash != Account().code_hash)
    for b in range(sz["import_blocks"] - 1):
        txs = []
        for t in range(sz["import_txs"]):
            w = wallets[(b + t) % len(wallets)]
            if t % 2:
                word = lambda v: int(v).to_bytes(32, "big")  # noqa: E731
                txs.append(w.call(contract, word(b * 64 + t) + word(
                    rng.integers(1, 1 << 62)), chain_id=chain_id))
            else:
                txs.append(w.transfer(rng.bytes(20), int(rng.integers(
                    1, 10**15)), chain_id=chain_id))
        builder.build_block(txs)
    gpath, cpath = tmp / "import-genesis.json", tmp / "chain.rlp"
    gpath.write_text(json.dumps(spec))
    cpath.write_bytes(builder.export_rlp())
    build_wall = time.time() - t0
    datadir = tmp / "import-dev"
    with on_device("import", ("keccak_calls", "turbo_nodes")) as rep:
        t_dev = time.time()
        out = cli(["import", "--datadir", str(datadir), "--genesis",
                   str(gpath), "--hasher", "device", str(cpath)])
        tip = len(builder.blocks) - 1
        assert f"pipeline synced to {tip}" in out, out
        import_wall = time.time() - t_dev
        out = cli(["db", "verify-trie", "--datadir", str(datadir),
                   "--hasher", "device"])
        want = f"trie OK at block {tip}: 0x{builder.tip.state_root.hex()}"
        assert want in out, f"import: {out!r} lacks {want!r}"
    emit("import", time.time() - t0, blocks=tip,
         txs=sum(len(b.transactions) for b in builder.blocks),
         genesis_accounts=len(alloc), chain_build_wall_s=round(build_wall, 3),
         import_wall_s=round(import_wall, 3),
         state_root="0x" + builder.tip.state_root.hex(),
         checked="pipeline verified the tip header's state root (sealed on "
                 "the numpy twin); db verify-trie recomputed it and "
                 "returned 0",
         **rep)


# -- phase: node --------------------------------------------------------------


def _rpc(port: int, method: str, *params):
    req = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                      "params": list(params)})
    resp = urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/", req.encode(),
        {"Content-Type": "application/json"}), timeout=120)
    out = json.loads(resp.read())
    if "error" in out:
        raise RuntimeError(f"{method}: {out['error']}")
    return out["result"]


def _wait(what: str, fn, timeout: float = 600.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        got = fn()
        if got:
            return got
        time.sleep(0.05)
    raise TimeoutError(f"node: timed out waiting for {what}")


def phase_node(sz: dict, rng) -> None:
    from reth_tpu.cli import DEV_PRIVATE_KEY, main
    from reth_tpu.node import Node
    from reth_tpu.primitives import Account
    from reth_tpu.rpc.convert import data, parse_data, parse_qty
    from reth_tpu.testing import Wallet
    from reth_tpu.trie.proof import (AccountProof, StorageProof,
                                     verify_account_proof,
                                     verify_storage_proof)

    t0 = time.time()
    before = {id(o) for o in gc.get_objects() if isinstance(o, Node)}
    box: dict = {}

    def run():
        try:
            box["rc"] = main(["node", "--dev", "--block-time", "0",
                              "--http-port", "0", "--authrpc-port", "0",
                              "--disable-p2p", "--hasher", "device"])
        except BaseException as e:  # noqa: BLE001 — re-raised by the phase
            box["error"] = e

    buf = io.StringIO()
    with on_device("node", ("keccak_calls",)) as rep, \
            contextlib.redirect_stdout(buf):
        th = threading.Thread(target=run, name="smoke-node", daemon=True)
        th.start()

        def listening():
            if "error" in box:
                raise box["error"]
            if "rc" in box:
                raise RuntimeError(f"node exited {box['rc']} at start-up")
            # the port the CLI itself announces, as a user would read it
            tail = buf.getvalue().partition("RPC listening on 127.0.0.1:")[2]
            return int(tail.split(",")[0]) if "," in tail else None

        port = _wait("the dev node's RPC server", listening)
        node = next(o for o in gc.get_objects()
                    if isinstance(o, Node) and id(o) not in before)
        try:
            dev = Wallet(DEV_PRIVATE_KEY)
            chain_id = parse_qty(_rpc(port, "eth_chainId"))
            sent = []

            def send(tx):
                h = _rpc(port, "eth_sendRawTransaction", data(tx.encode()))
                rec = _wait(f"receipt of tx {len(sent)}", lambda: _rpc(
                    port, "eth_getTransactionReceipt", h))
                assert rec["status"] == "0x1", rec
                sent.append(rec)
                return rec

            rec = send(dev.deploy(STORE_INITCODE, chain_id=chain_id))
            contract = parse_data(rec["contractAddress"])
            slot, value = 7, int(rng.integers(1, 1 << 62))
            word = lambda v: int(v).to_bytes(32, "big")  # noqa: E731
            send(dev.call(contract, word(slot) + word(value),
                          chain_id=chain_id))
            payee, paid = rng.bytes(20), 0
            for _ in range(max(0, sz["node_txs"] - 2)):
                amount = int(rng.integers(1, 10**15))
                send(dev.transfer(payee, amount, chain_id=chain_id))
                paid += amount
            blk = _rpc(port, "eth_getBlockByNumber", "latest", False)
            assert parse_qty(blk["number"]) >= 1
            assert parse_qty(_rpc(port, "eth_getBalance", data(payee),
                                  "latest")) == paid
            root = parse_data(blk["stateRoot"])
            proof = _rpc(port, "eth_getProof", data(contract), [hex(slot)],
                         "latest")
            acct = Account(nonce=parse_qty(proof["nonce"]),
                           balance=parse_qty(proof["balance"]),
                           storage_root=parse_data(proof["storageHash"]),
                           code_hash=parse_data(proof["codeHash"]))
            assert verify_account_proof(root, contract, AccountProof(
                address=contract, account=acct,
                proof=[parse_data(x) for x in proof["accountProof"]]))
            sp = proof["storageProof"][0]
            assert parse_qty(sp["value"]) == value
            assert verify_storage_proof(acct.storage_root, StorageProof(
                key=word(slot), value=value,
                proof=[parse_data(x) for x in sp["proof"]]))
        finally:
            node.tasks.shutdown.signal()
            th.join(120)
        assert not th.is_alive(), "node: did not stop"
        if "error" in box:
            raise box["error"]
        assert box.get("rc") == 0, f"node: exited {box.get('rc')}"
    sys.stderr.write(buf.getvalue())
    emit("node", time.time() - t0, txs=len(sent),
         blocks=parse_qty(blk["number"]), state_root=blk["stateRoot"],
         checked="every tx mined with status 1 over HTTP; "
                 "eth_getBlockByNumber / eth_getBalance answered; the "
                 "eth_getProof account + storage proofs verify against the "
                 "block's state root (pure-Python keccak); node exited 0",
         **rep)


# -- phase: rebuild -----------------------------------------------------------


class RebuildState:
    """MerkleStage-chunk-shaped jobs (the shape of bench.py's build_state):
    per-account storage tries (committed at depth 0), then the account
    trie as 256 two-nibble-prefix subtries (``start_depth=2``) whose
    owners' leaves carry the storage roots just computed. RLP is laid out
    in bulk with numpy and spot-checked against the repo's own encoders."""

    def __init__(self, rng, n_accounts: int, n_slots: int):
        from reth_tpu.primitives import Account
        from reth_tpu.primitives.rlp import encode_int, rlp_encode
        from reth_tpu.primitives.types import EMPTY_ROOT_HASH

        keys = rng.integers(0, 256, size=(n_accounts, 32), dtype=np.uint8)
        self.akeys = np.unique(keys.view("S32").ravel()).view(
            np.uint8).reshape(-1, 32)  # sorted, so prefixes are contiguous
        n = len(self.akeys)
        self.nonces = rng.integers(1, 0x80, size=n).astype(np.uint8)
        self.balances = rng.integers(1 << 56, 1 << 63, size=n).astype(">u8")
        self.code_hash = np.frombuffer(Account().code_hash, dtype=np.uint8)
        self.empty_root = np.frombuffer(EMPTY_ROOT_HASH, dtype=np.uint8)
        # storage: n_slots spread over n // 10 tries, owned by every 10th
        # account
        self.n_tries = max(1, n // 10)
        self.owners = np.arange(self.n_tries) * (n // self.n_tries)
        skeys = rng.integers(0, 256, size=(n_slots, 32), dtype=np.uint8)
        svals = np.empty((n_slots, 9), dtype=np.uint8)
        svals[:, 0] = 0x88
        raw = rng.integers(1 << 56, 1 << 63, size=n_slots).astype(">u8")
        svals[:, 1:] = raw.view(np.uint8).reshape(-1, 8)
        assert bytes(svals[0]) == rlp_encode(encode_int(int(raw[0])))
        blob = svals.tobytes()
        self.storage_jobs = []
        for o in range(self.n_tries):
            sel = np.arange(o, n_slots, self.n_tries)
            if len(sel):
                self.storage_jobs.append(
                    (skeys[sel], [blob[i * 9:i * 9 + 9] for i in sel]))
        self.n_accounts, self.n_slots = n, n_slots

    def account_jobs(self, storage_roots: list[bytes]):
        from reth_tpu.primitives import Account

        n = self.n_accounts
        # rlp([nonce, balance, storage_root, code_hash]), fixed layout:
        # f8 4c | nonce | 88 balance(8) | a0 root(32) | a0 code_hash(32)
        vals = np.empty((n, 78), dtype=np.uint8)
        vals[:, 0], vals[:, 1] = 0xF8, 0x4C
        vals[:, 2] = self.nonces
        vals[:, 3] = 0x88
        vals[:, 4:12] = self.balances.view(np.uint8).reshape(-1, 8)
        vals[:, 12], vals[:, 45] = 0xA0, 0xA0
        vals[:, 13:45] = self.empty_root
        vals[:, 46:78] = self.code_hash
        roots = np.frombuffer(b"".join(storage_roots), dtype=np.uint8)
        vals[self.owners[: len(storage_roots)], 13:45] = roots.reshape(-1, 32)
        for i in (0, int(self.owners[-1]), n - 1):
            assert bytes(vals[i]) == Account(
                nonce=int(self.nonces[i]), balance=int(self.balances[i]),
                storage_root=bytes(vals[i, 13:45])).trie_encode()
        blob = vals.tobytes()
        bounds = np.searchsorted(self.akeys[:, 0], np.arange(257))
        return [(self.akeys[lo:hi],
                 [blob[i * 78:i * 78 + 78] for i in range(lo, hi)])
                for lo, hi in zip(bounds[:-1], bounds[1:]) if hi > lo]

    def commit(self, committer) -> dict:
        """One full rebuild the way MerkleStage's chunks drive it."""
        t0 = time.time()
        sres = committer.commit_hashed_pipelined(
            self.storage_jobs, collect_branches=True)
        sroots = [r.root for r in sres]
        ares = committer.commit_hashed_pipelined(
            self.account_jobs(sroots), collect_branches=True, start_depth=2)
        return {"wall_s": time.time() - t0,
                "storage_roots": sroots,
                "account_roots": [r.root for r in ares],
                "branch_nodes": [r.branch_nodes for r in ares],
                "hashed": sres[-1].hashed_nodes + ares[-1].hashed_nodes}


def _same_roots(what: str, a: dict, b: dict) -> None:
    for k in ("storage_roots", "account_roots", "branch_nodes", "hashed"):
        assert a[k] == b[k], f"{what}: {k} differ"


def _peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_rebuild(sz: dict, rng, size_name: str) -> None:
    from reth_tpu.trie.turbo import TurboCommitter

    t0 = time.time()
    state = RebuildState(rng, sz["rebuild_accounts"], sz["rebuild_slots"])
    build_wall = time.time() - t0
    dev = TurboCommitter(backend="device")
    with on_device("rebuild", ("turbo_nodes", "fused_dispatches")) as rep:
        cold = state.commit(dev)
        warm = state.commit(dev)
    _same_roots("rebuild cold/warm", cold, warm)
    twin = state.commit(TurboCommitter(backend="numpy"))
    _same_roots("rebuild device/numpy", warm, twin)
    full = SIZES["full"]
    cut = ("none" if size_name == "full" else
           f"--tiny rehearsal size (full: {full['rebuild_accounts']} "
           f"accounts + {full['rebuild_slots']} slots)")
    emit("rebuild", time.time() - t0, accounts=state.n_accounts,
         slots=state.n_slots, storage_tries=state.n_tries,
         account_subtries=len(cold["account_roots"]),
         node_hashes=cold["hashed"], state_build_wall_s=round(build_wall, 3),
         device_cold_wall_s=round(cold["wall_s"], 3),
         device_second_wall_s=round(warm["wall_s"], 3),
         numpy_twin_wall_s=round(twin["wall_s"], 3),
         peak_device_bytes=_peak_bytes(),
         cut=cut, cut_vs_mainnet="mainnet holds ~300 M accounts / ~1.4 B "
         "slots; 1 M + 1 M is what the Python state builder and the 1200 s "
         "limit allow",
         checked="100% of storage roots, account subtrie roots and account "
                 "branch nodes equal the numpy twin's, cold and second run",
         **rep)


# -- --chips 4: the mesh path and what it is compared with --------------------


def phase_mesh(sz: dict, rng, chips: int) -> None:
    import jax

    from reth_tpu.ops.fused_commit import FusedMeshEngine
    from reth_tpu.parallel.mesh import HashMesh
    from reth_tpu.trie.turbo import TurboCommitter

    t0 = time.time()
    assert DEVICE["count"] == chips, (
        f"--chips {chips} needs {chips} devices, JAX reports {DEVICE['count']}")
    state = RebuildState(rng, sz["rebuild_accounts"], sz["rebuild_slots"])
    placed = {"batch": [], "arena": []}
    put_batch, put_arena = FusedMeshEngine._put_batch, FusedMeshEngine._device_put

    def spy(kind, orig):
        def wrapped(self, arr):
            out = orig(self, arr)
            placed[kind].append(
                (frozenset(d.id for d in out.sharding.device_set),
                 len({s.device.id for s in out.addressable_shards
                      if s.data.size}),
                 out.sharding.is_fully_replicated))
            return out
        return wrapped

    FusedMeshEngine._put_batch = spy("batch", put_batch)
    FusedMeshEngine._device_put = spy("arena", put_arena)
    try:
        with on_device("mesh", ("turbo_nodes", "fused_dispatches")) as rep:
            single = state.commit(TurboCommitter(backend="device"))
            mesh = HashMesh.build(chips)
            sharded = state.commit(TurboCommitter(backend="device", mesh=mesh))
    finally:
        FusedMeshEngine._put_batch = put_batch
        FusedMeshEngine._device_put = put_arena
    _same_roots("mesh/single-device", sharded, single)
    all_ids = frozenset(d.id for d in jax.devices()[:chips])
    assert placed["batch"] and placed["arena"], "mesh: nothing was placed"
    for kind, want_replicated in (("batch", False), ("arena", True)):
        for ids, shards_on, replicated in placed[kind]:
            assert ids == all_ids and shards_on == chips, (
                f"mesh: a {kind} array sits on devices {sorted(ids)} "
                f"({shards_on} with data), not on all of {sorted(all_ids)}")
            assert replicated == want_replicated, (
                f"mesh: a {kind} array has replicated={replicated}")
    snap = mesh.snapshot()
    assert snap["unhealthy"] == 0, snap
    emit("mesh", time.time() - t0, chips=chips, accounts=state.n_accounts,
         slots=state.n_slots, node_hashes=single["hashed"],
         single_device_wall_s=round(single["wall_s"], 3),
         mesh_wall_s=round(sharded["wall_s"], 3),
         sharded_level_inputs=len(placed["batch"]),
         replicated_arena_arrays=len(placed["arena"]),
         peak_device_bytes=_peak_bytes(),
         checked=f"mesh roots == single-device roots; every level input "
                 f"batch-sharded with a non-empty shard on each of "
                 f"{chips} distinct devices; the digest arena replicated "
                 f"on all {chips}",
         **rep)


# -- main ---------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the four-chip mesh path and the "
                         "single-device commit it is compared with")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal/test sizes (the CPU rehearsal of the "
                         "on-chip-measurement guide); the result line is "
                         "still printed only on a TPU")
    args = ap.parse_args(argv)
    t0 = time.time()

    from reth_tpu.ops.device import (configure_compile_cache,
                                     require_device)
    from reth_tpu.ops.warmup import CompileCache

    # the platform check comes first: no TPU (and no explicit
    # JAX_PLATFORMS=cpu) -> DeviceUnavailable, non-zero exit, no phase runs
    platform, kind, count = require_device()
    DEVICE.update(platform=platform, kind=kind, count=count)
    cache_dir = configure_compile_cache()
    import jax

    def on_event(name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            CACHE_EVENTS["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            CACHE_EVENTS["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    cache = CompileCache()
    size_name = "tiny" if args.tiny else "full"
    sz = SIZES[size_name]
    rng = np.random.default_rng(args.seed)
    emit("start", 0.0, seed=args.seed, size=size_name, chips=args.chips,
         compile_cache={"dir": str(cache_dir),
                        "entries_at_start": cache.entries_at_start})
    if args.chips == 4:
        phase_mesh(sz, rng, 4)
    else:
        with tempfile.TemporaryDirectory(prefix="reth-tpu-smoke-") as tmp:
            phase_kernels(sz, rng)
            phase_init(sz, rng, Path(tmp))
            phase_import(sz, rng, Path(tmp))
            phase_node(sz, rng)
            phase_rebuild(sz, rng, size_name)
    from reth_tpu.metrics import compile_tracker

    emit("done", time.time() - t0,
         compile_cache={"dir": str(cache_dir),
                        "entries_at_start": cache.entries_at_start,
                        "entries_at_end": cache.entry_count(),
                        "hits": CACHE_EVENTS["hits"],
                        "misses": CACHE_EVENTS["misses"]},
         compiled=compile_tracker.totals())
    if platform != "tpu":
        print(f"chip_smoke: every phase passed on platform {platform!r}, "
              f"which is a rehearsal, not a chip run: no result line",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": DEVICE}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
