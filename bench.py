"""Benchmark suite. DEFAULT mode (``RETH_TPU_BENCH_MODE`` unset or
``exec``): optimistic parallel EVM execution vs the serial interpreter —
a CPU-measurable number (engine/optimistic.py + native/evmexec.cpp), so
the perf trajectory records a real measurement with or without a device.
``RETH_TPU_BENCH_MODE=rebuild`` selects the device state-root rebuild
benchmark described below; ``service``/``sparse``/``gateway``
select the other subsystem benches; ``mesh`` shards the production
turbo/fused rebuild loop over 1/2/4/8 simulated host devices (one
subprocess per mesh size, roots verified bit-identical vs the
single-device committer before any number prints, per-mesh-size
throughput + compile wall in ``per_mesh``); ``subtrie`` compares the
whole-subtrie k-level fused committer (one dispatch per k levels) to
the per-level committer at k ∈ {1,2,4,8} across 1/2/4/8 simulated
devices — dispatches/block + wall per k, roots verified bit-identical
before any number prints, and every mode's JSON line now carries
``dispatches_per_block``; ``fleet`` measures
sustained RPC throughput + p99 through the fleet gateway at 1/2/4/8
witness-fed replica subprocesses vs the single-node gateway
(duplicate-heavy + long-tail mixes, responses verified bit-identical
to an ungated dispatch before any number prints, per-size results in
``per_fleet``); ``txflow`` floods the insertion batcher with adversarial
submission mixes at 1k-50k offered tx/s and measures tx->inclusion p99 +
txs/block through the continuous block producer vs the serial
build-on-demand miner, with the hot candidate's inclusion set verified
bit-identical against a serial greedy build over a cloned pool at every
load point before any number prints (per-rate results in ``per_rate``);
``hotstate`` imports an interleaved sibling-fork stream with the
hot-state plane (cross-block trie-node cache + device digest arena) on
vs off — proof-target reduction factor as the headline, proof walls,
hit rate, H2D bytes/block and the delta-upload fraction as extras,
every payload VALID (root-checked) in both runs before any number
prints.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"backend", "vs_prev", "regression"}. ``backend`` records which plane
actually produced the number; ``vs_prev`` compares against the trailing
last-N-good-runs baseline for the same metric+mode+backend+warmup key
(health.BenchBaselineStore, persisted at RETH_TPU_BENCH_BASELINE_STORE
or <repo>/.bench_baselines.json) and ``regression`` flips true when the
run drops under RETH_TPU_BENCH_REGRESSION_THRESHOLD (default 0.8x) of
it — RETH_TPU_BENCH_STRICT=1 turns that into rc=3.

The ``rebuild`` mode is a DEVICE measurement and tells the truth about
it: it calls ``ops/device.require_device()`` first and prints platform,
device kind and count in its JSON line; a missing chip, a device/numpy
root mismatch, any moved CPU-route counter
(``ops/device.CPU_ROUTE_COUNTERS``), a cache or warm-up error, or the
watchdog is a NON-ZERO exit. A number from numpy is never printed under
the device metric's name.

Workload = benchmark config #2/#3 in miniature (BASELINE.md): a synthetic
hashed state (accounts + storage slots) is committed bottom-up with the
TURBO committer — C++ structure sweep (native/triebuild.cpp), packed/bitmap
level arrays, device-resident digest buffer, zero mid-commit D2H
(reth_tpu/trie/turbo.py + reth_tpu/ops/fused_commit.py). ``vs_baseline``
is the wall-clock speedup over the SAME turbo pipeline with the numpy CPU
hashing backend — an honest strong baseline standing in for the
reference's rayon keccak path (reference
crates/stages/stages/src/stages/hashing_account.rs:29-32).

Hardening:
- The fused committer at a forced single batch tier keeps the XLA program
  count <= ~4.
- The phase-aware watchdog guarantees one JSON line no matter what, and a
  non-zero exit when it fires.

Env knobs: RETH_TPU_BENCH_ACCOUNTS (default 150000), RETH_TPU_BENCH_SLOTS
(default 60000), RETH_TPU_BENCH_TIER (fused batch tier, default 16384),
RETH_TPU_BENCH_TIMEOUT (watchdog, default 1200), RETH_TPU_PROBE_TIMEOUT
(health-probe budget of the service/sparse modes, default 120).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

_DEADLINE = int(os.environ.get("RETH_TPU_BENCH_TIMEOUT", "1200"))
_STATE: dict = {"phase": "startup", "device_result": None}


def _flight_excerpt(n: int = 24) -> list:
    """Tail of the flight recorder (probe outcomes, fault events, recent
    spans) — the trail an error line carries with it."""
    try:
        from reth_tpu import tracing

        return [{k: rec.get(k) for k in
                 ("kind", "target", "name", "ts", "dur_ms", "fields",
                  "error")}
                for rec in tracing.flight_snapshot(n)]
    except Exception:  # noqa: BLE001 — diagnostics only
        return []


def _compile_split() -> dict:
    """compile_wall_s vs steady-state: the per-shape first-call walls the
    compile tracker collected (metrics.DeviceCompileTracker) — every mode
    reports the split so a compile storm can't masquerade as slow
    hashing."""
    try:
        from reth_tpu.metrics import compile_tracker

        t = compile_tracker.totals()
        return {"compile_wall_s": t["compile_wall_s"],
                "compiled_shapes": t["shapes"]}
    except Exception:  # noqa: BLE001 — diagnostics only
        return {"compile_wall_s": 0.0, "compiled_shapes": 0}


def _assess_vs_prev(line, error) -> None:
    """Perf-regression sentinel (health.BenchBaselineStore): every line
    gains ``vs_prev`` (value / median of the trailing last-N GOOD runs
    for the same metric+mode+backend+warmup-state key) and a loud
    ``regression`` flag. Good runs append to the store;
    error/zero lines only read it. Never fatal to the bench."""
    try:
        from reth_tpu.health import BenchBaselineStore

        mode = os.environ.get("RETH_TPU_BENCH_MODE", "exec")
        threshold = float(
            os.environ.get("RETH_TPU_BENCH_REGRESSION_THRESHOLD", "0.8"))
        store = BenchBaselineStore()
        value = line["value"]
        good = not error and isinstance(value, (int, float)) and value > 0
        if good:
            verdict = store.assess(line["metric"], mode, line["backend"],
                                   line["warmup_state"], float(value),
                                   threshold=threshold)
            store.record(line["metric"], mode, line["backend"],
                         line["warmup_state"], float(value),
                         vs_baseline=line.get("vs_baseline"))
        else:
            verdict = {"vs_prev": None, "regression": False,
                       "baseline_n": 0, "baseline": None}
        line["vs_prev"] = verdict["vs_prev"]
        line["regression"] = verdict["regression"]
        line["baseline_n"] = verdict["baseline_n"]
        if verdict["baseline"] is not None:
            line["baseline_prev"] = verdict["baseline"]
        if verdict["regression"]:
            print(f"PERF REGRESSION: {line['metric']} = {value} "
                  f"{line['unit']} is {verdict['vs_prev']}x the trailing "
                  f"baseline ({verdict['baseline']} over "
                  f"{verdict['baseline_n']} runs)", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — the sentinel never fails a bench
        line.setdefault("vs_prev", None)
        line.setdefault("regression", False)
        line["baseline_error"] = f"{type(e).__name__}: {e}"


def _emit(value, vs_baseline, error=None, exit_code=None, **extra):
    line = {
        "metric": _STATE.get("metric", "merkle_rebuild_keccak_per_sec"),
        "value": value,
        "unit": _STATE.get("unit", "hashes/s"),
        "vs_baseline": vs_baseline,
        "backend": _STATE.get("backend", "unknown"),
        # warm-up attribution rides on EVERY line (incl. watchdog/error
        # lines). Resolved LIVE from the manager so even a line emitted
        # mid-warm-up (watchdog fired while a compile wedged) records
        # which shape it died on.
        "warmup_state": (_STATE["warmup_mgr"].snapshot()
                         if _STATE.get("warmup_mgr") is not None
                         else _STATE.get("warmup_state", "off")),
        "compile_cache": _STATE.get("compile_cache", "off"),
    }
    line.update(_compile_split())
    # dispatch accounting rides on EVERY line (the BENCH trajectory was
    # empty on this axis): the last fused commit's device-dispatch count,
    # 0 when no fused commit ran this process
    try:
        from reth_tpu.metrics import fused_metrics

        line.setdefault("dispatches_per_block",
                        (fused_metrics.last or {}).get("dispatches", 0))
    except Exception:  # noqa: BLE001 — diagnostics only
        line.setdefault("dispatches_per_block", 0)
    # cross-block pipeline attribution rides on EVERY line: depth 1 and
    # overlap 0 when no pipelined import ran this process
    try:
        from reth_tpu.metrics import block_pipeline_metrics

        bp = block_pipeline_metrics.last or {}
        line.setdefault("pipeline_depth", bp.get("depth") or 1)
        line.setdefault("overlap_fraction", round(bp.get("overlap") or 0.0, 4))
    except Exception:  # noqa: BLE001 — diagnostics only
        line.setdefault("pipeline_depth", 1)
        line.setdefault("overlap_fraction", 0.0)
    if error:
        line["error"] = error
        line["flight_recorder"] = _flight_excerpt()
    elif extra.get("device_unavailable"):
        line["flight_recorder"] = _flight_excerpt()
    line.update(extra)
    _assess_vs_prev(line, error)
    print(json.dumps(line), flush=True)
    if exit_code is not None:
        if (line.get("regression")
                and os.environ.get("RETH_TPU_BENCH_STRICT")
                and exit_code == 0):
            # strict mode: a regression vs the trailing baseline is a
            # FAILURE, not a footnote (opt-in: the driver's rc contract
            # treats nonzero as harness breakage, so default stays 0)
            os._exit(3)
        os._exit(exit_code)


def _watchdog():
    time.sleep(_DEADLINE)
    dev = _STATE["device_result"]
    # a run the watchdog had to cut is a FAILED run: the error field and
    # the flight-recorder excerpt carry the postmortem, the exit code says
    # that no number was measured to the end
    if dev is not None:
        _emit(dev, 0, error=f"timed out during {_STATE['phase']} after the device run "
                            f"completed (baseline unmeasured)", exit_code=1)
    _emit(0, 0, error=f"timed out during {_STATE['phase']} after {_DEADLINE}s",
          exit_code=1)


threading.Thread(target=_watchdog, daemon=True).start()


def probe_device_diag() -> str | None:
    """One in-process health probe (reth_tpu/ops/supervisor.probe_device —
    the SAME implementation the node's ``--hasher auto`` supervisor runs):
    None when the device this process is entitled to answers, else the
    diagnostic. The service/sparse modes use it to pick (and LABEL) their
    backend; the rebuild mode does not — it requires the device."""
    from reth_tpu.ops.supervisor import FaultInjector, probe_device

    _STATE["phase"] = "device health probe"
    result = probe_device(injector=FaultInjector.from_env())
    return None if result.ok else result.diag


def build_state(n_accounts: int, n_slots: int):
    """MerkleStage-chunk-shaped jobs: per-account storage tries (committed
    at depth 0) + the account trie as 256 two-nibble-prefix subtries
    (committed at ``start_depth=2``) — exactly what ``_account_chunk``
    feeds the committer. Returns (storage_jobs, account_prefix_jobs)."""
    from reth_tpu.primitives.rlp import encode_int, rlp_encode
    from reth_tpu.primitives.types import Account
    from reth_tpu.storage.tables import encode_account

    rng = np.random.default_rng(42)
    akeys = rng.integers(0, 256, size=(n_accounts, 32), dtype=np.uint8)
    akeys = np.unique(akeys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
    n_accounts = len(akeys)
    balances = rng.integers(1, 1 << 60, size=n_accounts)
    avals = [
        encode_account(Account(nonce=int(i % 300), balance=int(balances[i])))
        for i in range(n_accounts)
    ]
    account_jobs = []
    for pfx in range(256):
        sel = np.nonzero(akeys[:, 0] == pfx)[0]
        if len(sel):
            account_jobs.append((akeys[sel], [avals[i] for i in sel]))
    # storage tries: n_slots spread over n_accounts//10 accounts
    n_storage_accts = max(1, n_accounts // 10)
    skeys = rng.integers(0, 256, size=(n_slots, 32), dtype=np.uint8)
    svals = [rlp_encode(encode_int(int(v))) for v in rng.integers(1, 1 << 60, size=n_slots)]
    storage_jobs = []
    for owner in range(n_storage_accts):
        sel = np.arange(owner, n_slots, n_storage_accts)
        if len(sel):
            storage_jobs.append((skeys[sel], [svals[i] for i in sel]))
    return storage_jobs, account_jobs


def run_rebuild(committer, storage_jobs, account_jobs, pipelined: bool):
    """One full-rebuild pass. ``pipelined=False`` is the seed's SERIAL
    chunked path: storage tries in one batched call, then one commit per
    account prefix subtrie (sweep → hash → fetch with nothing overlapped).
    ``pipelined=True`` routes both phases through the overlapped pipeline
    (pooled sweeps + cross-subtrie level packing + resident arena)."""
    t0 = time.time()
    if pipelined:
        res = committer.commit_hashed_pipelined(storage_jobs)
        res += committer.commit_hashed_pipelined(account_jobs, start_depth=2)
    else:
        res = committer.commit_hashed_many(storage_jobs)
        for job in account_jobs:
            res += committer.commit_hashed_many([job], start_depth=2)
    dt = time.time() - t0
    hashed = sum(r.hashed_nodes for r in res)
    return [r.root for r in res], hashed, dt


def run_service_mode() -> None:
    """RETH_TPU_BENCH_MODE=service: coalesced small-batch throughput vs
    per-call dispatch — the hash-service headline (ops/hash_service.py).

    Workload: T concurrent clients each issuing many SMALL hash requests
    (the SparseRootTask / proof shape the service exists for). Baseline =
    every request dispatched directly on the backend (per-call overhead,
    tiny batches); measured = the same requests through the service's
    coalescing window (continuous batching into full-rate dispatches).
    Runs on the device when the health probe passes, else the numpy
    twin — either way one JSON line with the speedup and the measured
    coalesce factor. Env: RETH_TPU_BENCH_SVC_CLIENTS (default 8),
    RETH_TPU_BENCH_SVC_REQS (requests/client, default 300),
    RETH_TPU_BENCH_SVC_KEYS (keys/request, default 4)."""
    import numpy as _np

    from reth_tpu.metrics import MetricsRegistry
    from reth_tpu.ops.hash_service import HashService
    from reth_tpu.primitives.keccak import keccak256_batch_np

    clients = int(os.environ.get("RETH_TPU_BENCH_SVC_CLIENTS", "8"))
    reqs = int(os.environ.get("RETH_TPU_BENCH_SVC_REQS", "300"))
    keys = int(os.environ.get("RETH_TPU_BENCH_SVC_KEYS", "4"))
    _STATE["metric"] = "hash_service_small_batch_per_sec"
    _STATE["phase"] = "service bench probe"
    diag = probe_device_diag()
    if diag is None:
        from reth_tpu.ops.keccak_jax import KeccakDevice

        _STATE["backend"] = "device"
        backend = KeccakDevice(min_tier=1024, block_tier=4).hash_batch
    else:
        _STATE["backend"] = "numpy"
        backend = keccak256_batch_np
    rng = _np.random.default_rng(7)
    workload = [
        [rng.integers(0, 256, size=64, dtype=_np.uint8).tobytes()
         for _ in range(keys)]
        for _ in range(clients * reqs)
    ]
    lanes = ("live", "payload", "rebuild", "proof")

    def run_clients(dispatch_fn) -> float:
        errs: list = []

        def worker(c):
            try:
                for i in range(reqs):
                    dispatch_fn(lanes[c % 4], workload[c * reqs + i])
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(c,)) for c in range(clients)]
        t0 = time.time()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise errs[0]
        return time.time() - t0

    total = clients * reqs * keys
    _STATE["phase"] = "per-call baseline (direct dispatch)"
    backend(workload[0])  # warm compiles out of the measured window
    dt_direct = run_clients(lambda lane, msgs: backend(msgs))
    _STATE["phase"] = "service run (coalesced)"
    svc = HashService(backend=backend, registry=MetricsRegistry())
    try:
        dt_svc = run_clients(lambda lane, msgs: svc.hash(lane, msgs))
        factor = round(svc.coalesce_factor(), 2)
        dispatches = svc.dispatches
    finally:
        svc.stop()
    _STATE["device_result"] = round(total / dt_svc, 1)
    _emit(round(total / dt_svc, 1), round(dt_direct / dt_svc, 3),
          coalesce_factor=factor, service_dispatches=dispatches,
          requests=clients * reqs, keys_per_request=keys,
          percall_wall_s=round(dt_direct, 3), service_wall_s=round(dt_svc, 3),
          percall_hashes_per_sec=round(total / dt_direct, 1),
          **({"device_unavailable": diag} if diag else {}),
          exit_code=0)


def run_gateway_mode() -> None:
    """RETH_TPU_BENCH_MODE=gateway: coalesced vs naive requests/s under a
    duplicate-heavy read workload — the RPC serving gateway headline
    (rpc/gateway.py).

    Workload: T client threads each issuing many ``eth_call``-shaped
    requests drawn from a SMALL key pool (trackers and wallets hammer the
    same few reads), against a handler doing real CPU work (a batched
    keccak over params-derived messages — the CPU-fallback path, so this
    reports a real number with or without a device). Baseline = the same
    requests through an ungated RpcServer (every duplicate recomputes
    under the coarse handler lock); measured = one gateway coalescing
    in-flight duplicates and serving repeats from the head-scoped
    response cache. Responses are checked bit-identical to the naive
    path before the number is emitted. Env: RETH_TPU_BENCH_GW_CLIENTS
    (default 8), RETH_TPU_BENCH_GW_REQS (requests/client, default 150),
    RETH_TPU_BENCH_GW_KEYS (distinct request keys, default 8),
    RETH_TPU_BENCH_GW_WORK (keccak msgs per handler call, default 600)."""
    from reth_tpu.metrics import MetricsRegistry
    from reth_tpu.primitives.keccak import keccak256_batch_np
    from reth_tpu.rpc.gateway import RpcGateway
    from reth_tpu.rpc.server import RpcServer

    clients = int(os.environ.get("RETH_TPU_BENCH_GW_CLIENTS", "8"))
    reqs = int(os.environ.get("RETH_TPU_BENCH_GW_REQS", "150"))
    n_keys = int(os.environ.get("RETH_TPU_BENCH_GW_KEYS", "8"))
    work = int(os.environ.get("RETH_TPU_BENCH_GW_WORK", "600"))
    _STATE["metric"] = "gateway_requests_per_sec"
    _STATE["unit"] = "requests/s"
    _STATE["backend"] = "cpu"

    def handler(*params):
        seed = json.dumps(params, sort_keys=True).encode()
        msgs = [seed + i.to_bytes(4, "big") for i in range(work)]
        return {"data": "0x" + keccak256_batch_np(msgs)[0].hex()}

    def make_server(gateway):
        srv = RpcServer(gateway=gateway)
        srv.register_method("eth_call", handler)
        return srv

    bodies = [json.dumps({
        "jsonrpc": "2.0", "id": 7, "method": "eth_call",
        "params": [{"to": f"0x{k:040x}", "data": "0xdeadbeef"}, "latest"],
    }).encode() for k in range(n_keys)]

    def run_clients(srv) -> float:
        errs: list = []

        def worker(c):
            try:
                rng = np.random.default_rng(c)
                for i in range(reqs):
                    srv.handle(bodies[int(rng.integers(0, n_keys))])
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(c,))
              for c in range(clients)]
        t0 = time.time()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise errs[0]
        return time.time() - t0

    total = clients * reqs
    _STATE["phase"] = "naive baseline (ungated dispatch)"
    naive = make_server(None)
    naive.handle(bodies[0])  # warm allocations out of the measured window
    dt_naive = run_clients(naive)
    _STATE["phase"] = "gateway run (coalesced + cached)"
    gw = RpcGateway(head_supplier=lambda: b"bench-head",
                    registry=MetricsRegistry())
    gated = make_server(gw)
    dt_gated = run_clients(gated)
    _STATE["phase"] = "response parity check"
    for body in bodies:
        if gated.handle(body) != naive.handle(body):
            _emit(0, 0, error="gated/naive response mismatch", exit_code=1)
    snap = gw.snapshot()
    _STATE["device_result"] = round(total / dt_gated, 1)
    _emit(round(total / dt_gated, 1), round(dt_naive / dt_gated, 3),
          coalesce_factor=snap["coalesce_factor"],
          cache_hit_rate=snap["cache_hit_rate"],
          executions=snap["executions"], requests=total,
          distinct_keys=n_keys, work_msgs_per_call=work,
          naive_wall_s=round(dt_naive, 3), gateway_wall_s=round(dt_gated, 3),
          naive_requests_per_sec=round(total / dt_naive, 1),
          exit_code=0)


def build_sparse_state(n_tries: int, slots: int, dirty: int, seed: int = 3):
    """One storage-heavy live-tip block in miniature: a SparseStateTrie
    with ``n_tries`` fully-revealed storage tries x ``slots`` slots plus
    matching account leaves, committed once (clean refs — the preserved
    cross-block state), then ``dirty`` slot writes + a few deletes/wipes
    per-trie and account churn: exactly the dirty set finish() sees."""
    import numpy as _np

    from reth_tpu.trie.sparse import SparseStateTrie, SparseTrie
    from reth_tpu.primitives.keccak import keccak256_batch_np

    rng = _np.random.default_rng(seed)
    st = SparseStateTrie()
    owners = []
    slot_keys: dict[bytes, list[bytes]] = {}
    for _ in range(n_tries):
        ha = bytes(rng.integers(0, 256, 32, dtype=_np.uint8))
        owners.append(ha)
        t = st.storage_trie(ha)
        keys = [bytes(rng.integers(0, 256, 32, dtype=_np.uint8))
                for _ in range(slots)]
        slot_keys[ha] = keys
        for k in keys:
            t.update(k, bytes(rng.integers(1, 256, 8, dtype=_np.uint8)))
        st.update_account(ha, b"account-leaf-" + ha)
    st.root(keccak256_batch_np)  # clean baseline (serial; untimed)
    # the block's dirty set
    for i, ha in enumerate(owners):
        t = st.storage_trie(ha)
        keys = slot_keys[ha]
        for j in range(dirty):
            t.update(keys[j % len(keys)],
                     bytes(rng.integers(1, 256, 8, dtype=_np.uint8)))
        t.delete(keys[-1])
        if i % 16 == 15:  # a few SELFDESTRUCT wipes
            st.storage_tries[ha] = SparseTrie()
        st.update_account(ha, b"post-leaf-" + ha)
    return st


def run_sparse_mode() -> None:
    """RETH_TPU_BENCH_MODE=sparse: storage-heavy live-tip ``finish()``
    commit latency — the PARALLEL packed path (cross-trie per-depth
    dispatch fusion + lower-subtrie encode pool,
    trie/sparse.py ParallelSparseCommitter) vs the serial per-trie
    ``root_hash_compute`` loop the seed ran. Roots must be bit-identical;
    ``vs_baseline`` = serial wall / parallel wall. Runs on the device
    when the health probe passes, else the numpy twin (labelled by the
    line's "backend" field). Env: RETH_TPU_BENCH_SPARSE_TRIES
    (default 192), RETH_TPU_BENCH_SPARSE_SLOTS (slots/trie, default 64),
    RETH_TPU_BENCH_SPARSE_DIRTY (dirty writes/trie, default 16),
    RETH_TPU_SPARSE_WORKERS (encode-pool width, default auto)."""
    from reth_tpu.primitives.keccak import keccak256_batch_np
    from reth_tpu.trie.sparse import ParallelSparseCommitter

    n_tries = int(os.environ.get("RETH_TPU_BENCH_SPARSE_TRIES", "192"))
    slots = int(os.environ.get("RETH_TPU_BENCH_SPARSE_SLOTS", "64"))
    dirty = int(os.environ.get("RETH_TPU_BENCH_SPARSE_DIRTY", "16"))
    _STATE["metric"] = "sparse_commit_hashes_per_sec"
    _STATE["phase"] = "sparse bench probe"
    diag = probe_device_diag()
    if diag is None:
        from reth_tpu.ops.keccak_jax import KeccakDevice

        _STATE["backend"] = "device"
        hasher = KeccakDevice(min_tier=1024, block_tier=4).hash_batch
    else:
        _STATE["backend"] = "numpy"
        hasher = keccak256_batch_np

    _STATE["phase"] = "sparse state build (serial pass)"
    st_serial = build_sparse_state(n_tries, slots, dirty)
    t0 = time.time()
    root_serial = st_serial.root(hasher)
    dt_serial = time.time() - t0

    _STATE["phase"] = "sparse state build (parallel pass)"
    st_par = build_sparse_state(n_tries, slots, dirty)
    committer = ParallelSparseCommitter()
    t0 = time.time()
    root_par = st_par.root(hasher, committer=committer)
    dt_par = time.time() - t0
    if root_serial != root_par:
        _emit(0, 0, error="parallel/serial sparse root mismatch", exit_code=1)
    stats = committer.last or {}
    hashed = stats.get("hashed", 0)
    _STATE["device_result"] = round(hashed / dt_par, 1)
    _emit(round(hashed / dt_par, 1), round(dt_serial / dt_par, 3),
          serial_wall_s=round(dt_serial, 4),
          parallel_wall_s=round(dt_par, 4),
          tries=stats.get("tries"), levels_packed=stats.get("levels"),
          dispatches=stats.get("dispatches"),
          encode_chunks=stats.get("encode_chunks"),
          sparse_workers=committer.workers,
          **({"device_unavailable": diag} if diag else {}),
          exit_code=0)


def _exec_bench_block(n_txs: int, conflict_rate: float, reps: int):
    """One synthetic block: every tx calls a compute-heavy store contract
    (``reps`` unrolled MUL/ADD units then SSTORE slot0 — natively
    executable, interpreter-expensive). A ``conflict_rate`` fraction of
    ranks call ONE shared contract (write-after-write on the same slot —
    those ranks invalidate and re-run serially); the rest each own a
    private contract, so their writes are fully disjoint. Senders are
    synthetic (the executor trusts the provided sender list), so the
    workload needs no signing."""
    from reth_tpu.evm.executor import InMemoryStateSource
    from reth_tpu.primitives import Account
    from reth_tpu.primitives.keccak import keccak256
    from reth_tpu.primitives.types import Block, Header, Transaction

    # PUSH0 CALLDATALOAD; reps x (PUSH1 31 MUL PUSH1 7 ADD); DUP1 PUSH0
    # SSTORE; STOP — seed-dependent compute chain ending in one store
    code = (b"\x5f\x35" + bytes.fromhex("601f02600701") * reps
            + bytes.fromhex("805f5500"))
    ch = keccak256(code)
    senders = [bytes([0xA0]) + i.to_bytes(19, "big") for i in range(n_txs)]
    accounts = {s: Account(balance=10**20) for s in senders}
    shared = b"\x5e" * 20
    accounts[shared] = Account(code_hash=ch)
    txs = []
    stride = int(1 / conflict_rate) if conflict_rate else 0
    for i in range(n_txs):
        if stride and i % stride == 0:
            to = shared  # conflicting rank: same contract, same slot
        else:
            to = bytes([0x5C]) + i.to_bytes(19, "big")
            accounts[to] = Account(code_hash=ch)
        txs.append(Transaction(
            tx_type=2, chain_id=1, nonce=0, max_fee_per_gas=100 * 10**9,
            max_priority_fee_per_gas=10**9, gas_limit=500_000, to=to,
            value=0, data=(0xBEEF00 + i).to_bytes(32, "big")))
    header = Header(number=1, gas_limit=10**9, base_fee_per_gas=7,
                    beneficiary=b"\xc0" * 20)
    block = Block(header, tuple(txs), (), ())

    def mk_source():
        return InMemoryStateSource(dict(accounts), codes={ch: code})

    return block, senders, mk_source


def run_exec_mode() -> None:
    """RETH_TPU_BENCH_MODE=exec (the DEFAULT): optimistic parallel block
    execution (engine/optimistic.py — Block-STM-style native speculation
    + read-set validation + async storage prefetch) vs the serial
    ``BlockExecutor`` interpreter, parameterized by conflict rate.
    Receipts and post state are verified bit-identical before any number
    is emitted. Headline = txs/s at 0% conflicts; ``vs_baseline`` = the
    serial wall over the optimistic wall on that workload. Extras carry
    the 10%/50%-conflict points and a workers=1 run (scheduler overhead
    floor / thread-scaling reference). Env: RETH_TPU_BENCH_EXEC_TXS
    (default 384), RETH_TPU_BENCH_EXEC_WORKERS (default 8),
    RETH_TPU_BENCH_EXEC_REPS (compute units per tx, default 400)."""
    from reth_tpu.engine.optimistic import execute_block_optimistic
    from reth_tpu.evm import BlockExecutor, EvmConfig

    n_txs = int(os.environ.get("RETH_TPU_BENCH_EXEC_TXS", "384"))
    workers = int(os.environ.get("RETH_TPU_BENCH_EXEC_WORKERS", "8"))
    reps = int(os.environ.get("RETH_TPU_BENCH_EXEC_REPS", "400"))
    cfg = EvmConfig(chain_id=1)
    _STATE["metric"] = "exec_parallel_txs_per_sec"
    _STATE["unit"] = "txs/s"
    _STATE["backend"] = "cpu"
    per_rate = {}
    headline = None
    for rate in (0.0, 0.1, 0.5):
        _STATE["phase"] = f"exec bench: build block ({rate:.0%} conflicts)"
        block, senders, mk_source = _exec_bench_block(n_txs, rate, reps)
        # warm: native library build + first-call allocations stay out of
        # the measured walls
        execute_block_optimistic(mk_source(), block, senders, cfg,
                                 max_workers=workers)
        _STATE["phase"] = f"exec bench: serial pass ({rate:.0%} conflicts)"
        t0 = time.time()
        serial = BlockExecutor(mk_source(), cfg).execute(block, senders)
        dt_serial = time.time() - t0
        _STATE["phase"] = f"exec bench: optimistic pass ({rate:.0%})"
        t0 = time.time()
        out, stats = execute_block_optimistic(mk_source(), block, senders,
                                              cfg, max_workers=workers)
        dt_opt = time.time() - t0
        _STATE["phase"] = f"exec bench: verify receipts ({rate:.0%})"
        if [r.encode_2718() for r in serial.receipts] != \
                [r.encode_2718() for r in out.receipts] or \
                serial.post_accounts != out.post_accounts or \
                serial.post_storage != out.post_storage or \
                serial.gas_used != out.gas_used:
            _emit(0, 0, error=f"optimistic/serial output mismatch at "
                              f"{rate:.0%} conflicts", exit_code=1)
        if stats.get("native"):
            _STATE["backend"] = "native-cpu"
        per_rate[f"{rate:.0%}"] = {
            "serial_wall_s": round(dt_serial, 4),
            "optimistic_wall_s": round(dt_opt, 4),
            "speedup": round(dt_serial / dt_opt, 3),
            "txs_per_sec": round(n_txs / dt_opt, 1),
            "serial_txs_per_sec": round(n_txs / dt_serial, 1),
            "rounds": stats.get("rounds"), "native": stats.get("native"),
            "conflicts": stats.get("conflicts"),
            "serial_reruns": stats.get("serial_rerun"),
            "prefetched": stats.get("prefetched"),
            "fallback": stats.get("fallback"),
        }
        if rate == 0.0:
            headline = (round(n_txs / dt_opt, 1),
                        round(dt_serial / dt_opt, 3))
    # scheduler overhead floor: same 0%-conflict block at ONE worker
    _STATE["phase"] = "exec bench: workers=1 reference"
    block, senders, mk_source = _exec_bench_block(n_txs, 0.0, reps)
    t0 = time.time()
    execute_block_optimistic(mk_source(), block, senders, cfg, max_workers=1)
    per_rate["0%"]["workers1_wall_s"] = round(time.time() - t0, 4)
    _STATE["device_result"] = headline[0]
    _emit(headline[0], headline[1], txs=n_txs, workers=workers,
          compute_reps=reps, conflict_rates=per_rate,
          receipts_identical=True, exit_code=0)


def run_import_mode():
    """RETH_TPU_BENCH_MODE=import: cross-block pipelined import
    (engine/block_pipeline.py — execute block N+1 over N's frozen commit
    window while N's fused root dispatches run) vs strictly serial
    import of the SAME chain through a depth-1 tree. Per-block state
    roots, receipts and senders are verified bit-identical BEFORE any
    number is emitted. Headline = blocks/s through the pipelined tree;
    ``vs_baseline`` = serial wall over pipelined wall. Extras carry the
    exec/commit leg walls, ``overlap_fraction`` (share of speculative
    exec that ran inside the parent's commit window), the abort ladder
    counters, and the sustained-wall target (wall/block < max leg —
    reachable only where the commit leg is device-bound; on a 1-core
    host the overlap is time-sliced and the fraction is still the
    honest signal). Env: RETH_TPU_BENCH_IMPORT_BLOCKS (default 8),
    RETH_TPU_BENCH_IMPORT_TXS (default 24),
    RETH_TPU_BENCH_IMPORT_WALLETS (default 48)."""
    from reth_tpu.engine import EngineTree
    from reth_tpu.engine.block_pipeline import import_chain
    from reth_tpu.engine.tree import PayloadStatusKind
    from reth_tpu.primitives import Account
    from reth_tpu.primitives.keccak import keccak256_batch_np
    from reth_tpu.storage import MemDb, ProviderFactory
    from reth_tpu.storage.genesis import init_genesis
    from reth_tpu.testing import ChainBuilder, Wallet
    from reth_tpu.trie import TrieCommitter

    n_blocks = int(os.environ.get("RETH_TPU_BENCH_IMPORT_BLOCKS", "8"))
    n_txs = int(os.environ.get("RETH_TPU_BENCH_IMPORT_TXS", "24"))
    n_wallets = int(os.environ.get("RETH_TPU_BENCH_IMPORT_WALLETS", "48"))
    _STATE["metric"] = "import_pipelined_blocks_per_sec"
    _STATE["unit"] = "blocks/s"

    cpu = TrieCommitter(hasher=keccak256_batch_np)
    committer = TrieCommitter()  # device/jitted keccak where available
    _STATE["backend"] = getattr(committer, "backend", None) or "device"

    def make_chain():
        ws = [Wallet(0x1000 + i) for i in range(n_wallets)]
        genesis = {w.address: Account(balance=10**21) for w in ws}
        b = ChainBuilder(genesis, committer=cpu)
        half = n_wallets // 2
        for i in range(n_blocks):
            # disjoint senders -> receivers; receivers spend next block,
            # so every block N+1 reads block N's uncommitted writes
            send, recv = (ws[:half], ws[half:]) if i % 2 == 0 else \
                         (ws[half:], ws[:half])
            b.build_block([send[j % half].transfer(
                recv[j % half].address, 10**14 + i * n_txs + j)
                for j in range(n_txs)])
        f = ProviderFactory(MemDb())
        init_genesis(f, b.genesis, b.accounts_at_genesis, committer=cpu)
        return b, f

    def run(depth, overlap):
        b, f = make_chain()
        tree = EngineTree(f, committer=committer,
                          persistence_threshold=10**9, pipeline_depth=depth)
        t0 = time.time()
        sts = import_chain(tree, b.blocks[1:], fcu=False, overlap=overlap)
        return b, tree, time.time() - t0, sts

    _STATE["phase"] = "import bench: warm-up chain"
    run(1, False)  # jit compiles + first-call allocations off the walls
    _STATE["phase"] = "import bench: serial import"
    b_s, t_serial, serial_wall, st_s = run(1, False)
    _STATE["phase"] = "import bench: pipelined import"
    b_p, t_piped, piped_wall, st_p = run(2, True)

    _STATE["phase"] = "import bench: verify roots bit-identical"
    if not all(s.status is PayloadStatusKind.VALID for s in st_s + st_p):
        _emit(0, 0, error="import bench: non-VALID payload status",
              exit_code=1)
    for i, (bs, bp_) in enumerate(zip(b_s.blocks[1:], b_p.blocks[1:])):
        es, ep = t_serial.blocks.get(bs.hash), t_piped.blocks.get(bp_.hash)
        if es is None or ep is None or \
                es.block.header.state_root != ep.block.header.state_root or \
                es.receipts != ep.receipts or es.senders != ep.senders:
            _emit(0, 0, error=f"import bench: serial/pipelined divergence "
                              f"at block {i + 1}", exit_code=1)

    stats = t_piped.pipeline.stats_snapshot()
    if stats["leases_active"]:
        _emit(0, 0, error=f"import bench: {stats['leases_active']} leaked "
                          f"sub-mesh leases", exit_code=1)
    adopted = stats["adopted"]
    exec_pb = stats["exec_wall_s"] / max(1, adopted + 1)
    commit_pb = stats["commit_wall_s"] / max(1, adopted + 1)
    sustained_pb = piped_wall / n_blocks
    max_leg_pb = max(exec_pb, commit_pb)
    _STATE["device_result"] = round(n_blocks / piped_wall, 3)
    _emit(round(n_blocks / piped_wall, 3),
          round(serial_wall / piped_wall, 3),
          blocks=n_blocks, txs_per_block=n_txs,
          serial_wall_s=round(serial_wall, 4),
          pipelined_wall_s=round(piped_wall, 4),
          serial_blocks_per_sec=round(n_blocks / serial_wall, 3),
          exec_wall_s=round(stats["exec_wall_s"], 4),
          commit_wall_s=round(stats["commit_wall_s"], 4),
          overlap_wall_s=round(stats["overlap_wall_s"], 4),
          overlap_fraction=round(stats["overlap_fraction"], 4),
          pipeline_depth=stats["depth"],
          speculations=stats["speculations"], adopted=adopted,
          aborted=stats["aborted"], abort_reasons=stats["abort_reasons"],
          sustained_per_block_s=round(sustained_pb, 4),
          max_leg_per_block_s=round(max_leg_pb, 4),
          wall_lt_max_leg=bool(sustained_pb < max_leg_pb),
          host_cores=os.cpu_count(),
          roots_identical=True, exit_code=0)


def run_hotstate_mode():
    """RETH_TPU_BENCH_MODE=hotstate: sustained overlapping import with
    the hot-state plane (trie/hot_cache.py + the digest arena) ON vs
    OFF over the SAME block stream. The stream interleaves two sibling
    forks over one wallet set (A1 B1 A2 B2 ...), so the single-claimant
    preserved trie misses on every import and the sparse task must
    reveal its anchors each block — the exact shape the cross-block
    cache exists for. Every payload status from BOTH runs must be VALID
    (each VALID is already a computed-root == header-root check against
    the CPU truth chain) BEFORE any number prints. Headline =
    proof-target reduction factor (uncached targets/block over cached
    targets/block; the issue's bar is >= 2x). Extras carry the
    proof-fetch walls, cache hit rate, per-block H2D bytes both ways,
    the delta-upload fraction (staged rows over staged+stamped; bar
    < 0.5 on this steady overlap), and the arena epoch counters.
    Env: RETH_TPU_BENCH_HOTSTATE_BLOCKS (default 8, per fork),
    RETH_TPU_BENCH_HOTSTATE_TXS (default 24),
    RETH_TPU_BENCH_HOTSTATE_WALLETS (default 48)."""
    from reth_tpu.engine import EngineTree
    from reth_tpu.engine.tree import PayloadStatusKind
    from reth_tpu.primitives import Account
    from reth_tpu.primitives.keccak import keccak256_batch_np
    from reth_tpu.storage import MemDb, ProviderFactory
    from reth_tpu.storage.genesis import init_genesis
    from reth_tpu.testing import ChainBuilder, Wallet
    from reth_tpu.trie import TrieCommitter

    n_blocks = int(os.environ.get("RETH_TPU_BENCH_HOTSTATE_BLOCKS", "8"))
    n_txs = int(os.environ.get("RETH_TPU_BENCH_HOTSTATE_TXS", "24"))
    n_wallets = int(os.environ.get("RETH_TPU_BENCH_HOTSTATE_WALLETS", "48"))
    _STATE["metric"] = "hotstate_proof_target_reduction"
    _STATE["unit"] = "x"

    cpu = TrieCommitter(hasher=keccak256_batch_np)
    committer = TrieCommitter()  # device/jitted keccak where available
    _STATE["backend"] = getattr(committer, "backend", None) or "device"

    def make_stream():
        genesis = {Wallet(0x2000 + i).address: Account(balance=10**21)
                   for i in range(n_wallets)}
        half = n_wallets // 2
        chains = []
        for fork in range(2):
            # both forks root at the SAME genesis and churn the SAME
            # wallet set; fresh Wallet objects per fork so each chain's
            # nonce tracking starts from genesis — distinct values keep
            # the sibling headers apart
            ws = [Wallet(0x2000 + i) for i in range(n_wallets)]
            b = ChainBuilder(genesis, committer=cpu)
            for i in range(n_blocks):
                send, recv = (ws[:half], ws[half:]) if i % 2 == 0 else \
                             (ws[half:], ws[:half])
                b.build_block([send[j % half].transfer(
                    recv[j % half].address,
                    10**13 + fork * 7 + i * n_txs + j)
                    for j in range(n_txs)])
            chains.append(b)
        order = []
        for i in range(1, n_blocks + 1):
            order.append(chains[0].blocks[i])
            order.append(chains[1].blocks[i])
        return chains[0], order

    def run(hot: bool):
        b, order = make_stream()
        f = ProviderFactory(MemDb())
        init_genesis(f, b.genesis, b.accounts_at_genesis, committer=cpu)
        tree = EngineTree(f, committer=committer,
                          persistence_threshold=10**9, hot_state=hot)
        agg = {"proof_wall_s": 0.0, "proof_targets": 0,
               "cache_unblinds": 0, "h2d_bytes": 0,
               "delta_fractions": [], "sparse_blocks": 0}
        t0 = time.time()
        sts = []
        for blk in order:
            sts.append(tree.on_new_payload(blk))
            m = tree.last_sparse or {}
            if m.get("strategy") == "sparse":
                agg["sparse_blocks"] += 1
                agg["proof_wall_s"] += m.get("proof", 0.0)
                agg["proof_targets"] += m.get("proof_targets", 0)
                agg["cache_unblinds"] += m.get("cache_unblinds", 0)
                cs = m.get("commit") or {}
                agg["h2d_bytes"] += int(cs.get("h2d_bytes", 0) or 0)
                if "delta_fraction" in cs:
                    agg["delta_fractions"].append(cs["delta_fraction"])
        agg["wall_s"] = time.time() - t0
        return tree, order, sts, agg

    _STATE["phase"] = "hotstate bench: warm-up run"
    run(False)  # jit compiles + first-call allocations off the walls
    _STATE["phase"] = "hotstate bench: uncached import"
    t_cold, order, st_cold, cold = run(False)
    _STATE["phase"] = "hotstate bench: cached import"
    t_hot, _, st_hot, hot = run(True)

    _STATE["phase"] = "hotstate bench: verify roots bit-identical"
    if not all(s.status is PayloadStatusKind.VALID
               for s in st_cold + st_hot):
        _emit(0, 0, error="hotstate bench: non-VALID payload status",
              exit_code=1)
    for blk in order:
        ec = t_cold.blocks.get(blk.hash)
        eh = t_hot.blocks.get(blk.hash)
        if ec is None or eh is None or \
                ec.block.header.state_root != eh.block.header.state_root:
            _emit(0, 0, error=f"hotstate bench: cached/uncached "
                              f"divergence at block "
                              f"{blk.header.number}", exit_code=1)

    n_imported = len(order)
    cold_pb = cold["proof_targets"] / n_imported
    hot_pb = hot["proof_targets"] / n_imported
    reduction = cold_pb / hot_pb if hot_pb else float(cold_pb or 1.0)
    cache_stats = t_hot.hot_cache.stats() if t_hot.hot_cache else {}
    lookups = cache_stats.get("hits", 0) + cache_stats.get("misses", 0)
    hit_rate = cache_stats.get("hits", 0) / lookups if lookups else 0.0
    arena = t_hot.hot_arena.snapshot() if t_hot.hot_arena else {}
    dfs = hot["delta_fractions"]
    _STATE["device_result"] = round(reduction, 3)
    _emit(round(reduction, 3), round(reduction, 3),
          blocks=n_imported, txs_per_block=n_txs,
          uncached_wall_s=round(cold["wall_s"], 4),
          cached_wall_s=round(hot["wall_s"], 4),
          uncached_proof_wall_s=round(cold["proof_wall_s"], 4),
          cached_proof_wall_s=round(hot["proof_wall_s"], 4),
          uncached_proof_targets_per_block=round(cold_pb, 2),
          cached_proof_targets_per_block=round(hot_pb, 2),
          cache_unblinds=hot["cache_unblinds"],
          cache_hit_rate=round(hit_rate, 4),
          cache_entries=cache_stats.get("entries", 0),
          cache_stale_drops=cache_stats.get("stale_drops", 0),
          uncached_h2d_bytes_per_block=round(
              cold["h2d_bytes"] / n_imported),
          cached_h2d_bytes_per_block=round(
              hot["h2d_bytes"] / n_imported),
          delta_upload_fraction=round(sum(dfs) / len(dfs), 4)
          if dfs else None,
          arena_delta_epochs=arena.get("delta_epochs", 0),
          arena_full_epochs=arena.get("full_epochs", 0),
          arena_resident_rows=arena.get("resident_rows", 0),
          arena_evictions=arena.get("evictions", 0),
          arena_faults=arena.get("faults", 0),
          sparse_blocks=hot["sparse_blocks"],
          roots_identical=True, exit_code=0)


def _mesh_inner(n: int) -> None:
    """Inner body of ``RETH_TPU_BENCH_MODE=mesh``: runs in a subprocess
    whose XLA host-device count is forced to ``n``, commits the SAME
    synthetic update stream through the single-device committer and the
    mesh-sharded one (FusedMeshEngine over a ``parallel/mesh.py``
    HashMesh — the production turbo level loop, not a demo reduction),
    asserts the roots bit-identical, and prints ONE raw JSON line with
    the mesh throughput + compile/steady wall split."""
    from reth_tpu.metrics import compile_tracker
    from reth_tpu.parallel.mesh import HashMesh
    from reth_tpu.trie.turbo import TurboCommitter

    accounts = int(os.environ.get("RETH_TPU_BENCH_MESH_ACCOUNTS", "20000"))
    slots = int(os.environ.get("RETH_TPU_BENCH_MESH_SLOTS",
                               str(max(accounts * 2 // 5, 100))))
    tier = int(os.environ.get("RETH_TPU_BENCH_MESH_TIER", "4096"))
    _STATE["phase"] = f"mesh inner ({n} devices): state build"
    storage_jobs, account_jobs = build_state(accounts, slots)

    single = TurboCommitter(backend="device", min_tier=tier)
    _STATE["phase"] = f"mesh inner ({n} devices): single-device warm pass"
    run_rebuild(single, storage_jobs, account_jobs, pipelined=True)
    _STATE["phase"] = f"mesh inner ({n} devices): single-device run"
    roots_single, _h, dt_single = run_rebuild(
        single, storage_jobs, account_jobs, pipelined=True)

    hash_mesh = HashMesh.build(n)
    meshc = TurboCommitter(backend="device", min_tier=tier, mesh=hash_mesh)
    compile_before = _compile_split()["compile_wall_s"]
    _STATE["phase"] = f"mesh inner ({n} devices): mesh warm pass (compiles)"
    run_rebuild(meshc, storage_jobs, account_jobs, pipelined=True)
    compile_wall = round(
        _compile_split()["compile_wall_s"] - compile_before, 4)
    _STATE["phase"] = f"mesh inner ({n} devices): mesh measured pass"
    roots_mesh, hashed, dt_mesh = run_rebuild(
        meshc, storage_jobs, account_jobs, pipelined=True)

    ok = roots_mesh == roots_single
    print(json.dumps({
        "n_devices": hash_mesh.n_devices,
        "roots_identical": ok,
        "hashes_per_sec": round(hashed / dt_mesh, 1),
        "steady_wall_s": round(dt_mesh, 4),
        "compile_wall_s": compile_wall,
        "single_hashes_per_sec": round(hashed / dt_single, 1),
        "hashed": hashed,
        "mesh_degraded": hash_mesh.snapshot()["unhealthy"],
        "compiled_shapes": compile_tracker.totals()["shapes"],
    }), flush=True)
    os._exit(0 if ok else 4)


def run_mesh_mode() -> None:
    """RETH_TPU_BENCH_MODE=mesh: the production turbo/fused rebuild loop
    SPMD-sharded over 1/2/4/8 SIMULATED host devices — each mesh size in
    its own subprocess (the XLA host-device count is fixed at backend
    init), with ``JAX_PLATFORMS=cpu`` forced so the mode is hermetic (it
    measures sharding overhead/scaling shape, never a chip). The parent
    has not touched JAX when it starts them (main() routes here before
    any cache/warm-up setup), so the children never contend for a device
    the parent holds. Roots are verified bit-identical to the
    single-device committer on the same update stream BEFORE any number
    prints; the headline is the largest mesh's steady-state hashes/s with
    per-mesh-size throughput + compile wall in ``per_mesh``. Env:
    RETH_TPU_BENCH_MESH_DEVICES (default "1,2,4,8"),
    RETH_TPU_BENCH_MESH_ACCOUNTS / _SLOTS / _TIER (workload)."""
    import subprocess

    sizes = sorted({int(x) for x in os.environ.get(
        "RETH_TPU_BENCH_MESH_DEVICES", "1,2,4,8").split(",") if x.strip()})
    _STATE["metric"] = "mesh_rebuild_hashes_per_sec"
    # simulated host devices: honest labeling — this mode never touches
    # a chip, it measures the sharded data plane's scaling
    _STATE["backend"] = "jax-cpu-mesh"
    per: dict[str, dict] = {}
    degraded = 0
    budget = max(90, (_DEADLINE - 60) // max(len(sizes), 1))
    for n in sizes:
        _STATE["phase"] = f"mesh subprocess ({n} devices)"
        env = {k: v for k, v in os.environ.items()
               if k != "RETH_TPU_WARMUP"}
        env["JAX_PLATFORMS"] = "cpu"
        flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                         if "host_platform_device_count" not in f)
        env["XLA_FLAGS"] = \
            (flags + f" --xla_force_host_platform_device_count={n}").strip()
        env["RETH_TPU_BENCH_MESH_INNER"] = str(n)
        env["RETH_TPU_BENCH_TIMEOUT"] = str(budget)
        try:
            r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                               env=env, capture_output=True, text=True,
                               timeout=budget + 60)
        except subprocess.TimeoutExpired:
            _emit(0, 0, error=f"mesh inner ({n} devices) exceeded "
                              f"{budget + 60}s", exit_code=0)
        line = None
        for out_line in reversed(r.stdout.strip().splitlines()):
            try:
                parsed = json.loads(out_line)
            except ValueError:
                continue
            if isinstance(parsed, dict):
                line = parsed
                break
        if not line or "n_devices" not in line or line.get("error"):
            diag = ((line or {}).get("error")
                    or (r.stderr or r.stdout or "no output")[-300:])
            _emit(0, 0, error=f"mesh inner ({n} devices) failed "
                              f"rc={r.returncode}: {diag}", exit_code=0)
        if not line.get("roots_identical"):
            # acceptance contract: a root divergence is a correctness
            # failure — no throughput number may print over it
            _emit(0, 0, error=f"mesh inner ({n} devices): roots diverged "
                              f"from the single-device committer",
                  exit_code=1)
        degraded = max(degraded, int(line.get("mesh_degraded", 0)))
        per[str(line["n_devices"])] = {
            k: line[k] for k in ("hashes_per_sec", "compile_wall_s",
                                 "steady_wall_s", "single_hashes_per_sec",
                                 "hashed", "compiled_shapes")
            if k in line}
    top = per[str(max(sizes))]
    base = per.get("1", {}).get("hashes_per_sec")
    _STATE["device_result"] = top["hashes_per_sec"]
    _emit(top["hashes_per_sec"],
          round(top["hashes_per_sec"] / base, 3) if base else 0,
          n_devices=max(sizes), per_mesh=per, mesh_degraded=degraded,
          roots_identical=True, exit_code=0)


def _subtrie_inner(n: int) -> None:
    """Inner body of ``RETH_TPU_BENCH_MODE=subtrie``: runs in a subprocess
    whose XLA host-device count is forced to ``n``, commits the SAME
    window set through the per-level committer (Mega/FusedMesh — one
    dispatch per staged level) and the whole-subtrie committer at
    k ∈ {1,2,4,8}, asserts every k's roots bit-identical to the
    per-level path BEFORE any number prints, and emits ONE raw JSON line
    with wall + dispatches/block per k."""
    from reth_tpu.metrics import fused_metrics
    from reth_tpu.parallel.mesh import HashMesh
    from reth_tpu.trie.turbo import TurboCommitter

    accounts = int(os.environ.get("RETH_TPU_BENCH_SUBTRIE_ACCOUNTS", "8000"))
    slots = int(os.environ.get("RETH_TPU_BENCH_SUBTRIE_SLOTS",
                               str(max(accounts * 2 // 5, 100))))
    tier = int(os.environ.get("RETH_TPU_BENCH_SUBTRIE_TIER", "1024"))
    ks = [int(x) for x in os.environ.get(
        "RETH_TPU_BENCH_SUBTRIE_KS", "1,2,4,8").split(",") if x.strip()]
    _STATE["phase"] = f"subtrie inner ({n} devices): state build"
    storage_jobs, account_jobs = build_state(accounts, slots)
    mesh = HashMesh.build(n) if n > 1 else None

    def measure(k: int):
        c = TurboCommitter(backend="device", min_tier=tier, mesh=mesh,
                           subtrie_levels=k)
        _STATE["phase"] = f"subtrie inner ({n} dev, k={k}): warm pass"
        run_rebuild(c, storage_jobs, account_jobs, pipelined=True)
        d0 = fused_metrics.dispatches_cum
        _STATE["phase"] = f"subtrie inner ({n} dev, k={k}): measured pass"
        roots, hashed, dt = run_rebuild(c, storage_jobs, account_jobs,
                                        pipelined=True)
        # one rebuild pass = 2 committer runs (storage tries + account
        # prefix subtries) — the "block" unit for dispatches/block
        disp = fused_metrics.dispatches_cum - d0
        return roots, hashed, dt, disp, round(disp / 2, 1)

    roots_pl, hashed, dt_pl, disp_pl, dpb_pl = measure(0)
    per_k: dict[str, dict] = {}
    ok = True
    for k in ks:
        roots_k, _h, dt_k, disp_k, dpb_k = measure(k)
        if roots_k != roots_pl:
            ok = False
        per_k[str(k)] = {
            "wall_s": round(dt_k, 4),
            "dispatches": disp_k,
            "dispatches_per_block": dpb_k,
            "dispatch_reduction": round(disp_pl / disp_k, 2) if disp_k else 0,
            "hashes_per_sec": round(hashed / dt_k, 1),
        }
    print(json.dumps({
        "n_devices": n,
        "roots_identical": ok,
        "hashed": hashed,
        "perlevel": {"wall_s": round(dt_pl, 4), "dispatches": disp_pl,
                     "dispatches_per_block": dpb_pl,
                     "hashes_per_sec": round(hashed / dt_pl, 1)},
        "per_k": per_k,
    }), flush=True)
    os._exit(0 if ok else 4)


def run_subtrie_mode() -> None:
    """RETH_TPU_BENCH_MODE=subtrie: whole-subtrie k-level fused commits
    vs the per-level committer — dispatches/block + wall at
    k ∈ {1,2,4,8}, on 1/2/4/8 SIMULATED host devices (one hermetic
    subprocess per mesh size, JAX_PLATFORMS=cpu forced, started from a
    parent that has not touched JAX). Every k's roots are verified bit-identical to the
    per-level committer on the same window set BEFORE any number prints;
    the headline is the dispatch-count reduction at the largest k on the
    largest mesh. Env: RETH_TPU_BENCH_SUBTRIE_DEVICES (default
    "1,2,4,8"), RETH_TPU_BENCH_SUBTRIE_KS (default "1,2,4,8"),
    RETH_TPU_BENCH_SUBTRIE_ACCOUNTS / _SLOTS / _TIER (workload)."""
    import subprocess

    sizes = sorted({int(x) for x in os.environ.get(
        "RETH_TPU_BENCH_SUBTRIE_DEVICES", "1,2,4,8").split(",") if x.strip()})
    _STATE["metric"] = "subtrie_dispatch_reduction"
    _STATE["unit"] = "x"
    _STATE["backend"] = "jax-cpu-mesh"
    per: dict[str, dict] = {}
    budget = max(120, (_DEADLINE - 60) // max(len(sizes), 1))
    for n in sizes:
        _STATE["phase"] = f"subtrie subprocess ({n} devices)"
        env = {k: v for k, v in os.environ.items()
               if k != "RETH_TPU_WARMUP"}
        env["JAX_PLATFORMS"] = "cpu"
        flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                         if "host_platform_device_count" not in f)
        env["XLA_FLAGS"] = \
            (flags + f" --xla_force_host_platform_device_count={n}").strip()
        env["RETH_TPU_BENCH_SUBTRIE_INNER"] = str(n)
        env["RETH_TPU_BENCH_TIMEOUT"] = str(budget)
        try:
            r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                               env=env, capture_output=True, text=True,
                               timeout=budget + 60)
        except subprocess.TimeoutExpired:
            _emit(0, 0, error=f"subtrie inner ({n} devices) exceeded "
                              f"{budget + 60}s", exit_code=0)
        line = None
        for out_line in reversed(r.stdout.strip().splitlines()):
            try:
                parsed = json.loads(out_line)
            except ValueError:
                continue
            if isinstance(parsed, dict):
                line = parsed
                break
        if not line or "n_devices" not in line or line.get("error"):
            diag = ((line or {}).get("error")
                    or (r.stderr or r.stdout or "no output")[-300:])
            _emit(0, 0, error=f"subtrie inner ({n} devices) failed "
                              f"rc={r.returncode}: {diag}", exit_code=0)
        if not line.get("roots_identical"):
            # acceptance contract: a root divergence is a correctness
            # failure — no dispatch number may print over it
            _emit(0, 0, error=f"subtrie inner ({n} devices): k-level roots "
                              f"diverged from the per-level committer",
                  exit_code=1)
        per[str(line["n_devices"])] = {
            "perlevel": line["perlevel"], "per_k": line["per_k"],
            "hashed": line["hashed"]}
    top = per[str(max(sizes))]
    best_k = max(top["per_k"], key=int)
    headline = top["per_k"][best_k]["dispatch_reduction"]
    _STATE["device_result"] = headline
    _emit(headline, headline,
          n_devices=max(sizes), k=int(best_k),
          dispatches_per_block=top["per_k"][best_k]["dispatches_per_block"],
          perlevel_dispatches_per_block=top["perlevel"][
              "dispatches_per_block"],
          per_mesh=per, roots_identical=True,
          verified="k-level roots bit-identical to the per-level "
                   "committer at every mesh size before measuring",
          exit_code=0)


def run_fleet_mode() -> None:
    """RETH_TPU_BENCH_MODE=fleet: sustained RPC throughput + p99 through
    the fleet gateway at 1/2/4/8 replicas vs the single-node gateway
    (fleet/): a dev full node in fleet mode feeds witness-validated
    replica SUBPROCESSES over the socket protocol, and the load runs two
    mixes through the gateway — duplicate-heavy (a small pool of hot
    reads: trackers/wallets hammering the same few calls, where the
    gateway cache + the ring's stable key→replica mapping should absorb
    nearly everything) and long-tail (mostly-distinct eth_calls, where
    replicas absorb the execution work the full node would otherwise
    serialize under its handler lock). Before ANY number prints, every
    distinct request's fleet-routed response is verified bit-identical
    to a direct ungated dispatch on the full node. Env:
    RETH_TPU_BENCH_FLEET_SIZES (default "1,2,4,8"),
    RETH_TPU_BENCH_FLEET_CLIENTS (default 6),
    RETH_TPU_BENCH_FLEET_REQS (requests/client/mix, default 50),
    RETH_TPU_BENCH_FLEET_KEYS (duplicate pool size, default 8)."""
    import shutil
    import subprocess
    import tempfile
    from pathlib import Path

    from reth_tpu.node import Node, NodeConfig
    from reth_tpu.primitives.keccak import keccak256_batch_np
    from reth_tpu.primitives.types import Account
    from reth_tpu.rpc.server import RpcServer
    from reth_tpu.testing import ChainBuilder, Wallet
    from reth_tpu.trie.committer import TrieCommitter

    sizes = [int(s) for s in os.environ.get(
        "RETH_TPU_BENCH_FLEET_SIZES", "1,2,4,8").split(",") if s]
    clients = int(os.environ.get("RETH_TPU_BENCH_FLEET_CLIENTS", "6"))
    reqs = int(os.environ.get("RETH_TPU_BENCH_FLEET_REQS", "50"))
    n_keys = int(os.environ.get("RETH_TPU_BENCH_FLEET_KEYS", "8"))
    _STATE["metric"] = "fleet_requests_per_sec"
    _STATE["unit"] = "requests/s"
    _STATE["backend"] = "cpu"
    _STATE["phase"] = "fleet node build"

    # fleet observability coverage: the bench runs TRACED — the node and
    # every replica export Chrome traces, and trace_stitched on the JSON
    # line asserts cross-process parent-id resolution held during the
    # bench. The exporter must install BEFORE the node mines: bench
    # main() enables span recording at process start (error-trail
    # contract), so witness spans generated during mining would
    # otherwise record + propagate without ever exporting.
    from reth_tpu import tracing as _tracing

    base = Path(tempfile.mkdtemp(prefix="reth-tpu-bench-fleet-"))
    _tracing.init_block_tracing(chrome_path=base / "node.trace.json")
    trace_stitched = False
    trace_pids = 0
    trace_diag: dict = {}

    committer = TrieCommitter(hasher=keccak256_batch_np)
    committer.turbo_backend = "numpy"
    wallet = Wallet(0xA11CE)
    builder = ChainBuilder({wallet.address: Account(balance=10**21)},
                           committer=committer)
    node = Node(NodeConfig(dev=True, genesis_header=builder.genesis,
                           genesis_alloc=builder.accounts_at_genesis,
                           fleet=True, http_port=0, authrpc_port=0),
                committer=committer)
    node.start_rpc()
    node.fleet_router.probe_interval = 0  # probed explicitly below
    fport = node.feed_server.port
    sink = b"\x0b" * 20
    blocks = 3
    for i in range(blocks):
        node.pool.add_transaction(wallet.transfer(sink, 100 + i))
        node.miner.mine_block(timestamp=1_700_000_000 + i * 12)

    def call_body(i):
        return json.dumps({
            "jsonrpc": "2.0", "id": 1, "method": "eth_call",
            "params": [{"from": "0x" + wallet.address.hex(),
                        "to": "0x" + sink.hex(), "value": hex(i)},
                       "latest"]}).encode()

    dup_pool = [call_body(i) for i in range(n_keys - 2)]
    dup_pool.append(json.dumps({
        "jsonrpc": "2.0", "id": 1, "method": "eth_getBlockByNumber",
        "params": [hex(blocks), False]}).encode())
    dup_pool.append(json.dumps({
        "jsonrpc": "2.0", "id": 1, "method": "eth_getLogs",
        "params": [{"fromBlock": "0x1", "toBlock": hex(blocks)}]}).encode())
    tail_pool = [call_body(1000 + i) for i in range(clients * reqs)]

    def run_mix(pool, duplicate: bool):
        """(requests/s, p99_ms) over `clients` threads; duplicate mix
        samples a hot pool, long-tail walks distinct requests."""
        lats: list[float] = []
        errs: list = []
        lock = threading.Lock()

        def worker(c):
            rng = np.random.default_rng(c)
            try:
                for i in range(reqs):
                    body = (pool[int(rng.integers(0, len(pool)))]
                            if duplicate else pool[c * reqs + i])
                    t0 = time.monotonic()
                    resp = json.loads(node.rpc.handle(body))
                    dt = time.monotonic() - t0
                    with lock:
                        lats.append(dt)
                        if "error" in resp:
                            errs.append(resp["error"])
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(c,))
              for c in range(clients)]
        t0 = time.time()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        wall = time.time() - t0
        if errs:
            raise RuntimeError(f"fleet bench request failed: {errs[0]}")
        return (round(len(lats) / wall, 1),
                round(float(np.percentile(lats, 99)) * 1e3, 2))

    procs: list = []
    urls: list[str] = []
    per_fleet: dict = {}
    try:
        _STATE["phase"] = "replica spawn"
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("RETH_TPU_FAULT_")}
        env["JAX_PLATFORMS"] = "cpu"
        port_files = []
        for i in range(max(sizes)):
            pf = base / f"replica-{i}.port"
            log = open(base / f"replica-{i}.log", "w")
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "reth_tpu.fleet", "replica",
                 "--feed", f"127.0.0.1:{fport}",
                 "--port-file", str(pf), "--id", f"bench-r{i}",
                 "--trace-file", str(base / f"replica-{i}.trace.json")],
                env=env, stdout=log, stderr=log))
            port_files.append(pf)
        deadline = time.time() + 90
        for pf in port_files:
            while not pf.exists() and time.time() < deadline:
                time.sleep(0.05)
            if not pf.exists():
                _emit(0, 0, error="replica subprocess never bound its "
                                  "port", exit_code=1)
            urls.append("http://127.0.0.1:"
                        f"{json.loads(pf.read_text())['http_port']}")

        # single-node baseline: the same gateway with an empty ring
        _STATE["phase"] = "single-node baseline"
        node.gateway.on_head_change()  # comparable cold cache per run
        single = dict(zip(("dup_rps", "dup_p99_ms"),
                          run_mix(dup_pool, duplicate=True)))
        single.update(zip(("tail_rps", "tail_p99_ms"),
                          run_mix(tail_pool, duplicate=False)))

        naked = RpcServer(lock=node.rpc.lock)
        naked.methods = node.rpc.methods
        router = node.fleet_router
        for n in sizes:
            _STATE["phase"] = f"fleet x{n}: sync + verify"
            for url in urls[:n]:
                router.register(url)
            for url in urls[n:]:
                for h in list(router.replicas.values()):
                    if h.url == url:
                        router.deregister(h.id)
            deadline = time.time() + 60
            while time.time() < deadline:
                router.probe_once()
                s = router.snapshot()
                if s["healthy"] == n and s["max_lag"] == 0:
                    break
                time.sleep(0.1)
            else:
                _emit(0, 0, error=f"fleet x{n} never converged: "
                                  f"{router.snapshot()}", exit_code=1)
            # bit-identical BEFORE any number prints: every distinct
            # request through the fleet vs a direct ungated dispatch
            node.gateway.on_head_change()
            for body in dup_pool + tail_pool[::17]:
                via_fleet = json.loads(node.rpc.handle(body))
                direct = json.loads(naked.handle(body))
                if via_fleet != direct:
                    _emit(0, 0, error=f"fleet x{n} response mismatch: "
                                      f"{body[:120]!r}", exit_code=1)
            _STATE["phase"] = f"fleet x{n}: measured run"
            node.gateway.on_head_change()
            r0 = router.snapshot()
            entry = dict(zip(("dup_rps", "dup_p99_ms"),
                             run_mix(dup_pool, duplicate=True)))
            entry.update(zip(("tail_rps", "tail_p99_ms"),
                             run_mix(tail_pool, duplicate=False)))
            r1 = router.snapshot()
            entry["routed"] = r1["routed"] - r0["routed"]
            entry["failovers"] = r1["failovers"] - r0["failovers"]
            entry["local"] = (r1["local_fallbacks"]
                              - r0["local_fallbacks"])
            # per-replica breakdown: routed reads this run (router
            # handles) + lifetime served/read-p99 pulled over the
            # metrics federation — a hot or slow replica shows on the
            # bench line, not just in its own process
            before = {r["id"]: r["routed"] for r in r0["replicas"]}
            node.fleet_federation.pull_once()
            per_replica = {}
            for r in r1["replicas"]:
                rid = r["id"]
                served = node.fleet_federation.replica_latest(
                    rid, "gateway_requests_total_read")
                p99 = node.fleet_federation.replica_quantile(
                    rid, "gateway_service_seconds_read", 0.99)
                per_replica[rid] = {
                    "routed": r["routed"] - before.get(rid, 0),
                    "served_reads": (served["v"] if served else None),
                    "read_p99_ms": (round(p99 * 1e3, 3)
                                    if p99 is not None else None),
                }
            entry["per_replica"] = per_replica
            per_fleet[n] = entry
        # stitched-trace assertion: a few more routed reads, then merge
        # the node's + every replica's Chrome trace — every
        # cross-process parent id must resolve
        _STATE["phase"] = "trace stitch check"
        node.gateway.on_head_change()
        for i in range(8):
            node.rpc.handle(call_body(31000 + i))
        stitch = _tracing.stitch_chrome_traces(
            [base / "node.trace.json",
             *sorted(base.glob("replica-*.trace.json"))])
        trace_pids = len(stitch["pids"])
        trace_stitched = bool(stitch["stitched"]
                              and trace_pids >= min(max(sizes), 2) + 1)
        trace_diag = {"cross_refs": stitch["cross_refs"],
                      "unresolved_cross":
                          len(set(stitch["unresolved_cross"]))}
        if os.environ.get("RETH_TPU_BENCH_TRACE_DEBUG"):
            # triage aid: print the events whose cross-process parent
            # never resolved (which span, which pid, which parent)
            bad = set(stitch["unresolved_cross"])
            for e in stitch["events"]:
                if (e.get("args") or {}).get("parent_id") in bad:
                    sys.stderr.write(f"UNRESOLVED {json.dumps(e)}\n")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        shutil.rmtree(base, ignore_errors=True)
        node.stop()
        _tracing.shutdown_block_tracing()

    top = per_fleet[max(sizes)]
    value = top["tail_rps"]
    _STATE["device_result"] = value
    lo = per_fleet[min(sizes)]["tail_rps"]
    _emit(value,
          round(value / single["tail_rps"], 3) if single["tail_rps"] else 0,
          per_fleet={str(k): v for k, v in per_fleet.items()},
          single_node=single, fleet_sizes=sizes,
          # the scaling shape is the honest headline on a small host: a
          # 1-core container pays the HTTP hop on every routed read, so
          # vs_baseline < 1 there while fleet_scaling still shows the
          # fan-out working (replicas are real processes)
          fleet_scaling=round(value / lo, 2) if lo else 0,
          requests_per_mix=clients * reqs, duplicate_pool=len(dup_pool),
          trace_stitched=trace_stitched, trace_pids=trace_pids,
          trace_diag=trace_diag,
          verified="bit-identical vs ungated dispatch before measuring",
          exit_code=0)


def run_ha_mode() -> None:
    """RETH_TPU_BENCH_MODE=ha: leader-kill failover wall through the HA
    pair (fleet/standby.py). A leader subprocess (fleet+WAL dev node,
    mining continuously) ships its durable stream to a hot-standby
    subprocess; two replica subprocesses serve reads with the standby's
    takeover feed as their failover endpoint. A continuous read load
    runs against the replicas while the leader is SIGKILLed mid-stream;
    the headline is ``promote_ms`` (the standby's catching-up → leading
    wall) with ``failover_wall_s`` (kill → promoted gateway serving)
    and ``reads_failed`` (read-load failures across the whole failover
    window — the HA promise is zero). Env:
    RETH_TPU_BENCH_HA_HEARTBEAT (detection timeout, default 1.0s),
    RETH_TPU_BENCH_HA_BLOCKS (blocks mined before the kill, default 6)."""
    import shutil
    import signal as signal_mod
    import socket as socket_mod
    import subprocess
    import tempfile
    import urllib.request
    from pathlib import Path

    from reth_tpu.chaos import _child_env, _read_record

    heartbeat = float(os.environ.get("RETH_TPU_BENCH_HA_HEARTBEAT", "1.0"))
    pre_blocks = int(os.environ.get("RETH_TPU_BENCH_HA_BLOCKS", "6"))
    _STATE["metric"] = "ha_promote_ms"
    _STATE["unit"] = "ms"
    _STATE["backend"] = "cpu"
    _STATE["phase"] = "ha pair spawn"
    base = Path(tempfile.mkdtemp(prefix="reth-tpu-bench-ha-"))
    procs: list = []

    def rpc(port, method, params=None, timeout=10.0):
        body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                           "params": params or []}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/", data=body,
            headers={"Content-Type": "application/json"})
        return json.loads(urllib.request.urlopen(req, timeout=timeout).read())

    def spawn(cmd, env, log_name):
        log = open(base / log_name, "w")
        p = subprocess.Popen(cmd, env=env, stdout=log, stderr=log)
        procs.append(p)
        return p

    def wait_port_file(pf, what, deadline_s=90):
        deadline = time.time() + deadline_s
        while not pf.exists() and time.time() < deadline:
            time.sleep(0.05)
        if not pf.exists():
            _emit(0, 0, error=f"{what} never bound its port", exit_code=1)
        return json.loads(pf.read_text())

    try:
        with socket_mod.socket() as s:
            s.bind(("127.0.0.1", 0))
            tport = s.getsockname()[1]
        leader_dir = base / "leader"
        lpf = base / "leader.port"
        leader = spawn(
            [sys.executable, "-m", "reth_tpu.chaos", "ha-leader",
             "--datadir", str(leader_dir), "--seed", "1",
             "--port-file", str(lpf)],
            _child_env(), "leader.log")
        lports = wait_port_file(lpf, "leader")
        lhttp, lfeed = lports["http_port"], lports["feed_port"]

        spf = base / "standby.port"
        spawn(
            [sys.executable, "-m", "reth_tpu.fleet", "standby",
             "--feed", f"127.0.0.1:{lfeed}",
             "--datadir", str(base / "standby"),
             "--takeover-feed-port", str(tport),
             "--heartbeat-timeout", str(heartbeat),
             "--id", "bench-sb", "--port-file", str(spf)],
            _child_env(), "standby.log")
        shttp = wait_port_file(spf, "standby")["http_port"]

        rports = []
        for i in range(2):
            rpf = base / f"replica-{i}.port"
            spawn(
                [sys.executable, "-m", "reth_tpu.fleet", "replica",
                 "--feed", f"127.0.0.1:{lfeed}",
                 "--failover-feed", f"127.0.0.1:{tport}",
                 "--auto-register",
                 "--register", f"http://127.0.0.1:{lhttp}",
                 "--id", f"bench-r{i}", "--port-file", str(rpf)],
                _child_env(), f"replica-{i}.log")
            rports.append(wait_port_file(rpf, f"replica {i}")["http_port"])

        # gate: a recorded chain + a caught-up standby + serving replicas
        _STATE["phase"] = "ha pair sync"
        deadline = time.time() + 120
        status: dict = {}
        while time.time() < deadline:
            mined = [l for l in _read_record(leader_dir) if "hash" in l]
            try:
                status = rpc(shttp, "fleet_standbyStatus")["result"]
            except Exception:  # noqa: BLE001 — standby still booting
                status = {}
            if (len(mined) >= pre_blocks
                    and status.get("records_applied", 0) > 0
                    and not status.get("awaiting_resync", True)
                    and status.get("lag_heads", 99) <= 2):
                break
            time.sleep(0.1)
        else:
            _emit(0, 0, error=f"standby never caught up: "
                              f"{json.dumps(status)[:300]}", exit_code=1)

        # continuous read load against the replicas across the failover
        _STATE["phase"] = "leader kill + failover"
        stop = threading.Event()
        reads = {"total": 0, "failed": 0}
        rlock = threading.Lock()

        def load():
            i = 0
            while not stop.is_set():
                port = rports[i % len(rports)]
                i += 1
                try:
                    resp = rpc(port, "eth_getBlockByNumber",
                               ["latest", False], timeout=5)
                    bad = "error" in resp
                except Exception:  # noqa: BLE001 — transport loss counts
                    bad = True
                with rlock:
                    reads["total"] += 1
                    reads["failed"] += 1 if bad else 0
                time.sleep(0.01)

        loaders = [threading.Thread(target=load, daemon=True)
                   for _ in range(2)]
        for t in loaders:
            t.start()
        time.sleep(0.5)
        os.kill(leader.pid, signal_mod.SIGKILL)
        leader.wait()
        killed_at = time.time()

        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                status = rpc(shttp, "fleet_standbyStatus")["result"]
            except Exception:  # noqa: BLE001 — admin RPC mid-promotion
                status = {}
            if status.get("state") in ("leading", "failed"):
                break
            time.sleep(0.05)
        if status.get("state") != "leading":
            _emit(0, 0, error=f"standby never promoted: "
                              f"{json.dumps(status, default=str)[:300]}",
                  exit_code=1)
        pnode = status["node"]
        failover_wall_s = time.time() - killed_at

        # the promoted gateway serves, and the replicas re-anchor on it
        _STATE["phase"] = "post-promotion re-anchor"
        promoted_reads_failed = 0
        for i in range(8):
            try:
                resp = rpc(pnode["http_port"], "eth_blockNumber")
                promoted_reads_failed += 1 if "error" in resp else 0
            except Exception:  # noqa: BLE001
                promoted_reads_failed += 1
        deadline = time.time() + 90
        reanchored = False
        while time.time() < deadline and not reanchored:
            try:
                fs = rpc(pnode["http_port"], "fleet_status")["result"]
                reanchored = fs.get("registered", 0) >= 2
            except Exception:  # noqa: BLE001
                pass
            if not reanchored:
                time.sleep(0.2)
        stop.set()
        for t in loaders:
            t.join(timeout=5)

        value = float(status.get("promote_ms") or 0.0)
        _STATE["device_result"] = value
        _emit(value, 1.0,
              reads_failed=reads["failed"], reads_total=reads["total"],
              promoted_reads_failed=promoted_reads_failed,
              failover_wall_s=round(failover_wall_s, 2),
              detection_timeout_s=heartbeat,
              replicas_reanchored=reanchored,
              leader_epoch=status.get("leader_epoch"),
              standby_resyncs=status.get("resyncs_applied"),
              records_applied=status.get("records_applied"),
              verified="promoted head root recomputed at takeover "
                       "(recovery_verify_root)",
              exit_code=0 if (reads["failed"] == 0
                              and promoted_reads_failed == 0
                              and reanchored) else 1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        shutil.rmtree(base, ignore_errors=True)


def _txflow_schedule(wallets, under_wallet, txs_per_wallet: int, rng,
                     value_tag: int):
    """One adversarial submission schedule: per-wallet nonce chains with
    duplicates, valid replacements (2x fees, >= the 10% bump), underpriced
    replacements (+5%, below the bump), and one dedicated underpriced tx
    (fee cap below the base fee — admitted, never executable). Returns
    ``(schedule, slots)`` where schedule entries are ``(kind, tx, track)``
    in submission order (per-sender order preserved by a round-robin
    interleave) and ``slots`` is the number of (sender, nonce) slots the
    chain-valid stream should eventually mine exactly once each."""
    from itertools import zip_longest

    from reth_tpu.primitives.types import Transaction

    sink = b"\x0f" * 20
    per_wallet = []
    for wi, w in enumerate(wallets):
        seq = []
        bases = []
        for k in range(txs_per_wallet):
            tx = w.transfer(sink, 10**9 + value_tag + wi * 1000 + k)
            bases.append(tx)
            seq.append(("base", tx, True))
        # duplicate: the same raw tx again — rejected "already known"
        seq.append(("dup", bases[int(rng.integers(0, len(bases)))], False))
        if wi % 3 == 0:
            # valid replacement: same nonce at 2x fees — the winner; the
            # base it replaces must NEVER be mined (asserted via slots)
            tgt = bases[int(rng.integers(0, len(bases)))]
            seq.append(("repl", w.sign_tx(Transaction(
                tx_type=2, chain_id=1, nonce=tgt.nonce,
                max_fee_per_gas=tgt.max_fee_per_gas * 2,
                max_priority_fee_per_gas=tgt.max_priority_fee_per_gas * 2,
                gas_limit=21_000, to=sink, value=tgt.value + 1,
            ), bump_nonce=False), True))
        elif wi % 3 == 1:
            # underpriced replacement: +5% < the 10% min bump — rejected
            # ("replacement underpriced", or "nonce too low" when the base
            # won the race to a block first; both are correct outcomes)
            tgt = bases[int(rng.integers(0, len(bases)))]
            seq.append(("repl_under", w.sign_tx(Transaction(
                tx_type=2, chain_id=1, nonce=tgt.nonce,
                max_fee_per_gas=tgt.max_fee_per_gas * 105 // 100,
                max_priority_fee_per_gas=tgt.max_priority_fee_per_gas,
                gas_limit=21_000, to=sink, value=tgt.value + 1,
            ), bump_nonce=False), False))
        per_wallet.append(seq)
    sched = [e for rnd in zip_longest(*per_wallet) for e in rnd
             if e is not None]
    # fee cap below any base fee: admitted (balance/nonce are fine) but
    # effective tip < 0 — sits in the basefee bucket, never selected
    sched.insert(int(rng.integers(0, len(sched) + 1)),
                 ("under", under_wallet.transfer(
                     sink, 1, max_fee_per_gas=1,
                     max_priority_fee_per_gas=0), False))
    return sched, len(wallets) * txs_per_wallet


def _txflow_verify(node) -> str | None:
    """The txflow acceptance contract: wait for the hot candidate to reach
    pool parity, then compare its inclusion set bit-identically against ONE
    serial greedy ``build_payload`` pass over a CLONED pool (same txs,
    submission order preserved so heap ties break identically; the clone
    absorbs the serial pass's evictions instead of the live pool). Returns
    None on bit-identity, else a diagnostic string. Mining must be paused
    by the caller — the comparison needs a quiescent head."""
    from reth_tpu.payload.builder import build_payload
    from reth_tpu.pool.pool import TransactionPool

    prod = node.producer
    got = parent = attrs = None
    deadline = time.time() + 20
    while time.time() < deadline:
        with prod._lock:
            cand = prod.candidate
            with node.pool._lock:
                if (cand is not None and cand.window is None
                        and cand.parent_hash == node.tree.head_hash
                        and cand.pool_seq == node.pool.event_seq):
                    got = [t.hash for t in cand.selected]
                    parent, attrs = cand.parent_hash, cand.attrs
                    break
        time.sleep(0.01)
    if got is None:
        return "producer never reached pool parity"
    clone = TransactionPool(node.pool.state_reader, config=node.pool.config)
    clone.base_fee = node.pool.base_fee
    clone.blob_base_fee = node.pool.blob_base_fee
    with node.pool._lock:
        ptxs = sorted(node.pool.by_hash.values(),
                      key=lambda p: p.submission_id)
    for p in ptxs:
        clone.add_transaction(p.tx, sender=p.sender)
    block, _fees = build_payload(node.tree, clone, parent, attrs)
    want = [t.hash for t in block.transactions]
    if got != want:
        return (f"candidate/serial inclusion set mismatch: candidate "
                f"{len(got)} txs, serial {len(want)} txs, first divergence "
                f"at rank {next((i for i, (a, b) in enumerate(zip(got, want)) if a != b), min(len(got), len(want)))}")
    return None


def run_txflow_mode() -> None:
    """RETH_TPU_BENCH_MODE=txflow: the production write path end-to-end —
    txpool firehose -> continuous block production (payload/producer.py)
    vs the same flood through the serial build-on-demand miner. At each
    offered load point an adversarial submission mix (nonce chains +
    duplicates + replacements + underpriced) floods the insertion batcher
    while the dev miner seals on an interval; the headline is the
    tx->inclusion p99 at the top rate with txs/block, shed counts, and the
    producer's incremental economy (fresh vs replayed ranks, hot-hit rate)
    in ``per_rate``. ACCEPTANCE CONTRACT: at every load point the hot
    candidate's inclusion set is verified bit-identical against one serial
    greedy build over a cloned pool BEFORE any number prints (divergence
    = rc 1). ``vs_baseline`` = serial-miner p99 / continuous p99 at the
    top rate. Hermetic (CPU dev node, numpy committer — never touches a
    device). Env: RETH_TPU_BENCH_TXFLOW_RATES (default "1000,10000,50000"
    offered tx/s), RETH_TPU_BENCH_TXFLOW_WALLETS (default 10),
    RETH_TPU_BENCH_TXFLOW_TXS (chain length per wallet, default 6),
    RETH_TPU_BENCH_TXFLOW_INTERVAL (mining interval s, default 0.25)."""
    from reth_tpu.node import Node, NodeConfig
    from reth_tpu.pool.batcher import PoolOverloaded
    from reth_tpu.pool.pool import PoolError
    from reth_tpu.primitives.keccak import keccak256_batch_np
    from reth_tpu.primitives.types import Account
    from reth_tpu.testing import ChainBuilder, Wallet
    from reth_tpu.trie.committer import TrieCommitter

    rates = [int(r) for r in os.environ.get(
        "RETH_TPU_BENCH_TXFLOW_RATES", "1000,10000,50000").split(",") if r]
    n_wallets = int(os.environ.get("RETH_TPU_BENCH_TXFLOW_WALLETS", "10"))
    txs_per_wallet = int(os.environ.get("RETH_TPU_BENCH_TXFLOW_TXS", "6"))
    interval = float(os.environ.get("RETH_TPU_BENCH_TXFLOW_INTERVAL", "0.25"))
    _STATE["metric"] = "txflow_inclusion_p99_ms"
    _STATE["unit"] = "ms"
    _STATE["backend"] = "cpu"

    def make_node(continuous: bool):
        committer = TrieCommitter(hasher=keccak256_batch_np)
        committer.turbo_backend = "numpy"
        wallets = [Wallet(0xB100 + i) for i in range(n_wallets)]
        under_wallet = Wallet(0xBEEF)
        genesis = {w.address: Account(balance=10**21)
                   for w in wallets + [under_wallet]}
        builder = ChainBuilder(genesis, committer=committer)
        node = Node(NodeConfig(dev=True, genesis_header=builder.genesis,
                               genesis_alloc=builder.accounts_at_genesis,
                               continuous_build=continuous,
                               http_port=0, authrpc_port=0),
                    committer=committer)
        node.start_rpc()
        return node, wallets, under_wallet

    def run_point(continuous: bool, rate: int, seed: int) -> dict:
        rng = np.random.default_rng(seed)
        node, wallets, under_wallet = make_node(continuous)
        try:
            sched, _slots = _txflow_schedule(wallets, under_wallet,
                                             txs_per_wallet, rng, rate)
            sub_times: dict[bytes, tuple[float, bool]] = {}
            lats: list[float] = []
            counts = {"accepted": 0, "dup_rejected": 0,
                      "repl_rejected": 0, "sheds": 0}
            blocks = {"total": 0, "nonempty": 0, "mined": 0}
            mined_hashes: set[bytes] = set()
            pause = threading.Event()
            stop = threading.Event()
            miner_err: list = []

            def miner_loop():
                while not stop.is_set():
                    if stop.wait(interval):
                        return
                    if pause.is_set():
                        continue
                    try:
                        blk = node.miner.mine_block()
                    except Exception as e:  # noqa: BLE001 — surfaced below
                        miner_err.append(e)
                        return
                    now = time.monotonic()
                    blocks["total"] += 1
                    if blk.transactions:
                        blocks["nonempty"] += 1
                    for t in blk.transactions:
                        rec = sub_times.get(t.hash)
                        if rec is not None:
                            mined_hashes.add(t.hash)
                            blocks["mined"] += 1
                            if rec[1]:
                                lats.append(now - rec[0])

            mt = threading.Thread(target=miner_loop, daemon=True)
            mt.start()
            _STATE["phase"] = (f"txflow {rate}/s "
                               f"({'continuous' if continuous else 'serial'})"
                               f": flood")
            futs = []
            t0 = time.monotonic()
            for i, (kind, tx, track) in enumerate(sched):
                lag = t0 + i / rate - time.monotonic()
                if lag > 0:
                    time.sleep(lag)
                sub_times[tx.hash] = (time.monotonic(), track)
                futs.append((kind, tx, node.tx_batcher.submit(tx)))
            accepted: set[bytes] = set()
            for kind, tx, fut in futs:
                try:
                    fut.result(timeout=30)
                    counts["accepted"] += 1
                    accepted.add(tx.hash)
                except PoolOverloaded:
                    counts["sheds"] += 1
                except PoolError as e:
                    if kind == "dup":
                        counts["dup_rejected"] += 1
                    elif kind in ("repl", "repl_under"):
                        # "replacement underpriced", or "nonce too low"
                        # when the base won the race into a block first
                        counts["repl_rejected"] += 1
                    else:
                        raise RuntimeError(
                            f"txflow: unexpected rejection of a {kind} "
                            f"tx: {e}")
            # drain: every accepted slot mined, only the underpriced
            # straggler left pooled (it can never execute at this fee)
            _STATE["phase"] = (f"txflow {rate}/s: drain "
                               f"({'continuous' if continuous else 'serial'})")
            stragglers = sum(1 for k, t, _ in futs
                             if k == "under" and t.hash in accepted)
            deadline = time.time() + 90
            while time.time() < deadline and not miner_err:
                with node.pool._lock:
                    left = len(node.pool.by_hash)
                if left <= stragglers:
                    break
                time.sleep(0.02)
            else:
                if not miner_err:
                    raise RuntimeError(
                        f"txflow: pool never drained at {rate}/s "
                        f"({left} txs left, {stragglers} expected)")
            if miner_err:
                raise RuntimeError(f"txflow: miner failed: {miner_err[0]}")
            if continuous:
                # acceptance contract: pause mining, push one more
                # adversarial burst, and verify the refreshed candidate
                # bit-identical against a serial greedy build over a
                # cloned pool BEFORE this point's numbers count
                _STATE["phase"] = f"txflow {rate}/s: verify vs serial greedy"
                pause.set()
                burst, _ = _txflow_schedule(wallets, under_wallet,
                                            2, rng, rate + 1)
                bfuts = [(k, t, node.tx_batcher.submit(t))
                         for k, t, _tr in burst]
                for k, t, f in bfuts:
                    try:
                        f.result(timeout=30)
                        sub_times[t.hash] = (time.monotonic(), False)
                        accepted.add(t.hash)
                    except PoolError:
                        pass
                diag = _txflow_verify(node)
                if diag is not None:
                    _emit(0, 0, error=f"txflow at {rate}/s: {diag}",
                          exit_code=1)
                pause.clear()
                deadline = time.time() + 90
                while time.time() < deadline and not miner_err:
                    with node.pool._lock:
                        left = len(node.pool.by_hash)
                    if left <= stragglers + 1:  # + the burst's underpriced
                        break
                    time.sleep(0.02)
            stop.set()
            mt.join(timeout=10)
            if miner_err:
                raise RuntimeError(f"txflow: miner failed: {miner_err[0]}")
            if not lats:
                raise RuntimeError(f"txflow: no inclusion latencies at "
                                   f"{rate}/s")
            entry = {
                "p99_inclusion_ms": round(
                    float(np.percentile(lats, 99)) * 1e3, 2),
                "mean_inclusion_ms": round(
                    float(np.mean(lats)) * 1e3, 2),
                "txs_per_block": round(
                    blocks["mined"] / max(1, blocks["nonempty"]), 2),
                "blocks": blocks["total"],
                "nonempty_blocks": blocks["nonempty"],
                "mined": blocks["mined"],
                **counts,
                "batcher_sheds": node.tx_batcher.sheds,
            }
            if continuous and node.producer is not None:
                s = node.producer.snapshot()
                entry["producer"] = {
                    k: s[k] for k in ("refreshes", "full_rebuilds",
                                      "exec_ranks", "reexec_ranks",
                                      "invalidated", "hits", "misses",
                                      "sealed", "errors")}
                entry["miner_producer_seals"] = node.miner.producer_seals
                entry["miner_serial_builds"] = node.miner.serial_builds
            return entry
        finally:
            stop.set()
            node.stop()

    per_rate: dict[str, dict] = {}
    for rate in rates:
        entry = run_point(True, rate, seed=rate)
        entry["serial_miner"] = {
            k: v for k, v in run_point(False, rate, seed=rate).items()
            if k in ("p99_inclusion_ms", "mean_inclusion_ms",
                     "txs_per_block", "blocks", "mined")}
        per_rate[str(rate)] = entry
    top = per_rate[str(max(rates))]
    value = top["p99_inclusion_ms"]
    serial_p99 = top["serial_miner"]["p99_inclusion_ms"]
    _STATE["device_result"] = value
    _emit(value, round(serial_p99 / value, 3) if value else 0,
          per_rate=per_rate, rates=rates,
          txs_per_block=top["txs_per_block"],
          sheds=sum(per_rate[str(r)]["sheds"] for r in rates),
          wallets=n_wallets, chain_len=txs_per_wallet,
          mining_interval_s=interval,
          verified="candidate inclusion set bit-identical to a serial "
                   "greedy build over a cloned pool at every load point "
                   "before measuring",
          exit_code=0)


def _setup_compile_cache() -> None:
    """Configure the persistent XLA compilation cache in the one function
    every entry point shares (ops/device.configure_compile_cache:
    JAX_COMPILATION_CACHE_DIR, else the fixed in-checkout path). The
    emitted ``compile_cache`` field splits cold (empty cache, compiles pay
    full wall) from warm (rerun: compiles load from disk), so
    compile_wall_s is attributable. Errors are not caught: a bench that
    could not set its cache up did not measure what it says."""
    from reth_tpu.ops.device import configure_compile_cache
    from reth_tpu.ops.warmup import CompileCache

    _STATE["phase"] = "compile-cache setup"
    configure_compile_cache()
    cc = CompileCache()
    summary = cc.summary()
    _STATE["compile_cache"] = {"dir": summary["dir"],
                               "state": summary["mode"],
                               "entries": summary["entries"]}
    _STATE["_cache_obj"] = cc  # hands per-shape hit tracking to warm-up


def _maybe_warmup() -> None:
    """RETH_TPU_WARMUP=background|block: run the real warm-up manager
    (ops/warmup.py) over the default shape menu before measuring, so the
    measured window is pure steady state and the line's ``warmup_state``
    carries the per-shape compile walls + cache hit/miss split. Errors
    propagate (non-zero exit)."""
    mode = os.environ.get("RETH_TPU_WARMUP", "off")
    if mode == "off":
        return
    _STATE["phase"] = "managed warm-up (shape menu)"
    from reth_tpu.ops.warmup import WarmupManager

    mgr = WarmupManager(cache=_STATE.get("_cache_obj"))
    _STATE["warmup_mgr"] = mgr  # _emit snapshots it live
    if mode == "block":
        mgr.run()
    else:
        mgr.start()
        mgr.wait(timeout=_DEADLINE / 2)


def run_rebuild_mode() -> None:
    """RETH_TPU_BENCH_MODE=rebuild: the device state-root rebuild. The
    device or a non-zero exit — see the module docstring."""
    from reth_tpu.ops.device import (DeviceUnavailable, cpu_route_counters,
                                     moved_cpu_routes, require_device)

    n_accounts = int(os.environ.get("RETH_TPU_BENCH_ACCOUNTS", "150000"))
    n_slots = int(os.environ.get("RETH_TPU_BENCH_SLOTS", "60000"))
    tier = int(os.environ.get("RETH_TPU_BENCH_TIER", "16384"))

    _STATE["phase"] = "device check"
    try:
        platform, kind, count = require_device()
    except DeviceUnavailable as e:
        _emit(0, 0, error=f"no device: {e}", exit_code=1)
    device = {"platform": platform, "kind": kind, "count": count}

    from reth_tpu.trie.turbo import TurboCommitter

    _STATE["backend"] = "device"
    _STATE["phase"] = "state build"
    storage_jobs, account_jobs = build_state(n_accounts, n_slots)

    # forced large min_tier => one or two batch tiers => <=~4 XLA programs
    dev_committer = TurboCommitter(backend="device", min_tier=tier)
    cpu_committer = TurboCommitter(backend="numpy")

    # warm-up = one full untimed run, so every program shape the measured
    # run dispatches is already compiled (XLA caches by shape in-process).
    # Its wall is reported as the compile side of the compile/steady split
    # (the per-shape detail rides in via the compile tracker).
    routes_before = cpu_route_counters()
    _STATE["phase"] = "device warm-up (compiles)"
    t_warm = time.time()
    run_rebuild(dev_committer, storage_jobs, account_jobs, pipelined=True)
    dt_warm = time.time() - t_warm
    if (_STATE.get("warmup_mgr") is None
            and _STATE.get("warmup_state", "off") == "off"):
        # no managed warm-up ran: the untimed full pass IS the warm-up —
        # still attributed, so this line can't masquerade as steady state
        _STATE["warmup_state"] = {"state": "bench-warm-pass",
                                  "wall_s": round(dt_warm, 3)}

    _STATE["phase"] = "device run"
    roots_dev, hashed_dev, dt_dev = run_rebuild(
        dev_committer, storage_jobs, account_jobs, pipelined=True)
    _STATE["device_result"] = round(hashed_dev / dt_dev, 1)
    # read BEFORE the numpy baseline below moves the numpy-commit counter
    moved = moved_cpu_routes(routes_before)
    if moved:
        _emit(0, 0, error=f"work left the device (CPU-route counters "
                          f"moved): {moved}", device=device, exit_code=1)
    _STATE["phase"] = "cpu baseline"
    roots_cpu, _hashed_cpu, dt_cpu = run_rebuild(
        cpu_committer, storage_jobs, account_jobs, pipelined=True)
    if roots_dev != roots_cpu:
        _emit(0, 0, error="device/cpu root mismatch", device=device,
              exit_code=1)

    _emit(round(hashed_dev / dt_dev, 1), round(dt_cpu / dt_dev, 3),
          device=device,
          device_wall_s=round(dt_dev, 3), baseline_wall_s=round(dt_cpu, 3),
          warmup_wall_s=round(dt_warm, 3),
          steady_hashes_per_sec=round(hashed_dev / dt_dev, 1),
          exit_code=0)


def main():
    # record spans/events from the start: the flight-recorder excerpt in
    # any error line needs the trail (probe attempts, first compiles)
    from reth_tpu import tracing

    tracing.set_trace_enabled(True)
    inner = os.environ.get("RETH_TPU_BENCH_MESH_INNER")
    if inner:
        # mesh-mode subprocess: measure + verify, skip warm-up/cache setup
        # (the inner run attributes its own compile wall explicitly)
        _mesh_inner(int(inner))
        return
    inner = os.environ.get("RETH_TPU_BENCH_SUBTRIE_INNER")
    if inner:
        _subtrie_inner(int(inner))
        return
    mode = os.environ.get("RETH_TPU_BENCH_MODE", "exec")
    # mesh/subtrie: the parent only starts CPU-forced children, and does so
    # BEFORE it touches JAX (one process per device: a parent that had
    # initialised a backend would hold it against its own children)
    if mode == "mesh":
        run_mesh_mode()
        return
    if mode == "subtrie":
        run_subtrie_mode()
        return
    _setup_compile_cache()
    _maybe_warmup()
    if mode == "service":
        run_service_mode()
        return
    if mode == "sparse":
        run_sparse_mode()
        return
    if mode == "gateway":
        run_gateway_mode()
        return
    if mode == "fleet":
        run_fleet_mode()
        return
    if mode == "ha":
        run_ha_mode()
        return
    if mode == "txflow":
        run_txflow_mode()
        return
    if mode == "import":
        run_import_mode()
        return
    if mode == "hotstate":
        run_hotstate_mode()
        return
    if mode == "exec":
        # the DEFAULT: CPU-measurable optimistic parallel execution — the
        # perf trajectory records a real number with or without a device
        run_exec_mode()
        return
    run_rebuild_mode()


if __name__ == "__main__":
    main()
