# Developer entry points (reference: Makefile + nextest in CI,
# .github/workflows/unit.yml).

# Parallel test run: xdist shards by FILE (port-isolated fixtures make
# files independent); JAX pinned to CPU (JAX_PLATFORMS=cpu also entitles
# `--hasher device` paths to run there). Override workers with
# TEST_WORKERS=n. On the chip: `python chip_smoke.py` (one chip),
# `python chip_smoke.py --chips 4`.
TEST_WORKERS ?= 6

.PHONY: test test-serial test-faults test-pipeline test-service test-sparse test-parallel test-gateway test-obs test-warmup test-health test-mesh test-subtrie test-chaos test-reorg test-fleet test-fleet-obs test-ha test-txflow test-import-pipeline test-hotstate native tsan-triebuild

test:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests -q -p no:cacheprovider \
	  -n $(TEST_WORKERS) --dist loadfile

test-serial:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests -q -p no:cacheprovider

# device-supervisor failover drill: probes, breaker, watchdog, mid-commit
# CPU failover + fault injection — CPU-only, no device required
test-faults:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_supervisor.py -q -p no:cacheprovider

# shared hash service: continuous batching, priority lanes, backpressure,
# exclusive lease, and the RETH_TPU_FAULT_SERVICE_* overload/stall/failover
# drills — CPU-only, no device required
test-service:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_hash_service.py -q -p no:cacheprovider

# parallel sparse commit: randomized packed-vs-serial differential parity
# (bit-identical roots across updates/deletes/wipes, blinded + preserved
# edges), encode/proof pool-size sweeps, a threaded stress drill over a
# shared committer, and the RETH_TPU_FAULT_SPARSE_* abort/wedge fault
# drills (fallback to the incremental committer) — CPU-only
test-sparse:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_sparse_parallel.py tests/test_sparse.py \
	  tests/test_sparse_root_engine.py tests/test_hotstate.py \
	  -q -p no:cacheprovider

# hot-state plane (ISSUE 19): cross-block trie-node cache
# (trie/hot_cache.py) + device-resident digest arena (DigestArena in
# ops/fused_commit.py). Hash-keyed cache versioning, keccak validation
# (RETH_TPU_FAULT_HOTSTATE_POISON must be CAUGHT), the 10-seed
# cached-vs-uncached randomized differential (roots bit-identical over
# interleaved update/delete/wipe streams + fork switches), arena epoch
# eviction / fault-fallback / EVICT_STORM drills, sibling-fork engine
# integration, and the hotstate_* metrics + degrade-only SLO rule —
# CPU-only
test-hotstate:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_hotstate.py -q -p no:cacheprovider

# optimistic parallel execution (part of the default `make test` sweep):
# randomized differential parity vs the serial executor across conflict
# rates / worker counts / coinbase-sensitive ranks / mid-block reverts,
# the BAL + native-core equivalence suites it builds on, the
# RETH_TPU_FAULT_EXEC_* conflict-storm and rank-wedge drills (serial
# fallback ladder), and a threaded stress run over the shared native
# core — CPU-only
test-parallel:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_parallel_exec.py tests/test_bal.py \
	  tests/test_native_exec.py -q -p no:cacheprovider

# RPC serving gateway: threaded coalescing stress (bit-identical to the
# ungated path), priority/shed behavior under full queues, head-change
# cache invalidation, RETH_TPU_FAULT_GATEWAY_* drills, and HTTP/WS/IPC
# one-gateway transport parity — CPU-only
test-gateway:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_gateway.py -q -p no:cacheprovider

# block-lifecycle observability (part of the default `make test` flow —
# tests/ is swept wholesale): trace-context propagation + per-block
# timelines, flight-recorder dumps on RETH_TPU_FAULT_* drills, Chrome /
# OTLP span-file validation, /metrics exposition-format checks, the
# metrics thread-safety hammer, and the tracing-disabled overhead guard
# (span cost < 1% of the sparse-commit wall) — CPU-only
test-obs:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_observability.py tests/test_fleet_obs.py \
	  -q -p no:cacheprovider -m 'not slow'

# fleet observability plane: trace wire-form encode/decode + adoption
# (feed frames, routed-RPC traceparent), Chrome-trace stitching across
# >=3 pids, metrics-federation delta protocol + bucket-exact histogram
# merge (randomized property test) + stale degradation, correlated
# flight dumps fanned over the feed under RETH_TPU_FAULT_REPLICA_WEDGE,
# the fleet SLO rules, and the federation/wire-form overhead guards;
# the @slow half runs the chaos --domain fleet wedge drill end-to-end
# (3 processes, stitched trace + bucket-exact scope=fleet + one
# correlation id across all three dumps) — CPU-only
test-fleet-obs:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_fleet_obs.py -q -p no:cacheprovider

# node health & SLO engine (part of the default `make test` flow —
# tests/ is swept wholesale): metric ring-buffer retention + windowed
# quantiles, the burn-rate evaluator (degraded within one window,
# failing on sustained burn, hysteretic recovery), breach flight dumps +
# the RETH_TPU_FAULT_SLO_BREACH drill, /health + debug_healthCheck /
# debug_sloStatus / debug_metricsHistory end-to-end on a dev node with
# a hash-service stall, the bench perf-regression sentinel (rebuild mode
# without its device -> non-zero exit; exec mode -> a real CPU number +
# vs_prev), and the
# sampler/evaluator overhead guard (<1% of the sparse-commit wall) —
# CPU-only, no device required
test-health:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_health.py -q -p no:cacheprovider

# mesh-sharded hash service: partition-rule routed sharded dispatch,
# randomized mesh-vs-single-device differential parity (incl. non-pow2
# meshes / uneven tiers), sub-mesh rebuild leases with live traffic
# continuing, the per-device breaker shrink+replay ladder under
# RETH_TPU_FAULT_DEVICE_WEDGE, mesh warm-up menu variants, and the
# RETH_TPU_BENCH_MODE=mesh end-to-end drill — CPU-only (8 virtual
# host devices via conftest)
test-mesh:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_mesh_service.py tests/test_parallel.py \
	  -q -p no:cacheprovider
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_fused_commit.py tests/test_turbo_commit.py \
	  -q -p no:cacheprovider -m 'not slow'

# device warm-up manager: shape-menu AOT compile lifecycle (watchdog +
# backoff retry under the RETH_TPU_FAULT_COMPILE_WEDGE drill, degraded
# CPU serving, promotion after recovery), the one-place compile-cache
# configuration (ops/device.py), and the keccak/fused tier clamps —
# CPU-only, no device required
test-warmup:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_warmup.py -q -p no:cacheprovider

# consensus robustness: orphan BlockBuffer bound/TTL + buffered-child
# replay, invalid-cache LRU bound (incl. the @slow 10k-payload flood
# acceptance drill), fcU cancellation of in-flight inserts with a
# wedged proof worker held across the fcU, reorg-storm detection +
# speculation backoff, deep-reorg depth accounting, and the
# ForkBuilder/tamper machinery the chaos consensus domain drives —
# CPU-only (tier-1 runs the same files minus the @slow flood)
test-reorg:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_consensus_robustness.py \
	  tests/test_engine_tree.py tests/test_sparse_root_engine.py \
	  -q -p no:cacheprovider

# crash-safe persistence + chaos drills: WAL format/replay/checkpoint
# units, corrupt-image quarantine, reorg-across-restart, and the @slow
# subprocess matrix — kill -9 at EVERY declared crash point
# (RETH_TPU_FAULT_CRASH_AT), raw SIGKILL mid-mining, the 10-seed
# composed-injector storage campaign AND the 10-seed Engine-API
# consensus campaign (seeded reorg storms vs a fault-free twin; seeds
# printed on failure for exact replay via `python -m reth_tpu.chaos
# scenario --domain storage|consensus --seed N`), the deep-reorg-
# across-threshold SIGKILL drill, and the deliberately-broken
# torn-record-accepted drill proving the invariant suite can fail.
# Kill drills are `-m slow` so tier-1 keeps its budget; this target
# runs everything — including the fleet domain's replica-kill-mid-load
# drills (tests/test_fleet.py) and the hot-state cache dimension
# (half the consensus seeds storm a --hot-state node against an
# uncached twin; POISON/EVICT_STORM injectors; zero leaked arena
# rows post-storm) — CPU-only, no device required
test-chaos:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_wal_recovery.py tests/test_chaos.py \
	  tests/test_fleet.py tests/test_fleet_obs.py tests/test_ha.py \
	  tests/test_block_pipeline.py tests/test_txflow.py \
	  tests/test_hotstate.py -q -p no:cacheprovider

# production write path: txpool firehose -> continuous block production.
# Randomized differential producer-vs-serial-greedy parity (clone-pool
# bit-identity at pool-sequence parity), nonce-gap promotion mid-build,
# blob-tx fee gating, replacement-racing-inclusion slot accounting,
# TxBatcher backpressure (-32005 + retry_after + shed metrics), pt_*
# feed framing + replica pending-view reads, classify() pinning for
# producer_/txpool_, scenario determinism, plus the @slow multi-process
# drills: the SIGKILL-mid-build pool chaos domain (10 seeds, `python -m
# reth_tpu.chaos campaign --domain pool`) and the
# RETH_TPU_BENCH_MODE=txflow end-to-end capture — CPU-only
test-txflow:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_txflow.py -q -p no:cacheprovider

# cross-block import pipeline (engine/block_pipeline.py): randomized
# serial-vs-pipelined differential imports (roots/receipts/senders
# bit-identical), deterministic mid-commit speculation via a gated
# commit leg, the abort ladder (tampered-root parent, fcU reorg
# mid-speculation), lease hygiene, and depth plumbing — CPU-only.
# The consensus chaos domain storms depth-2 trees on half its seeds
# (see test-chaos / `python -m reth_tpu.chaos campaign --domain
# consensus`); RETH_TPU_BENCH_MODE=import is the perf capture.
test-import-pipeline:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_block_pipeline.py -q -p no:cacheprovider

# leader/standby high availability: promotion state machine + heartbeat
# monitor units, wire-framing corruption vetting (torn/CRC/stale-epoch/
# out-of-order-generation rejected exactly like on-disk replay),
# flapping-feed client backoff + resubscribe-from-last-seen-head, the
# fleet_promote/fleet_standbyStatus ENGINE admission pinning, live
# leader->standby WAL shipping + in-process promotion, plus the @slow
# multi-process drills: the SIGKILL-the-leader chaos domain (10 seeds,
# `python -m reth_tpu.chaos campaign --domain ha`), the no-fence
# negative drill proving the suite can fail, and the
# RETH_TPU_BENCH_MODE=ha end-to-end capture — CPU-only
test-ha:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_ha.py -q -p no:cacheprovider

# stateless read-replica fleet: consistent-hash ring units (stability,
# failover order), witness-feed CRC framing, router draining ladder
# (lag/wedge/transport-dead -> shed -> hysteretic heal) over fake
# replicas, a live fleet-mode dev node with a witness-fed replica
# serving eth_call/eth_estimateGas/eth_getProof/eth_getLogs/
# eth_getBlockBy* bit-identical to the full node (late-joiner blinded
# reads -> -32001 -> gateway failover), plus the @slow multi-process
# drills: SIGKILL-a-replica-mid-load, the 10-seed fleet chaos campaign,
# and the RETH_TPU_BENCH_MODE=fleet end-to-end capture — CPU-only
test-fleet:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_fleet.py -q -p no:cacheprovider

# whole-subtrie fused tree-hash kernels: k-level engine parity vs the
# per-level engines and the numpy twin (k x depth x mesh grid incl.
# non-pow2 6/3-device meshes), the RETH_TPU_FAULT_SUBTRIE_{WEDGE,ABORT}
# fused->per-level->CPU fault ladder, the hoisted ladder-cap regression
# (64-level branch-heavy window stays on-menu), warm-up k-shape routing,
# and hash-service multi-level window requests. The compile-heavy
# k-sweeps are `-m slow` so tier-1 keeps its budget; this target runs
# everything — CPU-only (8 virtual host devices via conftest)
test-subtrie:
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_subtrie_fused.py -q -p no:cacheprovider

# rebuild pipeline (the one turbo commit path): parity between layouts and
# with the plain reference, the one-group chunk, packing, arena residency, abort/failover drills, chunked-resume — fast, CPU-only
# (the sanitizer stress build is `-m slow`; run it via tsan-triebuild);
# the whole-subtrie k-level backend rides along (it is a pipeline
# backend: flush_window per packed window)
test-pipeline: test-subtrie
	JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_turbo_pipeline.py tests/test_merkle_resume.py \
	  -q -p no:cacheprovider -m 'not slow'

native:
	mkdir -p native/build
	g++ -O2 -std=c++17 -shared -fPIC -pthread native/triebuild.cpp -o native/build/libtriebuild.so
	g++ -O2 -std=c++17 -shared -fPIC native/secp256k1.cpp -o native/build/libsecp.so
	g++ -O2 -std=c++17 -shared -fPIC native/kvstore.cpp -o native/build/libkvstore.so
	g++ -O2 -std=c++17 -shared -fPIC native/pagedkv.cpp -o native/build/libpagedkv.so
	g++ -O2 -std=c++17 -shared -fPIC -pthread native/evmexec.cpp -o native/build/libevmexec.so

# threaded stress of the native structure sweep under TSAN (the rebuild
# pipeline calls rtb_build from a thread pool, and rtb_build sweeps a large
# job on threads of its own); mirrors kvstore_tsan.cpp.
# Where gcc's libtsan breaks on the running kernel, build with
# -fsanitize=address,undefined instead (tests/test_turbo_pipeline.py
# probes and picks automatically).
tsan-triebuild:
	mkdir -p native/build
	g++ -std=c++17 -O1 -g -fsanitize=thread -pthread \
	  native/triebuild.cpp native/triebuild_tsan.cpp -o native/build/triebuild_stress
	./native/build/triebuild_stress
