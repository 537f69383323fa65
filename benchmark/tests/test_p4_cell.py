"""``rebuild.accounts.p4`` (configuration ``sync-rebuild-32m``): the cell is
data alone, so these hold the data to what the cell is meant to be and drive
it at the rehearsal size: the one command off the TPU, the control, a fault
that only a chunk of several subtries can have (a sweep group's branch records
decoded against the wrong place in the shared arena), and the per-layer
metrics that read the pipeline's counters. ``test_rehearsal.py`` and
``test_correct.py`` run the cell too, by their parametrisation over
BENCHMARK.json."""

import os
import subprocess
import sys
from pathlib import Path

from benchmark import run as runmod
from benchmark.control import run_control
from benchmark.harness import spec as specmod

ROOT = Path(__file__).resolve().parents[2]
CELL = "rebuild.accounts.p4"
SPEC = specmod.Spec()
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_the_cell_is_the_chunk_of_four_subtries_the_deployment_commits():
    cell = SPEC.cell(CELL)
    config, old = SPEC.config(cell["config"]), SPEC.config("sync-rebuild")
    workload = SPEC.workload_file(CELL)
    assert (cell["config"], cell["chips"]) == ("sync-rebuild-32m", 1)
    assert config["accounts_total"] == 32_000_000
    assert config["leaves_per_prefix"] * 256 == config["accounts_total"]
    assert config["chunk_leaves"] == 500_000 == old["chunk_leaves"]
    # four whole prefixes reach chunk_leaves, three do not
    assert -(-config["chunk_leaves"] // config["leaves_per_prefix"]) == 4
    assert config["reduced"] == {} and config["guarantees"] == old["guarantees"]
    for key in ("turbo_backend", "hasher", "min_tier", "pipeline_knobs",
                "collect_branches", "key_bytes", "key_distribution",
                "account_value_bytes", "account_subtrie_prefix_nibbles"):
        assert config[key] == old[key], key
    assert workload["driver"] == "rebuild"
    assert workload["call"] == {"start_depth": 2, "collect_branches": True,
                                "traced_op": 1,
                                "rate_metric": "rebuild_hashes_per_s"}
    traffic, ours = workload["traffic"], SPEC.workload_file("rebuild.accounts")
    assert traffic == ours["traffic"] and traffic["distinct_ops"] == 4
    assert traffic["values"]["contract_share"] == 0.1
    assert workload["rehearsal"] == {
        "distinct_ops": 2,
        "jobs": {"chunk_leaves": 1000, "leaves_per_subtrie": 250}}


def test_cpu_rehearsal_runs_the_whole_path_and_prints_no_result():
    bench = SPEC.bench
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    out = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload", CELL, "--seed",
         "4294967389", "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 3, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "rehearsal, not a chip run: no result line" in out.stderr
    assert "FAIL" not in out.stderr
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in bench[g]]
    assert not any(n in out.stderr for n in names)


def test_the_pipelines_metrics_read_a_window_of_four_subtries():
    """Every operation goes through ``RebuildPipeline``: one window of four
    subtries, one arena re-plan, a pack phase, and no program shape first
    seen inside the window."""
    result, _ = runmod.measure(SPEC, SPEC.cell(CELL), 41, 0.5, True, DEVICE,
                               True)
    assert result["correct"] and result["failed"] == 0
    assert result["compiled_in_window"] == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert got["pipeline_windows_per_op"] == 1
    assert got["pipeline_subtries_per_window"] == 4
    assert got["arena_grows_per_op"] == 1
    assert got["program_shapes_first_seen_per_op"] == 0
    assert got["pipeline_wait_s_per_mhash"] >= 0
    # every host phase of the chunk has its metric on this cell too
    for name in ("pipeline_pack_s_per_mhash", "turbo_decode_s_per_mhash.p4",
                 "turbo_sweep_s_per_mhash.p4", "fused_stage_s_per_mhash.p4",
                 "h2d_s_per_mhash.p4", "h2d_bytes_per_hash.p4",
                 "device_wait_s_per_mhash.p4", "d2h_s_per_mhash.p4",
                 "d2h_bytes_per_hash.p4"):
        assert got[name] > 0, name
    listed = {m["name"] for m in SPEC.metrics("per_layer", CELL)}
    # what only a device trace gives is left out off the chip, not made up
    assert listed - set(got) == {"device_idle_pct.p4", "keccak_roofline.p4",
                                 "peak_hbm_mb.p4"}
    for name in listed:
        SPEC.metric_file(name)                        # each has its file


def test_control_is_not_correct():
    res = run_control(CELL, 4294967401, "lost_leaf", 0.5, rehearsal=True)
    assert not res["correct"]
    assert res["checks"]["root_mismatches"]["value"] > 0


def test_a_group_decoded_with_the_wrong_slot_base_is_not_correct(monkeypatch):
    """The roots come out right and the branch nodes carry their
    neighbours' hashes: only the comparison of every stored branch node
    sees it."""
    from reth_tpu.trie import turbo

    real = turbo._collect_meta_records

    def off_by_one(*args, slot_base=0):
        return real(*args, slot_base=slot_base + 1)

    monkeypatch.setattr(turbo, "_collect_meta_records", off_by_one)
    result, _ = runmod.measure(SPEC, SPEC.cell(CELL), 43, 0.5, False, DEVICE,
                               True)
    assert not result["correct"]
    checks = result["checks"]
    assert checks["root_mismatches"]["value"] == 0
    assert checks["branch_node_mismatches"]["value"] > 0
