"""The per-layer metrics that read the program's commit phases and its byte
and row counters: the ``counter_over_counter`` reader's arithmetic and the
cases in which it returns nothing, and that a rehearsal of a traced run of
the rebuild cell yields every one of them, consistent with one another."""

import json

import pytest

from benchmark import run as runmod
from benchmark.harness import spec as specmod
from benchmark.readers import counter_over_counter

SPEC = specmod.Spec()
CELL = "rebuild.accounts"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
TIME = ["turbo_sweep_s_per_mhash", "turbo_decode_s_per_mhash",
        "fused_stage_s_per_mhash", "h2d_s_per_mhash",
        "device_wait_s_per_mhash", "d2h_s_per_mhash"]
COUNT = ["fused_row_fill_pct", "h2d_bytes_per_hash", "d2h_bytes_per_hash"]


def test_counter_over_counter_arithmetic():
    facts = {"counters_before": {"a": 10.0, "b": 1.0, "c": 100.0},
             "counters_after": {"a": 40.0, "b": 3.0, "c": 228.0, "d": 7.0}}
    params = {"num": ["a", "b"], "den": ["c"], "scale": 100.0}
    # (30 + 2) / 128 * 100
    assert counter_over_counter.read(facts, params) == 25.0
    # a counter first seen after the window started counts from 0
    assert counter_over_counter.read(
        facts, {"num": ["d"], "den": ["b"]}) == 3.5


@pytest.mark.parametrize("params", [
    {"num": ["a"], "den": ["missing"]},        # the parent: no such counter
    {"num": ["missing"], "den": ["c"]},
    {"num": ["a"], "den": ["still"]},          # the denominator did not move
])
def test_counter_over_counter_returns_nothing(params):
    facts = {"counters_before": {"a": 1.0, "c": 2.0, "still": 5.0},
             "counters_after": {"a": 2.0, "c": 4.0, "still": 5.0}}
    assert counter_over_counter.read(facts, params) is None


def test_every_new_metric_has_a_file_that_names_counters_of_the_program():
    from reth_tpu.metrics import REGISTRY

    have = dict(REGISTRY.items())
    for name in TIME + COUNT:
        mf = SPEC.metric_file(name)
        p = mf["params"]
        for c in p.get("counters", []) + p.get("num", []) + p.get("den", []):
            assert c in have, (name, c)
    phases = [c for n in TIME for c in SPEC.metric_file(n)["params"]["counters"]]
    assert len(phases) == len(set(phases)) == 9  # each phase read once


@pytest.fixture(scope="module")
def traced():
    """One traced run at the rehearsal size: the result and the driver's
    own facts of the window."""
    drivers = []
    result, _ = runmod.measure(SPEC, SPEC.cell(CELL), 4294967400, 0.5, True,
                               DEVICE, True, driver_hook=drivers.append)
    return result, drivers[0].facts()


def test_traced_rehearsal_yields_the_nine_metrics(traced):
    result, _ = traced
    assert result["correct"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert all(got.get(n, 0) > 0 for n in TIME + COUNT), got
    assert 0 < got["fused_row_fill_pct"] < 100
    # every array crosses at least once: the arena goes up as zeros and
    # comes back whole, the staged bytes go up beside it
    assert got["h2d_bytes_per_hash"] > got["d2h_bytes_per_hash"] >= 32
    json.dumps(result)  # the line can be printed


def test_time_metrics_sum_to_the_operations_wall(traced):
    """The six time metrics cover all nine phases of the serial path, so
    times Mhash per operation they come back to the operation's wall (at
    the rehearsal size loosely: a small commit's fixed costs are not in any
    phase)."""
    result, facts = traced
    got = {k: v["value"] for k, v in result["metrics"].items()}
    per_op = sum(got[n] for n in TIME) * facts["mhashes"] / facts["ops"]
    mean_op = sum(facts["op_seconds"]) / facts["ops"]
    assert 0.5 * mean_op < per_op <= mean_op
