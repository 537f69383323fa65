"""``gather_operand_bytes_per_row`` and ``.p4``: the operand of the packed
level programs' row read over their row tiers. On a program that has the two
counters a traced rehearsal of either cell reads a number no larger than the
staging buffer would allow; on one that lacks them (the parent) the reader
returns nothing and the result line leaves the metric out."""

import pytest

from benchmark import run as runmod
from benchmark.harness import spec as specmod
from benchmark.readers import counter_over_counter

SPEC = specmod.Spec()
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
CELLS = {"rebuild.accounts": "gather_operand_bytes_per_row",
         "rebuild.accounts.p4": "gather_operand_bytes_per_row.p4"}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_traced_rehearsal_reads_the_levels_extent_over_its_row_tier(cell):
    from reth_tpu.ops.fused_commit import MegaFusedEngine
    from reth_tpu.primitives.keccak import RATE

    metric = CELLS[cell]
    assert SPEC.metric_file(metric)["reader"] == "counter_over_counter"
    assert [m["name"] for m in SPEC.metrics("per_layer", cell)
            if m["name"].startswith("gather_")] == [metric]
    result, _ = runmod.measure(SPEC, SPEC.cell(cell), 4294967431, 0.5, True,
                               DEVICE, True)
    assert result["correct"] and result["failed"] == 0
    value = result["metrics"][metric]["value"]
    # at the rehearsal size the staging buffer is shorter than a row tier of
    # rows, so the extent is the buffer: between nothing and L of tier 1
    assert 0 < value <= RATE
    assert value * MegaFusedEngine._ROW_FLOOR >= 1 << 16  # _buffer_lens' floor


def test_without_the_counters_the_reader_returns_nothing():
    params = SPEC.metric_file("gather_operand_bytes_per_row")["params"]
    assert params == SPEC.metric_file("gather_operand_bytes_per_row.p4")["params"]
    facts = {"counters_before": {"fused_rows_dispatched_total": 1.0},
             "counters_after": {"fused_rows_dispatched_total": 9.0}}
    assert counter_over_counter.read(facts, params) is None
    facts["counters_after"].update(fused_gather_operand_bytes_total=8912896.0,
                                   fused_gather_rows_total=65536.0)
    assert counter_over_counter.read(facts, params) == 136.0
