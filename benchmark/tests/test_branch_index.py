"""``branch_index_elems_per_row`` and ``.p4``: the elements of the index
arrays that the branch level programs' gathers and scatters take, over their
row tiers. On a program that has the two counters a traced rehearsal of
either cell reads a number under 64 (a body that indexes single bytes of the
rows reads hundreds); on one that lacks them (the parent) the reader returns
nothing and the result line leaves the metric out."""

import pytest

from benchmark import run as runmod
from benchmark.harness import spec as specmod
from benchmark.readers import counter_over_counter

SPEC = specmod.Spec()
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
CELLS = {"rebuild.accounts": "branch_index_elems_per_row",
         "rebuild.accounts.p4": "branch_index_elems_per_row.p4"}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_traced_rehearsal_reads_fewer_than_64_index_elements_a_row(cell):
    metric = CELLS[cell]
    assert SPEC.metric_file(metric)["reader"] == "counter_over_counter"
    assert [m["name"] for m in SPEC.metrics("per_layer", cell)
            if m["name"].startswith("branch_")] == [metric]
    result, _ = runmod.measure(SPEC, SPEC.cell(cell), 4294967432, 0.5, True,
                               DEVICE, True)
    assert result["correct"] and result["failed"] == 0
    # sixteen table entries and one digest write a row, and the triples'
    # tier over the row tier: both at their floor of 2,048 at this size
    assert 17 < result["metrics"][metric]["value"] < 64


def test_without_the_counters_the_reader_returns_nothing():
    params = SPEC.metric_file("branch_index_elems_per_row")["params"]
    assert params == SPEC.metric_file("branch_index_elems_per_row.p4")["params"]
    facts = {"counters_before": {"fused_rows_dispatched_total": 1.0},
             "counters_after": {"fused_rows_dispatched_total": 9.0}}
    assert counter_over_counter.read(facts, params) is None
    facts["counters_after"].update(fused_branch_index_elems_total=2162688.0,
                                   fused_branch_rows_total=65536.0)
    assert counter_over_counter.read(facts, params) == 33.0
