"""``rebuild.storage.big`` (configuration ``sync-rebuild-storage-big``): the
storage chunk that a trie larger than ``chunk_leaves`` closes. The cell brings
a generator and a driver of its own over ``rebuild.storage``'s; these hold the
generator's sizes to the law (re-derived here, not read from the file), the two
configuration files to each other, and ``correct`` to false for the control and
for faults only this shape can have. ``test_rehearsal.py`` runs the cell too, by
its parametrisation over BENCHMARK.json."""

import numpy as np
import pytest

from benchmark import run as runmod
from benchmark.control import run_control
from benchmark.drivers import rebuild_storage, rebuild_storage_big
from benchmark.harness import spec as specmod
from benchmark.harness import traffic_storage as small_gen
from benchmark.harness import traffic_storage_big as gen

CELL = "rebuild.storage.big"
SPEC = specmod.Spec()
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
OWN_KEYS = {"name", "source", "deployment", "big_trie", "fill_before",
            "reduced", "assumed"}


def _driver(rehearsal=True, seed=5):
    cell = SPEC.cell(CELL)
    return rebuild_storage_big.Driver(SPEC.config(cell["config"]),
                                      SPEC.workload_file(CELL), seed, rehearsal)


# -- the data -----------------------------------------------------------------


def test_the_two_storage_configurations_are_equal_on_every_shared_key():
    """The law, ``chunk_leaves``, ``start_depth``, the value widths, the two
    aggregates, the three guarantees, the backend and the chips: one
    deployment, seen at another moment of the same phase."""
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sync-rebuild-storage-big", "storage-big", 1)
    big, small = SPEC.config(cell["config"]), SPEC.config("sync-rebuild-storage")
    assert set(small) - set(big) == set()
    assert set(big) - set(small) == {"big_trie", "fill_before"}
    for key in set(small) - OWN_KEYS:
        assert big[key] == small[key], key
    assert big["guarantees"] == small["guarantees"]
    assert big["source"] != small["source"] and len(big["source"]) <= 200
    assert big["big_trie"] == {"rule": "slot_weighted_median_over_chunk_leaves"}
    assert big["fill_before"] == {"rule": "half_chunk"}
    assert list(big["reduced"]) == ["big_trie"] and len(big["assumed"]) == 4
    entry = next(c for c in SPEC.bench["configs"] if c["name"] == big["name"])
    assert entry["reduced"] == ["big_trie"] and entry["source"] == big["source"]
    workload = SPEC.workload_file(CELL)
    assert workload["driver"] == "rebuild_storage_big"
    assert workload["call"] == SPEC.workload_file("rebuild.storage")["call"]
    assert workload["traffic"]["distinct_ops"] == 2
    full, small_t = _driver(rehearsal=False).traffic, _driver().traffic
    assert full["jobs"]["chunk_leaves"] == 500_000
    assert full["jobs"]["size_law"] == big["storage_trie_size_law"]
    assert full["jobs"]["big_trie"] == big["big_trie"]
    assert full["values"]["rlp_len_weights"] == \
        big["storage_value_rlp_len_weights"]
    assert (small_t["jobs"]["chunk_leaves"], small_t["jobs"]["big_trie"],
            small_t["jobs"]["fill_before"], small_t["distinct_ops"]) == (
        2000, {"slots": 6000}, {"rule": "half_chunk"}, 2)


def test_the_chunks_sizes_follow_from_the_law():
    """Re-derived from the law's weights, not through the generator's
    ``_cdf``: the slot-weighted median of the tries over ``chunk_leaves``,
    the other quantiles PERF.md states, and the half chunk before it."""
    config = SPEC.config("sync-rebuild-storage-big")
    law, chunk = config["storage_trie_size_law"], config["chunk_leaves"]
    s = np.arange(1, law["max"] + 1, dtype=np.float64)
    w = s ** -law["alpha"]
    slots = np.cumsum((w * s)[chunk:])              # tries of chunk + 1 ..
    want = {q: int(chunk + 1 + np.searchsorted(slots, q * slots[-1]))
            for q in (1 / 8, 1 / 4, 1 / 2, 3 / 4, 7 / 8)}
    assert want == {1 / 8: 799_267, 1 / 4: 1_234_129, 1 / 2: 2_700_399,
                    3 / 4: 5_388_889, 7 / 8: 7_400_746}
    # the generator reads the same sizes off ``_cdf``'s normalised sums: the
    # median to the digit, a far quantile within one slot of rounding
    assert gen.slot_quantile_over(law, chunk) == want[1 / 2]
    for q, size in want.items():
        assert abs(gen.slot_quantile_over(law, chunk, q) - size) <= 1
    tries_over = config["storage_tries_total"] * w[chunk:].sum() / w.sum()
    assert round(tries_over, 1) == 335.4
    assert round(100 * slots[-1] / (w * s).sum(), 2) == 42.38
    assert round(slots[-1] / w[chunk:].sum()) == 1_768_274  # their mean size
    count = np.cumsum(w[chunk:])
    assert chunk + 1 + np.searchsorted(count, count[-1] / 2) == 1_046_737

    traffic = _driver(rehearsal=False).traffic
    shape = traffic["jobs"]
    assert gen.big_trie_slots(shape) == 2_700_399
    fill = gen.fill_sizes(shape)
    assert (len(fill), int(fill.sum()), int(fill.max())) == (
        9_947, 250_023, 88_604)
    assert round(100 * float((fill == 1).mean()), 1) == 54.4
    # the least count that reaches half a chunk
    assert small_gen.quantile_sizes(law, 9_946).sum() < chunk // 2
    orders = [gen.chunk_sizes(traffic, o) for o in range(2)]
    for sizes in orders:
        assert (len(sizes), int(sizes.sum())) == (9_948, 2_950_422)
        assert sizes[-1] == 2_700_399               # the big trie comes LAST
        assert sorted(sizes[:-1].tolist()) == fill.tolist()
    assert not (orders[0] == orders[1]).all()
    assert round(100 * 2_700_399 / 2_950_422, 1) == 91.5


def test_a_law_with_no_trie_over_the_chunk_needs_a_stated_size():
    shape = dict(_driver().traffic["jobs"])
    assert gen.big_trie_slots(shape) == 6000
    shape["big_trie"] = {"rule": "slot_weighted_median_over_chunk_leaves"}
    with pytest.raises(ValueError, match="no trie over"):
        gen.big_trie_slots(shape)
    with pytest.raises(ValueError, match="unknown big-trie rule"):
        gen.big_trie_slots(dict(shape, big_trie={"rule": "largest"}))
    with pytest.raises(ValueError, match="unknown fill rule"):
        gen.fill_sizes(dict(shape, fill_before={"rule": "empty"}))


def test_two_seeds_give_equal_sizes_and_order_and_other_keys_and_values():
    traffic = _driver().traffic
    a, b = (gen.big_chunk_ops(traffic, s) for s in (4294967311, 17))
    again = gen.big_chunk_ops(traffic, 17)
    assert len(a) == len(b) == 2
    for op, (x, y, z) in enumerate(zip(a, b, again)):
        sizes = [len(v) for _, v in x]
        assert sizes == [len(v) for _, v in y] == \
            gen.chunk_sizes(traffic, op).tolist()
        assert sizes[-1] == 6000 and 1000 <= sum(sizes[:-1]) < 1400
        assert not any((kx == ky).all() for (kx, _), (ky, _) in zip(x, y))
        assert [v for _, v in x] != [v for _, v in y]
        assert all((ky == kz).all() and vy == vz
                   for (ky, vy), (kz, vz) in zip(y, z))    # a seed repeats
        for keys, _ in y:
            flat = keys.view("S32").ravel()
            assert (flat[1:] > flat[:-1]).all()            # ascending, distinct
    assert {len(v) for v in b[0][-1][1]} == {1, 3, 9, 21, 33}


# -- correct comes out true, and false when it should -------------------------


@pytest.mark.parametrize("trace", [False, True])
def test_program_is_correct_at_the_rehearsal_size(trace):
    result, _ = runmod.measure(SPEC, SPEC.cell(CELL), 11, 0.5, trace, DEVICE,
                               True)
    assert result["correct"] and result["attempted"] >= 1
    assert result["failed"] == 0 and result["compiled_in_window"] == 0
    assert all(c["value"] == 0 for c in result["checks"].values())
    if not trace:
        return
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # the rehearsal's chunk is under LEAVES_PER_SWEEP: one group, swept by
    # the caller, so the largest group is the whole chunk
    assert got["pipeline_groups_per_op.big"] == 1
    assert got["pipeline_windows_per_op.big"] == 1
    assert got["pipeline_largest_group_leaf_pct.big"] == 100.0
    assert 250 < got["pipeline_tries_per_window.big"] < 270
    assert got["program_shapes_first_seen_per_op.big"] == 0
    listed = {m["name"] for m in SPEC.metrics("per_layer", CELL)}
    assert len(listed) == 25
    # what only a device trace gives is left out off the chip, not made up
    assert listed - set(got) == {"device_idle_pct.big", "keccak_roofline.big",
                                 "peak_hbm_mb.big"}
    for name in listed:
        assert name.endswith(".big") and SPEC.metric_file(name)
        entry = next(m for m in SPEC.bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "rebuild_hashes_per_s"
        twin = name[:-len(".big")] + ".storage"
        if name != "pipeline_largest_group_leaf_pct.big":
            mine, theirs = SPEC.metric_file(name), SPEC.metric_file(twin)
            assert (mine["reader"], mine["params"]) == (
                theirs["reader"], theirs["params"])


@pytest.mark.parametrize("broken,fails", [
    ("lost_leaf", "root_mismatches"), ("no_tree_mask", "branch_node_mismatches")])
def test_control_is_not_correct(broken, fails):
    res = run_control(CELL, 4294967401, broken, 0.5, rehearsal=True)
    assert not res["correct"]
    assert res["checks"][fails]["value"] > 0


def test_reference_in_the_programs_place_is_correct():
    assert run_control(CELL, 24, "none", 0.5, rehearsal=True)["correct"]


def _wrapped(driver, answer):
    """The committer with ``answer(results)`` applied to what it returns."""
    make = driver.make_committer

    class Wrapped:
        def __init__(self):
            self.inner = make()

        def commit_hashed_pipelined(self, jobs, **kw):
            res = self.inner.commit_hashed_pipelined(jobs, **kw)
            answer(res)
            return res

    driver.make_committer = Wrapped


def _the_big_trie_answered_with_a_small_ones_root(driver, monkeypatch):
    """The last job's root is its neighbour's: the stage would write a small
    contract's storage root under the big contract's address. One job of
    258, the hashed-node count right."""
    def answer(res):
        res[-1].root = res[-2].root

    _wrapped(driver, answer)


def _one_branch_node_of_the_big_trie_dropped(driver, monkeypatch):
    """One of the big trie's stored branch nodes is not returned: the next
    incremental walk would find no record there."""
    def answer(res):
        nodes = res[-1].branch_nodes
        del nodes[max(nodes, key=len)]

    _wrapped(driver, answer)


def _a_lost_worker(driver, monkeypatch):
    """The reference's worker for one chunk is lost: its jobs are unchecked,
    which is not correct however right the answers."""
    real = rebuild_storage._reference_answers

    def lost(todo, ops, start_depth):
        out = real(todo, ops, start_depth)
        out[todo[0]] = None
        return out

    monkeypatch.setattr(rebuild_storage, "_reference_answers", lost)


@pytest.mark.parametrize("fault,fails,exactly", [
    (_the_big_trie_answered_with_a_small_ones_root, "root_mismatches", True),
    (_one_branch_node_of_the_big_trie_dropped, "branch_node_mismatches", True),
    (_a_lost_worker, "jobs_unchecked", False)])
def test_fault_under_the_timed_path_is_not_correct(monkeypatch, fault, fails,
                                                   exactly):
    result, _ = runmod.measure(
        SPEC, SPEC.cell(CELL), 31, 0.5, False, DEVICE, True,
        driver_hook=lambda driver: fault(driver, monkeypatch))
    assert not result["correct"], result["checks"]
    assert result["checks"][fails]["value"] > 0
    if exactly:     # one answer of one job an operation, and nothing else
        assert result["checks"][fails]["value"] == result["attempted"]
        others = {k: v["value"] for k, v in result["checks"].items()
                  if k != fails}
        assert not any(others.values()), others
