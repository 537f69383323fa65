"""``rebuild.storage`` (configuration ``sync-rebuild-storage``): the storage
phase of the clean rebuild. The cell brings a generator, a driver and a
many-tries entry to the reference of its own; these hold each to what it is
meant to be: the generator's sizes at the published parameters, ``build_tries``
to ``build_trie`` job for job, the driver's work count to the reference's, and
``correct`` false for the control and for faults only this shape can have.
``test_rehearsal.py`` runs the cell too, by its parametrisation over
BENCHMARK.json."""

import numpy as np
import pytest

from benchmark import run as runmod
from benchmark.control import run_control
from benchmark.drivers import rebuild_storage
from benchmark.harness import spec as specmod
from benchmark.harness import traffic_storage as gen
from benchmark.harness.work import trie_work
from benchmark.reference.mpt import EMPTY_ROOT, build_trie
from benchmark.reference.mpt_many import build_tries

CELL = "rebuild.storage"
SPEC = specmod.Spec()
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _driver(rehearsal=True, seed=5):
    cell = SPEC.cell(CELL)
    return rebuild_storage.Driver(SPEC.config(cell["config"]),
                                  SPEC.workload_file(CELL), seed, rehearsal)


def _set_nibble(key, i, v):
    key[i // 2] = ((v << 4) | (key[i // 2] & 0x0F) if i % 2 == 0
                   else (key[i // 2] & 0xF0) | v)


def _embedded_job(share, seed):
    """A trie of three slots: two keys that share ``share`` nibbles, with
    one-byte values (leaves under 32 bytes inside their branch, below an
    extension), and a third under another first nibble."""
    rng = np.random.default_rng(seed)
    keys = np.repeat(rng.integers(0, 256, (1, 32), dtype=np.uint8), 3, axis=0)
    _set_nibble(keys[0], share, 1)
    _set_nibble(keys[1], share, 7)
    _set_nibble(keys[2], 0, (int(keys[0, 0]) >> 4) ^ 8)
    _set_nibble(keys[2], share, 12)      # distinct under a shared prefix too
    values = [b"\x01", b"\x7f", b"\x94" + bytes(range(20))]
    order = np.argsort(keys.view("S32").ravel())       # ascending, as a job is
    return keys[order], [values[i] for i in order]


# -- the data -----------------------------------------------------------------


def test_the_cell_is_the_storage_chunk_the_deployment_commits():
    cell = SPEC.cell(CELL)
    config, old = SPEC.config(cell["config"]), SPEC.config("sync-rebuild")
    workload = SPEC.workload_file(CELL)
    assert (cell["config"], cell["chips"]) == ("sync-rebuild-storage", 1)
    for key in ("turbo_backend", "hasher", "min_tier", "collect_branches",
                "key_bytes", "key_distribution", "chunk_leaves"):
        assert config[key] == old[key], key
    assert config["chunk_leaves"] == 500_000 and config["start_depth"] == 0
    assert config["reduced"] == {} and len(config["assumed"]) >= 5
    assert config["guarantees"] == {
        k: v.replace("every subtrie root", "every storage trie's root")
        for k, v in old["guarantees"].items()}
    assert config["storage_trie_size_law"] == {
        "form": "power", "alpha": 1.83, "max": 10_000_000}
    assert sum(config["storage_value_rlp_len_weights"].values()) == \
        pytest.approx(1.0)
    assert workload["driver"] == "rebuild_storage"
    assert workload["call"] == {"start_depth": 0, "collect_branches": True,
                                "traced_op": 1,
                                "rate_metric": "rebuild_hashes_per_s"}
    assert workload["traffic"]["distinct_ops"] == 4
    full, small = _driver(rehearsal=False).traffic, _driver().traffic
    assert full["jobs"]["chunk_leaves"] == 500_000
    assert full["jobs"]["size_law"] == config["storage_trie_size_law"]
    assert full["values"]["rlp_len_weights"] == \
        config["storage_value_rlp_len_weights"]
    assert (small["jobs"]["chunk_leaves"], small["jobs"]["size_law"]["max"],
            small["distinct_ops"]) == (2000, 400, 2)


def test_the_size_law_is_fitted_to_the_two_aggregates():
    """tries x mean = slots: the arithmetic the file shows under ``assumed``.
    The law keeps its tail (max is the largest trie, not a cut)."""
    config = SPEC.config("sync-rebuild-storage")
    law = config["storage_trie_size_law"]
    assert config["storage_tries_total"] * 10 == \
        SPEC.config("sync-rebuild")["accounts_total"]      # "a tenth"
    s = np.arange(1, law["max"] + 1, dtype=np.float64)
    w = s ** -law["alpha"]
    mean = float((w * s).sum() / w.sum())
    assert round(mean, 2) == 46.65
    assert mean * config["storage_tries_total"] == \
        pytest.approx(config["slots_total"], rel=0.001)
    # what the sampling leaves out: the slots in tries beyond the chunk's
    # largest, and in tries that are a chunk of their own
    mass = np.cumsum(w * s) / (w * s).sum()
    assert round(100 * (1 - mass[173_101 - 1]), 1) == 52.9
    assert round(100 * (1 - mass[500_000 - 1]), 1) == 42.4
    over = config["storage_tries_total"] * w[500_000:].sum() / w.sum()
    assert round(over) == 335


@pytest.mark.parametrize("alpha,tries,slots,largest", [
    (1.83, 17_606, 500_035, [173_101, 47_384, 25_752, 17_211, 12_732]),
    (1.80, 13_332, 500_018, [193_455]),        # the band the file states
    (1.85, 21_022, 500_020, [159_802]),
    (1.90, 31_760, 500_016, [128_168])])
def test_sizes_at_the_published_parameters(alpha, tries, slots, largest):
    """Sizes only, no keys: what the configuration file and PERF.md state."""
    config = SPEC.config("sync-rebuild-storage")
    law = dict(config["storage_trie_size_law"], alpha=alpha)
    sizes = gen.power_law_sizes(law, config["chunk_leaves"])
    assert (len(sizes), int(sizes.sum())) == (tries, slots)
    assert sizes[::-1][:len(largest)].tolist() == largest
    # the least count: one trie fewer does not reach the chunk
    assert gen.quantile_sizes(law, tries - 1).sum() < config["chunk_leaves"]
    if alpha == 1.83:
        assert round(100 * float((sizes == 1).mean()), 2) == 54.38
        assert round(100 * float((sizes <= 3).mean()), 2) == 76.96
        traffic = _driver(rehearsal=False).traffic
        orders = [gen.chunk_sizes(traffic, o) for o in range(4)]
        assert all(sorted(o.tolist()) == sizes.tolist() for o in orders)
        assert not any((orders[0] == o).all() for o in orders[1:])
        # no size follows the order: the largest trie is not at an end
        assert 0 < int(orders[0].argmax()) < tries - 1


def test_two_seeds_give_equal_sizes_and_order_and_other_keys_and_values():
    traffic = _driver().traffic
    a, b = (gen.storage_chunk_ops(traffic, s) for s in (4294967311, 17))
    again = gen.storage_chunk_ops(traffic, 17)
    assert len(a) == len(b) == 2
    for op, (x, y, z) in enumerate(zip(a, b, again)):
        sizes = [len(v) for _, v in x]
        assert sizes == [len(v) for _, v in y] == \
            gen.chunk_sizes(traffic, op).tolist()
        assert sum(sizes) >= 2000 and max(sizes) <= 400
        assert not any((kx == ky).all() for (kx, _), (ky, _) in zip(x, y))
        assert [v for _, v in x] != [v for _, v in y]
        assert all((ky == kz).all() and vy == vz
                   for (ky, vy), (kz, vz) in zip(y, z))    # a seed repeats
        for keys, values in y:
            flat = keys.view("S32").ravel()
            assert (flat[1:] > flat[:-1]).all()            # ascending, distinct
            for v in values:                               # canonical RLP
                assert (0 < v[0] < 0x80 if len(v) == 1 else
                        v[0] == 0x80 + len(v) - 1 and v[1] != 0
                        and (len(v) > 2 or v[1] >= 0x80))
    assert {len(v) for _, vals in b[0] for v in vals} == {1, 3, 9, 21, 33}


# -- the reference's many-tries entry -----------------------------------------


def test_build_tries_equals_build_trie_job_for_job():
    rng = np.random.default_rng(9)
    jobs = gen.storage_chunk_ops(_driver().traffic, 23)[0][:200]
    jobs += [_embedded_job(share, share) for share in (9, 12, 30, 60, 63)]
    jobs += [(np.zeros((0, 32), dtype=np.uint8), []),                # empty
             (rng.integers(0, 256, (1, 32), dtype=np.uint8), [b"\x01"]),
             (rng.integers(0, 256, (2, 32), dtype=np.uint8)[::-1],   # any order
              [b"\x02", b"\xa0" + bytes(32)])]
    order = rng.permutation(len(jobs))
    jobs = [jobs[i] for i in order]
    for start_depth, batch in ((0, jobs), (2, [_prefixed(j) for j in jobs])):
        many = build_tries(batch, start_depth)
        one = [build_trie(k, v, start_depth) for k, v in batch]
        assert many == one             # root, branches, n_hashes, n_blocks
    assert sum(r.root == EMPTY_ROOT for r in many) == 1
    # the list holds what the storage shape has and the account cells lack:
    # embedded leaves (fewer hashes than nodes) and inline branch children
    assert any(hm != sm for r in many for sm, _, hm, _ in r.branches.values())
    assert build_tries([], 0) == []


def _prefixed(job):
    keys = np.array(job[0], dtype=np.uint8).reshape(-1, 32)
    keys[:, 0] = 0x5A
    return keys, job[1]


# -- the driver's work count --------------------------------------------------


def test_the_work_count_is_the_references_where_trie_work_cannot_count():
    driver = _driver()
    plain = gen.storage_chunk_ops(driver.traffic, 31)[0]
    driver.ops = [plain, plain + [_embedded_job(10, 1)]]
    driver.start_depth = 0
    refs = [build_tries(op, 0) for op in driver.ops]
    want = [(sum(r.n_hashes for r in ref), sum(r.n_blocks for r in ref))
            for ref in refs]
    assert trie_work(driver.ops[0], 0) == want[0]    # the two counts agree
    with pytest.raises(NotImplementedError):
        trie_work(driver.ops[1], 0)
    assert [driver._work(0), driver._work(1)] == want
    assert want[1][0] == want[0][0] + 4              # two leaves embedded


def _with_an_embedded_leaf(monkeypatch):
    real = gen.storage_chunk_ops

    def ops(traffic, seed):
        return [op + [_embedded_job(9 + i, seed + i)]
                for i, op in enumerate(real(traffic, seed))]

    monkeypatch.setattr(rebuild_storage.gen, "storage_chunk_ops", ops)


@pytest.mark.parametrize("trace", [False, True])
def test_a_chunk_with_an_embedded_leaf_is_correct_with_no_gap(monkeypatch, trace):
    _with_an_embedded_leaf(monkeypatch)
    held = {}
    result, _ = runmod.measure(SPEC, SPEC.cell(CELL), 41, 0.5, trace, DEVICE,
                               True, driver_hook=lambda d: held.update(d=d))
    with pytest.raises(NotImplementedError):
        trie_work(held["d"].ops[0], 0)
    assert result["correct"] and result["failed"] == 0, result["checks"]
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert result["compiled_in_window"] == 0
    if not trace:
        return
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # the rehearsal's chunk: ~510 tries in 8 groups of 64 jobs, one window
    assert got["pipeline_groups_per_op.storage"] == 8
    assert got["pipeline_windows_per_op.storage"] == 1
    assert 500 < got["pipeline_tries_per_window.storage"] < 520
    assert got["program_shapes_first_seen_per_op.storage"] == 0
    assert got["turbo_collect_s_per_mhash.storage"] > 0
    listed = {m["name"] for m in SPEC.metrics("per_layer", CELL)}
    # what only a device trace gives is left out off the chip, not made up
    assert listed - set(got) == {"device_idle_pct.storage",
                                 "keccak_roofline.storage",
                                 "peak_hbm_mb.storage"}
    for name in listed:
        assert name.endswith(".storage") and SPEC.metric_file(name)


# -- correct comes out false when it should -----------------------------------


def test_program_is_correct_at_the_rehearsal_size():
    result, _ = runmod.measure(SPEC, SPEC.cell(CELL), 11, 0.5, False, DEVICE,
                               True)
    assert result["correct"] and result["attempted"] >= 1
    assert all(c["value"] == 0 for c in result["checks"].values())


@pytest.mark.parametrize("broken,fails", [
    ("lost_leaf", "root_mismatches"), ("no_tree_mask", "branch_node_mismatches")])
def test_control_is_not_correct(broken, fails):
    res = run_control(CELL, 4294967401, broken, 0.5, rehearsal=True)
    assert not res["correct"]
    assert res["checks"][fails]["value"] > 0


def test_reference_in_the_programs_place_is_correct():
    assert run_control(CELL, 24, "none", 0.5, rehearsal=True)["correct"]


def _answers_of_the_neighbour(driver, monkeypatch):
    """Every trie answered with its neighbour's result: the stage would write
    each storage root under the wrong address. The hashed-node count is
    right; only the job-for-job comparison sees it."""
    make = driver.make_committer

    class Shifted:
        def __init__(self):
            self.inner = make()

        def commit_hashed_pipelined(self, jobs, **kw):
            res = self.inner.commit_hashed_pipelined(jobs, **kw)
            res[-1].hashed_nodes, total = 0, res[-1].hashed_nodes
            res = res[1:] + res[:1]
            res[-1].hashed_nodes = total
            return res

    driver.make_committer = Shifted


def _a_lost_worker(driver, monkeypatch):
    """The reference's worker for one chunk is lost: its jobs are unchecked,
    which is not correct however right the answers."""
    real = rebuild_storage._reference_answers

    def lost(todo, ops, start_depth):
        out = real(todo, ops, start_depth)
        out[todo[0]] = None
        return out

    monkeypatch.setattr(rebuild_storage, "_reference_answers", lost)


@pytest.mark.parametrize("fault,fails", [
    (_answers_of_the_neighbour, "root_mismatches"),
    (_a_lost_worker, "jobs_unchecked")])
def test_fault_under_the_timed_path_is_not_correct(monkeypatch, fault, fails):
    result, _ = runmod.measure(
        SPEC, SPEC.cell(CELL), 31, 0.5, False, DEVICE, True,
        driver_hook=lambda driver: fault(driver, monkeypatch))
    assert not result["correct"], result["checks"]
    assert result["checks"][fails]["value"] > 0


def test_a_group_decoded_with_the_wrong_slot_base_is_not_correct(monkeypatch):
    """The roots come out right and the branch nodes carry their neighbours'
    hashes: only the comparison of every stored branch node sees it."""
    from reth_tpu.trie import turbo

    real = turbo._collect_meta_records

    def off_by_one(*args, slot_base=0):
        return real(*args, slot_base=slot_base + 1)

    monkeypatch.setattr(turbo, "_collect_meta_records", off_by_one)
    result, _ = runmod.measure(SPEC, SPEC.cell(CELL), 43, 0.5, False, DEVICE,
                               True)
    assert not result["correct"]
    assert result["checks"]["root_mismatches"]["value"] == 0
    assert result["checks"]["branch_node_mismatches"]["value"] > 0
