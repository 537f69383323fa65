"""``rebuild.storage.db`` (configuration ``sync-rebuild-storage-db``): the
storage phase of ``stage run merkle`` on its own paged datadir, one
``Pipeline.step`` an operation. These hold the cell to what it is meant to
be: the configuration's shape to ``sync-rebuild-storage``'s, the generator's
entries to the values the reference hashes, the driver's chunk boundaries to
the stage's progress blobs, and ``correct`` true for the rehearsal and false
for the control and for three faults planted in the stage's writes.
``test_rehearsal.py`` runs the cell too, by its parametrisation over
BENCHMARK.json."""

import numpy as np
import pytest

from benchmark import run as runmod
from benchmark.drivers import rebuild_storage_db as drv
from benchmark.harness import spec as specmod
from benchmark.harness import traffic_storage_db as gen
from benchmark.reference import db_entries

CELL = "rebuild.storage.db"
SPEC = specmod.Spec()
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
SHAPE_KEYS = ("chips", "turbo_backend", "hasher", "min_tier", "pipeline_knobs",
              "collect_branches", "key_bytes", "key_distribution",
              "chunk_leaves", "start_depth", "slots_total",
              "storage_tries_total", "storage_trie_size_law",
              "storage_value_rlp_len_weights", "storage_value_kinds")


def _driver(rehearsal=True, seed=6):
    cell = SPEC.cell(CELL)
    return drv.Driver(SPEC.config(cell["config"]), SPEC.workload_file(CELL),
                      seed, rehearsal)


def _measure(hook=None, seed=4_294_967_311, seconds=1.0):
    result, lines = runmod.measure(SPEC, SPEC.cell(CELL), seed, seconds, False,
                                   DEVICE, True, driver_hook=hook)
    return result, lines


# -- the configuration and the data -------------------------------------------


def test_the_configuration_is_sync_rebuild_storage_on_a_datadir():
    cell = SPEC.cell(CELL)
    config, old = SPEC.config(cell["config"]), SPEC.config("sync-rebuild-storage")
    assert (cell["config"], cell["chips"]) == ("sync-rebuild-storage-db", 1)
    for key in SHAPE_KEYS:
        assert config[key] == old[key], key
    assert "sync-rebuild-storage assumes" in config["assumed"][0]
    assert {k: config["guarantees"][k] for k in old["guarantees"]} == \
        old["guarantees"]
    assert set(config["guarantees"]) - set(old["guarantees"]) == {"durable"}
    assert list(config["reduced"]) == ["datadir_scale"]
    (entry,) = [c for c in SPEC.bench["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == ["datadir_scale"]
    full = _driver(rehearsal=False).traffic
    assert full["datadir"] == {"blocks": 20}
    assert full["jobs"]["chunk_leaves"] == 500_000
    assert full["jobs"]["size_law"] == old["storage_trie_size_law"]


def test_a_block_is_the_storage_chunk_of_sync_rebuild_storage():
    """Each block holds the chunk's quantile sizes in the order the storage
    cell's generator gives its operation of that number."""
    from benchmark.harness import traffic_storage

    plan = gen.Plan(_driver().traffic, 6)
    storage = _driver().traffic | {"kind": "storage_chunks", "distinct_ops": 3}
    for b in range(3):
        assert (plan.sizes[b * plan.per_block:(b + 1) * plan.per_block]
                == traffic_storage.chunk_sizes(storage, b)).all()
    assert plan.addrs.shape == (plan.n_tries, 32)
    assert sorted(map(bytes, plan.addrs)) == list(map(bytes, plan.addrs))


def test_the_entries_hold_the_values_the_reference_hashes():
    """A stored value's ``rlp_encode(encode_int(value))`` (what the stage
    hashes) is the RLP the reference gets, every width 1 .. 33 included."""
    from reth_tpu.primitives.rlp import encode_int, rlp_encode

    plan = gen.Plan(_driver().traffic, 7)
    keys, dups = plan.entries(1)
    jobs = plan.jobs(plan.per_block, 2 * plan.per_block)
    want = [(s.tobytes(), v) for slots, vals in jobs for s, v in zip(slots, vals)]
    got = [(d[:32].tobytes(), rlp_encode(encode_int(int.from_bytes(
        d[32:].tobytes(), "big")))) for d in dups]
    assert got == want
    assert {len(v) for _, v in want} == {1, 3, 9, 21, 33}
    assert (keys == np.repeat(plan.addrs[plan.per_block:2 * plan.per_block],
                              plan.sizes[plan.per_block:2 * plan.per_block],
                              axis=0)).all()


def test_account_rows_are_the_programs_encoding():
    from reth_tpu.primitives.types import EMPTY_ROOT_HASH
    from reth_tpu.storage import tables as T

    plan = gen.Plan(_driver().traffic, 8)
    for row in gen.account_rows(plan)[:50]:
        acct = T.decode_account(row.tobytes())
        assert acct.storage_root == EMPTY_ROOT_HASH and acct.nonce == 1
        assert T.encode_account(acct) == row.tobytes()
        assert db_entries.account_storage_root(row.tobytes()) == EMPTY_ROOT_HASH


def test_the_decoder_reads_what_the_program_writes():
    from reth_tpu.storage import tables as T
    from reth_tpu.trie.committer import BranchNode

    rng = np.random.default_rng(9)
    for n in (0, 1, 5, 16):
        node = BranchNode(0xFFFF, int(rng.integers(0, 1 << 16)),
                          (1 << n) - 1, tuple(rng.integers(0, 256, 32, np.uint8)
                                              .tobytes() for _ in range(n)))
        path = bytes(rng.integers(0, 16, n % 7, np.uint8))
        dup = T.encode_storage_trie_entry(path, node)
        assert db_entries.storage_trie_entry(dup) == (
            path, (node.state_mask, node.tree_mask, node.hash_mask,
                   tuple(node.hashes)))


# -- the driver ---------------------------------------------------------------


def test_the_chunk_boundaries_are_the_stages_progress_blobs():
    from reth_tpu.storage.tables import Tables

    d = _driver()
    d.setup()
    blobs = []
    for _ in range(len(d.plan.chunks) - d.next_chunk):
        d._step()
        with d.db.tx() as tx:
            blobs.append(tx.get(Tables.StageCheckpointProgresses.name,
                                drv.STAGE.encode()))
    d.release()
    drv._remove(d.dir)
    tb = b"S" + d.target.to_bytes(8, "big")
    warm = int(d.call["warmup_ops"])
    assert blobs == [tb + d.plan.addr(end - 1) for _, end in d.plan.chunks[warm:]]
    # chunks end inside blocks, not at their ends
    ends = {end for _, end in d.plan.chunks}
    assert any(e % d.plan.per_block for e in ends)
    # every chunk holds chunk_leaves slots or more but the phase's tail
    leaves = [int(d.plan.starts[e] - d.plan.starts[f]) for f, e in d.plan.chunks]
    assert all(n >= d.plan.chunk_leaves for n in leaves[:-1])
    assert d.plan.full_chunks == len(leaves) - (leaves[-1] < d.plan.chunk_leaves)


def test_the_rehearsal_is_correct():
    result, lines = _measure()
    assert result["correct"], lines
    assert result["checks"]["progress_mismatch"]["value"] == 0
    assert result["attempted"] >= 1 and result["failed"] == 0


def test_a_window_longer_than_the_datadir_fails_the_run():
    with pytest.raises(RuntimeError, match="ran out"):
        _measure(seconds=600.0)


def test_the_control_is_not_correct():
    def hook(d):
        d.commit_override = drv.ReferenceCommit()

    result, _ = _measure(hook)
    assert not result["correct"]
    assert result["checks"]["root_mismatches"]["value"] > 0


def _in_the_window(driver, patch):
    """Run ``patch`` (a context manager factory) around the window only."""
    window = driver.window

    def patched(seconds, tracer):
        with patch(driver):
            window(seconds, tracer)

    driver.window = patched


def _dropped_node(driver):
    from reth_tpu.storage.provider import DatabaseProvider

    real, seen = DatabaseProvider.put_storage_branch, []

    def put(self, addr, path, node):
        seen.append(addr)
        if len(seen) != 5:
            real(self, addr, path, node)

    return pytest.MonkeyPatch.context(), ("put_storage_branch", put)


def _stale_root(driver):
    from reth_tpu.storage.provider import DatabaseProvider

    real, seen = DatabaseProvider.put_hashed_account, []

    def put(self, addr, account, preserve_storage_root=True):
        seen.append(addr)
        if len(seen) != 3:
            real(self, addr, account, preserve_storage_root)

    return pytest.MonkeyPatch.context(), ("put_hashed_account", put)


def _neighbours_address(driver):
    from reth_tpu.storage.provider import DatabaseProvider

    real, moved = DatabaseProvider.put_storage_branch, []
    first, end = driver.plan.chunks[driver.next_chunk]
    big = max(range(first, end - 1), key=lambda t: driver.plan.sizes[t])
    victim, neighbour = driver.plan.addr(big), driver.plan.addr(big + 1)

    def put(self, addr, path, node):
        if addr == victim:
            moved.append(path)
            addr = neighbour
        real(self, addr, path, node)

    return pytest.MonkeyPatch.context(), ("put_storage_branch", put)


@pytest.mark.parametrize("fault,check", [
    (_dropped_node, "branch_node_mismatches"),
    (_stale_root, "root_mismatches"),
    (_neighbours_address, "branch_node_mismatches"),
])
def test_a_planted_fault_in_the_stages_writes_is_not_correct(fault, check):
    import contextlib

    from reth_tpu.storage.provider import DatabaseProvider

    @contextlib.contextmanager
    def patch(driver):
        ctx, (name, fn) = fault(driver)
        with ctx as mp:
            mp.setattr(DatabaseProvider, name, fn)
            yield

    result, _ = _measure(lambda d: _in_the_window(d, patch))
    assert not result["correct"]
    assert result["checks"][check]["value"] > 0
