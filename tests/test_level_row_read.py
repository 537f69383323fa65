"""The packed level programs' row read (ISSUE 30): ``_level_rows`` reads a
level's tightly staged rows from the level's own extent of the staging
buffer, shared by ``_staged_packed`` and ``_subtrie_program``. Held here:
the helper against a numpy loop wherever the level lies in the buffer;
whole commits through both engines against the numpy twin with levels
split and extents clamped; the programs' signatures (no shape key moved);
the two counters that say what the read addresses."""

import jax.numpy as jnp
import numpy as np
import pytest

from reth_tpu.metrics import REGISTRY
from reth_tpu.ops import fused_commit as fc
from reth_tpu.primitives.keccak import RATE
from reth_tpu.trie.turbo import TurboCommitter

N_POW = 64
GATHER = ["fused_gather_operand_bytes_total", "fused_gather_rows_total"]


def _numpy_rows(u8, flat_off, row_len, L):
    out = np.zeros((len(row_len), L), dtype=np.uint8)
    off = flat_off
    for i, n in enumerate(int(x) for x in row_len):
        out[i, :n] = u8[off:off + n]
        off += n
    return out


def _level(case, L, rng):
    """(u8_len, flat_off, row_len of N_POW rows) for one placement of a
    level in the staging buffer; rows past the real ones have length 0, as
    the programs make them."""
    row_len = np.zeros(N_POW, dtype=np.int64)
    n_real = {"one_row": 1}.get(case, N_POW - 1)
    row_len[:n_real] = rng.integers(1, L, n_real)
    if case == "len_0_and_L-1":
        row_len[:n_real:3], row_len[1:n_real:3] = 0, L - 1
    total = int(row_len.sum())
    if case == "tiny_buffer":        # u8_len < n_pow * L: the extent is the buffer
        u8_len = total + 40
        flat_off = 17
    else:
        u8_len = 4 * N_POW * L
        flat_off = {"offset_0": 0, "last": u8_len - total}.get(case, u8_len // 3 + 1)
    return u8_len, flat_off, row_len


CASES = ["offset_0", "middle", "last", "tiny_buffer", "one_row", "len_0_and_L-1"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("b_tier", [1, 2, 4])
def test_level_rows_equal_a_numpy_loop_over_the_staged_level(b_tier, case):
    L = b_tier * RATE
    rng = np.random.default_rng(b_tier * 100 + CASES.index(case))
    u8_len, flat_off, row_len = _level(case, L, rng)
    u8 = rng.integers(1, 256, u8_len, dtype=np.uint8)   # no zero byte: a
    ext = fc._level_extent(N_POW, L, u8_len)             # masked read shows
    if case == "last":
        assert flat_off + N_POW * L > u8_len > ext       # the start is clamped
    if case == "tiny_buffer":
        assert ext == u8_len < N_POW * L
    row_off = np.cumsum(row_len) - row_len
    got = fc._level_rows(jnp.asarray(u8), jnp.int32(flat_off),
                         jnp.asarray(row_off, dtype=jnp.int32),
                         jnp.asarray(row_len, dtype=jnp.uint32), L)
    assert got.dtype == jnp.uint8 and got.shape == (N_POW, L)
    np.testing.assert_array_equal(np.asarray(got),
                                  _numpy_rows(u8, flat_off, row_len, L))


# -- whole commits -------------------------------------------------------------


def _job(n, seed, prefix=None, val_len=None):
    """Account-like values of 70-78 bytes, or of lengths drawn from
    ``val_len`` (rows of several block tiers)."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    if prefix is not None:
        keys[:, 0] = prefix
    lens = (70 + np.arange(n) % 9 if val_len is None
            else rng.integers(*val_len, size=n))
    vals = [bytes(rng.integers(1, 256, int(m), dtype=np.uint8)) for m in lens]
    return keys, vals


TRIES = {
    "one_deep_subtrie": ([(2500, 3, 0x5A)], 2),
    "three_tries": ([(900, 4), (1400, 5), (7, 6)], 0),
    "long_values": ([(1200, 7, None, (1, 400))], 0),
}


@pytest.fixture
def small_tiers(monkeypatch):
    """Row tiers and the row cap brought down to the test's size: every wide
    level splits (``_row_cap`` 200) and a level's extent (256 rows) is shorter
    than the staging buffer, so starts are clamped at the buffer's end."""
    monkeypatch.setattr(fc.FusedLevelEngine, "_row_cap", lambda self: 200)
    monkeypatch.setattr(fc.MegaFusedEngine, "_ROW_FLOOR", 256)
    return _dispatched_keys(monkeypatch)


def _dispatched_keys(monkeypatch):
    """Every dispatch's compile-tracker key, ``(kind, *shape)``, in order."""
    seen = []
    orig = fc._timed_call

    def spy(kind, shape, fn, *args):
        seen.append((kind, *shape))
        return orig(kind, shape, fn, *args)

    monkeypatch.setattr(fc, "_timed_call", spy)
    return seen


@pytest.mark.parametrize("k", [0, 4])
@pytest.mark.parametrize("name", sorted(TRIES))
def test_split_levels_hash_bit_equal_to_the_numpy_twin(small_tiers, name, k):
    specs, start_depth = TRIES[name]
    jobs = [_job(*s) for s in specs]
    want = TurboCommitter(backend="numpy").commit_hashed_many(
        jobs, collect_branches=True, start_depth=start_depth)
    dev = TurboCommitter(backend="device", min_tier=8, subtrie_levels=k)
    before = REGISTRY.counter("fused_dispatches_total").value
    got = dev.commit_hashed_many(jobs, collect_branches=True,
                                 start_depth=start_depth)
    assert [r.root for r in got] == [r.root for r in want]
    # every stored branch node's child hashes: all the digests but the root's
    assert [r.branch_nodes for r in got] == [r.branch_nodes for r in want]
    assert [r.hashed_nodes for r in got] == [r.hashed_nodes for r in want]
    if k == 0:  # a level of 900+ rows went out in pieces of at most 199
        assert REGISTRY.counter("fused_dispatches_total").value - before > 10
    # a packed level's extent (row tier x L) was shorter than the buffer:
    # key = (kind, b_tier, n_pow, h_pow, [steps,] u8_len, ...)
    packed = [key for key in small_tiers if key[0] != "mega.branch"]
    assert any(key[2] * key[1] * RATE < key[-3 if k == 0 else -4]
               for key in packed)


# -- signatures ----------------------------------------------------------------

# the shape keys the PARENT of ISSUE 30 (bfb47b2) gives the compile tracker for
# this chunk, in dispatch order: the row read changed under them, they did not
MEGA_KEYS = [
    ("mega.packed", 1, 2048, 2048, 786432, 28672, 16384),
    ("mega.packed", 1, 2048, 2048, 786432, 28672, 16384),
    ("mega.branch", 2048, 2048, 786432, 28672, 16384),
    ("mega.packed", 1, 8192, 2048, 786432, 28672, 16384),
    ("mega.branch", 2048, 2048, 786432, 28672, 16384),
    ("mega.packed", 1, 2048, 2048, 786432, 28672, 16384),
    ("mega.branch", 2048, 8192, 786432, 28672, 16384),
    ("mega.branch", 2048, 4096, 786432, 28672, 16384),
    ("mega.branch", 2048, 2048, 786432, 28672, 16384),
    ("mega.branch", 2048, 2048, 786432, 28672, 16384),
]
SUBTRIE_KEYS = [
    ("fused.subtrie", 4, 8192, 2048, 8, 786432, 32768, 16384, 1),
    ("fused.subtrie", 4, 2048, 8192, 8, 786432, 32768, 16384, 1),
    ("fused.subtrie", 4, 2048, 2048, 8, 786432, 32768, 16384, 1),
]
ROOT = "ec19688b02750d8aab0218b3a50f83678cea8a7544a5ee6a906d4222a055c681"


def _fixed_chunk():
    return _job(6000, 30, prefix=0x5A)


@pytest.mark.parametrize("k,keys", [(0, MEGA_KEYS), (4, SUBTRIE_KEYS)])
def test_a_fixed_chunk_asks_for_the_parents_shape_keys(monkeypatch, k, keys):
    seen = _dispatched_keys(monkeypatch)
    committer = TurboCommitter(backend="device", min_tier=8, subtrie_levels=k)
    (res,) = committer.commit_hashed_many([_fixed_chunk()],
                                          collect_branches=True, start_depth=2)
    assert seen == keys
    assert res.root.hex() == ROOT and res.hashed_nodes == 8286


# -- counters ------------------------------------------------------------------


def test_gather_counters_move_by_extent_and_row_tier_per_packed_dispatch(
        monkeypatch):
    plans = []
    orig = fc.MegaFusedEngine._execute

    def spy(self):
        if self._buf is None:
            plans.append((list(self._plan), self._buffer_lens()[0]))
        return orig(self)

    monkeypatch.setattr(fc.MegaFusedEngine, "_execute", spy)
    before = {n: REGISTRY.counter(n).value for n in GATHER}
    committer = TurboCommitter(backend="device", min_tier=8)
    committer.commit_hashed_many([_fixed_chunk()], collect_branches=True,
                                 start_depth=2)
    moved = {n: REGISTRY.counter(n).value - before[n] for n in GATHER}
    ((plan, u8_len),) = plans
    packed = [e for e in plan if e[0] == "packed"]
    assert len(packed) == 4 and u8_len == 786432
    # entry = ("packed", b_tier, n_pow, ...): the extent, never the buffer
    # where a row tier of rows is shorter than it
    want = [min(e[2] * e[1] * RATE, u8_len) for e in packed]
    assert want == [278528, 278528, 786432, 278528]
    assert moved["fused_gather_operand_bytes_total"] == sum(want)
    assert moved["fused_gather_rows_total"] == sum(e[2] for e in packed)
    rendered = REGISTRY.render()
    assert all(f"# TYPE {n} counter" in rendered for n in GATHER)
