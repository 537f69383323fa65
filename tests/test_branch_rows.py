"""The branch level programs' rows (ISSUE 32): ``_branch_level`` builds the
all-hashed-children branch RLPs on the device with no index that addresses a
single byte, one body behind ``_jitted("branch", 4)``, ``_staged_branch``
and the subtrie program's ``branch_step``. Held here: the rows and the
digests against a plain RLP loop (the f8 -> f9 edge, junk triples, the
padding row, a split level); the three callers on one fixed level; what the
program's jaxpr indexes, against the one helper that counts it; the two
counters the helper feeds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reth_tpu.metrics import REGISTRY
from reth_tpu.ops import fused_commit as fc
from reth_tpu.primitives.keccak import RATE, keccak256
from reth_tpu.primitives.rlp import rlp_encode

L = 4 * RATE
N = 2048
S_TIER = 8192
BRANCH = ["fused_branch_index_elems_total", "fused_branch_rows_total"]


def _mask_of(rng, children: int) -> int:
    nibs = rng.choice(16, children, replace=False)
    return int(np.bitwise_or.reduce(1 << nibs))


def _level(rng, masks, n_slots=S_TIER):
    """(masks u16, slots, (3, c) triples in the native order, a digest
    buffer of random bytes) for a level whose children live in slots
    1 .. n_slots/2 and whose own digests go above them."""
    masks = np.asarray(masks, dtype=np.uint16)
    rows, nibs = np.nonzero((masks[:, None] >> np.arange(16)) & 1)
    srcs = rng.integers(1, n_slots // 2, len(rows))
    slots = n_slots // 2 + rng.permutation(n_slots // 2 - 1)[:len(masks)]
    children = np.stack((rows, nibs, srcs)).astype(np.int32)
    buf = rng.integers(0, 256, (n_slots, 32), dtype=np.uint8)
    return masks, slots.astype(np.int32), children, buf


def _rlp_loop(masks, children, buf):
    """Every row's node RLP by the Yellow Paper: a list of the sixteen
    children (a 32-byte reference, or empty) and the empty value."""
    kids = {(int(r), int(nb)): int(s) for r, nb, s in children.T}
    return [rlp_encode([buf[kids[r, nb]].tobytes() if m >> nb & 1 else b""
                        for nb in range(16)] + [b""])
            for r, m in enumerate(int(x) for x in masks)]


def _padded(masks, slots, children, n_pow, ch_pow):
    """The arrays as `mega_branch` hands them over: rows past the level
    have mask 0 and slot 0, junk triples sit on the padding row."""
    n, c = len(masks), children.shape[1]
    m = np.zeros(n_pow, dtype=np.int32)
    m[:n] = masks
    s = np.zeros(n_pow, dtype=np.int32)
    s[:n] = slots
    cr = np.full(ch_pow, n, dtype=np.int32)
    cn = np.zeros(ch_pow, dtype=np.int32)
    cs = np.zeros(ch_pow, dtype=np.int32)
    cr[:c], cn[:c], cs[:c] = children
    return tuple(jnp.asarray(a) for a in (m, s, cr, cn, cs))


def _masks(case, rng, n):
    if case == "random":
        return [_mask_of(rng, int(k)) for k in rng.integers(2, 17, n)]
    if case == "f8_f9_edge":   # 7 children: payload 241; 8: 273, the f9 form
        return [_mask_of(rng, 7 + i % 2) for i in range(n)]
    return [_mask_of(rng, int(case)) for _ in range(n)]


CASES = ["2", "7", "8", "15", "16", "f8_f9_edge", "random"]


@pytest.mark.parametrize("case", CASES)
def test_rows_and_digests_equal_a_plain_rlp_loop(case):
    rng = np.random.default_rng(CASES.index(case))
    n = N - 1 if case == "random" else N - 40    # the rest: padding rows
    masks, slots, children, buf = _level(rng, _masks(case, rng, n))
    m, s, cr, cn, cs = _padded(masks, slots, children, N, 16 * N)
    want = _rlp_loop(masks, children, buf)
    assert {len(w) for w in want} <= set(range(83, 533))

    table = np.zeros((N, 16), dtype=np.int32)
    table[children[0], children[1]] = children[2]
    rows, total = fc._branch_rows(m, jnp.asarray(buf)[jnp.asarray(table)], L)
    rows, total = np.asarray(rows), np.asarray(total)
    assert rows.shape == (N, L) and rows.dtype == np.uint8
    for r, w in enumerate(want):
        assert total[r] == len(w)
        assert rows[r, :len(w)].tobytes() == w and not rows[r, len(w):].any()
    # a padding row (mask 0) is f8 11 and seventeen empty items: no trie
    # node, hashed into the dummy slot
    pad = bytes([0xF8, 17]) + b"\x80" * 17
    assert (total[n:] == 19).all()
    assert all(rows[r, :19].tobytes() == pad and not rows[r, 19:].any()
               for r in range(n, N))

    got = np.asarray(jax.jit(fc._branch_level, static_argnames="b_tier")(
        m, s, cr, cn, cs, jnp.asarray(buf), b_tier=4))
    for r, w in enumerate(want):
        assert got[slots[r]].tobytes() == keccak256(w)
    touched = np.zeros(S_TIER, dtype=bool)
    touched[slots] = touched[0] = True
    assert (got[~touched] == buf[~touched]).all()


def test_a_split_level_hashes_to_the_rlp_loops_digests(monkeypatch):
    """`dispatch_branch` cuts a level past the row cap by row ranges and
    `_filter_triples` rebases the triples: every piece has its own padding
    row and junk triple."""
    monkeypatch.setattr(fc.FusedLevelEngine, "_row_cap", lambda self: 200)
    monkeypatch.setattr(fc.MegaFusedEngine, "_ROW_FLOOR", 256)
    rng = np.random.default_rng(7)
    masks, slots, children, buf = _level(rng, _masks("random", rng, 700))
    eng = fc.MegaFusedEngine(min_tier=8)
    eng.begin(S_TIER - 1)
    eng.dispatch_branch(masks, slots, children)
    assert len(eng._plan) == 4 and {e[1] for e in eng._plan} == {256}
    # the children's digests: the buffer the level programs start from
    monkeypatch.setattr(
        eng, "_device_put",
        lambda a: jnp.asarray(buf if a.shape == (S_TIER, 32) else a))
    got = eng.finish()
    for r, w in enumerate(_rlp_loop(masks, children, buf)):
        assert got[slots[r]].tobytes() == keccak256(w)


# -- the three callers -----------------------------------------------------------


def _engine(kind):
    if kind == "level":        # _jitted("branch", 4)
        return fc.FusedLevelEngine(min_tier=8)
    if kind == "mega":         # _staged_branch
        return fc.MegaFusedEngine(min_tier=8)
    return fc.SubtrieFusedEngine(min_tier=8, k=4, row_floor=32, hole_floor=32)


@pytest.mark.parametrize("kind", ["level", "mega", "subtrie"])
def test_every_caller_hashes_one_fixed_level_to_the_loops_digests(kind):
    rng = np.random.default_rng(32)
    leaves = [rng.integers(0, 256, int(k), dtype=np.uint8).tobytes()
              for k in rng.integers(40, 120, 60)]
    masks = np.array([_mask_of(rng, k) for k in (2, 7, 8, 15, 16, 3, 9)],
                     dtype=np.uint16)
    eng = _engine(kind)
    eng.begin(80)
    leaf_slots = np.array([eng.alloc_slot() for _ in leaves], np.int32)
    rl = np.array([len(r) for r in leaves], np.uint32)
    eng.dispatch_packed(np.frombuffer(b"".join(leaves), np.uint8),
                        (np.cumsum(rl) - rl).astype(np.uint32), rl,
                        leaf_slots, None, 1)
    rows, nibs = np.nonzero((masks[:, None] >> np.arange(16)) & 1)
    children = np.stack((rows, nibs, leaf_slots[:len(rows)])).astype(np.int32)
    slots = np.array([eng.alloc_slot() for _ in masks], np.int32)
    eng.dispatch_branch(masks, slots, children)
    got = eng.finish()
    buf = np.zeros((80, 32), dtype=np.uint8)
    for s, leaf in zip(leaf_slots, leaves):
        buf[s] = np.frombuffer(keccak256(leaf), np.uint8)
    assert (got[leaf_slots] == buf[leaf_slots]).all()
    for r, w in enumerate(_rlp_loop(masks, children, buf)):
        assert got[slots[r]].tobytes() == keccak256(w)


# -- what the program indexes ------------------------------------------------------


def _indexed(jaxpr, out):
    """(primitive, operand aval, index aval, elements moved) of every gather
    and scatter of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("gather", "scatter", "scatter-add"):
            operand, index = eqn.invars[0].aval, eqn.invars[1].aval
            moved = (eqn.outvars[0] if eqn.primitive.name == "gather"
                     else eqn.invars[2]).aval
            out.append((eqn.primitive.name, operand, index,
                        int(np.prod(moved.shape))))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _indexed(inner, out)
    return out


def _mega_branch_jaxpr(n_pow, ch_pow, s_tier):
    u8_len, i32_len = max(1 << 16, 2 * n_pow), 2 * (n_pow + 2 * ch_pow)
    fn = fc._staged_branch.__wrapped__(n_pow, ch_pow, u8_len, i32_len, s_tier)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((u8_len,), jnp.uint8),
        jax.ShapeDtypeStruct((i32_len,), jnp.int32),
        jax.ShapeDtypeStruct((s_tier, 32), jnp.uint8), *[scalar] * 6).jaxpr


@pytest.mark.parametrize("n_pow,ch_pow,s_tier", [
    (2048, 2048, 1 << 14), (2048, 8192, 1 << 14),
    (65536, 1 << 18, 1 << 21), (65536, 1 << 20, 1 << 21)])
def test_no_index_addresses_a_byte_and_the_helper_counts_them_all(
        n_pow, ch_pow, s_tier):
    found = _indexed(_mega_branch_jaxpr(n_pow, ch_pow, s_tier), [])
    # an index array of ONE element is a slice update at a fixed place (the
    # absorb writes its rate lanes so): everything else is counted
    found = [f for f in found if np.prod(f[2].shape) > 1]
    assert len(found) == 3
    for name, operand, index, moved in found:
        per_index = moved // int(np.prod(index.shape))
        if operand.dtype == jnp.uint8:   # bytes move as whole digest rows
            assert operand.shape == (s_tier, 32) and per_index == 32, name
        else:                            # the table: words
            assert operand.dtype == jnp.int32 and per_index == 1, name
    elems = sum(int(np.prod(f[2].shape)) for f in found)
    assert elems == fc._branch_index_elems(n_pow, ch_pow)
    assert elems < 64 * n_pow            # the per-byte body: 33 ch_pow + 18 n_pow


def test_branch_counters_move_by_the_helper_and_the_row_tier(monkeypatch):
    plans = []
    orig = fc.MegaFusedEngine._execute

    def spy(self):
        if self._buf is None:
            plans.append(list(self._plan))
        return orig(self)

    monkeypatch.setattr(fc.MegaFusedEngine, "_execute", spy)
    before = {n: REGISTRY.counter(n).value for n in BRANCH}
    rng = np.random.default_rng(5)
    masks, slots, children, _ = _level(rng, _masks("random", rng, 3000))
    eng = fc.MegaFusedEngine(min_tier=8)
    eng.begin(S_TIER - 1)
    eng.dispatch_branch(masks[:100], slots[:100],
                        fc.FusedLevelEngine._filter_triples(children, 0, 100))
    eng.dispatch_branch(masks, slots, children)
    eng.finish()
    moved = {n: REGISTRY.counter(n).value - before[n] for n in BRANCH}
    ((first, second),) = plans
    assert [e[1:3] for e in (first, second)] == [(2048, 2048), (4096, 32768)]
    assert moved["fused_branch_rows_total"] == 2048 + 4096
    assert moved["fused_branch_index_elems_total"] == sum(
        fc._branch_index_elems(*e[1:3]) for e in (first, second))
    rendered = REGISTRY.render()
    assert all(f"# TYPE {n} counter" in rendered for n in BRANCH)
