"""Compile the programs ``chip_smoke.py`` dispatches, at their real shapes,
for a DESCRIBED TPU v5e 2x2 — no chip attached, nothing runs.

This is the third rehearsal of the on-chip-measurement guide: what the
TPU's compiler refuses here (VMEM limit, unaligned slice, a layout it
cannot tile, a program that does not fit device memory, a kernel that
cannot be partitioned) costs no chip time. A compile that passes is not a
chip run and is never reported as one.

The shapes are the ones a full-size ``chip_smoke.py`` records in the
compile tracker (1,000,000 accounts + 1,000,000 slots, seed 0): the keccak
front-end at the menu ceiling, the Pallas kernel, every staged per-level
program the single-device ``MegaFusedEngine`` mints, the largest sharded
level programs ``FusedMeshEngine`` mints on four devices, plus the fused
plain/splice and whole-subtrie (k=4) programs of the live-tip path.

The topology is described inside a module-scoped fixture (never at import
time, never in a child process), and the persistent compilation cache is
switched off around these compiles: an executable compiled for a described
chip is written to the cache but cannot be read back without the chip.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from reth_tpu.ops import fused_commit as fc  # noqa: E402
from reth_tpu.ops.keccak_jax import (keccak256_jax_words,  # noqa: E402
                                     keccak256_jax_words_masked)
from reth_tpu.ops.keccak_pallas import keccak256_pallas_wordsT  # noqa: E402
from reth_tpu.primitives.keccak import RATE  # noqa: E402

ROWS = 16384  # KeccakDevice.MAX_BATCH_TIER — chip_smoke's kernel phase


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    assert len(topo.devices) == 4
    return Mesh(np.array(topo.devices), ("data",))


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    # one program at a time must fit the chip's 16 GB with room to spare
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 12 * 2**30, f"program needs {total / 2**30:.1f} GiB"
    return compiled


# -- keccak front-end at the menu ceiling -------------------------------------


@pytest.mark.parametrize("blocks", [1, 4, 32])
def test_masked_keccak_at_the_menu_ceiling(one_chip, blocks):
    _compile(keccak256_jax_words_masked,
             _arg((ROWS, 34 * blocks), jnp.uint32, one_chip), blocks,
             _arg((ROWS,), jnp.int32, one_chip))


@pytest.mark.parametrize("blocks", [1, 4])
def test_exact_keccak_at_the_menu_ceiling(one_chip, blocks):
    _compile(keccak256_jax_words,
             _arg((ROWS, 34 * blocks), jnp.uint32, one_chip), blocks)


@pytest.mark.parametrize("rows", [256, ROWS])
def test_pallas_kernel_lowers_for_the_chip(one_chip, rows):
    compiled = _compile(keccak256_pallas_wordsT,
                        _arg((34, rows), jnp.uint32, one_chip), False)
    assert "tpu_custom_call" in compiled.as_text()  # Mosaic, not XLA


# -- single-device rebuild: MegaFusedEngine's staged per-level programs -------

S_TIER = 2_097_152      # digest arena rows (64 MiB) at 1M + 1M
I32_LEN = 4_194_304
U8_STORAGE, U8_ACCOUNTS = 50_331_648, 117_440_512  # staged bytes per commit

# a cut of the 6 packed / 8 branch shapes the full run mints: the tiers
# differ only in row count, and the largest take ~20 s each to compile
# (the largest packed program stays; (16384, 32768) branch and the
# 65536-row mesh branch compiled for the described chip when this file
# was written, and were left out for time)
MEGA_PACKED = [  # (b_tier, n_pow, h_pow, u8_len)
    (1, 2048, 2048, U8_STORAGE), (1, 4096, 2048, U8_STORAGE),
    (1, 8192, 2048, U8_ACCOUNTS), (1, 32768, 2048, U8_ACCOUNTS),
]
MEGA_BRANCH = [  # (n_pow, ch_pow, u8_len)
    (2048, 2048, U8_STORAGE), (2048, 16384, U8_ACCOUNTS),
    (4096, 16384, U8_ACCOUNTS),
]


def _staged_args(one_chip, u8_len, n_scalars):
    scalar = _arg((), jnp.int32, one_chip)
    return (_arg((u8_len,), jnp.uint8, one_chip),
            _arg((I32_LEN,), jnp.int32, one_chip),
            _arg((S_TIER, 32), jnp.uint8, one_chip)) + (scalar,) * n_scalars


@pytest.mark.parametrize("b_tier,n_pow,h_pow,u8_len", MEGA_PACKED)
def test_mega_packed_level_program(one_chip, b_tier, n_pow, h_pow, u8_len):
    fn = fc._staged_packed(b_tier, n_pow, h_pow, u8_len, I32_LEN, S_TIER)
    _compile(fn, *_staged_args(one_chip, u8_len, 7))


@pytest.mark.parametrize("n_pow,ch_pow,u8_len", MEGA_BRANCH)
def test_mega_branch_level_program(one_chip, n_pow, ch_pow, u8_len):
    fn = fc._staged_branch(n_pow, ch_pow, u8_len, I32_LEN, S_TIER)
    _compile(fn, *_staged_args(one_chip, u8_len, 6))


# -- live-tip fused programs: plain / splice / whole-subtrie k=4 --------------


@pytest.mark.parametrize("n_tier", [1024, 4096])  # x4 from min_tier
def test_fused_plain_and_splice_level_programs(one_chip, n_tier):
    b_tier = 4  # a branch node tops out at 4 rate blocks
    i32 = lambda n: _arg((n,), jnp.int32, one_chip)  # noqa: E731
    templates = _arg((n_tier, b_tier * RATE), jnp.uint8, one_chip)
    buf = _arg((4 * n_tier, 32), jnp.uint8, one_chip)
    _compile(fc._jitted("plain", b_tier), templates, i32(n_tier),
             i32(n_tier), buf)
    holes = fc.FusedLevelEngine._HOLE_FACTOR * n_tier
    _compile(fc._jitted("splice", b_tier), templates, i32(n_tier),
             i32(holes), i32(holes), i32(holes), i32(n_tier), buf)


def test_subtrie_program_k4(one_chip):
    n_pow = h_pow = fc.MegaFusedEngine._ROW_FLOOR
    steps_pow, u8_len, i32_len, s_tier = 8, 1 << 20, 1 << 16, 1 << 16
    fn = fc._subtrie_program(4, n_pow, h_pow, steps_pow, u8_len, i32_len,
                             s_tier, None)
    _compile(fn, _arg((u8_len,), jnp.uint8, one_chip),
             _arg((i32_len,), jnp.int32, one_chip),
             _arg((steps_pow, fc._PARAM_W), jnp.int32, one_chip),
             _arg((s_tier, 32), jnp.uint8, one_chip),
             _arg((), jnp.int32, one_chip))


# -- four devices: FusedMeshEngine's sharded level programs -------------------

MESH_PACKED = [  # (b_tier, n_tier, flat_tier, hole_tier, arena rows)
    (1, 1024, 4096, 256, 1024),
    (1, 65536, 8_388_608, 4096, 524_288),
    (1, 16384, 1_048_576, 256, 2_097_152),
]
MESH_BRANCH = [  # (n_tier, child_tier, arena rows)
    (1024, 2048, 1024),
    (4096, 16384, 524_288),
]


@pytest.mark.parametrize("b_tier,n_tier,flat_tier,h_tier,s_tier", MESH_PACKED)
def test_mesh_packed_level_program(mesh4, b_tier, n_tier, flat_tier, h_tier,
                                   s_tier):
    shard = NamedSharding(mesh4, P("data"))
    rep = NamedSharding(mesh4, P())
    rows = lambda dt: _arg((n_tier,), dt, shard)  # noqa: E731
    hole = _arg((h_tier,), jnp.int32, shard)
    compiled = _compile(
        fc._jitted("packed", b_tier, mesh4),
        _arg((flat_tier,), jnp.uint8, rep), rows(jnp.uint32),
        rows(jnp.uint32), rows(jnp.int32), hole, hole, hole,
        rows(jnp.int32), _arg((s_tier, 32), jnp.uint8, rep))
    # the sharded level's digests reach the replicated arena through a
    # collective the compiler inserted (all-gather / all-reduce family)
    text = compiled.as_text()
    assert "all-gather" in text or "all-reduce" in text \
        or "collective-permute" in text


@pytest.mark.parametrize("n_tier,c_tier,s_tier", MESH_BRANCH)
def test_mesh_branch_level_program(mesh4, n_tier, c_tier, s_tier):
    shard = NamedSharding(mesh4, P("data"))
    rep = NamedSharding(mesh4, P())
    rows = _arg((n_tier,), jnp.int32, shard)
    child = _arg((c_tier,), jnp.int32, shard)
    _compile(fc._jitted("branch", 4, mesh4), rows, rows, child, child, child,
             _arg((s_tier, 32), jnp.uint8, rep))


def test_subtrie_program_k4_on_the_mesh(mesh4):
    rep = NamedSharding(mesh4, P())
    n_pow = h_pow = fc.MegaFusedEngine._ROW_FLOOR
    steps_pow, u8_len, i32_len, s_tier = 8, 1 << 20, 1 << 16, 1 << 16
    fn = fc._subtrie_program(4, n_pow, h_pow, steps_pow, u8_len, i32_len,
                             s_tier, mesh4)
    _compile(fn, _arg((u8_len,), jnp.uint8, rep),
             _arg((i32_len,), jnp.int32, rep),
             _arg((steps_pow, fc._PARAM_W), jnp.int32, rep),
             _arg((s_tier, 32), jnp.uint8, rep), _arg((), jnp.int32, rep))
