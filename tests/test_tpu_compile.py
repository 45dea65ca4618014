"""Compile the served path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler is asked, ahead of time, for each kernel at
the widths the executor uses (one (8, 1024) tile, and the bucketed width of
a 2.5M-row shard), with ``interpret=False``.  A tiling or lowering rule the
interpreter does not check fails here instead of on the chip, and each
compiled program must hold the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitpack_kernel as bp
from repro.kernels import ops as kops
from repro.kernels import popcount as pc
from repro.kernels import word_logical as wl

# bucketed word count of one 2.5M-row shard (a 10M-row store cut in four)
SHARD_COLS = kops.bucket_cols(2_500_000 // 32)


@pytest.fixture(scope="module")
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - depends on the installed libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def test_shard_bucket_width():
    assert SHARD_COLS == 131072


@pytest.mark.parametrize("cols", [1024, SHARD_COLS])
@pytest.mark.parametrize("op", ["and", "andnot"])
def test_word_logical_compiles(one_chip, op, cols):
    rows = wl.BLOCK_ROWS
    tiles = (rows // wl.BLOCK_ROWS, cols // wl.BLOCK_COLS)
    words = _spec((rows, cols), jnp.uint32, one_chip)
    flags = _spec(tiles, jnp.int32, one_chip)
    _assert_kernel(wl.word_logical.lower(words, words, flags, flags, op=op,
                                         interpret=False))


# the lex cell's shard width: 1.5M rows, 46,875 words, in a 65,536 bucket
LEX_COLS = kops.bucket_cols(1_500_000 // 32)


@pytest.mark.parametrize("rows", [2, 32])
@pytest.mark.parametrize("op", ["and", "or"])
def test_logical_reduce_tree_compiles(one_chip, op, rows):
    # the whole reduction tree, as the executor sends it: one (1, cols) row
    # per operand and int8 row flags; one kernel per round
    assert LEX_COLS == 65536
    parts = tuple(_spec((1, LEX_COLS), jnp.uint32, one_chip)
                  for _ in range(rows))
    flags = _spec((rows, LEX_COLS // wl.BLOCK_COLS), jnp.int8, one_chip)
    lowered = kops._reduce_program.lower(
        parts, flags, op=op, cols=LEX_COLS,
        block_rows=wl.BLOCK_ROWS, block_cols=wl.BLOCK_COLS, interpret=False)
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == rows.bit_length() - 1


def test_word_logical_program_compiles(one_chip):
    # the executor's ANDNOT of two reductions: pad, flags, kernel, slice
    row = _spec((1, LEX_COLS), jnp.uint32, one_chip)
    lowered = kops._logical_program.lower(
        row, row, op="andnot", width=LEX_COLS, cols=LEX_COLS,
        block_rows=wl.BLOCK_ROWS, block_cols=wl.BLOCK_COLS, interpret=False)
    assert lowered.compile().as_text().count("tpu_custom_call") == 1


def test_popcount_total_compiles(one_chip):
    words = _spec((8, SHARD_COLS), jnp.uint32, one_chip)
    _assert_kernel(pc.popcount_total.lower(words, interpret=False))


def test_popcount_rows_compiles(one_chip):
    words = _spec((8, SHARD_COLS), jnp.uint32, one_chip)
    _assert_kernel(pc.popcount_rows.lower(words, interpret=False))


def test_bitpack_compiles(one_chip):
    bits = _spec((4 * bp.ROW_BLOCK, 2 * bp.COL_BLOCK), jnp.bool_, one_chip)
    _assert_kernel(bp.bitpack.lower(bits, interpret=False))
