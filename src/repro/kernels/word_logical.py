"""Pallas TPU kernel: word-aligned logical ops with clean-tile skipping.

TPU adaptation of EWAH's Lemma 2 (DESIGN.md §3): bitmaps live on device as
dense uint32 word arrays tiled into VMEM blocks; a per-tile *flag* sideband
says whether a tile is clean (all-0 / all-1).  The kernel resolves clean×any
tiles from flag algebra alone (``@pl.when`` branches write the constant or
pass the other operand through) and only runs the elementwise word op on
dirty×dirty tiles — recovering "only touch non-zero words" at VMEM-tile
granularity, which is the granularity a TPU can actually skip at.

Tiling: (SUBLANES=8, LANES=128) words per VREG op for 32-bit types; default
block (8, 1024) = 32 KiB/operand in VMEM.  The flags are scalar-prefetched
into SMEM (one int32 per tile): a (1, 1) VMEM block per tile would break
the (8, 128) block rule.

Compilation contract: ``word_logical`` is jit-compiled once per *input
shape* (plus static block/op params).  Callers must therefore keep the
shape universe small — ``repro.kernels.ops`` pads the word dimension to
power-of-two multiples of ``block_cols`` and operand stacks to power-of-two
row counts, and calls this kernel from inside its own programs (a whole
reduction tree is one), so one compiled program serves every operand count
and word count in a bucket, across shards, queries, and index rebuilds.
The tile flags come from per-row flags (``ops.np_row_flags``, cached by the
executor, or made on the device); a conservative merge of row flags into
tile flags is valid because DIRTY only means "read the words".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# flag values for a tile
DIRTY = 0
CLEAN0 = 1
CLEAN1 = 2

OPS = ("and", "or", "xor", "andnot")

BLOCK_ROWS = 8
BLOCK_COLS = 1024


def _apply(op: str, a, b):
    if op == "and":
        return a & b
    if op == "or":
        return a | b
    if op == "xor":
        return a ^ b
    return a & ~b  # andnot


def _kernel(op: str, gc: int, fa_ref, fb_ref, a_ref, b_ref, o_ref):
    # flags are scalar-prefetched into SMEM, flattened row-major by tile
    t = pl.program_id(0) * gc + pl.program_id(1)
    fa = fa_ref[t]
    fb = fb_ref[t]
    both_dirty = (fa == DIRTY) & (fb == DIRTY)

    @pl.when(both_dirty)
    def _():
        o_ref[...] = _apply(op, a_ref[...], b_ref[...])

    @pl.when(~both_dirty)
    def _():
        # resolve from flag algebra: substitute clean tiles by their constant
        av = jnp.where(fa == DIRTY, a_ref[...],
                       jnp.where(fa == CLEAN1, jnp.uint32(0xFFFFFFFF), jnp.uint32(0)))
        bv = jnp.where(fb == DIRTY, b_ref[...],
                       jnp.where(fb == CLEAN1, jnp.uint32(0xFFFFFFFF), jnp.uint32(0)))
        o_ref[...] = _apply(op, av, bv)


@functools.partial(jax.jit, static_argnames=("op", "block_rows", "block_cols", "interpret"))
def word_logical(
    a: jax.Array,
    b: jax.Array,
    flags_a: jax.Array,
    flags_b: jax.Array,
    op: str = "and",
    block_rows: int = BLOCK_ROWS,
    block_cols: int = BLOCK_COLS,
    *,
    interpret: bool,
) -> jax.Array:
    """op(a, b) over (R, C) uint32 word arrays with (R/br, C/bc) tile flags."""
    assert op in OPS
    R, C = a.shape
    assert a.shape == b.shape
    gr, gc = R // block_rows, C // block_cols
    assert gr * block_rows == R and gc * block_cols == C, (a.shape, block_rows, block_cols)
    assert flags_a.shape == (gr, gc) == flags_b.shape

    # index maps see the two prefetched flag refs after the grid indices
    tile = pl.BlockSpec((block_rows, block_cols), lambda i, j, fa, fb: (i, j))
    return pl.pallas_call(
        functools.partial(_kernel, op, gc),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(gr, gc),
            in_specs=[tile, tile], out_specs=tile),
        interpret=interpret,
    )(flags_a.reshape(-1), flags_b.reshape(-1), a, b)

