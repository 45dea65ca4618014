"""Pallas TPU kernel: popcount over packed uint32 word arrays.

Bit-twiddling (Hamming weight) inside the kernel; one int32 partial sum per
grid tile, reduced by the wrapper.  Used for bitmap selectivity estimation
and the paper's 1-C/N profiles at query-planning time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 8
BLOCK_COLS = 1024


def _popcount_u32(v):
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (v * jnp.uint32(0x01010101)) >> 24


def _kernel(a_ref, o_ref):
    counts = _popcount_u32(a_ref[...]).astype(jnp.int32)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        o_ref[0, 0] = jnp.int32(0)

    o_ref[0, 0] += jnp.sum(counts)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def popcount_total(a: jax.Array, block_rows: int = BLOCK_ROWS,
                   block_cols: int = BLOCK_COLS, *, interpret: bool) -> jax.Array:
    """Total number of set bits in an (R, C) uint32 array.

    One SMEM scalar accumulates across the (sequential) grid: every tile
    adds its count, the first one zeroes it first."""
    R, C = a.shape
    gr, gc = R // block_rows, C // block_cols
    assert gr * block_rows == R and gc * block_cols == C
    total = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        grid=(gr, gc),
        in_specs=[pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=interpret,
    )(a)
    return total[0, 0]


def _kernel_rows(a_ref, o_ref, *, first_col):
    counts = _popcount_u32(a_ref[...]).astype(jnp.int32)
    row_sum = jnp.sum(counts, axis=1, keepdims=True)  # (block_rows, 1)

    @pl.when(first_col())
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += row_sum


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def popcount_rows(a: jax.Array, block_rows: int = BLOCK_ROWS,
                  block_cols: int = BLOCK_COLS, *, interpret: bool) -> jax.Array:
    """Per-row set-bit counts of an (R, C) uint32 array -> (R,) int32.

    Grid iterates columns innermost; the output row-block accumulates across
    column steps (standard TPU reduction pattern: zero on first visit).
    """
    R, C = a.shape
    gr, gc = R // block_rows, C // block_cols
    assert gr * block_rows == R and gc * block_cols == C
    out = pl.pallas_call(
        functools.partial(_kernel_rows, first_col=lambda: pl.program_id(1) == 0),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.int32),
        grid=(gr, gc),
        in_specs=[pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        interpret=interpret,
    )(a)
    return out[:, 0]
