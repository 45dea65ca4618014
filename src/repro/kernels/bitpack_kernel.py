"""Pallas TPU kernel: bit packing (rows x bitmaps) bools -> uint32 words.

Inner loop of the index builder (Algorithm 3): 32 consecutive rows of a
bitmap column become one 32-bit word.  In-kernel the pack is a weighted sum
over the 32-row axis with weights 2^i, vectorized over 128 bitmap lanes —
MXU-free, pure VPU work.  The sum runs in int32 (the TPU has no unsigned
reductions; bit 31's weight wraps to -2^31) and is bitcast to uint32: the
weighted bits are disjoint, so the two's-complement sum is exactly their OR.

Layout: bits (N_ROWS, L) -> words (N_ROWS // 32, L); bit i of word w is row
32*w + i (the codec's little-endian convention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_BLOCK = 1024   # rows per tile -> 32 words
COL_BLOCK = 128    # bitmap lanes per tile
WORD_BITS = 32


def _kernel(bits_ref, words_ref):
    bits = bits_ref[...].astype(jnp.int32)            # (ROW_BLOCK, COL_BLOCK)
    r, c = bits.shape
    w = r // WORD_BITS
    bits = bits.reshape(w, WORD_BITS, c)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, WORD_BITS, c), 1)
    words = jnp.sum(bits << shifts, axis=1)
    words_ref[...] = jax.lax.bitcast_convert_type(words, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("row_block", "col_block", "interpret"))
def bitpack(bits: jax.Array, row_block: int = ROW_BLOCK, col_block: int = COL_BLOCK,
            *, interpret: bool) -> jax.Array:
    """(N, L) bools -> (N//32, L) uint32 words."""
    N, L = bits.shape
    assert N % WORD_BITS == 0, "pad rows to a word multiple"
    gr, gc = N // row_block, L // col_block
    assert gr * row_block == N and gc * col_block == L, (bits.shape, row_block, col_block)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((N // WORD_BITS, L), jnp.uint32),
        grid=(gr, gc),
        in_specs=[pl.BlockSpec((row_block, col_block), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((row_block // WORD_BITS, col_block), lambda i, j: (i, j)),
        interpret=interpret,
    )(bits)
