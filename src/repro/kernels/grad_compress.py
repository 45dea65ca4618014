"""Pallas TPU kernel: blockwise gradient statistics for EWAH sparse all-reduce.

The distributed substrate (DESIGN.md §4.2) sparsifies gradients block-wise:
keep the highest-energy blocks, ship (EWAH-compressed keep-bitmap + packed
payload).  The kernel computes per-block squared L2 norms in one pass; the
jnp wrapper derives the keep threshold and mask.  The mask's *bitmap* is then
packed by the ``bitpack`` kernel and EWAH-encoded host-side.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

VALUES_PER_BLOCK = 256   # gradient values per compression block
TILE_BLOCKS = 512        # compression blocks per kernel tile


def _kernel(g_ref, o_ref):
    g = g_ref[...]                       # (TILE_BLOCKS, VALUES_PER_BLOCK) f32
    o_ref[...] = jnp.sum(g * g, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("values_per_block", "tile_blocks", "interpret"))
def block_sqnorms(grad_flat: jax.Array, values_per_block: int = VALUES_PER_BLOCK,
                  tile_blocks: int = TILE_BLOCKS, *, interpret: bool) -> jax.Array:
    """(n_blocks * values_per_block,) f32 -> (n_blocks,) squared block norms."""
    n = grad_flat.shape[0]
    n_blocks = n // values_per_block
    assert n_blocks * values_per_block == n, "pad gradient to a block multiple"
    g2 = grad_flat.reshape(n_blocks, values_per_block)
    gb = max(n_blocks // tile_blocks, 1)
    tb = n_blocks // gb
    assert tb * gb == n_blocks, (n_blocks, tile_blocks)
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((n_blocks, 1), jnp.float32),
        grid=(gb,),
        in_specs=[pl.BlockSpec((tb, values_per_block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        interpret=interpret,
    )(g2.astype(jnp.float32))
    return out[:, 0]


def topk_block_mask(grad_flat: jax.Array, keep_ratio: float,
                    values_per_block: int = VALUES_PER_BLOCK,
                    *, interpret: bool) -> jax.Array:
    """Boolean keep-mask over compression blocks (True = block survives)."""
    norms = block_sqnorms(grad_flat, values_per_block, interpret=interpret)
    n_blocks = norms.shape[0]
    k = max(int(n_blocks * keep_ratio), 1)
    thresh = jax.lax.top_k(norms, k)[0][-1]
    return norms >= thresh
