"""EWAH sparse-gradient exchange with error feedback (DESIGN.md §4.2).

The paper's machinery applied to a distributed-training collective: gradients
are sparsified block-wise (keep the top-energy blocks), and the surviving-
block *bitmap* — exactly the kind of sparse boolean vector EWAH compresses
well — indexes the packed payload.  On real multi-host TPU the exchange
would ship (EWAH bitmap + payload) over DCN between pods; under single-
process SPMD we apply the mask and let the partitioner all-reduce the masked
gradient, which is numerically identical, while reporting the wire-size the
bitmap+payload encoding would achieve.

Error feedback (Stich et al.) accumulates the dropped mass so convergence is
preserved; `tests/test_grad_compression.py` checks both the exactness of the
mask algebra and convergence parity on a toy problem.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ewah import EWAH
from repro.kernels import ops as kops


class CompressionStats(NamedTuple):
    dense_bytes: int
    payload_bytes: int
    bitmap_words: int

    @property
    def wire_bytes(self) -> int:
        return self.payload_bytes + 4 * self.bitmap_words

    @property
    def ratio(self) -> float:
        return self.dense_bytes / max(self.wire_bytes, 1)


def _flatten(tree):
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    return flat, leaves


def _unflatten(tree, flat):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    off = 0
    for l in leaves:
        n = int(np.prod(l.shape)) if l.shape else 1
        out.append(flat[off:off + n].reshape(l.shape).astype(l.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def sparsify(grads, error: Any, keep_ratio: float, values_per_block: int = 256,
             interpret: Optional[bool] = None):
    """(grads, error-feedback) -> (masked grads, new error, keep-mask, flat)."""
    flat, _ = _flatten(grads)
    if error is not None:
        eflat, _ = _flatten(error)
        flat = flat + eflat
    n = flat.shape[0]
    npad = -(-n // values_per_block) * values_per_block
    fpad = jnp.pad(flat, (0, npad - n))
    mask_blocks = kops.topk_block_mask(fpad, keep_ratio, values_per_block,
                                       interpret=interpret)
    mask = jnp.repeat(mask_blocks, values_per_block)[:n]
    kept = flat * mask
    new_error_flat = flat - kept
    return kept, new_error_flat, mask_blocks, flat


def compressed_allreduce(grads, error, keep_ratio: float,
                         values_per_block: int = 256,
                         interpret: Optional[bool] = None
                         ) -> Tuple[Any, Any, CompressionStats]:
    """Returns (sparsified grads pytree, new error pytree, wire stats).

    The actual cross-replica mean happens in the caller's pjit (the masked
    gradient is what gets all-reduced); stats report what the EWAH-encoded
    exchange would put on the wire.
    """
    kept, new_error_flat, mask_blocks, flat = sparsify(
        grads, error, keep_ratio, values_per_block, interpret)
    grads_out = _unflatten(grads, kept)
    error_out = _unflatten(grads, new_error_flat)

    mask_np = np.asarray(mask_blocks)
    bitmap = EWAH.from_bool(mask_np)
    n_kept = int(mask_np.sum()) * values_per_block
    stats = CompressionStats(
        dense_bytes=int(flat.shape[0]) * 4,
        payload_bytes=n_kept * 4,
        bitmap_words=bitmap.size_words,
    )
    return grads_out, error_out, stats


def init_error(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
