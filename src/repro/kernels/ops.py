"""Jit'd public wrappers around the Pallas kernels (+ padding glue).

Every wrapper takes ``interpret=None``, resolved by ``interpret_mode()``
from the JAX backend: compiled on a TPU, interpreted on the CPU, refused
anywhere else.  The kernels are written against TPU tiling rules
(multiples of (8, 128) for 32-bit types); ``tests/test_tpu_compile.py``
compiles them for a described v5e chip.

Shape bucketing (the JIT cold-start fix): ``jax.jit`` compiles one program
per operand shape, so a query stream whose bitmaps span many distinct word
counts used to trigger a fresh Pallas compile per count.  The wrappers now
pad the word dimension up to power-of-two multiples of ``block_cols``
(``bucket_cols``) and the operand dimension up to a power of two filled with
the op's identity word, collapsing the compiled-shape universe to
O(log max_words x log max_operands) entries that are reused across shards,
queries, and index generations.  Callers that already hold bucketed operands
can pass precomputed per-row clean flags (``np_row_flags``) so the sideband
is not recomputed per query — the executor caches them next to the words.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import word_logical as _wl
from . import popcount as _pc
from . import bitpack_kernel as _bp
from . import grad_compress as _gc

_ALL_ONES = np.uint32(0xFFFFFFFF)

# the checkout root (src/repro/kernels/ops.py -> three levels up)
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted on this process's backend:
    ``False`` on a TPU, ``True`` on the CPU; any other backend raises."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels target TPU (or the CPU "
                       f"interpreter), not backend {backend!r}")


def _interpret(interpret: Optional[bool]) -> bool:
    return interpret_mode() if interpret is None else interpret


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache for this process.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is where JAX already keeps
    it; otherwise the cache goes to ``.jax_cache/`` in the checkout (a
    fixed path, since the path is part of what a later run must find).
    Kernels compile in well under JAX's default one-second floor for
    caching, so the floor is dropped.  Returns the cache directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (>= 1)."""
    return 1 << max(0, int(x) - 1).bit_length() if x > 1 else 1


def bucket_cols(n_words: int, block_cols: int = 1024) -> int:
    """Bucketed (padded) word count: block_cols x next power of two.

    All operands whose word counts fall in the same bucket share one
    compiled kernel; padding words are zero and sliced away by the caller.
    """
    return block_cols * next_pow2(-(-max(int(n_words), 1) // block_cols))


def np_row_flags(words: np.ndarray, block_cols: int = 1024) -> np.ndarray:
    """Host-side per-row clean flags for a bucketed word row (or matrix).

    ``words``' last axis must be a multiple of ``block_cols``; returns
    DIRTY/CLEAN0/CLEAN1 per ``block_cols`` span.  Cacheable alongside the
    padded words (one cheap pass at load time instead of one per query).
    """
    t = words.reshape(words.shape[:-1] + (-1, block_cols))
    all0 = (t == 0).all(axis=-1)
    all1 = (t == _ALL_ONES).all(axis=-1)
    return np.where(all0, _wl.CLEAN0,
                    np.where(all1, _wl.CLEAN1, _wl.DIRTY)).astype(np.int32)


def container_row_flags(cont, padded_words: int,
                        block_cols: int = 1024) -> np.ndarray:
    """Per-block clean flags straight off a container chunk directory.

    Equivalent to ``np_row_flags`` on the padded dense words, but EMPTY /
    FULL chunks resolve from the directory alone and ARRAY chunks from a
    position shift — only DENSE / RUN chunk payloads are scanned.  The
    flags are exact (bit-identical to ``np_row_flags``), not merely
    conservative, so kernel short-circuiting is equally effective.
    """
    from repro.core import containers as C  # lazy: avoid import cycle
    if C.CHUNK_WORDS % block_cols:
        return np_row_flags(_np_pad_words(C.containers_to_dense(cont),
                                          padded_words), block_cols)
    bpc = C.CHUNK_WORDS // block_cols          # blocks per chunk
    bits_per_block = block_cols * 32
    n_blocks = padded_words // block_cols
    flags = np.full(n_blocks, _wl.CLEAN0, dtype=np.int32)
    for i in range(cont.n_chunks):
        t, _, payload = cont.chunk(i)
        if t == C.T_EMPTY:
            continue
        b0, nw = i * bpc, cont.chunk_nw(i)
        nb = -(-nw // block_cols)              # blocks this chunk spans
        if t == C.T_FULL:
            fb = nw // block_cols              # fully covered blocks
            flags[b0:b0 + fb] = _wl.CLEAN1
            if nw % block_cols:                # ragged tail: ones then pad
                flags[b0 + fb] = _wl.DIRTY
            continue
        if t == C.T_ARRAY:
            # a block holding any position is DIRTY (all-ones needs 32768
            # positions, above any array cutoff); empty blocks stay CLEAN0
            occupied = np.unique(np.asarray(payload).astype(np.int64)
                                 // bits_per_block)
            flags[b0 + occupied] = _wl.DIRTY
            continue
        w = C._to_chunk_words(t, payload, nw)
        if nw % block_cols:
            w = np.pad(w, (0, nb * block_cols - nw))
        tw = w.reshape(nb, block_cols)
        all0 = (tw == 0).all(axis=1)
        all1 = (tw == _ALL_ONES).all(axis=1)
        flags[b0:b0 + nb] = np.where(
            all0, _wl.CLEAN0,
            np.where(all1, _wl.CLEAN1, _wl.DIRTY)).astype(np.int32)
    return flags


def _np_pad_words(w: np.ndarray, padded_words: int) -> np.ndarray:
    return np.pad(w, (0, padded_words - len(w))) \
        if len(w) < padded_words else w


def _combine_row_flags(rf: np.ndarray, block_rows: int) -> np.ndarray:
    """Conservatively merge (R, gc) per-row flags into (R/br, gc) tile flags
    (a tile mixing clean values — or any dirty row — is DIRTY)."""
    R, gc = rf.shape
    t = rf.reshape(R // block_rows, block_rows, gc)
    all0 = (t == _wl.CLEAN0).all(axis=1)
    all1 = (t == _wl.CLEAN1).all(axis=1)
    return np.where(all0, _wl.CLEAN0,
                    np.where(all1, _wl.CLEAN1, _wl.DIRTY)).astype(np.int32)


def _pad2(a: jax.Array, br: int, bc: int, fill=0) -> Tuple[jax.Array, Tuple[int, int]]:
    R, C = a.shape
    Rp = -(-R // br) * br
    Cp = -(-C // bc) * bc
    if (Rp, Cp) != (R, C):
        a = jnp.pad(a, ((0, Rp - R), (0, Cp - C)), constant_values=fill)
    return a, (R, C)


def _pad_rows_np(rf: Optional[np.ndarray], rows: int, br: int) -> Optional[np.ndarray]:
    pad = -(-rows // br) * br - rows
    if rf is None or pad == 0:
        return rf
    # zero-filled pad rows are clean-zero
    return np.pad(rf, ((0, pad), (0, 0)), constant_values=_wl.CLEAN0)


def word_logical(a, b, op: str = "and", interpret: Optional[bool] = None,
                 block_rows: int = 8, block_cols: int = 1024,
                 bucket: bool = True,
                 row_flags_a: Optional[np.ndarray] = None,
                 row_flags_b: Optional[np.ndarray] = None) -> jax.Array:
    """Word-aligned logical op over (L, n_words) uint32 arrays.

    Dispatches the clean-tile-skipping kernel — the device-side equivalent
    of EWAH's Lemma 2.  With ``bucket`` (default) the word dimension pads to
    a power-of-two bucket so one compiled kernel serves every operand count
    in the bucket.  ``row_flags_*`` are optional precomputed ``np_row_flags``
    sidebands for the (bucketed) inputs; absent, flags are computed on
    device.
    """
    a = jnp.asarray(a, jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    bc_pad = bucket_cols(a.shape[1], block_cols) if bucket else block_cols
    ap, orig = _pad2(a, block_rows, bc_pad)
    bp_, _ = _pad2(b, block_rows, bc_pad)
    if row_flags_a is None:
        fa = _wl.tile_flags(ap, block_rows, block_cols)
    else:
        fa = jnp.asarray(_combine_row_flags(
            _pad_rows_np(row_flags_a, orig[0], block_rows), block_rows))
    if row_flags_b is None:
        fb = _wl.tile_flags(bp_, block_rows, block_cols)
    else:
        fb = jnp.asarray(_combine_row_flags(
            _pad_rows_np(row_flags_b, orig[0], block_rows), block_rows))
    out = _wl.word_logical(ap, bp_, fa, fb, op=op, block_rows=block_rows,
                           block_cols=block_cols,
                           interpret=_interpret(interpret))
    return out[: orig[0], : orig[1]]


def logical_reduce(mat, op: str = "and", interpret: Optional[bool] = None,
                   block_rows: int = 8, block_cols: int = 1024,
                   bucket: bool = True,
                   row_flags: Optional[np.ndarray] = None) -> jax.Array:
    """Reduce the rows of an (L, n_words) uint32 matrix to one word row.

    Tree reduction: each round halves the operand count by running the
    clean-tile-skipping ``word_logical`` kernel on the two matrix halves, so
    an L-way AND/OR costs ceil(log2 L) kernel launches over ever-smaller
    stacks — the dense executor path for n-ary query nodes.

    With ``bucket`` (default) the words pad to a power-of-two column bucket
    and the rows pad to a power of two filled with the op's identity word
    (all-ones for AND, zero for OR/XOR), so every round halves exactly and
    the compiled kernel shapes depend only on (pow2 rows, bucketed cols) —
    reused across queries regardless of the precise operand count.
    ``row_flags`` is the optional (L, cols/block_cols) precomputed clean
    sideband of the input rows; it accelerates the first (widest) round,
    later rounds recompute flags on device for their intermediate results.
    """
    assert op in ("and", "or", "xor"), op  # associative ops only
    interpret = _interpret(interpret)
    mat = jnp.asarray(mat, jnp.uint32)
    assert mat.ndim == 2 and mat.shape[0] >= 1, mat.shape
    L, C = mat.shape
    if bucket:
        Cp = bucket_cols(C, block_cols)
        Lp = next_pow2(L)
        identity = _ALL_ONES if op == "and" else np.uint32(0)
        if Cp != C:
            mat = jnp.pad(mat, ((0, 0), (0, Cp - C)))
        if Lp != L:
            mat = jnp.concatenate(
                [mat, jnp.full((Lp - L, Cp), identity, jnp.uint32)], axis=0)
        if row_flags is not None:
            pad_flag = _wl.CLEAN1 if op == "and" else _wl.CLEAN0
            row_flags = np.pad(row_flags, ((0, Lp - L), (0, 0)),
                               constant_values=pad_flag)
    first = True
    while mat.shape[0] > 1:
        half = mat.shape[0] // 2
        rfa = rfb = None
        if first and row_flags is not None:
            # word_logical row-pads flags itself (CLEAN0, matching _pad2's
            # zero rows), so any half size works
            rfa, rfb = row_flags[:half], row_flags[half:2 * half]
        red = word_logical(mat[:half], mat[half:2 * half], op,
                           interpret=interpret, block_rows=block_rows,
                           block_cols=block_cols, bucket=bucket,
                           row_flags_a=rfa, row_flags_b=rfb)
        if mat.shape[0] % 2:  # odd row carries to the next round
            red = jnp.concatenate([red, mat[2 * half:]], axis=0)
        mat = red
        first = False
    return mat[0][:C]


def popcount_total(a, interpret: Optional[bool] = None) -> jax.Array:
    a = jnp.asarray(a, jnp.uint32)
    ap, _ = _pad2(a, 8, 1024)
    return _pc.popcount_total(ap, interpret=_interpret(interpret))


def popcount_rows(a, interpret: Optional[bool] = None) -> jax.Array:
    a = jnp.asarray(a, jnp.uint32)
    ap, (R, _) = _pad2(a, 8, 1024)
    return _pc.popcount_rows(ap, interpret=_interpret(interpret))[:R]


def bitpack(bits, interpret: Optional[bool] = None) -> jax.Array:
    """(N, L) bools -> (ceil(N/32), L) uint32 words."""
    bits = jnp.asarray(bits, jnp.bool_)
    N, L = bits.shape
    bp2, (_, _) = _pad2(bits, 1024, 128, fill=False)
    out = _bp.bitpack(bp2, interpret=_interpret(interpret))
    return out[: -(-N // 32), :L]


def block_sqnorms(grad_flat, values_per_block: int = 256,
                  interpret: Optional[bool] = None) -> jax.Array:
    grad_flat = jnp.asarray(grad_flat, jnp.float32)
    n = grad_flat.shape[0]
    npad = -(-n // values_per_block) * values_per_block
    if npad != n:
        grad_flat = jnp.pad(grad_flat, (0, npad - n))
    return _gc.block_sqnorms(grad_flat, values_per_block,
                             interpret=_interpret(interpret))


def topk_block_mask(grad_flat, keep_ratio: float, values_per_block: int = 256,
                    interpret: Optional[bool] = None) -> jax.Array:
    return _gc.topk_block_mask(jnp.asarray(grad_flat, jnp.float32), keep_ratio,
                               values_per_block,
                               interpret=_interpret(interpret))
