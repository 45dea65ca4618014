"""Jit'd public wrappers around the Pallas kernels: one device program a call.

Every wrapper takes ``interpret=None``, resolved by ``interpret_mode()``
from the JAX backend: compiled on a TPU, interpreted on the CPU, refused
anywhere else.  The kernels are written against TPU tiling rules
(multiples of (8, 128) for 32-bit types); ``tests/test_tpu_compile.py``
compiles them for a described v5e chip.

One program a call: what a wrapper does once its operands are on the
device (pads, clean-tile flags, every round of a reduction tree, the final
slice) is a single ``jax.jit`` program, so a call costs one dispatch from
the host, not one per array operation.  ``kops.programs`` counts them.

Shape bucketing (the JIT cold-start fix): ``jax.jit`` compiles one program
per operand shape, so the wrappers pad the word dimension up to
power-of-two multiples of ``block_cols`` (``bucket_cols``) and a
reduction's operand count up to a power of two with the op's identity row,
collapsing the compiled-shape universe to O(log max_words x log
max_operands) programs that are reused across shards, queries, and index
generations.  Callers that already hold bucketed operands can pass
precomputed per-row clean flags (``np_row_flags``) so the sideband is not
recomputed per query — the executor caches them next to the words.
"""
from __future__ import annotations

import functools
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
from repro.core import trace as _trace

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


_IDENTITY = {"and": _ALL_ONES, "or": np.uint32(0), "xor": np.uint32(0)}
_IDENTITY_FLAG = {"and": _wl.CLEAN1, "or": _wl.CLEAN0, "xor": _wl.CLEAN0}


def _pad2(a: jax.Array, br: int, bc: int, fill=0) -> Tuple[jax.Array, Tuple[int, int]]:
    R, C = a.shape
    Rp = -(-R // br) * br
    Cp = -(-C // bc) * bc
    if (Rp, Cp) != (R, C):
        a = jnp.pad(a, ((0, Rp - R), (0, Cp - C)), constant_values=fill)
    return a, (R, C)


def _width(cols: int, block_cols: int, bucket: bool) -> int:
    """The word count a kernel program runs at: the power-of-two bucket, or
    (``bucket=False``) the next whole tile."""
    return bucket_cols(cols, block_cols) if bucket \
        else -(-max(int(cols), 1) // block_cols) * block_cols


def _rows_to_device(rows, width: int) -> tuple:
    """Host word rows as ``(1, width)`` device arrays, one transfer each and
    zero-padded to ``width`` on the host first; their bytes count as
    ``kops.h2d_bytes``."""
    rows = [np.asarray(r, np.uint32).reshape(1, -1) for r in rows]
    if rows[0].shape[1] < width:
        rows = [np.pad(r, ((0, 0), (0, width - r.shape[1]))) for r in rows]
    _trace.add("kops.h2d_bytes", 4 * width * len(rows))
    return tuple(jax.device_put(rows))


def _to_device(x, width: int) -> jax.Array:
    """``x`` as a device uint32 array; a host array is zero-padded to
    ``width`` words first and its bytes count as ``kops.h2d_bytes``."""
    if isinstance(x, jax.Array):
        return jnp.asarray(x, jnp.uint32)
    x = np.asarray(x, np.uint32)
    if x.shape[1] < width:
        x = np.pad(x, ((0, 0), (0, width - x.shape[1])))
    _trace.add("kops.h2d_bytes", x.nbytes)
    return jax.device_put(x)


def _flags_to_device(row_flags, rows: int, pad_flag: int) -> jax.Array:
    """Host per-row flags, filled to ``rows`` rows with ``pad_flag``, on the
    device at one byte a flag (counted as ``kops.h2d_bytes``)."""
    rf = np.asarray(row_flags)
    flags = np.full((rows, rf.shape[1]), pad_flag, np.int8)
    flags[:len(rf)] = rf
    _trace.add("kops.h2d_bytes", flags.nbytes)
    return jax.device_put(flags)


@functools.lru_cache(maxsize=64)
def _identity_row(width: int, op: str) -> jax.Array:
    """One ``(1, width)`` row of ``op``'s identity word, put on the device
    once per width and op (its bytes count then) and shared by every
    reduction that fills its operands up to a power of two with it."""
    row = np.full((1, width), _IDENTITY[op], np.uint32)
    _trace.add("kops.h2d_bytes", row.nbytes)
    return jax.device_put(row)


# -- inside the programs (traced once per shape) ------------------------------
def _pad_cols(a: jax.Array, width: int) -> jax.Array:
    return a if a.shape[1] == width \
        else jnp.pad(a, ((0, 0), (0, width - a.shape[1])))


def _flag(all0: jax.Array, all1: jax.Array) -> jax.Array:
    return jnp.where(all0, _wl.CLEAN0,
                     jnp.where(all1, _wl.CLEAN1, _wl.DIRTY)).astype(jnp.int32)


def _row_flags_dev(words: jax.Array, block_cols: int) -> jax.Array:
    """(R, C) words -> (R, C/block_cols) per-row DIRTY/CLEAN0/CLEAN1 flags,
    as ``np_row_flags`` makes them on the host."""
    t = words.reshape(words.shape[0], -1, block_cols)
    return _flag(jnp.all(t == 0, axis=-1), jnp.all(t == _ALL_ONES, axis=-1))


def _flags_after(op: str, fa: jax.Array, fb: jax.Array) -> jax.Array:
    """Row flags of ``op(a, b)`` from those of ``a`` and ``b``: a clean
    result is named clean only where the operands' flags prove it; the rest
    is DIRTY, which only means "read the words"."""
    c0a, c0b = fa == _wl.CLEAN0, fb == _wl.CLEAN0
    c1a, c1b = fa == _wl.CLEAN1, fb == _wl.CLEAN1
    if op == "and":
        out = jnp.where(c0a | c0b, _wl.CLEAN0,
                        jnp.where(c1a & c1b, _wl.CLEAN1, _wl.DIRTY))
    elif op == "or":
        out = jnp.where(c1a | c1b, _wl.CLEAN1,
                        jnp.where(c0a & c0b, _wl.CLEAN0, _wl.DIRTY))
    else:  # xor: two clean operands make a clean result
        clean = (fa != _wl.DIRTY) & (fb != _wl.DIRTY)
        out = jnp.where(clean, jnp.where(fa == fb, _wl.CLEAN0, _wl.CLEAN1),
                        _wl.DIRTY)
    return out.astype(jnp.int32)


def _tile_flags(rf: jax.Array, block_rows: int) -> jax.Array:
    """(R, gc) row flags -> tile flags of the rows padded to whole tiles.

    A tile is clean where all its real rows share one clean flag.  The pad
    rows repeat the last real row's flag: a clean tile's rows all take its
    constant, and the pad rows' results are sliced off, so their words are
    never needed."""
    R = rf.shape[0]
    Rt = -(-R // block_rows) * block_rows
    if Rt != R:
        rf = jnp.pad(rf, ((0, Rt - R), (0, 0)), mode="edge")
    t = rf.reshape(Rt // block_rows, block_rows, -1)
    return _flag(jnp.all(t == _wl.CLEAN0, axis=1),
                 jnp.all(t == _wl.CLEAN1, axis=1))


def _logical_rows(a, b, fa, fb, op: str, block_rows: int, block_cols: int,
                  interpret: bool) -> jax.Array:
    """``word_logical`` on (R, C) words with their (R, C/block_cols) row
    flags; the rows pad to whole tiles and the pad is sliced off."""
    R = a.shape[0]
    pad = ((0, -(-R // block_rows) * block_rows - R), (0, 0))
    out = _wl.word_logical(jnp.pad(a, pad), jnp.pad(b, pad),
                           _tile_flags(fa, block_rows),
                           _tile_flags(fb, block_rows), op=op,
                           block_rows=block_rows, block_cols=block_cols,
                           interpret=interpret)
    return out[:R]


@functools.partial(jax.jit, static_argnames=(
    "op", "width", "cols", "block_rows", "block_cols", "interpret"))
def _logical_program(a, b, *, op, width, cols, block_rows, block_cols,
                     interpret):
    """``word_logical``'s whole device side: pad, flags, kernel, slice."""
    a, b = _pad_cols(a, width), _pad_cols(b, width)
    return _logical_rows(a, b, _row_flags_dev(a, block_cols),
                         _row_flags_dev(b, block_cols), op, block_rows,
                         block_cols, interpret)[:, :cols]


@functools.partial(jax.jit, static_argnames=(
    "op", "cols", "block_rows", "block_cols", "interpret"))
def _reduce_program(rows, row_flags, *, op, cols, block_rows, block_cols,
                    interpret):
    """``logical_reduce``'s whole device side as one program: stack the
    rows, then halve them a round at a time with ``word_logical`` (an odd
    row carries to the next round).  The first round reads the given row
    flags, or flags made here; later rounds take theirs from
    ``_flags_after``, with no pass over the words."""
    mat = jnp.concatenate(rows, axis=0)
    rf = _row_flags_dev(mat, block_cols) if row_flags is None \
        else row_flags.astype(jnp.int32)
    while mat.shape[0] > 1:
        half = mat.shape[0] // 2
        a, b = mat[:half], mat[half:2 * half]
        fa, fb = rf[:half], rf[half:2 * half]
        red = _logical_rows(a, b, fa, fb, op, block_rows, block_cols,
                            interpret)
        red_flags = _flags_after(op, fa, fb)
        if mat.shape[0] % 2:
            red = jnp.concatenate([red, mat[2 * half:]], axis=0)
            red_flags = jnp.concatenate([red_flags, rf[2 * half:]], axis=0)
        mat, rf = red, red_flags
    return mat[0, :cols]


# -- the wrappers ---------------------------------------------------------------
def word_logical(a, b, op: str = "and", interpret: Optional[bool] = None,
                 block_rows: int = 8, block_cols: int = 1024,
                 bucket: bool = True) -> jax.Array:
    """Word-aligned logical op over (L, n_words) uint32 arrays.

    Dispatches the clean-tile-skipping kernel — the device-side equivalent
    of EWAH's Lemma 2 — as one device program, which makes the operands'
    clean-tile flags itself.  With ``bucket`` (default) the word dimension
    pads to a power-of-two bucket so one compiled program serves every word
    count in the bucket.
    """
    assert op in _wl.OPS, op
    with _trace.span("kops.transfer_in"):
        cols = a.shape[1]
        width = _width(cols, block_cols, bucket)
        a, b = _to_device(a, width), _to_device(b, width)
    with _trace.span("kops.launch"):
        _trace.add("kops.programs", 1)
        return _logical_program(a, b, op=op, width=width, cols=cols,
                                block_rows=block_rows, block_cols=block_cols,
                                interpret=_interpret(interpret))


def logical_reduce(mat, op: str = "and", interpret: Optional[bool] = None,
                   block_rows: int = 8, block_cols: int = 1024,
                   bucket: bool = True,
                   row_flags: Optional[np.ndarray] = None) -> jax.Array:
    """Reduce L host word rows to one: ``mat`` is an (L, n_words) uint32
    matrix, or a sequence of L word rows of one length.

    Tree reduction: each round halves the operand count by running the
    clean-tile-skipping ``word_logical`` kernel on the two halves, so an
    L-way AND/OR costs ceil(log2 L) kernel calls over ever-smaller stacks —
    the dense executor path for n-ary query nodes.  The whole tree is one
    device program (``_reduce_program``); the rows go to the device one
    buffer each, so the program is keyed by the padded row count, not L.

    With ``bucket`` (default) the words pad to a power-of-two column bucket
    and the rows to a power of two Lp with the op's identity row (all-ones
    for AND, zero for OR/XOR), kept on the device (``_identity_row``), so
    every round halves exactly and one compiled program serves every operand
    count up to Lp.  ``row_flags`` is the optional (L, cols/block_cols)
    precomputed clean sideband of the (bucketed) input rows; the first round
    reads it, later rounds derive theirs from it.  Its DIRTY flags count as
    ``kops.dirty_tile_bytes``.
    """
    assert op in ("and", "or", "xor"), op  # associative ops only
    interpret = _interpret(interpret)
    with _trace.span("kops.transfer_in"):
        L = len(mat)
        assert L >= 1, L
        C = len(mat[0])
        width = _width(C, block_cols, bucket)
        Lp = next_pow2(L) if bucket else L
        rows = _rows_to_device(mat, width) \
            + (_identity_row(width, op),) * (Lp - L)
        flags = None
        if row_flags is not None:
            assert np.shape(row_flags) == (L, width // block_cols), \
                (np.shape(row_flags), L, width)
            _trace.add("kops.dirty_tile_bytes", 4 * block_cols * int(
                np.count_nonzero(np.asarray(row_flags) == _wl.DIRTY)))
            flags = _flags_to_device(row_flags, Lp, _IDENTITY_FLAG[op])
    with _trace.span("kops.launch"):
        _trace.add("kops.programs", 1)
        return _reduce_program(rows, flags, op=op, cols=C,
                               block_rows=block_rows, block_cols=block_cols,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames="interpret")
def _popcount_total_program(a, *, interpret):
    return _pc.popcount_total(_pad2(a, 8, 1024)[0], interpret=interpret)


@functools.partial(jax.jit, static_argnames="interpret")
def _popcount_rows_program(a, *, interpret):
    return _pc.popcount_rows(_pad2(a, 8, 1024)[0],
                             interpret=interpret)[:a.shape[0]]


@functools.partial(jax.jit, static_argnames="interpret")
def _bitpack_program(bits, *, interpret):
    N, L = bits.shape
    out = _bp.bitpack(_pad2(bits, 1024, 128, fill=False)[0],
                      interpret=interpret)
    return out[: -(-N // 32), :L]


def popcount_total(a, interpret: Optional[bool] = None) -> jax.Array:
    interpret = _interpret(interpret)
    _trace.add("kops.programs", 1)
    return _popcount_total_program(jnp.asarray(a, jnp.uint32),
                                   interpret=interpret)


def popcount_rows(a, interpret: Optional[bool] = None) -> jax.Array:
    interpret = _interpret(interpret)
    _trace.add("kops.programs", 1)
    return _popcount_rows_program(jnp.asarray(a, jnp.uint32),
                                  interpret=interpret)


def bitpack(bits, interpret: Optional[bool] = None) -> jax.Array:
    """(N, L) bools -> (ceil(N/32), L) uint32 words."""
    interpret = _interpret(interpret)
    _trace.add("kops.programs", 1)
    return _bitpack_program(jnp.asarray(bits, jnp.bool_), interpret=interpret)


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
