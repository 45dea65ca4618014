"""Lemmas 1/2: compressed logical ops scale with non-zero words, not n_bits.

Also times the Pallas word_logical kernel (interpret mode — correctness
path; its TPU timings come from the chip benchmark) vs the jnp oracle,
and compares EWAH vs WAH compressed sizes across densities.
"""
from __future__ import annotations

import numpy as np

from repro.core import EWAH, WAH
from repro.kernels import ops, ref

from .common import emit, time_call


def run():
    rng = np.random.default_rng(0)
    # Lemma 2: fixed n_bits, growing set-bit counts -> time grows ~linearly
    n = 1 << 22
    for density in (1e-5, 1e-4, 1e-3, 1e-2):
        a = rng.random(n) < density
        b = rng.random(n) < density
        A, B = EWAH.from_bool(a), EWAH.from_bool(b)
        us = time_call(lambda: A & B, repeats=5)
        emit(f"lemma2_and_density{density:g}", us,
             f"nonzero_words={A.size_words + B.size_words}")

    # EWAH vs WAH sizes (paper §2.3: EWAH bounded expansion, WAH 32/31)
    for density in (1e-4, 1e-2, 0.5):
        bits = rng.random(1 << 20) < density
        e, w = EWAH.from_bool(bits), WAH.from_bool(bits)
        emit(f"ewah_vs_wah_density{density:g}", 0.0,
             f"ewah_words={e.size_words};wah_words={w.size_words};"
             f"ratio={e.size_words / max(w.size_words, 1):.3f}")

    # kernel vs oracle timing (CPU interpret mode)
    a = rng.integers(0, 2**32, size=(64, 4096), dtype=np.uint32)
    b = rng.integers(0, 2**32, size=(64, 4096), dtype=np.uint32)
    a[:32] = 0  # half the tiles clean
    import jax
    ja, jb = jax.numpy.asarray(a), jax.numpy.asarray(b)
    k_us = time_call(lambda: ops.word_logical(ja, jb, "and").block_until_ready(),
                     repeats=3)
    r_us = time_call(lambda: ref.word_logical(ja, jb, "and").block_until_ready(),
                     repeats=3)
    emit("kernel_word_logical_interpret", k_us, f"jnp_oracle_us={r_us:.0f}")


if __name__ == "__main__":
    run()
