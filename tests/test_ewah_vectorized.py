"""Vectorized EWAH run-list path vs the segment-cursor reference oracle.

The contract is *word identity*: for any inputs, the vectorized ops must
produce exactly the words ``binary_op`` (the retained ``_SegCursor`` merge)
produces — not merely the same boolean content — so the compressed streams
stay canonical and cache/equality semantics are preserved.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ewah as ewah_mod
from repro.core.ewah import (ALL_ONES, EWAH, MAX_CLEAN, MAX_LIT, WORD_DTYPE,
                             RunList, _decode_runlist, _emit, _split_literal,
                             and_many, binary_op, or_many, vec_binary_op)

OPS = ("and", "or", "xor", "andnot")


def structured_bits(seed: int, n: int, style: int) -> np.ndarray:
    """Random bitmaps spanning the codec's regimes: uniform noise, clean-run
    dominated, literal fringes, and degenerate all-0 / all-1."""
    rng = np.random.default_rng(seed)
    if style == 0:      # uniform density
        return rng.random(n) < rng.uniform(0, 1)
    if style == 1:      # all zeros
        return np.zeros(n, bool)
    if style == 2:      # all ones
        return np.ones(n, bool)
    # clean runs interleaved with literal stretches (sorted-table shape)
    out = np.zeros(n, bool)
    pos = 0
    while pos < n:
        seg = int(rng.integers(1, max(2, n // 4)))
        kind = rng.integers(0, 3)
        if kind == 1:
            out[pos:pos + seg] = True
        elif kind == 2:
            out[pos:pos + min(seg, n - pos)] = \
                rng.random(min(seg, n - pos)) < 0.5
        pos += seg
    return out


def bitmap_pair_strategy(max_n=4096):
    return st.builds(
        lambda seed, n, sa, sb: (structured_bits(seed, n, sa),
                                 structured_bits(seed + 1, n, sb)),
        st.integers(0, 2**31), st.integers(0, max_n),
        st.integers(0, 3), st.integers(0, 3))


@settings(max_examples=200, deadline=None)
@given(bitmap_pair_strategy())
def test_binary_ops_word_identical_to_cursor_oracle(pair):
    a, b = pair
    A, B = EWAH.from_bool(a), EWAH.from_bool(b)
    for op in OPS:
        ref = binary_op(A, B, op)
        got = vec_binary_op(A, B, op)
        assert got.n_bits == ref.n_bits
        assert np.array_equal(got.words, ref.words), op
        # boolean semantics as a second, independent check
        assert np.array_equal(got.to_bool(), ref.to_bool()), op


@settings(max_examples=60, deadline=None)
@given(st.integers(0, 2**31), st.integers(1, 2048), st.integers(2, 9))
def test_nary_word_identical_to_cursor_folds(seed, n, k):
    mats = [structured_bits(seed + i, n, (seed + i) % 4) for i in range(k)]
    bms = [EWAH.from_bool(m) for m in mats]
    ref_and = bms[0]
    for bm in bms[1:]:
        ref_and = binary_op(ref_and, bm, "and")
    items = list(bms)
    while len(items) > 1:
        items = [binary_op(items[i], items[i + 1], "or")
                 if i + 1 < len(items) else items[i]
                 for i in range(0, len(items), 2)]
    assert np.array_equal(and_many(bms).words, ref_and.words)
    assert np.array_equal(or_many(bms).words, items[0].words)


@settings(max_examples=100, deadline=None)
@given(st.integers(0, 2**31), st.integers(0, 4096), st.integers(0, 3))
def test_count_matches_boolean_popcount(seed, n, style):
    bits = structured_bits(seed, n, style)
    e = EWAH.from_bool(bits)
    assert e.count() == int(bits.sum())
    assert e.count() == e.count()  # memoized second read


def test_zero_row_bitmaps():
    z = EWAH.from_bool(np.zeros(0, bool))
    for op in OPS:
        ref = binary_op(z, z, op)
        got = vec_binary_op(z, z, op)
        assert np.array_equal(got.words, ref.words)
        assert got.n_bits == 0
    assert and_many([z, z]).n_bits == 0
    assert or_many([z, z]).n_bits == 0
    assert z.count() == 0


def test_all_ones_and_all_zero_runs():
    n = 10_000_000  # multi-marker clean runs (MAX_CLEAN splitting)
    one = EWAH.from_bool(np.ones(n, bool))
    zero = EWAH.from_bool(np.zeros(n, bool))
    for op in OPS:
        for x, y in ((one, zero), (zero, one), (one, one), (zero, zero)):
            assert np.array_equal(vec_binary_op(x, y, op).words,
                                  binary_op(x, y, op).words), op
    assert (one | zero).size_words == one.size_words
    assert one.count() == n


def test_unaligned_tail_padding():
    # n_bits not a multiple of 32: pad bits must stay clear through the ops
    for n in (1, 31, 33, 95, 1027):
        rng = np.random.default_rng(n)
        a, b = rng.random(n) < 0.5, rng.random(n) < 0.2
        A, B = EWAH.from_bool(a), EWAH.from_bool(b)
        for op in OPS:
            assert np.array_equal(vec_binary_op(A, B, op).words,
                                  binary_op(A, B, op).words)
        assert (A | B).count() == int((a | b).sum())


def test_runlist_is_memoized_and_canonical():
    rng = np.random.default_rng(7)
    bits = rng.random(5000) < 0.3
    e = EWAH.from_bool(bits)
    rl = e.runlist()
    assert e.runlist() is rl  # memoized
    assert isinstance(rl, RunList)
    assert rl.bounds[0] == 0 and rl.n_words == e.n_words_uncompressed
    # canonical: adjacent intervals differ in kind, literals have no clean words
    assert (np.diff(rl.bounds) > 0).all()
    assert (rl.kinds[1:] != rl.kinds[:-1]).all()
    assert not np.isin(rl.lits, (0, 0xFFFFFFFF)).any()


def test_nary_short_circuits_stay_exact():
    n = 64 * 1024
    a = np.zeros(n, bool); a[:100] = True
    b = np.zeros(n, bool); b[-100:] = True
    bms = [EWAH.from_bool(a), EWAH.from_bool(b),
           EWAH.from_bool(np.ones(n, bool))]
    # AND empties after the first fold; OR saturates with the all-ones operand
    assert and_many(bms).count() == 0
    full = or_many([EWAH.from_bool(np.ones(n, bool))] * 3)
    assert full.count() == n
    ref = binary_op(binary_op(bms[0], bms[1], "and"), bms[2], "and")
    assert np.array_equal(and_many(bms).words, ref.words)


@pytest.mark.parametrize("op", OPS)
def test_result_runlist_reuse(op):
    # results carry their run-list so chained ops skip re-decoding
    rng = np.random.default_rng(3)
    A = EWAH.from_bool(rng.random(3000) < 0.4)
    B = EWAH.from_bool(rng.random(3000) < 0.6)
    out = vec_binary_op(A, B, op)
    assert out._rl is not None
    chained = out & A
    assert np.array_equal(chained.words, binary_op(out, A, "and").words)


@settings(max_examples=80, deadline=None)
@given(st.integers(0, 2**31), st.integers(0, 3000), st.integers(2, 7))
def test_kway_and_many_mixed_operands(seed, n, k):
    """One-pass k-way AND vs the cursor-oracle fold, with degenerate
    operands (all-zero / all-one) mixed in so the short-circuit and
    identity-drop paths are hit alongside the aligned intersection."""
    rng = np.random.default_rng(seed)
    bms = []
    for i in range(k):
        style = int(rng.integers(0, 4))
        bms.append(EWAH.from_bool(structured_bits(seed + 7 * i, n, style)))
    ref = bms[0]
    for bm in bms[1:]:
        ref = binary_op(ref, bm, "and")
    got = and_many(bms)
    assert got.n_bits == ref.n_bits
    assert np.array_equal(got.words, ref.words)


@settings(max_examples=100, deadline=None)
@given(st.integers(0, 2**31), st.integers(0, 4096), st.integers(0, 3))
def test_from_positions_runlist_direct(seed, n, style):
    """``from_positions`` must emit words identical to the dense build and
    come out with its run-list memo already populated (no ``_emit``
    round-trip, no cold decode on first use)."""
    bits = structured_bits(seed, n, style)
    direct = EWAH.from_positions(np.flatnonzero(bits), n)
    dense = EWAH.from_bool(bits)
    assert np.array_equal(direct.words, dense.words)
    assert direct._rl is not None  # memo warm at construction
    assert np.array_equal(direct.runlist().bounds, dense.runlist().bounds)
    assert np.array_equal(direct.to_bool(), bits)


@settings(max_examples=100, deadline=None)
@given(st.integers(0, 2**31), st.integers(0, 4096), st.integers(0, 3))
def test_invert_runlist_direct(seed, n, style):
    """``~`` runs on the run-list: word-identical to the dense complement
    (pad bits clear), memo warm, and an involution on the words."""
    bits = structured_bits(seed, n, style)
    e = EWAH.from_bool(bits)
    inv = ~e
    assert np.array_equal(inv.words, EWAH.from_bool(~bits).words)
    assert inv._rl is not None
    assert np.array_equal((~inv).words, e.words)
    if n:
        assert inv.count() == n - e.count()  # pad bits stayed clear


@settings(max_examples=150, deadline=None)
@given(bitmap_pair_strategy())
def test_and_count_matches_materialized(pair):
    """``and_count`` (the aggregation kernel) must equal the popcount of
    the materialized intersection without building it."""
    a, b = pair
    A, B = EWAH.from_bool(a), EWAH.from_bool(b)
    assert A.and_count(B) == int((a & b).sum())
    assert A.and_count(B) == binary_op(A, B, "and").count()
    assert A.and_count(A) == A.count()


@settings(max_examples=150, deadline=None)
@given(st.integers(0, 2**31), st.integers(0, 4096), st.integers(0, 3))
def test_set_intervals_reconstruct(seed, n, style):
    """Interval view invariants: disjoint, sorted, coalesced, clipped to
    n_bits, and exactly covering the set bits."""
    bits = structured_bits(seed, n, style)
    e = EWAH.from_bool(bits)
    s, t = e.set_intervals()
    assert int((t - s).sum()) == e.count() == int(bits.sum())
    assert np.all(s < t)
    assert np.all(s[1:] > t[:-1])  # disjoint AND coalesced (gap > 0)
    if len(t):
        assert t[-1] <= n
    rec = np.zeros(n, bool)
    for x, y in zip(s, t):
        rec[x:y] = True
    assert np.array_equal(rec, bits)


@settings(max_examples=150, deadline=None)
@given(st.integers(0, 2**31), st.integers(0, 4096), st.integers(0, 3))
def test_vectorized_decode_matches_segments(seed, n, style):
    """The pointer-jumping marker decode must reproduce the segment
    stream's run-list exactly (the old per-marker loop's contract)."""
    from repro.core.ewah import (KIND_CLEAN0, KIND_CLEAN1, KIND_LIT,
                                 _decode_runlist)
    bits = structured_bits(seed, n, style)
    e = EWAH.from_bool(bits)
    rl = _decode_runlist(e.words)
    # rebuild the interval stream from the canonical segment iterator
    kinds, counts, lits = [], [], []
    for seg in e.segments():
        if seg[0] == "run":
            kinds.append(KIND_CLEAN1 if seg[1] else KIND_CLEAN0)
            counts.append(seg[2])
        else:
            kinds.append(KIND_LIT)
            counts.append(len(seg[1]))
            lits.append(seg[1])
    assert rl.kinds.tolist() == kinds
    assert np.diff(rl.bounds).tolist() == counts
    want_lits = (np.concatenate(lits) if lits
                 else np.empty(0, e.words.dtype))
    assert np.array_equal(rl.lits, want_lits)


# -- the dense-word codec: to_words / from_words ----------------------------
# The per-marker and per-segment loops these functions once ran, kept here
# as the oracles the whole-array codec must match word for word.

def loop_to_words(bm: EWAH) -> np.ndarray:
    out = np.empty(bm.n_words_uncompressed, dtype=WORD_DTYPE)
    pos = 0
    for seg in bm.segments():
        if seg[0] == "run":
            _, bit, cnt = seg
            out[pos:pos + cnt] = ALL_ONES if bit else 0
            pos += cnt
        else:
            out[pos:pos + len(seg[1])] = seg[1]
            pos += len(seg[1])
    assert pos == bm.n_words_uncompressed
    return out


def loop_from_words(words: np.ndarray) -> np.ndarray:
    return _emit(_split_literal(np.asarray(words, dtype=WORD_DTYPE)))


def assert_codec_matches_loops(words: np.ndarray, n_bits: int) -> None:
    """``from_words`` against ``_emit(_split_literal(.))``, and ``to_words``
    against the segment loop, both from the marker stream alone (cold) and
    from the memoized run-list."""
    got = EWAH.from_words(words, n_bits)
    want = loop_from_words(words)
    assert got.words.dtype == WORD_DTYPE
    assert np.array_equal(got.words, want)
    cold = EWAH(want, n_bits)
    assert cold._rl is None
    dense = cold.to_words()
    assert np.array_equal(dense, loop_to_words(cold))
    assert np.array_equal(dense, words)
    assert got._rl is not None
    assert np.array_equal(got.to_words(), words)


@settings(max_examples=150, deadline=None)
@given(st.integers(0, 2**31), st.integers(0, 4096), st.integers(0, 3))
def test_to_words_word_identical_to_segment_loop(seed, n, style):
    bits = structured_bits(seed, n, style)
    e = EWAH(EWAH.from_bool(bits).words, n)  # no run-list memo
    assert np.array_equal(e.to_words(), loop_to_words(e))
    assert np.array_equal(e.to_bool(), bits)
    e.runlist()  # memoized: the run-list branch gives the same words
    assert np.array_equal(e.to_words(), loop_to_words(e))


@settings(max_examples=150, deadline=None)
@given(st.integers(0, 2**31), st.integers(0, 4096), st.integers(0, 3))
def test_from_words_word_identical_to_emit(seed, n, style):
    from repro.core.bitpack import pack_bits
    words = pack_bits(structured_bits(seed, n, style))
    assert_codec_matches_loops(words, n)


def _long_clean_run():
    # a clean-one run past MAX_CLEAN words (> 2.1M bits) between literals
    w = np.full(2 * MAX_CLEAN + 7, ALL_ONES, WORD_DTYPE)
    w[0], w[-1] = 0x5, 0xA0000000
    return w, len(w) * 32


def _long_literal_stretch():
    # more than MAX_LIT literal words in a row: continuation markers
    rng = np.random.default_rng(5)
    w = rng.integers(1, 0xFFFFFFFF, 2 * MAX_LIT + 11, dtype=np.uint64)
    return w.astype(WORD_DTYPE), len(w) * 32


def _shard_quantity():
    # the 1.5M-row shard shape at density 1/51: a marker every few words
    rng = np.random.default_rng(51)
    from repro.core.bitpack import pack_bits
    return pack_bits(rng.integers(0, 51, 1_500_000) == 0), 1_500_000


def _unaligned_tail():
    from repro.core.bitpack import pack_bits
    rng = np.random.default_rng(9)
    n = 32 * 1000 + 13
    return pack_bits(rng.random(n) < 0.3), n


@pytest.mark.parametrize("make", [
    _long_clean_run,
    _long_literal_stretch,
    _shard_quantity,
    lambda: (np.zeros(0, WORD_DTYPE), 0),                      # zero rows
    _unaligned_tail,
    lambda: (np.full(3 * MAX_CLEAN, ALL_ONES, WORD_DTYPE),     # all ones
             3 * MAX_CLEAN * 32),
    lambda: (np.zeros(3 * MAX_CLEAN + 1, WORD_DTYPE),          # all zeros
             (3 * MAX_CLEAN + 1) * 32),
], ids=["clean-run-over-max-clean", "literals-over-max-lit",
        "shard-1.5M-density-1-51", "zero-rows", "unaligned-tail",
        "all-ones", "all-zeros"])
def test_codec_regimes_match_loops(make):
    words, n_bits = make()
    assert_codec_matches_loops(words, n_bits)


def test_from_words_memoizes_its_runlist(monkeypatch):
    """``from_words`` hands back the run-list it encoded from, equal to a
    decode of its own words, so ``set_intervals`` and ``count`` never decode
    the re-encoded result again."""
    from repro.core.bitpack import pack_bits
    rng = np.random.default_rng(13)
    bits = rng.random(40_000) < 0.02
    e = EWAH.from_words(pack_bits(bits), len(bits))
    rl, ref = e._rl, _decode_runlist(e.words)
    assert rl is not None
    assert np.array_equal(rl.bounds, ref.bounds)
    assert np.array_equal(rl.kinds, ref.kinds)
    assert np.array_equal(rl.lit_starts, ref.lit_starts)
    assert np.array_equal(rl.lits, ref.lits)

    def no_decode(_words):
        raise AssertionError("the re-encoded result was decoded again")

    monkeypatch.setattr(ewah_mod, "_decode_runlist", no_decode)
    monkeypatch.setattr(ewah_mod, "_decode_words", no_decode)
    s, t = e.set_intervals()
    assert int((t - s).sum()) == e.count() == int(bits.sum())
    assert np.array_equal(e.to_words(), pack_bits(bits))
