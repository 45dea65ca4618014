"""Physical executor: run a plan over EWAH bitmaps or Pallas kernels.

Per-node backend choice (Roaring's lesson, arXiv:1402.6407 — pick the
physical representation per operation, by density, not globally): an n-ary
AND/OR whose operands are mostly *dense* (compressed size close to the
uncompressed word count, so EWAH's run-skipping buys nothing) is offloaded
to the Pallas ``word_logical`` kernel as a dense tree reduction; sparse
operands stay on the compressed EWAH path — the vectorized run-list ops in
``repro.core.ewah`` — where cost is O(non-zero words) (Lemma 2).  The
decision reads the operands' actual compressed sizes, which the index
already tracks, against the **measured** crossover density from
``repro.core.cost_model`` (calibrated per machine; static 0.5 fallback
when no calibration has run).

Kernel-path operands are padded to power-of-two word-count buckets and
cached *with* their per-row clean-tile flags (``("dense", col, bid,
bucket)`` entries), so one compiled Pallas program serves every operand
shape in a bucket and the clean sideband is computed once per bitmap, not
once per query (see ``repro.kernels.ops``).

One ``Executor`` evaluates plans over one ``BitmapIndex`` (a shard) against
an operand cache: physical bitmaps (and their bucketed dense decompressions
+ flags, when the kernel path is taken) are loaded once and reused across
every plan run against the same cache (``repro.core.statement.QueryBatch``).
Constant plan nodes memoize their full-length bitmaps in the same cache.
Statements, their fan-out over shards and the merge of per-shard partials
live in ``repro.core.statement``.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

from . import cost_model as _cm
from . import measures as _ms
from . import trace as _trace
from .ewah import EWAH, and_many, or_many
from .index import BitmapIndex
from .planner import (PAgg, PAnd, PBitmap, PConst, PCount, PDiff,
                      PGroupAgg, PGroupCount, PNot, POr, PPinned, PlanNode)

# the historical static threshold, kept as the uncalibrated fallback; the
# live value comes from ``repro.core.cost_model`` (measured crossover when a
# calibration has been persisted on this machine)
DENSE_THRESHOLD = _cm.DEFAULT_DENSE_THRESHOLD

Backend = str  # "auto" | "ewah" | "kernel"

# caps on memoized subexpression results per operand cache: leaf entries
# are bounded by the index itself, but composite results are keyed by query
# shape, and a long-lived cache (a process-pool worker's, a persistent
# batch cache) serving a varied stream would otherwise grow without bound —
# both an entry cap and a byte budget over the cached EWAH payloads apply
SUB_CACHE_ENTRIES = 512
SUB_CACHE_BYTES = 32 << 20
_SUB_ORDER_KEY = ("sub_order",)
_SUB_BYTES_KEY = ("sub_bytes",)


def _const_bitmap(index: BitmapIndex, value: bool,
                  cache: Optional[Dict] = None) -> EWAH:
    """All-ones / all-zeros bitmap over the index's rows, memoized per
    (index rows, value) in the operand cache — constant plan nodes used to
    rebuild a full-length EWAH on every evaluation."""
    key = ("const", index.n_rows, value)
    if cache is not None:
        bm = cache.get(key)
        if bm is not None:
            return bm
    bm = EWAH.from_bool(np.full(index.n_rows, value, dtype=bool))
    if cache is not None:
        cache[key] = bm
    return bm


class Executor:
    # process-wide count of plan nodes dispatched to the Pallas kernels
    # (a smoke run asserts the device path was taken at all)
    kernel_dispatches = 0

    def __init__(self, index: BitmapIndex, backend: Backend = "auto",
                 cache: Optional[Dict] = None,
                 dense_threshold: Optional[float] = None):
        assert backend in ("auto", "ewah", "kernel"), backend
        self.index = index
        self.backend = backend
        self.cache = cache if cache is not None else {}
        # None -> the process cost model (calibrated crossover if available)
        self.dense_threshold = (
            _cm.get_default().dense_threshold
            if dense_threshold is None else dense_threshold)
        # subexpression-sharing accounting: composite plan nodes memoize
        # their results in ``cache`` under their canonical plan key, so a
        # subtree repeated across the statements of a batch (the group-by
        # fan-out's shared filter, a dashboard's common clause) evaluates
        # once; these counters make the sharing testable/observable
        self.sub_hits = 0
        self.sub_misses = 0

    # -- operand loading (shared across a batch via ``cache``) ------------
    def _load(self, node: PBitmap) -> EWAH:
        key = ("bm", node.col, node.bitmap_id)
        bm = self.cache.get(key)
        if bm is None:
            with _trace.span("executor.load"):
                bm = self.index.bitmap(node.col, node.bitmap_id)
            self.cache[key] = bm
        return bm

    def _dense_operand(self, node: PlanNode, bm: EWAH):
        """(bucket-padded words, per-row clean flags) for the kernel path.

        Both are cached per bitmap *and bucket* so repeated dense queries
        decompress once and never recompute the clean-tile sideband; the
        power-of-two bucket keeps the compiled-kernel universe small (see
        ``repro.kernels.ops``)."""
        from repro.kernels import ops as kops  # lazy: jax only on this path
        cp = kops.bucket_cols(bm.n_words_uncompressed)
        if isinstance(node, PBitmap):
            key = ("dense", node.col, node.bitmap_id, cp)
            hit = self.cache.get(key)
            if hit is None:
                hit = self._pad_and_flags(bm, cp)
                self.cache[key] = hit
            return hit
        return self._pad_and_flags(bm, cp)

    @staticmethod
    def _pad_and_flags(bm: EWAH, cp: int):
        from repro.kernels import ops as kops
        with _trace.span("executor.densify"):
            w = bm.to_words()
            if len(w) < cp:
                w = np.pad(w, (0, cp - len(w)))
            if bm._cont is not None and bm._words is None:
                # container-backed: flags come off the chunk directory
                # (EMPTY/FULL/ARRAY chunks never scan words), bit-identical
                # to below
                return w, kops.container_row_flags(bm._cont, len(w))
            return w, kops.np_row_flags(w)

    # -- evaluation --------------------------------------------------------
    def run(self, node: PlanNode) -> EWAH:
        """Evaluate a plan tree to an EWAH result.

        The top-level statement *reads* the subexpression cache (it may be
        a subtree of an earlier statement) but does not write its own
        result into it — whole-result caching belongs to the dedicated
        result LRUs, and an operand cache that also memoized roots would
        silently turn repeat-latency measurements into dictionary lookups.
        Strict subtrees are cached (see ``_run``)."""
        return self._run(node, write=False)

    def _run(self, node: PlanNode, write: bool = True) -> EWAH:
        if isinstance(node, PConst):
            return _const_bitmap(self.index, node.value, self.cache)
        if isinstance(node, PBitmap):
            return self._load(node)
        if isinstance(node, PPinned):
            # an externally-evaluated bitmap (live-ingest tombstone masks);
            # its ckey is None, so no enclosing subtree caches around it
            return node.bitmap
        # composite subtrees memoize by canonical plan key: a subexpression
        # shared across a batch's statements (same ``ckey``, possibly under
        # commutative reordering) is evaluated exactly once per cache
        key = ("sub", node.ckey) if node.ckey is not None else None
        if key is not None:
            hit = self.cache.get(key)
            if hit is not None:
                self.sub_hits += 1
                return hit
            self.sub_misses += 1
        bm = self._run_composite(node)
        if key is not None and write:
            # FIFO-bounded by entries *and* result bytes: the eviction
            # bookkeeping lives in the cache dict itself so the bounds
            # follow the cache's lifetime, not the (per-call) executor's.
            # Races on a shared dict are as benign as the rest of the
            # operand cache — worst case a subtree recomputes once.
            order = self.cache.setdefault(_SUB_ORDER_KEY, [])
            if key not in self.cache:
                order.append(key)
                self.cache[key] = bm
                total = self.cache.get(_SUB_BYTES_KEY, 0) + bm.size_bytes
                while order and (len(order) > SUB_CACHE_ENTRIES
                                 or total > SUB_CACHE_BYTES):
                    old = self.cache.pop(order.pop(0), None)
                    if old is not None:
                        total -= old.size_bytes
                self.cache[_SUB_BYTES_KEY] = max(total, 0)
            else:
                self.cache[key] = bm
        return bm

    def _run_composite(self, node: PlanNode) -> EWAH:
        if isinstance(node, PNot):
            child = self._run(node.child)
            with _trace.span("executor.ewah_logical"):
                return ~child
        if isinstance(node, PDiff):
            return self._run_diff(node)
        assert isinstance(node, (PAnd, POr))
        op = "and" if isinstance(node, PAnd) else "or"
        children = [(ch, self._run(ch)) for ch in node.children]
        if self._use_kernel([bm for _, bm in children]):
            return self._reduce_kernel(children, op)
        bms = [bm for _, bm in children]
        with _trace.span("executor.ewah_logical"):
            return and_many(bms) if op == "and" else or_many(bms)

    # -- aggregation (compressed domain) -----------------------------------
    def run_count(self, node: PCount) -> int:
        """COUNT(*): the filter's memoized compressed-domain popcount —
        no row ids, no result materialization."""
        child = node.child
        if isinstance(child, PConst):
            return self.index.n_rows if child.value else 0
        # the filter is a *subexpression* of the count statement: cached,
        # so a row query or group-by over the same filter reuses it
        bm = self._run(child)
        with _trace.span("executor.aggregate"):
            return bm.count()

    # a group bitmap whose literal pool would expand to far more intervals
    # than the filter exposes is cheaper to intersect pairwise: past this
    # expansion-to-filter-intervals ratio the run-aligned
    # ``EWAH.and_count`` beats contributing the (huge) expansion to the
    # batched coverage pass — per query, cold or warm
    LIT_INTERVAL_CUTOFF = 4

    # a grouping column is ranked at the filter's rows (``_rank_catalog``)
    # when ``card x (n_words + F)`` is below this share of its bitmaps'
    # compressed words times ``log2(2 + the filter's intervals)``, the
    # interval path's cost (``_interval_catalog``).  Measured on one CPU
    # over 1.5M-row SSB shards (PERF.md): unsorted Q3.1 and Q4.2 columns
    # read 0.10-0.28 and rank 3-8x faster; a lex-sorted table's most
    # fragmented column under Q4.2 reads 0.88 and keeps intervals, 6%
    # faster there, as every unfiltered column does (22 and up)
    RANK_CUTOFF = 0.5

    def run_group_count(self, node: PGroupCount) -> np.ndarray:
        """Per-value counts of one column under the node's filter.

        Without a filter each group is its bitmap's memoized popcount.
        With one, the filter evaluates once (shared across the whole
        fan-out through the operand cache) and every group intersects it in
        the compressed domain, by one of two kernels: run-dominated bitmaps
        (the sorted-table case) contribute their set-bit intervals —
        clean-one runs plus literal expansions, memoized per bitmap — to a
        batch scored against the filter's interval coverage function in two
        vectorized ``searchsorted`` passes over all groups at once;
        literal-heavy bitmaps, whose interval expansion would approach one
        interval per set bit, use the pairwise ``EWAH.and_count`` (aligned
        run-lists, popcount without materializing the AND).  Nothing is
        decompressed to rows and no result bitmap exists, per group or
        globally.
        """
        out = np.zeros(len(node.groups), dtype=np.int64)
        filt = node.filter
        if isinstance(filt, PConst):
            if not filt.value:
                return out
            filt = None
        if filt is None:
            with _trace.span("executor.aggregate"):
                for g, gn in enumerate(node.groups):
                    if isinstance(gn, PConst):
                        out[g] = self.index.n_rows if gn.value else 0
                    else:
                        out[g] = self._run(gn).count()
            return out
        fbm = self._run(filt)
        with _trace.span("executor.aggregate"):
            return self._group_count(node, fbm, out)

    def _group_count(self, node: PGroupCount, fbm: EWAH,
                     out: np.ndarray) -> np.ndarray:
        """``run_group_count`` once its filter is evaluated to ``fbm``."""
        # the filter always takes the interval view, even when
        # literal-heavy: its expansion is paid once (memoized on the EWAH,
        # which the subexpression cache keeps alive) and the per-query
        # coverage passes scan *group* intervals with only a log factor in
        # the filter's interval count — whereas escaping a fragmented
        # filter to pairwise ``and_count`` costs O(filter runs) per group,
        # which is catastrophic for high-cardinality group-bys
        fs, fe = fbm.set_intervals()
        if len(fs) == 0:
            return out
        starts, ends, gids = [], [], []
        pair_budget = self.LIT_INTERVAL_CUTOFF * (len(fs) + 32)
        for g, gn in enumerate(node.groups):
            gbm = self._run(gn)
            rl = gbm.runlist()
            # 32 * literal words bounds the group's expanded interval count
            if 32 * len(rl.lits) > pair_budget + rl.n_intervals:
                out[g] = fbm.and_count(gbm)
                continue
            s, e = gbm.set_intervals()
            if len(s):
                starts.append(s)
                ends.append(e)
                gids.append(np.full(len(s), g, dtype=np.int64))
        if not starts:
            return out
        S = np.concatenate(starts)
        E = np.concatenate(ends)
        G = np.concatenate(gids)
        w = _interval_coverage(fs, fe, E) - _interval_coverage(fs, fe, S)
        out += np.bincount(G, weights=w,
                           minlength=len(node.groups)).astype(np.int64)
        return out

    def _filter(self, filt: Optional[PlanNode]):
        """Evaluate a filter node: its EWAH, or ``True``/``False`` for all
        or no rows (a ``None`` filter covers all rows)."""
        if filt is None:
            return True
        if isinstance(filt, PConst):
            return filt.value
        return self._run(filt)

    def _filter_intervals(self, f):
        """Set-bit intervals of an evaluated filter (``_filter``); empty
        arrays for an all-false filter."""
        if f is True:
            n = self.index.n_rows
            if not n:
                return (np.empty(0, dtype=np.int64),
                        np.empty(0, dtype=np.int64))
            return (np.asarray([0], dtype=np.int64),
                    np.asarray([n], dtype=np.int64))
        if f is False:
            return (np.empty(0, dtype=np.int64),
                    np.empty(0, dtype=np.int64))
        return f.set_intervals()

    def run_agg(self, node: PAgg):
        """Scalar ``(sum, count, min, max)`` of a measure under the node's
        filter: the filter's run intervals slice the mmap'd measure array
        directly (one gather, three reductions) — no row ids, no result
        bitmap, no row reconstruction."""
        values = self.index.measure(node.measure)
        f = self._filter(node.filter)
        with _trace.span("executor.aggregate"):
            fs, fe = self._filter_intervals(f)
            return _ms.reduce_intervals(values, fs, fe)

    def run_group_agg(self, node: PGroupAgg) -> Dict:
        """Grouped aggregates over one or more columns in the filtered
        domain.

        The filter's intervals define a dense coordinate space of
        ``count(filter)`` positions; the measure is gathered into it once
        and prefix-summed, so every segment's sum is one subtraction and
        its min/max one segmented ``reduceat``.  Each grouping column's
        rank bitmaps *partition* the rows (every row holds exactly one
        value), so their interval images partition the filtered domain.
        One sweep serves any number of columns: the *elementary segments*
        start wherever any column changes rank, each is homogeneous in
        every column, and each is binned into its row-major cell of the
        dense ``[card(c0), ..., card(cn-1)]`` cube — cost O(selected rows
        + intervals), never O(prod(cards) * rows).
        """
        f = self._filter(node.filter)
        with _trace.span("executor.aggregate"):
            return self._group_agg(node, f)

    def _group_agg(self, node: PGroupAgg, f) -> Dict:
        """``run_group_agg`` once its filter is evaluated to ``f``."""
        _trace.add("executor.group_aggs", 1)
        cards = tuple(len(g) for g in node.groups)
        name = node.measure
        values = self.index.measure(name) if name is not None else None
        dt = _ms.measure_dtype_str(values) if values is not None else None
        out = _ms.empty_group_agg(node.cols, cards, name, dt)
        fs, fe = self._filter_intervals(f)
        if not len(fs):
            return out
        fvals = _ms.gather(values, fs, fe) if values is not None else None
        with _trace.span("executor.group_sweep"):
            self._group_sweep(node.groups, cards, fs, fe, fvals, out)
        return out

    def _group_sweep(self, groups, cards, fs, fe, fvals, out: Dict) -> None:
        """Bin the elementary segments of the grouping columns' partitions
        of the filtered domain ``[fs, fe)`` into ``out``'s cells."""
        F = int((fe - fs).sum())
        # per-column catalogs in filtered coordinates: the segment starts,
        # ascending, and each segment's rank (a column's segments are
        # disjoint and cover [0, F), so a segment ends where the next starts)
        catalogs = []
        mapped = ranked = 0
        pos = None
        for col_groups in groups:
            bms = [self._run(gn) for gn in col_groups]
            if self._rank_at_rows(bms, F, len(fs)):
                if pos is None:
                    pos = _ms.interval_positions(fs, fe)
                starts, ranks = _rank_catalog(bms, pos)
                mapped += len(starts)
                ranked += F
            else:
                starts, ranks, n = _interval_catalog(bms, fs, fe)
                mapped += n
            if not len(starts):
                break
            catalogs.append((starts, ranks))
        _trace.add("executor.group_intervals", mapped)
        _trace.add("executor.group_rows_ranked", ranked)
        if len(catalogs) < len(groups):
            return  # a partition with no coverage means F == 0
        _bin_segments(catalogs, cards, F, fvals, out)

    def _rank_at_rows(self, bms: Sequence[EWAH], F: int, n_fs: int) -> bool:
        """Whether to rank a column at the filter's ``F`` rows
        (``_rank_catalog``) rather than map its bitmaps' intervals into the
        filter's ``n_fs`` intervals (``_interval_catalog``).  Ranking reads
        every bitmap's words and one word per filtered row, ``card x
        (n_words + F)``, whatever the compression; the intervals grow with
        the bitmaps' compressed words, and each is searched among the
        filter's intervals."""
        n_words = bms[0].n_words_uncompressed
        size = sum(bm.size_words for bm in bms)
        return (len(bms) * (n_words + F)
                < self.RANK_CUTOFF * size * np.log2(2 + n_fs))

    def _run_diff(self, node: PDiff) -> EWAH:
        """AND(pos) \\ OR(neg) via EWAH's native andnot — negated operands
        never materialize their complements."""
        pos = [(ch, self._run(ch)) for ch in node.pos]
        neg = [(ch, self._run(ch)) for ch in node.neg]
        if self._use_kernel([bm for _, bm in pos + neg]):
            from repro.kernels import ops as kops
            self._count_dispatch()
            pw, pf = zip(*[self._dense_operand(n, bm) for n, bm in pos])
            nw, nf = zip(*[self._dense_operand(n, bm) for n, bm in neg])
            with _trace.span("executor.kernel"):
                a = kops.logical_reduce(pw, op="and", row_flags=np.stack(pf))
                b = kops.logical_reduce(nw, op="or", row_flags=np.stack(nf))
                out = self._fetch(kops.word_logical(a[None, :], b[None, :],
                                                    "andnot"))[0]
            return self._reencode(out, pos[0][1])
        with _trace.span("executor.ewah_logical"):
            acc = and_many([bm for _, bm in pos])
            for _, bm in neg:
                acc = acc.andnot(bm)
            return acc

    def _use_kernel(self, bms: Sequence[EWAH]) -> bool:
        if self.backend == "ewah":
            return False
        n_words = bms[0].n_words_uncompressed
        if n_words == 0:
            # zero-row operands (e.g. an empty shard): nothing to reduce
            # densely, and Pallas rejects zero-size blocks
            return False
        if self.backend == "kernel":
            return True
        density = sum(bm.size_words for bm in bms) / (len(bms) * n_words)
        return len(bms) >= 2 and density >= self.dense_threshold

    def _reduce_kernel(self, children, op: str) -> EWAH:
        from repro.kernels import ops as kops  # lazy: jax only on this path
        self._count_dispatch()
        ws, fs = zip(*[self._dense_operand(node, bm) for node, bm in children])
        with _trace.span("executor.kernel"):
            out = self._fetch(kops.logical_reduce(ws, op=op,
                                                  row_flags=np.stack(fs)))
        return self._reencode(out, children[0][1])

    @staticmethod
    def _count_dispatch() -> None:
        # shard threads dispatch concurrently, and ``+=`` on the class
        # attribute is a read-modify-write: it takes the counters' lock
        with _trace.counter_lock:
            Executor.kernel_dispatches += 1

    @staticmethod
    def _fetch(out) -> np.ndarray:
        """A kernel result copied to the host (waits for the device)."""
        with _trace.span("executor.fetch"):
            host = np.asarray(out)
        _trace.add("kops.d2h_bytes", host.nbytes)
        return host

    @staticmethod
    def _reencode(words: np.ndarray, like: EWAH) -> EWAH:
        """Dense result words back to an EWAH of ``like``'s length."""
        with _trace.span("executor.reencode"):
            return EWAH.from_words(words[:like.n_words_uncompressed],
                                   like.n_bits)


def _interval_coverage(fs: np.ndarray, fe: np.ndarray,
                       xs: np.ndarray) -> np.ndarray:
    """Covered length below each ``x`` of the sorted disjoint intervals
    ``[fs, fe)`` — the filter's prefix-popcount function, evaluated for all
    group-interval endpoints in one ``searchsorted`` pass."""
    pref = np.concatenate(([0], np.cumsum(fe - fs)))
    i = np.searchsorted(fs, xs, side="right") - 1
    i0 = np.maximum(i, 0)
    inside = np.clip(xs - fs[i0], 0, fe[i0] - fs[i0])
    return np.where(i >= 0, pref[i0] + inside, 0)


def _interval_catalog(bms: Sequence[EWAH], fs: np.ndarray,
                      fe: np.ndarray):
    """A grouping column's catalog from its bitmaps' set-bit intervals:
    ``(starts, ranks, mapped)``, every interval mapped into the filter's
    coordinates ``[fs, fe)`` and those that meet it kept.  Cheap where a
    bitmap is a few long runs (a sorted table)."""
    ss, rs = [], []
    mapped = 0
    for g, bm in enumerate(bms):
        s, e = bm.set_intervals()
        if not len(s):
            continue
        mapped += len(s)
        cs = _ms.interval_coverage(fs, fe, s)
        ce = _ms.interval_coverage(fs, fe, e)
        keep = ce > cs
        if keep.any():
            ss.append(cs[keep])
            rs.append(np.full(int(keep.sum()), g, dtype=np.int64))
    if not ss:
        return np.empty(0, np.int64), np.empty(0, np.int64), mapped
    S = np.concatenate(ss)
    R = np.concatenate(rs)
    order = np.argsort(S, kind="stable")
    return S[order], R[order], mapped


def _rank_catalog(bms: Sequence[EWAH], pos: np.ndarray):
    """A grouping column's catalog from its bitmaps' bits at the filter's
    row positions ``pos``: ``(starts, ranks)``, a start wherever the rank
    changes along the filtered rows.  The bitmaps partition the rows, so a
    row's rank is the sum of ``g`` over the bitmaps ``g`` that hold it.
    Costs ``card x (n_words + len(pos))`` whatever the compression: cheap
    where literal words would expand into about one interval per set bit
    (an unsorted table)."""
    widx = pos >> 5
    shift = (pos & 31).astype(np.uint32)
    rank = np.zeros(len(pos), dtype=np.uint32)
    for g, bm in enumerate(bms):
        if g:
            rank += ((bm.to_words()[widx] >> shift) & 1) * np.uint32(g)
    starts = np.flatnonzero(np.concatenate(([True], rank[1:] != rank[:-1])))
    return starts, rank[starts].astype(np.int64)


def _bin_segments(catalogs, cards, F: int, fvals, out: Dict) -> None:
    """Bin the elementary segments of the columns' ``catalogs`` (each a
    partition of ``[0, F)``) into ``out``'s row-major cube cells."""
    # elementary segments: a boundary wherever any column changes rank
    S = np.unique(np.concatenate([starts for starts, _ in catalogs]))
    E = np.append(S[1:], F)
    _trace.add("executor.group_segments", len(S))
    cell = np.zeros(len(S), dtype=np.int64)
    for (starts, ranks), card in zip(catalogs, cards):
        cell = cell * card + ranks[np.searchsorted(starts, S,
                                                   side="right") - 1]
    size = int(np.prod(cards))
    out["counts"] += np.bincount(cell, weights=(E - S),
                                 minlength=size).astype(np.int64)
    if fvals is not None:
        pref = _ms.prefix_sums(fvals)
        # np.add.at (not bincount) keeps int64 sums exact past 2^53
        np.add.at(out["sums"], cell, pref[E] - pref[S])
        mins, maxs = _ms.segmented_min_max(fvals, S, E)
        np.minimum.at(out["mins"], cell, mins)
        np.maximum.at(out["maxs"], cell, maxs)
