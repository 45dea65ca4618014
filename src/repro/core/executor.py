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

``QueryBatch`` evaluates many expressions in one pass over a shared operand
cache: physical bitmaps (and their bucketed dense decompressions + flags,
when the kernel path is taken) are loaded once and reused across all plans
in the batch.  Constant plan nodes memoize their full-length bitmaps in the
same cache.  Sharded execution forwards an optional worker pool for
shard-parallel fan-out (``repro.core.shard``).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from . import cost_model as _cm
from . import measures as _ms
from . import trace as _trace
from .ewah import EWAH, and_many, or_many
from .expr import Expr
from .index import BitmapIndex
from .planner import (PAgg, PAnd, PBitmap, PConst, PCount, PDiff,
                      PGroupAgg, PGroupCount, PNot, POr, PPinned, PlanNode,
                      Planner, plan)

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
        """Grouped aggregates over one or two columns in the filtered
        domain.

        The filter's intervals define a dense coordinate space of
        ``count(filter)`` positions; the measure is gathered into it once
        and prefix-summed, so every group's sum is two subtractions and its
        min/max one segmented ``reduceat``.  Each grouping column's rank
        bitmaps *partition* the rows (every row holds exactly one value),
        so their interval images partition the filtered domain: one column
        accumulates per-rank segments directly; two columns sweep the
        *elementary segments* induced by both columns' boundaries, binning
        each into its ``(rank_a, rank_b)`` cell — cost O(selected rows +
        intervals), never O(card_a * card_b * rows).
        """
        f = self._filter(node.filter)
        with _trace.span("executor.aggregate"):
            return self._group_agg(node, f)

    def _group_agg(self, node: PGroupAgg, f) -> Dict:
        """``run_group_agg`` once its filter is evaluated to ``f``."""
        cards = tuple(len(g) for g in node.groups)
        name = node.measure
        values = self.index.measure(name) if name is not None else None
        dt = _ms.measure_dtype_str(values) if values is not None else None
        out = _ms.empty_group_agg(node.cols, cards, name, dt)
        fs, fe = self._filter_intervals(f)
        if not len(fs):
            return out
        F = int((fe - fs).sum())
        fvals = _ms.gather(values, fs, fe) if values is not None else None
        pref = _ms.prefix_sums(fvals) if fvals is not None else None
        # per-column segment catalogs in filtered coordinates, sorted by
        # start (segments of one column are disjoint and cover [0, F))
        catalogs = []
        for groups in node.groups:
            ss, es, rs = [], [], []
            for g, gn in enumerate(groups):
                s, e = self._run(gn).set_intervals()
                if not len(s):
                    continue
                cs = _ms.interval_coverage(fs, fe, s)
                ce = _ms.interval_coverage(fs, fe, e)
                keep = ce > cs
                if not keep.any():
                    continue
                ss.append(cs[keep])
                es.append(ce[keep])
                rs.append(np.full(int(keep.sum()), g, dtype=np.int64))
            if not ss:
                return out  # a partition with no coverage means F == 0
            S = np.concatenate(ss)
            E = np.concatenate(es)
            R = np.concatenate(rs)
            order = np.argsort(S, kind="stable")
            catalogs.append((S[order], E[order], R[order]))
        if len(catalogs) == 1:
            S, E, R = catalogs[0]
            cell = R
            size = cards[0]
        else:
            # elementary segments: boundaries wherever either column
            # changes rank; each segment is homogeneous in both columns
            (sa, _, ra), (sb, _, rb) = catalogs
            S = np.unique(np.concatenate([sa, sb]))
            E = np.concatenate([S[1:], [F]]).astype(np.int64)
            ia = np.searchsorted(sa, S, side="right") - 1
            ib = np.searchsorted(sb, S, side="right") - 1
            cell = ra[ia] * cards[1] + rb[ib]
            size = cards[0] * cards[1]
        out["counts"] += np.bincount(cell, weights=(E - S),
                                     minlength=size).astype(np.int64)
        if values is not None:
            # np.add.at (not bincount) keeps int64 sums exact past 2^53
            np.add.at(out["sums"], cell, pref[E] - pref[S])
            mins, maxs = _ms.segmented_min_max(fvals, S, E)
            np.minimum.at(out["mins"], cell, mins)
            np.maximum.at(out["maxs"], cell, maxs)
        return out

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
                a = kops.logical_reduce(np.stack(pw), op="and",
                                        row_flags=np.stack(pf))
                b = kops.logical_reduce(np.stack(nw), op="or",
                                        row_flags=np.stack(nf))
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
            out = self._fetch(kops.logical_reduce(np.stack(ws), op=op,
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


def _shard_caches(index, cache: Optional[Dict]) -> Optional[List[Dict]]:
    """Per-shard operand sub-dicts inside one caller-supplied cache, so a
    persistent cache keeps sharing operands across calls on every
    statement path (one keying scheme, used by all dispatchers)."""
    if cache is None:
        return None
    return [cache.setdefault(("shard", i), {})
            for i in range(index.n_shards)]


def execute(index, e: Union[Expr, PlanNode],
            backend: Backend = "auto", optimize: bool = True,
            cache: Optional[Dict] = None, pool=None) -> EWAH:
    """Plan (unless given a plan) and evaluate one expression -> EWAH.

    Accepts a monolithic ``BitmapIndex`` or a ``ShardedIndex``; the sharded
    path plans and executes per shard — concurrently when ``pool`` (a
    ``concurrent.futures`` executor) is given — then concatenates the EWAH
    results.
    """
    from .shard import ShardedIndex  # local: shard imports this module
    from .ingest import LiveIndex   # local: ingest imports this module
    if isinstance(index, LiveIndex):
        return index.execute(e, backend=backend, optimize=optimize,
                             pool=pool)
    if isinstance(index, ShardedIndex):
        return index.execute(e, backend=backend, optimize=optimize,
                             caches=_shard_caches(index, cache), pool=pool)
    node = plan(index, e, optimize=optimize) if isinstance(e, Expr) else e
    return Executor(index, backend=backend, cache=cache).run(node)


def execute_rows(index, e: Union[Expr, PlanNode],
                 backend: Backend = "auto", optimize: bool = True) -> np.ndarray:
    """Evaluate and return matching row ids (sorted)."""
    return execute(index, e, backend=backend, optimize=optimize).set_bits()


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


def execute_count(index, e: Optional[Expr] = None,
                  backend: Backend = "auto", optimize: bool = True,
                  cache: Optional[Dict] = None, pool=None) -> int:
    """COUNT(*) of a filter (``e=None`` counts all rows), computed in the
    compressed domain — on a ``ShardedIndex`` per-shard partial counts are
    summed at the coordinator, never a concatenated result bitmap."""
    from .shard import ShardedIndex
    from .ingest import LiveIndex
    if isinstance(index, LiveIndex):
        return index.count(e, backend=backend, optimize=optimize, pool=pool)
    if isinstance(index, ShardedIndex):
        return index.count(e, backend=backend, optimize=optimize,
                           caches=_shard_caches(index, cache), pool=pool)
    node = Planner(index, optimize=optimize).plan_count(e)
    return Executor(index, backend=backend, cache=cache).run_count(node)


def execute_group_count(index, col, e: Optional[Expr] = None,
                        backend: Backend = "auto", optimize: bool = True,
                        cache: Optional[Dict] = None, pool=None) -> np.ndarray:
    """GROUP BY ``col`` COUNT(*) under filter ``e`` -> int64 array of
    length ``card(col)`` (a ``np.bincount``-shaped result).  Sharded
    indexes merge per-shard partial count vectors by summation."""
    from .shard import ShardedIndex
    from .ingest import LiveIndex
    if isinstance(index, LiveIndex):
        return index.group_count(col, e, backend=backend, optimize=optimize,
                                 pool=pool)
    if isinstance(index, ShardedIndex):
        return index.group_count(col, e, backend=backend, optimize=optimize,
                                 caches=_shard_caches(index, cache),
                                 pool=pool)
    node = Planner(index, optimize=optimize).plan_group_count(col, e)
    return Executor(index, backend=backend,
                    cache=cache).run_group_count(node)


def execute_agg(index, measure: str, e: Optional[Expr] = None,
                backend: Backend = "auto", optimize: bool = True,
                cache: Optional[Dict] = None, pool=None):
    """Scalar ``(sum, count, min, max)`` of ``measure`` under filter ``e``
    (``e=None`` aggregates all rows), computed by interval-slicing the
    measure sidecar — sharded indexes merge per-shard partial tuples at
    the coordinator (``repro.core.measures.merge_scalar_aggs``)."""
    from .shard import ShardedIndex
    from .ingest import LiveIndex
    if isinstance(index, LiveIndex):
        return index.agg(measure, e, backend=backend, optimize=optimize,
                         pool=pool)
    if isinstance(index, ShardedIndex):
        return index.agg(measure, e, backend=backend, optimize=optimize,
                         caches=_shard_caches(index, cache), pool=pool)
    node = Planner(index, optimize=optimize).plan_agg(measure, e)
    return Executor(index, backend=backend, cache=cache).run_agg(node)


def execute_group_agg(index, measure: Optional[str], cols,
                      e: Optional[Expr] = None,
                      backend: Backend = "auto", optimize: bool = True,
                      cache: Optional[Dict] = None, pool=None) -> Dict:
    """GROUP BY one or two columns, aggregating ``measure`` (or counting
    rows when ``measure`` is ``None``) under filter ``e``.  Returns the
    partial-aggregate dict of ``Executor.run_group_agg``; project it onto
    one op with ``repro.core.measures.finalize_group``.  Sharded indexes
    merge per-shard partials elementwise."""
    from .shard import ShardedIndex
    from .ingest import LiveIndex
    if isinstance(index, LiveIndex):
        return index.group_agg(measure, cols, e, backend=backend,
                               optimize=optimize, pool=pool)
    if isinstance(index, ShardedIndex):
        return index.group_agg(measure, cols, e, backend=backend,
                               optimize=optimize,
                               caches=_shard_caches(index, cache),
                               pool=pool)
    node = Planner(index, optimize=optimize).plan_group_agg(measure, cols, e)
    return Executor(index, backend=backend,
                    cache=cache).run_group_agg(node)


class QueryBatch:
    """Evaluate many expressions in one pass sharing loaded operands.

    Plans are built up front, then all plans execute against one operand
    cache, so a bitmap referenced by several queries (the common case for
    dashboard-style workloads: same dimensions, different slices) is
    concatenated from its partitions — and decompressed, on the kernel
    path — exactly once.
    """

    def __init__(self, exprs: Sequence[Expr]):
        self.exprs = list(exprs)

    def execute(self, index, backend: Backend = "auto",
                optimize: bool = True, pool=None) -> List[EWAH]:
        from .shard import ShardedIndex
        if isinstance(index, ShardedIndex):
            # one operand cache per shard, shared across the whole batch
            caches: List[Dict] = [{} for _ in index.shards]
            return [index.execute(e, backend=backend, optimize=optimize,
                                  caches=caches, pool=pool)
                    for e in self.exprs]
        plans = [plan(index, e, optimize=optimize) for e in self.exprs]
        cache: Dict = {}
        ex = Executor(index, backend=backend, cache=cache)
        return [ex.run(p) for p in plans]

    def execute_rows(self, index, backend: Backend = "auto",
                     optimize: bool = True, pool=None) -> List[np.ndarray]:
        return [bm.set_bits()
                for bm in self.execute(index, backend=backend,
                                       optimize=optimize, pool=pool)]
