"""Statements: the one module that knows what a statement is.

A statement is ``parse_statement``'s descriptor (``count``,
``group_count``, ``agg``, ``group_agg`` or ``top_k`` under an optional
filter; ``make`` builds one in code).  It runs as a *task*, a small
picklable tuple whose last item is the filter:

    ("expr", e)                      the filter's EWAH result (row queries)
    ("count", e)                     COUNT(*)
    ("gcount", col, e)               per-value counts of one column
    ("agg", measure, e)              ``(sum, count, min, max)`` of a measure
    ("gagg", measure, cols, e)       the grouped partial over 1+ columns
    ("gtop", measure, col, m, e)     a shard's top-m report (TPUT phase 1)
    ("gvals", measure, col, ids, e)  exact partials at candidate ids

The filter is ``None`` (every row), an ``Expr``, or an already-lowered plan
node (the live layer pins tombstone-masked filters as ``PPinned``);
columns are resolved to positions before a task is built.

Every fan-out runs a task the same way: each shard through
``run_on_shard``, the per-shard partials through ``merge``, and a
statement's merged value through ``answer`` into the JSON every front end
returns.  Only how the shards are reached differs.  ``run`` is the one
switch on the index type: a ``BitmapIndex`` is one shard; a
``ShardedIndex`` maps ``run_on_shard`` over its shards on a pool
(``repro.core.shard``), a ``LiveIndex`` over its layers with tombstones
pinned into the filter (``repro.core.ingest``).  ``ClusterService``
(``repro.distributed.cluster``) reaches the shards through socket workers
(``serve/worker_api.py``): tasks travel as ``encode_task`` requests and
partials as ``encode_partial`` frames.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from . import measures as _ms
from .ewah import EWAH, WORD_DTYPE, _empty_ewah
from .executor import Executor
from .expr import Expr, canonical_key, from_wire, to_wire
from .index import BitmapIndex, concat_bitmaps
from .planner import Planner

# -- statement descriptors ----------------------------------------------------

_FIELDS = ("op", "measure", "col", "by", "k")


def make(kind: Optional[str], where: Optional[Expr] = None, **fields) -> Dict:
    """A statement descriptor: ``kind``, ``op`` (``sum`` / ``avg`` /
    ``min`` / ``max`` / ``count`` for measure statements), ``measure``,
    ``col``, ``by`` (grouping column list), ``k`` and ``where`` — None
    where not applicable."""
    return {"kind": kind, **dict.fromkeys(_FIELDS), **fields, "where": where}


def parse_expr(obj: Dict) -> Expr:
    """JSON wire format -> Expr tree (raises ValueError on malformed input).

    Alias of ``repro.core.expr.from_wire`` — one wire codec shared by the
    HTTP layer and the write-ahead log's delete frames."""
    return from_wire(obj)


def expr_to_json(e: Expr) -> Dict:
    """Inverse of ``parse_expr`` (alias of ``repro.core.expr.to_wire``)."""
    return to_wire(e)


_AGG_OPS = ("sum", "avg", "min", "max")


def parse_statement(obj: Dict) -> Dict:
    """``{"select": ..., "where": ..., "limit": ...}`` -> statement
    descriptor.

    Returns a ``make`` descriptor whose ``kind`` is ``"count"`` /
    ``"group_count"`` / ``"agg"`` / ``"group_agg"`` / ``"top_k"`` and
    whose ``where`` is the parsed ``Expr``.  A top-level ``limit`` rewrites
    a single-column count/sum group-by into the equivalent top-k (the
    shard-prunable ranking ops).  Raises ValueError on malformed
    statements (mapped to HTTP 400).
    """
    sel = obj.get("select")
    if not isinstance(sel, dict):
        raise ValueError(
            f"'select' must be an object naming one of count / group_count "
            f"/ top_k / sum / avg / min / max: {sel!r}")
    where = obj.get("where")
    e = parse_expr(where) if where is not None else None
    by = sel.get("by")
    keys = [k for k in sel if k != "by"]
    if len(keys) != 1:
        raise ValueError(
            f"'select' must name exactly one of count / group_count / "
            f"top_k / sum / avg / min / max (plus an optional 'by'): "
            f"{sel!r}")
    kind, arg = keys[0], sel[keys[0]]
    if by is not None:
        if isinstance(by, (str, int)) and not isinstance(by, bool):
            by = [by]
        if (not isinstance(by, list) or not by
                or any(isinstance(c, bool) or not isinstance(c, (str, int))
                       for c in by)):
            raise ValueError(
                f"'by' must list one or more grouping columns, got {by!r}")
    out = make(None, e)
    if kind == "count":
        if arg is not True:
            raise ValueError('use {"count": true}')
        if by is None:
            out["kind"] = "count"
        else:
            out.update(kind="group_agg", op="count", by=by)
    elif kind in _AGG_OPS:
        if not isinstance(arg, str) or not arg:
            raise ValueError(f"{kind} needs a measure name, got {arg!r}")
        out.update(op=kind, measure=arg)
        if by is None:
            out["kind"] = "agg"
        else:
            out.update(kind="group_agg", by=by)
    elif by is not None:
        raise ValueError(f"'by' does not combine with {kind!r}")
    elif kind == "group_count":
        _check_col(arg, "group_count")
        out.update(kind="group_count", col=arg)
    elif kind == "top_k":
        if not (isinstance(arg, dict) and "col" in arg and "k" in arg):
            raise ValueError(
                f'top_k needs {{"col": ..., "k": ...}}, got {arg!r}')
        _check_col(arg["col"], "top_k")
        m = arg.get("measure")
        if m is not None and (not isinstance(m, str) or not m):
            raise ValueError(f"top_k 'measure' must be a name, got {m!r}")
        out.update(kind="top_k", col=arg["col"], k=int(arg["k"]), measure=m)
    else:
        raise ValueError(f"unknown select {kind!r}")
    return _apply_limit(out, obj.get("limit"))


def _apply_limit(st: Dict, limit) -> Dict:
    """Rewrite ``limit`` on a single-column group statement into the
    equivalent top-k (count and sum rankings — the ops shard pruning can
    bound; an avg/min/max ranking has no monotone partial)."""
    if limit is None:
        return st
    if isinstance(limit, bool) or not isinstance(limit, int) or limit < 1:
        raise ValueError(f"'limit' must be a positive integer, got {limit!r}")
    if st["kind"] == "group_count":
        return {**st, "kind": "top_k", "k": int(limit), "measure": None}
    if (st["kind"] == "group_agg" and st["by"] is not None
            and len(st["by"]) == 1 and st["op"] in ("count", "sum")):
        return {**st, "kind": "top_k", "col": st["by"][0], "by": None,
                "k": int(limit), "measure": st["measure"]}
    if st["kind"] == "top_k":
        return {**st, "k": min(st["k"], int(limit))}
    raise ValueError(
        "'limit' ranks a single-column count or sum group-by (top-k); it "
        "cannot truncate a scalar, a multi-column cube, or an avg/min/max "
        "ranking")


def _check_col(arg, kind: str) -> None:
    # bool is a subclass of int: {"group_count": true} (a typo'd copy of
    # the count shape) must be a 400, not a query against column 1
    if isinstance(arg, bool) or not isinstance(arg, (str, int)):
        raise ValueError(f"{kind} needs a column name or position, "
                         f"got {arg!r}")


def nan_to_none(x):
    """Recursively replace NaN (empty avg/min/max cells) with None so
    grouped results serialize as strict JSON ``null``."""
    if isinstance(x, list):
        return [nan_to_none(v) for v in x]
    if isinstance(x, float) and x != x:
        return None
    return x


# -- SQL-ish front door ------------------------------------------------------

def _sql_tokens(sql: str) -> List[tuple]:
    out: List[tuple] = []
    i, n = 0, len(sql)
    while i < n:
        ch = sql[i]
        if ch.isspace():
            i += 1
            continue
        if ch in "(),=*":
            out.append((ch, ch))
            i += 1
            continue
        m = re.match(r"[A-Za-z_][A-Za-z0-9_]*", sql[i:])
        if m:
            out.append(("ident", m.group(0)))
            i += len(m.group(0))
            continue
        m = re.match(r"-?\d+", sql[i:])
        if m:
            out.append(("int", int(m.group(0))))
            i += len(m.group(0))
            continue
        raise ValueError(f"SQL: unexpected character {ch!r} at offset {i}")
    out.append(("end", None))
    return out


class _SqlParser:
    """Recursive-descent parser for the SQL-ish statement subset::

        SELECT count(*) | sum(m) | avg(m) | min(m) | max(m)
        FROM <table>                      -- single-table engine: name ignored
        [WHERE <pred>]                    -- =, IN (...), BETWEEN a AND b,
                                          --   AND / OR / NOT, parentheses
        [GROUP BY a[, b, ...]]
        [LIMIT k]

    Values are integer *ranks* (the dictionary-encoded domain the bitmap
    index stores).  Produces the JSON statement object ``parse_statement``
    accepts, so SQL and JSON front doors share one semantics."""

    def __init__(self, sql: str):
        self.toks = _sql_tokens(sql)
        self.pos = 0

    def peek(self) -> tuple:
        return self.toks[self.pos]

    def next(self) -> tuple:
        t = self.toks[self.pos]
        self.pos += 1
        return t

    def at_kw(self, word: str) -> bool:
        t, v = self.peek()
        return t == "ident" and v.upper() == word

    def expect_kw(self, word: str) -> None:
        if not self.at_kw(word):
            raise ValueError(f"SQL: expected {word}, got {self.peek()[1]!r}")
        self.next()

    def expect(self, typ: str):
        t, v = self.next()
        if t != typ:
            raise ValueError(f"SQL: expected {typ!r}, got {v!r}")
        return v

    # predicate grammar: OR < AND < NOT < primary
    def pred_or(self) -> Dict:
        args = [self.pred_and()]
        while self.at_kw("OR"):
            self.next()
            args.append(self.pred_and())
        return args[0] if len(args) == 1 else {"op": "or", "args": args}

    def pred_and(self) -> Dict:
        args = [self.pred_not()]
        while self.at_kw("AND"):
            self.next()
            args.append(self.pred_not())
        return args[0] if len(args) == 1 else {"op": "and", "args": args}

    def pred_not(self) -> Dict:
        if self.at_kw("NOT"):
            self.next()
            return {"op": "not", "arg": self.pred_not()}
        return self.primary()

    def primary(self) -> Dict:
        t, v = self.peek()
        if t == "(":
            self.next()
            e = self.pred_or()
            self.expect(")")
            return e
        if t != "ident":
            raise ValueError(f"SQL: expected a column name, got {v!r}")
        self.next()
        col = v
        t2, v2 = self.next()
        if t2 == "=":
            return {"op": "eq", "col": col, "value": self.expect("int")}
        if t2 == "ident" and v2.upper() == "IN":
            self.expect("(")
            vals = [self.expect("int")]
            while self.peek()[0] == ",":
                self.next()
                vals.append(self.expect("int"))
            self.expect(")")
            return {"op": "in", "col": col, "values": vals}
        if t2 == "ident" and v2.upper() == "BETWEEN":
            lo = self.expect("int")
            self.expect_kw("AND")
            hi = self.expect("int")
            return {"op": "range", "col": col, "lo": lo, "hi": hi}
        raise ValueError(f"SQL: expected =, IN or BETWEEN after "
                         f"{col!r}, got {v2!r}")

    def parse(self) -> Dict:
        self.expect_kw("SELECT")
        t, fn = self.next()
        if t != "ident" or fn.upper() not in ("COUNT", "SUM", "AVG",
                                              "MIN", "MAX"):
            raise ValueError(f"SQL: expected count(*)/sum(m)/avg(m)/min(m)"
                             f"/max(m), got {fn!r}")
        fn = fn.upper()
        self.expect("(")
        if fn == "COUNT":
            self.expect("*")
            sel: Dict = {"count": True}
        else:
            sel = {fn.lower(): self.expect("ident")}
        self.expect(")")
        self.expect_kw("FROM")
        self.expect("ident")  # table name: single-table engine, ignored
        out: Dict = {}
        if self.at_kw("WHERE"):
            self.next()
            out["where"] = self.pred_or()
        if self.at_kw("GROUP"):
            self.next()
            self.expect_kw("BY")
            by = [self.expect("ident")]
            while self.peek()[0] == ",":
                self.next()
                by.append(self.expect("ident"))
            sel["by"] = by
        if self.at_kw("LIMIT"):
            self.next()
            out["limit"] = self.expect("int")
        if self.peek()[0] != "end":
            raise ValueError(f"SQL: trailing input at {self.peek()[1]!r}")
        out["select"] = sel
        return out


def parse_sql(sql: str) -> Dict:
    """SQL-ish text -> the JSON statement object ``parse_statement``
    accepts (and ``POST /query`` executes).  See ``_SqlParser``."""
    if not isinstance(sql, str) or not sql.strip():
        raise ValueError("'sql' must be a non-empty statement string")
    return _SqlParser(sql).parse()


# -- tasks --------------------------------------------------------------------

# each task kind and how many arguments sit between the kind and the filter
TASK_ARGS = {"expr": 0, "count": 0, "gcount": 1, "agg": 1, "gagg": 2,
             "gtop": 3, "gvals": 3}


def group_task(measure: Optional[str], cols, resolve,
               e: Optional[Expr] = None) -> tuple:
    """``("gagg", measure, cols, e)``, ``cols`` (one column or a list)
    resolved to positions by ``resolve``."""
    if isinstance(cols, (str, int, np.integer)):
        cols = [cols]
    return ("gagg", None if measure is None else str(measure),
            tuple(resolve(c) for c in cols), e)


def task_of(st: Dict, resolve) -> tuple:
    """The task whose merged result answers statement ``st``; ``resolve``
    maps a column name or position to its position.  A top-k ranks the
    merged group count, or the merged grouped sum of its measure."""
    kind, e = st["kind"], st["where"]
    if kind == "count":
        return ("count", e)
    if kind == "agg":
        return ("agg", st["measure"], e)
    if kind == "group_agg":
        return group_task(st["measure"], st["by"], resolve, e)
    if kind == "group_count" or st["measure"] is None:
        return ("gcount", resolve(st["col"]), e)
    return group_task(st["measure"], [st["col"]], resolve, e)


def task_measure(task) -> Optional[str]:
    """The measure a task aggregates (``None``: it counts rows)."""
    return task[1] if task[0] in ("agg", "gagg", "gtop", "gvals") else None


def task_key(task) -> Optional[tuple]:
    """A task's identity in result caches: its kind, its arguments and the
    filter's canonical key, so a commutatively reordered filter hits the
    same entry.  ``None`` when the filter is a plan node, which has no
    stable identity."""
    e = task[-1]
    if e is not None and not isinstance(e, Expr):
        return None
    return task[:-1] + (None if e is None else canonical_key(e),)


def run_on_shard(shard: BitmapIndex, task, backend: str = "auto",
                 optimize: bool = True, cache: Optional[Dict] = None):
    """Plan and execute one task on one ``BitmapIndex``; returns its partial.

    The one shard-side path: the thread fan-out, the forked
    ``ShardProcessPool``, the live layers and the socket workers all call
    it, so every tier computes for a shard exactly what the others would.
    Aggregates return a few numbers (an int, a count vector, a ``(sum,
    count, min, max)`` tuple, a grouped partial dict), never a bitmap.
    ``gtop`` reports the shard's top ``m`` groups with the threshold
    ``tau`` bounding every group it left out, and whether sum-pruning is
    sound there (no negative partial); ``gvals`` the exact partials at
    the candidate ``ids``."""
    kind, e = task[0], task[-1]
    planner = Planner(shard, optimize=optimize)
    ex = Executor(shard, backend=backend, cache=cache)
    if kind == "expr":
        return ex.run(planner.plan(e))
    if kind == "count":
        return ex.run_count(planner.plan_count(e))
    if kind == "gcount":
        return ex.run_group_count(planner.plan_group_count(task[1], e))
    if kind == "agg":
        return ex.run_agg(planner.plan_agg(task[1], e))
    if kind == "gagg":
        return ex.run_group_agg(
            planner.plan_group_agg(task[1], list(task[2]), e))
    if kind not in ("gtop", "gvals"):
        raise ValueError(f"unknown shard task {kind!r}")
    measure, col, arg = task[1], task[2], task[3]
    agg = ex.run_group_agg(planner.plan_group_agg(measure, [col], e))
    counts = agg["counts"]
    vals = counts if measure is None else agg["sums"]
    if kind == "gvals":
        ids = np.asarray(arg, dtype=np.int64)
        return {"vals": vals[ids], "counts": counts[ids]}
    m = int(arg)
    nz = np.flatnonzero(counts)
    # sum-pruning needs non-negative partials everywhere: one negative
    # value and "unreported <= tau" no longer bounds anything
    prunable = (measure is None or not len(nz)
                or not bool(vals[nz].min() < 0))
    order = nz[np.lexsort((nz, -vals[nz]))][:m]
    if len(nz) > m:
        tau = vals[order[-1]]
        tau = float(tau) if vals.dtype.kind == "f" else int(tau)
    else:
        tau = 0.0 if vals.dtype.kind == "f" else 0
    return {"ids": order, "vals": vals[order], "counts": counts[order],
            "tau": tau, "prunable": prunable}


def merge(task, parts: Sequence, index):
    """Merge the per-shard partials of ``task`` over ``index``'s shards:
    counts sum, count vectors add, measure partials merge
    (``repro.core.measures``), bitmaps concatenate in shard order.  A
    ``None`` part (a shard no replica answered) is left out; ``index``
    supplies the shapes when nothing is left."""
    kind = task[0]
    parts = [p for p in parts if p is not None]
    if kind == "expr":
        return concat_bitmaps(parts) if parts else _empty_ewah(0)
    if kind == "count":
        return int(sum(parts))
    if kind == "gcount":
        out = np.zeros(index.card(task[1]), dtype=np.int64)
        for p in parts:
            out += p
        return out
    if kind == "agg":
        return _ms.merge_scalar_aggs(parts)
    if kind != "gagg":
        raise ValueError(f"shard task {kind!r} has no merge")
    if parts:
        return _ms.merge_group_aggs(parts)
    name, cols = task[1], task[2]
    dt = None if name is None else _ms.measure_dtype_str(
        np.asarray(index.shards[0].measure(name)))
    return _ms.empty_group_agg(cols, tuple(index.card(c) for c in cols),
                               name, dt)


def run(index, task, backend: str = "auto", optimize: bool = True,
        cache: Optional[Dict] = None, pool=None):
    """``task`` on any index — the one switch on the index type.  A
    ``BitmapIndex`` runs it as one shard (``cache`` is its operand cache);
    a ``ShardedIndex`` or ``LiveIndex`` fans it out, on ``pool`` when given
    (a ``concurrent.futures`` executor or a ``ShardProcessPool``), and
    merges the partials."""
    if isinstance(index, BitmapIndex):
        return run_on_shard(index, task, backend, optimize, cache)
    return index.run(task, backend=backend, optimize=optimize, cache=cache,
                     pool=pool)


def execute(index, e, backend: str = "auto", optimize: bool = True,
            cache: Optional[Dict] = None, pool=None) -> EWAH:
    """Plan (unless given a plan) and evaluate one expression -> EWAH, on
    any index: the ``("expr", e)`` task."""
    return run(index, ("expr", e), backend, optimize, cache, pool)


def execute_rows(index, e, backend: str = "auto",
                 optimize: bool = True) -> np.ndarray:
    """Evaluate and return matching row ids (sorted)."""
    return execute(index, e, backend=backend, optimize=optimize).set_bits()


class QueryBatch:
    """Evaluate many expressions in one pass sharing loaded operands.

    All expressions execute against one operand cache (one per shard on a
    sharded index), so a bitmap referenced by several queries (the common
    case for dashboard-style workloads: same dimensions, different slices)
    is concatenated from its partitions — and decompressed, on the kernel
    path — exactly once, and a subtree they share evaluates once.
    """

    def __init__(self, exprs: Sequence[Expr]):
        self.exprs = list(exprs)

    def execute(self, index, backend: str = "auto", optimize: bool = True,
                pool=None) -> List[EWAH]:
        cache: Dict = {}
        return [execute(index, e, backend, optimize, cache, pool)
                for e in self.exprs]

    def execute_rows(self, index, backend: str = "auto",
                     optimize: bool = True, pool=None) -> List[np.ndarray]:
        return [bm.set_bits()
                for bm in self.execute(index, backend=backend,
                                       optimize=optimize, pool=pool)]


# -- answers ------------------------------------------------------------------

def top_k_from_counts(counts: np.ndarray, k: int) -> List[Tuple[int, int]]:
    """The ``k`` largest entries of a group-count vector as
    ``[(value_rank, count), ...]``: descending count, ties by ascending
    rank, zero-count values never included."""
    counts = np.asarray(counts)
    nz = np.flatnonzero(counts)
    order = nz[np.lexsort((nz, -counts[nz]))][:max(int(k), 0)]
    return [(int(v), int(counts[v])) for v in order]


def top_k_from_values(values: np.ndarray, counts: np.ndarray,
                      k: int) -> List[Tuple[int, Union[int, float]]]:
    """The ``k`` largest entries of a per-group value vector (measure sums)
    as ``[(value_rank, value), ...]``: descending value, ties by ascending
    rank — the *same* deterministic tie-break as ``top_k_from_counts``, so
    mono, sharded and cluster top-k orderings agree.  Groups with zero
    rows (``counts == 0``) never appear, even when their value is 0."""
    values = np.asarray(values)
    counts = np.asarray(counts)
    nz = np.flatnonzero(counts)
    order = nz[np.lexsort((nz, -values[nz]))][:max(int(k), 0)]
    if values.dtype.kind == "f":
        return [(int(v), float(values[v])) for v in order]
    return [(int(v), int(values[v])) for v in order]


def top_k_of(measure: Optional[str], merged, k: int) -> List[tuple]:
    """The top ``k`` of a merged group count (``measure`` None) or of a
    merged grouped partial's sums."""
    if measure is None:
        return top_k_from_counts(merged, k)
    return top_k_from_values(merged["sums"], merged["counts"], k)


def answer(st: Dict, merged, cached: bool, **extra) -> Dict:
    """The JSON answer to statement ``st`` from its task's merged result
    (for a top-k, also the ranked list itself).  Cubes are the dense
    row-major ``[card(c0), ..., card(cn-1)]`` as nested lists; empty cells
    count 0 and sum 0, and their avg/min/max are null.  ``extra`` fields
    (a cluster's coverage) go before ``cached``."""
    kind, op, measure = st["kind"], st["op"], st["measure"]
    if kind == "count":
        out = {"select": "count", "count": int(merged)}
    elif kind == "group_count":
        out = {"select": "group_count", "col": st["col"],
               "counts": [int(x) for x in merged]}
    elif kind == "agg":
        out = {"select": op, "measure": measure,
               "value": _ms.finalize_scalar(op, merged),
               "count": int(merged[1])}
    elif kind == "group_agg":
        shape = list(merged["shape"])
        out = {"select": "group_agg", "op": op, "measure": measure,
               "by": list(st["by"]), "shape": shape,
               "counts": np.asarray(merged["counts"]).reshape(shape).tolist()}
        if op != "count":
            out["values"] = nan_to_none(np.asarray(
                _ms.finalize_group(op, merged)).reshape(shape).tolist())
    else:
        top = merged if isinstance(merged, list) \
            else top_k_of(measure, merged, st["k"])
        out = {"select": "top_k", "col": st["col"], "k": int(st["k"]),
               "measure": measure,
               "top": [[int(r), (int(v) if isinstance(v, (int, np.integer))
                                 else float(v))] for r, v in top]}
    return {**out, **extra, "cached": cached}


# -- the worker wire codec ----------------------------------------------------

def encode_task(task) -> Dict:
    """A task as a worker request: ``{"op": kind, "args": [...], "where":
    wire-expr | null}``."""
    kind, e = task[0], task[-1]
    return {"op": kind,
            "args": [list(a) if isinstance(a, tuple) else a
                     for a in task[1:-1]],
            "where": None if e is None else to_wire(e)}


def decode_task(obj: Dict) -> tuple:
    """Inverse of ``encode_task``; raises ValueError on a malformed one."""
    kind, args, w = obj.get("op"), obj.get("args") or [], obj.get("where")
    if kind not in TASK_ARGS or not isinstance(args, list) \
            or len(args) != TASK_ARGS[kind]:
        raise ValueError(f"malformed task {kind!r} with arguments {args!r}")
    return (kind, *(tuple(a) if isinstance(a, list) else a for a in args),
            None if w is None else from_wire(w))


def encode_partial(part, tag: str, arrays: Dict[str, np.ndarray]):
    """A shard's partial as JSON, its arrays moved into ``arrays`` (the
    frame's binary section) under names that start with ``tag``.  Scalars
    travel as themselves; an array, bitmap, tuple or dict as a one-key
    object naming its form."""
    if isinstance(part, EWAH):
        arrays[tag] = np.asarray(part.words, dtype=WORD_DTYPE)
        return {"ewah": [tag, int(part.n_bits)]}
    if isinstance(part, np.ndarray):
        arrays[tag] = part
        return {"array": tag}
    if isinstance(part, tuple):
        return {"tuple": [encode_partial(v, f"{tag}.{i}", arrays)
                          for i, v in enumerate(part)]}
    if isinstance(part, dict):
        return {"dict": {k: encode_partial(v, f"{tag}.{k}", arrays)
                         for k, v in part.items()}}
    return part.item() if isinstance(part, np.generic) else part


def decode_partial(obj, arrays: Dict[str, np.ndarray]):
    """Inverse of ``encode_partial``."""
    if not isinstance(obj, dict):
        return obj
    (form, val), = obj.items()
    if form == "ewah":
        return EWAH(np.ascontiguousarray(arrays[val[0]]), int(val[1]))
    if form == "array":
        return arrays[val]
    if form == "tuple":
        return tuple(decode_partial(v, arrays) for v in val)
    return {k: decode_partial(v, arrays) for k, v in val.items()}
