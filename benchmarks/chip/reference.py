"""Plain row-scan reference: the answers a ``POST /query`` body must get.

A straightforward NumPy evaluation over the generated table, row by row
(a boolean mask per filter), written from the wire format's documented
semantics.  It imports nothing of the system under test and takes nothing
it made: it sorts the table itself when the configuration stores it sorted,
so row ids are positions in the same stored order.

Semantics, as the service documents them: values are ranks; a value outside
a column's domain matches no row; ``range`` bounds are inclusive and either
may be absent; a column's domain is ``0..max`` over the whole table;
``top_k`` ranks descending with ties by ascending rank and leaves out empty
groups; ``sum`` of nothing is 0 and ``avg``/``min``/``max`` of nothing is
null; a row answer lists at most ``max_rows`` ids, ascending, with the
exact count.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

AGG_OPS = ("sum", "avg", "min", "max")


def sort_order(columns: Dict[str, np.ndarray],
               sort: Optional[Sequence[str]]) -> Optional[np.ndarray]:
    """Row order of a lexicographic sort, ``sort[0]`` most significant."""
    if not sort:
        return None
    return np.lexsort(tuple(columns[c] for c in reversed(list(sort))))


class Reference:
    def __init__(self, columns: Dict[str, np.ndarray],
                 measures: Optional[Dict[str, np.ndarray]] = None,
                 sort: Optional[Sequence[str]] = None,
                 max_rows: int = 10_000, drop_last: int = 0):
        """``drop_last`` > 0 answers as a stale copy that has not seen the
        table's last ``drop_last`` rows: the control, which breaks the
        guarantee that every stored row is visible."""
        columns = {k: np.asarray(v) for k, v in columns.items()}
        measures = {k: np.asarray(v) for k, v in (measures or {}).items()}
        # domains over the whole table, as the stored index has them
        self.cards = {k: int(v.max()) + 1 if len(v) else 1
                      for k, v in columns.items()}
        if drop_last:
            columns = {k: v[:-drop_last] for k, v in columns.items()}
            measures = {k: v[:-drop_last] for k, v in measures.items()}
        order = sort_order(columns, sort)
        if order is not None:
            columns = {k: v[order] for k, v in columns.items()}
            measures = {k: v[order] for k, v in measures.items()}
        self.columns = columns
        self.measures = measures
        self.n_rows = len(next(iter(columns.values())))
        self.max_rows = int(max_rows)
        self._masks: Dict[str, np.ndarray] = {}

    # -- filters -------------------------------------------------------------
    def mask(self, e: Optional[Dict]) -> np.ndarray:
        if e is None:
            return np.ones(self.n_rows, dtype=bool)
        op = e["op"]
        if op in ("and", "or"):
            parts = [self.mask(a) for a in e["args"]]
            out = parts[0].copy()
            for p in parts[1:]:
                if op == "and":
                    out &= p
                else:
                    out |= p
            return out
        if op == "not":
            return ~self.mask(e["arg"])
        col = self.columns[e["col"]]
        if op == "eq":
            return col == int(e["value"])
        if op == "in":
            card = self.cards[e["col"]]
            member = np.zeros(card, dtype=bool)
            vals = np.asarray([int(v) for v in e["values"]], dtype=np.int64)
            member[vals[(vals >= 0) & (vals < card)]] = True
            return member[col]
        if op == "range":
            out = np.ones(self.n_rows, dtype=bool)
            if e.get("lo") is not None:
                out &= col >= int(e["lo"])
            if e.get("hi") is not None:
                out &= col <= int(e["hi"])
            return out
        raise ValueError(f"unknown filter op {op!r}")

    def _filter(self, e: Optional[Dict]) -> np.ndarray:
        # statements of a window share filters (a count and a sum over the
        # same predicate); memoize by the filter's text
        key = repr(e)
        m = self._masks.get(key)
        if m is None:
            if len(self._masks) > 64:
                self._masks.clear()
            m = self._masks[key] = self.mask(e)
        return m

    # -- statements ----------------------------------------------------------
    def answer(self, body: Dict) -> Dict:
        """The fields of the service's answer to ``body`` that must match."""
        if "query" in body:
            rows = np.flatnonzero(self._filter(body["query"]))
            return {"count": int(len(rows)),
                    "rows": rows[:self.max_rows].tolist(),
                    "truncated": bool(len(rows) > self.max_rows)}
        sel = body["select"]
        m = self._filter(body.get("where"))
        by = sel.get("by")
        kind = [k for k in sel if k != "by"][0]
        if by is not None:
            return self._group_agg(kind, sel[kind], [by] if isinstance(
                by, str) else list(by), m)
        if kind == "count":
            return {"count": int(m.sum())}
        if kind == "group_count":
            return {"counts": self._counts(sel[kind], m).tolist()}
        if kind == "top_k":
            spec = sel[kind]
            return {"top": self._top_k(spec["col"], int(spec["k"]),
                                       spec.get("measure"), m)}
        if kind in AGG_OPS:
            vals = self.measures[sel[kind]][m]
            return {"value": _scalar(kind, vals), "count": int(len(vals))}
        raise ValueError(f"unknown select {kind!r}")

    def _counts(self, col: str, m: np.ndarray) -> np.ndarray:
        return np.bincount(self.columns[col][m], minlength=self.cards[col])

    def _top_k(self, col: str, k: int, measure: Optional[str],
               m: np.ndarray) -> List[List]:
        counts = self._counts(col, m)
        if measure is None:
            score = counts
        else:
            vals = self.measures[measure][m]
            score = np.zeros(len(counts), dtype=vals.dtype)
            np.add.at(score, self.columns[col][m], vals)
        present = [v for v in range(len(counts)) if counts[v] > 0]
        present.sort(key=lambda v: (-score[v], v))
        return [[v, _py(score[v])] for v in present[:k]]

    def _group_agg(self, op: str, measure, by: List[str],
                   m: np.ndarray) -> Dict:
        cards = [self.cards[c] for c in by]
        cell = np.zeros(int(m.sum()), dtype=np.int64)
        for c, card in zip(by, cards):
            cell = cell * card + self.columns[c][m]
        size = int(np.prod(cards))
        counts = np.bincount(cell, minlength=size)
        out = {"shape": cards,
               "counts": counts.reshape(cards).tolist()}
        if op != "count":
            vals = self.measures[measure][m]
            order = np.argsort(cell, kind="stable")
            bounds = np.searchsorted(cell[order], np.arange(size + 1))
            cells = [_scalar(op, vals[order[bounds[g]:bounds[g + 1]]])
                     for g in range(size)]
            out["values"] = np.asarray(cells, dtype=object).reshape(
                cards).tolist()
        return out


def _py(v):
    return int(v) if isinstance(v, (int, np.integer)) else float(v)


def _scalar(op: str, vals: np.ndarray):
    # int64 sums are exact: the measures are far below int64's range
    if op == "sum":
        return _py(np.sum(vals))
    if not len(vals):
        return None
    if op == "avg":
        return _py(np.sum(vals)) / len(vals)
    return _py(vals.min() if op == "min" else vals.max())


def matches(expected: Dict, got: Dict) -> bool:
    """Whether every field the reference gives is in ``got``, equal."""
    return all(k in got and got[k] == v for k, v in expected.items())
