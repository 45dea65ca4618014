"""The one general traffic generator: statements from a mix file and a seed.

A traffic mix is data (``traffic/<mix>.json``)::

    {"clients": 4,
     "templates": [
       {"name": "q6_count", "weight": 1,
        "draw": {"d": {"int": [0, 8]}, "q": {"int": [2, 50]}},
        "body": {"select": {"count": true},
                 "where": {"op": "range", "col": "l_discount",
                           "lo": "$d", "hi": "$d+2"}}}]}

``body`` is the JSON POSTed to ``/query``.  A string ``"$x"``, ``"$x+n"``
or ``"$x-n"`` anywhere in it is replaced by the drawn value of ``x``
(plus or minus the integer ``n``).  A draw ``{"int": [lo, hi]}`` takes
an integer from ``lo..hi`` inclusive; a bound may be ``"max:<col>"``, the
largest rank of that column in the configuration's ``domains``, or
``"max:<col>-n"``.

Statements come in blocks: each block holds every template ``weight``
times, interleaved by smooth weighted round robin, so that every prefix of
the stream holds each template as near its share as whole statements
allow.  That sequence of templates is the same for every seed: a window
cut anywhere holds the same mix.  The seed deals each template's values
like cards: its k-th statement takes the k-th entry of a seeded
permutation of every combination of its draws' values, and a new
permutation starts when one is used up.  So every seed sends the same
statements in another order, and a statement repeats (and may hit a
result cache) only once all of its template's combinations have been sent.
Statement ``i`` of a seed is the same whoever draws it: the load generator
and the correctness check regenerate it independently.  This module
imports only NumPy (the load generator never imports JAX).
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
_REF = re.compile(r"^\$([A-Za-z_]\w*)([+-]\d+)?$")
_MAX = re.compile(r"^max:([A-Za-z_][\w.]*)([+-]\d+)?$")

# stream id of the decks inside a seed
_DECKS = 3


def file_stem(name: str) -> str:
    """A name from BENCHMARK.json as a file name: ``.`` and ``-`` -> ``_``."""
    return name.replace(".", "_").replace("-", "_")


def load_mix(name: str, bench_dir: Path = HERE) -> Dict:
    """The mix ``name`` (``traffic/<name>.json``), or the file at ``name``
    where it is a path to one."""
    path = Path(name)
    if path.suffix != ".json":
        path = bench_dir / "traffic" / f"{file_stem(name)}.json"
    return json.loads(path.read_text())


def _seed_words(seed: int) -> List[int]:
    # SeedSequence takes non-negative integers of any size
    s = int(seed) % (1 << 64)
    return [s & 0xFFFFFFFF, s >> 32]


def _interleave(weights: List[int]) -> List[int]:
    """One block: template ``t`` ``weights[t]`` times, by smooth weighted
    round robin (each slot goes to the template furthest behind its share;
    ties to the first)."""
    total = sum(weights)
    current = [0] * len(weights)
    out = []
    for _ in range(total):
        current = [c + w for c, w in zip(current, weights)]
        t = max(range(len(weights)), key=lambda x: (current[x], -x))
        current[t] -= total
        out.append(t)
    return out


class Traffic:
    """Statements of one mix over one configuration's value domains."""

    def __init__(self, mix: Dict, domains: Dict[str, int]):
        self.mix = mix
        self.domains = dict(domains)
        self.templates = mix["templates"]
        weights = [int(tpl.get("weight", 1)) for tpl in self.templates]
        if sum(weights) < 1 or min(weights) < 0:
            raise ValueError("a traffic mix needs a template of weight 1+")
        self.block = _interleave(weights)
        # how many statements of its template precede each slot of a block
        self._before = [self.block[:j].count(t)
                        for j, t in enumerate(self.block)]
        self.clients = int(mix.get("clients", 4))
        self._decks: Dict[Tuple[int, int, int], np.ndarray] = {}

    # -- draws -------------------------------------------------------------
    def _bound(self, b) -> int:
        if isinstance(b, int):
            return b
        m = _MAX.match(str(b))
        if not m:
            raise ValueError(f"bad bound {b!r}")
        return int(self.domains[m.group(1)]) - 1 + int(m.group(2) or 0)

    def _ranges(self, t: int) -> List[Tuple[str, int, int]]:
        """(name, lo, hi) of each draw of template ``t``, in key order."""
        out = []
        for k, spec in sorted(self.templates[t].get("draw", {}).items()):
            if set(spec) != {"int"}:
                raise ValueError(f"unknown draw {spec!r}")
            lo, hi = (self._bound(b) for b in spec["int"])
            if hi < lo:
                raise ValueError(f"empty draw {spec!r}")
            out.append((k, lo, hi))
        return out

    def deck_size(self, t: int) -> int:
        """Combinations of template ``t``'s draws: the statements it sends
        before any repeats."""
        n = 1
        for _, lo, hi in self._ranges(t):
            n *= hi - lo + 1
        return n

    def _dealt(self, t: int, g: int) -> Dict[str, int]:
        """Combination ``g`` of template ``t``'s draws (mixed radix)."""
        values = {}
        for k, lo, hi in self._ranges(t):
            g, r = divmod(g, hi - lo + 1)
            values[k] = lo + r
        return values

    def _ends(self, t: int, end: str) -> Dict[str, int]:
        return {k: lo if end == "lo" else hi for k, lo, hi in self._ranges(t)}

    def _fill(self, node, values: Dict):
        if isinstance(node, dict):
            return {k: self._fill(v, values) for k, v in node.items()}
        if isinstance(node, list):
            return [self._fill(v, values) for v in node]
        if isinstance(node, str) and node.startswith("$"):
            m = _REF.match(node)
            if not m:
                raise ValueError(f"bad reference {node!r}")
            v = values[m.group(1)]
            return v + int(m.group(2)) if m.group(2) else v
        return node

    def _render(self, t: int, values: Dict[str, int]) -> Tuple[str, Dict]:
        tpl = self.templates[t]
        return tpl["name"], self._fill(tpl["body"], values)

    # -- streams -----------------------------------------------------------
    def _deck(self, seed: int, t: int, cycle: int) -> np.ndarray:
        key = (seed, t, cycle)
        if key not in self._decks:
            rng = np.random.default_rng(_seed_words(seed) + [_DECKS, t, cycle])
            self._decks[key] = rng.permutation(self.deck_size(t))
        return self._decks[key]

    def statement(self, seed: int, i: int) -> Tuple[str, Dict]:
        """(template name, request body) of window statement ``i``."""
        b, j = divmod(int(i), len(self.block))
        t = self.block[j]
        k = b * self.block.count(t) + self._before[j]
        cycle, pos = divmod(k, self.deck_size(t))
        return self._render(t, self._dealt(
            t, int(self._deck(seed, t, cycle)[pos])))

    def warmup(self, ends=("lo", "hi")) -> List[Tuple[str, Dict]]:
        """Every template at each of ``ends`` of its draws' ranges (by
        default the low and the high end): the operand counts, and so the
        kernel shapes, at both ends."""
        return [self._render(t, self._ends(t, end))
                for t in range(len(self.templates)) for end in ends]

    def _values(self, node) -> int:
        """Column values a filter names (a range clipped to the domain)."""
        op = node["op"]
        if op in ("and", "or"):
            return sum(self._values(a) for a in node["args"])
        if op == "not":
            return self._values(node["arg"])
        if op == "eq":
            return 1
        if op == "in":
            return len(set(node["values"]))
        if op == "range":
            card = int(self.domains[node["col"]])
            lo = max(int(node.get("lo", 0)), 0)
            hi = min(int(node.get("hi", card - 1)), card - 1)
            return max(hi - lo + 1, 0)
        raise ValueError(f"unknown filter op {op!r}")

    def max_operands(self, k: int) -> int:
        """The most bitmaps one statement of the mix names, at ``k`` bitmaps
        a value: a bound on the operands of any AND/OR the planner makes of
        it.  Read from the warm-up statements, since a draw at one end of
        its range names the most values."""
        most = 1
        for _, body in self.warmup():
            where = body.get("query", body.get("where"))
            if where is not None:
                most = max(most, k * self._values(where))
        return most
