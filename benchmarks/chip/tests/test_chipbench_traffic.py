"""The traffic generator: determinism, block shares, dealt draws, operand
bounds."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from traffic import Traffic, load_mix  # noqa: E402

MIX = {"clients": 2, "templates": [
    {"name": "a", "weight": 3,
     "draw": {"x": {"int": [0, 99]}, "v": {"int": [0, "max:c-2"]}},
     "body": {"select": {"count": True},
              "where": {"op": "in", "col": "c",
                        "values": ["$v", "$v+1", "$v+2"]},
              "lo": "$x", "hi": "$x+10", "lo2": "$x-1"}},
    {"name": "b", "weight": 1, "draw": {"y": {"int": [5, 6]}},
     "body": {"query": {"op": "eq", "col": "c", "value": "$y"}}}]}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 3, 2**40 + 1])
def test_same_seed_same_statements(seed):
    a, b = Traffic(MIX, {"c": 50}), Traffic(MIX, {"c": 50})
    assert [a.statement(seed, i) for i in range(30)] == \
        [b.statement(seed, i) for i in range(30)]
    assert a.statement(seed, 3) != a.statement(seed + 1, 3) or \
        a.statement(seed, 4) != a.statement(seed + 1, 4)


def test_every_block_holds_the_weights():
    t = Traffic(MIX, {"c": 50})
    for b in range(20):
        names = [t.statement(11, 4 * b + j)[0] for j in range(4)]
        assert sorted(names) == ["a", "a", "a", "b"]


def test_every_seed_sends_one_template_sequence():
    t = Traffic(load_mix("dense-filters"), {
        "l_discount": 11, "l_quantity": 51, "l_shipmode": 7,
        "l_shipinstruct": 4})
    names = [[t.statement(seed, i)[0] for i in range(60)]
             for seed in (1, 2**31 + 5, 2**40)]
    assert names[0] == names[1] == names[2]
    # every prefix holds Q6 as near half as whole statements allow
    for n in range(1, 61):
        q6 = sum(1 for x in names[0][:n] if x.startswith("q6"))
        assert abs(q6 - n / 2) <= 1.5


def test_substitution_and_draw_ranges():
    t = Traffic(MIX, {"c": 50})
    for i in range(200):
        name, body = t.statement(5, i)
        if name == "a":
            x = body["lo"]
            assert 0 <= x <= 99 and body["hi"] == x + 10
            assert body["lo2"] == x - 1
            s = body["where"]["values"]
            assert s == list(range(s[0], s[0] + 3))
            assert 0 <= min(s) and max(s) <= 49
        else:
            assert body["query"]["value"] in (5, 6)


@pytest.mark.parametrize("seed", [1, 2, 3, 2**33])
def test_int_draws_are_uniform_over_the_range(seed):
    t = Traffic(MIX, {"c": 50})
    ys = [body["query"]["value"] for name, body in
          (t.statement(seed, i) for i in range(800)) if name == "b"]
    # dealt: each pair of b statements holds 5 and 6 once, in a seeded order
    assert len(ys) == 200 and ys.count(5) == 100
    assert all(sorted(ys[j:j + 2]) == [5, 6] for j in range(0, 200, 2))
    assert ys[:40] != sorted(ys[:40])


@pytest.mark.parametrize("seed", [4, 2**31 + 11])
def test_every_seed_deals_each_combination_once_a_cycle(seed):
    mix = {"templates": [
        {"name": "q", "weight": 2,
         "draw": {"d": {"int": [2, 9]}, "q": {"int": [24, 25]}},
         "body": {"select": {"count": True},
                  "where": {"op": "and", "args": [
                      {"op": "range", "col": "c", "lo": "$d-1", "hi": "$d+1"},
                      {"op": "range", "col": "c", "hi": "$q-1"}]}}},
        {"name": "r", "weight": 1, "draw": {"y": {"int": [1, 3]}},
         "body": {"query": {"op": "eq", "col": "c", "value": "$y"}}}]}
    t = Traffic(mix, {"c": 50})
    assert t.deck_size(0) == 16 and t.deck_size(1) == 3
    keys = {}
    for i in range(3 * 16 * 3):
        name, body = t.statement(seed, i)
        keys.setdefault(name, []).append(json.dumps(body, sort_keys=True))
    for name, size in (("q", 16), ("r", 3)):
        ks = keys[name]
        cycles = [ks[c:c + size] for c in range(0, len(ks), size)]
        # every combination once in each cycle, no repeat inside one
        assert all(sorted(c) == sorted(cycles[0]) for c in cycles)
        assert all(len(set(c)) == size for c in cycles)
    other = Traffic(mix, {"c": 50})
    assert sorted(json.dumps(other.statement(seed + 1, i)[1], sort_keys=True)
                  for i in range(48) if other.statement(seed + 1, i)[0] == "q") \
        == sorted(keys["q"][:32])


def test_max_operands_counts_the_widest_statement():
    t = Traffic(MIX, {"c": 50})
    assert t.max_operands(1) == 3 and t.max_operands(2) == 6
    mix = {"templates": [
        {"name": "q", "draw": {"q": {"int": [3, 30]}},
         "body": {"select": {"count": True},
                  "where": {"op": "and", "args": [
                      {"op": "range", "col": "c", "hi": "$q"},
                      {"op": "not", "arg": {"op": "eq", "col": "c",
                                            "value": 1}},
                      {"op": "range", "col": "c", "lo": 45, "hi": 99}]}}}]}
    # 0..30, one value, 45..49 (the domain's end)
    assert Traffic(mix, {"c": 50}).max_operands(1) == 31 + 1 + 5
    dense = Traffic(load_mix("dense-filters"), {
        "l_discount": 11, "l_quantity": 51, "l_shipmode": 7,
        "l_shipinstruct": 4})
    # Q6 at DISCOUNT 0.09 and QUANTITY 25: 3 discounts and 0..24
    assert dense.max_operands(1) == 3 + 25


def test_warmup_takes_both_ends():
    t = Traffic(MIX, {"c": 50})
    w = t.warmup()
    assert [n for n, _ in w] == ["a", "a", "b", "b"]
    assert w[0][1]["lo"] == 0 and w[1][1]["lo"] == 99
    assert w[0][1]["where"]["values"] == [0, 1, 2]
    assert w[1][1]["where"]["values"] == [47, 48, 49]
    assert [b["query"]["value"] for _, b in w[2:]] == [5, 6]
    assert [b["lo"] for _, b in t.warmup(ends=("hi",))[:1]] == [99]


def test_mixes_load_by_name():
    mix = load_mix("dense-filters")
    assert mix["templates"] and mix["clients"] == 4
    assert load_mix("dense_filters") == mix
