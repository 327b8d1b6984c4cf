"""The generic evaluator against answers worked out record by record in
plain Python, for every family the benchmark ships, each against the
table of the dataset it is asked of."""

import itertools
import json

import numpy as np
import pytest

from harness import manifest, oracle, traffic

MAN = manifest.Manifest()


class Hand:
    """4,000 records of one dataset, as the oracle's table and as plain
    Python records."""

    def __init__(self, name):
        self.dataset = MAN.dataset(name)
        self.fields = self.dataset.fields()
        self.by_name = {f["name"]: f for f in self.fields}
        columns = self.dataset.make(7, 0, 4000)
        self.table = oracle.Table(self.fields, columns)
        self.records = [{k: int(v[i]) for k, v in columns.items()}
                        for i in range(4000)]


HANDS = {}


def hand(name):
    if name not in HANDS:
        HANDS[name] = Hand(name)
    return HANDS[name]


SSB = hand("ssb_flat")
DATASET, FIELDS, BY_NAME, TABLE = (SSB.dataset, SSB.fields, SSB.by_name,
                                   SSB.table)

_PY_OPS = {
    "==": lambda c, v: c == v, "!=": lambda c, v: c != v,
    "<": lambda c, v: c < v, "<=": lambda c, v: c <= v,
    ">": lambda c, v: c > v, ">=": lambda c, v: c >= v,
    "between": lambda c, v: v[0] <= c <= v[1],
    "in": lambda c, v: c in v,
}


def by_hand(meaning, of=SSB):
    """The answer by a loop over records, sharing nothing with oracle.py
    but the form of the result."""
    rows = [r for r in of.records
            if all(_PY_OPS[op](r[f], v) for f, op, v in meaning["filter"])]
    agg = meaning["agg"]
    if agg == "count":
        return len(rows)
    if "groupby" in agg:
        groups = {}
        for r in rows:
            key = tuple(r[f] for f in agg["groupby"])
            g = groups.setdefault(key, [0, 0])
            g[0] += 1
            g[1] += r[agg["sum"]] if agg.get("sum") else 0
        out = []
        for key in sorted(groups):
            row = {"slots": list(key), "count": groups[key][0]}
            if agg.get("sum"):
                row["agg"] = groups[key][1]
            out.append(row)
        return {"groups": out[:agg.get("limit")]}
    if "topn" in agg:
        counts = [0] * of.by_name[agg["topn"][0]]["rows"]
        for r in rows:
            counts[r[agg["topn"][0]]] += 1
        return counts
    return {"value": sum(r[agg["sum"]] for r in rows), "count": len(rows)}


def shipped_families():
    """(dataset, family) for every family under ``queries/``, against
    the dataset of a configuration whose mix names it, or, where no mix
    does, against the dataset that holds the fields it names."""
    import run

    return [pytest.param(dataset, fam,
                         id=fam["name"] + ":" + fam["text"][:24])
            for name in sorted(MAN.families)
            for dataset in MAN.family_datasets(name)
            for fam in run.expand_fields(MAN.families[name],
                                         hand(dataset).fields)]


@pytest.mark.parametrize("dataset, family", shipped_families())
def test_family_equals_the_hand_computation(dataset, family):
    of = hand(dataset)
    rng = np.random.default_rng(11)
    for _ in range(3):
        req = traffic.instantiate(family, of.by_name, of.dataset.INDEX,
                                  rng)
        got = of.table.evaluate(req.meaning)
        want = by_hand(req.meaning, of)
        if isinstance(got, dict) and "counts" in got:
            assert got["counts"].tolist() == want
        else:
            assert got == want
        # the text carries every parameter: nothing is left unrendered
        assert "{" not in req.text and "}" not in req.text


@pytest.mark.parametrize("op, value", [
    ("==", 3), ("!=", 3), ("<", 10), ("<=", 10), (">", 40), (">=", 40),
    ("between", [5, 9]), ("in", [1, 7, 50])])
def test_every_filter_operator(op, value):
    meaning = {"filter": [["lo_quantity", op, value]], "agg": "count"}
    assert TABLE.evaluate(meaning) == by_hand(meaning) > 0


def test_every_parameter_reaches_the_text():
    req = traffic.instantiate(MAN.families["count-intersect"], BY_NAME,
                              "ssb", np.random.default_rng(0))
    (_, _, year), (_, _, brand) = req.meaning["filter"]
    assert f"lo_year={BY_NAME['lo_year']['ids'][year]}" in req.text
    assert json.dumps(BY_NAME["p_brand1"]["keys"][brand]) in req.text


def test_prefix_adds_parts_and_within_bounds_a_racing_read():
    table = oracle.Table(FIELDS, DATASET.make(7, 0, 1000))
    for stream in (1, 2):
        table.append(DATASET.make(7, stream, 500))
    meaning = {"filter": [["lo_quantity", "<", 25]],
               "agg": {"sum": "lo_revenue"}}
    whole = table.evaluate(meaning)
    assert table.prefix("q", meaning, 3) == whole
    lo, hi = table.prefix("q", meaning, 1), table.prefix("q", meaning, 3)
    mid = table.prefix("q", meaning, 2)
    assert oracle.within(lo, mid, hi)
    assert not oracle.within(lo, {"value": hi["value"] + 1,
                                  "count": hi["count"]}, hi)
    assert not oracle.within(lo, None, hi)


def wire(meaning, expected):
    """The JSON body a correct server would send."""
    agg = meaning["agg"]
    if agg == "count" or ("sum" in agg and "groupby" not in agg):
        return {"results": [expected]}
    if "topn" in agg:
        field = BY_NAME[agg["topn"][0]]
        order = sorted(range(field["rows"]),
                       key=lambda s: -expected["counts"][s])[:agg["topn"][1]]
        return {"results": [{"field": field["name"], "rows": [
            {"key": field["keys"][s], "count": int(expected["counts"][s])}
            for s in order if expected["counts"][s]]}]}
    fields = [BY_NAME[f] for f in agg["groupby"]]
    rows = []
    for g in expected["groups"]:
        row = {"group": [oracle._wire(f, s)
                         for f, s in zip(fields, g["slots"])],
               "count": g["count"]}
        if "agg" in g:
            row["agg"] = g["agg"]
        rows.append(row)
    return {"results": [rows]}


@pytest.mark.parametrize("name", ["count-intersect", "sum-filter",
                                  "topn-filter", "ssb-q2.1", "ssb-q3.1",
                                  "groupby2-count"])
def test_matches_accepts_the_right_body_and_refuses_a_wrong_one(name):
    req = traffic.instantiate(MAN.families[name], BY_NAME, "ssb",
                              np.random.default_rng(5))
    expected = TABLE.evaluate(req.meaning)
    body = wire(req.meaning, expected)
    assert oracle.matches(TABLE, req.meaning, expected, body)
    wrong = json.loads(json.dumps(body))
    first = wrong["results"][0]
    if isinstance(first, int):
        wrong["results"][0] += 1
    elif isinstance(first, list):
        first[0]["count"] += 1
    elif "rows" in first:
        first["rows"][0]["count"] += 1
    else:
        first["value"] += 1
    assert not oracle.matches(TABLE, req.meaning, expected, wrong)
    assert not oracle.matches(TABLE, req.meaning, expected, {"error": "x"})


def test_sql_answers_are_compared_as_rows():
    req = traffic.instantiate(MAN.families["sql-count"], BY_NAME, "ssb",
                              np.random.default_rng(5))
    n = TABLE.evaluate(req.meaning)
    assert oracle.matches(TABLE, req.meaning, n, {"data": [[n]]}, "sql")
    assert not oracle.matches(TABLE, req.meaning, n, {"data": [[n + 1]]},
                              "sql")
    assert oracle.decode(req.meaning, {"data": [[n]]}, "sql") == n


def test_topn_ties_may_rank_either_way():
    field = BY_NAME["lo_shipmode"]
    counts = np.array([5, 9, 9, 1, 0, 0, 0])
    expected = {"counts": counts, "n": 2}
    meaning = {"filter": [], "agg": {"topn": ["lo_shipmode", 2]}}
    for order in itertools.permutations([1, 2]):
        body = {"results": [{"rows": [{"id": field["ids"][s], "count": 9}
                                      for s in order]}]}
        assert oracle.matches(TABLE, meaning, expected, body)
    twice = {"results": [{"rows": [{"id": 1, "count": 9}] * 2}]}
    assert not oracle.matches(TABLE, meaning, expected, twice)
