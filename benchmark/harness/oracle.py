"""The plain reference: numpy over the generated columns.

A query family states its meaning as data (``benchmark/queries/*.json``):

    {"filter": [[field, op, value], ...],      # a conjunction
     "agg": "count" | {"sum": field} | {"topn": [field, n]}
            | {"groupby": [fields], "sum": field, "limit": n}}

``op`` is one of ``== != < <= > >= between in``. A value for a bitmap
field is a slot (see the dataset module); for an int field, the value.
``evaluate`` turns that into the expected answer and ``matches`` holds the
server's JSON to it, with no tolerance: answers are exact.
"""

import numpy as np


class Table:
    """The records the server is supposed to hold: the loaded columns
    plus every batch a writer had acknowledged."""

    def __init__(self, fields, columns):
        self.fields = {f["name"]: f for f in fields}
        self.parts = [columns]      # the loaded table, then each batch
        self._joined = (1, columns)
        self._memo = {}

    def append(self, batch):
        self.parts.append(batch)

    @property
    def columns(self):
        if self._joined[0] != len(self.parts):
            self._joined = (len(self.parts), {
                k: np.concatenate([p[k] for p in self.parts])
                for k in self.parts[0]})
        return self._joined[1]

    def evaluate(self, meaning):
        return evaluate(self.fields, self.columns, meaning)

    def prefix(self, text, meaning, n_parts):
        """The answer of an additive query over the first ``n_parts``
        parts: each part is evaluated once per query text."""
        total = None
        for i in range(n_parts):
            key = (text, i)
            if key not in self._memo:
                self._memo[key] = evaluate(self.fields, self.parts[i],
                                           meaning)
            total = _add(total, self._memo[key])
        return total


_OPS = {
    "==": lambda c, v: c == v, "!=": lambda c, v: c != v,
    "<": lambda c, v: c < v, "<=": lambda c, v: c <= v,
    ">": lambda c, v: c > v, ">=": lambda c, v: c >= v,
    "between": lambda c, v: (c >= v[0]) & (c <= v[1]),
    "in": lambda c, v: np.isin(c, v),
}


def mask(columns, conditions):
    out = np.ones(next(iter(columns.values())).size, dtype=bool)
    for field, op, value in conditions:
        out &= _OPS[op](columns[field], value)
    return out


def is_additive(meaning):
    """Counts and sums over disjoint sets of records add up."""
    agg = meaning["agg"]
    return agg == "count" or ("sum" in agg and "groupby" not in agg)


def evaluate(fields, columns, meaning):
    """The expected answer over ``columns`` in a canonical form that
    ``matches`` understands."""
    sel = mask(columns, meaning.get("filter", []))
    agg = meaning["agg"]
    if agg == "count":
        return int(sel.sum())
    if "sum" in agg and "groupby" not in agg:
        col = columns[agg["sum"]]
        return {"value": int(col[sel].sum(dtype=np.int64)),
                "count": int(sel.sum())}
    if "topn" in agg:
        field, n = agg["topn"]
        counts = np.bincount(columns[field][sel],
                             minlength=fields[field]["rows"])
        return {"counts": counts, "n": int(n)}
    names = agg["groupby"]
    dims = [fields[f]["rows"] for f in names]
    flat = np.ravel_multi_index(
        [columns[f][sel].astype(np.int64) for f in names], dims)
    size = int(np.prod(dims))
    counts = np.bincount(flat, minlength=size)
    sums = None
    if agg.get("sum"):
        # float64 weights are exact below 2^53, far above any sum here
        sums = np.bincount(flat, minlength=size, weights=columns[
            agg["sum"]][sel].astype(np.float64))
    groups = []
    # row-major over slots is ascending row-id order: ids and translated
    # keys both grow with the slot
    for g in np.flatnonzero(counts):
        row = {"slots": [int(s) for s in np.unravel_index(g, dims)],
               "count": int(counts[g])}
        if sums is not None:
            row["agg"] = int(sums[g])
        groups.append(row)
    limit = agg.get("limit")
    return {"groups": groups[:limit] if limit is not None else groups}


def _add(a, b):
    if a is None:
        return b
    if isinstance(b, dict):
        return {k: a[k] + b[k] for k in b}
    return a + b


def decode(meaning, answer, route="pql"):
    """The server's count or sum as this module writes them, or None when
    the body has another form."""
    try:
        if route == "sql":
            (value,), = answer["data"]
            return int(value)
        got = answer["results"][0]
        if meaning["agg"] == "count":
            return int(got)
        return {"value": int(got["value"]), "count": int(got["count"])}
    except (KeyError, IndexError, TypeError, ValueError):
        return None


def within(lo, got, hi):
    """lo <= got <= hi, component by component (records only ever arrive,
    and no stored value is negative, so counts and sums only grow)."""
    if got is None:
        return False
    if isinstance(lo, dict):
        return all(lo[k] <= got[k] <= hi[k] for k in lo)
    return lo <= got <= hi


def _wire(field, slot):
    """How the server names slot ``slot`` of ``field`` in an answer."""
    if field["keys"] is not None:
        return {"field": field["name"], "rowKey": field["keys"][slot]}
    return {"field": field["name"], "rowID": field["ids"][slot]}


def matches(table, meaning, expected, answer, route="pql"):
    """Whether the server's decoded JSON body equals the expectation."""
    agg = meaning["agg"]
    try:
        if route == "sql":
            return answer["data"] == [[expected]]
        got = answer["results"][0]
        if agg == "count" or ("sum" in agg and "groupby" not in agg):
            return got == expected
        if "topn" in agg:
            return _topn_matches(table.fields[agg["topn"][0]], expected,
                                 got)
        fields = [table.fields[f] for f in agg["groupby"]]
        want = []
        for g in expected["groups"]:
            row = {"group": [_wire(f, s)
                             for f, s in zip(fields, g["slots"])],
                   "count": g["count"]}
            if "agg" in g:
                row["agg"] = g["agg"]
            want.append(row)
        return got == want
    except (KeyError, IndexError, TypeError, ValueError):
        return False


def _topn_matches(field, expected, got):
    """Ties may rank either way: the count ladder must be the oracle's,
    every pair's count must be that row's, and no row may repeat."""
    counts, n = expected["counts"], expected["n"]
    ladder = sorted((int(c) for c in counts if c), reverse=True)[:n]
    if field["keys"] is not None:
        slot_of = {k: i for i, k in enumerate(field["keys"])}
        pairs = [(slot_of[p["key"]], p["count"]) for p in got["rows"]]
    else:
        slot_of = {r: i for i, r in enumerate(field["ids"])}
        pairs = [(slot_of[p["id"]], p["count"]) for p in got["rows"]]
    return ([c for _, c in pairs] == ladder
            and len({s for s, _ in pairs}) == len(pairs)
            and all(int(counts[s]) == c for s, c in pairs))
