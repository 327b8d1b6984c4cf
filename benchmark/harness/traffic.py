"""The one general traffic generator: a mix file plus a seed give the
whole schedule before the window opens.

A query family (``benchmark/queries/<family>.json``) is a text with named
parameters and its meaning as data; a mix (``benchmark/traffic/<mix>.json``)
says which families, in which proportion or order, on which loop. Nothing
here knows a family or a mix by name.
"""

import dataclasses
import json

import numpy as np


@dataclasses.dataclass
class Request:
    family: str
    route: str          # "pql" | "sql"
    text: str
    meaning: dict
    at: float = 0.0     # open loop: intended send time from window start


def _draw(rng, spec, field):
    """One parameter value: a slot of a bitmap field, a value of an int
    field, or a bare integer from ``lo``..``hi``."""
    if "value" in spec:
        return int(spec["value"])
    if field is None:
        lo, hi = spec["lo"], spec["hi"]
    elif field["type"] == "int":
        lo, hi = field["min"], field["max"]
    else:
        lo, hi = 0, field["rows"] - 1
    lo, hi = int(spec.get("lo", lo)), int(spec.get("hi", hi))
    dist = spec.get("dist", "uniform")
    if dist == "uniform":
        return int(rng.integers(lo, hi + 1))
    if dist.startswith("zipf:"):
        # rank r of the domain with probability ~ r^-s, rank 1 = lo
        ranks = np.arange(1, hi - lo + 2, dtype=np.float64)
        p = ranks ** -float(dist[5:])
        return lo + int(rng.choice(ranks.size, p=p / p.sum()))
    raise ValueError(f"unknown distribution {dist!r}")


def _render(value, field):
    """A parameter as it appears in the query text."""
    if field is None or field["type"] == "int":
        return str(value)
    if field["keys"] is not None:
        return json.dumps(field["keys"][value])
    return str(field["ids"][value])


def instantiate(family, fields, index, rng):
    """Draw a family's parameters and return the request."""
    values, shown = {}, {"index": index}
    for name, spec in family.get("params", {}).items():
        field = fields.get(spec.get("field"))
        if "from" in spec:
            values[name] = values[spec["from"]] + int(spec.get("add", 0))
        else:
            values[name] = _draw(rng, spec, field)
        shown[name] = _render(values[name], field)

    def bind(v):
        if isinstance(v, str):
            return values[v]
        if isinstance(v, list):
            return [bind(x) for x in v]
        return v

    meaning = dict(family["meaning"])
    meaning["filter"] = [[f, op, bind(v)]
                         for f, op, v in meaning.get("filter", [])]
    return Request(family["name"], family.get("route", "pql"),
                   family["text"].format(**shown), meaning)


def _weighted(rng, weights, n):
    names = sorted(weights)
    p = np.array([weights[k] for k in names], dtype=np.float64)
    return [names[i] for i in rng.choice(len(names), size=n, p=p / p.sum())]


def open_schedule(mix, families, fields, index, seed, seconds):
    """Requests with intended send times in [0, seconds): Poisson or
    evenly spaced arrivals at the mix's fixed rate. The families are
    drawn iid by their weights, or, with ``cycle``, walk a fixed cycle of
    that many places (``_cycle``) from its start: then the seed draws the
    parameters and never which families a window holds."""
    rng = np.random.default_rng([int(seed), 1])
    rate = float(mix["rate"])
    if mix.get("arrivals", "poisson") == "poisson":
        gaps = rng.exponential(1.0 / rate, int(rate * seconds * 1.5) + 64)
        times = np.cumsum(gaps)
    else:
        times = np.arange(int(rate * seconds)) / rate
    times = times[times < seconds]
    if "cycle" in mix:
        cycle = _cycle(mix["families"], int(mix["cycle"]))
        names = [cycle[i % len(cycle)] for i in range(times.size)]
    else:
        names = _weighted(rng, mix["families"], times.size)
    out = []
    for t, name in zip(times, names):
        req = instantiate(families[name], fields, index, rng)
        req.at = float(t)
        out.append(req)
    return out


CYCLE = 100


def _cycle(weights, size=CYCLE):
    """``size`` family names holding each family in its proportion
    (largest remainders make up the total) and spread evenly: the j-th of
    a family's c places is at (j + 1/2) / c of the cycle."""
    names = sorted(weights)
    total = float(sum(weights.values()))
    exact = {k: weights[k] / total * size for k in names}
    count = {k: int(exact[k]) for k in names}
    for k in sorted(names, key=lambda k: count[k] - exact[k])[
            :size - sum(count.values())]:
        count[k] += 1
    places = sorted(((j + 0.5) / count[k], k)
                    for k in names for j in range(count[k]))
    return [k for _, k in places]


def closed_sequences(mix, families, fields, index, seed, length=4096):
    """One request sequence per client, replayed from its start when it
    ends. With ``round`` the mix replays a fixed round of ``round_draws``
    instances per family, each client starting at its own offset; with
    ``families`` every client walks the same cycle of 100 families in
    proportion to the weights, evenly spread, each client starting a
    further 100 / clients places into it. The seed draws the parameters,
    never the amount of work."""
    rng = np.random.default_rng([int(seed), 2])
    clients = int(mix["clients"])
    if "round" in mix:
        base = [instantiate(families[name], fields, index, rng)
                for _ in range(int(mix.get("round_draws", 1)))
                for name in mix["round"]]
        step = max(1, len(base) // clients)
        return [base[i * step:] + base[:i * step] for i in range(clients)]
    cycle = _cycle(mix["families"])
    starts = [k * len(cycle) // clients for k in range(clients)]
    return [[instantiate(families[cycle[(start + i) % len(cycle)]], fields,
                         index, rng) for i in range(length)]
            for start in starts]
