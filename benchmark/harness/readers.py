"""Metric readers: one small JSON file per metric says where its number
comes from, and the few kinds of reader below turn that into the number.

    {"kind": "run", "value": "setup_s"}
    {"kind": "client", "series": "read_ms", "reduce": "p95"}
    {"kind": "scrape-delta", "num": [{"metric": ..., "labels": {...},
        "label_in": {...}}], "den": [...] | "reads" | "window_s" | ...,
        "scale": 1000}
    {"kind": "span-tree", "root": [...], "minus": [...]}   mean self ms
    {"kind": "span-tree", "count": "stack.build"}          mean per tree
    {"kind": "launcher", "counter": "programs"}            window delta
    {"kind": "trace", "match": regex | null, "per": "reads" | null,
        "scale": 1000}                                     device seconds
    {"kind": "trace-roofline", "match": regex,
        "cost": "mm" | "pair_sums"}                        % of roofline

A reader that finds nothing to read returns None and the harness leaves
the metric out of the line. ``Readings`` is what one run hands them.
"""

import dataclasses
import json
import os
import re

from . import kernel_cost, stats, xplane

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Readings:
    values: dict            # run-level numbers by name
    series: dict            # client-side samples by name, in ms
    counts: dict            # denominators: reads, window_s, batches...
    scrape_before: dict = None
    scrape_after: dict = None
    trees: list = None      # sampled ?profile=true span trees
    launcher_before: dict = None
    launcher_after: dict = None
    trace: dict = None      # xplane.dump() of the traced slice
    trace_counts: dict = None   # denominators inside the slice
    device_kind: str = ""


_REDUCE = {
    "p50": lambda xs: stats.percentile(xs, 0.50),
    "p95": lambda xs: stats.percentile(xs, 0.95),
    "p99": lambda xs: stats.percentile(xs, 0.99),
    "max": max,
    "mean": lambda xs: sum(xs) / len(xs),
    "tail": lambda xs: stats.tail(xs)[1],
}


def read(spec, r):
    """The metric's value as a float, or None when its source is absent
    from this run."""
    return _KINDS[spec["kind"]](spec, r)


def _run(spec, r):
    return r.values.get(spec["value"])


def _client(spec, r):
    xs = r.series.get(spec["series"])
    return float(_REDUCE[spec["reduce"]](xs)) if xs else None


def _series_delta(r, terms):
    return sum(stats.delta(r.scrape_before, r.scrape_after, t["metric"],
                           t.get("labels"), t.get("label_in"))
               for t in terms)


def _scrape_delta(spec, r):
    if r.scrape_before is None or r.scrape_after is None:
        return None
    num = _series_delta(r, spec["num"])
    den = spec.get("den")
    if den is None:
        den = 1.0
    elif isinstance(den, str):
        den = r.counts.get(den)
    else:
        den = _series_delta(r, den)
    if not den:
        return None
    return num / den * float(spec.get("scale", 1.0))


def _spans(tree, names):
    """Top-most spans of ``tree`` named in ``names`` (a match's own
    descendants are not searched)."""
    if tree.get("name") in names:
        return [tree]
    return [s for c in tree.get("children", []) for s in _spans(c, names)]


def _span_tree(spec, r):
    if not r.trees:
        return None
    per_tree = []
    for tree in r.trees:
        if "count" in spec:
            per_tree.append(float(len(_all_spans(tree, spec["count"]))))
            continue
        roots = _spans(tree, set(spec["root"]))
        if not roots:
            continue
        below = sum(s["duration_ns"] for c in roots[0].get("children", [])
                    for s in _spans(c, set(spec.get("minus", []))))
        per_tree.append((roots[0]["duration_ns"] - below) / 1e6)
    return sum(per_tree) / len(per_tree) if per_tree else None


def _all_spans(tree, name):
    own = [tree] if tree.get("name") == name else []
    return own + [s for c in tree.get("children", [])
                  for s in _all_spans(c, name)]


def _launcher(spec, r):
    if r.launcher_before is None or r.launcher_after is None:
        return None
    key = spec["counter"]
    return float(r.launcher_after[key] - r.launcher_before[key])


def _trace(spec, r):
    """Device seconds of the matching operations (the busy union when
    ``match`` is null), averaged over the chips."""
    if r.trace is None:
        return None
    planes = xplane.device_ops(r.trace)
    den = r.trace_counts.get(spec["per"]) if spec.get("per") else 1.0
    if not planes or not den:
        return None
    if spec.get("match") is None:
        per_chip = [xplane.busy_seconds(ev) for ev in planes.values()]
    else:
        per_chip = [sum(xplane.op_seconds(ev, spec["match"]).values())
                    for ev in planes.values()]
    return (sum(per_chip) / len(per_chip) / den
            * float(spec.get("scale", 1.0)))


def _trace_roofline(spec, r):
    """Share of its roofline the matching kernel reached: the least time
    the chip could take for its calls (operations over peak, bytes over
    peak bandwidth, whichever is larger, from each call's own shapes)
    over the time the trace shows for them."""
    if r.trace is None:
        return None
    rx = re.compile(spec["match"])
    cost = kernel_cost.FROM_TEXT[spec["cost"]]
    calls = [(need, dur) for events in xplane.device_ops(r.trace).values()
             for name, _, dur in events if rx.search(name)
             for need in [cost(name)] if need is not None]
    if not calls:
        return None
    peak = peaks(r.device_kind)
    least = sum(max(ops / (peak["int8_tops"] * 1e12),
                    nbytes / (peak["hbm_gbps"] * 1e9))
                for (ops, nbytes), _ in calls)
    return 100.0 * least / (sum(dur for _, dur in calls) / 1e9)


def peaks(device_kind):
    """The table row of ``device_kind``; a device that is not in the
    table is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)["peaks"]
    if device_kind not in table:
        raise LookupError(f"benchmark/peaks.json has no row for device "
                          f"kind {device_kind!r}")
    return table[device_kind]


_KINDS = {"run": _run, "client": _client, "scrape-delta": _scrape_delta,
          "span-tree": _span_tree, "launcher": _launcher, "trace": _trace,
          "trace-roofline": _trace_roofline}
KINDS = frozenset(_KINDS)
