"""The arithmetic between raw readings and reported numbers."""

import math
import re


def percentile(values, q):
    """The ``q``-quantile (0..1) by linear interpolation between order
    statistics; raises on an empty sample."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tail(values, beyond=10):
    """(q, value) of the highest percentile that still has ``beyond``
    samples above it; the maximum's rank when the sample is too small."""
    xs = sorted(values)
    if not xs:
        raise ValueError("tail of an empty sample")
    i = max(0, len(xs) - 1 - beyond)
    return (i / (len(xs) - 1) if len(xs) > 1 else 1.0), xs[i]


def whole_cycles(events):
    """Rate over whole background cycles.

    ``events`` is a list of ``(time, cumulative_work, cycles_seen)`` in
    time order, one per acknowledged unit of work. A window that holds
    four and a half checkpoints shows a different rate from one that
    holds five; counting from the first reading after a cycle completed
    to the last such reading leaves the halves out. Returns (work,
    seconds, cycles) between those two readings, or None when fewer than
    two cycles completed."""
    marks = [e for prev, e in zip(events, events[1:]) if e[2] > prev[2]]
    if len(marks) < 2:
        return None
    first, last = marks[0], marks[-1]
    return last[1] - first[1], last[0] - first[0], last[2] - first[2]


# -- Prometheus text ---------------------------------------------------------

_LINE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})? (\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text):
    """{(name, frozenset(label items)): value} of an exposition."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if m is None:
            continue
        name, labels, value = m.groups()
        key = (name, frozenset(_LABEL.findall(labels or "")))
        out[key] = float(value)
    return out


def series_sum(scrape, name, labels=None, label_in=None):
    """Sum of every series called ``name`` (with or without the one-word
    prefix the server puts before its names), whose labels include
    ``labels``, and whose label ``k`` is one of ``label_in[k]``."""
    want = set((labels or {}).items())
    total = 0.0
    for (n, labs), v in scrape.items():
        if name not in (n, n.partition("_")[2]):
            continue
        d = dict(labs)
        if not want <= set(d.items()):
            continue
        if any(d.get(k) not in vs for k, vs in (label_in or {}).items()):
            continue
        total += v
    return total


def delta(before, after, name, labels=None, label_in=None):
    return (series_sum(after, name, labels, label_in)
            - series_sum(before, name, labels, label_in))
