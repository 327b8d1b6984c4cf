"""From the profiler's ``.xplane.pb`` to numbers.

Reading the file needs JAX's own reader, and the benchmark's parent stays
off JAX, so ``python xplane.py <trace.xplane.pb> <out.json>`` runs as a
short process of its own (on the CPU platform) and leaves plain JSON:

    {"names": [...],
     "planes": [{"name": ..., "lines": [{"name": ...,
        "events": [[index into names, start_ns, duration_ns], ...]}]}]}

(a TPU's operation events are named by their whole HLO text and repeat
by the hundred thousand, so names are kept once). ``load`` puts the names
back. Everything after that (which planes are devices, the union of busy
intervals, per-operation sums, who owned an idle gap) is plain Python
below, tested on a trace recorded on the chip (``benchmark/tests/data``).

On a TPU v5e the device plane ``/device:TPU:<n>`` has a line ``XLA Ops``
with one event per executed HLO operation (a ``while`` and the operations
of its body both appear, so sums over operations count loops twice and
only the union says how long the device was busy) and a line ``XLA
Modules`` with one event per launched program, named
``jit_<function>(<fingerprint>)``.
"""

import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def dump(pb_path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(pb_path)
    index, planes = {}, []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[index.setdefault(e.name, len(index)),
                       float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"names": list(index), "planes": planes}


def load(path):
    """The dumped trace with every event's name in place."""
    with open(path) as fh:
        trace = json.load(fh)
    names = trace.pop("names")
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for event in line["events"]:
                event[0] = names[event[0]]
    return trace


def device_line(trace, line_name):
    """{plane name: [[name, start_ns, dur_ns], ...]}: the events of one
    line of every device plane."""
    return {plane["name"]: [e for ln in plane["lines"]
                            if ln["name"] == line_name
                            for e in ln["events"]]
            for plane in trace["planes"]
            if DEVICE_PLANE.match(plane["name"])}


def device_ops(trace):
    return device_line(trace, OPS_LINE)


def device_modules(trace):
    return device_line(trace, MODULES_LINE)


def module_name(name):
    """``jit_f(123)`` -> ``jit_f``: the fingerprint changes with every
    change to the program, the function's name does not."""
    return re.sub(r"\(\d+\)$", "", name)


def host_events(trace):
    """Every event of every host plane, with durations above zero."""
    return [e for plane in trace["planes"]
            if plane["name"].startswith("/host:")
            for ln in plane["lines"] for e in ln["events"] if e[2] > 0]


def merged(events):
    """Sorted disjoint [start, end) intervals covering the events."""
    out = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def busy_seconds(events):
    return sum(end - start for start, end in merged(events)) / 1e9


def op_seconds(events, pattern=None):
    """{operation name: seconds}, optionally only names matching."""
    rx = re.compile(pattern) if pattern else None
    out = {}
    for name, _, dur in events:
        if rx is None or rx.search(name):
            out[name] = out.get(name, 0.0) + dur / 1e9
    return out


def span(trace):
    """(first start, last end) in ns over every event of the trace."""
    starts = [e[1] for p in trace["planes"] for ln in p["lines"]
              for e in ln["events"]]
    ends = [e[1] + e[2] for p in trace["planes"] for ln in p["lines"]
            for e in ln["events"]]
    return (min(starts), max(ends)) if starts else (0.0, 0.0)


def idle_gaps(events, host, window):
    """{owner: idle seconds}: every gap between device operations inside
    ``window`` = (start_ns, end_ns), charged to the outermost host event
    running at the gap's midpoint (the longest one that covers it: a
    ``PjitFunction(f)`` rather than the allocator call inside it), or to
    "no host event" when the host was in no call the profiler marks."""
    busy = merged(events)
    edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = sorted(host, key=lambda e: e[1])
    out, active, i = {}, [], 0
    for g0, g1 in gaps:
        mid = (g0 + g1) / 2.0
        while i < len(host) and host[i][1] <= mid:
            active.append(host[i])
            i += 1
        active = [e for e in active if e[1] + e[2] > mid]
        owner = max(active, key=lambda e: e[2])[0] if active \
            else "no host event"
        out[owner] = out.get(owner, 0.0) + (g1 - g0) / 1e9
    return out


def top(table, n=10):
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


if __name__ == "__main__":
    with open(sys.argv[2], "w") as fh:
        json.dump(dump(sys.argv[1]), fh)
