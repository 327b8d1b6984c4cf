"""Holding the server to the oracle, outside the window."""

import concurrent.futures
import json
import threading

import numpy as np

from . import loadgen, oracle, stats
from .child import Failed


def check_read(cell, table, conn, req, what):
    """Send one read; it must equal the oracle."""
    status, body = loadgen.send_read(conn, req, cell.index)
    if status != 200:
        raise Failed(f"{what}: {req.text!r}: HTTP {status}: {body[:300]!r}")
    if not oracle.matches(table, req.meaning, table.evaluate(req.meaning),
                          json.loads(body), req.route):
        raise Failed(f"{what}: {req.text!r} differs from the oracle: "
                     f"{body[:300]!r}")


def verify_reads(cell, table, done, batches=(), loaded_parts=1,
                 sample=500):
    """(wrong, checked): how many of a seeded sample of answered reads
    differ from the oracle. A read that raced a writer must lie between
    the answer over the batches acknowledged before it was sent and the
    answer over those begun before it returned."""
    ok = [d for d in done if d.ok]
    rng = np.random.default_rng([cell.seed, 5])
    if len(ok) > sample:
        ok = [ok[i] for i in rng.choice(len(ok), sample, replace=False)]
    cache, lock = {}, threading.Lock()

    def expected(req):
        with lock:
            hit = cache.get(req.text)
        if hit is None:
            hit = table.evaluate(req.meaning)
            with lock:
                cache[req.text] = hit
        return hit

    def one(d):
        answer = json.loads(d.body)
        req = d.request
        if not batches:
            return oracle.matches(table, req.meaning, expected(req),
                                  answer, req.route)
        lo = loaded_parts + sum(1 for b in batches
                                if b.acked and b.acked <= d.sent)
        hi = loaded_parts + sum(1 for b in batches if b.started < d.done)
        return oracle.within(
            table.prefix(req.text, req.meaning, lo),
            oracle.decode(req.meaning, answer, req.route),
            table.prefix(req.text, req.meaning, min(hi, len(table.parts))))

    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        return sum(1 for good in pool.map(one, ok) if not good), len(ok)


def kernel_table(scraped):
    """{kernel: {"dispatch": n, "fallback": {why: n}}} since the server
    started, and its mesh placement fallbacks."""
    table = {}
    for (name, labels), value in scraped.items():
        d = dict(labels)
        if name.endswith("ops_pallas_dispatch_total"):
            table.setdefault(d["kernel"], {"dispatch": 0, "fallback": {}})[
                "dispatch"] += int(value)
        elif name.endswith("ops_pallas_fallback_total") and value:
            table.setdefault(d["kernel"], {"dispatch": 0, "fallback": {}})[
                "fallback"][d["why"]] = int(value)
    return table, int(stats.series_sum(scraped,
                                       "mesh_sharding_fallback_total"))


def span_trees(done):
    """The ``?profile=true`` trees of the sampled reads."""
    out = []
    for d in done:
        if d.profiled and d.ok:
            tree = json.loads(d.body).get("profile")
            if tree:
                out.append(tree)
    return out
