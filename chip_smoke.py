#!/usr/bin/env python3
"""Chip smoke: the served query path, end to end, on the accelerator.

Starts ONE child — ``python -m pilosa_tpu server`` — which owns the chip,
loads an SSB-shaped fact table (SF-1 lineorder: 6 shards x 2^20 columns)
through the HTTP import endpoints, asks it the query families the engine
serves, and compares every answer for equality with a numpy oracle
computed from the same arrays. Then a write is acknowledged and read
back, and ``/metrics`` must show every expected Pallas kernel dispatched
with no error fallback. Any failed check, non-2xx answer, dead server or
raised phase exits non-zero and prints no result line.

Then, with the server gone and the chip free, one child process checks
the key-plane form of a mutex stack too tall for its device budget
(``core/stacked.KeyedSet``): a small stack under a tiny budget, its rows
derived by the compiled ``_key_rows_pallas`` equal to the interpreter's,
its served answers equal to numpy, and the kernel's dispatch counter
ticked (on a mesh: the XLA twin, counted ``why="mesh"``).

The last two lines of standard output are JSON objects: first the smoke
report (per-query cold/warm ms, load rows/s, the kernel table, device
bytes, ``native``, versions, compile cache), then the verdict, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": n}}``
with the device as the server's JAX reports it.

This parent never imports JAX (stdlib + numpy + ``pilosa_tpu.client``): a
parent that touched JAX would hold the chip the server child needs.

    python chip_smoke.py                        # the chip check
    python chip_smoke.py --shards 1 --allow-cpu # CPU dry run of the flow

``--allow-cpu`` skips only the device and kernel-dispatch assertions and
says so in the report (``"device_checks": "skipped"``). Timings printed
here are smoke timings, not metrics.
"""

import argparse
import importlib.metadata
import json
import os
import re
import shutil
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

from pilosa_tpu.client import Client, Schema

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

SHARD_WIDTH = 1 << 20
INDEX = "ssb"
YEARS = 7
BRANDS = 1000
SHIPMODES = 7
REVENUE_BITS = 20
DAY_COLS = 65_536
#: the load starts 5,000 columns into a day, so every ingest-day range
#: straddles the 16,384-column compression tiles: the compressed block
#: then holds real dense tiles for ctile_count, not only run tiles
DAY_OFFSET = 5_000
READ_RUNS = 3

#: Pallas kernels that must show >= 1 dispatch on one chip. On a mesh a
#: compiled pallas_call cannot take sharded operands: the pair-count
#: family (the GroupBy and its Sum) runs it per chip under shard_map +
#: psum and still dispatches; the other families (and compression) take
#: the XLA path with why="mesh". What dispatches there is listed below.
EXPECTED_KERNELS = ("tape_count", "bsi_compare", "bsi_sum", "topn",
                    "pair_counts", "pair_sums", "ingest_scatter",
                    "ctile_count")
EXPECTED_KERNELS_MESH = ("pair_counts", "pair_sums", "ingest_scatter")
#: ... and which of those must have run as the per-chip mesh program
EXPECTED_ON_MESH = ("pair_counts", "pair_sums")


def fail(msg):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def brand_key(b):
    return f"MFGR#{1000 + int(b)}"


# -- data (numpy only, from --seed) ------------------------------------------

def make_data(seed, shards):
    n = shards * SHARD_WIDTH
    rng = np.random.default_rng(seed)
    cols = np.arange(n, dtype=np.int64)
    return {
        "cols": cols,
        "year": rng.integers(0, YEARS, n, dtype=np.int64),
        "brand": rng.integers(0, BRANDS, n, dtype=np.int64),
        "quantity": rng.integers(1, 51, n, dtype=np.int64),
        "revenue": rng.integers(0, 1 << REVENUE_BITS, n, dtype=np.int64),
        "shipmode": rng.integers(0, SHIPMODES, n, dtype=np.int64),
        "loadday": (cols + DAY_OFFSET) // DAY_COLS,
    }


def load(client, data, shards):
    """One shard per request through /import and /import-values."""
    schema = Schema()
    idx = schema.index(INDEX)
    idx.field("year", type="mutex")
    idx.field("brand", type="mutex", keys=True)
    idx.field("quantity", type="int", min=1, max=50)
    idx.field("revenue", type="int", min=0, max=(1 << REVENUE_BITS) - 1)
    idx.field("shipmode", type="set")
    idx.field("loadday", type="set")
    client.sync_schema(schema)
    keys = np.array([brand_key(b) for b in range(BRANDS)])
    rows = 0
    t0 = time.perf_counter()
    for s in range(shards):
        sl = slice(s * SHARD_WIDTH, (s + 1) * SHARD_WIDTH)
        cols = data["cols"][sl].tolist()
        for field in ("year", "shipmode", "loadday"):
            client._json("POST", f"/index/{INDEX}/import",
                         {"field": field, "rows": data[field][sl].tolist(),
                          "cols": cols})
        client._json("POST", f"/index/{INDEX}/import",
                     {"field": "brand", "cols": cols,
                      "rowKeys": keys[data["brand"][sl]].tolist()})
        for field in ("quantity", "revenue"):
            client._json("POST", f"/index/{INDEX}/import-values",
                         {"field": field, "cols": cols,
                          "values": data[field][sl].tolist()})
        rows += len(cols)
    dt = time.perf_counter() - t0
    return {"rows": rows, "seconds": dt, "rows_per_s": rows / dt}


# -- checks: each returns (pql, oracle-compare(result) -> bool) --------------

def read_checks(d):
    year, brand, qty, rev = d["year"], d["brand"], d["quantity"], d["revenue"]
    pair = np.bincount(year * BRANDS + brand,
                       minlength=YEARS * BRANDS).reshape(YEARS, BRANDS)
    brand_n = pair.sum(axis=0)
    day_n = np.bincount(d["loadday"])
    sel2 = year == 2

    def topn_brand(res):
        # brand row ids are server-assigned, so rank ties may order
        # either way: the count ladder must match, every pair's count
        # must be that brand's, and no brand may repeat
        want = sorted(brand_n.tolist(), reverse=True)[:10]
        got = [(p["key"], p["count"]) for p in res["rows"]]
        return ([c for _, c in got] == want
                and len({k for k, _ in got}) == len(got)
                and all(int(brand_n[int(k[5:]) - 1000]) == c
                        for k, c in got))

    def groupby(res):
        # groups come sorted by (year row, brand row id); every
        # (year=0, brand) pair is non-empty at this scale, so the first
        # 100 groups are 100 distinct brands of year 0
        seen = set()
        for g in res:
            y = g["group"][0]["rowID"]
            b = int(g["group"][1]["rowKey"][5:]) - 1000
            if y != 0 or g["count"] != int(pair[y, b]):
                return False
            seen.add(b)
        return len(res) == 100 and len(seen) == 100

    sel = d["shipmode"] == 2
    cell = (year * BRANDS + brand)[sel]
    sel_n = np.bincount(cell, minlength=YEARS * BRANDS)
    # float64 weights are exact here: a sum stays far below 2^53
    sel_rev = np.bincount(cell, weights=rev[sel].astype(np.float64),
                          minlength=YEARS * BRANDS)

    def groupby_sum(res):
        # every non-empty (year, brand) cell of the filtered rows, once,
        # with its count and the exact sum of its revenue
        got = {(g["group"][0]["rowID"],
                int(g["group"][1]["rowKey"][5:]) - 1000):
               (g["count"], g["agg"]) for g in res}
        want = {(int(c) // BRANDS, int(c) % BRANDS):
                (int(sel_n[c]), int(sel_rev[c]))
                for c in np.flatnonzero(sel_n)}
        return len(got) == len(res) and got == want

    def topn_day(res):
        order = sorted(range(day_n.size), key=lambda r: (-day_n[r], r))[:5]
        return ([(p["id"], p["count"]) for p in res["rows"]]
                == [(r, int(day_n[r])) for r in order])

    lt25 = int((qty < 25).sum())
    return [
        ("tape_count",
         f'Count(Intersect(Row(year=3), Row(brand="{brand_key(234)}")))',
         lambda r: r == int(pair[3, 234])),
        ("bsi_compare", "Count(Row(quantity < 25))", lambda r: r == lt25),
        ("bsi_sum", "Sum(Row(year=2), field=revenue)",
         lambda r: (r["value"], r["count"])
         == (int(rev[sel2].sum()), int(sel2.sum()))),
        ("topn", "TopN(brand, n=10)", topn_brand),
        ("groupby", "GroupBy(Rows(year), Rows(brand), limit=100)", groupby),
        ("groupby_sum", "GroupBy(Rows(year), Rows(brand), "
         "filter=Row(shipmode=2), aggregate=Sum(field=revenue))",
         groupby_sum),
        ("compressed_row", "Count(Row(loadday=5))",
         lambda r: r == int(day_n[5]) == DAY_COLS),
        ("compressed_topn", "TopN(loadday, n=5)", topn_day),
    ], lt25


def timed_reads(run, label, oracle_ok, out):
    """Run one read READ_RUNS times; every answer must pass the oracle.
    Prints and records the cold (first) and warm (last) wall times."""
    ms = []
    for i in range(READ_RUNS):
        t0 = time.perf_counter()
        res = run()
        ms.append((time.perf_counter() - t0) * 1e3)
        if not oracle_ok(res):
            fail(f"{label}: run {i + 1} differs from the numpy oracle: "
                 f"{json.dumps(res)[:400]}")
    print(f"  {label:<16} ok  cold {ms[0]:10.1f} ms   warm {ms[-1]:9.1f} ms")
    out[label] = {"equal": True, "cold_ms": ms[0], "warm_ms": ms[-1],
                  "runs_ms": ms}


# -- /metrics ------------------------------------------------------------------

_METRIC = re.compile(r"^(\w+?)(?:\{(.*)\})? (\S+)$")


def kernel_table(text):
    """{kernel: {"dispatch": n, "on_mesh": n, "body": {body: n},
    "fallback": {why: n}}} (``on_mesh``: the dispatches that ran per chip
    under shard_map; ``body``: those of the pair-count kernel by the body
    its operands' heights chose) and the mesh placement fallback count,
    from the Prometheus exposition."""
    table, mesh_fallback = {}, 0.0

    def row_of(kernel):
        return table.setdefault(
            kernel, {"dispatch": 0, "on_mesh": 0, "body": {},
                     "fallback": {}})

    for line in text.splitlines():
        m = None if line.startswith("#") else _METRIC.match(line)
        if m is None:
            continue
        name, labels, value = m.groups()
        lab = dict(re.findall(r'(\w+)="([^"]*)"', labels or ""))
        if name.endswith("ops_pallas_dispatch_total"):
            row_of(lab["kernel"])["dispatch"] += int(float(value))
        elif name.endswith("ops_pallas_mesh_dispatch_total"):
            row_of(lab["kernel"])["on_mesh"] += int(float(value))
        elif name.endswith("ops_pallas_body_total"):
            row_of(lab["kernel"])["body"][lab["body"]] = int(float(value))
        elif name.endswith("ops_pallas_fallback_total"):
            row_of(lab["kernel"])["fallback"][lab["why"]] = int(float(value))
        elif name.endswith("mesh_sharding_fallback_total"):
            mesh_fallback += float(value)
    return table, int(mesh_fallback)


def check_kernels(table, mesh_fallback, expected, log_path, on_mesh=()):
    """No kernel may have struck out, no stack may have lost its mesh
    placement, every expected kernel must have dispatched, those of
    ``on_mesh`` as the per-chip mesh program, and every ``pair_counts``
    dispatch must name the kernel body it took."""
    for kernel, row in table.items():
        for why in ("error", "failures"):
            if row["fallback"].get(why):
                fail(f"kernel {kernel} fell back with why={why!r} "
                     f"x{row['fallback'][why]}; see {log_path}")
    if mesh_fallback:
        fail(f"mesh_sharding_fallback_total = {mesh_fallback}")
    missing = [k for k in expected if not table.get(k, {}).get("dispatch")]
    if missing:
        fail(f"kernel dispatch check: zero dispatches of {missing}")
    missing = [k for k in on_mesh if not table.get(k, {}).get("on_mesh")]
    if missing:
        fail(f"kernel dispatch check: zero mesh dispatches of {missing}")
    row = table.get("pair_counts")
    if row and sum(row["body"].values()) != row["dispatch"]:
        fail(f"kernel body check: {row['dispatch']} dispatches of "
             f"pair_counts took the bodies {row['body']}")


# -- server child ----------------------------------------------------------------

def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_ready(client, proc, log_path, timeout_s=300.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            fail(f"server exited rc={proc.returncode} before serving; "
                 f"see {log_path}")
        try:
            client.status()
            return
        except OSError:
            time.sleep(0.2)
    fail(f"server not ready after {timeout_s:.0f}s; see {log_path}")


def cache_entries(cache_dir):
    """Files in the server's persistent compile cache (0 before the
    first program is written there)."""
    if not os.path.isdir(cache_dir):
        return 0
    return len(os.listdir(cache_dir))


#: the key-rows step: one shard of a 300-row mutex field, on one chip in
#: 32-row blocks (SF-10's height), 40 MB dense against a budget of 4 MB
#: (both limits are a device's: on a mesh the blocks are taller)
KEY_ROWS_ENV = {"PILOSA_TPU_DEVICE_BUDGET": str(4 << 20),
                "PILOSA_TPU_BLOCK_BYTES_MB": "8"}
KEY_ROWS = 300


def key_rows_child(seed):
    """The key-rows step's own process (it imports JAX and takes the
    chip): prints one JSON line of what it found."""
    import jax

    from pilosa_tpu.core import FieldOptions, FieldType, Holder
    from pilosa_tpu.core import stacked as stx
    from pilosa_tpu.obs import metrics as M
    from pilosa_tpu.ops import keyrows as K
    from pilosa_tpu.pql import Executor

    rng = np.random.default_rng(seed)
    cols = rng.choice(SHARD_WIDTH, 200_000, replace=False)
    rows = rng.integers(0, KEY_ROWS, cols.size)
    holder = Holder()
    field = holder.create_index("k").create_field(
        "m", FieldOptions(type=FieldType.MUTEX))
    field.import_bits(rows.tolist(), cols.tolist())
    ex = Executor(holder)
    st = stx.stacked_set(field, [0], "standard")
    want = np.bincount(rows, minlength=KEY_ROWS)
    top = ex.execute("k", f"TopN(m, n={KEY_ROWS})")[0]
    served = ({p.id: p.count for p in top.pairs}
              == {r: int(n) for r, n in enumerate(want) if n}
              and ex.execute("k", "Count(Row(m=7))")[0] == int(want[7]))
    out = {"form": type(st).__name__, "block_rows": st.block_rows,
           "bits": st.bits if isinstance(st, stx.KeyedSet) else None,
           "served_equal": bool(served)}
    if jax.devices()[0].platform == "tpu" and isinstance(st, stx.KeyedSet):
        keys = jax.device_put(st._ensure_keys(), jax.devices()[0])
        first = jax.device_put(np.array([st.block_rows], dtype=np.int32),
                               jax.devices()[0])
        compiled, interpreted = (np.asarray(K._key_rows_pallas(
            keys, first, st.block_rows, st.bits, interpret))
            for interpret in (False, True))
        out["compiled_equals_interpret"] = bool(
            np.array_equal(compiled, interpreted))
    snap = M.REGISTRY.snapshot()["counters"]
    out["key_rows"] = {k: v for k, v in snap.items()
                       if 'kernel="key_rows"' in k}
    print(json.dumps(out))


def key_rows_step(args, on_chip, mesh):
    """Run the key-rows child and check what it found: the key form, the
    served answers, and on a chip the compiled kernel against the
    interpreter and its dispatch (or, on a mesh, its ``why="mesh"``
    fallback)."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--key-rows-child",
         "--seed", str(args.seed)],
        cwd=HERE, env=dict(os.environ, **KEY_ROWS_ENV),
        capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        fail(f"key-rows step exited rc={proc.returncode}: "
             f"{proc.stderr[-2000:]}")
    found = json.loads(proc.stdout.splitlines()[-1])
    print(f"key rows: {json.dumps(found, sort_keys=True)}")
    if found["form"] != "KeyedSet" or not found["served_equal"]:
        fail(f"key-rows step: {found}")
    if on_chip:
        table, _ = kernel_table("\n".join(
            f"{k} {v}" for k, v in found["key_rows"].items()))
        row = table.get("key_rows", {"dispatch": 0, "fallback": {}})
        ticked = (row["fallback"].get("mesh") if mesh
                  else row["dispatch"])
        if not found.get("compiled_equals_interpret") or not ticked \
                or row["fallback"].get("error"):
            fail(f"key-rows step: compiled kernel or its counter: {found}")
    return found


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=6,
                    help="shards of 2^20 columns (default 6 = SSB SF-1)")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="CPU dry run of the control flow: skips the "
                         "device and kernel-dispatch assertions")
    ap.add_argument("--key-rows-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.key_rows_child:
        key_rows_child(args.seed)
        return 0

    os.makedirs(OUT_DIR, exist_ok=True)
    data_dir = tempfile.mkdtemp(prefix="data-", dir=OUT_DIR)
    log_path = os.path.join(OUT_DIR, f"server-{os.getpid()}.log")
    port = free_port()
    client = Client(f"http://127.0.0.1:{port}", timeout=900.0)
    t_spawn = time.perf_counter()
    with open(log_path, "wb") as log:
        # the environment passes through unchanged: JAX_PLATFORMS and
        # JAX_COMPILATION_CACHE_DIR mean to the server what they mean here
        proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu", "server",
             "--port", str(port), "--data-dir", data_dir],
            cwd=HERE, stdout=log, stderr=subprocess.STDOUT)
    try:
        report = drive(args, client, proc, log_path, t_spawn)
    except BaseException:
        with open(log_path, errors="replace") as f:
            sys.stderr.write("---- server log tail ----\n"
                             + f.read()[-4000:] + "\n")
        raise
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        shutil.rmtree(data_dir, ignore_errors=True)
    on_chip = report["device_checks"] == "done"
    report["key_rows"] = key_rows_step(
        args, on_chip, report["device"]["count"] > 1)
    print(json.dumps(report))
    # the verdict: these keys and no others, on the last line
    print(json.dumps({"ok": True, "device": report["device"]}))
    return 0


def drive(args, client, proc, log_path, t_spawn):
    wait_ready(client, proc, log_path)
    ready_s = time.perf_counter() - t_spawn

    info = client.info()
    device = {"platform": info["platform"], "kind": info["deviceKind"],
              "count": len(info["devices"])}
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"devices={device['count']}")
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.allow_cpu:
        fail(f"device check: every device must be a TPU, the server "
             f"reports platform={device['platform']!r} "
             f"({info['devices']})")
    mesh = device["count"] > 1
    cache_dir = info["compileCacheDir"]
    cache_at_start = cache_entries(cache_dir)

    data = make_data(args.seed, args.shards)
    loaded = load(client, data, args.shards)
    print(f"loaded {loaded['rows']:,} columns x 6 fields in "
          f"{loaded['seconds']:.1f}s ({loaded['rows_per_s']:,.0f} rows/s, "
          f"smoke timing)")
    mem_after_load = client._json("GET", "/internal/mem-usage")

    checks, lt25 = read_checks(data)
    queries = {}
    print("reads (each x3, every answer equal to the numpy oracle):")
    for label, pql, ok in checks:
        timed_reads(lambda: client.query(pql, index=INDEX)[0], label, ok,
                    queries)
    first = queries[checks[0][0]]["cold_ms"]
    timed_reads(
        lambda: client.sql(
            f"SELECT COUNT(*) FROM {INDEX} WHERE quantity < 25"),
        "sql_count", lambda r: r["data"] == [[lt25]], queries)

    # a write, acknowledged, then read back one higher: the device
    # stack-advance path, and the guarantee that an acked write is visible
    r = 3
    col = int(np.flatnonzero(data["shipmode"] != r)[0])
    before = int((data["shipmode"] == r).sum())

    def read():
        return client.query(f"Count(Row(shipmode={r}))", index=INDEX)[0]

    timed_reads(read, "write_before", lambda v: v == before, queries)
    acked = client.query(f"Set({col}, shipmode={r})", index=INDEX)[0]
    if acked is not True:
        fail(f"Set({col}, shipmode={r}) answered {acked!r}, not true")
    timed_reads(read, "write_readback", lambda v: v == before + 1, queries)

    mem_after = client._json("GET", "/internal/mem-usage")
    metrics = client._request("GET", "/metrics").decode()
    with open(log_path[:-len(".log")] + ".metrics", "w") as f:
        f.write(metrics)  # every counter of the run, beside the log
    table, mesh_fallback = kernel_table(metrics)
    print(f"{'kernel':<16}{'dispatch':>9}{'on mesh':>9}  "
          f"{'body{body}':<24}fallback{{why}}")
    for kernel in sorted(table):
        row = table[kernel]
        print(f"{kernel:<16}{row['dispatch']:>9}{row['on_mesh']:>9}  "
              f"{json.dumps(row['body'], sort_keys=True):<24}"
              f"{json.dumps(row['fallback'], sort_keys=True)}")
    print(f"mesh_sharding_fallback_total {mesh_fallback}")
    expected = EXPECTED_KERNELS_MESH if mesh else EXPECTED_KERNELS
    check_kernels(table, mesh_fallback, expected if on_chip else (),
                  log_path, EXPECTED_ON_MESH if on_chip and mesh else ())
    if proc.poll() is not None:
        fail(f"server exited rc={proc.returncode} during the run")

    start_s = ready_s + first / 1e3
    cold = sum(q["cold_ms"] for q in queries.values()) / 1e3
    warm = sum(q["warm_ms"] for q in queries.values()) / 1e3
    print(f"start-to-first-answer {start_s:.2f}s (server ready "
          f"{ready_s:.2f}s + first query cold {first / 1e3:.2f}s; load "
          f"excluded); all reads cold {cold:.2f}s vs warm {warm:.2f}s; "
          f"compile cache {cache_dir} held {cache_at_start} entries at "
          f"start")
    return {
        "device": device,
        "device_checks": "done" if on_chip else "skipped",
        "shards": args.shards,
        "columns": loaded["rows"],
        "load": loaded,
        "queries": queries,
        "kernels": table,
        "expected_kernels": list(expected),
        "mesh_sharding_fallback_total": mesh_fallback,
        "device_bytes_in_use": {
            "after_load": mem_after_load["deviceBytesInUse"],
            "after_queries": mem_after["deviceBytesInUse"]},
        "native": info["native"],
        "versions": {pkg: importlib.metadata.version(pkg)
                     for pkg in ("jax", "jaxlib", "libtpu", "numpy")},
        "server_ready_s": ready_s,
        "start_to_first_answer_s": start_s,
        "compile_cache": {
            "dir": cache_dir, "entries_at_start": cache_at_start,
            "entries_at_end": cache_entries(cache_dir)},
    }


if __name__ == "__main__":
    sys.exit(main())
