#!/usr/bin/env python3
"""One run of one benchmark cell against the served path, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

This parent never imports JAX: it makes the table and the oracle with
numpy from ``--seed``, writes the data directory, generates load, scrapes
``/metrics``, reduces and prints. The one child
(``harness/server_child.py``) runs the program's ordinary server and owns
the chip. Set-up (spawn to the end of warm-up) is: start the child, write
the table as a checkpoint, let the server recover it, and send every
query family of the cell's mix until answers equal the oracle and no new
program appears. Then the window runs for ``--seconds``.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (``breakdown`` when
traced, ``writer`` in a writer cell) and, last, ``checks``: every number
that was compared beside its limit, which is also the last line of
standard error. The metrics are the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``. Without a TPU,
or with fewer chips than the cell states, the run fails and prints no
result.

Three more flags serve whoever defines or debugs a cell, never the
driver: ``--allow-cpu [--shards N]`` rehearses the control flow without a
TPU and reports the device it found and ``correct``, no metric;
``--sweep r1,r2,...`` runs one window per rate of an open loop in one
server and prints a line per rate, no result; ``--keep DIR`` leaves the
server log, the raw trace and every reading there.
"""

import argparse
import concurrent.futures
import dataclasses
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from harness import loadgen, manifest, oracle, readers, snapshot, stats
from harness import traffic, verify, window, xplane
from harness.child import Failed, Launcher

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE = os.path.join(HERE, ".cache")
WARM_PASSES = (2, 8)    # at least, at most


class Cell:
    """Everything one run needs, resolved from the manifest."""

    def __init__(self, man, name, seed, shards=None):
        self.man, self.name, self.seed = man, name, seed
        w = man.cells[name]
        self.chips = w["chips"]
        self.config = man.configs[w["config"]]
        self.mix = man.mixes[w["traffic"]]
        self.dataset = man.dataset(self.config["dataset"])
        self.fields = self.dataset.fields()
        self.by_name = {f["name"]: f for f in self.fields}
        self.index = self.dataset.INDEX
        self.shards = int(shards or self.config["shards"])

    def draw(self, family, rng):
        return traffic.instantiate(self.man.families[family], self.by_name,
                                   self.index, rng)


# -- set-up --------------------------------------------------------------------

def sweep_stale_runs():
    """Remove data directories of runs that were killed before they could
    clean up (the name ends in the pid that made it)."""
    for d in glob.glob(os.path.join(CACHE, "run", "*")):
        try:
            os.kill(int(d.rsplit("-", 1)[1]), 0)
        except (ValueError, IndexError, ProcessLookupError):
            shutil.rmtree(d, ignore_errors=True)
        except PermissionError:
            pass


def build_table(cell, data_dir):
    """Make every shard from the seed, write it as a checkpoint, and keep
    the columns for the oracle."""
    snapshot.write_schema(data_dir, cell.index, cell.fields)

    def one(shard):
        cols = cell.dataset.make(cell.seed, shard, snapshot.SHARD_WIDTH)
        snapshot.write_shard(data_dir, cell.index, cell.fields, shard, cols)
        return cols

    with concurrent.futures.ThreadPoolExecutor(
            min(cell.shards, 8)) as pool:
        parts = list(pool.map(one, range(cell.shards)))
    return oracle.Table(cell.fields, {
        k: np.concatenate([p[k] for p in parts]) for k in parts[0]})


def wait_serving(conn, child, timeout=300.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not child.alive():
            raise Failed(f"server exited rc={child.proc.returncode} before "
                         f"serving")
        try:
            if conn.request("GET", "/status")[0] == 200:
                return
        except OSError:
            conn.close()
        time.sleep(0.1)
    raise Failed("server not serving in time")


def warm_up(cell, table, child, conn):
    """Every family of the mix, with fresh parameters each pass, until a
    whole pass builds no new program. Returns the passes it took."""
    rng = np.random.default_rng([cell.seed, 3])
    names = sorted(cell.man.mix_families(cell.mix)
                   - set(cell.mix.get("final", [])))
    programs = child.command("stats")["programs"]
    for n in range(1, WARM_PASSES[1] + 1):
        for name in names:
            verify.check_read(cell, table, conn, cell.draw(name, rng),
                              "warm-up")
        now = child.command("stats")["programs"]
        if now == programs and n >= WARM_PASSES[0]:
            return n
        programs = now
    raise Failed(f"warm-up still built programs after {WARM_PASSES[1]} "
                 f"passes")


@dataclasses.dataclass
class Warm:
    """A warm server and what the parent knows about it."""
    table: oracle.Table
    conn: loadgen.Conn
    device: dict        # platform, kind, count as the child's JAX found
    first_batch: int    # a writer's next batch (its warm-up sent one)
    setup_s: float


def set_up(args, cell, child, data_dir, t_spawn):
    """From spawn to a warm server."""
    table = build_table(cell, data_dir)
    t_data = time.perf_counter() - t_spawn
    ready = child.ready()
    device = {"platform": ready["platform"], "kind": ready["kind"],
              "count": ready["count"]}
    if device["platform"] != "tpu" and not args.allow_cpu:
        raise Failed(f"no accelerator: JAX reports {device}")
    if device["platform"] == "tpu" and device["count"] != cell.chips:
        # the server meshes over every chip it finds: more would be
        # another deployment, fewer cannot hold this one
        raise Failed(f"the cell is defined on {cell.chips} chips, JAX "
                     f"reports {device['count']}")
    t_ready = time.perf_counter() - t_spawn
    child.send("go")
    conn = loadgen.Conn(child.port, loadgen.READ_TIMEOUT_S)
    wait_serving(conn, child)
    t_serving = time.perf_counter() - t_spawn
    info = json.loads(conn.request("GET", "/info")[1])
    if (info["platform"], len(info["devices"])) != (device["platform"],
                                                    device["count"]):
        raise Failed(f"the server serves from {info['platform']} x "
                     f"{len(info['devices'])}, the child found {device}")
    first_batch = 0
    if cell.mix["loop"] == "writer":
        # the first batch opens the shard the writer fills, so that the
        # warm-up below builds its programs for the shapes of the window
        batch, = window.run_writer(cell, table, child.port,
                                   time.perf_counter(), 1e9, 0, conn,
                                   stop_after=1)
        if batch.error or not batch.readback_ok:
            raise Failed(f"warm-up batch: {batch}")
        first_batch = 1
    passes = warm_up(cell, table, child, conn)
    setup_s = time.perf_counter() - t_spawn
    print(f"set-up {setup_s:.2f}s: table written {t_data:.2f}s, child "
          f"ready {t_ready:.2f}s, serving {t_serving:.2f}s, warm-up "
          f"{passes} passes", file=sys.stderr)
    return Warm(table, conn, device, first_batch, setup_s)


# -- after the window ----------------------------------------------------------

def expand_fields(family, fields):
    """A family with ``for_each_field`` stands for one family per field of
    those types, ``{field}`` replaced by the field's name."""
    kinds = family.get("for_each_field")
    if not kinds:
        return [family]
    text = json.dumps(family)
    return [json.loads(text.replace("{field}", f["name"]))
            for f in fields if f["type"] in kinds]


def final_checks(cell, table, conn):
    """The mix's ``final`` families, every one against the oracle over
    everything acknowledged. Returns how many differ."""
    rng = np.random.default_rng([cell.seed, 6])
    wrong = 0
    for name in cell.mix.get("final", []):
        for fam in expand_fields(cell.man.families[name], cell.fields):
            req = traffic.instantiate(fam, cell.by_name, cell.index, rng)
            try:
                verify.check_read(cell, table, conn, req, "read-back")
            except Failed as e:
                print(e, file=sys.stderr)
                wrong += 1
    return wrong


def load_trace(trace_dir):
    """Reduce the profiler's file to JSON in a process of its own (the
    reader is JAX's; this parent stays off JAX) and load it."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise Failed(f"no .xplane.pb under {trace_dir}")
    out = os.path.join(trace_dir, "trace.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "harness", "xplane.py"),
         files[0], out], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise Failed(f"trace reduction failed: {proc.stderr[-2000:]}")
    return xplane.load(out)


def trace_breakdown(trace, planes):
    """The programs that took most device time and the idle gaps by what
    the host was doing, in seconds per chip (averaged over the chips, as
    ``busy_s`` is)."""
    programs, gaps = {}, {}
    span = xplane.span(trace)
    host = xplane.host_events(trace)
    for events in xplane.device_modules(trace).values():
        for k, v in xplane.op_seconds(events).items():
            k = xplane.module_name(k)
            programs[k] = programs.get(k, 0.0) + v / len(planes)
    for events in planes.values():
        for k, v in xplane.idle_gaps(events, host, span).items():
            gaps[k] = gaps.get(k, 0.0) + v / len(planes)
    return {"device_ops": xplane.top(programs),
            "idle_gaps": xplane.top(gaps)}


def readings(cell, w, warm):
    """What the metric readers read, from one window."""
    seconds = w.seconds
    reads = w.done + w.side
    in_window = [d for d in w.done if d.ok and d.done <= seconds]
    values = {"setup_s": warm.setup_s,
              "read_qps": len(in_window) / seconds if in_window else None}
    values.update(window.ingest_rates(w.batches, seconds,
                                      int(cell.mix.get("batches", 0))))
    series = {
        "read_ms": [(d.done - d.intended) * 1e3 for d in w.done if d.ok],
        "lateness_ms": [(d.sent - d.intended) * 1e3 for d in w.done]
        if cell.mix["loop"] == "open" else [],
        "side_read_ms": [(d.done - d.intended) * 1e3
                         for d in w.side if d.ok]}
    acked = w.acked
    counts = {"reads": float(sum(1 for d in reads if d.ok)),
              "window_s": w.window_s, "batches": float(len(acked)),
              "records": float(sum(b.records for b in acked))}
    return readers.Readings(values, series, counts, w.scrape0, w.scrape1,
                            verify.span_trees(reads), w.stats0, w.stats1,
                            device_kind=warm.device["kind"])


def writer_summary(w, cut):
    """What a writer's window held, for whoever reads the line: whether
    the window cut the fixed work, the last acknowledgement, and which of
    the window's batches (1, 2, ...) carried a whole checkpoint."""
    acked = w.acked
    before = seen = int(stats.series_sum(
        w.scrape0, "recovery_checkpoint_seconds_count"))
    carried = []
    for i, b in enumerate(acked, 1):
        if b.checkpoints > seen:
            carried.append(i)
            seen = b.checkpoints
    return {"writer_cut": cut,
            "batches_acked": len(acked),
            "last_ack_s": acked[-1].acked if acked else None,
            "in_flight_s": sum(b.acked - b.started for b in acked),
            "checkpoints": seen - before, "checkpoint_batches": carried}


# -- one run ---------------------------------------------------------------------

def run(args, man):
    cell = Cell(man, args.workload, args.seed, args.shards)
    for d in (os.path.join(CACHE, "run"), os.path.join(CACHE, "logs"),
              args.keep):
        if d:
            os.makedirs(d, exist_ok=True)
    sweep_stale_runs()
    work = tempfile.mkdtemp(prefix=f"{cell.name}-", suffix=f"-{os.getpid()}",
                            dir=os.path.join(CACHE, "run"))
    toml = None
    if cell.config.get("server_toml"):
        toml = os.path.join(work, "server.toml")
        with open(toml, "w") as fh:
            fh.write(cell.config["server_toml"])
    t_spawn = time.perf_counter()
    child = Launcher(os.path.join(work, "data"),
                     os.path.join(CACHE, "logs", f"{cell.name}.log"), toml)
    try:
        if args.sweep:
            return sweep(args, cell, child, work, t_spawn)
        return measure(args, cell, child, work, t_spawn)
    except BaseException:
        sys.stderr.write("---- server log tail ----\n" + child.log_tail()
                         + "\n")
        raise
    finally:
        child.stop()
        if args.keep:
            shutil.copy(child.log_path, args.keep)
            for f in glob.glob(os.path.join(work, "trace", "**", "*.pb"),
                               recursive=True):
                shutil.copy(f, args.keep)
        shutil.rmtree(work, ignore_errors=True)


def sweep(args, cell, child, work, t_spawn):
    """One window per rate in one server; prints a line per rate."""
    if cell.mix["loop"] != "open":
        raise Failed("--sweep needs an open-loop cell")
    warm = set_up(args, cell, child, os.path.join(work, "data"), t_spawn)
    for rate in args.sweep:
        w = window.run_window(cell, warm.table, child, warm.conn,
                              args.seconds, rate=rate)
        series = readings(cell, w, warm).series
        ms, late = series["read_ms"], series["lateness_ms"]
        wrong, _ = verify.verify_reads(cell, warm.table, w.done, sample=100)
        print(json.dumps({
            "rate": rate, "sent": len(w.done), "answered": len(ms),
            "wrong_of_100": wrong,
            "p50_ms": stats.percentile(ms, 0.5),
            "p95_ms": stats.percentile(ms, 0.95),
            "p99_ms": stats.percentile(ms, 0.99),
            "late_p95_ms": stats.percentile(late, 0.95),
            # how long after the last intended send the last answer came
            "backlog_s": max(d.done for d in w.done) - args.seconds,
            "ms": ms if args.keep else None}))
    return None


def measure(args, cell, child, work, t_spawn):
    warm = set_up(args, cell, child, os.path.join(work, "data"), t_spawn)
    table, device = warm.table, warm.device
    on_chip = device["platform"] == "tpu"
    trace_dir = os.path.join(work, "trace") if args.trace else None
    w = window.run_window(cell, table, child, warm.conn, args.seconds,
                          trace_dir, warm.first_batch)
    wrong_final = final_checks(cell, table, warm.conn)
    warm.conn.close()
    child.stop()

    # answers are checked now, off the server's cores
    reads = w.done + w.side
    wrong, verified = verify.verify_reads(
        cell, table, reads, w.batches if w.side else (),
        1 + warm.first_batch,
        int(cell.mix.get("verify_sample", 500)))
    unanswered = sum(1 for d in reads if not d.ok)
    bad_batches = sum(1 for b in w.batches if b.error or not b.readback_ok)
    ktable, mesh_fallbacks = verify.kernel_table(w.scrape1)
    kernel_errors = sum(row["fallback"].get(why, 0)
                        for row in ktable.values()
                        for why in ("error", "failures"))
    print(json.dumps({"kernels": ktable,
                      "mesh_sharding_fallback_total": mesh_fallbacks,
                      "verified_reads": verified, "wrong_reads": wrong,
                      "unanswered": unanswered, "batches": len(w.batches)}))

    r = readings(cell, w, warm)
    breakdown = None
    writer = None
    if cell.mix["loop"] == "writer":
        writer = writer_summary(w, bool(r.values.get("writer_cut", True)))
    if w.tracer:
        device["window_s"] = w.tracer.ended - w.tracer.began
        r.trace = load_trace(trace_dir)
        r.trace_counts = {"reads": float(sum(
            1 for d in reads
            if d.ok and w.tracer.began <= d.done <= w.tracer.ended))}
        planes = {k: v for k, v in xplane.device_ops(r.trace).items()
                  if v}
        if planes:
            device["busy_s"] = sum(xplane.busy_seconds(ev)
                                   for ev in planes.values()) / len(planes)
            breakdown = trace_breakdown(r.trace, planes)
        elif on_chip:
            raise Failed("no operation ran on the device in the traced "
                         "slice")
    device["memory_peak_bytes"] = w.stats1["peak_bytes"]

    metrics = {}
    for group in ("end_to_end", "per_layer"):
        for m in cell.man.metrics(cell.name, group):
            value = readers.read(cell.man.readers[m["name"]], r)
            if value is not None:
                metrics.setdefault(group, {})[m["name"]] = {
                    "value": value, "unit": m["unit"]}
    reported = "per_layer" if args.trace else "end_to_end"
    if args.keep:
        with open(os.path.join(args.keep, f"{cell.name}.json"), "w") as fh:
            json.dump({"metrics": metrics, "values": r.values,
                       "counts": r.counts, "series": r.series,
                       "side": [[d.sent, d.done] for d in w.side],
                       "batches": [vars(b) for b in w.batches]}, fh)
    # every comparison is exact: each number beside its limit, 0
    checks = {"wrong_reads": [wrong, 0], "unanswered": [unanswered, 0],
              "wrong_final": [wrong_final, 0],
              "bad_batches": [bad_batches, 0],
              "kernel_errors": [kernel_errors, 0],
              "mesh_fallbacks": [mesh_fallbacks, 0]}
    result = {
        "correct": not any(n > limit for n, limit in checks.values()),
        "attempted": len(reads) + len(w.batches) * len(cell.fields),
        "failed": wrong + wrong_final + unanswered + bad_batches,
        "metrics": metrics.get(reported, {}), "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    if writer:
        result["writer"] = writer
    other = {k: v for k, v in metrics.items() if k != reported}
    print(f"also read: {json.dumps(other)} {json.dumps(r.values)}",
          file=sys.stderr)
    if not on_chip:
        # a number from a CPU run is never written under a metric's name
        print(f"rehearsal on {device['platform']}, metrics withheld: "
              f"{json.dumps(result['metrics'])}", file=sys.stderr)
        result["metrics"] = {}
        result.pop("breakdown", None)
        device.pop("busy_s", None)
    # what was compared, each beside its limit: last on standard error
    # and last in the line
    result["checks"] = checks
    print(f"compared (number, limit): verified_reads {verified} "
          f"{json.dumps(checks)}", file=sys.stderr)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--shards", type=int)
    ap.add_argument("--sweep", type=lambda s: [float(x) for x in
                                               s.split(",")])
    ap.add_argument("--keep")
    args = ap.parse_args(argv)
    if args.shards and not args.allow_cpu:
        ap.error("--shards is for the --allow-cpu rehearsal only")
    try:
        man = manifest.Manifest()
        man.check()
        if args.workload not in man.cells:
            raise manifest.ManifestError(
                f"no cell {args.workload!r} in BENCHMARK.json")
        result = run(args, man)
    except (Failed, manifest.ManifestError) as e:
        print(f"benchmark: FAIL: {e}", file=sys.stderr)
        return 1
    if result is not None:
        print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
