"""What happens between the window's first and last second: the loops of
``loadgen`` driven from a mix, a traced slice in the middle, the scrapes
and launcher readings on either side."""

import concurrent.futures
import dataclasses
import json
import threading
import time

import numpy as np

from . import loadgen, oracle, snapshot, stats, traffic
from .child import Failed

TRACE_SLICE_S = 3.0


def scrape(conn):
    status, body = conn.request("GET", "/metrics")
    if status != 200:
        raise Failed(f"GET /metrics: HTTP {status}")
    return stats.parse_metrics(body.decode())


class Tracer(threading.Thread):
    """Has the child trace a slice in the middle of the window."""

    def __init__(self, child, t0, seconds, out_dir, slice_s):
        super().__init__(daemon=True)
        self.child, self.t0, self.out_dir = child, t0, out_dir
        self.length = min(slice_s, seconds)
        self.start_at = (seconds - self.length) / 2.0
        self.began = self.ended = None
        self.error = None

    def run(self):
        try:
            time.sleep(max(0.0, self.t0 + self.start_at
                           - time.perf_counter()))
            self.child.command(f"trace-start {self.out_dir}")
            self.began = time.perf_counter() - self.t0
            time.sleep(self.length)
            self.ended = time.perf_counter() - self.t0
            self.child.command("trace-stop", timeout=300.0)
        except Failed as e:
            self.error = e


def run_writer(cell, table, port, t0, seconds, first_batch, conn_metrics,
               stop_after=None):
    """Append batches from ``first_batch`` on, after the loaded columns,
    until the window ends or ``stop_after`` batches are written (the
    mix's ``batches`` unless given). After every acknowledged batch the
    read-back families must already show it (an acknowledged write is
    visible to the next read)."""
    mix = cell.mix
    n = int(mix["batch_records"])
    base = cell.shards * snapshot.SHARD_WIDTH
    last = first_batch + int(stop_after or mix["batches"])
    conn = loadgen.Conn(port, loadgen.WRITE_TIMEOUT_S)
    rconn = loadgen.Conn(port, loadgen.READ_TIMEOUT_S)
    rng = np.random.default_rng([cell.seed, 4])
    batches = []

    def prepare(b):
        cols = cell.dataset.make(cell.seed,
                                 cell.dataset.INGEST_STREAM + b, n)
        return cols, loadgen.import_bodies(cell.fields, base + b * n, cols)

    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        ahead = pool.submit(prepare, first_batch)
        b = first_batch
        while time.perf_counter() < t0 + seconds and b < last:
            cols, bodies = ahead.result()
            ahead = pool.submit(prepare, b + 1)
            batch = loadgen.Batch(b, n, started=time.perf_counter() - t0)
            batches.append(batch)
            loadgen.send_batch(conn, cell.index, bodies, batch)
            if batch.error:
                break
            batch.acked = time.perf_counter() - t0
            table.append(cols)
            for name in mix["readback"]:
                req = cell.draw(name, rng)
                status, body = loadgen.send_read(rconn, req, cell.index)
                want = table.prefix(req.text, req.meaning, len(table.parts))
                if status != 200 or oracle.decode(
                        req.meaning, json.loads(body), req.route) != want:
                    batch.readback_ok = False
            batch.checkpoints = int(stats.series_sum(
                scrape(conn_metrics), "recovery_checkpoint_seconds_count"))
            b += 1
        ahead.cancel()
    conn.close()
    rconn.close()
    return batches


def ingest_rates(batches, seconds, planned=None):
    """Records per second: ``ingest_rows_per_s`` over the window (records
    acknowledged inside it, over the time to the last acknowledgement
    when the writer finished its fixed work of ``planned`` batches, over
    the window's length when the window cut it) and, where two
    checkpoints completed inside the window, ``ingest_rows_per_s_cycles``
    over whole cycles. ``writer_cut`` says which of the two it was: a cut
    writer's count moves in steps of one batch and is no rate to compare
    with a finished one's."""
    acked = [b for b in batches if b.acked and b.acked <= seconds]
    if not acked:
        return {}
    finished = (batches[-1].acked and batches[-1].acked < seconds
                and len(acked) >= (planned or 0))
    span = batches[-1].acked if finished else seconds
    out = {"ingest_rows_per_s": sum(b.records for b in acked) / span,
           "writer_cut": not finished}
    total, events = 0, []
    for b in acked:
        total += b.records
        events.append((b.acked, total, b.checkpoints))
    cycles = stats.whole_cycles(events)
    if cycles:
        out["ingest_rows_per_s_cycles"] = cycles[0] / cycles[1]
        out["checkpoint_cycles"] = float(cycles[2])
    return out


@dataclasses.dataclass
class Window:
    seconds: float
    done: list              # the cell's own reads
    side: list              # reads beside a writer
    batches: list
    scrape0: dict
    scrape1: dict
    stats0: dict
    stats1: dict
    tracer: Tracer
    window_s: float

    @property
    def acked(self):
        """The batches acknowledged inside the window."""
        return [b for b in self.batches
                if b.acked and b.acked <= self.seconds]


def run_window(cell, table, child, conn, seconds, trace_dir=None,
               first_batch=0, rate=None):
    """One measured window of the cell's mix; ``rate`` overrides an open
    loop's rate (the knee sweep)."""
    mix = cell.mix
    every = int(mix.get("profile_every", 20)) if trace_dir else 0
    if mix["loop"] == "closed":
        seqs = traffic.closed_sequences(mix, cell.man.families,
                                        cell.by_name, cell.index, cell.seed)
    else:
        spec = dict(mix if mix["loop"] == "open" else mix["side_reads"])
        spec["rate"] = rate or spec["rate"]
        reqs = traffic.open_schedule(spec, cell.man.families, cell.by_name,
                                     cell.index, cell.seed, seconds)
    scrape0 = scrape(conn)
    stats0 = child.command("stats")
    t0 = time.perf_counter() + 0.05
    tracer = None
    if trace_dir:
        tracer = Tracer(child, t0, seconds, trace_dir,
                        float(mix.get("trace_slice_s", TRACE_SLICE_S)))
        tracer.start()
    done, side, batches = [], [], []
    if mix["loop"] == "open":
        done = loadgen.run_open(reqs, child.port, cell.index,
                                int(mix.get("workers", 16)), t0, every)
    elif mix["loop"] == "closed":
        done = loadgen.run_closed(seqs, child.port, cell.index, t0, seconds,
                                  every)
    else:
        with concurrent.futures.ThreadPoolExecutor(1) as pool:
            reader = pool.submit(loadgen.run_open, reqs, child.port,
                                 cell.index, 4, t0, every)
            batches = run_writer(cell, table, child.port, t0, seconds,
                                 first_batch, conn)
            side = reader.result()
    window_s = max(seconds, time.perf_counter() - t0)
    if tracer:
        tracer.join(600.0)
        if tracer.error:
            raise tracer.error
    if not child.alive():
        raise Failed(f"server exited rc={child.proc.returncode} in the "
                     f"window")
    return Window(seconds, done, side, batches, scrape0, scrape(conn),
                  stats0, child.command("stats"), tracer, window_s)
