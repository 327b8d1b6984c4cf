"""Load generation against the served path, from one process with few
threads: an open loop that sends on a schedule whatever the server does,
a closed loop of clients that each wait for their answer, and a writer
that appends batches through the import endpoints.

Latency in the open loop runs from the *intended* send time, so a stall
is charged to every request that had to wait behind it, and how late the
generator itself sent is kept beside it. Bodies are stored undecoded:
answers are checked after the window, off the server's cores.
"""

import dataclasses
import http.client
import json
import queue
import threading
import time

READ_TIMEOUT_S = 60.0
WRITE_TIMEOUT_S = 600.0


@dataclasses.dataclass
class Done:
    request: object
    intended: float     # seconds from window start
    sent: float
    done: float
    status: int         # HTTP status, 0 when the request raised
    body: bytes
    profiled: bool = False

    @property
    def ok(self):
        return self.status == 200


class Conn:
    """One keep-alive connection; reconnects once when the peer closed."""

    def __init__(self, port, timeout):
        self.port, self.timeout = port, timeout
        self.http = None

    def request(self, method, path, body=None):
        try:
            return self._once(method, path, body)
        except (http.client.RemoteDisconnected, BrokenPipeError,
                ConnectionResetError):
            self.close()
            return self._once(method, path, body)

    def _once(self, method, path, body):
        if self.http is None:
            self.http = http.client.HTTPConnection(
                "127.0.0.1", self.port, timeout=self.timeout)
        self.http.request(method, path, body=body)
        resp = self.http.getresponse()
        return resp.status, resp.read()

    def close(self):
        if self.http is not None:
            self.http.close()
            self.http = None


def read_path(request, index, profiled=False):
    path = "/sql" if request.route == "sql" else f"/index/{index}/query"
    return path + ("?profile=true" if profiled else "")


def send_read(conn, request, index, profiled=False):
    """(status, body); status 0 and the error text when it raised."""
    try:
        return conn.request("POST", read_path(request, index, profiled),
                            request.text.encode())
    except (OSError, http.client.HTTPException) as e:
        conn.close()
        return 0, repr(e).encode()


def run_open(requests, port, index, workers, t0, profile_every=0,
             drain_s=30.0):
    """Send each request at ``t0 + request.at``; returns the Done list.
    Requests still unanswered ``drain_s`` after the last intended send
    are abandoned and come back with status 0."""
    todo = queue.Queue()
    out, lock = [], threading.Lock()

    def worker():
        conn = Conn(port, READ_TIMEOUT_S)
        while True:
            item = todo.get()
            if item is None:
                break
            i, req = item
            prof = bool(profile_every) and i % profile_every == 0
            sent = time.perf_counter() - t0
            status, body = send_read(conn, req, index, prof)
            d = Done(req, req.at, sent, time.perf_counter() - t0, status,
                     body, prof)
            with lock:
                out.append(d)
        conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(workers)]
    for t in threads:
        t.start()
    for i, req in enumerate(requests):
        delay = t0 + req.at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        todo.put((i, req))
    for _ in threads:
        todo.put(None)
    deadline = time.perf_counter() + drain_s
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    with lock:
        done = list(out)
    seen = {id(d.request) for d in done}
    now = time.perf_counter() - t0
    done += [Done(r, r.at, now, now, 0, b"abandoned: no answer in time")
             for r in requests if id(r) not in seen]
    return done


def run_closed(sequences, port, index, t0, seconds, profile_every=0):
    """One thread per client, each sending its sequence in order (and
    again from the start) until the window ends. A request in flight at
    the end is finished and recorded; ``done`` says when."""
    out, lock = [], threading.Lock()
    end = t0 + seconds

    def client(k, seq):
        conn = Conn(port, READ_TIMEOUT_S)
        i = 0
        while time.perf_counter() < end:
            req = seq[i % len(seq)]
            prof = bool(profile_every) and (i + k) % profile_every == 0
            sent = time.perf_counter() - t0
            status, body = send_read(conn, req, index, prof)
            d = Done(req, sent, sent, time.perf_counter() - t0, status,
                     body, prof)
            with lock:
                out.append(d)
            i += 1
        conn.close()

    threads = [threading.Thread(target=client, args=(k, seq), daemon=True)
               for k, seq in enumerate(sequences)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + READ_TIMEOUT_S + 5.0)
    with lock:
        return list(out)


# -- the writer ------------------------------------------------------------


@dataclasses.dataclass
class Batch:
    number: int
    records: int
    started: float = 0.0    # first import request sent
    acked: float = 0.0      # last field acknowledged; 0 = never
    checkpoints: int = 0    # server's checkpoint count seen after the ack
    readback_ok: bool = True
    error: str = ""
    request_s: list = dataclasses.field(default_factory=list)


def import_bodies(fields, first_col, columns):
    """One (path suffix, JSON body) per field for a batch of records that
    start at column ``first_col``."""
    n = next(iter(columns.values())).size
    cols = list(range(first_col, first_col + n))
    out = []
    for f in fields:
        col = columns[f["name"]]
        if f["type"] == "int":
            body = {"field": f["name"], "cols": cols,
                    "values": col.tolist()}
            out.append(("import-values", json.dumps(body).encode()))
            continue
        body = {"field": f["name"], "cols": cols}
        if f["keys"] is not None:
            keys = f["keys"]
            body["rowKeys"] = [keys[s] for s in col.tolist()]
        else:
            ids = f["ids"]
            body["rows"] = [ids[s] for s in col.tolist()]
        out.append(("import", json.dumps(body).encode()))
    return out


def send_batch(conn, index, bodies, batch):
    """Every field of one batch, in order. Fills in the seconds each
    request took and, when one was not acknowledged, what went wrong."""
    for suffix, body in bodies:
        t = time.perf_counter()
        try:
            status, resp = conn.request(
                "POST", f"/index/{index}/{suffix}", body)
        except (OSError, http.client.HTTPException) as e:
            conn.close()
            batch.error = repr(e)
            return
        batch.request_s.append(time.perf_counter() - t)
        if status != 200:
            batch.error = f"{suffix}: HTTP {status}: {resp[:200]!r}"
            return
