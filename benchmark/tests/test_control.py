"""``correct`` has to come out false when the served path breaks a stated
guarantee or alters an answer: the whole of a run on the CPU at one shard,
in this process, with the fault planted between the harness and the
server. (A sound run of the same command is ``test_rehearsal.py``'s.)"""

import json

import pytest

import run
from harness import loadgen, window

INGEST = "ssb-flat-sf1.ingest-sustained"


def lose_an_acknowledged_write(monkeypatch):
    """The control: the configuration's ``acknowledged_write`` guarantee
    broken. One field's import of the window's first batch is
    acknowledged to the writer and never reaches the server, so the
    records are there without that field."""
    send = loadgen.send_batch

    def lossy(conn, index, bodies, batch):
        if batch.number == 1:
            bodies = [b for b in bodies
                      if json.loads(b[1])["field"] != "lo_shipmode"]
        send(conn, index, bodies, batch)

    monkeypatch.setattr(loadgen, "send_batch", lossy)


def alter_answers_in_the_window(monkeypatch):
    """A fault of the timed path: every third answer of the window is one
    more than the server said."""
    send, run_window = loadgen.send_read, window.run_window
    state = {"in_window": False, "n": 0}

    def bump(doc):
        if isinstance(doc, bool):
            return doc, False
        if isinstance(doc, int):
            return doc + 1, True
        items = (doc.items() if isinstance(doc, dict)
                 else enumerate(doc) if isinstance(doc, list) else ())
        for k, v in items:
            doc[k], hit = bump(v)
            if hit:
                return doc, True
        return doc, False

    def altered(conn, request, index, profiled=False):
        status, body = send(conn, request, index, profiled)
        state["n"] += state["in_window"]
        if state["in_window"] and status == 200 and state["n"] % 3 == 0:
            body = json.dumps(bump(json.loads(body))[0]).encode()
        return status, body

    def flagged(*args, **kw):
        state["in_window"] = True
        try:
            return run_window(*args, **kw)
        finally:
            state["in_window"] = False

    monkeypatch.setattr(loadgen, "send_read", altered)
    monkeypatch.setattr(window, "run_window", flagged)


@pytest.mark.parametrize("cell, fault, caught_by", [
    (INGEST, lose_an_acknowledged_write, ["bad_batches", "wrong_final"]),
    (INGEST, alter_answers_in_the_window, ["wrong_reads"]),
    ("ssb-flat-sf1.filter-open", alter_answers_in_the_window,
     ["wrong_reads"]),
])
def test_a_planted_fault_comes_out_not_correct(cell, fault, caught_by,
                                               monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    fault(monkeypatch)
    rc = run.main(["--workload", cell, "--seed", "5", "--seconds", "3",
                   "--trace", "0", "--allow-cpu", "--shards", "1"])
    assert rc == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] > 0
    for name in caught_by:
        number, limit = last["checks"][name]
        assert number > limit == 0, (name, last["checks"])
