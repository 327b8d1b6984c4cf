"""The planted-answer control of ``taxi-1b-c16.rides-closed``: the whole
run on the CPU at two shards, in this process, with every third answer of
the window altered between the server and the harness: ``correct`` comes
out false by ``wrong_reads``. (The fault is ``test_control.py``'s; the
sound run of the same command is this file's second test.)"""

import json
import os

import pytest

import run
from harness import manifest
from test_control import alter_answers_in_the_window

CELL = "taxi-1b-c16.rides-closed"
# the whole command needs the program beside the benchmark; a copy of the
# benchmark alone (test_manifest.py's rehearsal of an addition) has none
pytestmark = pytest.mark.skipif(
    not os.path.isdir(os.path.join(manifest.ROOT, "pilosa_tpu")),
    reason="no pilosa_tpu beside this benchmark")


def _run(capsys):
    rc = run.main(["--workload", CELL, "--seed", "2900000005", "--seconds",
                   "3", "--trace", "0", "--allow-cpu", "--shards", "2"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_an_altered_answer_comes_out_not_correct(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    alter_answers_in_the_window(monkeypatch)
    last = _run(capsys)
    assert last["correct"] is False and last["failed"] > 0
    number, limit = last["checks"]["wrong_reads"]
    assert number > limit == 0, last["checks"]


def test_the_sound_rehearsal_is_correct_and_reports_no_metric(monkeypatch,
                                                              capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    last = _run(capsys)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 12      # every family met, in the window too
    assert last["metrics"] == {} and last["device"]["platform"] == "cpu"
    assert all(n <= limit for n, limit in last["checks"].values())
