"""The whole command on the CPU at one shard: the control flow of a run,
and the form of its last line."""

import json
import os
import subprocess
import sys

import pytest

from harness import manifest

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "checks"}


def run_cell(cell, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH, "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", "3", *extra],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=900)


@pytest.mark.parametrize("cell, trace", [
    ("ssb-flat-sf1.filter-open", "0"),
    ("ssb-flat-sf1.ingest-sustained", "1"),
])
def test_last_line_has_exactly_the_contract_keys(cell, trace):
    proc = run_cell(cell, "--trace", trace, "--allow-cpu", "--shards", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(last) - {"writer"} == CONTRACT_KEYS
    assert last["correct"] is True and last["failed"] == 0
    # what was compared comes last, each number beside its limit, and is
    # the end of standard error too
    assert list(last)[-1] == "checks"
    assert all(n <= limit for n, limit in last["checks"].values())
    assert json.dumps(last["checks"]) in proc.stderr.splitlines()[-1]
    if "ingest" in cell:
        # three seconds cut the writer's fixed work, and the line says so
        assert last["writer"]["writer_cut"] is True
    assert last["attempted"] > 0
    # a CPU run names its device and withholds every number
    assert last["device"]["platform"] == "cpu"
    assert last["metrics"] == {}
    assert "busy_s" not in last["device"]
    # the kernel dispatch and fallback table is on an earlier line
    table = json.loads(proc.stdout.strip().splitlines()[-2])
    assert "kernels" in table and table["wrong_reads"] == 0


def test_without_an_accelerator_a_run_fails_and_prints_no_result():
    proc = run_cell("ssb-flat-sf1.filter-open", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_an_unknown_cell_fails_before_anything_starts():
    proc = run_cell("no-such-cell", "--trace", "0")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
