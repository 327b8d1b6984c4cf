"""chip_smoke.py on the CPU: the control flow of the chip check, its
failure exits, and the compile-cache placement rule it relies on.

The chip itself is checked by running ``python chip_smoke.py`` on a
machine that has one; here ``--shards 1 --allow-cpu`` drives the same
served path (HTTP import, every query family, the write read-back) with
only the device and kernel-dispatch assertions skipped.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

ORACLE_CHECKS = {"tape_count", "bsi_compare", "bsi_sum", "topn", "groupby",
                 "groupby_sum", "compressed_row", "compressed_topn", "sql_count",
                 "write_before", "write_readback"}


def _run_smoke(*args, **env):
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
        capture_output=True, text=True, timeout=600)


def test_cpu_dry_run_passes_every_oracle_check(tmp_path):
    # the environment reaches the server child unchanged: a compile
    # cache placed from outside is the one the server reports
    cache = str(tmp_path / "compile-cache")
    r = _run_smoke("--shards", "1", "--allow-cpu",
                   JAX_COMPILATION_CACHE_DIR=cache)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    report, verdict = r.stdout.splitlines()[-2:]
    out = json.loads(report)
    # the last line is the verdict: exactly these keys, no others
    assert json.loads(verdict) == {"ok": True, "device": out["device"]}
    assert out["compile_cache"]["dir"] == cache
    # ... and the one written, sub-second programs included
    assert out["compile_cache"]["entries_at_start"] == 0
    assert out["compile_cache"]["entries_at_end"] > 0
    assert out["device_checks"] == "skipped"
    # conftest's 8 virtual CPU devices reach the server through
    # XLA_FLAGS, so this run also serves from a sharded engine mesh
    n = out["device"]["count"]
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": n}
    assert r.stdout.splitlines()[0] == \
        f"platform=cpu device_kind=cpu devices={n}"
    assert out["columns"] == 1 << 20
    assert set(out["queries"]) == ORACLE_CHECKS
    assert all(q["equal"] is True for q in out["queries"].values())
    # the kernel table is read even where its assertions are skipped
    assert "tape_count" in out["kernels"]
    assert out["mesh_sharding_fallback_total"] == 0
    # the key-rows step: the key form, answers equal to numpy; the
    # compiled kernel and its counter only on a chip
    kr = out["key_rows"]
    assert kr["form"] == "KeyedSet" and kr["bits"] >= 9
    assert kr["served_equal"] is True
    assert "compiled_equals_interpret" not in kr
    assert f"key rows: {json.dumps(kr, sort_keys=True)}" in r.stdout


def test_without_allow_cpu_fails_naming_the_device_check():
    r = _run_smoke("--shards", "1")
    assert r.returncode != 0
    assert "device check" in r.stderr
    assert '"ok"' not in r.stdout  # no result line on failure


def test_parent_side_modules_do_not_import_jax():
    code = ("import sys, chip_smoke; "
            "bad = [m for m in sys.modules if m == 'jax' "
            "or m.startswith(('jax.', 'jaxlib'))]; "
            "sys.exit(repr(bad) if bad else 0)")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr


# -- the failure exits, each shown once ---------------------------------------

def test_oracle_mismatch_exits_nonzero():
    with pytest.raises(SystemExit, match="differs from the numpy oracle"):
        chip_smoke.timed_reads(lambda: 41, "tape_count",
                               lambda r: r == 42, {})


def test_dead_server_exits_nonzero(tmp_path):
    proc = subprocess.Popen([sys.executable, "-c", "raise SystemExit(7)"])
    proc.wait(timeout=30)
    client = chip_smoke.Client(f"http://127.0.0.1:{chip_smoke.free_port()}")
    with pytest.raises(SystemExit, match="server exited rc=7"):
        chip_smoke.wait_ready(client, proc, tmp_path / "server.log")


METRICS = """\
# TYPE pilosa_tpu_ops_pallas_dispatch_total counter
pilosa_tpu_ops_pallas_dispatch_total{kernel="tape_count"} 12
pilosa_tpu_ops_pallas_dispatch_total{kernel="topn"} 3
pilosa_tpu_ops_pallas_dispatch_total{kernel="pair_sums"} 2
pilosa_tpu_ops_pallas_mesh_dispatch_total{kernel="pair_sums"} 2
pilosa_tpu_ops_pallas_body_total{kernel="pair_sums",body="vpu"} 2
pilosa_tpu_ops_pallas_body_total{kernel="topn",body="vpu"} 2
pilosa_tpu_ops_pallas_body_total{kernel="topn",body="mxu"} 1
pilosa_tpu_ops_pallas_fallback_total{kernel="bsi_sum",why="mesh"} 3
pilosa_tpu_ops_pallas_fallback_total{kernel="topn",why="error"} 1
pilosa_tpu_mesh_sharding_fallback_total 0
pilosa_tpu_pql_queries_total 40
"""


def test_kernel_table_and_dispatch_checks():
    table, mesh_fallback = chip_smoke.kernel_table(METRICS)
    assert mesh_fallback == 0
    assert table["tape_count"] == {"dispatch": 12, "on_mesh": 0,
                                   "body": {}, "fallback": {}}
    assert table["bsi_sum"] == {"dispatch": 0, "on_mesh": 0, "body": {},
                                "fallback": {"mesh": 3}}
    # a mesh dispatch counts in both series, once each
    assert table["pair_sums"] == {"dispatch": 2, "on_mesh": 2,
                                  "body": {"vpu": 2}, "fallback": {}}
    assert table["topn"]["body"] == {"vpu": 2, "mxu": 1}
    with pytest.raises(SystemExit, match="topn fell back with why='error'"):
        chip_smoke.check_kernels(table, 0, (), "log")
    del table["topn"]["fallback"]["error"]
    chip_smoke.check_kernels(table, 0, ("tape_count", "topn"), "log")
    with pytest.raises(SystemExit,
                       match=r"zero dispatches of \['bsi_sum', 'pair"):
        chip_smoke.check_kernels(
            table, 0, ("tape_count", "bsi_sum", "pair_counts"), "log")
    with pytest.raises(SystemExit, match="mesh_sharding_fallback_total"):
        chip_smoke.check_kernels(table, 2, (), "log")


def test_every_pair_counts_dispatch_must_name_its_body():
    series = "pilosa_tpu_ops_pallas_{}_total{{kernel=\"pair_counts\"{}}} {}\n"
    text = (series.format("dispatch", "", 5)
            + series.format("body", ',body="vpu"', 3))
    table, _ = chip_smoke.kernel_table(text)
    assert table["pair_counts"]["body"] == {"vpu": 3}
    with pytest.raises(SystemExit, match=r"kernel body check: 5 dispatches "
                                         r"of pair_counts took .*'vpu': 3"):
        chip_smoke.check_kernels(table, 0, ("pair_counts",), "log")
    table, _ = chip_smoke.kernel_table(
        text + series.format("body", ',body="mxu"', 2))
    chip_smoke.check_kernels(table, 0, ("pair_counts",), "log")
    # a program that lacks the counter names no body at all
    table, _ = chip_smoke.kernel_table(series.format("dispatch", "", 5))
    with pytest.raises(SystemExit, match="kernel body check"):
        chip_smoke.check_kernels(table, 0, ("pair_counts",), "log")


def test_mesh_kernels_must_dispatch_on_the_mesh():
    """On several chips the pair-count family has to run as the per-chip
    mesh program: a dispatch of the one-chip program (or none) fails."""
    assert set(chip_smoke.EXPECTED_ON_MESH) <= set(
        chip_smoke.EXPECTED_KERNELS_MESH) <= set(chip_smoke.EXPECTED_KERNELS)
    table, _ = chip_smoke.kernel_table(METRICS)
    del table["topn"]["fallback"]["error"]
    chip_smoke.check_kernels(table, 0, ("pair_sums",), "log",
                             on_mesh=("pair_sums",))
    with pytest.raises(SystemExit,
                       match=r"zero mesh dispatches of \['topn'\]"):
        chip_smoke.check_kernels(table, 0, ("topn",), "log",
                                 on_mesh=("topn", "pair_sums"))


# -- compile cache placement -----------------------------------------------------

def test_compile_cache_placement(monkeypatch):
    import jax

    from pilosa_tpu import platform

    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        # set from outside: left alone, no path set in code — but the
        # sub-second programs of a query family are kept there too
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        assert platform.configure_compile_cache() == "/x"
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        # unset: the fixed <checkout>/.jax_cache, on every backend
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        want = str(ROOT / ".jax_cache")
        assert platform.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
