"""The self-check, and the claim that a cell, a family, a mix and a metric
are each added by new files plus one manifest entry."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import manifest, readers, stats, traffic

BENCH = manifest.BENCH


def test_the_shipped_benchmark_passes_its_own_check():
    man = manifest.Manifest()
    man.check()
    assert len(man.cells) == len(man.bench["workloads"]) >= 4
    # all 13 SSB queries ship as templates
    assert sum(n.startswith("ssb-q") for n in man.families) == 13


@pytest.fixture
def copy(tmp_path, monkeypatch):
    """A copy of the benchmark that a test may add files to."""
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.
                    ignore_patterns(".cache", "__pycache__", "tests"))
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(manifest, "BENCH", str(tmp_path / "benchmark"))
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    return tmp_path


def edit_manifest(root, fn):
    path = root / "BENCHMARK.json"
    doc = json.loads(path.read_text())
    fn(doc)
    path.write_text(json.dumps(doc))


def test_additions_are_files_plus_one_entry_each(copy):
    before = {p: p.read_bytes() for p in (copy / "benchmark").rglob("*")
              if p.is_file()}
    b = copy / "benchmark"
    (b / "queries" / "count-nation.json").write_text(json.dumps({
        "name": "count-nation", "route": "pql",
        "text": "Count(Row(c_nation={n}))",
        "params": {"n": {"field": "c_nation", "dist": "zipf:1.1"}},
        "meaning": {"filter": [["c_nation", "==", "n"]], "agg": "count"}}))
    (b / "traffic" / "nation-burst.json").write_text(json.dumps({
        "loop": "open", "arrivals": "uniform", "rate": 50,
        "families": {"count-nation": 1}}))
    (b / "layer_metrics" / "hbm_hits_per_read.json").write_text(json.dumps({
        "kind": "scrape-delta", "den": "reads",
        "num": [{"metric": "device_resident_hits_total"}]}))
    cell = "ssb-flat-sf1.nation-burst"

    def add(doc):
        doc["workloads"].append({
            "name": cell, "config": "ssb-flat-sf1",
            "traffic": "nation-burst", "chips": 1, "why": "rehearsal"})
        for m in doc["end_to_end"]:
            if m["name"] == "read_p50_ms":
                m["workloads"].append(cell)
        doc["per_layer"].append({
            "name": "hbm_hits_per_read", "unit": "1", "better": "higher",
            "source": "program_counter", "layer": "residency",
            "moves": "read_p50_ms", "workloads": [cell]})

    edit_manifest(copy, add)
    man = manifest.Manifest()
    man.check()
    # a new cell reports what lists it and nothing else
    assert [m["name"] for m in man.metrics(cell, "per_layer")] == [
        "hbm_hits_per_read"]
    fields = {f["name"]: f
              for f in manifest.load_dataset("ssb_flat").fields()}
    reqs = traffic.open_schedule(man.mixes["nation-burst"], man.families,
                                 fields, "ssb", 1, 2.0)
    assert len(reqs) == 100 and reqs[0].text.startswith("Count(Row(c_nat")
    r = readers.Readings(
        {}, {}, {"reads": 4.0},
        stats.parse_metrics("p_device_resident_hits_total 10\n"),
        stats.parse_metrics("p_device_resident_hits_total 30\n"))
    assert readers.read(man.readers["hbm_hits_per_read"], r) == 5.0
    # no file that was there has changed
    assert all(p.read_bytes() == data for p, data in before.items())


def test_every_per_layer_metric_lists_its_cells():
    """A metric without a list would join every cell a later PR adds,
    whether or not that cell has anything for it to read."""
    man = manifest.Manifest()
    for m in man.bench["per_layer"]:
        assert m.get("workloads"), m["name"]
        assert set(m["workloads"]) <= set(man.cells), m["name"]


TOY_DATASET = '''
import numpy as np

INDEX = "toy"
INGEST_STREAM = 1 << 16


def fields():
    return [{"name": "kind", "type": "mutex", "rows": 4,
             "ids": [10, 20, 30, 40], "keys": None},
            {"name": "size", "type": "int", "min": 0, "max": 99}]


def make(seed, stream, count):
    rng = np.random.default_rng([int(seed), int(stream)])
    return {"kind": rng.integers(0, 4, count).astype(np.int8),
            "size": rng.integers(0, 100, count).astype(np.int64)}
'''


def test_a_second_dataset_is_files_and_entries_and_its_tests_are_green(
        tmp_path):
    """A toy second dataset with one configuration, one family, one mix
    and one cell, added to a copy of the benchmark with its tests: the
    self-check takes it, the family is drawn against its own table, and
    ``pytest benchmark/tests`` is green on the copy."""
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.
                    ignore_patterns(".cache", "__pycache__",
                                    ".pytest_cache"))
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    b = tmp_path / "benchmark"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    (b / "datasets" / "toy_events.py").write_text(TOY_DATASET)
    (b / "configs" / "toy-1.json").write_text(json.dumps({
        "name": "toy-1", "dataset": "toy_events", "shards": 1, "chips": 1,
        "server_toml": None, "reduced": []}))
    (b / "queries" / "toy-sum.json").write_text(json.dumps({
        "name": "toy-sum", "route": "pql",
        "text": "Sum(Row(kind={k}), field=size)",
        "params": {"k": {"field": "kind", "dist": "uniform"}},
        "meaning": {"filter": [["kind", "==", "k"]],
                    "agg": {"sum": "size"}}}))
    # no mix names this one: it goes to the dataset that has its field
    (b / "queries" / "toy-range.json").write_text(json.dumps({
        "name": "toy-range", "route": "pql",
        "text": "Count(Row(size < {v}))",
        "params": {"v": {"field": "size", "dist": "uniform"}},
        "meaning": {"filter": [["size", "<", "v"]], "agg": "count"}}))
    (b / "traffic" / "toy-open.json").write_text(json.dumps({
        "loop": "open", "arrivals": "uniform", "rate": 10,
        "families": {"toy-sum": 1}}))
    cell = "toy-1.toy-open"

    def add(doc):
        doc["configs"].append({
            "name": "toy-1", "source": "rehearsal",
            "file": "benchmark/configs/toy-1.json", "reduced": [],
            "why": "rehearsal"})
        doc["workloads"].append({
            "name": cell, "config": "toy-1", "traffic": "toy-open",
            "chips": 1, "why": "rehearsal"})
        for m in doc["end_to_end"] + doc["per_layer"]:
            if m["name"] in ("read_p50_ms", "http_server_ms", "parse_ms",
                             "programs_built_in_window"):
                m["workloads"].append(cell)

    edit_manifest(tmp_path, add)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "benchmark/tests", "-v",
         "-p", "no:cacheprovider",
         # the whole command on the CPU needs the program beside it, and
         # this test would copy the copy
         "--ignore", "benchmark/tests/test_rehearsal.py",
         "--ignore", "benchmark/tests/test_control.py",
         "--deselect", "benchmark/tests/test_manifest.py::"
         "test_a_second_dataset_is_files_and_entries_and_its_tests_are_green"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    passed = [ln for ln in proc.stdout.splitlines() if "PASSED" in ln]
    for family in ("toy-sum", "toy-range"):
        assert any("test_family_equals_the_hand_computation" in ln
                   and family in ln for ln in passed), family
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_family_of_no_dataset_and_a_mix_over_the_wrong_one_are_refused(
        copy):
    b = copy / "benchmark"
    (b / "queries" / "count-rides.json").write_text(json.dumps({
        "name": "count-rides", "text": "Count(Row(cab_type={c}))",
        "params": {"c": {"field": "cab_type"}},
        "meaning": {"filter": [["cab_type", "==", "c"]], "agg": "count"}}))
    with pytest.raises(manifest.ManifestError,
                       match=r"count-rides.json: no cell's mix names it "
                             r"and no dataset holds its fields "
                             r"\['cab_type'\]"):
        manifest.Manifest().check()
    mix = json.loads((b / "traffic" / "filter-open.json").read_text())
    mix["families"] = {"count-rides": 1}
    (b / "traffic" / "filter-open.json").write_text(json.dumps(mix))
    with pytest.raises(manifest.ManifestError,
                       match=r"cell ssb-flat-sf1.filter-open: queries/"
                             r"count-rides.json names \['cab_type'\], "
                             r"which datasets/ssb_flat.py does not have"):
        manifest.Manifest().check()


@pytest.mark.parametrize("break_it, says", [
    (lambda b: (b / "queries" / "bad.json").write_text("{not json"),
     "queries/bad.json"),
    (lambda b: (b / "traffic" / "m.json").write_text(json.dumps(
        {"loop": "open", "rate": 1, "families": {"no-such-family": 1}})),
     "no queries/no-such-family.json"),
    (lambda b: (b / "traffic" / "m.json").write_text(json.dumps(
        {"loop": "spiral"})), "loop must be one of"),
    (lambda b: (b / "layer_metrics" / "x.json").write_text(json.dumps(
        {"kind": "telepathy"})), "unknown kind"),
    (lambda b: os.remove(b / "layer_metrics" / "checkpoint_share.json"),
     "checkpoint_share: no reader file"),
    (lambda b: os.remove(b / "configs" / "ssb-flat-mesh4.json"),
     "no configs/ssb-flat-mesh4.json"),
])
def test_a_bad_file_fails_before_any_chip_time(copy, break_it, says):
    break_it(copy / "benchmark")
    with pytest.raises(manifest.ManifestError, match=says):
        manifest.Manifest().check()


def test_moves_must_name_a_metric_the_cell_reports(copy):
    def point_elsewhere(doc):
        for m in doc["per_layer"]:
            if m["name"] == "checkpoint_share":
                m["moves"] = "read_p95_ms"

    edit_manifest(copy, point_elsewhere)
    with pytest.raises(manifest.ManifestError,
                       match="checkpoint_share moves read_p95_ms"):
        manifest.Manifest().check()


@pytest.mark.parametrize("group, key, value, says", [
    ("per_layer", "layer", "load generator", "layer 'load generator'"),
    ("per_layer", "layer", "lowering + programs", "is not a plain name"),
    ("per_layer", "name", "p95 ms", "'p95 ms' is not a plain name"),
    ("workloads", "why", "x" * 201, "over 200 characters"),
])
def test_names_the_driver_would_refuse_fail_here(copy, group, key, value,
                                                 says):
    edit_manifest(copy, lambda doc: doc[group][0].__setitem__(key, value))
    with pytest.raises(manifest.ManifestError, match=says):
        manifest.Manifest().check()


def test_an_unknown_dataset_is_refused(copy):
    cfg = copy / "benchmark" / "configs" / "ssb-flat-sf1.json"
    doc = json.loads(cfg.read_text())
    doc["dataset"] = "nowhere"
    cfg.write_text(json.dumps(doc))
    with pytest.raises(manifest.ManifestError, match="dataset"):
        manifest.Manifest().check()
