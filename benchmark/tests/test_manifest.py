"""The self-check, and the claim that a cell, a family, a mix and a metric
are each added by new files plus one manifest entry."""

import json
import os
import shutil

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
    assert [m["name"] for m in man.metrics(cell, "per_layer")] == [
        "compiles_in_window", "hbm_hits_per_read"]
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
