"""The per-layer metrics that read the program's read-stage counters and
its idle-server counter: the self-check accepts their files, each is
read once against a hand-written pair of scrapes and gives the number
worked out by hand, and each names its layer, what it moves and its
cells."""

import pytest

from harness import manifest, readers, stats

BEFORE = stats.parse_metrics("""
pilosa_query_stage_seconds_total{stage="parse"} 0.5
pilosa_query_stage_seconds_total{stage="plan"} 1.0
pilosa_query_stage_seconds_total{stage="emit"} 2.0
pilosa_query_stage_seconds_total{stage="render"} 3.0
pilosa_query_stage_seconds_total{stage="encode"} 4.0
pilosa_query_stage_seconds_total{stage="write"} 5.0
pilosa_http_server_idle_seconds_total 100.0
pilosa_http_requests_in_flight 0
""")

AFTER = stats.parse_metrics("""
pilosa_query_stage_seconds_total{stage="parse"} 0.7
pilosa_query_stage_seconds_total{stage="plan"} 1.6
pilosa_query_stage_seconds_total{stage="emit"} 8.0
pilosa_query_stage_seconds_total{stage="render"} 6.0
pilosa_query_stage_seconds_total{stage="encode"} 10.0
pilosa_query_stage_seconds_total{stage="write"} 7.4
pilosa_http_server_idle_seconds_total 120.0
pilosa_http_requests_in_flight 2
""")

COUNTS = {"window_s": 50.0, "reads": 1200.0}

#: metric -> the number worked out by hand from the scrapes above
BY_HAND = {
    "server_idle_share": 20.0 / 50.0 * 100,                        # 40 %
    "emit_ms_per_read": 6.0 / 1200 * 1000,                         # 5 ms
    "respond_ms_per_read": (3.0 + 6.0 + 2.4) / 1200 * 1000,        # 9.5 ms
    "plan_ms_per_read": 0.6 / 1200 * 1000,                         # 0.5 ms
}

GROUPBY = ["ssb-flat-sf1.groupby-closed", "ssb-flat-sf10.groupby-closed"]
TAXI = ["taxi-1b-c16.rides-closed", "taxi-1b-mesh4.rides-closed"]
MESH = "ssb-flat-mesh4.mixed-closed"


@pytest.fixture(scope="module")
def man():
    m = manifest.Manifest()
    m.check()   # the self-check accepts the new files and entries
    return m


def _readings(**kw):
    base = dict(values={}, series={}, counts=dict(COUNTS))
    base.update(kw)
    return readers.Readings(**base)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_scrape_delta_metric_gives_the_number_worked_out_by_hand(man, name):
    spec = man.readers[name]
    assert spec["kind"] == "scrape-delta"
    r = _readings(scrape_before=BEFORE, scrape_after=AFTER)
    assert readers.read(spec, r) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_program_without_the_counters_reads_zero_and_does_not_raise(
        man, name):
    """The parent commit has none of these series: a window delta over
    a series that is absent on both sides reads 0 (a ``scrape-delta``
    reader cannot tell it from a counter that stood still)."""
    empty = stats.parse_metrics("pilosa_pql_queries_total 5\n")
    r = _readings(scrape_before=empty, scrape_after=empty)
    assert readers.read(man.readers[name], r) == 0.0


@pytest.mark.parametrize("name, layer, moves, cells", [
    ("server_idle_share", "front_end", "read_qps",
     GROUPBY + TAXI + [MESH]),
    ("emit_ms_per_read", "lowering", "read_qps", GROUPBY + TAXI),
    ("respond_ms_per_read", "front_end", "read_qps",
     GROUPBY + TAXI + [MESH]),
    ("plan_ms_per_read", "lowering", "read_p50_ms",
     ["ssb-flat-sf1.filter-open", MESH]),
])
def test_manifest_entry_names_its_layer_and_cells(man, name, layer, moves,
                                                  cells):
    """The layer, what it moves, the source, and that these cells are
    among those that report it: a later PR may append its own cell."""
    entry, = [m for m in man.bench["per_layer"] if m["name"] == name]
    assert (entry["layer"], entry["moves"]) == (layer, moves)
    assert entry["source"] == "program_counter"
    assert set(cells) <= set(entry["workloads"])
