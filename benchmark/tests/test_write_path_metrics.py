"""The per-layer metrics that read the program's write-path, start-up and
leaf-span instrumentation: the self-check accepts their files, and each
is read once against a hand-written pair of scrapes or a hand-written
span tree and gives the number worked out by hand."""

import pytest

from harness import manifest, readers, stats

BEFORE = stats.parse_metrics("""
pilosa_recovery_checkpoint_phase_seconds_total{phase="wal_flush"} 1.0
pilosa_recovery_checkpoint_phase_seconds_total{phase="serialize"} 10.0
pilosa_recovery_checkpoint_phase_seconds_total{phase="fsync"} 2.0
pilosa_recovery_checkpoint_fragments_total{state="changed"} 100
pilosa_recovery_checkpoint_fragments_total{state="skipped"} 0
pilosa_recovery_checkpoint_bytes_total{kind="raw"} 1000
pilosa_recovery_checkpoint_bytes_total{kind="stored"} 900
pilosa_ingest_stage_seconds_total{stage="decode"} 1.0
pilosa_ingest_stage_seconds_total{stage="key_translate"} 2.0
pilosa_ingest_stage_seconds_total{stage="fragment_advance"} 3.0
pilosa_ingest_stage_seconds_total{stage="wal_commit"} 4.0
pilosa_ingest_stage_seconds_total{stage="lock_wait"} 5.0
pilosa_ingest_stage_seconds_total{stage="checkpoint"} 6.0
pilosa_ingest_stage_bytes_total{stage="wal_commit"} 5000
pilosa_ingest_stage_bytes_total{stage="decode"} 2000
pilosa_http_request_body_bytes_total{route="post_import"} 1000
pilosa_http_request_body_bytes_total{route="post_import_values"} 1000
pilosa_stack_writer_wait_seconds_total 0.5
pilosa_stack_writer_wait_total 3
pilosa_device_programs_built_total{program="jit(f)",source="compiled"} 7
pilosa_device_programs_built_total{program="jit(g)",source="cache"} 40
pilosa_device_program_build_seconds_total 9.0
""")

AFTER = stats.parse_metrics("""
pilosa_recovery_checkpoint_phase_seconds_total{phase="wal_flush"} 1.5
pilosa_recovery_checkpoint_phase_seconds_total{phase="serialize"} 35.0
pilosa_recovery_checkpoint_phase_seconds_total{phase="fsync"} 7.0
pilosa_recovery_checkpoint_fragments_total{state="changed"} 120
pilosa_recovery_checkpoint_fragments_total{state="skipped"} 120
pilosa_recovery_checkpoint_bytes_total{kind="raw"} 5000
pilosa_recovery_checkpoint_bytes_total{kind="stored"} 4500
pilosa_ingest_stage_seconds_total{stage="decode"} 1.4
pilosa_ingest_stage_seconds_total{stage="key_translate"} 2.6
pilosa_ingest_stage_seconds_total{stage="fragment_advance"} 6.0
pilosa_ingest_stage_seconds_total{stage="wal_commit"} 4.2
pilosa_ingest_stage_seconds_total{stage="lock_wait"} 5.01
pilosa_ingest_stage_seconds_total{stage="checkpoint"} 41.0
pilosa_ingest_stage_bytes_total{stage="wal_commit"} 85000
pilosa_ingest_stage_bytes_total{stage="decode"} 42000
pilosa_http_request_body_bytes_total{route="post_import"} 21000
pilosa_http_request_body_bytes_total{route="post_import_values"} 21000
pilosa_stack_writer_wait_seconds_total 36.5
pilosa_stack_writer_wait_total 9
pilosa_device_programs_built_total{program="jit(f)",source="compiled"} 10
pilosa_device_programs_built_total{program="jit(g)",source="cache"} 68
pilosa_device_programs_built_total{program="jit(h)",source="compiled"} 1
pilosa_device_program_build_seconds_total 12.0
""")

COUNTS = {"window_s": 50.0, "batches": 2.0, "reads": 240.0}

#: metric -> the number worked out by hand from the scrapes above
BY_HAND = {
    "checkpoint_serialize_share": (35.0 - 10.0) / 50.0 * 100,      # 50 %
    "checkpoint_fsync_share": (7.0 - 2.0) / 50.0 * 100,            # 10 %
    "checkpoint_skipped_share": 120 / (20 + 120) * 100,
    "checkpoint_stored_per_raw_byte": (4500 - 900) / (5000 - 1000),
    "wal_bytes_per_user_byte": (85000 - 5000) / (2 * (21000 - 1000)),
    "import_decode_ms_per_batch": 0.4 / 2 * 1000,
    "import_translate_ms_per_batch": 0.6 / 2 * 1000,
    "import_advance_ms_per_batch": 3.0 / 2 * 1000,
    "import_wal_ms_per_batch": 0.2 / 2 * 1000,
    "import_lock_wait_ms_per_batch": 0.01 / 2 * 1000,
    "writer_wait_ms_per_read": 36.0 / 240 * 1000,
    "programs_built_in_window": 3 + 28 + 1,
}

TREE = {"name": "query.profile", "duration_ns": 12_000_000, "children": [
    {"name": "query.pql", "duration_ns": 11_000_000, "children": [
        {"name": "pql.parse", "duration_ns": 90_000, "children": []},
        {"name": "device.dispatch", "duration_ns": 500_000, "children": []},
        {"name": "stack.build", "duration_ns": 2_000_000, "children": [
            {"name": "device.dispatch", "duration_ns": 300_000,
             "children": []}]},
        {"name": "device.dispatch", "duration_ns": 400_000, "children": []},
        {"name": "pql.fetch", "duration_ns": 1_200_000, "children": []}]}]}
SQL_TREE = {"name": "sql.profile", "duration_ns": 9_000_000, "children": [
    {"name": "query.sql", "duration_ns": 8_000_000, "children": [
        {"name": "device.dispatch", "duration_ns": 500_000, "children": []},
        {"name": "pql.fetch", "duration_ns": 800_000, "children": []}]}]}


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


@pytest.mark.parametrize("name, by_hand", [
    # one PQL tree with three dispatches and one SQL tree with one
    ("dispatches_per_read", (3 + 1) / 2),
    # the SQL tree has no pql.parse and is left out of the mean
    ("parse_ms", 0.09),
    ("fetch_ms", (1.2 + 0.8) / 2),
])
def test_span_tree_metric_gives_the_number_worked_out_by_hand(man, name,
                                                              by_hand):
    spec = man.readers[name]
    assert spec["kind"] == "span-tree"
    r = _readings(trees=[TREE, SQL_TREE])
    assert readers.read(spec, r) == pytest.approx(by_hand)


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise(
        man):
    """The parent commit has none of these series and none of these
    spans: a ratio of two counters and a span metric are left out of the
    line; a plain window delta reads 0."""
    empty = stats.parse_metrics("pilosa_pql_queries_total 5\n")
    old_tree = {"name": "query.profile", "duration_ns": 1, "children": [
        {"name": "query.pql", "duration_ns": 1, "children": []}]}
    r = _readings(scrape_before=empty, scrape_after=empty, trees=[old_tree])
    for name in ("checkpoint_skipped_share",
                 "checkpoint_stored_per_raw_byte",
                 "wal_bytes_per_user_byte", "parse_ms", "fetch_ms"):
        assert readers.read(man.readers[name], r) is None
    assert readers.read(man.readers["programs_built_in_window"], r) == 0.0
    assert readers.read(man.readers["dispatches_per_read"], r) == 0.0


INGEST = "ssb-flat-sf1.ingest-sustained"


@pytest.mark.parametrize("name, layer, moves, cells", [
    ("checkpoint_serialize_share", "durability", "ingest_rows_per_s",
     [INGEST]),
    ("checkpoint_skipped_share", "durability", "ingest_rows_per_s",
     [INGEST]),
    ("import_advance_ms_per_batch", "ingest", "ingest_rows_per_s",
     [INGEST]),
    ("import_decode_ms_per_batch", "front_end", "ingest_rows_per_s",
     [INGEST]),
    ("writer_wait_ms_per_read", "residency", "ingest_rows_per_s",
     [INGEST]),
    ("programs_built_in_window", "lowering", "setup_s", [INGEST]),
    ("programs_from_cache_in_window", "lowering", "ingest_rows_per_s",
     [INGEST]),
    ("parse_ms", "lowering", "read_p50_ms",
     ["ssb-flat-sf1.filter-open", "ssb-flat-mesh4.mixed-closed"]),
])
def test_manifest_entry_names_its_layer_and_cells(man, name, layer, moves,
                                                  cells):
    """The layer, what it moves, and that these cells are among those
    that report it: a later PR may append its own cell to the list."""
    entry, = [m for m in man.bench["per_layer"] if m["name"] == name]
    assert (entry["layer"], entry["moves"]) == (layer, moves)
    assert set(cells) <= set(entry["workloads"])


def test_the_two_program_counts_read_the_launcher_and_the_scrape(man):
    """Built in the window (the program's own counter) beside fetched
    from the compile cache in the window (the launcher's): a cold side
    shows as built far above fetched."""
    r = _readings(scrape_before=BEFORE, scrape_after=AFTER,
                  launcher_before={"programs": 70, "cache_loads": 60},
                  launcher_after={"programs": 102, "cache_loads": 88})
    built = readers.read(man.readers["programs_built_in_window"], r)
    fetched = readers.read(man.readers["programs_from_cache_in_window"], r)
    assert (built, fetched) == (32.0, 28.0)
    assert readers.read(man.readers["programs_from_cache_in_window"],
                        _readings()) is None
