"""The taxi table's invariants: what ``configs/taxi-1b-c16.json`` says of
the data under ``assumed`` holds of what ``datasets/taxi.py`` makes, and
the configuration is the deployment the issue stated."""

import json
import os

import numpy as np
import pytest

from harness import manifest

MAN = manifest.Manifest()
TAXI = MAN.dataset("taxi")
FIELDS = {f["name"]: f for f in TAXI.fields()}
N = 1 << 16
T0 = np.datetime64("2009-01-01")


def shard(stream, seed=7, count=N):
    return TAXI.make(seed, stream, count)


def test_the_schema_is_the_sources_488_rows_and_one_int_field():
    rows = {n: f["rows"] for n, f in FIELDS.items() if f["type"] != "int"}
    assert sum(rows.values()) == 488 and len(rows) == 15
    assert rows == {
        "cab": 2, "passenger_count": 10, "dist_miles": 64,
        "duration_minutes": 120, "speed_mph": 80,
        **{f"{s}_{k}": n for s in ("pickup", "drop") for k, n in
           (("year", 8), ("month", 12), ("mday", 31), ("day", 7),
            ("time", 48))}}
    ints = [f for f in FIELDS.values() if f["type"] == "int"]
    assert [(f["name"], f["min"], f["max"]) for f in ints] == [
        ("total_amount_dollars", 0, 511)]
    # ids grow with the slot, as the oracle's group order needs
    assert all(f["ids"] == sorted(f["ids"]) for f in FIELDS.values()
               if f["type"] != "int")


def test_the_configuration_states_the_deployment():
    cfg = MAN.configs["taxi-1b-c16"]
    assert (cfg["shards"], cfg["table_shards"], cfg["nodes"],
            cfg["chips"], cfg["server_toml"]) == (66, 1049, 16, 1, None)
    assert cfg["reduced"] == ["nodes", "pickup_grid_id", "drop_grid_id"]
    assert set(cfg["guarantees"]) == {"exact_answers", "acknowledged_write",
                                      "served_state"}
    assert cfg["guarantees"] == MAN.configs["ssb-flat-sf1"]["guarantees"]
    assert "oracle.py" in cfg["reference"] and cfg["assumed"]
    entry, = [c for c in MAN.bench["configs"] if c["name"] == cfg["name"]]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert cfg["shards"] == TAXI.CHIP_SHARDS


def test_the_same_seed_and_stream_give_the_same_rides():
    a, b = shard(3, seed=2900000001), shard(3, seed=2900000001)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    other = shard(3, seed=2900000002)
    assert any(not np.array_equal(a[k], other[k]) for k in a)
    assert set(a) == set(FIELDS)


@pytest.mark.parametrize("stream", [0, 17, 40, 65, TAXI.INGEST_STREAM + 2])
def test_every_slot_lies_inside_its_fields_rows(stream):
    for name, col in shard(stream).items():
        f = FIELDS[name]
        hi = f["max"] if f["type"] == "int" else f["rows"] - 1
        assert 0 <= int(col.min()) and int(col.max()) <= hi, name
        assert col.size == N


def test_pickups_ascend_across_streams_and_within_one_up_to_the_jitter():
    last = -1
    for s in range(TAXI.CHIP_SHARDS):
        t = TAXI.pickup_seconds(7, s, TAXI.SHARD_WIDTH)
        # a ride is never more than the jitter, twice, before its
        # predecessor
        assert int(np.min(np.diff(t))) >= -2 * TAXI.JITTER_S
        assert int(t[0]) >= last - 2 * TAXI.JITTER_S
        span_days = (int(t[-1]) - int(t[0])) / 86400
        assert 30 <= span_days <= 75, (s, span_days)    # about six weeks
        last = int(t[-1])
    assert T0 + np.timedelta64(last, "s") >= np.datetime64("2016-06-30")
    # the writer's batches go on from the table's end
    nxt = TAXI.pickup_seconds(7, TAXI.INGEST_STREAM, 32768)
    after = TAXI.pickup_seconds(7, TAXI.INGEST_STREAM + 1, 32768)
    assert last - 2 * TAXI.JITTER_S <= int(nxt[0]) < int(after[0])


@pytest.mark.parametrize("stream", [0, 31, 65])
def test_a_day_has_one_year_month_day_of_month_and_weekday(stream):
    cols = shard(stream, count=TAXI.SHARD_WIDTH)
    for side in ("pickup", "drop"):
        year = cols[f"{side}_year"].astype(np.int64) + 2009
        month = cols[f"{side}_month"].astype(np.int64) + 1
        mday = cols[f"{side}_mday"].astype(np.int64) + 1
        dates = (np.array([f"{y}-{m:02d}" for y, m in
                           zip(*np.unique(np.stack([year, month]),
                                          axis=1))], dtype="datetime64[M]"))
        assert len(dates) <= 3      # a shard spans about six weeks
        day = ((year - 1970) * 12 + month - 1).astype("datetime64[M]") \
            .astype("datetime64[D]") + (mday - 1)
        # the calendar agrees with numpy's: the date exists and its
        # weekday is the one stated (0 = Monday)
        assert np.array_equal(day.astype("datetime64[M]").astype(np.int64),
                              (year - 1970) * 12 + month - 1)
        assert np.array_equal((day.astype(np.int64) + 3) % 7,
                              cols[f"{side}_day"])


def test_drop_is_pickup_plus_the_duration():
    cols = shard(12, count=TAXI.SHARD_WIDTH)

    def minutes(side):
        year = cols[f"{side}_year"].astype(np.int64) + 2009
        month = cols[f"{side}_month"].astype(np.int64) + 1
        day = ((year - 1970) * 12 + month - 1).astype("datetime64[M]") \
            .astype("datetime64[D]").astype(np.int64) + cols[f"{side}_mday"]
        return day * 1440 + cols[f"{side}_time"].astype(np.int64) * 30

    gap = minutes("drop") - minutes("pickup")       # in half hours' steps
    assert int(gap.min()) >= 0
    lasted = cols["duration_minutes"].astype(np.int64)
    short = lasted < 119
    assert np.all(np.abs(gap[short] - lasted[short]) <= 30)
    assert np.all(gap[~short] >= 90)
    # speed is distance over duration
    capped = (cols["dist_miles"] < 63) & short & (cols["speed_mph"] < 79)
    mph = cols["dist_miles"][capped] * 60.0 / lasted[capped]
    assert np.all(np.abs(mph - cols["speed_mph"][capped])
                  <= 30.0 / lasted[capped] + 0.5)


def test_green_cabs_only_from_august_2013():
    first_green = None
    for s in range(TAXI.CHIP_SHARDS):
        cols = shard(s)
        t = TAXI.pickup_seconds(7, s, N)
        green = cols["cab"] == 1
        if green.any():
            first_green = first_green if first_green is not None else s
            assert T0 + np.timedelta64(int(t[green].min()), "s") \
                >= np.datetime64("2013-08-01")
    assert first_green is not None
    late = shard(TAXI.CHIP_SHARDS - 1, count=TAXI.SHARD_WIDTH)
    assert abs(float(np.mean(late["cab"])) - TAXI.GREEN_SHARE) < 0.005


def test_the_skews_are_what_assumed_says():
    cols = shard(20, count=TAXI.SHARD_WIDTH)
    share = np.bincount(cols["passenger_count"], minlength=10) / cols[
        "passenger_count"].size
    for count, want in ((1, 0.705), (2, 0.14), (5, 0.06), (3, 0.04),
                        (6, 0.025), (4, 0.02), (0, 0.01)):
        assert abs(share[count] - want) < 0.004, (count, share[count])
    assert share[7:].sum() < 1e-4
    miles = cols["dist_miles"]
    assert 1 <= float(np.median(miles)) <= 2
    assert np.mean(miles <= 3) > 0.7 and np.mean(miles >= 20) < 0.01
    # the evening peak and the small hours
    by_half_hour = np.bincount(cols["pickup_time"], minlength=48)
    assert 36 <= int(np.argmax(by_half_hour)) <= 39
    assert 7 <= int(np.argmin(by_half_hour)) <= 10
    assert by_half_hour.max() > 5 * by_half_hour.min()
    # years in the published proportions: shards of 2^20 rides a year
    years = np.concatenate([shard(s, count=4096)["pickup_year"]
                            for s in range(TAXI.CHIP_SHARDS)])
    got = np.bincount(years, minlength=8) / years.size
    want = np.array(TAXI.YEAR_RIDES) / sum(TAXI.YEAR_RIDES)
    assert np.all(np.abs(got - want) < 0.012), (got, want)


def test_a_year_month_pair_lives_in_a_few_shards_and_every_month_in_some():
    held = {}
    for s in range(TAXI.CHIP_SHARDS):
        t = TAXI.pickup_seconds(7, s, TAXI.SHARD_WIDTH)
        months = (T0 + t.astype("timedelta64[s]")).astype("datetime64[M]")
        for month in np.unique(months):
            held.setdefault(str(month), set()).add(s)
    # January 2009 to June 2016, and the jitter's few seconds of July
    assert set(held) - {"2016-07"} == {
        f"{y}-{m:02d}" for y in range(2009, 2017) for m in range(1, 13)
        if (y, m) < (2016, 7)}
    assert max(len(v) for v in held.values()) <= 3
    assert sum(len(v) for v in held.values()) / len(held) < 2


def test_the_cell_reads_282_rows_and_the_int_field():
    mix = MAN.mixes["rides-closed"]
    assert len(mix["round"]) == 12 and mix["round_draws"] == 2
    assert sum(name.startswith("taxi-q4") for name in mix["round"]) == 6
    read = set()
    for name in set(mix["round"]):
        read |= manifest.family_fields(MAN.families[name])
    assert sum(FIELDS[f]["rows"] for f in read
               if FIELDS[f]["type"] != "int") == 282
    assert [f for f in read if FIELDS[f]["type"] == "int"] == [
        "total_amount_dollars"]
    assert set(FIELDS) - read == {"pickup_mday", "pickup_time", "drop_day",
                                  "duration_minutes"}
    # the cell reports what it appended its name to, and each is readable
    cell = "taxi-1b-c16.rides-closed"
    assert {m["name"] for m in MAN.metrics(cell, "end_to_end")} == {
        "read_qps", "setup_s"}
    new = {"groupby_fetches_per_read", "group_plane_mb_per_read",
           "groupby_levels_per_read", "block_decodes_per_read",
           "block_decode_mb_per_read"}
    layer = {m["name"]: m for m in MAN.metrics(cell, "per_layer")}
    assert set(layer) == new | {
        "kernel_ms_per_read", "pallas_fallbacks_per_read",
        "pair_counts_roofline", "read_median_ms",
        "programs_built_in_window"}
    assert all(layer[n]["workloads"] == [cell] for n in new)
    for n in new:
        with open(os.path.join(manifest.BENCH, "layer_metrics",
                               n + ".json")) as fh:
            assert json.load(fh)["kind"] in ("scrape-delta", "span-tree")
