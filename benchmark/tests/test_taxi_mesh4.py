"""``taxi-1b-mesh4``: the taxi table as one four-chip host of a v5e-16
holds it. What its configuration says holds of ``datasets/taxi_mesh4.py``
(the timeline in 264 slices, everything else ``datasets/taxi.py``'s),
its cell runs end to end on four virtual CPU devices, and the reader
files it brought read what they should."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import kernel_cost, manifest, readers, stats

MAN = manifest.Manifest()
MESH4 = MAN.dataset("taxi_mesh4")
TAXI = MAN.dataset("taxi")
CELL = "taxi-1b-mesh4.rides-closed"
T0 = np.datetime64("2009-01-01")


# -- the dataset ---------------------------------------------------------------

def test_the_schema_is_taxi_pys_and_that_module_is_left_alone():
    assert MESH4.fields() == TAXI.fields()
    assert (MESH4.INDEX, MESH4.INGEST_STREAM, MESH4.SHARD_WIDTH) == (
        TAXI.INDEX, TAXI.INGEST_STREAM, TAXI.SHARD_WIDTH)
    assert MESH4.NODE_SHARDS == 264 and MESH4.TABLE_SHARDS == 1049
    # the copy taxi-1b-c16 runs still cuts the timeline in 66
    assert TAXI.CHIP_SHARDS == 66
    assert manifest.load_dataset("taxi").CHIP_SHARDS == 66


def test_the_configuration_states_the_deployment():
    cfg = MAN.configs["taxi-1b-mesh4"]
    c16 = MAN.configs["taxi-1b-c16"]
    assert (cfg["dataset"], cfg["shards"], cfg["table_shards"],
            cfg["nodes"], cfg["chips"], cfg["server_toml"]) == (
        "taxi_mesh4", 264, 1049, 4, 4, None)
    assert cfg["shards"] == MESH4.NODE_SHARDS
    assert cfg["shards"] * cfg["nodes"] >= cfg["table_shards"]
    assert cfg["shards"] % cfg["chips"] == 0
    assert cfg["reduced"] == ["nodes", "pickup_grid_id", "drop_grid_id"]
    assert cfg["guarantees"] == c16["guarantees"]       # word for word
    assert "oracle.py" in cfg["reference"] and cfg["reduced_why"]
    # taxi-1b-c16's list, its line on the slices restated, plus one
    assert len(cfg["assumed"]) == len(c16["assumed"]) + 1
    assert sum(a != b for a, b in zip(cfg["assumed"], c16["assumed"])) == 1
    entry, = [c for c in MAN.bench["configs"] if c["name"] == cfg["name"]]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["source"] != c16["source"]
    cell = MAN.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "taxi-1b-mesh4", "rides-closed", 4)
    assert {m["name"] for m in MAN.metrics(CELL, "end_to_end")} == {
        "read_qps", "setup_s"}
    new = {"stack_evictions_per_read", "stack_build_mb_per_read",
           "mesh_dispatch_share", "mesh_collective_ms_per_read",
           "pair_counts_mesh_roofline"}
    layer = {m["name"]: m for m in MAN.metrics(CELL, "per_layer")}
    assert set(layer) == new | {
        "kernel_ms_per_read", "pallas_fallbacks_per_read", "read_median_ms",
        "programs_built_in_window"}
    assert all(layer[n]["workloads"] == [CELL]
               and layer[n]["moves"] == "read_qps" for n in new)


def test_the_size_arithmetic_of_the_configuration():
    fields = MESH4.fields()
    planes = (sum(f["rows"] for f in fields if f["type"] != "int")
              + 2 + int(511).bit_length() + 1)    # exists, sign, 9 bits; _exists
    assert planes == 500
    plane_mb = 264 * MESH4.SHARD_WIDTH / 8 / 1e6
    assert round(plane_mb, 1) == 34.6
    assert round(planes * plane_mb / 1e3, 1) == 17.3        # GB on the node
    assert round(planes * plane_mb / 4 / 1e3, 1) == 4.3     # GB a chip
    assert 264 * MESH4.SHARD_WIDTH == 276_824_064


def test_the_same_seed_and_stream_give_the_same_rides():
    a = MESH4.make(3300000001, 130, 1 << 14)
    b = MESH4.make(3300000001, 130, 1 << 14)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    other = MESH4.make(3300000002, 130, 1 << 14)
    assert any(not np.array_equal(a[k], other[k]) for k in a)
    assert set(a) == {f["name"] for f in MESH4.fields()}
    # every draw that does not hang on a ride's place on the timeline is
    # taxi.py's own for that seed and stream
    same = TAXI.make(3300000001, 130, 1 << 14)
    assert np.array_equal(a["passenger_count"], same["passenger_count"])
    assert np.array_equal(a["dist_miles"], same["dist_miles"])
    assert not np.array_equal(a["pickup_month"], same["pickup_month"])


def test_the_264_slices_abut_and_span_the_timeline():
    last = None
    spans = []
    for s in range(MESH4.NODE_SHARDS):
        t = MESH4.pickup_seconds(7, s, MESH4.SHARD_WIDTH)
        # pickups ascend up to the jitter, within a shard and across two
        assert int(np.min(np.diff(t))) >= -2 * MESH4.JITTER_S
        if last is not None:
            assert last - 2 * MESH4.JITTER_S <= int(t[0]) \
                <= last + 2 * MESH4.JITTER_S + 600
        spans.append((int(t[-1]) - int(t[0])) / 86400)
        last = int(t[-1])
        if s == 0:
            assert T0 + np.timedelta64(int(t[0]), "s") \
                < np.datetime64("2009-01-02")
    assert T0 + np.timedelta64(last, "s") >= np.datetime64("2016-06-30")
    assert T0 + np.timedelta64(last, "s") < np.datetime64("2016-07-02")
    # about ten days a shard (the year's rate moves it), a quarter of a
    # sixteenth's six weeks
    assert 8 <= min(spans) and max(spans) <= 25
    assert 9 <= float(np.median(spans)) <= 12
    # the writer's streams go on from the table's end
    nxt = MESH4.pickup_seconds(7, MESH4.INGEST_STREAM, 32768)
    assert last - 2 * MESH4.JITTER_S <= int(nxt[0])


@pytest.mark.parametrize("stream", [0, 131, 263, MESH4.INGEST_STREAM + 1])
def test_every_slot_lies_inside_its_fields_rows(stream):
    by_name = {f["name"]: f for f in MESH4.fields()}
    for name, col in MESH4.make(7, stream, 1 << 15).items():
        f = by_name[name]
        hi = f["max"] if f["type"] == "int" else f["rows"] - 1
        assert 0 <= int(col.min()) and int(col.max()) <= hi, name


def test_every_family_of_the_round_can_be_asked_of_this_table():
    names = {f["name"] for f in MESH4.fields()}
    for fam in MAN.mix_families(MAN.mixes["rides-closed"]):
        assert manifest.family_fields(MAN.families[fam]) <= names, fam
        assert "taxi_mesh4" in MAN.family_datasets(fam)


# -- the cell, rehearsed ----------------------------------------------------------

def test_the_cell_runs_on_four_virtual_devices_and_ends_correct(tmp_path):
    if not os.path.isdir(os.path.join(manifest.ROOT, "pilosa_tpu")):
        pytest.skip("the whole command needs the program beside it")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH, "run.py"),
         "--workload", CELL, "--seed", "3300000005", "--seconds", "4",
         "--trace", "1", "--allow-cpu", "--shards", "4",
         "--keep", str(tmp_path)],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"] == {**last["device"], "platform": "cpu",
                              "count": 4}
    assert last["metrics"] == {}        # a CPU run withholds every number
    assert last["checks"]["mesh_fallbacks"] == [0, 0]
    table = json.loads(proc.stdout.strip().splitlines()[-2])
    assert table["wrong_reads"] == 0 and table["verified_reads"] > 0
    assert table["mesh_sharding_fallback_total"] == 0
    # the scrape-based readers this cell brought found their series: a
    # warm round evicts nothing and builds nothing
    with open(tmp_path / f"{CELL}.json") as fh:
        read = json.load(fh)["metrics"]["per_layer"]
    assert read["stack_evictions_per_read"]["value"] == 0.0
    assert read["stack_build_mb_per_read"]["value"] == 0.0


# -- the reader files --------------------------------------------------------------

def scrape(evictions, built, mesh, dispatched, sums=0):
    return stats.parse_metrics(
        f"p_device_budget_evictions_total {evictions}\n"
        f"p_device_stack_evictions_total {evictions}\n"
        f"p_stack_build_bytes_total {built}\n"
        f'p_ops_pallas_mesh_dispatch_total{{kernel="pair_counts"}} {mesh}\n'
        f'p_ops_pallas_mesh_dispatch_total{{kernel="pair_sums"}} {sums}\n'
        f'p_ops_pallas_dispatch_total{{kernel="pair_counts"}} {dispatched}\n'
        f'p_ops_pallas_dispatch_total{{kernel="pair_sums"}} {sums}\n'
        f'p_ops_pallas_dispatch_total{{kernel="topn"}} 77\n')


def readings(**kw):
    base = dict(values={}, series={}, counts={})
    base.update(kw)
    return readers.Readings(**base)


def test_the_residency_and_mesh_counters_are_read_per_read():
    r = readings(scrape_before=scrape(3, 10e6, 100, 120),
                 scrape_after=scrape(43, 2010e6, 900, 1120, sums=9),
                 counts={"reads": 100.0})
    read = {n: readers.read(MAN.readers[n], r) for n in (
        "stack_evictions_per_read", "stack_build_mb_per_read",
        "mesh_dispatch_share")}
    assert read["stack_evictions_per_read"] == pytest.approx(0.4)
    assert read["stack_build_mb_per_read"] == pytest.approx(20.0)
    # 800 of the window's 1000 pair counts ran per chip; pair_sums' and
    # TopN's ticks are not in it
    assert read["mesh_dispatch_share"] == pytest.approx(80.0)
    # a program without the counters (the parent) and a window without a
    # pair count: nothing to divide by is no reading
    still = readings(scrape_before=scrape(3, 0, 5, 5),
                     scrape_after=scrape(3, 0, 5, 5), counts={"reads": 9.0})
    assert readers.read(MAN.readers["mesh_dispatch_share"], still) is None
    assert readers.read(MAN.readers["stack_evictions_per_read"], still) == 0
    for n in ("stack_evictions_per_read", "stack_build_mb_per_read",
              "mesh_dispatch_share"):
        assert readers.read(MAN.readers[n], readings()) is None


#: a chip's call of the mesh program as a v5e's trace names it: the
#: custom call inside ``jit__pair_counts_mesh``'s shard_map, with the
#: shapes that chip saw (2,162,688 words = 66 shards)
CHIP_CALL = ("%shard_map.19 = s32[24,16]{1,0:T(8,128)S(1)} custom-call("
             "u32[24,2162688]{1,0:T(8,128)} %param.2, "
             "u32[16,2162688]{1,0:T(8,128)} %param.3), "
             "custom_call_target=\"tpu_custom_call\"")
ONE_CHIP_CALL = CHIP_CALL.replace("%shard_map.19", "%_pair_counts_pallas.1")
PSUM = ("%all-reduce.1 = s32[24,16]{1,0:T(8,128)} all-reduce(s32[24,16] "
        "%shard_map.19), replica_groups={{0,1,2,3}}")
GATHER = "%all-gather.3 = u32[16,8650752]{1,0} all-gather(u32[16,2162688] %p)"
PAD = "%shard_map.7 = u32[16,2162688]{1,0} pad(u32[10,2162688] %p, u32[] %c)"


def test_the_mesh_readers_on_a_four_chip_trace():
    chip = [[CHIP_CALL, 0.0, 4e6], [PSUM, 4e6, 2e4], [PAD, 5e6, 1e6],
            [CHIP_CALL, 7e6, 4e6], [PSUM, 1.1e7, 2e4]]
    trace = {"planes": [
        {"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Ops", "events": chip + (
                [[GATHER, 2e7, 3e6]] if i == 0 else [])}]}
        for i in range(4)]}
    r = readings(trace=trace, trace_counts={"reads": 2.0},
                 device_kind="TPU v5 lite")
    # each chip's call is held to one chip's peaks at that chip's shapes
    ops, nbytes = kernel_cost.mm(24, 16, 2162688)
    assert kernel_cost.mm_from_text(CHIP_CALL) == (ops, nbytes)
    least = max(ops / 393e12, nbytes / 819e9)
    spec = MAN.readers["pair_counts_mesh_roofline"]
    assert readers.read(spec, r) == pytest.approx(100 * least / 4e-3)
    # the one-chip program's name is pair_counts_roofline's, not this one's
    assert readers.read(spec, readings(
        trace={"planes": [{"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [[ONE_CHIP_CALL, 0.0, 4e6]]}]}]},
        trace_counts={}, device_kind="TPU v5 lite")) is None
    assert readers.read(MAN.readers["pair_counts_roofline"], r) is None
    # the psums, and an all-gather that should not be there, per chip and
    # per read: (4 x 2 x 0.02 ms + 3 ms) / 4 chips / 2 reads
    assert readers.read(MAN.readers["mesh_collective_ms_per_read"], r) \
        == pytest.approx((4 * 2 * 0.02 + 3.0) / 4 / 2)
    assert readers.read(spec, readings()) is None
