"""Each kind of metric reader on readings small enough to check by eye."""

import pytest

from harness import kernel_cost, readers, stats

TREE = {"name": "query.profile", "duration_ns": 10_000_000, "children": [
    {"name": "query.pql", "duration_ns": 9_000_000, "children": [
        {"name": "stack.build", "duration_ns": 2_000_000, "children": [
            {"name": "device.h2d_copy", "duration_ns": 1_500_000,
             "children": []},
            {"name": "device.dispatch", "duration_ns": 400_000,
             "children": []}]},
        {"name": "device.dispatch", "duration_ns": 1_000_000,
         "children": []},
        {"name": "device.block_until_ready", "duration_ns": 500_000,
         "children": []}]}]}


def readings(**kw):
    base = dict(values={}, series={}, counts={})
    base.update(kw)
    return readers.Readings(**base)


def test_run_and_client_readers():
    r = readings(values={"setup_s": 31.5},
                 series={"read_ms": [1.0, 2.0, 3.0, 4.0, 100.0]})
    assert readers.read({"kind": "run", "value": "setup_s"}, r) == 31.5
    assert readers.read({"kind": "run", "value": "absent"}, r) is None
    spec = {"kind": "client", "series": "read_ms", "reduce": "p50"}
    assert readers.read(spec, r) == 3.0
    assert readers.read(dict(spec, reduce="max"), r) == 100.0
    assert readers.read(dict(spec, series="side_read_ms"), r) is None


def test_span_tree_self_time_subtracts_topmost_matches_only():
    r = readings(trees=[TREE, TREE])
    spec = {"kind": "span-tree", "root": ["query.pql", "query.sql"],
            "minus": ["stack.build", "device.dispatch",
                      "device.block_until_ready"]}
    # 9 ms - (2 + 1 + 0.5) ms; the dispatch inside stack.build is part of
    # stack.build, not taken off twice
    assert readers.read(spec, r) == pytest.approx(5.5)
    assert readers.read({"kind": "span-tree", "count": "stack.build"},
                        r) == 1.0
    assert readers.read({"kind": "span-tree", "count": "device.dispatch"},
                        r) == 2.0
    assert readers.read(spec, readings(trees=[])) is None


def test_scrape_delta_divides_deltas_and_scales():
    before = stats.parse_metrics(
        'p_http_request_duration_seconds_sum{route="post_query"} 1.0\n'
        'p_http_request_duration_seconds_count{route="post_query"} 100\n'
        'p_recovery_checkpoint_seconds_sum 0\n')
    after = stats.parse_metrics(
        'p_http_request_duration_seconds_sum{route="post_query"} 3.0\n'
        'p_http_request_duration_seconds_count{route="post_query"} 500\n'
        'p_recovery_checkpoint_seconds_sum 12\n')
    r = readings(scrape_before=before, scrape_after=after,
                 counts={"window_s": 40.0, "reads": 0.0})
    q = {"route": ["post_query", "post_sql"]}
    spec = {"kind": "scrape-delta", "scale": 1000,
            "num": [{"metric": "http_request_duration_seconds_sum",
                     "label_in": q}],
            "den": [{"metric": "http_request_duration_seconds_count",
                     "label_in": q}]}
    assert readers.read(spec, r) == pytest.approx(5.0)
    share = {"kind": "scrape-delta", "scale": 100, "den": "window_s",
             "num": [{"metric": "recovery_checkpoint_seconds_sum"}]}
    assert readers.read(share, r) == pytest.approx(30.0)
    # nothing to divide by: the metric is left out, not reported as 0
    assert readers.read(dict(share, den="reads"), r) is None
    assert readers.read(share, readings()) is None


def test_launcher_reader_is_the_window_delta():
    r = readings(launcher_before={"programs": 40},
                 launcher_after={"programs": 43})
    spec = {"kind": "launcher", "counter": "programs"}
    assert readers.read(spec, r) == 3.0
    assert readers.read(spec, readings()) is None


TRACE = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_f", 0.0, 9e8]]},
        {"name": "XLA Ops", "events": [
            ["fusion.1", 0.0, 2e8], ["all-reduce.3", 2e8, 1e8],
            ["fusion.1", 5e8, 2e8]]}]},
    {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["fusion.1", 0.0, 1e8]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "t", "events": [["Execute", 3e8, 2e8]]}]}]}


def test_trace_reader_averages_over_chips_and_divides_by_reads():
    r = readings(trace=TRACE, trace_counts={"reads": 10.0})
    busy = {"kind": "trace", "match": None, "per": "reads", "scale": 1000}
    # chip 0 is busy 0.5 s, chip 1 0.1 s: 0.3 s a chip, 30 ms a read
    assert readers.read(busy, r) == pytest.approx(30.0)
    coll = {"kind": "trace", "match": "all-reduce", "scale": 1000}
    assert readers.read(coll, r) == pytest.approx(50.0)
    assert readers.read(busy, readings()) is None
    assert readers.read(busy, readings(
        trace=TRACE, trace_counts={"reads": 0.0})) is None


MM = ("%_pair_counts_pallas.1 = s32[8,256]{1,0:T(8,128)} custom-call("
      "u32[8,196608]{1,0:T(8,128)} %a.1, u32[256,196608]{1,0:T(8,128)} "
      "%b.1), custom_call_target=\"tpu_custom_call\"")
SCAN = ("%while.27 = (s32[]{:T(128)}, s32[17,8,256]{2,1,0:T(8,128)}, "
        "s32[17,8,256]{2,1,0:T(8,128)}, u32[17,196608]{1,0:T(8,128)}, "
        "u32[256,196608]{0,1:T(8,128)}, u32[8,196608]{1,0:T(8,128)S(1)}) "
        "while(...), condition=%c, body=%b")


def test_roofline_share_reads_each_calls_shapes_from_its_text():
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            [MM, 0.0, 8e5], [MM, 1e6, 8e5], [SCAN, 2e6, 9e7],
            ["%fusion.1 = u32[8,196608] fusion(...)", 1e8, 5e5]]}]}]}
    r = readings(trace=trace, trace_counts={}, device_kind="TPU v5 lite")
    # one pair count reads 4 B x (8 + 256) rows x 196608 words and writes
    # an 8 x 256 result: bandwidth-bound on a v5e
    nbytes = 4.0 * 264 * 196608 + 4.0 * 8 * 256
    ops = 2.0 * 8 * 256 * 32 * 196608
    assert kernel_cost.mm_from_text(MM) == (ops, nbytes)
    assert nbytes / 819e9 > ops / 393e12
    spec = {"kind": "trace-roofline", "match": "_pair_counts_pallas",
            "cost": "mm"}
    assert readers.read(spec, r) == pytest.approx(
        100 * 2 * (nbytes / 819e9) / 1.6e-3)
    # the scan makes 2 x 17 such matmuls but need read its operands once:
    # compute-bound
    sops, sbytes = kernel_cost.pair_sums_from_text(SCAN)
    assert sops == 34 * ops
    assert sbytes == 4.0 * (8 + 256 + 17 + 2) * 196608 + 8.0 * 17 * 8 * 256
    assert sops / 393e12 > sbytes / 819e9
    scan = {"kind": "trace-roofline", "match": "^%while", "cost":
            "pair_sums"}
    assert readers.read(scan, r) == pytest.approx(
        100 * (sops / 393e12) / 9e-2)
    # text of another form is not a call of this kernel
    assert kernel_cost.mm_from_text(SCAN) is None
    assert kernel_cost.pair_sums_from_text(MM) is None
    none = dict(spec, match="no_such_kernel")
    assert readers.read(none, r) is None


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(LookupError):
        readers.peaks("TPU v9 imaginary")
    assert readers.peaks("TPU v5 lite")["hbm_gbps"] == 819.0
