"""The reduction from the profiler's file to numbers, on a trace recorded
on the chip: a 0.8 s window of ``ssb-flat-sf1.filter-open`` on one TPU
v5e (PR 22), and on intervals small enough to check by eye."""

import gzip
import os
import shutil

import pytest

from harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "filter-open-0.8s.xplane.pb.gz")
CHIP = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded trace through ``dump`` (JAX's reader; the test
    process may import JAX, the benchmark's parent does not) and
    ``load``."""
    import json

    tmp = tmp_path_factory.mktemp("trace")
    pb = tmp / "t.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(pb, "wb") as dst:
        shutil.copyfileobj(src, dst)
    out = tmp / "t.json"
    out.write_text(json.dumps(xplane.dump(str(pb))))
    return xplane.load(str(out))


def test_the_device_plane_and_its_lines_are_found(recorded):
    ops, modules = xplane.device_ops(recorded), xplane.device_modules(
        recorded)
    assert list(ops) == list(modules) == [CHIP]
    assert len(ops[CHIP]) == 2112 and len(modules[CHIP]) == 1050
    # an operation is named by its HLO text, a program by its function
    assert any(e[0].startswith("%_compare_pallas") for e in ops[CHIP])
    assert {xplane.module_name(e[0]) for e in modules[CHIP]} >= {
        "jit__plane_popcounts_pallas", "jit__compare_pallas"}


def test_busy_union_on_the_recorded_trace(recorded):
    ops = xplane.device_ops(recorded)[CHIP]
    assert xplane.busy_seconds(ops) == pytest.approx(0.055198569, abs=1e-9)
    # operations of a straight-line program do not overlap, so here the
    # union is the sum; whole programs include the gaps between their
    # operations and are busy a little longer
    assert sum(e[2] for e in ops) / 1e9 == pytest.approx(0.055198569,
                                                         abs=1e-9)
    modules = xplane.device_modules(recorded)[CHIP]
    assert xplane.busy_seconds(modules) == pytest.approx(0.055513374,
                                                         abs=1e-9)


def test_per_kernel_sums_on_the_recorded_trace(recorded):
    modules = xplane.device_modules(recorded)[CHIP]
    by_program = {}
    for name, secs in xplane.op_seconds(modules).items():
        key = xplane.module_name(name)
        by_program[key] = by_program.get(key, 0.0) + secs
    (first, t1), (second, t2) = xplane.top(by_program, 2)
    assert first == "jit__plane_popcounts_pallas"
    assert t1 == pytest.approx(0.046507311, abs=1e-9)
    assert second == "jit__compare_pallas"
    assert t2 == pytest.approx(0.005603802, abs=1e-9)
    only = xplane.op_seconds(xplane.device_ops(recorded)[CHIP],
                             r"^%_compare_pallas")
    assert only and all(k.startswith("%_compare_pallas") for k in only)
    assert sum(only.values()) < t2  # the kernel is part of its program


def test_idle_gaps_cover_what_the_union_leaves(recorded):
    ops = xplane.device_ops(recorded)[CHIP]
    window = xplane.span(recorded)
    host = xplane.host_events(recorded)
    assert len(host) == 38574
    gaps = xplane.idle_gaps(ops, host, window)
    idle = (window[1] - window[0]) / 1e9 - xplane.busy_seconds(ops)
    assert sum(gaps.values()) == pytest.approx(idle, abs=1e-9)
    assert idle == pytest.approx(0.688718674, abs=1e-9)
    top = dict(xplane.top(gaps, 3))
    assert top["no host event"] == pytest.approx(0.280623246, abs=1e-9)
    assert "PjitFunction(dynamic_slice)" in top


def test_merged_and_busy_on_small_intervals():
    events = [["a", 0.0, 10.0], ["b", 5.0, 10.0], ["c", 30.0, 5.0],
              ["d", 31.0, 1.0], ["e", 35.0, 5.0]]
    assert xplane.merged(events) == [[0.0, 15.0], [30.0, 40.0]]
    assert xplane.busy_seconds(events) == 25e-9
    assert xplane.busy_seconds([]) == 0.0
    assert xplane.op_seconds(events + [["a", 50.0, 2.0]], "^a") == {
        "a": 12e-9}


def test_a_gap_goes_to_the_outermost_host_event_at_its_midpoint():
    device = [["k", 10.0, 10.0], ["k", 40.0, 10.0]]
    host = [["PjitFunction(f)", 18.0, 24.0], ["Allocate", 25.0, 6.0],
            ["late", 90.0, 5.0]]
    gaps = xplane.idle_gaps(device, host, (0.0, 100.0))
    # [0,10) nobody; [20,40) midpoint 30 lies in both host events, the
    # outer one owns it; [50,100) midpoint 75 lies in none
    assert gaps == {"no host event": 60e-9, "PjitFunction(f)": 20e-9}


def test_module_names_drop_the_fingerprint_only():
    assert xplane.module_name("jit_pair_sums(3970795015038422538)") == \
        "jit_pair_sums"
    assert xplane.module_name("jit_f(x)") == "jit_f(x)"
