"""Cluster health plane: timeline sampler, SLO burn rates, trace
exemplars, flight recorder — plus the registry hardening that rode
along (exposition escaping, deque history ring, thread-safety)."""

import json
import os
import threading
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.obs import metrics as M
from pilosa_tpu.obs import tracing as T
from pilosa_tpu.obs.flight import FlightRecorder
from pilosa_tpu.obs.health import HealthPlane
from pilosa_tpu.obs.history import ExecutionRequestsAPI
from pilosa_tpu.obs.slo import Objective, SLOTracker
from pilosa_tpu.obs.timeline import TimelineSampler, estimate_quantile
from pilosa_tpu.sched.clock import ManualClock


# ---------------------------------------------------------------------------
# satellite: Prometheus exposition escaping
# ---------------------------------------------------------------------------


class TestExpositionEscaping:
    def test_label_values_escaped_per_spec(self):
        reg = M.MetricsRegistry()
        reg.count("q_total", q='say "hi"\nback\\slash')
        lines = [l for l in reg.prometheus_text().splitlines()
                 if l.startswith("pilosa_q_total{")]
        assert lines == [
            'pilosa_q_total{q="say \\"hi\\"\\nback\\\\slash"} 1']
        # the raw value never leaks an unescaped quote or newline into
        # the exposition line
        assert "\n" not in lines[0]

    def test_clean_values_unchanged(self):
        reg = M.MetricsRegistry()
        reg.gauge("g", 2.0, node="n1")
        assert 'pilosa_g{node="n1"} 2.0' in reg.prometheus_text()


# ---------------------------------------------------------------------------
# satellite: history ring is a deque with a serve limit
# ---------------------------------------------------------------------------


class TestHistoryRing:
    def test_deque_eviction_keeps_newest(self):
        h = ExecutionRequestsAPI(capacity=5)
        for i in range(8):
            rec = h.begin("i", f"q{i}", "pql")
            h.end(rec)
        out = h.list()
        assert len(out) == 5
        assert [r.query for r in out] == ["q7", "q6", "q5", "q4", "q3"]

    def test_list_limit(self):
        h = ExecutionRequestsAPI(capacity=10)
        for i in range(6):
            h.end(h.begin("i", f"q{i}", "pql"))
        assert [r.query for r in h.list(limit=2)] == ["q5", "q4"]
        assert h.list(limit=0) == []
        assert len(h.list(limit=99)) == 6


# ---------------------------------------------------------------------------
# satellite: registry thread-safety under reader/writer load
# ---------------------------------------------------------------------------


class TestRegistryThreadSafety:
    def test_hammer_with_concurrent_exposition(self):
        reg = M.MetricsRegistry()
        iters, writers = 500, 8
        errors = []
        stop = threading.Event()

        def writer(tid):
            try:
                for i in range(iters):
                    reg.count("hammer_total", labelled=str(tid % 2))
                    reg.gauge("hammer_gauge", float(i))
                    reg.observe_bucketed(
                        "hammer_ms", float(i % 40), (5.0, 10.0, 20.0))
            except Exception as e:  # pragma: no cover - the assertion
                errors.append(e)

        def reader():
            try:
                while not stop.is_set():
                    reg.prometheus_text()
                    reg.as_json()
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(writers)]
        readers = [threading.Thread(target=reader) for _ in range(2)]
        for t in readers + threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        for t in readers:
            t.join()
        assert errors == []
        total = sum(reg.value("hammer_total", labelled=str(v))
                    for v in (0, 1))
        assert total == writers * iters
        h = reg.histogram("hammer_ms")
        assert h["count"] == writers * iters


# ---------------------------------------------------------------------------
# timeline sampler
# ---------------------------------------------------------------------------


class TestTimelineSampler:
    def test_counter_deltas_become_rates(self):
        reg = M.MetricsRegistry()
        clock = ManualClock()
        tl = TimelineSampler(interval_ms=100, capacity=10, registry=reg,
                             clock=clock)
        reg.count("reqs_total", 5)
        first = tl.sample()
        assert first["rates"] == {}  # no previous sample to diff against
        clock.advance(2.0)
        reg.count("reqs_total", 10)
        second = tl.sample()
        assert second["rates"]["reqs_total"] == pytest.approx(5.0)

    def test_histogram_quantiles_over_interval_deltas(self):
        reg = M.MetricsRegistry()
        clock = ManualClock()
        tl = TimelineSampler(registry=reg, clock=clock)
        for v in (3.0, 3.0, 3.0, 3.0):
            reg.observe_bucketed("lat_ms", v, (2.0, 4.0, 8.0))
        s = tl.sample()
        q = s["quantiles"]["lat_ms"]
        assert q["count"] == 4
        assert 2.0 <= q["p50"] <= 4.0
        clock.advance(1.0)
        s2 = tl.sample()  # no new observations -> series omitted
        assert "lat_ms" not in s2["quantiles"]

    def test_estimate_quantile_interpolates(self):
        assert estimate_quantile([10.0, 20.0, 30.0], [0, 4, 0, 0], 0.5) \
            == pytest.approx(15.0)
        # overflow bucket clamps to the last bound
        assert estimate_quantile([10.0, 20.0], [0, 0, 3], 0.99) == 20.0
        assert estimate_quantile([10.0], [0, 0], 0.5) == 0.0

    def test_estimate_quantile_empty_delta_window(self):
        # an interval where no histogram observations landed produces an
        # all-zero delta; any quantile over it must be 0.0, not a crash
        for q in (0.0, 0.5, 0.99, 1.0):
            assert estimate_quantile([10.0, 20.0], [0, 0, 0], q) == 0.0
        assert estimate_quantile([], [], 0.5) == 0.0
        # negative deltas (counter reset mid-window) also sum to <= 0
        assert estimate_quantile([10.0], [-2, 0], 0.5) == 0.0

    def test_estimate_quantile_single_populated_bucket(self):
        bounds = [10.0, 20.0, 30.0]
        # every quantile interpolates within the one live bucket
        assert estimate_quantile(bounds, [0, 10, 0, 0], 0.1) \
            == pytest.approx(11.0)
        assert estimate_quantile(bounds, [0, 10, 0, 0], 1.0) \
            == pytest.approx(20.0)
        # first bucket interpolates from an implicit 0.0 lower edge
        assert estimate_quantile(bounds, [4, 0, 0, 0], 0.5) \
            == pytest.approx(5.0)

    def test_estimate_quantile_all_counts_in_overflow(self):
        # nothing sane can be interpolated past +Inf: clamp to bounds[-1]
        bounds = [10.0, 20.0, 30.0]
        for q in (0.01, 0.5, 1.0):
            assert estimate_quantile(bounds, [0, 0, 0, 7], q) == 30.0
        # degenerate: overflow counts but no finite bounds at all
        assert estimate_quantile([], [5], 0.5) == 0.0

    def test_estimate_quantile_exact_bucket_boundary(self):
        # rank landing exactly on a bucket's cumulative edge stays inside
        # that bucket and interpolates to its upper bound, not past it
        bounds = [10.0, 20.0]
        counts = [2, 2, 0]  # cum edges at rank 2 and 4
        assert estimate_quantile(bounds, counts, 0.5) \
            == pytest.approx(10.0)  # rank=2 == first bucket's cum edge
        assert estimate_quantile(bounds, counts, 1.0) \
            == pytest.approx(20.0)
        # q=0 takes the first populated bucket's lower edge
        assert estimate_quantile(bounds, counts, 0.0) \
            == pytest.approx(0.0)

    def test_window_filters_by_clock(self):
        clock = ManualClock()
        tl = TimelineSampler(registry=M.MetricsRegistry(), clock=clock)
        for _ in range(3):
            tl.sample()
            clock.advance(2.0)
        # now=6; samples at t=0,2,4
        assert len(tl.window(2.5)) == 1
        assert len(tl.window(5.0)) == 2
        assert len(tl.window(None)) == 3

    def test_sick_probe_degrades_not_fatal(self):
        tl = TimelineSampler(registry=M.MetricsRegistry(),
                             clock=ManualClock())
        tl.add_probe("bad", lambda: 1 / 0)
        tl.add_probe("good", lambda: {"v": 1})
        s = tl.sample()
        assert "error" in s["probes"]["bad"]
        assert s["probes"]["good"] == {"v": 1}

    def test_maybe_sample_respects_cadence(self):
        clock = ManualClock()
        tl = TimelineSampler(interval_ms=1000, registry=M.MetricsRegistry(),
                             clock=clock)
        assert tl.maybe_sample() is not None  # first call always samples
        assert tl.maybe_sample() is None      # same instant: not due
        clock.advance(1.5)
        assert tl.maybe_sample() is not None

    def test_ring_bounded(self):
        clock = ManualClock()
        tl = TimelineSampler(capacity=4, registry=M.MetricsRegistry(),
                             clock=clock)
        for _ in range(9):
            tl.sample()
            clock.advance(1.0)
        assert len(tl) == 4


# ---------------------------------------------------------------------------
# SLO burn rates
# ---------------------------------------------------------------------------


def _latency_slo(threshold_ms=100.0, target=0.9):
    return Objective("q-lat", "query", "latency", target,
                     threshold_ms=threshold_ms)


class TestSLOTracker:
    def test_burn_rate_is_bad_fraction_over_budget(self):
        clock = ManualClock()
        slo = SLOTracker(objectives=[_latency_slo()], registry=M.MetricsRegistry(),
                         clock=clock, fast_burn_alert=4.0)
        for i in range(10):
            slo.record("query", 500.0 if i < 5 else 10.0)
        row = slo.burn_rates()[0]
        # 5/10 bad over a 10% budget -> burning 5x
        assert row["fast_burn"] == pytest.approx(5.0)
        assert row["alerting"] is True
        assert slo.status()["alerting"] == ["q-lat"]

    def test_min_events_guards_single_sample_spikes(self):
        slo = SLOTracker(objectives=[_latency_slo()],
                         registry=M.MetricsRegistry(), clock=ManualClock(),
                         fast_burn_alert=1.0, min_events=5)
        slo.record("query", 9999.0)
        row = slo.burn_rates()[0]
        assert row["fast_burn"] > 1.0 and row["alerting"] is False

    def test_error_objective(self):
        obj = Objective("q-err", "query", "errors", 0.99)
        slo = SLOTracker(objectives=[obj], registry=M.MetricsRegistry(),
                         clock=ManualClock())
        for i in range(10):
            slo.record("query", 1.0, error=(i == 0))
        row = slo.burn_rates()[0]
        assert row["fast_burn"] == pytest.approx(10.0)  # 10% errors / 1%

    def test_events_age_out_of_fast_window(self):
        clock = ManualClock()
        slo = SLOTracker(objectives=[_latency_slo()],
                         registry=M.MetricsRegistry(), clock=clock,
                         fast_window_s=60.0, slow_window_s=600.0)
        for _ in range(6):
            slo.record("query", 500.0)
        assert slo.burn_rates()[0]["fast_burn"] > 0
        clock.advance(120.0)
        row = slo.burn_rates()[0]
        assert row["fast_burn"] == 0.0          # aged out of fast window
        assert row["slow_burn"] > 0.0           # still in the slow window

    def test_publishes_gauges(self):
        reg = M.MetricsRegistry()
        slo = SLOTracker(objectives=[_latency_slo()], registry=reg,
                         clock=ManualClock())
        slo.record("query", 500.0)
        slo.burn_rates()
        assert reg.value(M.METRIC_SLO_BURN_RATE, slo="q-lat",
                         window="fast") > 0


# ---------------------------------------------------------------------------
# trace exemplars
# ---------------------------------------------------------------------------


class TestExemplars:
    def test_bucket_links_to_active_trace(self):
        prev = T.get_tracer()
        tracer = T.set_tracer(T.Tracer(enabled=True, sample_rate=1.0,
                                       store=T.TraceStore(8)))
        reg = M.MetricsRegistry(exemplars=True)
        try:
            span = tracer.start_trace("x")
            reg.observe_bucketed("lat_ms", 3.0, (1.0, 5.0, 10.0))
            span.finish()
        finally:
            T.set_tracer(prev)
        text = reg.prometheus_text()
        line = next(l for l in text.splitlines()
                    if l.startswith('pilosa_lat_ms_bucket{le="5"'))
        assert f'# {{trace_id="{span.trace_id}"}} 3' in line

    def test_disabled_by_default(self):
        prev = T.get_tracer()
        tracer = T.set_tracer(T.Tracer(enabled=True, sample_rate=1.0))
        reg = M.MetricsRegistry()  # exemplars off
        try:
            span = tracer.start_trace("x")
            reg.observe_bucketed("lat_ms", 3.0, (1.0, 5.0))
            span.finish()
        finally:
            T.set_tracer(prev)
        assert "trace_id=" not in reg.prometheus_text()

    def test_no_exemplar_outside_trace(self):
        reg = M.MetricsRegistry(exemplars=True)
        reg.observe_bucketed("lat_ms", 3.0, (1.0, 5.0))
        assert "trace_id=" not in reg.prometheus_text()

    def test_trace_histograms_carry_exemplars_at_finish(self):
        # the tracer observes trace_duration_ms/_stage_latency_ms AFTER
        # the span scope is reset, so the trace ID rides explicitly
        prev = T.get_tracer()
        reg = M.MetricsRegistry(exemplars=True)
        tracer = T.set_tracer(T.Tracer(enabled=True, sample_rate=1.0,
                                       registry=reg))
        try:
            span = tracer.start_trace("q")
            with tracer.start_span("stage.one"):
                pass
            span.finish()
        finally:
            T.set_tracer(prev)
        text = reg.prometheus_text()
        for series in ("trace_duration_ms_bucket",
                       "trace_stage_latency_ms_bucket"):
            line = next(l for l in text.splitlines()
                        if l.startswith(f"pilosa_{series}")
                        and "trace_id=" in l)
            assert f'trace_id="{span.trace_id}"' in line

    def test_disable_health_clears_exemplar_flag(self):
        from pilosa_tpu.api import API
        from pilosa_tpu.config import Config

        api = API()
        assert M.REGISTRY.exemplars is False
        api.enable_health(config=Config(obs_timeline_exemplars=True))
        assert M.REGISTRY.exemplars is True
        api.disable_health()
        assert M.REGISTRY.exemplars is False


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def _plane(clock, reg, **kw):
    kw.setdefault("interval_ms", 100.0)
    kw.setdefault("min_events", 1)
    return HealthPlane(registry=reg, clock=clock, **kw)


class TestFlightRecorder:
    def test_wal_stall_trigger(self):
        clock, reg = ManualClock(), M.MetricsRegistry()
        hp = _plane(clock, reg, wal_stall_s=5.0)
        hp.timeline.add_probe("wal", lambda: {"flush_lag_s": 9.0})
        hp.timeline.sample()
        bundles = hp.flight.bundles()
        assert [b["trigger"] for b in bundles] == ["wal_stall"]
        assert "9.0s" in bundles[0]["reason"]

    def test_breaker_open_trigger_from_probe(self):
        clock, reg = ManualClock(), M.MetricsRegistry()
        hp = _plane(clock, reg)
        hp.timeline.add_probe(
            "breakers",
            lambda: {"enabled": True, "states": {"n2": "open",
                                                 "n3": "closed"}})
        hp.timeline.sample()
        b = hp.flight.bundles()[0]
        assert b["trigger"] == "breaker_open" and "n2" in b["reason"]

    def test_eviction_storm_trigger(self):
        clock, reg = ManualClock(), M.MetricsRegistry()
        hp = _plane(clock, reg, eviction_rate=10.0)
        hp.timeline.sample()
        clock.advance(1.0)
        reg.count(M.METRIC_DEVICE_STACK_EVICTIONS, 50)
        hp.timeline.sample()
        assert [b["trigger"] for b in hp.flight.bundles()] \
            == ["eviction_storm"]

    def test_slow_query_burst_trigger(self):
        clock, reg = ManualClock(), M.MetricsRegistry()
        hp = _plane(clock, reg, slow_burst_per_s=5.0)
        hp.timeline.sample()
        clock.advance(1.0)
        reg.count(M.METRIC_TRACE_SLOW_QUERIES, 10, kind="pql")
        hp.timeline.sample()
        assert [b["trigger"] for b in hp.flight.bundles()] \
            == ["slow_query_burst"]

    def test_membership_flap_trigger(self):
        clock, reg = ManualClock(), M.MetricsRegistry()
        hp = _plane(clock, reg, membership_flap_transitions=6.0)
        flaps = {"n": 2}
        hp.timeline.add_probe(
            "membership",
            lambda: {"enabled": True, "alive": 3, "suspect": 0, "down": 0,
                     "recent_transitions": flaps["n"]})
        hp.timeline.sample()
        assert hp.flight.bundles() == []  # 2 transitions: normal churn
        clock.advance(1.0)
        flaps["n"] = 7
        hp.timeline.sample()
        bundles = hp.flight.bundles()
        assert [b["trigger"] for b in bundles] == ["membership_flap"]
        assert "7 membership transitions" in bundles[0]["reason"]

    def test_membership_probe_absent_never_fires(self):
        clock, reg = ManualClock(), M.MetricsRegistry()
        hp = _plane(clock, reg, membership_flap_transitions=1.0)
        hp.timeline.sample()  # no membership probe attached at all
        assert hp.flight.bundles() == []

    def test_cooldown_bounds_refires(self):
        clock, reg = ManualClock(), M.MetricsRegistry()
        hp = _plane(clock, reg, wal_stall_s=1.0, flight_cooldown_s=30.0)
        hp.timeline.add_probe("wal", lambda: {"flush_lag_s": 5.0})
        hp.timeline.sample()
        clock.advance(5.0)
        hp.timeline.sample()  # still stalled, but inside the cooldown
        assert len(hp.flight.bundles()) == 1
        clock.advance(31.0)
        hp.timeline.sample()
        assert len(hp.flight.bundles()) == 2

    def test_bundle_contents_and_lookup(self):
        clock, reg = ManualClock(), M.MetricsRegistry()
        hp = _plane(clock, reg, wal_stall_s=1.0)
        hp.flight.record_event("note", detail="before")
        hp.timeline.add_probe("wal", lambda: {"flush_lag_s": 5.0})
        hp.timeline.sample()
        b = hp.flight.bundles()[0]
        assert b["events"][0]["kind"] == "note"
        assert len(b["timeline"]) >= 1
        assert "objectives" in b["slo"]
        assert hp.flight.get(b["id"])["id"] == b["id"]
        with pytest.raises(KeyError):
            hp.flight.get("fb-nope")

    def test_disk_dump(self, tmp_path):
        clock, reg = ManualClock(), M.MetricsRegistry()
        hp = _plane(clock, reg, wal_stall_s=1.0,
                    dump_dir=str(tmp_path / "dumps"))
        hp.timeline.add_probe("wal", lambda: {"flush_lag_s": 5.0})
        hp.timeline.sample()
        b = hp.flight.bundles()[0]
        path = tmp_path / "dumps" / f"{b['id']}.json"
        assert path.exists()
        assert json.loads(path.read_text())["trigger"] == "wal_stall"

    def test_counts_bundles_metric(self):
        clock, reg = ManualClock(), M.MetricsRegistry()
        hp = _plane(clock, reg, wal_stall_s=1.0)
        hp.timeline.add_probe("wal", lambda: {"flush_lag_s": 5.0})
        hp.timeline.sample()
        assert reg.value(M.METRIC_FLIGHT_BUNDLES,
                         trigger="wal_stall") == 1


# ---------------------------------------------------------------------------
# API integration + env bootstrap
# ---------------------------------------------------------------------------


class TestAPIHealth:
    def test_query_paths_feed_slo(self):
        from pilosa_tpu.api import API

        api = API()
        if api.health is not None:  # PILOSA_TPU_OBS_TIMELINE=1 bootstrap
            api.disable_health()
        api.create_index("i")
        api.create_field("i", "f")
        api.import_bits("i", "f", rows=[0], cols=[0])
        # no plane: a query takes no timeline sample
        sampled = M.REGISTRY.value(M.METRIC_TIMELINE_SAMPLES)
        off = api.query_json("i", "Count(Row(f=0))")
        assert M.REGISTRY.value(M.METRIC_TIMELINE_SAMPLES) == sampled
        clock = ManualClock()
        hp = api.enable_health(clock=clock, interval_ms=100.0)
        try:
            api.import_bits("i", "f", rows=[0], cols=[0])
            clock.advance(1.0)
            # the plane changes no answer
            assert api.query_json("i", "Count(Row(f=0))") == off
            rows = {r["name"]: r for r in hp.slo.burn_rates()}
            assert rows["query-latency"]["events_fast"] == 1
            assert rows["ingest-latency"]["events_fast"] == 1
            assert hp.timeline.latest() is not None
        finally:
            api.disable_health()
        assert api.health is None

    def test_env_bootstrap_zero_threads(self, monkeypatch):
        from pilosa_tpu.api import API

        monkeypatch.setenv("PILOSA_TPU_OBS_TIMELINE", "1")
        before = threading.active_count()
        api = API()
        try:
            assert api.health is not None
            assert api.health.timeline.running is False
            assert threading.active_count() == before
            api.create_index("i")
            api.create_field("i", "f")
            api.query("i", "Count(Row(f=0))")
        finally:
            api.disable_health()

    def test_from_config(self):
        from pilosa_tpu.config import Config

        cfg = Config(obs_timeline_interval_ms=50.0,
                     obs_timeline_capacity=7,
                     obs_timeline_slo_fast_burn_alert=2.5)
        hp = HealthPlane.from_config(cfg, registry=M.MetricsRegistry())
        assert hp.timeline.interval_s == pytest.approx(0.05)
        assert hp.timeline._ring.maxlen == 7
        assert hp.slo.fast_burn_alert == 2.5


# ---------------------------------------------------------------------------
# the acceptance scenario: 3-node cluster, slow node, burn -> bundle
# ---------------------------------------------------------------------------


def _get_json(url):
    with urllib.request.urlopen(url) as r:
        return json.load(r)


class TestClusterHealthAcceptance:
    def test_slow_node_burn_fires_flight_recorder(self):
        from pilosa_tpu.cluster import LocalCluster
        from pilosa_tpu.cluster.resilience import FaultPlan
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        prev = T.get_tracer()
        T.set_tracer(T.Tracer(enabled=True, sample_rate=1.0, slow_ms=20.0,
                              store=T.TraceStore(128)))
        plan = FaultPlan(seed=7)
        clock = ManualClock()
        objectives = [
            Objective("query-latency", "query", "latency", 0.99,
                      threshold_ms=10.0),
            Objective("query-errors", "query", "errors", 0.999),
        ]
        try:
            with LocalCluster(3, replica_n=1, fault_plan=plan) as lc:
                coord = lc.coordinator
                coord.enable_resilience(breaker_threshold=1,
                                        breaker_open_ms=60000.0,
                                        hedge=False)
                planes = lc.enable_health(
                    clock=clock, interval_ms=100.0, objectives=objectives,
                    slo_fast_window_s=60.0, slo_slow_window_s=600.0,
                    fast_burn_alert=10.0, min_events=5,
                    flight_cooldown_s=0.5)
                coord.create_index("health")
                coord.create_field("health", "f")
                for s in range(8):
                    coord.import_bits("health", "f", rows=[1],
                                      cols=[s * SHARD_WIDTH + 1])
                peers = [n for n in lc.nodes if n is not coord]
                snap = coord.snapshot()
                owners = {snap.primary_shard_node("health", s).id
                          for s in range(8)}
                assert any(p.node.id in owners for p in peers), \
                    "fixture regression: every shard landed on the coord"

                # phase 1 — injected slow peers: every fan-out query
                # pays >=50ms, blowing the 10ms latency objective
                for p in peers:
                    plan.delay(p.node.id, 0.05, op="query")
                for _ in range(8):
                    clock.advance(0.2)
                    coord.query("health", "Count(Row(f=1))")

                hp = coord.health
                assert hp.slo.status()["alerting"] == ["query-latency"]
                burn_bundles = [b for b in hp.flight.bundles()
                                if b["trigger"] == "slo_fast_burn"]
                assert burn_bundles, "fast burn never fired the recorder"

                # the cluster merge sees all three nodes (op="stats"
                # legs are NOT delayed — the rules scope to op="query")
                for plane in planes[1:]:
                    plane.timeline.sample()
                stats = coord.cluster_stats(window_s=600.0)
                ids = {n.id for n in coord.snapshot().nodes}
                assert set(stats["nodes"]) == ids and len(ids) == 3
                assert all(tl.get("enabled") for tl in
                           stats["nodes"].values())
                assert stats["cluster"]["nodes_reporting"] == 3

                # ... and over real HTTP on the coordinator
                base = coord.node.uri
                http_stats = _get_json(
                    base + "/internal/stats/cluster?window=600")
                assert set(http_stats["nodes"]) == ids
                http_slo = _get_json(base + "/internal/slo")
                assert http_slo["alerting"] == ["query-latency"]
                tl = _get_json(
                    base + "/internal/stats/timeline?window=600")
                assert tl["enabled"] and len(tl["samples"]) >= 1
                # cluster-path queries bypass api.history; seed two
                # records directly to exercise the ?n= serve limit
                for q in ("Count(Row(f=1))", "Count(Row(f=2))"):
                    coord.api.history.end(
                        coord.api.history.begin("health", q, "pql"))
                hist = _get_json(base + "/query-history?n=1")
                assert len(hist) == 1
                assert hist[0]["query"] == "Count(Row(f=2))"

                # phase 2 — drop a shard-owning peer: breaker opens,
                # the transition lands in the event ring, the next
                # sample captures a breaker_open bundle
                victim = next(p for p in peers if p.node.id in owners)
                clock.advance(1.0)
                plan.clear(victim.node.id)
                plan.drop(victim.node.id,
                          first=plan.seen(victim.node.id), op="query")
                with pytest.raises(Exception):
                    coord.query("health", "Count(Row(f=1))")
                assert coord.resilience.breaker.state(
                    victim.node.id) == "open"
                breaker_bundles = [b for b in hp.flight.bundles()
                                   if b["trigger"] == "breaker_open"]
                assert breaker_bundles, "breaker open never captured"
                bundle = breaker_bundles[-1]

                # bundle completeness: timeline window, the breaker
                # transition, and >=1 slow trace that resolves over
                # /internal/traces/{id}
                assert len(bundle["timeline"]) >= 1
                transitions = [e for e in bundle["events"]
                               if e["kind"] == "breaker"
                               and e["to"] == "open"
                               and e["node"] == victim.node.id]
                assert transitions
                assert len(bundle["slow_traces"]) >= 1
                tid = bundle["slow_traces"][0]["traceID"]
                trace = _get_json(base + f"/internal/traces/{tid}")
                assert trace["traceID"] == tid

                # the bundle itself serves over HTTP
                listing = _get_json(base + "/internal/debug/bundles")
                assert bundle["id"] in [b["id"] for b in
                                        listing["bundles"]]
                served = _get_json(
                    base + f"/internal/debug/bundles/{bundle['id']}")
                assert served["trigger"] == "breaker_open"
                with pytest.raises(urllib.error.HTTPError):
                    _get_json(base + "/internal/debug/bundles/fb-nope")
        finally:
            T.set_tracer(prev)
            M.REGISTRY.reset()
