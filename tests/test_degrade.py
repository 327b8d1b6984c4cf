"""Graceful-degradation (brownout) control plane (sched/degrade.py).

Unit-level: the hysteresis ladder (escalation jumps, one-rung recovery,
hold streaks, dwell), shed ordering (batch from SHED_BATCH, interactive
only at SATURATED), honest Retry-After propagation, and deadline
tightening. Integration-level: scheduler admission sheds, brownout
stale-serving through the result cache with the ``stale=true`` response
tag, the bulk-import ingress shed, and the PILOSA_TPU_DEGRADE=0
zero-cost-off contract.
"""

import pytest

from pilosa_tpu.api import API
from pilosa_tpu.errors import AdmissionError
from pilosa_tpu.obs.metrics import (METRIC_DEGRADE_STATE,
                                    METRIC_DEGRADE_TRANSITIONS,
                                    MetricsRegistry)
from pilosa_tpu.sched.degrade import (BROWNOUT, NORMAL, SATURATED,
                                      SHED_BATCH, DegradeController)


def sample(t, queue_frac=0.0, burn=0.0, rates=None):
    """One synthetic timeline sample shaped like HealthPlane's."""
    mq = 100.0
    return {
        "t": t,
        "probes": {
            "scheduler": {"max_queue": mq,
                          "queue_depth": queue_frac * mq,
                          "inflight_admits": 0},
            "slo": {"max_fast_burn": burn},
        },
        "rates": dict(rates or {}),
    }


def controller(**kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("min_dwell_s", 0.0)
    kw.setdefault("up_hold", 1)
    kw.setdefault("down_hold", 1)
    return DegradeController(**kw)


class TestLadderHysteresis:
    def test_escalation_jumps_recovery_steps(self):
        deg = controller()
        deg.observe(sample(0.0, queue_frac=0.99))
        assert deg.level == SATURATED  # escalation may jump rungs
        levels = []
        for i in range(1, 5):
            deg.observe(sample(float(i), queue_frac=0.0))
            levels.append(deg.level)
        # recovery is deliberate: one rung per qualifying sample
        assert levels == [BROWNOUT, SHED_BATCH, NORMAL, NORMAL]

    def test_up_hold_requires_consecutive_samples(self):
        deg = controller(up_hold=2)
        deg.observe(sample(0.0, queue_frac=0.99))
        assert deg.level == NORMAL  # one hot sample is not enough
        deg.observe(sample(0.1, queue_frac=0.0))  # streak broken
        deg.observe(sample(0.2, queue_frac=0.99))
        assert deg.level == NORMAL
        deg.observe(sample(0.3, queue_frac=0.99))  # second consecutive
        assert deg.level == SATURATED

    def test_down_hold_and_exit_band(self):
        deg = controller(queue_shed=0.5, exit_ratio=0.7, down_hold=2)
        deg.observe(sample(0.0, queue_frac=0.6))
        assert deg.level == SHED_BATCH
        # inside the hysteresis band (exit edge 0.35 <= q < 0.5):
        # neither escalation nor recovery, and streaks reset
        for i in range(1, 6):
            deg.observe(sample(float(i), queue_frac=0.4))
            assert deg.level == SHED_BATCH
        deg.observe(sample(6.0, queue_frac=0.1))
        assert deg.level == SHED_BATCH  # down_hold=2: first sample holds
        deg.observe(sample(7.0, queue_frac=0.1))
        assert deg.level == NORMAL

    def test_min_dwell_blocks_flapping(self):
        deg = controller(min_dwell_s=1.0, down_hold=1)
        deg.observe(sample(0.0, queue_frac=0.99))
        assert deg.level == SATURATED
        deg.observe(sample(0.5, queue_frac=0.0))  # too soon to move
        assert deg.level == SATURATED
        deg.observe(sample(1.5, queue_frac=0.0))
        assert deg.level == BROWNOUT

    def test_burn_and_aux_signals_drive_ladder(self):
        deg = controller(burn_shed=2.0, burn_brownout=6.0,
                         burn_saturate=14.0)
        deg.observe(sample(0.0, burn=7.0))
        assert deg.level == BROWNOUT
        deg.reset()
        # deadline-miss rate is a BROWNOUT signal, evictions a
        # SHED_BATCH signal; both arrive via the counter-delta map
        deg2 = controller(miss_rate_brownout=1.0)
        deg2.observe(
            sample(0.0, rates={"sched_deadline_missed_total": 2.0}))
        assert deg2.level == BROWNOUT
        deg3 = controller(eviction_rate_shed=5.0)
        deg3.observe(
            sample(0.0, rates={"device_budget_evictions_total": 9.0}))
        assert deg3.level == SHED_BATCH

    def test_transitions_are_metered_and_recorded(self):
        reg = MetricsRegistry()
        deg = controller(registry=reg)

        class FakeFlight:
            def __init__(self):
                self.events = []
                self.triggers = []

            def record_event(self, kind, **info):
                self.events.append((kind, info))

            def trigger(self, name, reason, sample=None):
                self.triggers.append((name, reason))

        deg.flight = fl = FakeFlight()
        deg.observe(sample(0.0, queue_frac=0.99))
        deg.observe(sample(1.0))
        assert deg.probe()["transitions"] == 2
        assert [k for k, _ in fl.events] == ["degrade_transition"] * 2
        assert fl.triggers and fl.triggers[0][0] == "degrade_escalation"
        text = reg.prometheus_text()
        assert METRIC_DEGRADE_STATE in text
        assert METRIC_DEGRADE_TRANSITIONS in text


class TestShedContract:
    def test_shed_order_batch_before_interactive(self):
        deg = controller()
        assert deg.shed_reason("batch") is None
        deg._level = SHED_BATCH
        assert deg.shed_reason("batch") == "degrade_shed_batch"
        assert deg.shed_reason("interactive") is None
        deg._level = BROWNOUT
        assert deg.shed_reason("interactive") is None
        deg._level = SATURATED
        assert deg.shed_reason("batch") == "degrade_shed_batch"
        assert deg.shed_reason("interactive") == "degrade_saturated"

    def test_shed_carries_live_retry_after(self):
        deg = controller(retry_after_s=2.5)
        deg._level = SATURATED
        err = deg.shed("interactive")
        assert isinstance(err, AdmissionError)
        assert err.retry_after_s == 2.5  # static default until wired
        deg.retry_after_fn = lambda: 0.75
        assert deg.shed("batch").retry_after_s == 0.75
        assert deg.shed("batch", retry_after_s=0.2).retry_after_s == 0.2

    def test_tighten_deadline_only_at_brownout(self):
        deg = controller(deadline_factor=0.5, brownout_deadline_ms=250.0)
        assert deg.tighten_deadline(1.0) == 1.0
        deg._level = BROWNOUT
        assert deg.tighten_deadline(1.0) == 0.5
        assert deg.tighten_deadline(0.0) == 0.25  # imposed default


class TestSchedulerIntegration:
    @pytest.fixture
    def api(self):
        a = API()
        a.create_index("i")
        a.create_field("i", "f")
        a.import_bits("i", "f", rows=[1, 1, 2], cols=[1, 2, 3])
        a.enable_scheduler()
        yield a
        a.disable_scheduler()

    def test_admission_sheds_in_ladder_order(self, api):
        deg = api.enable_degrade(min_dwell_s=0.0)
        deg._level = SHED_BATCH
        with pytest.raises(AdmissionError) as ei:
            with api.scheduler.admit(priority="batch"):
                pass
        assert ei.value.retry_after_s > 0
        assert "batch" in str(ei.value)
        # interactive flows at SHED_BATCH, sheds only at SATURATED
        assert api.query_json("i", "Count(Row(f=1))")["results"] == [2]
        deg._level = SATURATED
        with pytest.raises(AdmissionError):
            api.query_json("i", "Count(Row(f=1))")

    def test_import_ingress_shed_helper(self, api):
        deg = api.enable_degrade()
        api._degrade_shed_batch()  # NORMAL: no-op
        deg._level = SHED_BATCH
        with pytest.raises(AdmissionError):
            api._degrade_shed_batch()
        # direct import_bits is NOT shed: SQL DML, WAL replay, and
        # fan-out legs must never be torn mid-statement
        assert api.import_bits("i", "f", rows=[3], cols=[9]) == 1

    def test_zero_cost_off(self, api):
        api.disable_degrade()  # under the PILOSA_TPU_DEGRADE=1 lane
        assert api.degrade is None
        reg = api.scheduler.registry

        def degrade_lines():
            # the registry is process-global: other tests may have moved
            # degrade metrics, so zero-cost means NO MOVEMENT, not absence
            return [line for line in reg.prometheus_text().splitlines()
                    if "degrade_" in line]

        before = degrade_lines()
        with api.scheduler.admit(priority="batch"):
            pass
        assert api.query_json("i", "Count(Row(f=2))")["results"] == [1]
        assert degrade_lines() == before

    def test_env_auto_enable(self, monkeypatch):
        monkeypatch.setenv("PILOSA_TPU_DEGRADE", "1")
        a = API()
        try:
            assert a.degrade is not None
            assert a.degrade.level == NORMAL
        finally:
            a.disable_scheduler()


class TestBrownoutStaleServing:
    def test_stale_serve_is_tagged_and_recovers(self):
        api = API()
        try:
            api.create_index("i")
            api.create_field("i", "f")
            api.import_bits("i", "f", rows=[1, 1], cols=[1, 2])
            api.enable_cache()
            deg = api.enable_degrade()
            q = "Count(Row(f=1))"
            assert api.query_json("i", q) == {"results": [2]}
            # the write moves the version fingerprint: the cached entry
            # is now stale-by-version, not expired
            api.import_bits("i", "f", rows=[1], cols=[3])
            fresh = api.query_json("i", q)
            assert fresh == {"results": [3]}
            api.import_bits("i", "f", rows=[1], cols=[4])
            deg._level = BROWNOUT
            browned = api.query_json("i", q)
            assert browned["results"] == [3]  # previous answer
            assert browned["stale"] is True
            assert api.cache.stats()["stale_serves"] == 1
            # recovery: fresh execution again, no stale tag
            deg.reset()
            recovered = api.query_json("i", q)
            assert recovered == {"results": [4]}
        finally:
            api.disable_cache()

    def test_stale_disabled_for_remote_legs(self):
        from pilosa_tpu.cache.result_cache import ResultCache

        cache = ResultCache(registry=MetricsRegistry())
        deg = controller()
        deg._level = BROWNOUT
        cache.degrade = deg
        key = ("q", "i", "fp1")
        cache.run(key, lambda: [1])
        moved = ("q", "i", "fp2")
        # client-facing leg: stale predecessor served and flagged
        hit, value = cache.lookup(moved)
        assert (hit, value) == (True, [1])
        assert cache.take_stale_flag() is True
        # remote-serving leg: allow_stale=False never serves stale
        hit, _ = cache.lookup(moved, allow_stale=False)
        assert hit is False
        assert cache.take_stale_flag() is False
