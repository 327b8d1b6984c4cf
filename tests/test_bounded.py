"""Bounded-structure churn audit (ISSUE 19 satellite).

Every per-tenant / per-key table in the serving path has a hard cap so
a hostile or merely huge ID stream cannot grow resident state without
bound. This suite churns 10^5 distinct IDs (or enough distinct keys to
overflow the smaller module-level caches several times over) through
each structure and asserts the cap held, the overflow path engaged,
and the structure still answers sanely afterwards.
"""

import pytest

from pilosa_tpu.cache.result_cache import ResultCache
from pilosa_tpu.errors import QuotaExceededError
from pilosa_tpu.obs.flight import FlightRecorder
from pilosa_tpu.obs.metrics import MetricsRegistry
from pilosa_tpu.obs.slo import SLOTracker
from pilosa_tpu.obs.tenants import OVERFLOW_TENANT, TenantRegistry
from pilosa_tpu.obs.tracing import Span, TraceStore
from pilosa_tpu.sched import ManualClock, QueryScheduler
from pilosa_tpu.sched.scheduler import _Pending

CHURN = 100_000


def tenant_ids(n=CHURN):
    """``n`` distinct tenant IDs, generated and never held as a list."""
    return (f"t{i:07d}" for i in range(n))


class TestTenantRegistryChurn:
    def test_stats_table_caps_at_max_tracked(self):
        reg = TenantRegistry(max_tracked=64, registry=MetricsRegistry())
        for tid in tenant_ids():
            reg.note(tid, queries=1)
        # tracked cells + the single overflow cell, never one more
        assert len(reg._stats) <= reg.max_tracked + 1
        assert OVERFLOW_TENANT in reg._stats
        assert reg._dropped > 0
        # the overflow cell absorbed everything past the cap
        overflow = reg._stats[OVERFLOW_TENANT]
        assert overflow.queries >= CHURN - reg.max_tracked
        # the registry still publishes a sane snapshot afterwards
        snap = reg.stats_json()
        assert snap["tracked"] <= reg.max_tracked + 1
        assert snap["dropped"] == reg._dropped
        assert OVERFLOW_TENANT in snap["tenants"]

    def test_token_bucket_tables_stay_bounded(self):
        clock = ManualClock()
        reg = TenantRegistry(max_tracked=16, default_qps=1e9,
                             default_ingest_rows_s=1e9,
                             clock=clock.now, registry=MetricsRegistry())
        for tid in tenant_ids():
            reg.charge_query(tid)
            reg.charge_ingest(tid, rows=1)
        # hostile-ID bound: the tables clear past 4x max_tracked, so
        # they can never hold more than that plus the current insert
        assert len(reg._qps) <= 4 * reg.max_tracked + 1
        assert len(reg._ingest) <= 4 * reg.max_tracked + 1
        # quotas still enforce after the churn
        tight = TenantRegistry(max_tracked=16, default_qps=1.0,
                               clock=clock.now,
                               registry=MetricsRegistry())
        tight.charge_query("t0")
        with pytest.raises(QuotaExceededError) as ei:
            for _ in range(64):
                tight.charge_query("t0")
        assert ei.value.retry_after_s > 0


class TestSLOTenantChurn:
    def test_tenant_dimension_caps_with_overflow_cell(self):
        clock = ManualClock()
        tracker = SLOTracker(clock=clock, registry=MetricsRegistry())
        for tid in tenant_ids():
            tracker.record("query", 1.0, tenant=tid)
        # the set holds at most cap distinct IDs plus "__other__"
        assert len(tracker._tenant_ids) <= tracker.tenant_cap + 1
        assert "__other__" in tracker._tenant_ids
        rows = tracker.tenant_burn_rates()
        assert len({r["tenant"] for r in rows}) <= tracker.tenant_cap + 1


class TestSchedulerVtimeChurn:
    def test_vtime_table_clears_past_bound(self):
        from pilosa_tpu.pql.parser import parse

        sched = QueryScheduler(executor=object(), fair_share=True)
        q = parse("Count(Row(f=1))")
        for i, tid in enumerate(tenant_ids()):
            p = _Pending("i", q, None, "interactive", None, 0.0, i)
            p.tenant = tid
            sched._assign_vtime_locked(p)
            assert len(sched._tenant_vtime) <= 256
            # the vclock floor keeps post-clear vtimes monotone
            assert p.vtime >= sched._vclock


class TestTraceStoreChurn:
    def test_trace_store_evicts_oldest(self):
        reg = MetricsRegistry()
        store = TraceStore(capacity=64, registry=reg)
        last_ids = []
        for i in range(10_000):
            root = Span(f"q{i}")
            root.duration_s = 0.001
            store.add(root)
            last_ids.append(root.trace_id)
        assert len(store._traces) <= store.capacity
        # newest-first listing survives, oldest got evicted
        listed = {d["traceID"] for d in store.list()}
        assert listed == set(last_ids[-64:])


class TestFlightChurn:
    def test_event_ring_and_bundle_ring_bounded(self):
        clock = ManualClock()
        fl = FlightRecorder(capacity=4, cooldown_s=0.0,
                            registry=MetricsRegistry(), clock=clock)
        for i in range(CHURN):
            fl.record_event("churn", i=i)
        assert len(fl.events()) <= 64
        for i in range(100):
            clock.advance(1.0)
            fl.trigger(f"t{i % 8}", "churn")
        assert len(fl.summaries()) <= 4


class TestResultCacheChurn:
    def test_entry_and_byte_caps_hold(self):
        cache = ResultCache(max_entries=64, max_bytes=1 << 20,
                            registry=MetricsRegistry())
        for i in range(CHURN):
            out = cache.run(("q", i), lambda i=i: [i])
            assert out == [i]
        st = cache.stats()
        assert st["entries"] <= 64
        assert st["bytes"] <= 1 << 20
        assert st["evictions"] > 0
        # the cache still serves hits after the churn
        key = ("q", CHURN - 1)
        assert cache.run(key, lambda: ["recomputed"]) == [CHURN - 1]


class TestModuleLevelCaps:
    def test_device_zeros_cap(self):
        from pilosa_tpu.ops import bitmap as B

        planes = [B.device_zeros(8 * (i + 1)) for i in range(40)]
        assert len(B._DEVICE_ZEROS) <= B._DEVICE_ZEROS_CAP
        assert planes[-1].shape == (8 * 40,)

    def test_program_cache_cap(self, monkeypatch):
        from pilosa_tpu.parallel import mesh
        from pilosa_tpu.pql import programs as P

        # stub the compiler: this audits the cache's bound, not XLA
        monkeypatch.setattr(mesh, "compile_tape_plane",
                            lambda tape, masked: ("fn", tape))
        for i in range(P._PROGRAMS_CAP + 40):
            fn = P._program("plane", (("leaf", i),), 1, False, 8)
            assert fn == ("fn", (("leaf", i),))
        assert P.program_cache_len() <= P._PROGRAMS_CAP

    def test_mask_plane_cap(self, monkeypatch):
        from pilosa_tpu.pql import executor as X

        # stub device upload: this audits the LRU bound, not staging
        monkeypatch.setattr("pilosa_tpu.parallel.mesh.engine_put",
                            lambda plane: plane)
        for i in range(X._MASK_CAP + 20):
            X._mask_plane((i,), (i,))
        assert len(X._MASK_PLANES) <= X._MASK_CAP
