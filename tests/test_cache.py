"""Version-keyed result cache with single-flight dedup (pilosa_tpu/cache/).

Invalidation is structural — fragment versions live inside the key — so
every test here asserts on *dispatch counts* (via instance-level spies
on Executor._execute_query) plus result correctness: a stale hit would
show up as a wrong count, a missed invalidation as a skipped dispatch.

``TestHashSeed`` builds the same keys in two interpreters under
PYTHONHASHSEED=0 and =1; scripts/tier1.sh also runs the whole module
under both, to catch hash-order-dependent key bugs.
"""

import json
import os
import subprocess
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from pilosa_tpu.api import API
from pilosa_tpu.cache import ResultCache, estimate_cost, is_cacheable, \
    query_cache_key, shard_key, version_fingerprint
from pilosa_tpu.config import Config
from pilosa_tpu.core.fragment import _DELTA_MAX_COLS, _DELTA_MAX_OPS, \
    _DeltaLog
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.obs.metrics import MetricsRegistry
from pilosa_tpu.pql.parser import parse
from pilosa_tpu.sched.batch import group_key
from pilosa_tpu.shardwidth import SHARD_WIDTH


def spy_dispatches(executor):
    """Count real kernel dispatches by wrapping _execute_query on the
    instance — both the direct and the cached read path funnel there."""
    calls = []
    orig = executor._execute_query

    def wrapper(idx, query, shards):
        calls.append((query.to_pql(), shards))
        return orig(idx, query, shards)

    executor._execute_query = wrapper
    return calls


@pytest.fixture
def api():
    a = API()
    yield a
    a.disable_scheduler()


def seed_two_shards(api, index="i"):
    """f=1 set on one column in shard 0 and one in shard 1."""
    api.create_index(index)
    api.create_field(index, "f")
    api.import_bits(index, "f", rows=[1, 1], cols=[1, SHARD_WIDTH + 1])


# -- key construction ------------------------------------------------------


class TestShardKey:
    def test_canonicalizes_sorted_tuple(self):
        assert shard_key([2, 1, 3]) == (1, 2, 3)
        assert shard_key((3, 1)) == shard_key([1, 3])

    def test_none_without_expansion_stays_none(self):
        assert shard_key(None) is None

    def test_none_expands_to_all_shards(self):
        assert shard_key(None, all_shards={4, 0, 2}) == (0, 2, 4)

    def test_group_key_uses_same_canonicalization(self):
        q = parse("Count(Row(f=1))")
        assert group_key("i", q, [2, 1]).shards == shard_key([1, 2])
        assert group_key("i", q).shards == shard_key(None)


class TestQueryKey:
    def test_writes_and_external_lookups_uncacheable(self):
        assert not is_cacheable(parse("Count(Row(f=1))Set(1, f=2)"))
        assert is_cacheable(parse("Count(Row(f=1))"))

    def test_options_shards_override_uncacheable(self):
        assert not is_cacheable(parse("Options(Row(f=1), shards=[0])"))
        assert is_cacheable(parse("Options(Row(f=1))"))

    def test_fingerprint_tracks_writes_per_shard(self, api):
        seed_two_shards(api)
        idx = api.holder.index("i")
        fp0 = version_fingerprint(idx, [0])
        fp1 = version_fingerprint(idx, [1])
        fp_all = version_fingerprint(idx, [0, 1])
        api.query("i", "Set(2, f=1)")  # shard-0 write
        assert version_fingerprint(idx, [0]) != fp0
        assert version_fingerprint(idx, [0, 1]) != fp_all
        assert version_fingerprint(idx, [1]) == fp1

    def test_key_changes_with_pql_shards_and_versions(self, api):
        seed_two_shards(api)
        idx = api.holder.index("i")
        q = parse("Count(Row(f=1))")
        k = query_cache_key(idx, q, [0, 1])
        assert k == query_cache_key(idx, q, [1, 0])
        assert k != query_cache_key(idx, q, [0])
        assert k != query_cache_key(idx, parse("Count(Row(f=2))"), [0, 1])
        assert k != query_cache_key(idx, q, [0, 1], namespace="remote")
        api.query("i", "Set(2, f=1)")
        assert k != query_cache_key(idx, q, [0, 1])


# Run by TestHashSeed in a fresh interpreter: field names, views, shards
# and keyed rows arrive in an order no sort would leave them in.
_KEYS_SCRIPT = """
import json
from pilosa_tpu.api import API
from pilosa_tpu.cache import query_cache_key, version_fingerprint
from pilosa_tpu.pql.parser import parse
from pilosa_tpu.shardwidth import SHARD_WIDTH

api = API()
api.create_index("i")
for name in ("zeta", "alpha", "mu"):
    api.create_field("i", name)
api.create_field("i", "seg", {"type": "set", "keys": True})
api.create_field("i", "when", {"type": "time", "timeQuantum": "YMD"})
api.create_field("i", "amount", {"type": "int"})
cols = [2 * SHARD_WIDTH + 5, 3, SHARD_WIDTH + 9]
for name in ("mu", "zeta", "alpha"):
    api.import_bits("i", name, rows=[2, 1, 7], cols=cols)
api.import_bits("i", "seg", row_keys=["pear", "apple", "fig"], cols=cols)
api.query("i", "Set(3, when=4, 2020-03-01T00:00)"
               "Set(%d, when=4, 2019-11-30T00:00)" % (SHARD_WIDTH + 9))
api.import_values("i", "amount", cols=cols, values=[-4, 11, 0])
idx = api.holder.index("i")
q = parse("Count(Intersect(Row(zeta=1), Row(alpha=2), Row(seg='fig')))")
print(json.dumps({
    "version_fingerprint": repr(version_fingerprint(idx, [2, 0, 1])),
    "query_cache_key": repr(query_cache_key(idx, q, [2, 0, 1])),
}))
"""


class TestHashSeed:
    @pytest.fixture(scope="class")
    def keys_by_seed(self):
        """{"0": {...}, "1": {...}}: both keys' ``repr`` as a fresh
        interpreter under each PYTHONHASHSEED builds them."""
        out = {}
        for seed in ("0", "1"):
            done = subprocess.run(
                [sys.executable, "-c", _KEYS_SCRIPT],
                env=dict(os.environ, PYTHONHASHSEED=seed),
                capture_output=True, text=True, timeout=120)
            assert done.returncode == 0, done.stderr[-2000:]
            out[seed] = json.loads(done.stdout.splitlines()[-1])
        return out

    @pytest.mark.parametrize("key", ["version_fingerprint",
                                     "query_cache_key"])
    def test_key_does_not_depend_on_hash_seed(self, keys_by_seed, key):
        assert "zeta" in keys_by_seed["0"][key]
        assert keys_by_seed["0"][key] == keys_by_seed["1"][key]


# -- ResultCache unit ------------------------------------------------------


class TestResultCacheUnit:
    def test_roundtrip_and_copy_isolation(self):
        c = ResultCache(registry=MetricsRegistry())
        c.insert(("k",), [1, [2, 3]])
        hit, v = c.lookup(("k",))
        assert hit and v == [1, [2, 3]]
        v[1].append(99)  # caller mutation must not leak into the cache
        assert c.lookup(("k",))[1] == [1, [2, 3]]

    def test_entry_bound_evicts_lru(self):
        r = MetricsRegistry()
        c = ResultCache(max_entries=2, registry=r)
        c.insert(("a",), 1)
        c.insert(("b",), 2)
        assert c.lookup(("a",))[0]  # 'a' is now most-recent
        c.insert(("c",), 3)
        assert not c.lookup(("b",))[0]
        assert c.lookup(("a",))[0] and c.lookup(("c",))[0]
        assert r.value(M.METRIC_CACHE_EVICTIONS, reason="entries") == 1

    def test_byte_bound_evicts_and_rejects_oversize(self):
        r = MetricsRegistry()
        cost = estimate_cost("x" * 100)
        c = ResultCache(max_bytes=int(cost * 2.5), registry=r)
        c.insert(("a",), "x" * 100)
        c.insert(("b",), "x" * 100)
        c.insert(("c",), "x" * 100)  # evicts 'a' (LRU) to fit
        assert not c.lookup(("a",))[0]
        assert c.stats()["bytes"] <= int(cost * 2.5)
        assert r.value(M.METRIC_CACHE_EVICTIONS, reason="bytes") >= 1
        c.insert(("huge",), "x" * 1000)  # larger than the whole budget
        assert not c.lookup(("huge",))[0]

    def test_ttl_with_injected_clock(self):
        now = [0.0]
        c = ResultCache(ttl_ms=100, clock=lambda: now[0],
                        registry=MetricsRegistry())
        c.insert(("k",), 1)
        assert c.lookup(("k",))[0]
        now[0] = 0.099
        assert c.lookup(("k",))[0]
        now[0] = 0.101
        assert not c.lookup(("k",))[0]
        assert c.stats()["entries"] == 0

    def test_flush_and_stats(self):
        r = MetricsRegistry()
        c = ResultCache(registry=r)
        c.insert(("a",), 1)
        c.insert(("b",), 2)
        assert c.flush() == 2
        s = c.stats()
        assert s["entries"] == 0 and s["bytes"] == 0
        assert s["evictions"] == 2
        assert r.value(M.METRIC_CACHE_EVICTIONS, reason="flush") == 2
        assert r.value(M.METRIC_CACHE_ENTRIES) == 0

    def test_run_single_flight_one_compute(self):
        c = ResultCache(registry=MetricsRegistry())
        computes = []
        entered = threading.Event()
        release = threading.Event()

        def compute():
            computes.append(1)
            entered.set()
            release.wait(5)
            return {"v": 42}

        with ThreadPoolExecutor(max_workers=8) as pool:
            futs = [pool.submit(c.run, ("k",), compute) for _ in range(8)]
            entered.wait(5)  # leader inside compute; rest are followers/hits
            release.set()
            out = [f.result() for f in futs]
        assert len(computes) == 1
        assert all(o == {"v": 42} for o in out)
        # followers got copies, not the shared object
        assert len({id(o) for o in out}) == len(out)

    def test_run_failure_propagates_and_caches_nothing(self):
        c = ResultCache(registry=MetricsRegistry())

        def boom():
            raise RuntimeError("dispatch failed")

        with pytest.raises(RuntimeError):
            c.run(("k",), boom)
        assert c.stats()["inflight"] == 0
        # next attempt retries (and can succeed)
        assert c.run(("k",), lambda: 7) == 7


# -- executor wiring -------------------------------------------------------


class TestExecutorCache:
    def test_warm_hit_skips_dispatch(self, api):
        seed_two_shards(api)
        api.enable_cache(registry=MetricsRegistry())
        calls = spy_dispatches(api.executor)
        assert api.query("i", "Count(Row(f=1))") == [2]
        assert api.query("i", "Count(Row(f=1))") == [2]
        assert len(calls) == 1

    def test_write_invalidation_interleaved_across_shards(self, api):
        """Deterministic write/read interleaving: a shard-0 write must
        invalidate the shard-0 and all-shards entries but leave the
        shard-1 entry hot."""
        seed_two_shards(api)
        api.enable_cache(registry=MetricsRegistry())
        ex = api.executor
        calls = spy_dispatches(ex)
        q = "Count(Row(f=1))"
        assert ex.execute("i", q, shards=[0]) == [1]
        assert ex.execute("i", q, shards=[1]) == [1]
        assert ex.execute("i", q) == [2]
        assert len(calls) == 3
        api.query("i", "Set(2, f=1)")  # shard-0 write (1 dispatch)
        assert len(calls) == 4
        assert ex.execute("i", q, shards=[1]) == [1]  # still cached
        assert len(calls) == 4
        assert ex.execute("i", q, shards=[0]) == [2]  # re-dispatched
        assert ex.execute("i", q) == [3]
        assert len(calls) == 6
        # second round of writes, reading between each
        api.query("i", f"Set({SHARD_WIDTH + 2}, f=1)")  # shard-1 write
        assert ex.execute("i", q, shards=[0]) == [2]  # shard 0 stays hot
        assert ex.execute("i", q, shards=[1]) == [2]
        assert ex.execute("i", q) == [4]
        assert len(calls) == 9  # +1 write, +2 invalidated reads

    def test_execute_many_fills_and_hits(self, api):
        seed_two_shards(api)
        api.enable_cache(registry=MetricsRegistry())
        ex = api.executor
        fused = []
        orig = ex._execute_many

        def spy(idx, qs, shards):
            fused.append([q.to_pql() for q in qs])
            return orig(idx, qs, shards)

        ex._execute_many = spy
        calls = spy_dispatches(ex)
        qs = ["Count(Row(f=1))", "Row(f=1)"]
        first = ex.execute_many("i", qs)
        assert first[0] == [2]
        assert fused == [qs]  # whole batch was one fused dispatch
        assert ex.execute_many("i", qs) == first
        assert ex.execute("i", qs[0]) == [2]  # entry shared with execute
        assert fused == [qs] and calls == []

    def test_uncacheable_query_bypasses(self, api):
        seed_two_shards(api)
        reg = MetricsRegistry()
        api.enable_cache(registry=reg)
        calls = spy_dispatches(api.executor)
        q = "Options(Row(f=1), shards=[0])"
        r1 = api.query("i", q)
        r2 = api.query("i", q)
        assert r1 == r2
        assert len(calls) == 2  # never cached
        assert reg.value(M.METRIC_CACHE_BYPASS) == 2
        assert reg.value(M.METRIC_CACHE_HITS) == 0

    def test_disabled_cache_makes_zero_cache_calls(self, api):
        """cache.enabled=false must be byte-identical: after
        disable_cache, the read path touches no cache machinery at all
        (spy counts every entry point)."""
        seed_two_shards(api)

        class SpyCache(ResultCache):
            ops = []

            def lookup(self, *a, **k):
                self.ops.append("lookup")
                return super().lookup(*a, **k)

            def fetch(self, *a, **k):
                self.ops.append("fetch")
                return super().fetch(*a, **k)

            def insert(self, *a, **k):
                self.ops.append("insert")
                return super().insert(*a, **k)

            def run(self, *a, **k):
                self.ops.append("run")
                return super().run(*a, **k)

            def bypass(self, *a, **k):
                self.ops.append("bypass")
                return super().bypass(*a, **k)

        spy = SpyCache(registry=MetricsRegistry())
        api.cache = spy
        api.executor.cache = spy
        api.query("i", "Count(Row(f=1))")
        assert spy.ops  # enabled path does consult the cache
        api.disable_cache()
        assert api.executor.cache is None
        spy.ops.clear()
        assert api.query("i", "Count(Row(f=1))") == [2]
        api.executor.execute_many("i", ["Count(Row(f=1))"])
        assert spy.ops == []

    def test_single_flight_n_concurrent_cold_queries_one_dispatch(self, api):
        seed_two_shards(api)
        api.enable_cache(registry=MetricsRegistry())
        ex = api.executor
        dispatches = []
        entered = threading.Event()
        release = threading.Event()
        orig = ex._execute_read

        def slow_read(idx, query, shards):
            dispatches.append(query.to_pql())
            entered.set()
            release.wait(5)  # hold the leader so others pile up
            return orig(idx, query, shards)

        ex._execute_read = slow_read
        with ThreadPoolExecutor(max_workers=8) as pool:
            futs = [pool.submit(ex.execute, "i", "Count(Row(f=1))")
                    for _ in range(8)]
            entered.wait(5)
            release.set()
            out = [f.result() for f in futs]
        assert dispatches == ["Count(Row(f=1))"]  # exactly one
        assert out == [[2]] * 8


# -- scheduler integration -------------------------------------------------


class TestSchedulerCache:
    def test_hit_resolves_immediately_without_queueing(self, api):
        seed_two_shards(api)
        api.enable_scheduler(window_ms=0, registry=MetricsRegistry())
        api.enable_cache(registry=MetricsRegistry())
        sched = api.scheduler
        # warm through the scheduled path, then freeze the worker: a hit
        # must complete with the worker paused and the queue untouched
        assert api.query("i", "Count(Row(f=1))") == [2]
        sched.pause()
        sq = sched.submit("i", "Count(Row(f=1))")
        assert sq.done()
        assert sq.result(timeout=0) == [2]
        assert sched.queue_depth() == 0
        sched.resume()

    def test_scheduled_miss_populates_cache(self, api):
        seed_two_shards(api)
        api.enable_scheduler(window_ms=0, registry=MetricsRegistry())
        api.enable_cache(registry=MetricsRegistry())
        calls = spy_dispatches(api.executor)
        assert api.query("i", "Count(Row(f=1))") == [2]
        assert api.query("i", "Count(Row(f=1))") == [2]
        assert len(calls) == 1

    def test_stub_executors_unaffected(self):
        """Schedulers over plain stub executors (no cache attribute
        machinery) keep working — the fast-path is strictly optional."""
        from pilosa_tpu.sched import QueryScheduler

        class Stub:
            def execute(self, index, query, shards=None):
                return [c.to_pql() for c in query.calls]

        s = QueryScheduler(Stub(), window_ms=0,
                           registry=MetricsRegistry())
        try:
            assert s.execute("i", "Count(Row(f=1))") == ["Count(Row(f=1))"]
        finally:
            s.close()


# -- SQL SELECT path -------------------------------------------------------


class TestSQLCache:
    def test_select_hits_then_invalidates_on_insert(self, api):
        api.sql("create table t (_id id, v int)")
        api.sql("insert into t values (1, 5), (2, 9)")
        api.enable_cache(registry=MetricsRegistry())
        eng = api._sql_engine
        plans = []
        orig = eng.planner.plan_select

        def spy(stmt):
            plans.append(stmt.table)
            return orig(stmt)

        eng.planner.plan_select = spy
        r1 = api.sql("select count(*) from t")
        r2 = api.sql("select count(*) from t")
        assert r1.data == [[2]] and r2.data == [[2]]
        assert len(plans) == 1  # second SELECT served from cache
        api.sql("insert into t values (3, 1)")
        r3 = api.sql("select count(*) from t")
        assert r3.data == [[3]]  # write invalidated the entry
        assert len(plans) == 2

    def test_system_tables_bypass(self, api):
        reg = MetricsRegistry()
        api.enable_cache(registry=reg)
        api.sql("select name from fb_performance_counters limit 1")
        assert reg.value(M.METRIC_CACHE_HITS) == 0
        assert reg.value(M.METRIC_CACHE_MISSES) == 0


# -- DeltaLog guards (cache correctness depends on these) ------------------


class TestDeltaLogEdges:
    def test_version_gap_resets(self):
        log = _DeltaLog()
        log.record(1, "a")
        log.record(5, "b")  # gap: 5 not in (1, 2)
        assert log.base == 5 and log.head == 5 and not log.ops
        assert log.since(1, 5) is None  # cannot bridge across the gap
        assert log.since(5, 5) == []

    def test_base_ahead_of_head_guard(self):
        log = _DeltaLog()
        log.record(1, "a")
        assert log.since(2, 1) is None  # base ahead of head: foreign stack
        assert log.since(0, 5) is None  # version bumped past the log
        assert log.since(0, 1) == ["a"]

    def test_cost_triggered_reset(self):
        log = _DeltaLog()
        log.record(1, "wide", cost=_DELTA_MAX_COLS - 10)
        log.record(2, "straw", cost=11)  # pushes past the column budget
        assert not log.ops and log.base == 2
        assert log.since(1, 2) is None
        assert log.since(2, 2) == []

    def test_op_count_triggered_reset(self):
        log = _DeltaLog()
        for i in range(_DELTA_MAX_OPS):
            log.record(1, i)  # same-version continuation is allowed
        assert len(log.ops) == _DELTA_MAX_OPS
        log.record(2, "overflow")
        assert not log.ops and log.base == 2

    def test_since_returns_payloads_after_base(self):
        log = _DeltaLog()
        log.record(1, "a")
        log.record(2, "b")
        log.record(3, "c")
        assert log.since(1, 3) == ["b", "c"]
        assert log.since(3, 3) == []


# -- HTTP admin endpoints --------------------------------------------------


class TestHTTPEndpoints:
    def test_stats_and_flush(self):
        import json
        import urllib.request

        from pilosa_tpu.server import serve

        api = API()
        seed_two_shards(api)
        srv, _ = serve(api, port=0, background=True)
        base = f"http://127.0.0.1:{srv.server_address[1]}"

        def req(method, path):
            r = urllib.request.Request(base + path, method=method,
                                       data=b"" if method == "POST" else None)
            with urllib.request.urlopen(r) as resp:
                return json.loads(resp.read())

        try:
            assert req("GET", "/internal/cache/stats") == {"enabled": False}
            api.enable_cache(registry=MetricsRegistry())
            api.query("i", "Count(Row(f=1))")
            api.query("i", "Count(Row(f=1))")
            s = req("GET", "/internal/cache/stats")
            assert s["enabled"] and s["entries"] == 1
            assert s["hits"] == 1 and s["misses"] == 1
            out = req("POST", "/internal/cache/flush")
            assert out == {"enabled": True, "flushed": 1}
            assert req("GET", "/internal/cache/stats")["entries"] == 0
        finally:
            srv.shutdown()


# -- config surface --------------------------------------------------------


class TestConfigSurface:
    def test_defaults(self):
        cfg = Config()
        assert cfg.cache_enabled is False
        assert cfg.cache_max_bytes == 64 << 20
        assert cfg.cache_max_entries == 4096
        assert cfg.cache_ttl_ms == 0.0

    def test_env_overrides(self):
        cfg = Config.from_sources(env={
            "PILOSA_TPU_CACHE_ENABLED": "true",
            "PILOSA_TPU_CACHE_MAX_BYTES": "1048576",
            "PILOSA_TPU_CACHE_MAX_ENTRIES": "77",
            "PILOSA_TPU_CACHE_TTL_MS": "250",
        })
        assert cfg.cache_enabled is True
        assert cfg.cache_max_bytes == 1 << 20
        assert cfg.cache_max_entries == 77
        assert cfg.cache_ttl_ms == 250.0

    def test_from_config_and_overrides(self):
        cfg = Config()
        cfg.cache_max_entries = 9
        c = ResultCache.from_config(cfg, registry=MetricsRegistry())
        assert c.max_entries == 9
        assert c.max_bytes == cfg.cache_max_bytes
        c2 = ResultCache.from_config(cfg, max_entries=3,
                                     registry=MetricsRegistry())
        assert c2.max_entries == 3

    def test_api_enable_cache_from_config(self, api):
        cfg = Config()
        cfg.cache_max_entries = 5
        cache = api.enable_cache(cfg, registry=MetricsRegistry())
        assert api.cache is cache and api.executor.cache is cache
        assert cache.max_entries == 5
        api.disable_cache()
        assert api.cache is None and api.executor.cache is None


class TestClusterCache:
    """Remote-leg caching surface on a real (in-process) cluster: the
    local fan-out leg keys on fragment versions; the remote legs key on
    (pql, shard set, write epoch) and require ttl_ms > 0."""

    @pytest.fixture()
    def node(self):
        from pilosa_tpu.cluster import LocalCluster

        c = LocalCluster(3)
        n0 = c.nodes[0]
        n0.create_index("cc")
        n0.create_field("cc", "f")
        from pilosa_tpu.shardwidth import SHARD_WIDTH
        cols = list(range(0, 4 * SHARD_WIDTH, SHARD_WIDTH // 4))
        n0.import_bits("cc", "f", rows=[0] * len(cols), cols=cols)
        yield n0
        c.close()

    def test_repeat_query_hits_and_write_invalidates(self, node):
        cache = node.enable_cache(ttl_ms=60_000,
                                  registry=MetricsRegistry())
        assert node.cache is cache
        r1 = node.query("cc", "Count(Row(f=0))")
        hits0 = dict(cache.stats())["hits"]
        assert node.query("cc", "Count(Row(f=0))") == r1
        assert dict(cache.stats())["hits"] > hits0
        node.import_bits("cc", "f", rows=[0], cols=[3])
        assert node.query("cc", "Count(Row(f=0))") == [r1[0] + 1]

    def test_remote_legs_not_cached_without_ttl(self, node):
        cache = node.enable_cache(ttl_ms=0, registry=MetricsRegistry())
        node.query("cc", "Count(Row(f=0))")
        # no ("rleg", ...) staleness-bounded entries without a TTL; only
        # the local leg's version-keyed entries may be present
        with cache._lock:
            assert not any(k[0] == "rleg" for k in cache._entries)
        node.disable_cache()
        assert node.cache is None and node.executor.cache is None
