"""Distributed tracing plane (obs/tracing.py): contextvar span scopes,
traceparent propagation over internode RPC, the bounded trace store and
its /internal/traces surface, profile=true span trees, the slow-query
log, and the trace_* metrics exposition.

The cross-thread regression cases pin the two boundaries that used to
drop parentage: the scheduler's dispatch worker (span_scope restore) and
the cluster fan-out pool (full copy_context per leg — a hedged remote
leg's span must stay a child of the coordinator's query span).
"""

import json
import random
import threading
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.obs import metrics as M
from pilosa_tpu.obs import stages
from pilosa_tpu.obs.metrics import MetricsRegistry
from pilosa_tpu.obs import tracing as T
from pilosa_tpu.obs.tracing import (NOP_SPAN, NopTracer, Span, TraceStore,
                                    Tracer, current_span,
                                    current_traceparent, format_traceparent,
                                    parse_traceparent, span_scope)
from pilosa_tpu.server.http import InFlight


@pytest.fixture
def tracer():
    """An always-sampling global tracer with its own store + registry,
    restored after the test (the suite may run under the tier-1 tracing
    lane's env-bootstrapped tracer)."""
    prev = T.get_tracer()
    reg = MetricsRegistry()
    t = Tracer(enabled=True, sample_rate=1.0,
               store=TraceStore(64, registry=reg), registry=reg)
    T.set_tracer(t)
    yield t
    T.set_tracer(prev)


@pytest.fixture
def nop_global():
    """Force the disabled default tracer for profile-with-tracing-off
    cases."""
    prev = T.get_tracer()
    T.set_tracer(NopTracer())
    yield
    T.set_tracer(prev)


def _names(span_json, acc=None):
    """All span names in a to_json tree (local and remote alike)."""
    acc = acc if acc is not None else []
    acc.append(span_json.get("name", ""))
    for c in span_json.get("children", ()):
        _names(c, acc)
    return acc


def _find(span_json, name):
    """All subtree dicts with the given span name."""
    out = []
    if span_json.get("name") == name:
        out.append(span_json)
    for c in span_json.get("children", ()):
        out.extend(_find(c, name))
    return out


class TestSpanBasics:
    def test_span_tree_and_parentage(self, tracer):
        with tracer.start_trace("root", index="i") as root:
            assert current_span() is root
            with tracer.start_span("child") as child:
                assert current_span() is child
                with tracer.start_span("grand") as grand:
                    pass
            assert current_span() is root
        assert current_span() is None
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
        assert grand.parent_id == child.span_id
        doc = root.to_json()
        assert doc["name"] == "root"
        assert doc["tags"] == {"index": "i"}
        assert doc["duration_ns"] > 0
        assert [c["name"] for c in doc["children"]] == ["child"]
        assert tracer.registry.value(M.METRIC_TRACE_STARTED) == 1.0
        assert tracer.registry.value(M.METRIC_TRACE_FINISHED) == 1.0

    def test_record_attaches_premeasured_child(self, tracer):
        with tracer.start_trace("root") as root:
            root.record("sched.queue_wait", 0.005, priority="interactive")
        doc = root.to_json()
        (wait,) = doc["children"]
        assert wait["name"] == "sched.queue_wait"
        assert wait["duration_ns"] == 5_000_000
        assert wait["tags"] == {"priority": "interactive"}

    def test_exception_tags_error_and_unwinds(self, tracer):
        with pytest.raises(RuntimeError):
            with tracer.start_trace("root") as root:
                raise RuntimeError("boom")
        assert root.tags["error"] == "boom"
        assert current_span() is None

    def test_start_span_outside_any_trace_is_nop(self, tracer):
        # stages never create implicit roots: stray background work
        # (maintenance threads, gossip rounds) stays untraced
        assert tracer.start_span("orphan") is NOP_SPAN
        assert len(tracer.store) == 0

    def test_nested_start_trace_joins_as_child(self, tracer):
        # a profile wrapper and the query path compose into ONE trace
        with tracer.profile("query.profile") as outer:
            with tracer.start_trace("query.pql") as inner:
                pass
        assert inner.trace_id == outer.trace_id
        assert inner.parent_id == outer.span_id

    def test_remote_child_dict_passes_through_to_json(self, tracer):
        with tracer.start_trace("root") as root:
            root.add_remote({"name": "rpc.x", "children": []}, attempt=1)
        (sub,) = root.to_json()["children"]
        assert sub["name"] == "rpc.x"
        assert sub["tags"]["attempt"] == 1


class TestNopPath:
    def test_disabled_tracer_returns_the_one_shared_span(self):
        t = NopTracer()
        spans = {id(t.start_trace("a")), id(t.start_span("b")),
                 id(NOP_SPAN)}
        assert spans == {id(NOP_SPAN)}  # zero per-query allocations
        # the shared span is immutable and inert
        assert NOP_SPAN.set_tag("k", "v") is NOP_SPAN
        assert NOP_SPAN.record("x", 1.0) is NOP_SPAN
        assert NOP_SPAN.tags == {} and not NOP_SPAN.recording
        with NOP_SPAN as s:
            assert s is NOP_SPAN

    def test_profile_forces_a_real_span_with_tracing_off(self):
        t = NopTracer()
        with t.profile("query.profile") as root:
            with t.start_span("stage"):
                pass
        assert root is not NOP_SPAN
        assert [c["name"] for c in root.to_json()["children"]] == ["stage"]

    def test_unsampled_root_counts_and_allocates_nothing(self):
        reg = MetricsRegistry()
        t = Tracer(enabled=True, sample_rate=0.5, registry=reg,
                   rng=random.Random(7))
        real = 0
        for _ in range(40):  # finish each before the next: roots, not nests
            s = t.start_trace("q")
            real += s is not NOP_SPAN
            s.finish()
        assert 0 < real < 40  # head sampling actually splits
        assert reg.value(M.METRIC_TRACE_STARTED) == float(real)
        assert reg.value(M.METRIC_TRACE_UNSAMPLED) == float(40 - real)


class TestTraceparent:
    def test_round_trip(self):
        tid, sid = "ab" * 16, "cd" * 8
        assert parse_traceparent(format_traceparent(tid, sid, True)) == \
            (tid, sid, True)
        assert parse_traceparent(format_traceparent(tid, sid, False)) == \
            (tid, sid, False)

    @pytest.mark.parametrize("bad", [
        None, 42, "", "00-abc", "00-" + "g" * 32 + "-" + "cd" * 8 + "-01",
        "00-" + "ab" * 16 + "-" + "cd" * 4 + "-01",
        "00-" + "ab" * 16 + "-" + "cd" * 8 + "-zz",
        "0-" + "ab" * 16 + "-" + "cd" * 8 + "-01",
    ])
    def test_malformed_is_rejected(self, bad):
        assert parse_traceparent(bad) is None

    def test_current_traceparent_tracks_scope(self, tracer):
        assert current_traceparent() is None
        with tracer.start_trace("root") as root:
            tp = current_traceparent()
            assert tp == format_traceparent(root.trace_id, root.span_id)
            with tracer.start_span("child") as child:
                assert current_traceparent() == format_traceparent(
                    child.trace_id, child.span_id)
        assert current_traceparent() is None

    def test_start_remote_honours_wire_context_even_when_disabled(self):
        # the coordinator asked for this trace; the serving node records
        # under it regardless of its own local sampling config
        t = NopTracer()
        tp = format_traceparent("ab" * 16, "cd" * 8, True)
        span = t.start_remote("rpc.query", tp, node="n1")
        assert span is not NOP_SPAN
        assert span.trace_id == "ab" * 16
        assert span.parent_id == "cd" * 8
        span.finish()
        assert t.start_remote("rpc.query", "garbage") is NOP_SPAN
        unsampled = format_traceparent("ab" * 16, "cd" * 8, False)
        assert t.start_remote("rpc.query", unsampled) is NOP_SPAN


class TestCrossThreadParentage:
    def test_span_scope_restores_parentage_on_a_worker(self, tracer):
        # the scheduler-boundary idiom: capture the submitter's span,
        # restore it (span only, not the whole context) on the worker
        with tracer.start_trace("root") as root:
            got = {}

            def worker():
                assert current_span() is None  # fresh thread: no scope
                with span_scope(root):
                    with tracer.start_span("stage") as s:
                        got["span"] = s
                assert current_span() is None

            th = threading.Thread(target=worker)
            th.start()
            th.join()
        assert got["span"].trace_id == root.trace_id
        assert got["span"].parent_id == root.span_id
        assert [c["name"] for c in root.to_json()["children"]] == ["stage"]

    def test_hedged_leg_span_is_a_child_of_the_query_span(self, tracer):
        # regression for the fan-out pool boundary: a hedge leg runs on
        # a pool thread spawned mid-race, and its span must still join
        # the coordinator's trace (satellite #1)
        from pilosa_tpu.cluster.resilience import Resilience

        res = Resilience(registry=MetricsRegistry(), hedge_min_ms=1.0,
                         hedge_max_ms=1.0)

        def run_remote(node, shards, token):
            if node == "A":  # parked primary loses the race
                token.wait(10.0)
                from pilosa_tpu.cluster.client import LegCancelled
                raise LegCancelled("parked")
            return ("part", node)

        with tracer.start_trace("query.pql", index="i") as root:
            parts, failed = res.run_legs(
                {"a": [1, 2]}, {"a": "A", "b": "B"}, run_remote,
                lambda s, r: {"b": list(s)})
        assert parts == [("part", "B")] and failed == []
        # run_legs does not wait for the cancelled loser (pool.shutdown
        # without wait): its span joins the tree when its thread wakes
        give_up = time.monotonic() + 10.0
        while True:
            legs = _find(root.to_json(), "cluster.leg")
            if len(legs) == 2 or time.monotonic() > give_up:
                break
            time.sleep(0.01)
        assert len(legs) == 2  # primary + hedge, both under the root
        by_hedge = {leg["tags"]["hedge"]: leg for leg in legs}
        assert by_hedge[True]["tags"]["node"] == "b"
        assert by_hedge[True]["tags"]["hedge_won"] is True
        assert by_hedge[False]["tags"]["hedge_won"] is False
        for leg in legs:
            assert leg["traceID"] == root.trace_id
            assert leg["parentID"] == root.span_id


class TestTraceStore:
    def test_capacity_evicts_oldest_and_counts_drops(self):
        reg = MetricsRegistry()
        store = TraceStore(capacity=3, registry=reg)
        t = Tracer(enabled=True, store=store, registry=reg)
        ids = []
        for i in range(5):
            with t.start_trace(f"q{i}") as root:
                ids.append(root.trace_id)
        assert len(store) == 3
        assert reg.value(M.METRIC_TRACE_STORE_DROPPED) == 2.0
        summaries = store.list()
        assert [s["root"] for s in summaries] == ["q4", "q3", "q2"]
        assert "spans" not in summaries[0]  # list() is summaries only
        with pytest.raises(KeyError):
            store.get(ids[0])  # evicted
        assert store.get(ids[-1])["spans"]["name"] == "q4"


class TestEndToEndSingleNode:
    def test_query_trace_reaches_store_and_history(self, tracer):
        from pilosa_tpu.api import API

        api = API()
        api.create_index("t")
        api.create_field("t", "f")
        api.query("t", "Set(1, f=2)Set(3, f=2)")
        assert api.query("t", "Count(Row(f=2))") == [2]
        rec = api.history.list()[0]
        assert rec.trace_id  # request_id <-> trace_id linkage
        doc = tracer.store.get(rec.trace_id)
        assert doc["spans"]["tags"]["request_id"] == rec.request_id
        names = _names(doc["spans"])
        assert names[0] == "query.pql"
        assert "device.dispatch" in names  # the async-dispatch split
        assert "storage.wal.commit" in _names(
            tracer.store.get(api.history.list()[-1].trace_id)["spans"])

    def test_profile_true_with_tracing_globally_off(self, nop_global):
        from pilosa_tpu.api import API

        api = API()
        api.create_index("t")
        api.create_field("t", "f")
        api.query("t", "Set(1, f=2)")
        out = api.query_json("t", "Count(Row(f=2))", profile=True)
        assert out["results"] == [1]
        prof = out["profile"]
        assert prof["name"] == "query.profile"
        names = _names(prof)
        assert "query.pql" in names and "device.dispatch" in names

    def test_slow_query_log_links_request_and_trace(self, tmp_path):
        from pilosa_tpu.api import API

        prev = T.get_tracer()
        reg = MetricsRegistry()
        before = M.REGISTRY.value(M.METRIC_TRACE_SLOW_QUERIES, kind="pql")
        T.set_tracer(Tracer(enabled=True, slow_ms=0.0001,  # everything slow
                            store=TraceStore(16, registry=reg),
                            registry=reg))
        try:
            api = API()
            api.set_query_logger(str(tmp_path / "q.log"))
            api.create_index("t")
            api.create_field("t", "f")
            api.query("t", "Set(1, f=2)")
            api.query("t", "Count(Row(f=2))")
            lines = [json.loads(ln) for ln in
                     (tmp_path / "q.log").read_text().splitlines()]
            slow = [ln for ln in lines if ln["kind"] == "slow"]
            assert slow, f"no slow-query lines in {lines}"
            rec = api.history.list()[0]
            assert slow[-1]["traceID"] == rec.trace_id
            assert slow[-1]["requestID"] == rec.request_id
            # _maybe_slow_log counts on the process-global registry
            after = M.REGISTRY.value(M.METRIC_TRACE_SLOW_QUERIES, kind="pql")
            assert after >= before + 1.0
        finally:
            T.set_tracer(prev)

    def test_scheduler_stages_appear_in_trace(self, tracer):
        from pilosa_tpu.api import API

        api = API()
        api.create_index("t")
        api.create_field("t", "f")
        api.query("t", "Set(1, f=2)")
        api.enable_scheduler(window_ms=0.2)
        try:
            assert api.query("t", "Count(Row(f=2))") == [1]
        finally:
            api.disable_scheduler()
        rec = api.history.list()[0]
        names = _names(tracer.store.get(rec.trace_id)["spans"])
        assert "sched.queue_wait" in names


class TestClusterEndToEnd:
    def test_three_node_profile_collects_remote_stages(self, nop_global):
        # the acceptance scenario: profile=true on a 3-node cluster
        # returns ONE span tree whose remote legs carry the serving
        # nodes' rpc spans, with tracing globally OFF everywhere
        from pilosa_tpu.cluster import LocalCluster
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        with LocalCluster(3) as c:
            co = c.coordinator
            # shards 0/1/2 of index "prof" hash to node1/node2/node0 —
            # the fan-out has one local and two remote legs
            co.create_index("prof")
            co.create_field("prof", "f")
            for shard in range(3):
                co.import_bits("prof", "f", rows=[1, 1],
                               cols=[shard * SHARD_WIDTH,
                                     shard * SHARD_WIDTH + 5])
            co.enable_scheduler(window_ms=0.2)
            co.enable_cache()
            try:
                out = co.query_json("prof", "Count(Row(f=1))", profile=True)
            finally:
                co.disable_scheduler()
                co.disable_cache()
            assert out["results"] == [6]
            prof = out["profile"]
            names = _names(prof)
            assert "query.pql" in names
            assert "sched.queue_wait" in names  # scheduler admission
            assert "cache.lookup" in names  # cold read: counted miss
            legs = _find(prof, "cluster.leg")
            assert legs, f"no cluster.leg spans in {names}"
            rpc = _find(prof, "rpc.post_internal_query")
            assert rpc, f"no remote rpc spans shipped back in {names}"
            # remote spans are tagged with the serving node's id
            assert all(r["tags"].get("node", "").startswith("node")
                       for r in rpc)
            # attribution coverage: named stages should account for the
            # bulk of the wall time (roots pay dispatch floors, so use a
            # loose floor here; bench config 12 tracks the real number)
            total = prof["duration_ns"]
            staged = sum(c["duration_ns"] for c in prof["children"])
            assert staged > 0 and total > 0

    def test_internal_traces_endpoints(self):
        from pilosa_tpu.cluster import LocalCluster
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        prev = T.get_tracer()
        reg = MetricsRegistry()
        T.set_tracer(Tracer(enabled=True, store=TraceStore(32, registry=reg),
                            registry=reg))
        try:
            with LocalCluster(3) as c:
                co = c.coordinator
                co.create_index("prof")  # shards 0-2 span all three nodes
                co.create_field("prof", "f")
                for shard in range(3):
                    co.import_bits("prof", "f", rows=[1],
                                   cols=[shard * SHARD_WIDTH])
                assert co.query("prof", "Count(Row(f=1))") == [3]
                base = co.node.uri
                with urllib.request.urlopen(base + "/internal/traces") as r:
                    listing = json.loads(r.read())
                assert listing["enabled"]
                assert listing["traces"], "no finished traces listed"
                tid = listing["traces"][0]["traceID"]
                with urllib.request.urlopen(
                        base + f"/internal/traces/{tid}") as r:
                    doc = json.loads(r.read())
                assert doc["traceID"] == tid
                assert doc["spans"]["name"]
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(
                        base + "/internal/traces/deadbeef")
                assert ei.value.code == 404
                # the coordinator assembled remote spans into its tree
                q = [d for d in (T.get_tracer().store.get(s["traceID"])
                                 for s in listing["traces"])
                     if d["root"] == "query.pql"]
                assert any(_find(d["spans"], "rpc.post_internal_query")
                           for d in q)
        finally:
            T.set_tracer(prev)


class TestMetricsExposition:
    def test_trace_metrics_in_prometheus_and_json(self):
        reg = MetricsRegistry()
        t = Tracer(enabled=True, store=TraceStore(8, registry=reg),
                   registry=reg)
        with t.start_trace("q") as root:
            with t.start_span("stage"):
                pass
            root.record("sched.queue_wait", 0.001)
        text = reg.prometheus_text()
        assert "trace_started_total 1" in text
        assert "trace_finished_total 1" in text
        assert 'trace_duration_ms_bucket{le="+Inf"} 1' in text
        assert 'stage="sched.queue_wait"' in text
        assert "trace_stage_latency_ms_count" in text
        doc = reg.as_json()
        assert doc["counters"]["trace_started_total"] == 1.0
        hists = doc["histograms"]
        dur = next(v for k, v in hists.items()
                   if k.startswith("trace_duration_ms"))
        assert dur["count"] == 1
        assert any(k.startswith("trace_stage_latency_ms") for k in hists)


# ---------------------------------------------------------------------------
# Leaf annotations on the profiler's clock (tracing.annotate)
# ---------------------------------------------------------------------------

#: every annotation this program emits. Containers (query.pql,
#: device.dispatch, pql.fetch, storage.wal.commit, stack.build) are spans
#: of the sampled tree only, and waits (a reader behind the write lock, a
#: writer behind a reader's build) are counters only: both would take an
#: idle gap's seconds from the event that did the work
READ_LEAVES = ("http.read", "pql.parse", "pql.plan", "pql.emit",
               "pql.render", "http.encode", "http.write")
WRITE_LEAVES = ("import.decode", "import.key_translate",
                "import.fragment_advance", "import.wal_commit",
                "checkpoint.serialize", "checkpoint.fsync",
                "checkpoint.meta", "checkpoint.prune")
OUR_PREFIXES = ("http.", "pql.", "import.", "checkpoint.", "stack.",
                "query.", "device.", "storage.")


class TestAnnotateNopPath:
    def test_no_session_returns_the_one_shared_span(self, nop_global):
        t = T.get_tracer()
        got = {id(T.annotate("http.read")), id(t.start_span("pql.parse")),
               id(t.start_trace("query.pql")), id(NOP_SPAN)}
        assert got == {id(NOP_SPAN)}

    @pytest.mark.parametrize("stage", ["plan", "emit", "render"])
    def test_a_read_stage_leaf_is_the_shared_span(self, nop_global, stage):
        assert T.annotate(stages.QUERY_STAGE_LEAVES[stage]) is NOP_SPAN
        with stages.query_stage(stage) as region:
            assert region._leaf is NOP_SPAN

    def test_no_session_allocates_nothing(self, nop_global):
        import tracemalloc

        t = T.get_tracer()

        def request():
            with T.annotate("http.read"):
                pass
            with stages.query_stage("parse"), t.start_span("pql.parse"):
                pass
            for stage in ("plan", "emit", "render", "encode", "write"):
                with stages.query_stage(stage):
                    pass
            with t.start_span("pql.fetch"):
                pass

        request()  # resolves the lazy imports
        tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            for _ in range(200):
                request()
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        here = [tracemalloc.Filter(True, T.__file__),
                tracemalloc.Filter(True, stages.__file__)]
        grown = sum(s.size_diff for s in after.filter_traces(here)
                    .compare_to(before.filter_traces(here), "filename"))
        assert grown == 0

    def test_probe_missing_falls_back_to_always_annotate(self, monkeypatch):
        import builtins

        import jax

        real = builtins.__import__

        def no_private(name, *a, **kw):
            if name == "jax._src.lib":
                raise ImportError(name)
            return real(name, *a, **kw)

        monkeypatch.setattr(builtins, "__import__", no_private)
        monkeypatch.setattr(T, "_session_active", None)
        span = T.annotate("http.read")
        assert isinstance(span, jax.profiler.TraceAnnotation)
        with span:  # outside a session it records nothing and is harmless
            pass


def _settled():
    """Wait until the server holds no request: a response's last stage
    is counted after the client has read it."""
    deadline = time.monotonic() + 10.0
    while M.REGISTRY.value(M.METRIC_HTTP_IN_FLIGHT) and \
            time.monotonic() < deadline:
        time.sleep(0.01)


def _stage_seconds():
    return {s: M.REGISTRY.value(M.METRIC_QUERY_STAGE_SECONDS, stage=s)
            for s in stages.QUERY_STAGE_LEAVES}


@pytest.fixture(scope="module")
def host_session(tmp_path_factory):
    """One ``jax.profiler`` session on the CPU backend over a served
    import (keyed set field, set field and int field), served reads (a
    Count, a Sum, a keyed GroupBy of two fields with a Sum, a TopN, a
    SQL count) and a checkpoint. Returns ({thread line: [(name,
    start_ns, end_ns), ...]} of the host plane, {stage: seconds the
    ``query_stage_seconds_total`` counter grew by over the session})."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from pilosa_tpu.api import API
    from pilosa_tpu.server.http import serve

    root = tmp_path_factory.mktemp("host_trace")
    api = API(str(root / "data"))
    srv, _ = serve(api, port=0, background=True)
    base = f"http://127.0.0.1:{srv.server_address[1]}"

    def post(path, body):
        data = body.encode() if isinstance(body, str) \
            else json.dumps(body).encode()
        with urllib.request.urlopen(urllib.request.Request(
                base + path, data=data, method="POST")) as resp:
            return json.loads(resp.read())

    cols = list(range(4000))

    def load(offset):
        post("/index/i/import", {
            "field": "f", "cols": [c + offset for c in cols],
            "rowKeys": [f"k{c % 7}" for c in cols]})
        post("/index/i/import", {
            "field": "g", "cols": [c + offset for c in cols],
            "rows": [c % 3 for c in cols]})
        post("/index/i/import-values", {
            "field": "v", "cols": [c + offset for c in cols],
            "values": [c % 1000 for c in cols]})
        return (post("/index/i/query", "Count(Row(f=k1))")["results"],
                post("/index/i/query", "Sum(field=v)")["results"][0]["count"])

    def reads():
        groups, = post("/index/i/query", "GroupBy(Rows(f), Rows(g), "
                       "aggregate=Sum(field=v))")["results"]
        pairs, = post("/index/i/query", "TopN(f, n=3)")["results"]
        sql = post("/sql", "SELECT COUNT(*) FROM i WHERE v < 500")["data"]
        return (len(groups), sum(g["count"] for g in groups),
                len(pairs["rows"]), sql)

    try:
        post("/index/i", {})
        post("/index/i/field/f", {"options": {"type": "set", "keys": True}})
        post("/index/i/field/g", {"options": {"type": "set"}})
        post("/index/i/field/v",
             {"options": {"type": "int", "min": 0, "max": 1000}})
        load(0)  # compiles outside the session
        reads()
        _settled()
        before = _stage_seconds()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(root / "trace"), profiler_options=opts)
        try:
            assert load(len(cols)) == ([2 * 572], 2 * len(cols))
            assert reads() == (21, 2 * len(cols), 3, [[2 * 2000]])
            api.holder.checkpoint()
            _settled()
        finally:
            jax.profiler.stop_trace()
        after = _stage_seconds()
    finally:
        srv.shutdown()
        srv.server_close()
    pb, = glob.glob(str(root / "trace" / "plugins" / "profile" / "*"
                        / "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(pb).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            if events:
                lines[f"{line.name}#{i}"] = events
    return lines, {s: after[s] - before[s] for s in after}


@pytest.fixture(scope="module")
def host_trace(host_session):
    return host_session[0]


def _ours(events):
    return [e for e in events if e[0].startswith(OUR_PREFIXES)]


class TestAnnotationsOnTheProfilersClock:
    @pytest.mark.parametrize("name", READ_LEAVES + WRITE_LEAVES)
    def test_leaf_is_in_the_host_plane(self, host_trace, name):
        assert any(e[0] == name for ev in host_trace.values() for e in ev)

    def test_containers_stay_out_of_the_profile(self, host_trace):
        seen = {e[0] for ev in host_trace.values() for e in _ours(ev)}
        assert seen <= set(READ_LEAVES + WRITE_LEAVES), seen
        # named explicitly: these would swallow every owner inside them
        # or, for the waits, on the lock holder's thread
        for container in ("query.pql", "device.dispatch", "pql.fetch",
                          "storage.wal.commit", "stack.build",
                          "stack.writer_wait", "import.lock_wait"):
            assert container not in seen

    def test_no_annotation_of_ours_encloses_another(self, host_trace):
        for line, events in host_trace.items():
            ours = sorted(_ours(events), key=lambda e: e[1])
            for (a, _, a_end), (b, b_start, _) in zip(ours, ours[1:]):
                assert a_end <= b_start, f"{a} overlaps {b} on {line}"

    def test_no_annotation_of_ours_encloses_a_jax_call(self, host_trace):
        # import.fragment_advance may enclose the device scatter's
        # PjitFunction (accepted: it is the stage's own dispatch)
        jax_named = ("PjitFunction(", "np.asarray(", "ArrayImpl.",
                     "shard_args")
        for line, events in host_trace.items():
            inner = [e for e in events if e[0].startswith(jax_named)]
            for name, start, end in _ours(events):
                if name == "import.fragment_advance":
                    continue
                for j, j_start, j_end in inner:
                    assert not (start <= j_start and j_end <= end), \
                        f"{name} encloses {j} on {line}"


class TestReadStageCounters:
    #: the counter's clock starts before its leaf and stops after it; the
    #: profiler's and ``perf_counter``'s clocks agree to far better than 1%
    TOLERANCE = 0.01

    @pytest.mark.parametrize("stage", sorted(stages.QUERY_STAGE_LEAVES))
    def test_counter_holds_at_least_what_its_leaf_shows(self, host_session,
                                                        stage):
        lines, grown = host_session
        leaf = stages.QUERY_STAGE_LEAVES[stage]
        traced = [(end - start) / 1e9 for events in lines.values()
                  for name, start, end in events if name == leaf]
        assert traced, f"no {leaf} in the session"
        assert grown[stage] >= sum(traced) * (1 - self.TOLERANCE)


class TestServerIdleSeconds:
    def test_counts_only_while_nothing_is_held(self):
        reg = MetricsRegistry()
        now = [100.0]
        held = InFlight(clock=lambda: now[0], registry=reg)

        def idle():
            held.flush()
            return reg.value(M.METRIC_HTTP_SERVER_IDLE_SECONDS)

        now[0] += 2.0
        assert idle() == 2.0           # the open stretch, up to the flush
        now[0] += 1.0
        held.enter()                   # closes the stretch: 3 s in all
        assert reg.value(M.METRIC_HTTP_IN_FLIGHT) == 1
        held.enter()
        now[0] += 5.0
        held.leave()
        assert idle() == 3.0           # one request is still held
        now[0] += 4.0
        held.leave()
        assert reg.value(M.METRIC_HTTP_IN_FLIGHT) == 0
        now[0] += 0.5
        assert idle() == 3.5

    @pytest.fixture
    def served(self, tmp_path, monkeypatch):
        from pilosa_tpu.api import API
        from pilosa_tpu.server import http as H

        release = threading.Event()
        entered = threading.Event()
        real = H.Handler.get_schema

        def held_schema(handler):
            entered.set()
            release.wait(10.0)
            real(handler)

        monkeypatch.setattr(H.Handler, "get_schema", held_schema)
        srv, _ = H.serve(API(str(tmp_path / "data")), port=0,
                         background=True)
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        yield base, entered, release
        release.set()
        srv.shutdown()
        srv.server_close()

    @staticmethod
    def _scraped(base):
        with urllib.request.urlopen(base + "/metrics") as r:
            text = r.read().decode()
        idle = held = None
        for line in text.splitlines():
            if line.startswith("pilosa_" + M.METRIC_HTTP_SERVER_IDLE_SECONDS):
                idle = float(line.split()[-1])
            if line.startswith("pilosa_" + M.METRIC_HTTP_IN_FLIGHT + " "):
                held = float(line.split()[-1])
        return idle, held

    def test_a_scrape_includes_the_open_stretch(self, served):
        base, _, _ = served
        first, _ = self._scraped(base)
        time.sleep(0.3)
        second, held = self._scraped(base)
        # scrapes are probes: the server held nothing in between
        assert second - first >= 0.29
        assert not held

    def test_stands_still_while_a_request_is_held(self, served):
        base, entered, release = served
        got = []
        t = threading.Thread(target=lambda: got.append(
            urllib.request.urlopen(base + "/schema").status))
        t.start()
        try:
            assert entered.wait(10.0)
            first, held = self._scraped(base)
            assert held == 1
            time.sleep(0.3)
            second, held = self._scraped(base)
            assert held == 1
            assert second == first
        finally:
            release.set()
            t.join(10.0)
        assert got == [200]
        _settled()
        time.sleep(0.2)
        third, held = self._scraped(base)
        assert held == 0
        assert third - second >= 0.19
