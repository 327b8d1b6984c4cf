"""Streaming ingest subsystem tests (stream/): broker semantics, the
pipelined ingester's bit-identity against the classic Ingester oracle,
exactly-once crash/resume at every pipeline stage boundary, read-
protecting backpressure, and the satellite surfaces (rate-controlled
datagen, KafkaSource StreamConsumer protocol, HTTP push/stats,
ingest_stall flight trigger, [stream] config).

``PILOSA_TPU_CRASH_SEED`` (scripts/tier1.sh stream lane) steers the
seeded stream crash plan the same way the storage crash lane does.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.api import API
from pilosa_tpu.errors import AdmissionError
from pilosa_tpu.ingest.datagen import scenario
from pilosa_tpu.ingest.ingest import Ingester
from pilosa_tpu.sched.clock import ManualClock
from pilosa_tpu.storage.recovery import (
    CRASH_SITES, STREAM_CRASH_SITES, CrashPlan, SimulatedCrash,
    abandon_holder)
from pilosa_tpu.stream import (BrokerSource, PipelinedIngester,
                               StreamBroker, StreamService, chunk_columns,
                               iter_rows, make_chunk, split_tp, tp_key)

ROWS = 1200
BATCH = 200


def customer_records(rows=ROWS, seed=5):
    return list(scenario("customer", rows=rows, seed=seed).records())


def make_broker(recs, partitions=2, seed=3):
    broker = StreamBroker(partitions=partitions, seed=seed)
    broker.produce_records("t", recs)
    return broker


def pipelined_run(path, broker, schema, plan=None, group="ingest"):
    api = API(path=path)
    if plan is not None:
        api.holder.crash_plan = plan
    consumer = broker.consumer(group, ["t"])
    p = PipelinedIngester(api, "idx", consumer, schema=schema,
                          batch_rows=BATCH, plan=plan, group=group)
    return api, p


# -- broker -------------------------------------------------------------------


class TestBroker:
    def test_keys_and_offsets(self):
        b = StreamBroker(partitions=4, seed=1)
        p1, o1 = b.produce("t", {"id": 1}, key="k")
        p2, o2 = b.produce("t", {"id": 2}, key="k")
        assert p1 == p2 and o2 == o1 + 1  # keyed: stable partition
        assert b.end_offset("t", p1) == 2
        assert tp_key("t", p1) == f"t:{p1}"
        assert split_tp(tp_key("a:b", 3)) == ("a:b", 3)

    def test_unkeyed_round_robin_deterministic(self):
        def spread(seed):
            b = StreamBroker(partitions=3, seed=seed)
            return [b.produce("t", {"i": i})[0] for i in range(9)]

        assert spread(7) == spread(7)  # same seed, same assignment
        assert sorted(set(spread(7))) == [0, 1, 2]  # covers partitions

    def test_group_commit_monotonic(self):
        b = StreamBroker(partitions=1)
        b.produce_records("t", [{"i": i} for i in range(10)])
        b.commit("g", {"t:0": 7})
        b.commit("g", {"t:0": 4})  # late duplicate never regresses
        assert b.committed("g", "t", 0) == 7
        assert b.committed("other", "t", 0) == 0  # groups independent

    def test_consumer_poll_commit_resume(self):
        b = StreamBroker(partitions=2, seed=0)
        b.produce_records("t", [{"i": i} for i in range(10)])
        c = b.consumer("g", ["t"])
        got = c.poll(max_records=6)
        assert len(got) == 6
        c.commit()
        c2 = b.consumer("g", ["t"])  # new member resumes from commit
        rest = c2.poll(max_records=100)
        assert len(rest) == 4
        seen = {(r.topic, r.partition, r.offset) for r in got + rest}
        assert len(seen) == 10  # no loss, no duplicates

    def test_pause_resume_and_lag(self):
        clock = ManualClock()
        b = StreamBroker(partitions=1, clock=clock)
        b.produce_records("t", [{"i": i} for i in range(5)])
        c = b.consumer("g", ["t"])
        assert c.lag() == 5
        c.pause()
        assert c.poll(100) == [] and c.paused
        clock.advance(3.0)
        c.resume()
        assert c.paused_s() == pytest.approx(3.0)
        assert len(c.poll(100)) == 5 and c.lag() == 0


# -- pipelined ingest: bit-identity oracle ------------------------------------


class TestPipelineIdentity:
    def test_matches_classic_ingester(self, tmp_path):
        recs = customer_records()
        src = scenario("customer", rows=ROWS, seed=5)
        broker = make_broker(recs)

        api1 = API(path=str(tmp_path / "classic"))
        c1 = broker.consumer("g1", ["t"])
        n1 = Ingester(api1, "idx", BrokerSource(c1, src.schema()),
                      batch_size=BATCH).run()

        api2, p = pipelined_run(str(tmp_path / "piped"), broker,
                                src.schema(), group="g2")
        n2 = p.run()
        assert n1 == n2 == ROWS
        assert api1.checksum() == api2.checksum()
        offs = api2.holder.index("idx").stream_offsets["g2"]
        assert sum(offs.values()) == ROWS  # watermark covers every row

    def test_auto_id_records(self, tmp_path):
        # no id column: deterministic per-batch idalloc sessions
        broker = StreamBroker(partitions=1)
        broker.produce_records(
            "t", [{"color": ["red"]} for _ in range(300)])
        api = API(path=str(tmp_path))
        api.create_index("idx")
        from pilosa_tpu.core.schema import FieldOptions, FieldType
        api.holder.index("idx").create_field(
            "color", FieldOptions(type=FieldType.SET, keys=True))
        p = PipelinedIngester(api, "idx", broker.consumer("g", ["t"]),
                              id_field=None, batch_rows=100)
        assert p.run() == 300
        assert api.query("idx", "Count(Row(color=red))")[0] == 300

    def test_devprof_stage_gauges(self, tmp_path):
        # the pipeline's host/device split shows up as distinct ingest
        # stages — the overlap evidence the kernel plane reports
        from pilosa_tpu.obs import stages

        stages.INGEST.reset()
        try:
            recs = customer_records(rows=600)
            src = scenario("customer", rows=600, seed=5)
            broker = make_broker(recs)
            api, p = pipelined_run(str(tmp_path), broker, src.schema())
            p.run()
            seen = stages.INGEST.snapshot()
            assert "parse" in seen  # host side
            assert "fragment_advance" in seen  # device side
            assert "key_translate" in seen  # host-side bulk translate
        finally:
            stages.INGEST.reset()


# -- chunked messages (the Kafka batch-per-message production shape) ----------


def chunked_broker(rows=900, chunk=100, plain_tail=0, seed=11):
    """A broker whose "t" topic carries id/city/device as chunked
    column messages (plus ``plain_tail`` single-row dicts at the end)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    city = rng.integers(0, 50, rows)
    dev = rng.integers(0, 10, rows)
    broker = StreamBroker(partitions=1, seed=seed)
    body = rows - plain_tail
    for lo in range(0, body, chunk):
        hi = min(lo + chunk, body)
        broker.produce("t", make_chunk({
            "id": list(range(lo, hi)),
            "city": city[lo:hi],  # numpy columns ride through in-process
            "device": dev[lo:hi].tolist()}))
    for i in range(body, rows):
        broker.produce("t", {"id": i, "city": int(city[i]),
                             "device": int(dev[i])})
    return broker


def int_schema():
    from pilosa_tpu.ingest.source import _parse_header

    return _parse_header(["city__IS", "device__IS"])


class TestChunkedMessages:
    def test_make_chunk_validates_lengths(self):
        with pytest.raises(ValueError):
            make_chunk({"a": [1, 2], "b": [1]})
        assert chunk_columns(make_chunk({"a": [1, 2]})) == {"a": [1, 2]}
        assert chunk_columns({"id": 1}) is None  # plain rows pass through

    def test_iter_rows_expands_chunks(self):
        rows = list(iter_rows(make_chunk({"a": [1, 2], "b": [3, 4]})))
        assert rows == [{"a": 1, "b": 3}, {"a": 2, "b": 4}]
        assert list(iter_rows({"a": 5})) == [{"a": 5}]

    def test_chunked_identity_vs_classic(self, tmp_path):
        broker = chunked_broker()
        schema = int_schema()
        api1 = API(path=str(tmp_path / "classic"))
        n1 = Ingester(api1, "idx",
                      BrokerSource(broker.consumer("g1", ["t"]), schema),
                      batch_size=BATCH).run()
        api2, p = pipelined_run(str(tmp_path / "piped"), broker, schema,
                                group="g2")
        n2 = p.run()
        assert n1 == n2 == 900
        assert api1.checksum() == api2.checksum()
        # offsets count MESSAGES, not rows: 900 rows / 100-row chunks
        offs = api2.holder.index("idx").stream_offsets["g2"]
        assert sum(offs.values()) == 9

    def test_mixed_plain_and_chunked_batch(self, tmp_path):
        # a poll that straddles the chunked body and the plain tail takes
        # the row path via iter_rows — same bits either way
        broker = chunked_broker(rows=450, chunk=100, plain_tail=50)
        schema = int_schema()
        api1 = API(path=str(tmp_path / "classic"))
        n1 = Ingester(api1, "idx",
                      BrokerSource(broker.consumer("g1", ["t"]), schema),
                      batch_size=BATCH).run()
        api2, p = pipelined_run(str(tmp_path / "piped"), broker, schema,
                                group="g2")
        assert n1 == p.run() == 450
        assert api1.checksum() == api2.checksum()

    @pytest.mark.parametrize("site", STREAM_CRASH_SITES)
    def test_chunked_crash_resume(self, tmp_path, site):
        golden_api, g = pipelined_run(str(tmp_path / "golden"),
                                      chunked_broker(), int_schema())
        g.run()
        golden = golden_api.checksum()

        broker = chunked_broker()
        plan = CrashPlan().kill(site, at=2)
        api = API(path=str(tmp_path / "crash"))
        api.holder.crash_plan = plan
        # 3 chunk messages per poll -> 3 batches, so at=2 dies mid-stream
        p = PipelinedIngester(api, "idx", broker.consumer("ingest", ["t"]),
                              schema=int_schema(), batch_rows=3, plan=plan)
        crashed = False
        try:
            p.run()
        except SimulatedCrash:
            crashed = True
        assert crashed
        abandon_holder(api.holder)
        api2 = API(path=str(tmp_path / "crash"))
        p2 = PipelinedIngester(api2, "idx", broker.consumer("ingest", ["t"]),
                               schema=int_schema(), batch_rows=BATCH)
        p2.run()
        assert api2.checksum() == golden  # zero loss, zero duplicates


# -- exactly-once crash/resume ------------------------------------------------


def _crash_then_resume(tmp_path, plan, recs, schema):
    broker = make_broker(recs)
    api, p = pipelined_run(str(tmp_path), broker, schema, plan=plan)
    crashed = False
    try:
        p.run()
    except SimulatedCrash:
        crashed = True
    abandon_holder(api.holder)
    api2 = API(path=str(tmp_path))
    c2 = broker.consumer("ingest", ["t"])
    p2 = PipelinedIngester(api2, "idx", c2, schema=schema,
                           batch_rows=BATCH)
    p2.run()
    return crashed, api2


class TestStreamCrashMatrix:
    @pytest.fixture(scope="class")
    def golden(self, tmp_path_factory):
        recs = customer_records()
        src = scenario("customer", rows=ROWS, seed=5)
        d = tmp_path_factory.mktemp("golden")
        broker = make_broker(recs)
        api, p = pipelined_run(str(d), broker, src.schema())
        p.run()
        return api.checksum()

    @pytest.mark.parametrize("site", STREAM_CRASH_SITES)
    @pytest.mark.parametrize("at", [1, 2, 3])
    def test_kill_at_stage_boundary(self, tmp_path, golden, site, at):
        recs = customer_records()
        src = scenario("customer", rows=ROWS, seed=5)
        plan = CrashPlan().kill(site, at=at)
        crashed, api2 = _crash_then_resume(tmp_path, plan, recs,
                                           src.schema())
        assert crashed, f"{site}@{at} never fired"
        # zero lost, zero duplicated rows: bit-identical to a clean run
        assert api2.checksum() == golden
        offs = api2.holder.index("idx").stream_offsets["ingest"]
        assert sum(offs.values()) == ROWS

    def test_seeded_stream_plan(self, tmp_path, golden):
        """The tier1 stream lane's seed (PILOSA_TPU_CRASH_SEED) draws a
        deterministic site/hit-count from the stream site tuple."""
        seed = int(os.environ.get("PILOSA_TPU_CRASH_SEED", "1"))
        plan = CrashPlan.stream_seeded(seed)
        again = CrashPlan.stream_seeded(seed)
        assert plan._arms == again._arms  # same seed, same kill
        assert all(s in STREAM_CRASH_SITES for s in plan._arms)
        recs = customer_records()
        src = scenario("customer", rows=ROWS, seed=5)
        crashed, api2 = _crash_then_resume(tmp_path, plan, recs,
                                           src.schema())
        assert crashed
        assert api2.checksum() == golden

    def test_storage_sites_unchanged(self):
        # the stream sites live in their OWN tuple so storage-lane
        # seeded() draws are unchanged by this subsystem existing
        assert not set(STREAM_CRASH_SITES) & set(CRASH_SITES)

    def test_checkpoint_stamps_offsets_across_prune(self, tmp_path):
        recs = customer_records(rows=600)
        src = scenario("customer", rows=600, seed=5)
        broker = make_broker(recs)
        api, p = pipelined_run(str(tmp_path), broker, src.schema())
        p.run()
        want = api.checksum()
        api.save()  # checkpoint: stamps offsets, prunes the WAL tail
        abandon_holder(api.holder)
        api2 = API(path=str(tmp_path))
        # the watermark survived the prune via checkpoint.json
        offs = api2.holder.index("idx").stream_offsets["ingest"]
        assert sum(offs.values()) == 600
        # resume sees nothing new: zero rows re-ingested, state intact
        c2 = broker.consumer("ingest", ["t"])
        p2 = PipelinedIngester(api2, "idx", c2, schema=src.schema(),
                               batch_rows=BATCH)
        assert p2.run() == 0
        assert api2.checksum() == want


# -- backpressure -------------------------------------------------------------


class TestBackpressure:
    def test_enqueue_pauses_consumer_when_full(self, tmp_path):
        recs = customer_records(rows=100)
        src = scenario("customer", rows=100, seed=5)
        broker = make_broker(recs)
        api = API(path=str(tmp_path))
        consumer = broker.consumer("g", ["t"])
        p = PipelinedIngester(api, "idx", consumer, schema=src.schema(),
                              batch_rows=10, queue_depth=1)
        p._ensure_schema()
        batch = p._prepare(consumer.poll(10))
        p._queue.put_nowait(object())  # device side "busy": queue full
        assert p.credits() == 0
        t = threading.Thread(target=p._enqueue, args=(batch,))
        t.start()
        for _ in range(500):
            if consumer.paused:
                break
            time.sleep(0.002)
        assert consumer.paused  # host blocked -> consumer paused
        p._queue.get_nowait()  # device catches up
        t.join(timeout=5)
        assert not t.is_alive() and not consumer.paused
        assert p.paused_s >= 0.0

    def test_service_push_429_when_saturated(self, tmp_path):
        api = API(path=str(tmp_path))
        svc = StreamService(api, "idx", batch_rows=10, queue_depth=1,
                            max_backlog_rows=20)
        out = svc.push([{"id": i} for i in range(19)])
        assert out["accepted"] == 19
        svc.push([{"id": 99}])  # reaches the backlog bound
        with pytest.raises(AdmissionError):
            svc.push([{"id": 100}])
        assert svc.rejected == 1 and svc.stats()["saturated"]
        svc.step()  # drain
        assert not svc.saturated()
        assert svc.push([{"id": 100}])["accepted"] == 1
        svc.close()

    def test_push_validates_records(self, tmp_path):
        api = API(path=str(tmp_path))
        svc = StreamService(api, "idx")
        with pytest.raises(ValueError):
            svc.push(["not-a-dict"])
        svc.close()

    def test_scheduler_batch_priority_keeps_read_headroom(self, tmp_path):
        # with the scheduler on, the device stage admits at batch
        # priority; reads still execute during a full-rate drain
        recs = customer_records(rows=600)
        src = scenario("customer", rows=600, seed=5)
        broker = make_broker(recs)
        api = API(path=str(tmp_path))
        api.enable_scheduler()
        try:
            c = broker.consumer("g", ["t"])
            p = PipelinedIngester(api, "idx", c, schema=src.schema(),
                                  batch_rows=100)
            assert p.run() == 600
            assert api.query("idx", "Count(All())")[0] == 600
        finally:
            api.disable_scheduler()


# -- satellite: rate-controlled datagen ---------------------------------------


class TestRateControlledDatagen:
    def test_manual_clock_zero_wall_sleeps(self):
        clock = ManualClock()
        src = scenario("customer", rows=50, seed=1, rate_rows_s=100.0,
                       clock=clock)
        t0 = time.monotonic()
        recs = list(src.records())
        wall = time.monotonic() - t0
        assert len(recs) == 50
        # virtual time advanced to the release schedule, wall time didn't
        assert clock.now() == pytest.approx(49 / 100.0)
        assert wall < 1.0

    def test_rate_deterministic(self):
        a = list(scenario("customer", rows=20, seed=9, rate_rows_s=50.0,
                          clock=ManualClock()).records())
        b = list(scenario("customer", rows=20, seed=9, rate_rows_s=50.0,
                          clock=ManualClock()).records())
        assert a == b
        # and identical to the unpaced scenario's records
        assert a == list(scenario("customer", rows=20, seed=9).records())

    def test_rate_must_be_positive(self):
        with pytest.raises(ValueError):
            scenario("customer", rows=5, rate_rows_s=0.0,
                     clock=ManualClock())


# -- satellite: KafkaSource StreamConsumer protocol ---------------------------


class _FakeMsg:
    def __init__(self, topic, partition, offset, value, key=None):
        self._t, self._p, self._o = topic, partition, offset
        self._v, self._k = value, key

    def topic(self):
        return self._t

    def partition(self):
        return self._p

    def offset(self):
        return self._o

    def value(self):
        return self._v

    def key(self):
        return self._k

    def error(self):
        return None


class _FakeTopicPartition:
    def __init__(self, topic, partition, offset=-1001):
        self.topic, self.partition, self.offset = topic, partition, offset


class _FakeConsumer:
    """confluent-kafka-shaped consumer over an in-memory log."""

    def __init__(self, conf):
        self.conf = conf
        self.log = []  # injected by the test
        self.pos = 0
        self.commits = []
        self.paused_tps = []
        self.seeks = []

    def subscribe(self, topics):
        self.topics = topics

    def poll(self, timeout=0.0):
        if self.pos >= len(self.log):
            return None
        msg = self.log[self.pos]
        self.pos += 1
        return msg

    def assignment(self):
        return [_FakeTopicPartition("t", 0)]

    def commit(self, offsets=None, asynchronous=True):
        self.commits.append(offsets)

    def committed(self, tps):
        last = self.commits[-1] if self.commits else []
        return last or [_FakeTopicPartition("t", 0, 0)]

    def seek(self, tp):
        self.seeks.append((tp.topic, tp.partition, tp.offset))
        self.pos = tp.offset

    def pause(self, tps):
        self.paused_tps = tps

    def resume(self, tps):
        self.paused_tps = []


class _FakeClient:
    Consumer = _FakeConsumer
    TopicPartition = _FakeTopicPartition


class TestKafkaSourceProtocol:
    def make(self):
        from pilosa_tpu.ingest.kafka import KafkaSource

        src = KafkaSource("b:9092", ["t"], "g",
                          ["id", "color__SS"], client=_FakeClient())
        consumer = src.connect()
        consumer.log = [
            _FakeMsg("t", 0, i, json.dumps(
                {"id": i, "color": ["red"]}).encode())
            for i in range(5)]
        return src, consumer

    def test_gate_raises_without_client(self, monkeypatch):
        import builtins

        from pilosa_tpu.ingest import kafka as K

        real = builtins.__import__

        def deny(name, *a, **k):
            if name in ("confluent_kafka", "kafka"):
                raise ImportError(name)
            return real(name, *a, **k)

        monkeypatch.setattr(builtins, "__import__", deny)
        with pytest.raises(ImportError, match="no kafka client"):
            K._kafka_client()

    def test_poll_returns_stream_records(self):
        src, _ = self.make()
        recs = src.poll(max_records=3)
        assert [r.offset for r in recs] == [0, 1, 2]
        assert recs[0].topic == "t" and recs[0].partition == 0
        assert recs[0].value == {"id": 0, "color": ["red"]}
        assert len(src.poll(max_records=10)) == 2  # the rest

    def test_commit_offsets_mapping(self):
        src, consumer = self.make()
        src.poll(max_records=5)
        src.commit({"t:0": 5})
        (tps,) = consumer.commits
        assert (tps[0].topic, tps[0].partition, tps[0].offset) == \
            ("t", 0, 5)
        assert src.committed("t", 0) == 5

    def test_seek_pause_resume(self):
        src, consumer = self.make()
        src.poll(max_records=5)
        src.seek("t", 0, 2)
        assert consumer.seeks == [("t", 0, 2)]
        assert [r.offset for r in src.poll(max_records=10)] == [2, 3, 4]
        assert not src.paused
        src.pause()
        assert src.paused and consumer.paused_tps
        src.resume()
        assert not src.paused and not consumer.paused_tps

    def test_drives_pipelined_ingester(self, tmp_path):
        # the whole point of the shared protocol: the pipelined path
        # runs a (fake) real-Kafka consumer without a broker in between
        src, _ = self.make()
        api = API(path=str(tmp_path))
        p = PipelinedIngester(api, "idx", src, schema=src.schema(),
                              batch_rows=2)
        assert p.run() == 5
        assert api.query("idx", "Count(Row(color=red))")[0] == 5


# -- satellite: HTTP push / stats ---------------------------------------------


@pytest.fixture
def stream_server():
    from pilosa_tpu.server import serve

    api = API()
    svc = api.enable_stream("idx", batch_rows=10, queue_depth=1,
                            max_backlog_rows=20)
    srv, thread = serve(api, port=0, background=True)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    yield base, api, svc
    srv.shutdown()
    api.disable_stream()


def _req(base, method, path, body=None):
    data = json.dumps(body).encode() if body is not None else None
    r = urllib.request.Request(base + path, data=data, method=method,
                               headers={"Content-Type":
                                        "application/json"})
    with urllib.request.urlopen(r) as resp:
        return resp.status, json.loads(resp.read())


class TestHTTPSurface:
    def test_push_and_stats(self, stream_server):
        base, api, svc = stream_server
        status, out = _req(base, "POST", "/index/idx/stream/push",
                           {"records": [{"id": 1}, {"id": 2}]})
        assert status == 200 and out["accepted"] == 2
        status, out = _req(base, "GET", "/internal/stats/stream")
        assert status == 200
        assert out["enabled"] and out["lag"] == 2
        svc.step()
        status, out = _req(base, "GET", "/internal/stats/stream")
        assert out["lag"] == 0 and out["rows"] == 2

    def test_push_429_when_saturated(self, stream_server):
        base, api, svc = stream_server
        _req(base, "POST", "/index/idx/stream/push",
             {"records": [{"id": i} for i in range(20)]})
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(base, "POST", "/index/idx/stream/push",
                 {"records": [{"id": 99}]})
        assert ei.value.code == 429

    def test_push_unknown_index_404(self, stream_server):
        base, api, svc = stream_server
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(base, "POST", "/index/nope/stream/push",
                 {"records": [{"id": 1}]})
        assert ei.value.code == 404

    def test_stats_disabled(self):
        from pilosa_tpu.server import serve

        api = API()
        srv, thread = serve(api, port=0, background=True)
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        try:
            status, out = _req(base, "GET", "/internal/stats/stream")
            assert status == 200 and out == {"enabled": False}
        finally:
            srv.shutdown()


# -- satellite: ingest_stall flight trigger -----------------------------------


class TestIngestStallTrigger:
    def make_plane(self, **kw):
        from pilosa_tpu.obs.health import HealthPlane

        return HealthPlane(interval_ms=10.0, clock=ManualClock(),
                           ingest_stall_s=5.0, **kw)

    def test_fires_on_saturation(self):
        hp = self.make_plane()
        fired = hp.flight.observe({"probes": {"stream": {
            "enabled": True, "saturated": True, "paused_s": 0.0}},
            "rates": {}})
        assert [b["trigger"] for b in fired] == ["ingest_stall"]
        assert "saturated" in fired[0]["reason"]

    def test_fires_on_sustained_pause(self):
        hp = self.make_plane()
        fired = hp.flight.observe({"probes": {"stream": {
            "enabled": True, "saturated": False, "paused_s": 9.5}},
            "rates": {}})
        assert [b["trigger"] for b in fired] == ["ingest_stall"]
        assert "paused" in fired[0]["reason"]

    def test_quiet_pipeline_does_not_fire(self):
        hp = self.make_plane()
        for probe in ({"enabled": False},
                      {"enabled": True, "saturated": False,
                       "paused_s": 0.1}):
            assert hp.flight.observe(
                {"probes": {"stream": probe}, "rates": {}}) == []

    def test_stream_probe_rides_api_samples(self, tmp_path):
        api = API(path=str(tmp_path))
        api.enable_stream("idx", batch_rows=10)
        try:
            hp = api.enable_health(clock=ManualClock())
            hp.clock.advance(1.0)
            hp.timeline.maybe_sample()
            sample = hp.timeline.window(None)[-1]
            assert sample["probes"]["stream"]["enabled"]
            assert sample["probes"]["stream"]["topic"] == "ingest"
        finally:
            api.disable_health()
            api.disable_stream()

    def test_probe_disabled_without_service(self):
        api = API()
        try:
            hp = api.enable_health(clock=ManualClock())
            hp.clock.advance(1.0)
            hp.timeline.maybe_sample()
            sample = hp.timeline.window(None)[-1]
            assert sample["probes"]["stream"] == {"enabled": False}
        finally:
            api.disable_health()


# -- satellite: [stream] config -----------------------------------------------


class TestStreamConfig:
    def test_toml_section_and_env(self, tmp_path):
        from pilosa_tpu.config import Config

        p = tmp_path / "c.toml"
        p.write_text("[stream]\nenabled = true\nindex = \"events\"\n"
                     "batch_rows = 4096\nqueue_depth = 3\n"
                     "ingest_stall_s = 2.5\n")
        cfg = Config.from_sources(
            toml_path=str(p),
            env={"PILOSA_TPU_STREAM_GROUP": "workers",
                 "PILOSA_TPU_STREAM_MAX_BACKLOG_ROWS": "500"})
        assert cfg.stream_enabled and cfg.stream_index == "events"
        assert cfg.stream_batch_rows == 4096
        assert cfg.stream_queue_depth == 3
        assert cfg.stream_ingest_stall_s == 2.5
        assert cfg.stream_group == "workers"  # env wins over default
        assert cfg.stream_max_backlog_rows == 500

    def test_service_from_config(self, tmp_path):
        from pilosa_tpu.config import Config

        cfg = Config()
        cfg.stream_batch_rows = 123
        cfg.stream_queue_depth = 4
        cfg.stream_group = "g9"
        api = API(path=str(tmp_path))
        svc = api.enable_stream("idx", config=cfg)
        try:
            assert svc.ingester.batch_rows == 123
            assert svc.ingester.queue_depth == 4
            assert svc.group == "g9"
            # backlog bound defaults from batch_rows * depth * 8
            assert svc.max_backlog_rows == 123 * 4 * 8
        finally:
            api.disable_stream()

    def test_health_from_config_maps_stall(self):
        from pilosa_tpu.config import Config
        from pilosa_tpu.obs.health import HealthPlane

        cfg = Config()
        cfg.stream_ingest_stall_s = 1.25
        hp = HealthPlane.from_config(cfg, clock=ManualClock())
        assert hp.flight.ingest_stall_s == 1.25

    def test_service_background_drain(self, tmp_path):
        api = API(path=str(tmp_path))
        svc = api.enable_stream("idx", batch_rows=10)
        try:
            svc.start(interval_s=0.01)
            svc.push([{"id": i} for i in range(25)])
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if svc.ingester.rows >= 25:
                    break
                time.sleep(0.01)
            assert svc.ingester.rows == 25
            assert api.query("idx", "Count(All())")[0] == 25
        finally:
            api.disable_stream()
