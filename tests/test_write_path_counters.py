"""Always-on counters of the write path, the idle chip and start-up:
ingest stages with ``PILOSA_TPU_DEVPROF`` unset, request-body bytes of
the import routes, reads standing behind a writer, programs built in the
process, and the server's start-up phases."""

import json
import threading
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import platform
from pilosa_tpu.api import API
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.server.http import serve


def _stage(name, stage):
    return M.REGISTRY.value(name, stage=stage)


@pytest.fixture
def served(tmp_path):
    api = API(str(tmp_path / "data"))
    srv, _ = serve(api, port=0, background=True)
    base = f"http://127.0.0.1:{srv.server_address[1]}"

    def post(path, body):
        data = body.encode() if isinstance(body, str) \
            else json.dumps(body).encode()
        with urllib.request.urlopen(urllib.request.Request(
                base + path, data=data, method="POST")) as resp:
            return json.loads(resp.read()), len(data)

    post("/index/i", {})
    post("/index/i/field/f", {"options": {"type": "set", "keys": True}})
    post("/index/i/field/v",
         {"options": {"type": "int", "min": 0, "max": 1000}})
    yield api, post
    srv.shutdown()
    srv.server_close()


@pytest.fixture
def compile_cache_configured(monkeypatch, tmp_path):
    """``platform.configure_compile_cache()`` as an entry point calls it,
    without moving this worker's compile cache: a directory set from
    outside is left alone, and the one setting it does touch is put
    back."""
    import jax

    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    platform.configure_compile_cache()
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_secs)


STAGES = ("decode", "key_translate", "lock_wait", "fragment_advance",
          "wal_commit", "checkpoint")


class TestIngestStagesAlwaysOn:
    def test_import_request_moves_every_stage_without_devprof(self, served):
        api, post = served
        api.holder.checkpoint_bytes = 1  # every commit checkpoints
        before = {s: _stage(M.METRIC_INGEST_STAGE_SECONDS, s)
                  for s in STAGES}
        cols = list(range(3000))
        post("/index/i/import", {"field": "f", "cols": cols,
                                 "rowKeys": [f"k{c % 5}" for c in cols]})
        still = [s for s in STAGES
                 if _stage(M.METRIC_INGEST_STAGE_SECONDS, s) <= before[s]]
        assert not still

    def test_body_bytes_and_wal_bytes_are_counted(self, served):
        _, post = served
        body0 = M.REGISTRY.value(M.METRIC_HTTP_REQUEST_BODY_BYTES,
                                 route="post_import_values")
        wal0 = _stage(M.METRIC_INGEST_STAGE_BYTES, "wal_commit")
        dec0 = _stage(M.METRIC_INGEST_STAGE_BYTES, "decode")
        cols = list(range(2000))
        _, sent = post("/index/i/import-values",
                       {"field": "v", "cols": cols,
                        "values": [c % 1000 for c in cols]})
        assert M.REGISTRY.value(M.METRIC_HTTP_REQUEST_BODY_BYTES,
                                route="post_import_values") == body0 + sent
        assert _stage(M.METRIC_INGEST_STAGE_BYTES, "decode") == dec0 + sent
        # two records (the values and _exists), 8 bytes a column each
        assert _stage(M.METRIC_INGEST_STAGE_BYTES, "wal_commit") - wal0 \
            > 2 * 8 * len(cols)

    def test_query_route_counts_no_body_bytes(self, served):
        _, post = served
        post("/index/i/query", "Count(Row(f=k1))")
        text = M.REGISTRY.prometheus_text()
        assert 'http_request_body_bytes_total{route="post_query"}' \
            not in text


class TestWriterWait:
    def test_read_behind_a_writer_moves_the_counter(self):
        api = API()
        api.create_index("i")
        api.create_field("i", "f")
        api.import_bits("i", "f", rows=[1, 1], cols=[1, 2])
        assert api.query("i", "Count(Row(f=1))") == [2]
        secs0 = M.REGISTRY.value(M.METRIC_STACK_WRITER_WAIT_SECONDS)
        n0 = M.REGISTRY.value(M.METRIC_STACK_WRITER_WAIT_COUNT)
        held, release = threading.Event(), threading.Event()

        def writer():
            # a write request mid-flight: the fragment has advanced and
            # the writer (or its checkpoint) still holds the lock
            with api.holder.write_lock:
                api.holder.index("i").field("f").import_bits([1], [3])
                held.set()
                release.wait(10)

        t = threading.Thread(target=writer)
        t.start()
        assert held.wait(10)
        threading.Timer(0.2, release.set).start()
        assert api.query("i", "Count(Row(f=1))") == [3]  # needs an advance
        t.join()
        assert M.REGISTRY.value(M.METRIC_STACK_WRITER_WAIT_COUNT) > n0
        assert M.REGISTRY.value(M.METRIC_STACK_WRITER_WAIT_SECONDS) \
            - secs0 >= 0.15

    def test_warm_read_takes_no_lock(self):
        api = API()
        api.create_index("i")
        api.create_field("i", "f")
        api.import_bits("i", "f", rows=[1], cols=[1])
        api.query("i", "Count(Row(f=1))")
        n0 = M.REGISTRY.value(M.METRIC_STACK_WRITER_WAIT_COUNT)
        assert api.query("i", "Count(Row(f=1))") == [1]
        assert M.REGISTRY.value(M.METRIC_STACK_WRITER_WAIT_COUNT) == n0


class TestProgramsBuilt:
    @staticmethod
    def _built():
        snap = M.REGISTRY.snapshot()["counters"]
        return sum(v for k, v in snap.items()
                   if k.startswith(M.METRIC_DEVICE_PROGRAMS_BUILT))

    def test_first_call_builds_repeat_does_not(self,
                                               compile_cache_configured):
        import jax
        import jax.numpy as jnp

        platform.configure_compile_cache()  # registers its listener once

        @jax.jit
        def fresh_program_of_this_test(x):
            return x * 3 + 1

        before = self._built()
        secs = M.REGISTRY.value(M.METRIC_DEVICE_PROGRAM_BUILD_SECONDS)
        fresh_program_of_this_test(jnp.arange(8)).block_until_ready()
        built = self._built() - before
        assert built >= 1
        assert M.REGISTRY.value(M.METRIC_DEVICE_PROGRAM_BUILD_SECONDS) > secs
        named = [k for k in M.REGISTRY.snapshot()["counters"]
                 if "fresh_program_of_this_test" in k]
        assert len(named) == 1 and 'source="' in named[0]
        again = self._built()
        fresh_program_of_this_test(jnp.arange(8)).block_until_ready()
        assert self._built() == again

    def test_first_query_builds_repeat_does_not(self,
                                                compile_cache_configured):
        api = API()
        api.create_index("pb")
        api.create_field("pb", "f")
        cols = np.arange(0, 4099, 3)
        api.import_bits("pb", "f", rows=cols % 11, cols=cols)
        q = "Count(Union(Row(f=1), Row(f=2), Row(f=3), Row(f=7)))"
        api.query("pb", q)
        after_first = self._built()
        assert after_first > 0
        assert api.query("pb", q) == api.query("pb", q)
        assert self._built() == after_first


class TestStartupPhases:
    def test_server_reports_every_phase_and_logs_one_line(
            self, tmp_path, monkeypatch, caplog, compile_cache_configured):
        import logging

        from pilosa_tpu.ctl import cli
        from pilosa_tpu.obs.logger import get_logger

        logger = get_logger()
        monkeypatch.setattr(logger, "handlers", list(logger.handlers))
        monkeypatch.setattr(logger, "level", logger.level)

        class Served(Exception):
            pass

        def fake_serve(api, on_listening=None, **kw):
            on_listening()
            raise Served

        monkeypatch.setattr("pilosa_tpu.server.http.serve", fake_serve)
        for phase in ("backend", "load_checkpoint", "wal_replay", "listen"):
            M.REGISTRY.gauge(M.METRIC_STARTUP_PHASE_SECONDS, -1.0,
                             phase=phase)
        with caplog.at_level(logging.INFO, logger="pilosa_tpu"), \
                pytest.raises(Served):
            cli.main(["server", "--port", "0",
                      "--data-dir", str(tmp_path / "d")])
        for phase in ("backend", "load_checkpoint", "wal_replay", "listen"):
            assert M.REGISTRY.value(M.METRIC_STARTUP_PHASE_SECONDS,
                                    phase=phase) >= 0
        line, = [ln for ln in caplog.messages if ln.startswith("start-up:")]
        assert all(p in line for p in ("backend", "load_checkpoint",
                                       "wal_replay", "listen"))

    def test_serve_calls_on_listening_once_the_socket_accepts(self):
        import socket

        seen = []

        def listening():
            seen.append(1)

        srv, _ = serve(API(), port=0, background=True,
                       on_listening=listening)
        try:
            assert seen == [1]
            socket.create_connection(srv.server_address, timeout=5).close()
        finally:
            srv.shutdown()
            srv.server_close()
