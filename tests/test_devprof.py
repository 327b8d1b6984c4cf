"""Kernel performance attribution plane (ISSUE 11): analytic cost
model over compiled op tapes, MFU/roofline profiles keyed on
(family, shape_bucket, mesh_epoch), per-stage ingest throughput, the
and the ``/internal/stats/kernels`` surface.

The invariants are the acceptance criteria: bit-identical query results
with the plane on vs off, exactly zero cost-model work while disabled,
and a profile with MFU/GB/s for every compiled family on a warmed
cluster.
"""

import json
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import platform
from pilosa_tpu.api import API
from pilosa_tpu.config import Config
from pilosa_tpu.obs import devprof, stages
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.shardwidth import SHARD_WIDTH

SHARDS = 2

# three distinct tapes -> three kernel families (two count, one plane)
QUERIES = [
    "Count(Row(f=1))",
    "Count(Intersect(Row(f=1), Row(g=1)))",
    "Intersect(Row(f=2), Row(g=2))",
]


def _fill(target, index="dk"):
    target.create_index(index)
    target.create_field(index, "f")
    target.create_field(index, "g")
    rows, cols = [], []
    for c in range(0, SHARDS * SHARD_WIDTH, SHARD_WIDTH // 16):
        rows.append((c // 64) % 5)
        cols.append(c)
    target.import_bits(index, "f", rows=rows, cols=cols)
    target.import_bits(index, "g", rows=[r % 3 for r in rows], cols=cols)
    return index


@pytest.fixture
def profiled():
    """Plane ON with clean accumulators; restores the ambient state so
    the suite behaves identically under the PILOSA_TPU_DEVPROF=1 lane."""
    was = devprof.ENABLED
    devprof.enable()
    devprof.reset()
    yield
    devprof.reset()
    devprof.enable() if was else devprof.disable()


@pytest.fixture
def unprofiled():
    was = devprof.ENABLED
    devprof.disable()
    devprof.reset()
    yield
    devprof.enable() if was else devprof.disable()


# ---------------------------------------------------------------------------
# Analytic cost model
# ---------------------------------------------------------------------------


class TestCostModel:
    def test_count_tape_cost(self):
        # 1 op + popcount pass = 2 word passes * 32 lanes * 1024 words;
        # 2 leaf planes read * 4B * 1024 + 8B count scalar
        assert devprof.tape_cost("count", (("and", 0, 1),), 2, False,
                                 1024) == (65536.0, 8200.0)

    def test_plane_tape_cost_counts_scratch_write(self):
        flops, hbm = devprof.tape_cost(
            "plane", (("or", 0, 1), ("and", 2, 3)), 3, False, 512)
        assert flops == 32.0 * 2 * 512
        assert hbm == 4.0 * (3 + 1) * 512  # +1 scratch write, no scalar

    def test_mask_adds_one_pass_and_one_plane(self):
        flops, hbm = devprof.tape_cost("count", (("and", 0, 1),), 2,
                                       True, 1024)
        assert flops == 32.0 * 3 * 1024   # op + mask-AND + popcount
        assert hbm == 4.0 * 3 * 1024 + 8.0

    def test_cost_evals_counter_increments(self):
        before = devprof.cost_evals()
        devprof.tape_cost("count", (("or", 0, 1),), 2, False, 64)
        assert devprof.cost_evals() == before + 1

    def test_family_name_structure(self):
        fam = devprof.family_name("count", (("and", 0, 1),), 2, False)
        assert fam.startswith("count/2l/and1#") and len(fam) > 14
        # op mix is sorted and counted; the mask is tagged
        fam2 = devprof.family_name(
            "plane", (("or", 0, 1), ("and", 2, 3), ("or", 4, 5)), 3, True)
        assert fam2.startswith("plane/3l/and1+or2/m#")
        # distinct tape structure -> distinct digest
        a = devprof.family_name("count", (("and", 0, 1),), 2, False)
        b = devprof.family_name("count", (("and", 1, 0),), 2, False)
        assert a != b

    def test_shape_bucket_next_pow2(self):
        assert devprof.shape_bucket(1) == 1
        assert devprof.shape_bucket(3) == 4
        assert devprof.shape_bucket(1024) == 1024
        assert devprof.shape_bucket(1025) == 2048

    def test_pallas_mm_cost(self):
        # C[2, 14] matmul contracting 32*4096 lanes: 2*2*14*32*4096
        # FLOPs; HBM = (2+14) packed planes * 4B * 4096 + int32 result
        flops, hbm = devprof.tape_cost(
            "pallas", (("mm", 2, 14),), 16, False, 4096)
        assert flops == 2.0 * 2 * 14 * 32 * 4096
        assert hbm == 4.0 * 16 * 4096 + 4.0 * 2 * 14

    def test_pallas_cmp_cost(self):
        # depth=13 planes x 1 constant side: (6*13*1 + 8) word-ops * 32
        # lanes * 512 words; HBM reads exists+sign+result + 13 mags
        flops, hbm = devprof.tape_cost(
            "pallas", (("cmp", 13, 1),), 15, False, 512)
        assert flops == 32.0 * (6 * 13 + 8) * 512
        assert hbm == 4.0 * (3 + 13) * 512

    def test_pallas_scatter_cost(self):
        flops, hbm = devprof.tape_cost(
            "pallas", (("scatter", 300, 8),), 2, False, 8192)
        assert flops == 32.0 * 2 * 8192   # or-merge + popcount-andnot
        assert hbm == 4.0 * 3 * 8192      # planes + updates in, merged out

    def test_pallas_unknown_family_raises(self):
        with pytest.raises(ValueError):
            devprof.tape_cost("pallas", (("bogus", 1, 1),), 1, False, 64)

    def test_pallas_family_name(self):
        fam = devprof.family_name("pallas", (("mm", 2, 14),), 16, False)
        assert fam.startswith("pallas/16l/mm1#")


# ---------------------------------------------------------------------------
# KernelProfileRegistry + IngestAccounting
# ---------------------------------------------------------------------------


class TestKernelProfileRegistry:
    def _reg(self):
        return devprof.KernelProfileRegistry()

    def test_accumulate_and_roofline_snapshot(self):
        reg = self._reg()
        ent = reg.entry_for("count", (("and", 0, 1),), 2, False, 1024, 0)
        reg.record(ent, 0.001, 0.002)
        reg.record(ent, 0.001, 0.002)
        (row,) = reg.snapshot()
        assert row["dispatches"] == 2
        assert row["device_seconds"] == pytest.approx(0.006)
        assert row["flops"] == pytest.approx(2 * 65536.0)
        assert row["hbm_bytes"] == pytest.approx(2 * 8200.0)
        assert row["mfu_pct"] > 0 and row["achieved_gbps"] > 0
        assert row["us_per_dispatch"] == pytest.approx(3000.0)
        # bitmap tapes sit below any ridge point: memory-bound
        assert row["intensity_flops_per_byte"] == pytest.approx(
            65536.0 / 8200.0, rel=1e-3)
        assert row["roofline_bound"] == "memory"

    def test_same_family_different_bucket_split(self):
        reg = self._reg()
        reg.record(reg.entry_for("count", (("and", 0, 1),), 2, False,
                                 1024, 0), 0.001, 0.0)
        reg.record(reg.entry_for("count", (("and", 0, 1),), 2, False,
                                 4096, 0), 0.002, 0.0)
        rows = reg.snapshot()
        assert len(rows) == 2
        assert {r["shape_bucket"] for r in rows} == {1024, 4096}
        # sorted by device time, biggest first
        assert rows[0]["device_seconds"] >= rows[1]["device_seconds"]

    def test_mesh_epoch_keys_profiles_apart(self):
        reg = self._reg()
        reg.record(reg.entry_for("count", (("and", 0, 1),), 2, False,
                                 1024, 0), 0.001, 0.0)
        reg.record(reg.entry_for("count", (("and", 0, 1),), 2, False,
                                 1024, 1), 0.001, 0.0)
        assert reg.profile_count() == 2

    def test_call_cache_reuses_allocations(self):
        reg = self._reg()
        args = ("count", (("and", 0, 1),), 2, False, 1024, 0)
        e1 = reg.entry_for(*args)
        allocs = reg.allocations
        assert allocs == 2  # one profile + one call-cache entry
        assert reg.entry_for(*args) is e1
        assert reg.allocations == allocs

    def test_unattributed_dispatch_lands_in_other(self):
        reg = self._reg()
        reg.record(None, 0.001, 0.002)
        assert reg.other_dispatches == 1
        assert reg.other_device_s == pytest.approx(0.003)
        assert reg.snapshot() == []  # "other" is not a kernel profile

    def test_h2d_accounting(self):
        reg = self._reg()
        reg.record_h2d(1 << 20, 0.001)
        h = reg.h2d_json()
        assert h["copies"] == 1 and h["bytes"] == 1 << 20
        assert h["achieved_gbps"] == pytest.approx(
            (1 << 20) / 0.001 / 1e9, rel=1e-3)

    def test_snapshot_limit(self):
        reg = self._reg()
        for i in range(5):
            reg.record(reg.entry_for("count", (("and", 0, 1),), 2, False,
                                     1 << (6 + i), 0), 0.001 * (i + 1), 0.0)
        assert len(reg.snapshot(limit=3)) == 3

    def test_ingest_accounting_rates(self):
        acc = stages.IngestAccounting()
        acc.record("parse", 0.5, rows=1000)
        acc.record("parse", 0.5, rows=1000)
        acc.record("wal_commit", 0.25, nbytes=1 << 20)
        snap = acc.snapshot()
        assert snap["parse"]["rows"] == 2000
        assert snap["parse"]["batches"] == 2
        assert snap["parse"]["rows_per_s"] == pytest.approx(2000.0)
        assert snap["wal_commit"]["bytes_per_s"] == pytest.approx(
            (1 << 20) / 0.25)

    def test_ingest_accounting_publishes_counters_not_rates(self):
        # the derived rate gauges are gone: a rate is a scrape delta of
        # the counters they were computed from
        before = M.REGISTRY.value(M.METRIC_INGEST_STAGE_ROWS, stage="parse")
        stages.IngestAccounting().record("parse", 0.5, rows=1000)
        assert M.REGISTRY.value(M.METRIC_INGEST_STAGE_ROWS,
                                stage="parse") == before + 1000
        text = M.REGISTRY.prometheus_text()
        assert "ingest_stage_seconds_total" in text
        assert "ingest_stage_rows_per_s" not in text
        assert "ingest_stage_bytes_per_s" not in text


# ---------------------------------------------------------------------------
# Gating: zero work disabled, attribution enabled, identical results
# ---------------------------------------------------------------------------


class TestGating:
    def test_disabled_means_zero_cost_model_work(self, unprofiled):
        api = API()
        _fill(api)
        evals = devprof.cost_evals()
        allocs = devprof.KERNELS.allocations
        for q in QUERIES:
            api.query("dk", q)
        assert devprof.cost_evals() == evals
        assert devprof.KERNELS.allocations == allocs
        assert devprof.KERNELS.profile_count() == 0
        assert platform._DISPATCH_HOOK is None
        assert platform._H2D_HOOK is None
        assert devprof.stats_json() == {"enabled": False}

    def test_enabled_attributes_every_compiled_family(self, profiled):
        api = API()
        _fill(api)
        for q in QUERIES:
            api.query("dk", q)
        rows = devprof.KERNELS.snapshot()
        # three distinct tapes -> three families, all with device time
        assert len(rows) >= 3
        kinds = {r["family"].split("/")[0] for r in rows}
        assert kinds == {"count", "plane"}
        for r in rows:
            assert r["dispatches"] > 0
            assert r["device_seconds"] > 0
            assert r["mfu_pct"] > 0
            assert r["achieved_gbps"] > 0
        s = devprof.stats_json()
        assert s["enabled"] and s["device_kind"]
        assert s["peak_tflops"] > 0 and s["peak_gbps"] > 0
        assert s["cost_evals"] >= 3

    def test_results_bit_identical_on_vs_off(self, unprofiled):
        api = API()
        _fill(api)
        off = [api.query_json("dk", q) for q in QUERIES]
        devprof.enable()
        try:
            on = [api.query_json("dk", q) for q in QUERIES]
        finally:
            devprof.disable()
        assert json.dumps(on, sort_keys=True) \
            == json.dumps(off, sort_keys=True)

    def test_peak_override_env(self, profiled, monkeypatch):
        monkeypatch.setenv("PILOSA_TPU_DEVPROF_PEAK_TFLOPS", "2.0")
        monkeypatch.setenv("PILOSA_TPU_DEVPROF_PEAK_GBPS", "50.0")
        assert devprof.peaks() == (2.0, 50.0)

    def test_peaks_keyed_by_device_kind_unknown_raises(self):
        # the chip reports itself as "TPU v5 lite" (one v5e chip)
        assert devprof.peaks_for("TPU v5 lite") == (393.0, 819.0)
        with pytest.raises(LookupError, match="TPU v9"):
            devprof.peaks_for("TPU v9")


# ---------------------------------------------------------------------------
# Hook attribution details
# ---------------------------------------------------------------------------


class TestHooks:
    def test_h2d_attributed_to_ingest_only_in_scatter(self, profiled,
                                                      monkeypatch):
        from pilosa_tpu.core.fragment import SetFragment

        host = np.zeros(1024, dtype=np.uint32)
        platform.h2d_copy(host)  # a read-side staging copy
        assert devprof.KERNELS.h2d_copies == 1
        assert "h2d_copy" not in stages.INGEST.snapshot()
        # the ingest stage is the device scatter's own upload, taken at
        # its call site with or without the kernel-profiling hooks
        monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
        monkeypatch.delenv("PILOSA_TPU_NO_PALLAS", raising=False)
        rng = np.random.default_rng(5)
        SetFragment(0, words=1 << 9).set_many(
            rng.integers(0, 8, size=500), rng.integers(0, 1 << 14, size=500))
        assert devprof.KERNELS.h2d_copies >= 2
        assert stages.INGEST.snapshot()["h2d_copy"]["bytes"] > 0

    def test_kernel_scope_nests_and_restores(self, profiled):
        outer = ("count", (("and", 0, 1),), 2, False, 64)
        inner = ("plane", (("or", 0, 1),), 2, False, 64)
        with devprof.kernel_scope(*outer):
            ent_outer = devprof._TLS.kernel
            with devprof.kernel_scope(*inner):
                assert devprof._TLS.kernel is not ent_outer
            assert devprof._TLS.kernel is ent_outer
        assert getattr(devprof._TLS, "kernel", None) is None


# ---------------------------------------------------------------------------
# Ingest stage accounting through the real pipeline
# ---------------------------------------------------------------------------


class TestIngestStages:
    CSV = "id,city__S,pop__I\n" + "\n".join(
        f"{i},c{i % 7},{1000 + i}" for i in range(300))

    def test_columnar_ingest_populates_stages(self, profiled, tmp_path):
        from pilosa_tpu.ingest.ingest import Ingester
        from pilosa_tpu.ingest.source import CSVSource

        api = API(str(tmp_path))  # durable: WAL commits are real
        src = CSVSource(self.CSV, inline=True)
        n = Ingester(api, "cities", src).run()
        assert n == 300
        snap = stages.INGEST.snapshot()
        assert snap["parse"]["rows"] == 300
        assert snap["parse"]["rows_per_s"] > 0
        # city__S is keyed -> bulk translation is timed
        assert snap["key_translate"]["rows"] > 0
        assert snap["fragment_advance"]["rows"] > 0
        assert snap["wal_commit"]["bytes"] > 0
        assert snap["wal_commit"]["bytes_per_s"] > 0

    def test_batch_path_records_stages_too(self, profiled):
        from pilosa_tpu.ingest.datagen import scenario
        from pilosa_tpu.ingest.ingest import Ingester

        # record-stream sources (datagen, Kafka-style) ride the Batch
        # path: no whole-file parse stage, but fragment advance is timed
        api = API()
        Ingester(api, "cust", scenario("customer", rows=100)).run()
        snap = stages.INGEST.snapshot()
        assert snap["fragment_advance"]["rows"] > 0

    def test_disabled_devprof_still_records_ingest_stages(self, unprofiled,
                                                          tmp_path):
        # PILOSA_TPU_DEVPROF switches the kernel cost model only; the
        # ingest stage counters are always on
        from pilosa_tpu.ingest.ingest import Ingester
        from pilosa_tpu.ingest.source import CSVSource

        api = API(str(tmp_path))
        Ingester(api, "cities", CSVSource(self.CSV, inline=True)).run()
        snap = stages.INGEST.snapshot()
        for stage in ("parse", "key_translate", "lock_wait",
                      "fragment_advance", "wal_commit"):
            assert snap[stage]["seconds"] > 0, stage
        assert snap["parse"]["rows"] == 300
        assert snap["wal_commit"]["bytes"] > 0
        assert devprof.KERNELS.profile_count() == 0


# ---------------------------------------------------------------------------
# Serving surfaces: /internal/stats/kernels + the health-plane probe
# ---------------------------------------------------------------------------


class TestServing:
    def test_stats_kernels_on_warmed_cluster(self, profiled):
        from pilosa_tpu.cluster import LocalCluster

        with LocalCluster(3) as c:
            _fill(c.coordinator)
            for _ in range(2):  # warm: second pass hits compiled programs
                for q in QUERIES:
                    c.coordinator.query("dk", q)
            uri = c.coordinator.node.uri
            with urllib.request.urlopen(
                    uri + "/internal/stats/kernels") as r:
                payload = json.loads(r.read())
        assert payload["enabled"] is True
        assert payload["ridge_flops_per_byte"] > 0
        fams = {k["family"] for k in payload["kernels"]}
        assert len(fams) >= len(QUERIES)
        for k in payload["kernels"]:
            assert k["mfu_pct"] > 0
            assert k["achieved_gbps"] > 0
            assert k["roofline_bound"] in ("memory", "compute")

    def test_stats_kernels_disabled_payload(self, unprofiled):
        from pilosa_tpu.server.http import serve

        api = API()
        srv, _ = serve(api, port=0, background=True)
        try:
            host, port = srv.server_address[:2]
            with urllib.request.urlopen(
                    f"http://{host}:{port}/internal/stats/kernels") as r:
                assert json.loads(r.read()) == {"enabled": False}
        finally:
            srv.shutdown()
            srv.server_close()

    def test_timeline_probe_rides_health_samples(self, profiled):
        api = API()
        _fill(api)
        api.enable_health(config=Config())
        for q in QUERIES:
            api.query("dk", q)
        samp = api.health.timeline.sample()
        probe = samp["probes"]["kernels"]
        assert probe["enabled"] is True
        assert probe["kernels"], probe
        assert len(probe["kernels"]) <= 8  # bundles are size-bounded
        api.disable_health()

    def test_timeline_probe_disabled(self, unprofiled):
        assert devprof.timeline_probe() == {"enabled": False}

