"""Crash-consistent recovery plane: segmented WAL + checkpoints,
kill-point crash injection, replica catch-up via log shipping.

The core invariant, asserted from three directions:

* a crash at ANY byte of the write path leaves a snapshot + WAL tail
  that replays to exactly the last flushed commit (kill-point matrix vs
  an uncrashed oracle);
* the same WAL tail applied twice produces identical planes (replay
  idempotence, which is what makes fuzzy checkpoints and catch-up
  overlap safe);
* a lagging replica catches up over /internal/recovery/{snapshot,wal}
  to answer bit-identically, with mid-catch-up writes queued.

``PILOSA_TPU_CRASH_SEED`` (scripts/tier1.sh crash lane) steers the
seeded kill point the same way PILOSA_TPU_FAULT_SEED steers RPC faults.
"""

import os

import numpy as np
import pytest

from pilosa_tpu.api import API
from pilosa_tpu.cluster.harness import LocalCluster
from pilosa_tpu.cluster.resilience import FaultPlan
from pilosa_tpu.config import Config
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage.recovery import (
    CHECKPOINT_META, CRASH_SITES, CrashPlan, RecoveryManager,
    SimulatedCrash, abandon_holder, attach_crash_plan, crash_workload,
    filter_record, oracle_checksums, read_checkpoint_meta, record_shards,
    run_crash_point, write_checkpoint_meta,
)
from pilosa_tpu.storage.wal import WAL, iter_frames


# -- segmented WAL -----------------------------------------------------------


class TestSegmentedWAL:
    def test_rotation_produces_numbered_segments(self, tmp_path):
        w = WAL(str(tmp_path / "wal.log"), segment_bytes=64)
        lsns = [w.append(("set_bit", "f", "", i, i)) for i in range(8)]
        w.flush()
        assert lsns == sorted(lsns) and len(set(lsns)) == 8
        segs = sorted(p.name for p in tmp_path.iterdir()
                      if p.name.startswith("wal.log."))
        assert len(segs) > 1  # 64-byte segments force rotation
        assert segs[0] == "wal.log.00000001"
        assert [r for r in w.records()] == \
            [("set_bit", "f", "", i, i) for i in range(8)]
        w.close()

    def test_lsn_survives_reopen_and_truncate(self, tmp_path):
        p = str(tmp_path / "wal.log")
        w = WAL(p, segment_bytes=64)
        for i in range(5):
            w.append(("set_bit", "f", "", 0, i))
        w.flush()
        top = w.last_lsn
        w.close()
        w2 = WAL(p, segment_bytes=64)
        assert w2.last_lsn == top
        old_seqs = {int(q.name.rsplit(".", 1)[1]) for q in tmp_path.iterdir()}
        w2.truncate()
        assert w2.last_lsn == top  # the counter NEVER resets
        new_seqs = {int(q.name.rsplit(".", 1)[1]) for q in tmp_path.iterdir()}
        assert min(new_seqs) > max(old_seqs)  # fresh segment, later seq
        assert w2.append(("set_bit", "f", "", 0, 9)) == top + 1
        w2.close()

    def test_prune_drops_only_wholly_covered_segments(self, tmp_path):
        w = WAL(str(tmp_path / "wal.log"), segment_bytes=64)
        lsns = [w.append(("set_bit", "f", "", 0, i)) for i in range(9)]
        w.flush()
        n_before = len(list(tmp_path.iterdir()))
        assert n_before > 2
        mid = lsns[4]
        w.prune(mid)
        # every record above the checkpoint LSN must still replay
        kept = [lsn for lsn, _rec, _n in w.replay(after_lsn=mid)]
        assert kept == lsns[5:]
        # and pruning everything leaves the (empty) active segment only
        w.prune(w.last_lsn)
        assert w.record_bytes == 0
        assert list(w.records()) == []
        w.close()

    def test_legacy_single_file_adopted_as_segment(self, tmp_path):
        p = str(tmp_path / "wal.log")
        w = WAL(p)
        w.append(("set_bit", "f", "", 1, 2))
        w.flush()
        w.close()
        # simulate a pre-segmentation install: one bare wal.log file
        os.rename(w.path, p)
        for q in tmp_path.iterdir():
            assert q.name == "wal.log"
        w2 = WAL(p)
        assert list(w2.records()) == [("set_bit", "f", "", 1, 2)]
        assert not os.path.exists(p)  # renamed into the segment scheme
        w2.close()

    def test_legacy_ii_framed_log_converted_not_truncated(self, tmp_path):
        """Regression: a TRUE pre-segmentation log uses <II> framing
        (crc over payload alone, no LSN). Renaming it untouched fails
        every new-framing CRC, scans as torn at byte 0, and the first
        repair() silently truncates all its committed records; adoption
        must rewrite it with synthesized LSNs instead."""
        import pickle
        import struct
        import zlib

        recs = [("set_bit", "f", "", r, r + 1) for r in range(5)]
        p = str(tmp_path / "wal.log")
        with open(p, "wb") as f:
            for rec in recs:
                payload = pickle.dumps(rec, protocol=5)
                f.write(struct.pack("<II", zlib.crc32(payload),
                                    len(payload)) + payload)
        w = WAL(p)
        assert not os.path.exists(p)  # converted into the segment scheme
        assert list(w.records()) == recs
        assert [lsn for lsn, _r, _n in w.replay(0)] == [1, 2, 3, 4, 5]
        w.repair()  # a no-op: the converted segment is intact
        assert list(w.records()) == recs
        assert w.append(("set_bit", "f", "", 9, 9)) == 6  # LSNs continue
        w.flush()
        w.close()
        w2 = WAL(p)  # stable across a second open
        assert len(list(w2.records())) == 6
        w2.close()

    def test_legacy_log_torn_tail_keeps_intact_prefix(self, tmp_path):
        import pickle
        import struct
        import zlib

        recs = [("set_bit", "f", "", r, r) for r in range(3)]
        p = str(tmp_path / "wal.log")
        with open(p, "wb") as f:
            for rec in recs:
                payload = pickle.dumps(rec, protocol=5)
                f.write(struct.pack("<II", zlib.crc32(payload),
                                    len(payload)) + payload)
            f.write(b"\x01\x02\x03")  # torn mid-append legacy header
        w = WAL(p)
        assert list(w.records()) == recs
        w.close()


class TestTornTailVsMarker:
    def test_byte_exact_torn_tail_drops_only_last_write(self, tmp_path):
        """Regression for the zero-payload/torn-header conflation: a tear
        at any byte of the final frame must drop that frame only."""
        recs = [("set_bit", "f", "", 0, 1), ("import_bits", "f", [1], [9])]
        p = str(tmp_path / "wal.log")
        w = WAL(p)
        w.append(recs[0])
        w.flush()
        size_first = os.path.getsize(w.path)
        w.append(recs[1])
        w.flush()
        active = w.path
        w.close()
        with open(active, "rb") as f:
            blob = f.read()
        assert size_first < len(blob)
        for cut in range(size_first, len(blob)):  # every torn byte count
            with open(active, "wb") as f:
                f.write(blob[:cut])
            w2 = WAL(p)
            assert list(w2.records()) == recs[:1], f"cut at {cut} bytes"
            w2.close()
        # restoring the full file yields both again
        with open(active, "wb") as f:
            f.write(blob)
        w3 = WAL(p)
        assert list(w3.records()) == recs
        w3.close()

    def test_segment_markers_do_not_stop_replay(self, tmp_path):
        """Each segment opens with a zero-payload marker frame; replay
        must skip them, not treat them as a tear (the old behavior)."""
        w = WAL(str(tmp_path / "wal.log"), segment_bytes=1)  # rotate always
        recs = [("set_bit", "f", "", 0, i) for i in range(4)]
        for r in recs:
            w.append(r)
        w.flush()
        assert len(list(tmp_path.iterdir())) >= 4  # one record per segment
        assert list(w.records()) == recs
        w.close()

    def test_corrupt_interior_byte_stops_at_tear(self, tmp_path):
        w = WAL(str(tmp_path / "wal.log"))
        w.append(("set_bit", "f", "", 0, 1))
        w.append(("set_bit", "f", "", 0, 2))
        w.flush()
        active = w.path
        w.close()
        with open(active, "r+b") as f:
            f.seek(20)  # inside the first record's frame (after marker)
            b = f.read(1)
            f.seek(20)
            f.write(bytes([b[0] ^ 0xFF]))
        assert list(WAL(str(tmp_path / "wal.log")).records()) == []

    def test_repair_truncates_to_valid_prefix(self, tmp_path):
        p = str(tmp_path / "wal.log")
        w = WAL(p)
        w.append(("set_bit", "f", "", 0, 1))
        w.flush()
        good = os.path.getsize(w.path)
        active = w.path
        w.close()
        with open(active, "ab") as f:
            f.write(b"\x01\x02\x03")  # torn garbage
        w2 = WAL(p)
        w2.repair()
        assert os.path.getsize(active) == good
        assert list(w2.records()) == [("set_bit", "f", "", 0, 1)]
        w2.close()


class TestTailShipping:
    def test_tail_bytes_round_trips_through_iter_frames(self, tmp_path):
        w = WAL(str(tmp_path / "wal.log"), segment_bytes=96)
        recs = [("import_bits", "f", [i], [i * 3]) for i in range(6)]
        lsns = [w.append(r) for r in recs]
        w.flush()
        data, last, more = w.tail_bytes(0)
        assert not more and last == lsns[-1]
        assert [r for _lsn, r in iter_frames(data)] == recs
        # a mid-stream cursor ships only the strictly-later records
        data2, last2, _ = w.tail_bytes(lsns[2])
        assert [r for _l, r in iter_frames(data2)] == recs[3:]
        assert last2 == lsns[-1]
        w.close()

    def test_tail_bytes_paginates(self, tmp_path):
        w = WAL(str(tmp_path / "wal.log"), segment_bytes=96)
        recs = [("import_bits", "f", [i], [i]) for i in range(6)]
        for r in recs:
            w.append(r)
        w.flush()
        got, since, rounds = [], 0, 0
        while True:
            data, last, more = w.tail_bytes(since, max_bytes=64)
            got.extend(r for _l, r in iter_frames(data))
            rounds += 1
            since = last
            if not more:
                break
        assert got == recs and rounds > 1
        w.close()

    def test_iter_frames_rejects_corrupt_stream(self):
        with pytest.raises(ValueError):
            list(iter_frames(b"\x00" * 20))


# -- record shard filtering ---------------------------------------------------


class TestRecordFiltering:
    def test_record_shards(self):
        W = SHARD_WIDTH
        # set_bit records are (op, field, row, col, ts) — col at [3]
        assert record_shards(("set_bit", "f", 3, W + 1, None), W) == {1}
        assert record_shards(("clear_bit", "f", 3, 2 * W), W) == {2}
        assert record_shards(("import_bits", "f", [1, 2], [0, 2 * W]), W) \
            == {0, 2}
        assert record_shards(("set_values", "f", [0, W], [7, 8]), W) == {0, 1}
        assert record_shards(("row_plane", "f", b"", 5), W) == {5}
        assert record_shards(("clear_value", "f", W + 3), W) == {1}
        assert record_shards(("df_changeset", "t", 2, {}), W) == {2}
        assert record_shards(("delete_field", "f"), W) is None

    def test_filter_record_subsets_pairwise(self):
        W = SHARD_WIDTH
        rec = ("import_bits", "f", [1, 2, 3], [0, W, 2 * W])
        out = filter_record(rec, lambda s: s == 1, W)
        assert out == ("import_bits", "f", [2], [W])
        rec2 = ("set_values", "f", [0, W], [7, 8])
        assert filter_record(rec2, lambda s: s == 0, W) \
            == ("set_values", "f", [0], [7])
        assert filter_record(rec, lambda s: s == 9, W) is None
        # index-wide records always pass
        assert filter_record(("clear_row", "f", "", 3), lambda s: False, W) \
            == ("clear_row", "f", "", 3)


# -- checkpoint metadata ------------------------------------------------------


class TestCheckpointMeta:
    def test_roundtrip_and_missing(self, tmp_path):
        assert read_checkpoint_meta(str(tmp_path)) == 0
        assert read_checkpoint_meta(None) == 0
        write_checkpoint_meta(str(tmp_path), 42)
        assert read_checkpoint_meta(str(tmp_path)) == 42
        write_checkpoint_meta(str(tmp_path), 43)  # atomic replace
        assert read_checkpoint_meta(str(tmp_path)) == 43

    def test_checkpoint_stamps_lsn_and_prunes(self, tmp_path):
        api = API(str(tmp_path))
        api.create_index("i")
        api.create_field("i", "f")
        api.import_bits("i", "f", rows=[0, 1], cols=[3, 9])
        idx = api.holder.index("i")
        assert idx.wal.record_bytes > 0
        api.save()  # checkpoint: snapshot + meta + prune
        assert idx.wal.record_bytes == 0
        meta = os.path.join(api.holder._index_path("i"), CHECKPOINT_META)
        assert os.path.isfile(meta)
        assert read_checkpoint_meta(api.holder._index_path("i")) \
            == idx.wal.last_lsn

    def test_recovery_replays_only_above_checkpoint(self, tmp_path):
        api = API(str(tmp_path))
        api.create_index("i")
        api.create_field("i", "f")
        api.import_bits("i", "f", rows=[0], cols=[1])
        api.save()
        api.import_bits("i", "f", rows=[1], cols=[2])  # tail, not pruned
        want = api.checksum()
        api.holder.flush_wals()
        del api
        api2 = API(str(tmp_path))
        assert api2.checksum() == want
        assert api2.query("i", "Row(f=1)")[0].columns == [2]


# -- kill-point crash injection ----------------------------------------------


def _assert_oracle_prefix(result, oracle):
    """A crash may lose unacked work, never acked work, and never leave
    a state that is not an exact committed prefix."""
    assert result["checksum"] in oracle, "recovered state not a prefix"
    k = oracle.index(result["checksum"])
    assert k >= result["acked"], \
        f"acked batch lost: recovered prefix {k} < acked {result['acked']}"


class TestCrashInjection:
    # 5 sites x 6 hit counts (checkpoint-per-commit arms the savez and
    # checkpoint sites) + 6 pure-WAL points below = 36 kill points.
    @pytest.mark.parametrize("site", CRASH_SITES)
    @pytest.mark.parametrize("at", [1, 2, 3, 4, 5, 6])
    def test_kill_point_matrix(self, tmp_path, site, at):
        batches = crash_workload(n_batches=6)
        oracle = oracle_checksums(str(tmp_path), batches)
        plan = CrashPlan().kill(site, at=at)
        res = run_crash_point(str(tmp_path), plan, batches,
                              checkpoint_bytes=1)
        _assert_oracle_prefix(res, oracle)
        if not res["crashed"]:  # the site never reached its hit count
            assert res["checksum"] == oracle[-1]

    @pytest.mark.parametrize("site", ["wal.append", "wal.flush"])
    @pytest.mark.parametrize("at", [1, 2, 3])
    def test_kill_point_no_checkpoint(self, tmp_path, site, at):
        """The WAL sites again, without per-commit checkpoints: the tail
        alone must carry recovery."""
        batches = crash_workload(n_batches=6, seed=1)
        oracle = oracle_checksums(str(tmp_path), batches)
        res = run_crash_point(str(tmp_path), CrashPlan().kill(site, at=at),
                              batches)
        assert res["crashed"]
        _assert_oracle_prefix(res, oracle)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
    def test_seeded_crash_points(self, tmp_path, seed):
        """Seed-derived plans (the tier1.sh crash lane dialect): same
        seed, same kill point, forever."""
        batches = crash_workload(n_batches=6, seed=seed)
        oracle = oracle_checksums(str(tmp_path), batches)
        plan = CrashPlan.seeded(seed)
        assert plan._arms == CrashPlan.seeded(seed)._arms  # deterministic
        res = run_crash_point(str(tmp_path), plan, batches,
                              checkpoint_bytes=1)
        _assert_oracle_prefix(res, oracle)

    def test_env_seeded_plan(self, tmp_path):
        """The crash lane sets PILOSA_TPU_CRASH_SEED; default runs use a
        fixed fallback so the test always exercises a real plan."""
        plan = CrashPlan.from_env() or CrashPlan.seeded("lane-default")
        batches = crash_workload(n_batches=6, seed=9)
        oracle = oracle_checksums(str(tmp_path), batches)
        res = run_crash_point(str(tmp_path), plan, batches,
                              checkpoint_bytes=1)
        _assert_oracle_prefix(res, oracle)

    def test_from_env_parses(self, monkeypatch):
        monkeypatch.delenv("PILOSA_TPU_CRASH_SEED", raising=False)
        assert CrashPlan.from_env() is None
        monkeypatch.setenv("PILOSA_TPU_CRASH_SEED", "7")
        plan = CrashPlan.from_env()
        assert plan is not None and plan._arms == CrashPlan.seeded("7")._arms

    def test_dead_plan_noops_instead_of_rearming(self):
        plan = CrashPlan().kill("wal.append", at=1)
        with pytest.raises(SimulatedCrash):
            plan.fire("wal.append")
        assert plan.dead and plan.fired == ("wal.append", 1)
        # the dead 'process' performs no IO: every later fire says skip
        assert plan.fire("wal.append") is False
        assert plan.fire("wal.flush") is False

    def test_abandon_holder_loses_buffered_bytes(self, tmp_path):
        """The harness's crash fidelity: unflushed python-buffered bytes
        must NOT survive abandon + reopen (a plain close would flush)."""
        api = API(str(tmp_path))
        api.create_index("ci", {"trackExistence": False})
        api.create_field("ci", "f")
        api.save()
        idx = api.holder.index("ci")
        idx.wal.sync = "never"  # keep bytes in the BufferedWriter
        with api.holder.write_lock:
            idx.wal.append(("set_bit", "f", "", 0, 1))
        abandon_holder(api.holder)
        api2 = API(str(tmp_path))
        assert api2.query("ci", "Row(f=0)")[0].columns == []


# -- replay idempotence -------------------------------------------------------


class TestReplayIdempotence:
    def _source(self, path):
        api = API(path)
        api.create_index("i", {"keys": True})
        api.create_field("i", "f")
        api.create_field("i", "b", {"type": "int", "min": 0, "max": 1000})
        api.import_bits("i", "f", rows=[0, 1, 0], cols=[3, 9, SHARD_WIDTH])
        api.query("i", "Clear(9, f=1)")
        api.import_values("i", "b", cols=[3, 9], values=[10, 20])
        api.query("i", "Clear(9, b=20)")
        api.import_bits("i", "f", rows=[2], col_keys=["k1"])  # translate
        api.holder.flush_wals()
        return api

    @pytest.mark.parametrize("times", [1, 2, 3])
    def test_same_tail_applied_n_times_is_identical(self, tmp_path, times):
        src = self._source(str(tmp_path / "src"))
        recs = list(src.holder.index("i").wal.records())
        assert len(recs) >= 5

        replica = API(str(tmp_path / f"rep{times}"))
        replica.create_index("i", {"keys": True})
        replica.create_field("i", "f")
        replica.create_field("i", "b", {"type": "int", "min": 0,
                                        "max": 1000})
        idx = replica.holder.index("i")
        checks = []
        for _ in range(times):
            with replica.holder.write_lock:
                n = replica.holder.replay_records(idx, recs)
            assert n == len(recs)
            checks.append(replica.checksum())
        assert len(set(checks)) == 1, "replay is not idempotent"
        # and the planes match the source bit-for-bit
        for pql in ("Row(f=0)", "Row(f=1)", "Row(f=2)", "Row(b > 5)"):
            assert replica.query("i", pql)[0].columns == \
                src.query("i", pql)[0].columns


# -- configuration ------------------------------------------------------------


class TestRecoveryConfig:
    def test_toml_section_and_env_override(self, tmp_path):
        cfg_file = tmp_path / "pt.toml"
        cfg_file.write_text(
            "[storage.recovery]\n"
            "segment-bytes = 8192\n"
            "checkpoint-interval-bytes = 4096\n"
            "catchup-batch-bytes = 2048\n")
        cfg = Config.from_sources(toml_path=str(cfg_file), env={})
        assert cfg.storage_recovery_segment_bytes == 8192
        assert cfg.storage_recovery_checkpoint_interval_bytes == 4096
        assert cfg.storage_recovery_catchup_batch_bytes == 2048
        cfg2 = Config.from_sources(
            toml_path=str(cfg_file),
            env={"PILOSA_TPU_STORAGE_RECOVERY_SEGMENT_BYTES": "123",
                 "PILOSA_TPU_STORAGE_RECOVERY_CATCHUP_BATCH_BYTES": "77"})
        assert cfg2.storage_recovery_segment_bytes == 123  # env wins
        assert cfg2.storage_recovery_catchup_batch_bytes == 77
        assert cfg2.storage_recovery_checkpoint_interval_bytes == 4096

    def test_defaults(self):
        cfg = Config.from_sources(env={})
        assert cfg.storage_recovery_segment_bytes == 4 << 20
        assert cfg.storage_recovery_checkpoint_interval_bytes == 0
        assert cfg.storage_recovery_catchup_batch_bytes == 1 << 20

    def test_manager_from_config(self, tmp_path):
        with LocalCluster(1, base_path=str(tmp_path)) as c:
            cfg = Config.from_sources(
                env={"PILOSA_TPU_STORAGE_RECOVERY_CATCHUP_BATCH_BYTES":
                     "4096"})
            rm = RecoveryManager.from_config(c.nodes[0], cfg)
            assert rm.batch_bytes == 4096
            rm2 = RecoveryManager.from_config(c.nodes[0], cfg,
                                              batch_bytes=99)
            assert rm2.batch_bytes == 99  # explicit override wins


# -- replica catch-up ---------------------------------------------------------


def _lag_node2(c):
    """Schema + an initial replicated write, then writes that land only
    on node0/node1 (node2 'was down' for them)."""
    c.coordinator.create_index("i")
    c.coordinator.create_field("i", "f")
    c.coordinator.import_bits("i", "f", rows=[0, 1, 2, 0],
                              cols=[1, 5, SHARD_WIDTH + 1, 2 * SHARD_WIDTH + 1])
    c.run_gossip_rounds(2)
    for n in c.nodes[:2]:
        n.api.import_bits("i", "f", rows=[3, 3, 1],
                          cols=[7, SHARD_WIDTH + 2, 9])
        n._announce_shards("i")
    c.run_gossip_rounds(3)


class TestReplicaCatchUp:
    def test_lagging_detects_strictly_ahead_peers(self, tmp_path):
        with LocalCluster(3, replica_n=3, base_path=str(tmp_path)) as c:
            c.enable_gossip()
            rm = c.nodes[2].enable_recovery()
            _lag_node2(c)
            lag = rm.lagging("i")
            assert set(lag) == {"node0", "node1"}
            assert all(shards for shards in lag.values())
            # up-to-date nodes see no lag anywhere
            rm0 = c.nodes[0].enable_recovery()
            assert rm0.lagging("i") == {}

    def test_catch_up_converges_bit_identically(self, tmp_path):
        with LocalCluster(3, replica_n=3, base_path=str(tmp_path)) as c:
            c.enable_gossip()
            rm = c.nodes[2].enable_recovery()
            _lag_node2(c)
            assert c.nodes[2].api.checksum() != c.nodes[0].api.checksum()
            summary = rm.catch_up()
            assert summary["shards"] > 0
            sums = [n.api.checksum() for n in c.nodes]
            assert sums[0] == sums[1] == sums[2]
            assert c.nodes[2].query("i", "Row(f=3)")[0].columns == \
                [7, SHARD_WIDTH + 2]
            # second run: nothing left to repair
            again = rm.catch_up()
            assert again["shards"] == 0 and again["indexes"] == []

    def test_catch_up_under_injected_faults(self, tmp_path):
        """Dropped + delayed recovery RPCs are absorbed by the client's
        retry/backoff; catch-up still converges."""
        plan = (FaultPlan(seed=3)
                .drop("node0", first=0, count=1, op="recovery")
                .delay("node0", 0.01, first=1, count=2, op="recovery")
                .drop("node1", first=0, count=1, op="recovery"))
        with LocalCluster(3, replica_n=3, base_path=str(tmp_path),
                          fault_plan=plan) as c:
            c.enable_gossip()
            rm = c.nodes[2].enable_recovery()
            _lag_node2(c)
            summary = rm.catch_up()
            assert summary["shards"] > 0
            sums = [n.api.checksum() for n in c.nodes]
            assert sums[0] == sums[1] == sums[2]

    def test_writes_queue_during_catch_up_and_drain_after(self, tmp_path):
        with LocalCluster(3, replica_n=3, base_path=str(tmp_path)) as c:
            c.enable_gossip()
            c.coordinator.create_index("i")
            c.coordinator.create_field("i", "f")
            rm = c.nodes[2].enable_recovery()
            rm.begin("i")
            # a forwarded write arriving mid-catch-up must queue, not apply
            n = c.nodes[2].import_bits("i", "f", rows=[5], cols=[6],
                                       remote=True)
            assert n == 0
            assert c.nodes[2].api.query("i", "Row(f=5)")[0].columns == []
            assert rm.drain() == 1
            assert c.nodes[2].api.query("i", "Row(f=5)")[0].columns == [6]
            # drained: the next remote write applies immediately
            c.nodes[2].import_bits("i", "f", rows=[5], cols=[8],
                                   remote=True)
            assert c.nodes[2].api.query("i", "Row(f=5)")[0].columns == [6, 8]

    def test_catch_up_gossips_breaker_open_then_closed(self, tmp_path):
        with LocalCluster(3, replica_n=3, base_path=str(tmp_path)) as c:
            c.enable_gossip()
            rm = c.nodes[2].enable_recovery()
            _lag_node2(c)
            states = []
            orig = c.nodes[2].gossip.record_breaker

            def spy(node_id, state, **kw):
                states.append((node_id, state))
                return orig(node_id, state, **kw)

            c.nodes[2].gossip.record_breaker = spy
            rm.catch_up()
            assert ("node2", "open") in states
            assert ("node2", "closed") in states
            assert states.index(("node2", "open")) < \
                states.index(("node2", "closed"))

    def test_drain_is_per_index(self, tmp_path):
        """Regression: drain() used to clear the WHOLE active set and a
        single shared queue, so overlapping catch-up runs on different
        indexes released each other's deferred writes mid-replay."""
        with LocalCluster(3, replica_n=3, base_path=str(tmp_path)) as c:
            c.enable_gossip()
            for name in ("i", "j"):
                c.coordinator.create_index(name)
                c.coordinator.create_field(name, "f")
            rm = c.nodes[2].enable_recovery()
            rm.begin("i")
            rm.begin("j")
            assert c.nodes[2].import_bits("i", "f", rows=[1], cols=[2],
                                          remote=True) == 0
            assert c.nodes[2].import_bits("j", "f", rows=[3], cols=[4],
                                          remote=True) == 0
            assert rm.drain(["i"]) == 1  # only i's queue applies
            assert not rm.active("i") and rm.active("j")
            assert c.nodes[2].api.query("i", "Row(f=1)")[0].columns == [2]
            assert c.nodes[2].api.query("j", "Row(f=3)")[0].columns == []
            assert rm.drain() == 1  # bare drain still releases the rest
            assert c.nodes[2].api.query("j", "Row(f=3)")[0].columns == [4]

    def test_failed_catch_up_keeps_breaker_open(self, tmp_path):
        """Regression: catch_up's finally used to gossip 'closed' even
        when repair raised, so a still-lagging node advertised itself
        caught up and peers routed reads back to stale data. Failure
        must propagate and leave the breaker open; a retry that
        completes closes it."""
        with LocalCluster(3, replica_n=3, base_path=str(tmp_path)) as c:
            c.enable_gossip()
            rm = c.nodes[2].enable_recovery()
            _lag_node2(c)
            states = []
            orig = c.nodes[2].gossip.record_breaker

            def spy(node_id, state, **kw):
                states.append((node_id, state))
                return orig(node_id, state, **kw)

            c.nodes[2].gossip.record_breaker = spy

            def unreachable(index, origin, shards):
                raise OSError("peer unreachable")

            rm._repair_from = unreachable
            with pytest.raises(OSError):
                rm.catch_up()
            assert ("node2", "open") in states
            assert ("node2", "closed") not in states
            del rm._repair_from  # retry with the real repair path
            summary = rm.catch_up()
            assert summary["shards"] > 0
            assert ("node2", "closed") in states
            sums = [n.api.checksum() for n in c.nodes]
            assert sums[0] == sums[1] == sums[2]

    def test_recovery_endpoints_ship_snapshot_and_tail(self, tmp_path):
        """The transport itself: /internal/recovery/snapshot returns an
        installable npz + LSN; /internal/recovery/wal ships CRC-framed
        records above a cursor."""
        import base64

        with LocalCluster(2, replica_n=2, base_path=str(tmp_path)) as c:
            c.coordinator.create_index("i")
            c.coordinator.create_field("i", "f")
            c.coordinator.import_bits("i", "f", rows=[0, 1], cols=[3, 9])
            peer = c.nodes[0].node
            client = c.nodes[1].client
            snap = client.recovery_snapshot(peer, "i", 0)
            assert snap["lsn"] > 0 and snap["npz"]
            tail = client.recovery_wal(peer, "i", 0, 1 << 20)
            frames = base64.b64decode(tail["frames"])
            recs = [r for _lsn, r in iter_frames(frames)]
            assert any(r[0] == "import_bits" for r in recs)
            assert tail["last_lsn"] == snap["lsn"] and not tail["more"]
            # a cursor at the tip ships nothing
            empty = client.recovery_wal(peer, "i", tail["last_lsn"], 1 << 20)
            assert base64.b64decode(empty["frames"]) == b""


# -- metrics ------------------------------------------------------------------


class TestRecoveryMetrics:
    def test_checkpoint_and_catchup_metrics_exposed(self, tmp_path):
        api = API(str(tmp_path / "a"))
        api.create_index("i")
        api.create_field("i", "f")
        api.import_bits("i", "f", rows=[0], cols=[1])
        base = M.REGISTRY.summary(M.METRIC_RECOVERY_CHECKPOINT_SECONDS)[0]
        api.save()
        assert M.REGISTRY.summary(
            M.METRIC_RECOVERY_CHECKPOINT_SECONDS)[0] == base + 1
        text = M.REGISTRY.prometheus_text()
        assert "recovery_checkpoint_seconds" in text

    def test_catch_up_counts_shards_and_lag(self, tmp_path):
        reg = M.MetricsRegistry()
        with LocalCluster(3, replica_n=3, base_path=str(tmp_path)) as c:
            c.enable_gossip()
            rm = c.nodes[2].enable_recovery(registry=reg)
            _lag_node2(c)
            rm.catch_up()
            assert reg.value(M.METRIC_RECOVERY_CATCHUP_SHARDS) > 0
            h = reg.histogram(M.METRIC_RECOVERY_CATCHUP_LAG_MS)
            assert h is not None and h["count"] == 1


def _snapshot_files(root):
    """Every snapshot file under ``root``: path -> (inode, mtime_ns,
    bytes). A file written again has another inode (tmp + rename)."""
    out = {}
    for d, _, fs in os.walk(str(root)):
        for f in fs:
            if f.startswith("frag.") and f.endswith(".npz"):
                p = os.path.join(d, f)
                st = os.stat(p)
                with open(p, "rb") as fh:
                    out[p] = (st.st_ino, st.st_mtime_ns, fh.read())
    return out


def _assert_saved_versions_hold(holder):
    """What the skip rests on: wherever ``saved_versions`` says the disk
    holds a fragment at its present version, the file is there and holds
    exactly those planes."""
    claims = 0
    for path, (frag, version) in holder.saved_versions.items():
        if version != frag.version:
            continue  # advanced since: the next save writes it
        claims += 1
        with np.load(path) as z:
            planes = z["planes"]
            rows = z["row_ids"].tolist() if "row_ids" in z else None
        if rows is None:
            assert np.array_equal(planes, frag.planes), path
        else:
            assert rows == list(frag.row_ids), path
            assert np.array_equal(planes, frag.planes[:len(rows)]), path
    assert claims


class TestCheckpointAccrual:
    """The checkpoint's counters accrue per phase and per file, so that a
    window's delta is the window's (``recovery_checkpoint_seconds`` lands
    once, at the end)."""

    PHASES = ("wal_flush", "serialize", "fsync", "meta", "prune")

    @staticmethod
    def _phases():
        return {p: M.REGISTRY.value(
            M.METRIC_RECOVERY_CHECKPOINT_PHASE_SECONDS, phase=p)
            for p in TestCheckpointAccrual.PHASES}

    @staticmethod
    def _fragments():
        return {s: M.REGISTRY.value(
            M.METRIC_RECOVERY_CHECKPOINT_FRAGMENTS, state=s)
            for s in ("changed", "skipped", "unchanged")}

    @classmethod
    def _moved(cls, before):
        return {s: v - before[s] for s, v in cls._fragments().items()}

    @staticmethod
    def _bytes():
        return {k: M.REGISTRY.value(
            M.METRIC_RECOVERY_CHECKPOINT_BYTES, kind=k)
            for k in ("raw", "stored")}

    @staticmethod
    def _holder(tmp_path, rows=40):
        api = API(str(tmp_path / "a"))
        api.create_index("i")
        api.create_field("i", "f")
        api.create_field("i", "g")
        api.create_field("i", "v", {"type": "int", "min": 0, "max": 1 << 20})
        cols = np.arange(0, 2 * SHARD_WIDTH, 97)
        api.import_bits("i", "f", rows=cols % rows, cols=cols)
        api.import_bits("i", "g", rows=cols % 3, cols=cols)
        api.import_values("i", "v", cols=cols, values=cols % 1000)
        return api

    def test_every_phase_moves_and_sums_to_the_summary(self, tmp_path):
        api = self._holder(tmp_path)
        phases = self._phases()
        n, total = M.REGISTRY.summary(M.METRIC_RECOVERY_CHECKPOINT_SECONDS)
        api.holder.checkpoint()
        moved = {p: v - phases[p] for p, v in self._phases().items()}
        assert all(v > 0 for v in moved.values()), moved
        n1, total1 = M.REGISTRY.summary(M.METRIC_RECOVERY_CHECKPOINT_SECONDS)
        assert n1 == n + 1
        assert sum(moved.values()) == pytest.approx(total1 - total, rel=0.10)
        assert sum(moved.values()) <= total1 - total

    def test_untouched_fragment_is_skipped_not_rewritten(self, tmp_path):
        api = self._holder(tmp_path)
        first = self._fragments()
        api.holder.checkpoint()
        # nothing was on disk: every file is new
        assert self._moved(first) == {
            "changed": 8,  # (f, g, _exists, v) x 2 shards
            "skipped": 0, "unchanged": 0}
        on_disk = _snapshot_files(tmp_path / "a")
        assert len(on_disk) == 8
        api.import_bits("i", "g", rows=[1], cols=[5])  # shard 0 of g
        after_first = self._fragments()
        api.holder.checkpoint()
        # g and _exists of shard 0 advanced; the other six did not, and
        # their files are the very files the first checkpoint wrote
        assert self._moved(after_first) == {
            "changed": 2, "skipped": 6, "unchanged": 0}
        now = _snapshot_files(tmp_path / "a")
        rewritten = sorted(p for p in now if now[p] != on_disk[p])
        assert [p.split("/fields/")[1] for p in rewritten] == [
            "_exists/views/standard/frag.0.npz",
            "g/views/standard/frag.0.npz"]
        _assert_saved_versions_hold(api.holder)

    def test_recovery_seeds_the_saved_versions(self, tmp_path):
        api = self._holder(tmp_path)
        api.holder.checkpoint()
        on_disk = _snapshot_files(tmp_path / "a")
        api.import_bits("i", "f", rows=[2], cols=[SHARD_WIDTH + 9])
        api.holder.flush_wals()
        again = API(str(tmp_path / "a"))  # load checkpoint + replay tail
        before = self._fragments()
        again.holder.checkpoint()
        # the replayed record touched f and _exists of shard 1 only:
        # only those two are written
        assert self._moved(before) == {
            "changed": 2, "skipped": 6, "unchanged": 0}
        now = _snapshot_files(tmp_path / "a")
        assert sum(now[p] != on_disk[p] for p in now) == 2
        assert API(str(tmp_path / "a")).checksum() == again.checksum()

    def test_file_deleted_behind_the_holder_is_written_again(self, tmp_path):
        api = self._holder(tmp_path)
        api.holder.checkpoint()
        gone = [p for p in _snapshot_files(tmp_path / "a")
                if p.endswith("f/views/standard/frag.1.npz")]
        assert len(gone) == 1
        os.remove(gone[0])
        before = self._fragments()
        api.holder.checkpoint()
        assert self._moved(before) == {
            "changed": 1, "skipped": 7, "unchanged": 0}
        assert os.path.exists(gone[0])
        assert API(str(tmp_path / "a")).checksum() == api.checksum()

    def test_field_made_again_under_its_name_is_never_skipped(
            self, tmp_path):
        import shutil

        api = API(str(tmp_path / "a"))
        api.create_index("i", {"trackExistence": False})
        api.create_field("i", "f")
        api.import_bits("i", "f", rows=[1], cols=[3])
        api.holder.checkpoint()
        (path,) = _snapshot_files(tmp_path / "a")
        old = api.holder.index("i").field("f").views["standard"][0]
        shutil.copy(path, str(tmp_path / "kept.npz"))
        api.delete_field("i", "f")
        api.create_field("i", "f")
        api.import_bits("i", "f", rows=[7], cols=[9])
        new = api.holder.index("i").field("f").views["standard"][0]
        # the predecessor's file is back under the name, and its
        # successor stands at the same version: only identity tells them
        # apart
        assert new is not old and new.version == old.version
        os.makedirs(os.path.dirname(path), exist_ok=True)
        shutil.copy(str(tmp_path / "kept.npz"), path)
        before = self._fragments()
        api.holder.checkpoint()
        assert self._moved(before) == {
            "changed": 1, "skipped": 0, "unchanged": 0}
        again = API(str(tmp_path / "a"))
        assert again.checksum() == api.checksum()
        assert again.query("i", "Row(f=7)")[0].columns == [9]
        assert again.query("i", "Row(f=1)")[0].columns == []

    # a kill at each site of a checkpoint that has files to skip and
    # files to write: savez hits 1..4 are the four files that moved
    @pytest.mark.parametrize("site,at", [
        ("savez.pre_replace", 1), ("savez.pre_replace", 3),
        ("savez.post_replace", 1), ("savez.post_replace", 4),
        ("checkpoint.mid", 1)])
    def test_crash_in_a_skipping_checkpoint_recovers_to_the_oracle(
            self, tmp_path, site, at):
        api = self._holder(tmp_path)
        api.holder.checkpoint()
        api.import_bits("i", "g", rows=[1], cols=[5])
        api.import_bits("i", "f", rows=[2], cols=[SHARD_WIDTH + 9])
        api.holder.flush_wals()
        oracle = api.checksum()
        attach_crash_plan(api.holder, CrashPlan().kill(site, at=at))
        with pytest.raises(SimulatedCrash):
            api.holder.checkpoint()
        # the save died part-way: the map claims no file that was never
        # renamed into place
        _assert_saved_versions_hold(api.holder)
        abandon_holder(api.holder)
        again = API(str(tmp_path / "a"))  # old stamp: replays the tail
        assert again.checksum() == oracle
        before = self._fragments()
        again.holder.checkpoint()
        # mixed old and new files on disk; the four the tail touched are
        # written whichever they were, the four it did not are skipped
        assert self._moved(before) == {
            "changed": 4, "skipped": 4, "unchanged": 0}
        _assert_saved_versions_hold(again.holder)
        assert API(str(tmp_path / "a")).checksum() == oracle

    def test_dead_plan_records_no_path_it_did_not_write(self, tmp_path):
        from pilosa_tpu.storage.recovery import crash_scope
        from pilosa_tpu.storage.store import save_holder_data

        api = self._holder(tmp_path)
        api.holder.checkpoint()
        on_disk = _snapshot_files(tmp_path / "a")
        api.import_bits("i", "g", rows=[1], cols=[5])
        plan = CrashPlan()
        plan.dead = True  # the 'process' died elsewhere: no IO from here
        with crash_scope(plan):
            save_holder_data(api.holder)
        assert _snapshot_files(tmp_path / "a") == on_disk
        # the six files that stand are carried; the two that moved were
        # not written and are not claimed
        assert len(api.holder.saved_versions) == 6
        _assert_saved_versions_hold(api.holder)
        api.holder.checkpoint()
        assert len(api.holder.saved_versions) == 8
        _assert_saved_versions_hold(api.holder)

    def test_sparse_planes_store_smaller_than_raw(self, tmp_path):
        api = self._holder(tmp_path)
        before = self._bytes()
        api.holder.checkpoint()
        got = {k: v - before[k] for k, v in self._bytes().items()}
        on_disk = sum(
            os.path.getsize(os.path.join(d, f))
            for d, _, fs in os.walk(str(tmp_path / "a" / "indexes"))
            for f in fs if f.endswith(".npz"))
        assert got["stored"] == on_disk
        assert 0 < got["stored"] <= got["raw"]

    def test_startup_phases_set_by_recover(self, tmp_path):
        self._holder(tmp_path).holder.checkpoint()
        M.REGISTRY.gauge(M.METRIC_STARTUP_PHASE_SECONDS, -1.0,
                         phase="load_checkpoint")
        M.REGISTRY.gauge(M.METRIC_STARTUP_PHASE_SECONDS, -1.0,
                         phase="wal_replay")
        API(str(tmp_path / "a"))
        for phase in ("load_checkpoint", "wal_replay"):
            assert M.REGISTRY.value(M.METRIC_STARTUP_PHASE_SECONDS,
                                    phase=phase) >= 0


# -- every write route moves fragment.version --------------------------------


def _route_install_shard_arrays(api):
    from pilosa_tpu.shardwidth import WORDS_PER_SHARD
    from pilosa_tpu.storage.store import (export_shard_arrays,
                                          install_shard_arrays)

    idx = api.holder.index("i")
    arrays = {k: np.array(v) for k, v in export_shard_arrays(idx, 0).items()}
    arrays["set|f|standard"][0, 7] ^= np.uint32(1 << 3)
    arrays["bsi|v"][0, WORDS_PER_SHARD - 1] ^= np.uint32(1)
    with api.holder.write_lock:
        install_shard_arrays(idx, 0, arrays)


def _route_import_roaring(api):
    from pilosa_tpu.storage.roaring import encode_positions

    pos = np.array([3 * SHARD_WIDTH + 11, 90 * SHARD_WIDTH + 2],
                   dtype=np.uint64)
    api.import_roaring("i", "f", 1, {"standard": encode_positions(pos)})


def _route_import_roaring_clear(api):
    from pilosa_tpu.storage.roaring import encode_positions

    col = (SHARD_WIDTH // 97 + 1) * 97  # the holder's first bit of shard 1
    pos = np.array([(col % 40) * SHARD_WIDTH + col - SHARD_WIDTH],
                   dtype=np.uint64)
    api.import_roaring("i", "f", 1, {"standard": encode_positions(pos)},
                       clear=True)


def _route_wal_replay(api):
    idx = api.holder.index("i")
    with api.holder.write_lock:
        assert api.holder.replay_records(idx, [
            ("set_bit", "f", 4, 12345, ""),
            ("set_values", "v", [SHARD_WIDTH + 1], [77])]) == 2


def _route_restore_tar(api):
    import io

    src = API()
    src.create_index("i")
    src.create_field("i", "f")
    src.create_field("i", "v", {"type": "int", "min": 0, "max": 1 << 20})
    src.import_bits("i", "f", rows=[1, 2], cols=[3, SHARD_WIDTH + 4])
    src.import_values("i", "v", cols=[3, SHARD_WIDTH + 4], values=[9, 1000])
    buf = io.BytesIO()
    src.backup_tar(buf)
    buf.seek(0)
    api.restore_tar(buf)
    assert api.checksum() == src.checksum()
    # the BSI planes are copied in directly: that write counts as one
    for bfrag in api.holder.index("i").field("v").bsi.values():
        assert bfrag.version > 0


WRITE_ROUTES = {
    "set_bit": lambda api: api.query("i", "Set(77, f=5)"),
    "clear_bit": lambda api: api.query("i", "Clear(97, f=17)"),
    "import_bits": lambda api: api.import_bits(
        "i", "g", rows=[0, 2], cols=[1, SHARD_WIDTH + 1]),
    "import_bits_clear": lambda api: api.import_bits(
        "i", "g", rows=[1], cols=[97], clear=True),
    "import_values": lambda api: api.import_values(
        "i", "v", cols=[2, SHARD_WIDTH + 2], values=[5, 1 << 19]),
    "set_value": lambda api: api.query("i", "Set(97, v=123)"),
    "import_roaring": _route_import_roaring,
    "import_roaring_clear": _route_import_roaring_clear,
    "clear_row": lambda api: api.query("i", "ClearRow(f=1)"),
    "delete_records": lambda api: api.query("i", "Delete(Row(g=1))"),
    "install_shard_arrays": _route_install_shard_arrays,
    "wal_replay": _route_wal_replay,
    "restore_tar": _route_restore_tar,
}


class TestEveryWriteMovesTheVersion:
    """A checkpoint skips a fragment whose version stands where the disk
    holds it, so a write that changed planes and left the version alone
    would be lost at the next restart."""

    @staticmethod
    def _fragments(holder):
        out = {}
        for idx in holder.indexes.values():
            for field in idx.fields.values():
                for view, frags in field.views.items():
                    for shard, frag in frags.items():
                        out[idx.name, field.name, view, shard] = (
                            frag, frag.planes[:len(frag.row_ids)],
                            list(frag.row_ids))
                for shard, bfrag in field.bsi.items():
                    out[idx.name, field.name, "bsi", shard] = (
                        bfrag, bfrag.planes, None)
        return out

    @pytest.mark.parametrize("route", sorted(WRITE_ROUTES))
    def test_route_moves_version_and_survives_a_checkpoint(
            self, tmp_path, route):
        api = TestCheckpointAccrual._holder(tmp_path)
        api.holder.checkpoint()
        before = {k: (frag, frag.version, planes.copy(), rows)
                  for k, (frag, planes, rows)
                  in self._fragments(api.holder).items()}
        WRITE_ROUTES[route](api)
        wrote = 0
        for key, (frag, planes, rows) in self._fragments(api.holder).items():
            was, version, old_planes, old_rows = before.get(
                key, (None, None, None, None))
            if was is not frag:
                wrote += 1  # another object: identity keeps it from a skip
            elif rows != old_rows or not np.array_equal(planes, old_planes):
                wrote += 1
                assert frag.version > version, key
        assert wrote, "the route wrote nothing"
        api.holder.checkpoint()
        _assert_saved_versions_hold(api.holder)
        assert API(str(tmp_path / "a")).checksum() == api.checksum()
