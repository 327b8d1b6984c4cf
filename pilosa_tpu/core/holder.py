"""Holder: root container owning all indexes.

Reference: holder.go:58. Schema persistence is a JSON document on the
holder's data dir (the single-controller analog of the reference's etcd
Schemator, SURVEY.md §7 "etcd/disco -> host process owns schema").
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

from pilosa_tpu.core.index import Index
from pilosa_tpu.core.schema import FieldOptions, IndexOptions
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.obs.tracing import annotate
from pilosa_tpu.shardwidth import SHARD_WIDTH


class Holder:
    def __init__(self, path: Optional[str] = None, wal_sync: str = "batch",
                 checkpoint_bytes: int = 64 << 20, readonly: bool = False,
                 segment_bytes: Optional[int] = None):
        self.path = path
        self.wal_sync = wal_sync
        # readonly: open for a snapshot-only read pass (restore/inspect) —
        # no WAL handles are created and recover() refuses to replay logs
        # (a foreign wal.log is untrusted input; see API.restore_tar).
        self.readonly = readonly
        # WAL record volume that triggers an automatic fuzzy checkpoint
        # (snapshot + segment prune) — the analog of RBF's
        # MaxWALCheckpointSize (rbf/cfg/cfg.go:10-13).
        self.checkpoint_bytes = checkpoint_bytes
        # WAL segment rotation size (constructor param because WALs are
        # opened during _load_schema below).
        from pilosa_tpu.storage.wal import DEFAULT_SEGMENT_BYTES

        self.segment_bytes = segment_bytes or DEFAULT_SEGMENT_BYTES
        # storage/recovery.CrashPlan for deterministic kill-point tests;
        # attach via recovery.attach_crash_plan so existing WALs get it.
        self.crash_plan = None
        # Serializes write requests against each other and against
        # checkpoints (Qcx holds it for the request; reference: RBF's
        # single-writer tx lock). Reads never take it — they see
        # version-snapshotted device stacks (core/stacked.py).
        import threading

        self.write_lock = threading.RLock()
        # snapshot path -> (fragment, version) as the disk holds it: set
        # by load_holder_data and by every completed save_holder_data,
        # which skips the files it says the disk already holds
        self.saved_versions: Dict[str, tuple] = {}
        self.indexes: Dict[str, Index] = {}
        if path:
            os.makedirs(path, exist_ok=True)
            self._load_schema()

    # -- schema persistence ------------------------------------------------------

    def _schema_path(self) -> str:
        return os.path.join(self.path, "schema.json")

    def _load_schema(self) -> None:
        if not os.path.exists(self._schema_path()):
            return
        with open(self._schema_path()) as f:
            doc = json.load(f)
        for idx_doc in doc.get("indexes", []):
            idx = self._new_index(idx_doc["name"], IndexOptions.from_json(idx_doc["options"]))
            for f_doc in idx_doc.get("fields", []):
                if f_doc["name"] not in idx.fields:
                    idx.create_field(f_doc["name"], FieldOptions.from_json(f_doc["options"]))

    def save_schema(self) -> None:
        if not self.path:
            return
        doc = {
            "indexes": [
                {
                    "name": idx.name,
                    "options": idx.options.to_json(),
                    "fields": [
                        {"name": f.name, "options": f.options.to_json()}
                        for f in idx.public_fields()
                    ],
                }
                for idx in sorted(self.indexes.values(), key=lambda i: i.name)
            ]
        }
        tmp = self._schema_path() + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, self._schema_path())

    # -- index management --------------------------------------------------------

    def _index_path(self, name: str) -> Optional[str]:
        return os.path.join(self.path, "indexes", name) if self.path else None

    def _new_index(self, name: str, options: Optional[IndexOptions]) -> Index:
        wal = None
        if self.path and not self.readonly:
            from pilosa_tpu.storage.wal import WAL

            wal = WAL(os.path.join(self._index_path(name), "wal.log"),
                      sync=self.wal_sync, segment_bytes=self.segment_bytes,
                      crash_plan=self.crash_plan)
        idx = Index(name, options, path=self._index_path(name), wal=wal,
                    lock=self.write_lock)
        self.indexes[name] = idx
        return idx

    def create_index(self, name: str, options: Optional[IndexOptions] = None) -> Index:
        if name in self.indexes:
            raise ValueError(f"index {name!r} already exists")
        idx = self._new_index(name, options)
        self.save_schema()
        return idx

    def index(self, name: str) -> Index:
        idx = self.indexes.get(name)
        if idx is None:
            raise KeyError(f"index {name!r} not found")
        return idx

    def delete_index(self, name: str) -> None:
        from pilosa_tpu.core.stacked import release_field_cache

        idx = self.indexes.pop(name)
        for f in idx.fields.values():  # drop every field's HBM entries
            release_field_cache(f)
        if idx.wal is not None:
            idx.wal.close()
        # Remove the whole index dir (WAL, checkpoint npz fragments,
        # translate stores) — otherwise re-creating the name resurrects
        # the deleted planes on the next recover() (reference: index
        # deletion removes the per-index data dir, holder.go DeleteIndex).
        path = self._index_path(name)
        if path and os.path.isdir(path):
            import shutil

            shutil.rmtree(path)
        self.save_schema()

    # -- durability (reference: rbf WAL/checkpoint, rbf/db.go:149-230) ----------

    def flush_wals(self) -> None:
        """Group commit: one write barrier per dirty index (the Qcx.finish
        analog, txfactory.go:114)."""
        for idx in self.indexes.values():
            if idx.wal is not None:
                idx.wal.flush()

    def wal_bytes(self) -> int:
        """Record bytes pending checkpoint (segment markers excluded —
        a freshly checkpointed holder reports 0)."""
        return sum(idx.wal.record_bytes for idx in self.indexes.values()
                   if idx.wal is not None)

    def wal_flush_lag_s(self) -> float:
        """Max seconds any index WAL has held unflushed records (0 when
        every log is clean) — the health plane's WAL-stall probe."""
        return max((idx.wal.flush_lag_s() for idx in self.indexes.values()
                    if idx.wal is not None), default=0.0)

    def last_lsn(self) -> int:
        """The holder-wide commit position: max LSN assigned across all
        index WALs (each index has its own log, but LSNs only ever
        grow, so the max orders any two holder states)."""
        return max((idx.wal.last_lsn for idx in self.indexes.values()
                    if idx.wal is not None), default=0)

    def checkpoint(self) -> None:
        """Fuzzy checkpoint: flush, capture each index's LSN, snapshot
        the planes that moved, stamp ``checkpoint.json`` with the LSN,
        then prune segments wholly below it (reference: rbf checkpoint
        copying WAL pages into the DB file). A fragment whose file the
        disk already holds at its present version is not rewritten
        (``store.save_holder_data``): no record above that file's
        checkpoint touched it, so the file is the snapshot at the new
        LSN too. A crash between ANY two steps is safe: before the meta
        write, recovery replays from the old LSN over mixed old/new npz
        files (every WAL op is plane-idempotent; a skipped file is an
        old file that equals the new one); after it, the snapshot
        already covers everything the meta claims, and stale segments
        fall to the next prune. Takes the write lock so a concurrent
        writer can't append between snapshot and stamp (RLock: a no-op
        when called from inside the owning Qcx)."""
        if not self.path or self.readonly:
            return
        from pilosa_tpu.storage.recovery import (
            crash_scope, write_checkpoint_meta,
        )
        from pilosa_tpu.storage.store import save_holder_data

        plan = self.crash_plan
        if plan is not None and plan.dead:
            return

        def phase(name: str, since: float) -> float:
            # counted as each phase ends, never once at the end of the
            # checkpoint: a window's delta is the window's. ``serialize``
            # and ``fsync`` accrue per file in store._atomic_savez.
            now = time.perf_counter()
            M.REGISTRY.count(M.METRIC_RECOVERY_CHECKPOINT_PHASE_SECONDS,
                             now - since, phase=name)
            return now

        t0 = time.perf_counter()
        pruned = 0
        with self.write_lock:
            self.flush_wals()
            phase("wal_flush", t0)
            lsns = {name: idx.wal.last_lsn
                    for name, idx in self.indexes.items()
                    if idx.wal is not None}
            # stream watermarks captured under the same lock as the LSNs:
            # the stamp must describe exactly the state the snapshot holds
            offsets = {name: {g: dict(m)
                              for g, m in idx.stream_offsets.items()}
                       for name, idx in self.indexes.items()
                       if idx.stream_offsets}
            with crash_scope(plan):
                save_holder_data(self)
                if plan is not None and not plan.fire("checkpoint.mid"):
                    return
                t = time.perf_counter()
                with annotate("checkpoint.meta"):
                    for name, lsn in lsns.items():
                        write_checkpoint_meta(
                            self._index_path(name), lsn,
                            stream_offsets=offsets.get(name))
                t = phase("meta", t)
            with annotate("checkpoint.prune"):
                for name, lsn in lsns.items():
                    idx = self.indexes.get(name)
                    if idx is not None and idx.wal is not None:
                        pruned += idx.wal.prune(lsn)
            phase("prune", t)
        M.REGISTRY.observe(M.METRIC_RECOVERY_CHECKPOINT_SECONDS,
                           time.perf_counter() - t0)
        if pruned:
            M.REGISTRY.count(M.METRIC_RECOVERY_SEGMENTS_PRUNED, pruned)

    def maybe_checkpoint(self) -> bool:
        if self.path and self.wal_bytes() > self.checkpoint_bytes:
            self.checkpoint()
            return True
        return False

    def replay_records(self, idx: Index, records) -> int:
        """Apply an iterable of WAL record tuples to ``idx`` with
        re-logging suppressed — shared by crash recovery and replica
        catch-up (which feeds it shipped, shard-filtered tails). A bad
        record is skipped with a warning, never a brick. Returns records
        applied."""
        import logging

        wal = idx.wal
        prev = wal.replaying if wal is not None else False
        if wal is not None:
            wal.replaying = True
        applied = 0
        try:
            for rec in records:
                try:
                    self._apply_wal_record(idx, rec)
                    applied += 1
                except (ValueError, KeyError) as e:
                    logging.getLogger(__name__).warning(
                        "skipping unreplayable WAL record %r: %s",
                        rec[:2], e)
        finally:
            if wal is not None:
                wal.replaying = prev
        return applied

    def recover(self) -> None:
        """Crash recovery: load the last checkpoint, then replay each
        index's WAL records ABOVE its checkpoint LSN through the same
        field-level write methods that produced them (reference:
        rbf/db.go WAL replay on open; op-level like dax/storage
        snapshot+log resume)."""
        from pilosa_tpu.storage.recovery import (read_checkpoint_meta,
                                                 read_checkpoint_offsets)
        from pilosa_tpu.storage.store import load_holder_data

        t0 = time.perf_counter()
        load_holder_data(self)
        t1 = time.perf_counter()
        M.REGISTRY.gauge(M.METRIC_STARTUP_PHASE_SECONDS, t1 - t0,
                         phase="load_checkpoint")
        for name, idx in self.indexes.items():
            if idx.wal is None:
                continue
            ckpt = read_checkpoint_meta(self._index_path(name))
            # checkpoint-stamped stream watermarks first; the WAL tail's
            # stream_offsets records replayed below only move them forward
            for g, m in read_checkpoint_offsets(
                    self._index_path(name)).items():
                cur = idx.stream_offsets.setdefault(g, {})
                for k, v in m.items():
                    cur[k] = max(int(v), int(cur.get(k, 0)))
            nbytes = [0]

            def _tail(w=idx.wal, after=ckpt, nb=nbytes):
                for _lsn, rec, frame_len in w.replay(after):
                    nb[0] += frame_len
                    yield rec

            applied = self.replay_records(idx, _tail())
            if applied:
                M.REGISTRY.count(M.METRIC_RECOVERY_REPLAY_RECORDS, applied)
                M.REGISTRY.count(M.METRIC_RECOVERY_REPLAY_BYTES, nbytes[0])
            # chop any torn tail so post-recovery appends are readable
            idx.wal.repair()
        M.REGISTRY.gauge(M.METRIC_STARTUP_PHASE_SECONDS,
                         time.perf_counter() - t1, phase="wal_replay")

    @staticmethod
    def _apply_wal_record(idx: Index, rec) -> None:
        import datetime as dt

        from pilosa_tpu.shardwidth import WORDS_PER_SHARD
        from pilosa_tpu.storage.wal import unpack_plane

        op, fname = rec[0], rec[1]
        if op == "stream_offsets":  # consumer watermark; rec[1] is a group
            cur = idx.stream_offsets.setdefault(fname, {})
            for k, v in dict(rec[2]).items():
                cur[k] = max(int(v), int(cur.get(k, 0)))
            return
        if op == "df_changeset":  # dataframe record, no field name
            _, _, shard, ids, columns = rec
            idx.dataframe.apply_changeset(shard, ids, columns, log=False)
            return
        if op == "df_delete":  # tombstone: wipe changesets replayed so far
            idx.dataframe.delete(log=False)
            return
        if op == "delete_view":  # TTL sweep tombstone (server/maintenance)
            f = idx.fields.get(fname)
            if f is not None:
                from pilosa_tpu.core.stacked import release_field_cache

                f.views.pop(rec[2], None)
                release_field_cache(f)
            return
        if op == "delete_field":
            # tombstone: a field deleted (and possibly re-created) after
            # earlier records were logged — wipe what replay built so far
            f = idx.fields.get(fname)
            if f is not None:
                from pilosa_tpu.core.stacked import release_field_cache

                f.views.clear()
                f.bsi.clear()
                release_field_cache(f)
            return
        if op == "delete_cols":  # index-level record, no field name
            _, _, shard, packed = rec
            plane = unpack_plane(packed, WORDS_PER_SHARD)
            for field in idx.fields.values():
                field.clear_columns(shard, plane, log=False)
            return
        field = idx.fields.get(fname)
        if field is None:  # field deleted after the record was logged
            return
        if op == "set_bit":
            _, _, row, col, ts = rec
            field.set_bit(row, col,
                          dt.datetime.fromisoformat(ts) if ts else None)
        elif op == "clear_bit":
            field.clear_bit(rec[2], rec[3])
        elif op == "set_values":
            field.set_values(rec[2], rec[3])
        elif op == "clear_value":
            field.clear_value(rec[2])
        elif op == "import_bits":
            field.import_bits(rec[2], rec[3])
        elif op == "row_plane":
            _, _, view, shard, row, packed, clear = rec
            field.write_row_plane(shard, row,
                                  unpack_plane(packed, WORDS_PER_SHARD),
                                  clear=clear, view=view)
        elif op == "clear_row_bits":
            _, _, view, shard, row, packed = rec
            field.clear_row_plane_bits(
                shard, row, unpack_plane(packed, WORDS_PER_SHARD), view=view)
        elif op == "clear_row":
            field.clear_row(rec[2])
        elif op == "clear_cols":
            _, _, shard, packed = rec
            field.clear_columns(shard, unpack_plane(packed, WORDS_PER_SHARD))
        # unknown ops from a newer version are skipped (forward compat)

    # -- device residency (core/stacked.py) -------------------------------------

    def prewarm(self, index: Optional[str] = None) -> Dict[str, int]:
        """Build and pin the stacked device planes for every (field,
        view) up front, so the first query of each family runs warm —
        no ``stack.build`` / ``device.h2d_copy`` on the serving path.
        Returns {"set_stacks": n, "bsi_stacks": n}. Stacks land in the
        field caches under the global DeviceBudget: prewarming more
        than the budget holds simply LRU-evicts the coldest, identical
        to demand paging."""
        from pilosa_tpu.core.stacked import stacked_bsi, stacked_set

        indexes = ([self.index(index)] if index is not None
                   else list(self.indexes.values()))
        sets = bsis = 0
        for idx in indexes:
            shard_list = sorted(idx.shards())
            if not shard_list:
                continue
            for field in idx.fields.values():
                for view in sorted(field.views):
                    stacked_set(field, shard_list, view)
                    sets += 1
                if field.bsi:
                    stacked_bsi(field, shard_list)
                    bsis += 1
        return {"set_stacks": sets, "bsi_stacks": bsis}

    def residency_stats(self) -> Dict[str, float]:
        """Current device-residency accounting (mirrors the
        device_hbm_resident_bytes gauge plus budget capacity)."""
        from pilosa_tpu.core.stacked import BUDGET, PAGING_STATS

        return {
            "resident_bytes": BUDGET.used,
            "budget_bytes": BUDGET.cap,
            "evictions": PAGING_STATS["evictions"],
            "block_builds": PAGING_STATS["block_builds"],
            "stale_retries": PAGING_STATS["stale_retries"],
        }

    def schema(self) -> List[dict]:
        """JSON-facing schema (reference: api.go Schema / schema.go:502)."""
        return [
            {
                "name": idx.name,
                "options": idx.options.to_json(),
                "shardWidth": SHARD_WIDTH,
                "fields": [
                    {"name": f.name, "options": f.options.to_json()}
                    for f in idx.public_fields()
                ],
            }
            for idx in sorted(self.indexes.values(), key=lambda i: i.name)
        ]
