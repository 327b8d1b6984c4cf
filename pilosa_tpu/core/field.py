"""Field: a typed attribute of an index.

Reference: field.go:73. A field owns views (variants of its data — the
standard view plus time-quantum views, reference: view.go:26-33), each view
holding one fragment per shard. Int-like fields (int/decimal/timestamp)
store BSI fragments; set-like fields store bitmap-row fragments. Row-key
translation lives on the field (reference: field.go:449).
"""

from __future__ import annotations

import datetime as dt
import os
import time
from typing import Dict, Iterable, List, Optional, Set

import numpy as np

from pilosa_tpu.core import timeq
from pilosa_tpu.obs.stages import record_stage
from pilosa_tpu.obs.tracing import annotate
from pilosa_tpu.core.fragment import BSIFragment, SetFragment, group_sorted
from pilosa_tpu.core.schema import (
    BOOL_FALSE_ROW,
    BOOL_TRUE_ROW,
    FieldOptions,
    FieldType,
)
from pilosa_tpu.core.translate import TranslateStore
from pilosa_tpu.shardwidth import SHARD_WIDTH, SHARD_WIDTH_EXP

_TIME_UNITS_PER_S = {"s": 1, "ms": 1000, "us": 1_000_000, "ns": 1_000_000_000}


class Field:
    def __init__(self, index_name: str, name: str, options: FieldOptions,
                 path: Optional[str] = None):
        self.index_name = index_name
        self.name = name
        self.options = options
        self.path = path
        if options.type == FieldType.TIME:
            timeq.validate_quantum(options.time_quantum)
        # view name -> shard -> fragment
        self.views: Dict[str, Dict[int, SetFragment]] = {}
        # BSI storage (int/decimal/timestamp): shard -> BSIFragment
        self.bsi: Dict[int, BSIFragment] = {}
        self.translate = (
            TranslateStore(self._translate_path(), start=1) if options.keys else None
        )
        # Per-index write-ahead log, attached by the owning Index when the
        # holder is durable (storage/wal.py). Field-level write methods are
        # the single logging funnel; fragment methods never log.
        self.wal = None

    def _translate_path(self) -> Optional[str]:
        if self.path is None:
            return None
        return os.path.join(self.path, "keys.jsonl")

    # -- value <-> stored mapping (BSI) -------------------------------------

    def to_stored(self, value) -> int:
        """External value -> stored integer (reference: field.go bsiGroup
        base/scale handling; decimal scale field.go:293)."""
        t = self.options.type
        if t == FieldType.DECIMAL:
            scaled = round(float(value) * (10 ** self.options.scale))
            return int(scaled) - self.options.base
        if t == FieldType.TIMESTAMP:
            if isinstance(value, str):
                value = dt.datetime.fromisoformat(value.replace("Z", "+00:00"))
            if isinstance(value, dt.datetime):
                if value.tzinfo is None:
                    value = value.replace(tzinfo=dt.timezone.utc)
                value = value.timestamp() * _TIME_UNITS_PER_S[self.options.time_unit]
            return int(round(value)) - self.options.base
        if self.options.min is not None and value < self.options.min:
            raise ValueError(f"value {value} < field min {self.options.min}")
        if self.options.max is not None and value > self.options.max:
            raise ValueError(f"value {value} > field max {self.options.max}")
        return int(value) - self.options.base

    def from_stored(self, stored: int):
        t = self.options.type
        raw = stored + self.options.base
        if t == FieldType.DECIMAL:
            return raw / (10 ** self.options.scale)
        return raw

    # -- fragment accessors --------------------------------------------------

    def fragment(self, shard: int, view: str = timeq.VIEW_STANDARD,
                 create: bool = False) -> Optional[SetFragment]:
        frags = self.views.get(view)
        if frags is None:
            if not create:
                return None
            frags = self.views[view] = {}
        frag = frags.get(shard)
        if frag is None:
            if not create:
                return None
            frag = frags[shard] = SetFragment(shard)
        return frag

    def bsi_fragment(self, shard: int, create: bool = False) -> Optional[BSIFragment]:
        frag = self.bsi.get(shard)
        if frag is None and create:
            frag = self.bsi[shard] = BSIFragment(shard)
        return frag

    def shards(self) -> Set[int]:
        out: Set[int] = set(self.bsi)
        for frags in self.views.values():
            out.update(frags)
        return out

    def view_names(self) -> List[str]:
        return sorted(self.views)

    # -- write path ----------------------------------------------------------

    def _write_views(self, timestamp: Optional[dt.datetime]) -> List[str]:
        views = [timeq.VIEW_STANDARD]
        if timestamp is not None:
            if self.options.type != FieldType.TIME:
                raise ValueError(f"field {self.name} does not support timestamps")
            views += timeq.views_by_time(timestamp, self.options.time_quantum)
        return views

    def _log(self, *record) -> None:
        if self.wal is not None:
            self.wal.append(record)

    def set_bit(self, row: int, col: int,
                timestamp: Optional[dt.datetime] = None) -> bool:
        """Set (row, col); mutex/bool clear other rows of the column first
        (reference: fragment.go setBit + mutex handling
        fragment.go:1787)."""
        views = self._write_views(timestamp)  # validates before logging
        self._log("set_bit", self.name, row, col,
                  timestamp.isoformat() if timestamp else None)
        shard, pos = divmod(col, SHARD_WIDTH)
        changed = False
        for view in views:
            frag = self.fragment(shard, view, create=True)
            if self.options.type in (FieldType.MUTEX, FieldType.BOOL):
                changed |= frag.clear_column(pos, except_row=row)
            changed |= frag.set_bit(row, pos)
        return changed

    def clear_bit(self, row: int, col: int) -> bool:
        self._log("clear_bit", self.name, row, col)
        shard, pos = divmod(col, SHARD_WIDTH)
        changed = False
        # Clears apply to every view (reference: fragment clearBit per view).
        for view in list(self.views):
            frag = self.fragment(shard, view)
            if frag is not None:
                changed |= frag.clear_bit(row, pos)
        return changed

    def set_bool(self, col: int, value: bool) -> bool:
        return self.set_bit(BOOL_TRUE_ROW if value else BOOL_FALSE_ROW, col)

    def set_value(self, col: int, value) -> None:
        self.set_values([col], [value])

    def _to_stored_bulk(self, values) -> np.ndarray:
        """Vectorized to_stored for int/decimal columns; element-wise
        fallback (timestamps, mixed types) otherwise. Validates (min/max
        bounds raise here) exactly like to_stored."""
        t = self.options.type
        try:
            if t == FieldType.INT:
                out = np.asarray(values, dtype=np.int64)
            elif t == FieldType.DECIMAL:
                out = np.round(np.asarray(values, dtype=np.float64)
                               * (10 ** self.options.scale)).astype(np.int64)
                return out - self.options.base
            else:
                raise TypeError
        except (TypeError, ValueError, OverflowError):
            return np.array([self.to_stored(v) for v in values],
                            dtype=np.int64)
        if self.options.min is not None and (out < self.options.min).any():
            bad = int(out[out < self.options.min][0])
            raise ValueError(f"value {bad} < field min {self.options.min}")
        if self.options.max is not None and (out > self.options.max).any():
            bad = int(out[out > self.options.max][0])
            raise ValueError(f"value {bad} > field max {self.options.max}")
        return out - self.options.base

    def set_values(self, cols: Iterable[int], values: Iterable) -> None:
        # the ``fragment_advance`` stage spans the whole bulk call
        # (conversion, WAL append, fragment writes); the profiler leaf
        # starts after the WAL append, which flushes under
        # wal_sync=always and has a leaf of its own there
        t0 = time.perf_counter()
        if not isinstance(cols, (list, tuple, np.ndarray)):
            cols = list(cols)  # generators/iterators per the signature
        cols = np.asarray(cols, dtype=np.int64).ravel()
        # Convert (and validate: min/max bounds raise here) BEFORE logging
        # so a rejected write never poisons the WAL for replay.
        if not isinstance(values, (list, tuple, np.ndarray)):
            values = list(values)
        stored = self._to_stored_bulk(values)
        if cols.size != stored.size:
            raise ValueError("cols and values must be the same length")
        # Log *external* values so replay runs through to_stored again
        # (deterministic; keeps decimal/timestamp conversion in one place).
        self._log("set_values", self.name, cols, np.asarray(values))
        shards = cols >> SHARD_WIDTH_EXP
        pos = cols & (SHARD_WIDTH - 1)
        with annotate("import.fragment_advance"):
            for shard, (p, v) in group_sorted(shards, pos, stored):
                self.bsi_fragment(shard, create=True).set_values(p, v)
        record_stage("fragment_advance", time.perf_counter() - t0,
                     rows=cols.size)

    def clear_value(self, col: int) -> bool:
        self._log("clear_value", self.name, col)
        shard, pos = divmod(col, SHARD_WIDTH)
        frag = self.bsi_fragment(shard)
        return frag.clear_value(pos) if frag else False

    def import_bits(self, rows: Iterable[int], cols: Iterable[int],
                    clear: bool = False) -> int:
        """Bulk (row, col) import with IDs already translated (reference:
        fragment.go:1498 bulkImport; mutex variant :1787). Returns changed
        bit count. The one bulk WAL record replaces per-bit logging."""
        t0 = time.perf_counter()
        if not isinstance(rows, (list, tuple, np.ndarray)):
            rows = list(rows)  # generators/iterators per the signature
        if not isinstance(cols, (list, tuple, np.ndarray)):
            cols = list(cols)
        rows = np.asarray(rows, dtype=np.int64).ravel()
        cols = np.asarray(cols, dtype=np.int64).ravel()
        if rows.size != cols.size:
            raise ValueError("rows and cols must be the same length")
        n, changed = cols.size, 0
        try:
            if clear:
                # per-bit so every view is cleared; clear_bit logs itself
                for r, c in zip(rows, cols):
                    changed += self.clear_bit(int(r), int(c))
                return changed
            mutex = self.options.type in (FieldType.MUTEX, FieldType.BOOL)
            if mutex and rows.size < 256:
                # Small interactive batches: per-bit keeps fine-grained
                # device deltas (reference: fragment.go:1787
                # bulkImportMutex).
                for r, c in zip(rows, cols):
                    changed += self.set_bit(int(r), int(c))
                return changed
            if mutex:
                # Bulk mutex: later duplicates win per column, then one
                # vectorized clear-and-set per shard.
                _, last = np.unique(cols[::-1], return_index=True)
                idx = cols.size - 1 - last
                rows, cols = rows[idx], cols[idx]
            self._log("import_bits", self.name, rows, cols)
            shards = cols >> SHARD_WIDTH_EXP
            pos = cols & (SHARD_WIDTH - 1)
            # a profiler leaf like set_values': after the WAL append
            with annotate("import.fragment_advance"):
                for shard, (r, p) in group_sorted(shards, rows, pos):
                    frag = self.fragment(shard, create=True)
                    changed += frag.set_mutex_many(r, p) if mutex \
                        else frag.set_many(r, p)
            return changed
        finally:
            record_stage("fragment_advance", time.perf_counter() - t0,
                         rows=n)

    def write_row_plane(self, shard: int, row: int, plane,
                        clear: bool = False,
                        view: str = timeq.VIEW_STANDARD) -> None:
        """Merge (OR) or replace one row plane, WAL-logged (the Store /
        import-roaring write path; reference: fragment.go:2038
        importRoaring, executor.go executeSetRow)."""
        from pilosa_tpu.storage.wal import pack_plane

        self._log("row_plane", self.name, view, shard, row,
                  pack_plane(plane), clear)
        frag = self.fragment(shard, view, create=True)
        frag.import_row_plane(row, plane, clear=clear)

    def clear_row_plane_bits(self, shard: int, row: int, plane,
                             view: str = timeq.VIEW_STANDARD) -> bool:
        """Clear the bits of ``plane`` from one row (the clear side of a
        roaring import, reference: fragment.go:2053
        ImportRoaringClearAndSet)."""
        from pilosa_tpu.storage.wal import pack_plane

        self._log("clear_row_bits", self.name, view, shard, row,
                  pack_plane(plane))
        frag = self.fragment(shard, view)
        if frag is None:
            return False
        return frag.clear_row_plane_bits(row, plane)

    def clear_row(self, row: int) -> bool:
        """Zero a row across all views and shards (reference: executor.go
        executeClearRow)."""
        self._log("clear_row", self.name, row)
        changed = False
        for view in list(self.views):
            for shard, frag in self.views[view].items():
                if frag.has_row(row):
                    frag.import_row_plane(
                        row, np.zeros(frag.words, dtype=np.uint32), clear=True)
                    changed = True
        return changed

    def clear_columns(self, shard: int, plane, log: bool = True) -> None:
        """Clear the columns of ``plane`` from every view fragment and the
        BSI planes of this shard (record deletion, reference:
        executor.go:9050 executeDeleteRecords). ``log=False`` when the
        owning Index already logged one index-level delete record."""
        if log:
            from pilosa_tpu.storage.wal import pack_plane

            self._log("clear_cols", self.name, shard, pack_plane(plane))
        for view_frags in self.views.values():
            frag = view_frags.get(shard)
            if frag is not None:
                frag.clear_plane(plane)
        bsi = self.bsi.get(shard)
        if bsi is not None:
            bsi.clear_plane(plane)

    def value(self, col: int):
        shard, pos = divmod(col, SHARD_WIDTH)
        frag = self.bsi_fragment(shard)
        if frag is None:
            return None
        stored = frag.value(pos)
        return None if stored is None else self.from_stored(stored)

    # -- read helpers ----------------------------------------------------------

    def range_views(self, from_t: Optional[dt.datetime],
                    to_t: Optional[dt.datetime]) -> List[str]:
        """Views covering a time range query (reference: field.go:1001
        viewsByTimeRange dispatch)."""
        if from_t is None and to_t is None:
            return [timeq.VIEW_STANDARD]
        if self.options.type != FieldType.TIME:
            raise ValueError(f"field {self.name} is not a time field")
        # default bounds adopt the other side's tzinfo — naive-vs-aware
        # comparison raises in the cover recursion
        tz = (from_t or to_t).tzinfo
        lo = from_t or dt.datetime(1, 1, 1, tzinfo=tz)
        hi = to_t or dt.datetime(9999, 1, 1, tzinfo=tz)
        views = timeq.views_by_time_range(lo, hi, self.options.time_quantum)
        # open-ended ranges cover millennia of candidate view names;
        # only views holding data can contribute (reference reads are
        # bounded the same way — absent views have no fragments)
        return [v for v in views if v in self.views]
