"""Ingest stage accounting: always on, counted as it accrues.

Every write request passes a handful of stages, and each records its
wall seconds (and the rows or bytes it moved) here the moment it ends:
``decode`` (the import routes' JSON body), ``parse`` (a source's whole
column or batch), ``key_translate``, ``lock_wait`` (a write request
waiting for the holder's write lock), ``fragment_advance`` (one bulk
field call: conversion, WAL append, fragment writes), ``h2d_copy``
(the device scatter's upload, a part of ``fragment_advance``),
``wal_commit`` (flush + fsync) and ``checkpoint`` (a checkpoint that a
commit triggered). They feed the ``ingest_stage_*_total`` counters on
``GET /metrics`` (a scrape delta over a window is the window's; a rate
is a delta over its seconds) and the ``ingest`` block of
``GET /internal/stats/kernels``.

A stage record is one short lock and three dictionary updates, taken
once per bulk call or request, never per row.
"""

from __future__ import annotations

from typing import Dict

from pilosa_tpu.analysis import locktrace
from pilosa_tpu.obs import metrics as M


class IngestAccounting:
    """Per-stage cumulative wall seconds, rows, bytes and calls."""

    def __init__(self) -> None:
        self._lock = locktrace.tracked_lock("obs.stages.ingest")
        # stage -> [seconds, rows, bytes, batches]
        self._stages: Dict[str, list] = {}

    def record(self, stage: str, seconds: float, rows: int = 0,
               nbytes: int = 0) -> None:
        with self._lock:
            ent = self._stages.get(stage)
            if ent is None:
                ent = self._stages[stage] = [0.0, 0, 0, 0]
            ent[0] += seconds
            ent[1] += rows
            ent[2] += nbytes
            ent[3] += 1
        reg = M.REGISTRY
        reg.count(M.METRIC_INGEST_STAGE_SECONDS, seconds, stage=stage)
        if rows:
            reg.count(M.METRIC_INGEST_STAGE_ROWS, rows, stage=stage)
        if nbytes:
            reg.count(M.METRIC_INGEST_STAGE_BYTES, nbytes, stage=stage)

    def snapshot(self) -> Dict[str, dict]:
        with self._lock:
            rows = {s: list(e) for s, e in self._stages.items()}
        out: Dict[str, dict] = {}
        for stage, (secs, nrows, nbytes, batches) in rows.items():
            d = {"seconds": round(secs, 6), "rows": nrows,
                 "bytes": nbytes, "batches": batches}
            if secs > 0:
                if nrows:
                    d["rows_per_s"] = round(nrows / secs, 1)
                if nbytes:
                    d["bytes_per_s"] = round(nbytes / secs, 1)
            out[stage] = d
        return out

    def reset(self) -> None:
        with self._lock:
            self._stages.clear()


INGEST = IngestAccounting()


def record_stage(stage: str, seconds: float, rows: int = 0,
                 nbytes: int = 0) -> None:
    """Module-level convenience for the ingest, WAL and Qcx call sites."""
    INGEST.record(stage, seconds, rows=rows, nbytes=nbytes)
