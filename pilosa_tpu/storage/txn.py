"""Qcx / TxFactory: per-request transaction contexts.

Reference: txfactory.go:84 (Qcx) / :384 (TxFactory). The reference
multiplexes one RBF Tx per (index, shard) touched by a query, group-rolls
back reads and locally commits writes at ``Qcx.Finish``. In the TPU build
reads are snapshot-consistent for free (queries run against immutable
device arrays stacked from the host planes — a write bumps versions and
the next query re-stacks, core/stacked.py), so the read half of Qcx
disappears by construction.

What remains is the write half: WAL records buffer in each index's log
during a request and ``finish()`` issues ONE write barrier per dirty index
— the group commit that makes a multi-call PQL write request durable as a
unit (the analog of StartAtomicWriteTx, txfactory.go:344).
"""

from __future__ import annotations

import threading
import time
from typing import TYPE_CHECKING

from pilosa_tpu.obs.stages import record_stage
from pilosa_tpu.obs.tracing import get_tracer

if TYPE_CHECKING:
    from pilosa_tpu.core.holder import Holder

_WRITE_CTX = threading.local()


def in_write_qcx() -> bool:
    """True while the calling thread is inside a write Qcx. Stacked-cache
    publication is suppressed for such threads (core/stacked.py): a
    multi-call write request like Set(a)Set(b)Count() builds stacks
    mid-request, and publishing them would let concurrent lock-free
    readers observe the request's intermediate states — the request-level
    atomicity the always-Qcx read path used to provide."""
    return getattr(_WRITE_CTX, "depth", 0) > 0


class Qcx:
    """One query/request context. Use as a context manager:

        with txf.qcx() as qcx:
            ... writes ...
        # exit -> finish() -> WAL flush (fsync per dirty index)
    """

    def __init__(self, holder: "Holder"):
        self.holder = holder
        self._done = False
        # LSN of the last record this commit made durable (set by
        # finish; 0 for path-less holders / read-only requests).
        self.lsn = 0
        # Exclude concurrent writers AND checkpoints for the request: a
        # checkpoint racing a half-applied multi-call write would snapshot
        # and truncate records it never persisted. RLock so nested Qcx
        # (query -> import helpers) is fine. The wait is the write
        # path's ``lock_wait`` stage: behind another writer, a checkpoint
        # or a reader's stack build (a counter and no profiler leaf, like
        # stacked.writer_wait: the holder's work owns those seconds).
        t0 = time.perf_counter()
        self.holder.write_lock.acquire()
        record_stage("lock_wait", time.perf_counter() - t0)
        _WRITE_CTX.depth = getattr(_WRITE_CTX, "depth", 0) + 1

    def finish(self) -> int:
        """Group commit. Returns the commit LSN: every WAL record up to
        it is flushed (and fsynced per the sync mode) — the monotonic
        position checkpoints stamp and catch-up ships against."""
        if self._done:
            return self.lsn
        self._done = True
        try:
            with get_tracer().start_span("storage.wal.commit"):
                self.holder.flush_wals()
                self.lsn = self.holder.last_lsn()
                t0 = time.perf_counter()
                if self.holder.maybe_checkpoint():
                    record_stage("checkpoint", time.perf_counter() - t0)
        finally:
            _WRITE_CTX.depth -= 1
            self.holder.write_lock.release()
        return self.lsn

    def __enter__(self) -> "Qcx":
        return self

    def __exit__(self, *exc) -> None:
        self.finish()


class TxFactory:
    """Reference: txfactory.go:384. Owns the durability policy for a
    holder and mints Qcx contexts."""

    def __init__(self, holder: "Holder"):
        self.holder = holder

    def qcx(self) -> Qcx:
        return Qcx(self.holder)
