"""Key translation: string keys <-> uint64 IDs, host-side.

The reference keeps record-key stores partitioned across nodes (BoltDB,
reference: translate_boltdb.go:69, partition routing disco/snapshot.go:87)
and row-key stores on the field primary. Strings never reach the device —
IDs flow in, IDs flow out, translation happens on the host around kernel
dispatch (reference: executor.go:6814 preTranslate / :7519
translateResults). Here: an in-process dict store with an append-only
journal for durability (the BoltDB analog; swap for the C++ store later).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

from pilosa_tpu.obs.stages import record_stage
from pilosa_tpu.obs.tracing import annotate


class TranslateStore:
    """One key<->id namespace (an index's record keys, or a field's row
    keys). IDs are allocated sequentially from ``start``.

    Record-key stores start at 0; the reference reserves id 0 as invalid
    for row keys, so field stores pass start=1 (reference:
    translate.go boltdb sequence start).
    """

    def __init__(self, path: Optional[str] = None, start: int = 0):
        self._path = path
        self._start = start
        self._next = start
        self._lock = threading.Lock()  # create RPCs arrive concurrently
        self.key_to_id: Dict[str, int] = {}
        self.id_to_key: Dict[int, str] = {}
        if path and os.path.exists(path):
            self._load()

    def _load(self):
        with open(self._path) as f:
            for line in f:
                if not line.strip():
                    continue
                key, id_ = json.loads(line)
                self.key_to_id[key] = id_
                self.id_to_key[id_] = key
                self._next = max(self._next, id_ + 1)

    def _append(self, pairs: List):
        if not self._path:
            return
        os.makedirs(os.path.dirname(self._path), exist_ok=True)
        with open(self._path, "a") as f:
            for key, id_ in pairs:
                f.write(json.dumps([key, id_]) + "\n")

    def create_keys(self, keys: Iterable[str]) -> Dict[str, int]:
        return self.create_entries(keys)[0]

    def create_entries(self, keys: Iterable[str]
                       ) -> "Tuple[Dict[str, int], List]":
        """Find-or-create IDs; also returns the NEWLY allocated
        (key, id) pairs — the replication stream's payload (reference:
        cluster.go:233 createIndexKeys + translate.go EntryReader
        entries)."""
        out: Dict[str, int] = {}
        new: List = []
        with self._lock:
            for k in keys:
                id_ = self.key_to_id.get(k)
                if id_ is None:
                    id_ = self._next
                    self._next += 1
                    self.key_to_id[k] = id_
                    self.id_to_key[id_] = k
                    new.append((k, id_))
                out[k] = id_
            if new:
                self._append(new)
        return out, new

    def apply_entries(self, entries: Iterable) -> None:
        """Apply replicated (key, id) pairs from the primary (reference:
        the follower side of TranslationSyncer/EntryReader,
        translate.go). Idempotent; advances the allocator past every
        applied id so a PROMOTED replica allocates non-conflicting ids."""
        with self._lock:
            fresh = []
            for k, id_ in entries:
                id_ = int(id_)
                if self.key_to_id.get(k) == id_:
                    continue
                self.key_to_id[k] = id_
                self.id_to_key[id_] = k
                self._next = max(self._next, id_ + 1)
                fresh.append((k, id_))
            if fresh:
                self._append(fresh)

    def find_keys(self, keys: Iterable[str]) -> Dict[str, int]:
        return {k: self.key_to_id[k] for k in keys if k in self.key_to_id}

    def translate_ids(self, ids: Iterable[int]) -> Dict[int, str]:
        return {i: self.id_to_key[i] for i in ids if i in self.id_to_key}

    def replace_all(self, key_to_id: Dict[str, int]) -> None:
        """Replace the whole mapping AND rewrite the journal — the restore
        path (reference: restore writes translate partitions wholesale,
        ctl/restore.go)."""
        with self._lock:
            self.key_to_id = dict(key_to_id)
            self.id_to_key = {i: k for k, i in key_to_id.items()}
            self._next = max([i + 1 for i in key_to_id.values()]
                             + [self._start])
            if self._path:
                os.makedirs(os.path.dirname(self._path), exist_ok=True)
                with open(self._path, "w") as f:
                    for key, id_ in sorted(key_to_id.items(),
                                           key=lambda kv: kv[1]):
                        f.write(json.dumps([key, id_]) + "\n")

    def __len__(self) -> int:
        return len(self.key_to_id)


class PartitionedTranslateStore:
    """Record-key store partitioned the way the reference partitions its
    BoltDB stores (translate_boltdb.go:69 + disco/snapshot.go:87): a key
    belongs to partition fnv64a(index||key)%N, and the ID allocated for it
    is chosen so the ID's *shard* hashes back to the same partition
    (reference: translate.go:103 GenerateNextPartitionedID). Shard
    ownership and key ownership therefore coincide — the column a key
    names lives on the node that owns the key.

    Same journal format as TranslateStore; partition state is
    reconstructed from key hashes on load.
    """

    def __init__(self, index: str, path: Optional[str] = None,
                 partition_n: int = 256):
        from pilosa_tpu.hashing import key_to_partition, shard_to_partition
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        self._index = index
        self._path = path
        self._partition_n = partition_n
        self._key_to_partition = key_to_partition
        self._shard_to_partition = shard_to_partition
        self._shard_width = SHARD_WIDTH
        self._lock = threading.Lock()
        self.key_to_id: Dict[str, int] = {}
        self.id_to_key: Dict[int, str] = {}
        self._max_id: Dict[int, int] = {}  # partition -> max allocated id
        if path and os.path.exists(path):
            self._load()

    def _load(self):
        with open(self._path) as f:
            for line in f:
                if not line.strip():
                    continue
                key, id_ = json.loads(line)
                self.key_to_id[key] = id_
                self.id_to_key[id_] = key
                p = self.partition(key)
                self._max_id[p] = max(self._max_id.get(p, 0), id_)

    def partition(self, key: str) -> int:
        return self._key_to_partition(self._index, key, self._partition_n)

    def _next_partitioned_id(self, partition: int) -> int:
        """Reference: translate.go:111 — walk forward by shard until the
        shard's partition matches; IDs start at 1 (0 stays invalid). Also
        skips IDs already present, so journals written under other
        allocation schemes can't cause silent ID reuse."""
        id_ = self._max_id.get(partition, 0) + 1
        while True:
            if self._shard_to_partition(
                    self._index, id_ // self._shard_width,
                    self._partition_n) != partition:
                id_ += self._shard_width
            elif id_ in self.id_to_key:
                id_ += 1
            else:
                return id_

    def _append(self, pairs: List):
        if not self._path:
            return
        os.makedirs(os.path.dirname(self._path), exist_ok=True)
        with open(self._path, "a") as f:
            for key, id_ in pairs:
                f.write(json.dumps([key, id_]) + "\n")

    def create_keys(self, keys: Iterable[str]) -> Dict[str, int]:
        return self.create_entries(keys)[0]

    def create_entries(self, keys: Iterable[str]
                       ) -> "Tuple[Dict[str, int], List]":
        """Find-or-create with the new (key, id) pairs for the
        replication stream (see TranslateStore.create_entries)."""
        out: Dict[str, int] = {}
        new: List = []
        with self._lock:
            for k in keys:
                id_ = self.key_to_id.get(k)
                if id_ is None:
                    p = self.partition(k)
                    id_ = self._next_partitioned_id(p)
                    self._max_id[p] = id_
                    self.key_to_id[k] = id_
                    self.id_to_key[id_] = k
                    new.append((k, id_))
                out[k] = id_
            if new:
                self._append(new)
        return out, new

    def apply_entries(self, entries: Iterable) -> None:
        """Follower side of the replication stream (see
        TranslateStore.apply_entries); advances per-partition max ids so
        a promoted replica keeps the partitioned-ID invariant."""
        with self._lock:
            fresh = []
            for k, id_ in entries:
                id_ = int(id_)
                if self.key_to_id.get(k) == id_:
                    continue
                self.key_to_id[k] = id_
                self.id_to_key[id_] = k
                p = self.partition(k)
                self._max_id[p] = max(self._max_id.get(p, 0), id_)
                fresh.append((k, id_))
            if fresh:
                self._append(fresh)

    def find_keys(self, keys: Iterable[str]) -> Dict[str, int]:
        return {k: self.key_to_id[k] for k in keys if k in self.key_to_id}

    def translate_ids(self, ids: Iterable[int]) -> Dict[int, str]:
        return {i: self.id_to_key[i] for i in ids if i in self.id_to_key}

    def replace_all(self, key_to_id: Dict[str, int]) -> None:
        """Replace the whole mapping AND rewrite the journal (restore)."""
        with self._lock:
            self.key_to_id = dict(key_to_id)
            self.id_to_key = {i: k for k, i in key_to_id.items()}
            self._max_id = {}
            for k, id_ in key_to_id.items():
                p = self.partition(k)
                self._max_id[p] = max(self._max_id.get(p, 0), id_)
            if self._path:
                os.makedirs(os.path.dirname(self._path), exist_ok=True)
                with open(self._path, "w") as f:
                    for key, id_ in sorted(key_to_id.items(),
                                           key=lambda kv: kv[1]):
                        f.write(json.dumps([key, id_]) + "\n")

    def __len__(self) -> int:
        return len(self.key_to_id)


def bulk_translate_ids(store, keys) -> "object":
    """Vectorized find-or-create: ONE create_keys round on the unique
    keys, mapped back through a LUT (reference: batch.go:860
    doTranslation batches unique keys the same way). Returns an
    ``np.int64`` array aligned with ``keys``. Every bulk ingest path
    translates here, so this is where the ``key_translate`` stage is
    taken."""
    import numpy as np

    t0 = time.perf_counter()
    with annotate("import.key_translate"):
        arr = np.asarray(keys)
        uniq, inverse = np.unique(arr, return_inverse=True)
        uniq_l = [str(k) for k in uniq.tolist()]
        m = store.create_keys(uniq_l)
        lut = np.array([m[k] for k in uniq_l], dtype=np.int64)
        out = lut[inverse]
    record_stage("key_translate", time.perf_counter() - t0, rows=arr.size)
    return out
