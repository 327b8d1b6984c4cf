"""Stacked device views: a field's fragments across shards as ONE tensor,
paged into row blocks under an HBM budget.

The key TPU-latency insight: every PQL read kernel (popcount reductions,
BSI compare circuits, pair-count matmuls) reduces over *columns* and never
mixes columns, so concatenating the per-shard word axes

    shard planes  uint32[R, W]  x S shards  ->  uint32[R, S*W]

makes every single-shard kernel multi-shard with zero changes — one XLA
dispatch and ONE host round-trip per query instead of one per shard.
Every blocking fetch is a host-device round trip, so this is the
difference between per-query latency scaling with shard count (the
reference's per-shard map loop, executor.go:6742 mapperLocal) and staying
flat.

Row slots are the union of row IDs across the stacked fragments so one
slot index addresses the same row in every shard (the reference gets this
for free from row-major roaring addressing, fragment.go:34-49).

**Row-block paging (SURVEY §7 "ragged row counts").** Where roaring adapts
per container (roaring.go:53-58), dense planes cost ``S*W*4`` bytes per
row — a 50k-row field over 8 shards is ~50 GB, far beyond HBM. Stacks
whose full tensor exceeds one block therefore page: slots are chunked
into fixed-shape ``uint32[block_rows, S*W]`` blocks (one XLA executable
per shape), each built lazily from the host fragments on first touch and
LRU-evicted by the global :class:`DeviceBudget`. Full-scan kernels
(TopN/Rows/GroupBy) stream the blocks; point reads touch one block.

Lazy builds preserve snapshot consistency by *versioning*, not copying: a
block built after a member fragment changed raises :class:`StackStale`
and the executor retries the whole (pure, re-executable) read against a
fresh stack — the paging analog of RBF's page-map snapshot isolation
(rbf/page_map.go).

Caches are hung on the owning Field keyed by (view, shard tuple) and
validated against the fragment version vector — a write to any member
fragment invalidates, with two cheap advance paths instead of a rebuild:
masked scatters for existing-row bit flips, and in-place slot append for
new rows (streaming ingest; VERDICT r3 #5).
"""

from __future__ import annotations

import contextlib
import itertools
import math
import os
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu import platform
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.ops import bitmap as bitops
from pilosa_tpu.ops import bsi as bsiops
from pilosa_tpu.ops import ctiles
from pilosa_tpu.ops import keyrows
from pilosa_tpu.shardwidth import WORDS_PER_SHARD

_MIN_SLOTS = 8

#: a resident block is either a dense device tensor or a compressed-tile
#: block (ops/ctiles.py) — consumers that need dense words go through
#: :func:`_dense`, scans dispatch on the type for the tile-skipping path
Block = object


def _dense(blk) -> jax.Array:
    """Dense ``uint32[R, W]`` view of a resident block: identity for
    dense tensors, a device-side gather (no host staging) for
    compressed ones."""
    if isinstance(blk, ctiles.CompressedBlock):
        return blk.decode()
    return blk


def _take(blk, src) -> jax.Array:
    """Row-subset gather from a resident block (decodes only the
    requested rows of a compressed block)."""
    src = np.asarray(src, dtype=np.int32)
    if isinstance(blk, ctiles.CompressedBlock):
        return blk.decode(rows=src)
    return jnp.take(blk, jnp.asarray(src), axis=0)


# Full-stack uploads (host -> device transfers of whole stacked tensors or
# blocks). The incremental write-merge path must NOT bump these — tests
# assert a setbit between two queries costs a tiny scatter, not a
# re-upload.
UPLOAD_STATS = {"count": 0, "bytes": 0}

# Paged-stack observability: block (re)builds and budget evictions.
PAGING_STATS = {"block_builds": 0, "evictions": 0, "stale_retries": 0}


class StackStale(RuntimeError):
    """A lazy block build found its member fragments newer than the
    stack's snapshot version. The read must restart on a fresh stack
    (executor.execute retries; writes are excluded on the final try)."""


_SYNC_PARTS: Optional[bool] = None


def sync_part(arr):
    """On the CPU backend, block on each per-block kernel before the next
    launches: XLA's in-process CPU collectives can deadlock (and abort
    via AwaitAndLogIfStuck) when many SPMD programs queue concurrently.
    Real TPU streams execute programs in order, so block streaming stays
    fully async there."""
    global _SYNC_PARTS
    if _SYNC_PARTS is None:
        _SYNC_PARTS = jax.devices()[0].platform == "cpu"
    if _SYNC_PARTS:
        jax.block_until_ready(arr)
    return arr


def _sent(host: np.ndarray, kind: str, span, compress: bool = True):
    """The resident form of a block just assembled on the host, sent to
    the devices inside its ``stack.build`` span: compressed tiles where
    the policy says so (never for key planes, ``compress`` False), else
    dense on the engine device mesh — the fused
    (shard, word) last axis splits across all mesh devices, so the jitted
    query kernels execute SPMD with XLA-inserted collective reduces
    (parallel/mesh.py engine mesh; the reference's shard->node scatter +
    HTTP reduce, executor.go:6449, becomes shard->device + psum)."""
    from pilosa_tpu.parallel.mesh import engine_put

    blk = ctiles.maybe_compress(host, kind=kind) if compress else None
    if blk is None:
        blk = engine_put(host)
    UPLOAD_STATS["count"] += 1
    UPLOAD_STATS["bytes"] += blk.nbytes
    M.REGISTRY.count(M.METRIC_STACK_BUILD_BYTES, blk.nbytes)
    if span.recording:
        span.set_tag("form", "keys" if not compress else
                     "compressed" if isinstance(blk, ctiles.CompressedBlock)
                     else "dense")
        held = device_bytes(blk)
        span.set_tag("devices", len(held))
        span.set_tag("bytes_per_device", max(held.values()))
    return blk


def _pow2(n: int) -> int:
    cap = _MIN_SLOTS
    while cap < n:
        cap *= 2
    return cap


# ---------------------------------------------------------------------------
# Device-memory budget: LRU over ALL resident stacked planes. Paged blocks,
# unpaged single-block stacks, and BSI plane stacks are each a charged
# entry — the budget is the full accounting of the device-residency plane,
# and `device_hbm_resident_bytes` mirrors it. An evicted resident block is
# lazily rebuilt on next touch with the same version check paged blocks
# always had (a write since the snapshot -> StackStale -> executor retry).
# ---------------------------------------------------------------------------

def _env_mb(name: str, default_mb: int) -> int:
    try:
        return int(os.environ.get(name, default_mb))
    except ValueError:
        return default_mb


def _budget_bytes() -> int:
    """HBM budget in bytes. ``PILOSA_TPU_DEVICE_BUDGET`` (bytes — the CI
    clamp knob, precise enough to force paging on tiny test data) wins
    over ``PILOSA_TPU_HBM_BUDGET_MB``."""
    raw = os.environ.get("PILOSA_TPU_DEVICE_BUDGET")
    if raw:
        try:
            return int(raw)
        except ValueError:
            pass
    return _env_mb("PILOSA_TPU_HBM_BUDGET_MB", 6144) << 20


def device_bytes(*blocks) -> Dict[int, int]:
    """{device id: bytes} that ``blocks`` (dense device tensors or
    compressed blocks) hold on each device, read from their own
    sharding: a tensor split over the engine mesh costs each device its
    share, one placed on a single device (one chip, a mesh fallback, a
    compressed block) costs that device all of it, a replicated one
    costs every device all of it."""
    out: Dict[int, int] = {}
    for blk in blocks:
        if isinstance(blk, ctiles.CompressedBlock):
            sharding, per = blk.payload.sharding, blk.nbytes
        else:
            sharding = blk.sharding
            per = (math.prod(sharding.shard_shape(blk.shape))
                   * blk.dtype.itemsize)
        for d in sharding.device_set:
            out[d.id] = out.get(d.id, 0) + per
    return out


class DeviceBudget:
    """Per-device byte-capped LRU of evictable device arrays (paged
    stack blocks): every device stays under ``cap``, and an entry costs
    each device the bytes it holds there (:func:`device_bytes`; a plain
    byte count is bytes on the default device). ``used`` is the fullest
    device's bytes, which on one device is every byte charged.

    Eviction drops the owner's *reference*; in-flight kernels keep the
    buffer alive until they finish (XLA buffers are refcounted), so no
    pinning protocol is needed — an evicted block is simply rebuilt from
    the host on next touch (the RBF page-cache analog, rbf/db.go mmap)."""

    def __init__(self, cap_bytes: int):
        self.cap = cap_bytes
        self._used: Dict[int, int] = {}
        self._lock = threading.Lock()
        self._lru: "OrderedDict[Tuple, Tuple[Dict[int, int], object]]" = \
            OrderedDict()

    @property
    def used(self) -> int:
        return max(self._used.values(), default=0)

    def _add(self, cost: Dict[int, int], sign: int) -> None:
        for d, b in cost.items():
            left = self._used.get(d, 0) + sign * b
            if left:
                self._used[d] = left
            else:
                self._used.pop(d, None)

    def _over(self) -> List[int]:
        return [d for d, b in self._used.items() if b > self.cap]

    def _gauges(self) -> None:
        M.REGISTRY.gauge(M.METRIC_DEVICE_HBM_RESIDENT_BYTES, self.used)
        M.REGISTRY.gauge(M.METRIC_DEVICE_BUDGET_RESIDENT_BYTES, self.used)

    def charge(self, key: Tuple, cost, evict_cb) -> None:
        if not isinstance(cost, dict):
            cost = {jax.devices()[0].id: int(cost)}
        with self._lock:
            old = self._lru.pop(key, None)
            if old is not None:
                self._add(old[0], -1)
            self._lru[key] = (cost, evict_cb)
            self._add(cost, +1)
            over = self._over()
            # oldest first, never the entry being inserted, and only
            # entries that free bytes on a device that is over the cap
            for k in [k for k in self._lru if k != key] if over else ():
                held, cb = self._lru[k]
                if not any(held.get(d) for d in over):
                    continue
                del self._lru[k]
                self._add(held, -1)
                PAGING_STATS["evictions"] += 1
                M.REGISTRY.count(M.METRIC_DEVICE_STACK_EVICTIONS)
                M.REGISTRY.count(M.METRIC_DEVICE_BUDGET_EVICTIONS)
                cb()
                over = self._over()
                if not over:
                    break
            self._gauges()

    def touch(self, key: Tuple) -> None:
        with self._lock:
            if key in self._lru:
                self._lru.move_to_end(key)

    def room(self) -> int:
        """Bytes that can still be charged to the fullest device without
        evicting anything."""
        with self._lock:
            return self.cap - self.used

    def release(self, key: Tuple) -> None:
        with self._lock:
            old = self._lru.pop(key, None)
            if old is not None:
                self._add(old[0], -1)
                self._gauges()

    def audit(self) -> None:
        """Accounting invariants (the testhook auditor analog,
        reference: testhook/auditor.go): each device's byte counter must
        equal the sum of resident entries there — a drift means a leak or
        double-release somewhere in the charge/evict/release protocol."""
        with self._lock:
            total: Dict[int, int] = {}
            for held, _ in self._lru.values():
                for d, b in held.items():
                    total[d] = total.get(d, 0) + b
            assert total == self._used, (
                f"DeviceBudget drift: used={self._used} entries={total}")


#: Default HBM budget for resident stacked planes, on every device of the
#: engine mesh (a v5e chip has 16 GiB; leave headroom for kernel
#: workspace and XLA constants).
BUDGET = DeviceBudget(_budget_bytes())

#: Target bytes per row block on the device that holds most of it. A
#: stack pages when its full tensor would exceed one block. Tests
#: override via env to exercise paging cheaply.
_BLOCK_BYTES = _env_mb("PILOSA_TPU_BLOCK_BYTES_MB", 256) << 20


def _row_bytes(total_words: int) -> int:
    """Bytes of one ``total_words``-word plane on its widest device, as
    the engine mesh places a stack of that width."""
    from pilosa_tpu.parallel.mesh import words_per_device

    return words_per_device(total_words) * 4


def planes_per_block(total_words: int) -> int:
    """How many planes of ``total_words`` words one row block's bytes
    hold (at least one): what a layer above sizes a device working set
    by, so that it blocks where a stack of that width would page."""
    return max(1, _BLOCK_BYTES // max(_row_bytes(total_words), 1))


def _decode_whole(blk: ctiles.CompressedBlock, kind: str):
    """The dense words of a resident compressed block, decoded device-side
    for a consumer that walks them whole (GroupBy, Sum), and whether the
    budget has room for them: where it has, the caller keeps the dense
    words beside the block and charges both, so such a block is decoded
    once per residency, not once per read, while point reads and the
    tile-skipping counts go on reading the small form; a budget too tight
    for that keeps the small form alone and decodes per walk."""
    from pilosa_tpu.obs.tracing import annotate

    M.REGISTRY.count(M.METRIC_COMPRESS_DECODE, kind=kind)
    M.REGISTRY.count(M.METRIC_COMPRESS_DECODE_BYTES, blk.dense_nbytes)
    with annotate("stack.decode"):
        dense = blk.decode()
    return dense, BUDGET.room() >= blk.dense_nbytes

_stack_serial = itertools.count()


def _slot_layout(fragments, total_words: int):
    """(row ids, block rows, slot capacity) of a set stack over
    ``fragments``: slots are the union of their row ids in order, in
    power-of-two row blocks of at most ``_BLOCK_BYTES`` a device."""
    rows: set = set()
    for frag in fragments:
        if frag is not None:
            rows.update(frag.row_index)
    row_ids = sorted(rows)
    per_block = max(_MIN_SLOTS, planes_per_block(total_words))
    block_rows = min(_pow2(len(row_ids)), _pow2(per_block) // 2 or _MIN_SLOTS)
    if block_rows * _row_bytes(total_words) > _BLOCK_BYTES:
        block_rows = max(_MIN_SLOTS, block_rows // 2)
    cap = max(block_rows, -(-len(row_ids) // block_rows) * block_rows)
    return row_ids, block_rows, cap


def keyed_form(mutex: bool, cap: int, total_words: int) -> bool:
    """The rule, with no knob: a mutex (or bool) stack takes the
    key-plane form (:class:`KeyedSet`) when its dense bytes on its
    fullest device exceed the budget's cap. Such a stack can never be
    resident whole, so an LRU walk of it would miss every block and
    rebuild each from the host on every walk."""
    return mutex and cap * _row_bytes(total_words) > BUDGET.cap


class StackedSet:
    """Union-row view of set fragments: ``uint32[cap, S*W]`` in row blocks.

    Unpaged stacks (cap fits one block) materialize eagerly as a single
    tensor — the common case and the latency fast path. Paged stacks
    build blocks lazily and stream them.
    """

    def __init__(self, shards: Sequence[int], fragments,
                 words: int = WORDS_PER_SHARD, write_lock=None,
                 layout=None):
        self.shards = tuple(shards)
        self.words = words
        self.total_words = len(self.shards) * words
        self.serial = next(_stack_serial)
        # lazy block builds re-acquire this to exclude writers while
        # copying live host planes (the same lock stacked_set holds for
        # the eager build path)
        self._write_lock = (write_lock if write_lock is not None
                            else contextlib.nullcontext())
        self.row_ids, self.block_rows, self.cap = (
            layout or _slot_layout(fragments, self.total_words))
        self.row_index: Dict[int, int] = {
            r: i for i, r in enumerate(self.row_ids)}
        self.paged = self.cap > self.block_rows
        # snapshot context for lazy builds + advance
        self._fragments = list(fragments)
        self._built_vers = tuple(
            -1 if f is None else f.version for f in fragments)
        # entries are dense jax tensors OR ctiles.CompressedBlock
        self._blocks: List[Optional[object]] = (
            [None] * (self.cap // self.block_rows))
        self._lock = threading.Lock()
        # request-scoped stacks (built inside a write Qcx, never
        # published to the field cache) opt out of budget accounting —
        # they die with the request, and LRU entries would orphan
        self.ephemeral = False
        # block index -> (compressed block, its dense words): what a
        # whole walk decoded and the budget had room to keep
        self._walked: Dict[int, Tuple[ctiles.CompressedBlock, jax.Array]] = {}
        self._materialize()

    def _materialize(self) -> None:
        if not self.paged:
            # unpaged stacks are resident (pinned until LRU-evicted)
            # and charged like any block, so BUDGET is the complete
            # accounting of device-resident planes; an evicted block 0
            # lazily rebuilds with the usual version check.
            blk = self._build_block_host(0)
            self._blocks[0] = blk
            BUDGET.charge((self.serial, 0), device_bytes(blk),
                          lambda s=self: s._drop_block(0))

    # -- block machinery ----------------------------------------------------

    @property
    def n_blocks(self) -> int:
        return len(self._blocks)

    def _build_block_host(self, bi: int):
        """Assemble block ``bi`` from the host fragment planes and upload
        (compressed-tile form when the policy says so, dense otherwise).
        Caller must have validated the version snapshot (or hold the
        writer lock through the build, as __init__/advance do)."""
        from pilosa_tpu.obs.tracing import get_tracer

        lo_slot = bi * self.block_rows
        hi_slot = min(lo_slot + self.block_rows, len(self.row_ids))
        # the stack.build span covers host assembly AND the upload (the
        # device.h2d_copy span nests inside it): staging cost must be
        # attributable in traces, and its absence is what certifies a
        # warm resident query
        with get_tracer().start_span(
                "stack.build", block=bi,
                rows=hi_slot - lo_slot, words=self.total_words) as span:
            host = np.zeros((self.block_rows, self.total_words),
                            dtype=np.uint32)
            for si, frag in enumerate(self._fragments):
                if frag is None:
                    continue
                lo = si * self.words
                for slot in range(lo_slot, hi_slot):
                    fslot = frag.row_index.get(self.row_ids[slot])
                    if fslot is not None:
                        host[slot - lo_slot, lo:lo + self.words] = \
                            frag.planes[fslot]
            PAGING_STATS["block_builds"] += 1
            return _sent(host, "set", span)

    def _ensure_block(self, bi: int):
        blk = self._blocks[bi]
        if blk is not None:
            BUDGET.touch((self.serial, bi))
            return blk
        # The writer lock (not just the stack lock) spans the version
        # check AND the host copy: checking versions without excluding
        # writers would let a bulk import that mutates planes before its
        # single version bump produce a torn block.
        with writer_wait(self._write_lock), self._lock:
            blk = self._blocks[bi]
            if blk is not None:
                return blk
            _check_snapshot(self)
            blk = self._build_block_host(bi)
            self._blocks[bi] = blk
        if not self.ephemeral:
            BUDGET.charge((self.serial, bi), device_bytes(blk),
                          lambda s=self, i=bi: s._drop_block(i))
        return blk

    def release_device(self) -> None:
        """Drop this stack's budget entries (called when it leaves the
        field cache — replaced, LRU-popped, or cleared wholesale). Block
        arrays still referenced by in-flight reads stay alive via GC."""
        for bi in range(self.n_blocks):
            BUDGET.release((self.serial, bi))

    def _drop_block(self, bi: int) -> None:
        # eviction callback (paged blocks AND the unpaged block 0): the
        # next touch lazily rebuilds under the version check
        self._blocks[bi] = None
        self._walked.pop(bi, None)

    def _block_dense(self, bi: int) -> jax.Array:
        """Block ``bi`` as a dense device tensor (a compressed-resident
        block decodes device-side — no host transfer — and its dense
        words stay beside it where the budget has room:
        :func:`_decode_whole`)."""
        blk = self._ensure_block(bi)
        if not isinstance(blk, ctiles.CompressedBlock):
            return blk
        kept = self._walked.get(bi)
        if kept is not None and kept[0] is blk:
            return kept[1]
        dense, stays = _decode_whole(blk, "set")
        if stays and not self.ephemeral:
            with self._lock:
                stays = self._blocks[bi] is blk
                if stays:
                    self._walked[bi] = (blk, dense)
            if stays:
                BUDGET.charge((self.serial, bi), device_bytes(blk, dense),
                              lambda s=self, i=bi: s._drop_block(i))
        return dense

    def iter_blocks(self) -> Iterator[Tuple[int, jax.Array]]:
        """(start_slot, dense device block) over all blocks, built on
        demand; compressed-resident blocks decode device-side."""
        for bi in range(self.n_blocks):
            yield bi * self.block_rows, self._block_dense(bi)

    # -- single-tensor view (unpaged fast path) -------------------------------

    @property
    def planes(self) -> jax.Array:
        """The full ``[cap, S*W]`` tensor. Only unpaged stacks have one —
        paged consumers must stream ``iter_blocks()``/``row_counts()``."""
        if self.paged:
            raise AssertionError(
                "paged stack has no single tensor; use iter_blocks()")
        return self._block_dense(0)

    # -- reads ----------------------------------------------------------------

    def zero_plane(self) -> jax.Array:
        return bitops.device_zeros(self.total_words)

    def row_plane(self, row: int) -> jax.Array:
        """Device [S*W] plane for one row id (zeros when absent). Point
        reads touch exactly one block."""
        slot = self.row_index.get(row)
        if slot is None:
            return self.zero_plane()
        blk = self._ensure_block(slot // self.block_rows)
        if isinstance(blk, ctiles.CompressedBlock):
            return blk.decode(rows=[slot % self.block_rows])[0]
        return blk[slot % self.block_rows]

    def take_rows(self, rows: Sequence[int],
                  pad_to: Optional[int] = None) -> jax.Array:
        """Device ``[len(rows), S*W]`` gather of the given row ids (zero
        planes for absent rows), assembled block-locally. With ``pad_to``
        the gather has ``pad_to`` rows, those past ``len(rows)`` zero, and
        every block it reads gathers that many: its programs' shapes
        depend on ``pad_to`` alone, not on how the rows fall in blocks."""
        n = len(rows)
        by_block: Dict[int, Tuple[List[int], List[int]]] = {}
        missing: List[int] = []
        for i, r in enumerate(rows):
            slot = self.row_index.get(r)
            if slot is None:
                missing.append(i)
                continue
            dst, src = by_block.setdefault(slot // self.block_rows, ([], []))
            dst.append(i)
            src.append(slot % self.block_rows)
        if pad_to is not None:
            return self._take_padded(by_block, pad_to)
        if len(by_block) == 1 and not missing:
            bi, (dst, src) = next(iter(by_block.items()))
            blk = self._ensure_block(bi)
            order = np.argsort(dst)
            return _take(blk, np.asarray(src)[order])
        out = jnp.zeros((n, self.total_words), dtype=jnp.uint32)
        for bi, (dst, src) in by_block.items():
            sel = _take(self._ensure_block(bi), src)
            out = out.at[jnp.asarray(dst, dtype=jnp.int32)].set(sel)
        return out

    def _take_padded(self, by_block, n: int) -> jax.Array:
        """``take_rows(..., pad_to=n)``: a block's rows gathered ``n`` at a
        time (index 0 where the row is not the block's) and selected
        where they belong over zeros, so however many blocks the rows
        fall in, each costs the same three programs; the gather keeps
        the block's word sharding."""
        out = None
        for bi, (dst, src) in by_block.items():
            idx = np.zeros(n, dtype=np.int32)
            idx[dst] = src
            keep = np.zeros((n, 1), dtype=bool)
            keep[dst] = True
            sel = _take(self._ensure_block(bi), idx)
            out = jnp.where(keep, sel,
                            jnp.zeros_like(sel) if out is None else out)
        if out is None:
            return jnp.zeros((n, self.total_words), dtype=jnp.uint32)
        return out

    def rows_plane(self, rows: Sequence[int]) -> jax.Array:
        """OR of several rows' planes (UnionRows), streamed per block."""
        by_block: Dict[int, List[int]] = {}
        for r in rows:
            slot = self.row_index.get(r)
            if slot is not None:
                by_block.setdefault(slot // self.block_rows, []).append(
                    slot % self.block_rows)
        if not by_block:
            return self.zero_plane()
        acc = None
        for bi, slots in sorted(by_block.items()):
            sel = _take(self._ensure_block(bi), slots)
            part = jax.lax.reduce(
                sel, jnp.uint32(0), jax.lax.bitwise_or, dimensions=(0,))
            acc = part if acc is None else jnp.bitwise_or(acc, part)
            sync_part(acc)
        return acc

    def row_counts(self, filt: Optional[jax.Array] = None) -> jax.Array:
        """Device ``[cap]`` per-slot popcounts (optionally filtered),
        streamed per block (reference: fragment.go:1317 top counts)."""
        from pilosa_tpu.ops import topk as topkops

        parts = []
        for bi in range(self.n_blocks):
            blk = self._ensure_block(bi)
            if isinstance(blk, ctiles.CompressedBlock):
                # tile-skipping scan: zero/run tiles never reach the
                # kernel, bit-identical to the dense path
                parts.append(sync_part(blk.row_counts(filt)))
            else:
                parts.append(sync_part(topkops.row_counts(blk, filt)))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _check_snapshot(stack) -> None:
    """Raise :class:`StackStale` when a member fragment moved past the
    version ``stack`` was built at (a lazy rebuild must never serve a
    torn or newer state than the read's snapshot). Caller holds the
    writer lock."""
    for frag, built_v in zip(stack._fragments, stack._built_vers):
        if (frag.version if frag is not None else -1) != built_v:
            PAGING_STATS["stale_retries"] += 1
            raise StackStale("fragment advanced past the stack snapshot")


class KeyedSet(StackedSet):
    """A mutex stack too tall for the budget, held as key planes
    (``ops/keyrows.py``): ``uint32[k_pad, S*W]``, where K_i holds bit i of
    each record's slot + 1, one budget entry of ``k_pad`` planes in the
    place of ``cap`` (SSB SF-10's ``p_brand1``: 16 planes, 122 MB, where
    the dense stack is 7.8 GB). Rows are derived on the device, never
    built on the host or sent: a block for a walk by the Pallas kernel,
    kept and charged like a built block where the budget has room (else
    derived on every walk), the few rows of a point read on XLA. What a
    consumer sees (``iter_blocks``, ``row_plane``, ``take_rows``,
    ``rows_plane``, ``row_counts``, ``block_rows``, ``cap``) has exactly
    the dense form's shapes and bits."""

    #: budget key of the key planes beside the blocks' (serial, bi)
    _KEYS = -1
    #: derived blocks a walk lets run ahead of the device
    _AHEAD = 2

    def _materialize(self) -> None:
        self.paged = True
        self.bits = keyrows.key_bits(self.cap)
        self._keys: Optional[jax.Array] = self._build_keys_host()
        self._charge_keys()

    def _build_keys_host(self) -> jax.Array:
        """OR each fragment row's plane into K_i for every set bit i of
        its slot + 1, under the writer lock like a block build. Two rows
        of one record (the mutex invariant broken) raise: a key can hold
        one row, and folding two would answer wrong silently."""
        from pilosa_tpu.obs.tracing import get_tracer

        k_pad = keyrows.padded_planes(self.bits)
        with get_tracer().start_span(
                "stack.build", planes=k_pad, rows=len(self.row_ids),
                words=self.total_words) as span:
            host = np.zeros((k_pad, self.total_words), dtype=np.uint32)
            seen = np.empty(self.words, dtype=np.uint32)
            both = np.empty(self.words, dtype=np.uint32)
            for si, frag in enumerate(self._fragments):
                if frag is None:
                    continue
                lo = si * self.words
                keys = host[:, lo:lo + self.words]
                seen[:] = 0
                for fslot, row in enumerate(frag.row_ids):
                    plane = frag.planes[fslot]
                    if np.bitwise_and(seen, plane, out=both).any():
                        raise ValueError(
                            f"mutex stack: a record of shard "
                            f"{self.shards[si]} is in two rows (row {row} "
                            f"and another); key planes hold one")
                    np.bitwise_or(seen, plane, out=seen)
                    v = self.row_index[row] + 1
                    for i in range(self.bits):
                        if v >> i & 1:
                            np.bitwise_or(keys[i], plane, out=keys[i])
            PAGING_STATS["block_builds"] += 1
            return _sent(host, "set", span, compress=False)

    def _charge_keys(self) -> None:
        keys = self._keys
        if keys is not None and not self.ephemeral:
            BUDGET.charge((self.serial, self._KEYS), device_bytes(keys),
                          lambda s=self: s._drop_keys())

    def _drop_keys(self) -> None:
        # eviction callback: derived blocks stay valid (they are of this
        # snapshot); the next derivation rebuilds the planes
        self._keys = None

    def _ensure_keys(self) -> jax.Array:
        keys = self._keys
        if keys is not None:
            BUDGET.touch((self.serial, self._KEYS))
            return keys
        with writer_wait(self._write_lock), self._lock:
            keys = self._keys
            if keys is not None:
                return keys
            _check_snapshot(self)
            keys = self._keys = self._build_keys_host()
        self._charge_keys()
        return keys

    def release_device(self) -> None:
        super().release_device()
        BUDGET.release((self.serial, self._KEYS))

    @contextlib.contextmanager
    def _deriving(self, rows: int, **tags):
        """Yield the key planes to derive ``rows`` rows from, inside the
        ``stack.derive`` span and profiler leaf; counts the bytes."""
        from pilosa_tpu.obs.tracing import annotate, get_tracer

        keys = self._ensure_keys()
        nbytes = rows * _row_bytes(self.total_words)
        M.REGISTRY.count(M.METRIC_STACK_KEY_ROWS_BYTES, nbytes)
        with get_tracer().start_span(
                "stack.derive", rows=rows, key_planes=keys.shape[0],
                bytes=nbytes, **tags), annotate("stack.derive"):
            yield keys

    def _block_dense(self, bi: int) -> jax.Array:
        """Block ``bi``: kept, or derived from the key planes (and kept
        and charged like a built block where the budget has room for it
        without evicting anything)."""
        blk = self._blocks[bi]
        if blk is not None:
            BUDGET.touch((self.serial, bi))
            return blk
        with self._deriving(self.block_rows, block=bi) as keys:
            blk = keyrows.key_rows(keys, bi * self.block_rows,
                                   self.block_rows, self.bits)
        held = device_bytes(blk)
        if not self.ephemeral and BUDGET.room() >= max(held.values()):
            with self._lock:
                keep = self._blocks[bi] is None
                if keep:
                    self._blocks[bi] = blk
            if keep:
                BUDGET.charge((self.serial, bi), held,
                              lambda s=self, i=bi: s._drop_block(i))
        return blk

    _ensure_block = _block_dense

    def iter_blocks(self) -> Iterator[Tuple[int, jax.Array]]:
        """The dense form's walk, held to :data:`_AHEAD` blocks ahead of
        the device. A derived block the budget has no room for is charged
        to nothing, and dispatch is asynchronous: unheld, only timing
        bounds how many of a walk's blocks are on the device at once (SSB
        SF-10: up to 32 of 243 MB a walk of ``p_brand1``). So before it hands
        out a block, the walk waits until the one ``_AHEAD`` places back
        is derived; the device runs programs in order, so the consumers
        of the blocks before that one have run and their blocks are
        free."""
        ahead: deque = deque()
        for lo, blk in super().iter_blocks():
            ahead.append(blk)
            if len(ahead) > self._AHEAD:
                jax.block_until_ready(ahead.popleft())
            yield lo, blk

    def row_counts(self, filt: Optional[jax.Array] = None) -> jax.Array:
        from pilosa_tpu.ops import topk as topkops

        parts = [sync_part(topkops.row_counts(blk, filt))
                 for _, blk in self.iter_blocks()]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def _rows_at(self, slots: Sequence[int]) -> jax.Array:
        with self._deriving(len(slots)) as keys:
            return keyrows.key_rows_at(keys, slots)

    def row_plane(self, row: int) -> jax.Array:
        slot = self.row_index.get(row)
        if slot is None:
            return self.zero_plane()
        return self._rows_at([slot])[0]

    def take_rows(self, rows: Sequence[int],
                  pad_to: Optional[int] = None) -> jax.Array:
        """Only the rows asked for are derived (``_rows_at``); absent
        rows and the pad are zero planes (with ``pad_to``, selected
        whether or not there are any, so the programs are the same)."""
        n = len(rows) if pad_to is None else pad_to
        slots = [self.row_index.get(r) for r in rows]
        slots += [None] * (n - len(slots))
        keep = np.array([s is not None for s in slots], dtype=bool)[:, None]
        if not keep.any():
            return jnp.zeros((n, self.total_words), dtype=jnp.uint32)
        out = self._rows_at([0 if s is None else s for s in slots])
        if pad_to is None and keep.all():
            return out
        return jnp.where(keep, out, 0)

    def rows_plane(self, rows: Sequence[int]) -> jax.Array:
        slots = sorted({self.row_index[r] for r in rows
                        if r in self.row_index})
        if not slots:
            return self.zero_plane()
        return jax.lax.reduce(self._rows_at(slots), jnp.uint32(0),
                              jax.lax.bitwise_or, dimensions=(0,))


class StackedBSI:
    """BSI plane stacks across shards: device uint32[2+depth, S*W].

    Bit depth is bounded (<= 2 + 64 planes), so BSI stacks never page;
    shards with shallower depth than the widest member are zero-padded
    (a zero magnitude plane contributes nothing to compares or sums).
    Like StackedSet blocks, the plane tensor is budget-charged and
    evictable: an evicted tensor lazily rebuilds on next touch with the
    same version check (a write since the snapshot -> StackStale).
    """

    def __init__(self, shards: Sequence[int], fragments,
                 words: int = WORDS_PER_SHARD, write_lock=None):
        self.shards = tuple(shards)
        self.words = words
        self.total_words = len(self.shards) * words
        depth = max([f.depth for f in fragments if f is not None] or [1])
        self.depth = depth
        self.serial = next(_stack_serial)
        self._write_lock = (write_lock if write_lock is not None
                            else contextlib.nullcontext())
        self._lock = threading.Lock()
        self.ephemeral = False
        # (compressed stack, its dense words) once a whole walk decoded
        # it and the budget had room to keep them
        self._walked: Optional[Tuple[ctiles.CompressedBlock, jax.Array]] = None
        self._fragments = list(fragments)
        self._built_vers = tuple(
            -1 if f is None else f.version for f in fragments)
        self._planes: Optional[jax.Array] = self._build_host()
        self._charge()

    def _build_host(self):
        from pilosa_tpu.obs.tracing import get_tracer

        with get_tracer().start_span(
                "stack.build", kind="bsi", planes=bsiops.OFFSET + self.depth,
                words=self.total_words) as span:
            host = np.zeros((bsiops.OFFSET + self.depth, self.total_words),
                            dtype=np.uint32)
            for si, frag in enumerate(self._fragments):
                if frag is None:
                    continue
                lo = si * self.words
                host[: frag.planes.shape[0], lo:lo + self.words] = frag.planes
            return _sent(host, "bsi", span)

    def _charge(self) -> None:
        blk = self._planes
        if blk is not None and not self.ephemeral:
            kept = self._walked
            BUDGET.charge((self.serial, 0),
                          device_bytes(blk, *(() if kept is None
                                              else (kept[1],))),
                          lambda s=self: s._drop())

    def _drop(self) -> None:
        self._planes = None
        self._walked = None

    def release_device(self) -> None:
        BUDGET.release((self.serial, 0))

    def _entry(self):
        """The resident entry (dense tensor OR compressed block),
        rebuilding an evicted one under the writer lock with the version
        check (same protocol as StackedSet._ensure_block — a torn or
        stale rebuild must never serve a read)."""
        blk = self._planes
        if blk is not None:
            BUDGET.touch((self.serial, 0))
            return blk
        with writer_wait(self._write_lock), self._lock:
            blk = self._planes
            if blk is not None:
                return blk
            _check_snapshot(self)
            blk = self._build_host()
            self._planes = blk
        self._charge()
        return blk

    @property
    def planes(self) -> jax.Array:
        blk = self._entry()
        if not isinstance(blk, ctiles.CompressedBlock):
            return blk
        kept = self._walked
        if kept is not None and kept[0] is blk:
            return kept[1]
        dense, stays = _decode_whole(blk, "bsi")
        if stays and not self.ephemeral:
            with self._lock:
                stays = self._planes is blk
                if stays:
                    self._walked = (blk, dense)
            if stays:
                self._charge()
        return dense

    def compare(self, op: str, value: int,
                value2: Optional[int] = None) -> jax.Array:
        """Range compare over this stack. On a compressed-resident stack
        the scan narrows to active tiles (ops/ctiles.py) — sound because
        every ``bsi_compare`` output is EXISTS-masked, so all-zero tiles
        contribute exactly the zeros the scatter leaves behind."""
        blk = self._entry()
        if isinstance(blk, ctiles.CompressedBlock):
            return ctiles.bsi_compare_compressed(blk, op, value, value2)
        return bsiops.bsi_compare(blk, op, value, value2)

    def exists_plane(self) -> jax.Array:
        return self.planes[bsiops.EXISTS]


def _versions(fragments) -> Tuple:
    from pilosa_tpu.parallel.mesh import mesh_epoch

    # The mesh epoch is part of the version key: a mesh switch must
    # invalidate stacks placed on the old device set (mixed placements in
    # one kernel error out rather than resharding).
    return (mesh_epoch(),) + tuple(
        -1 if f is None else f.version for f in fragments)


# Cache layout: field._stacked_cache maps a *group* (kind, view) to an
# inner OrderedDict of shard-subset -> (versions, stacked). Groups are
# unbounded — each view's planes are distinct data, exactly as resident as
# the per-fragment device caches they replace (a 30-view time-range query
# keeps all 30 views warm). Within a group, each subset entry is a FULL
# duplicate device copy of the member planes (e.g. Options(shards=[...])
# stacks arbitrary subsets), so subsets are LRU-bounded to keep duplicates
# from pinning HBM for the process lifetime.
_MAX_SUBSETS_PER_GROUP = 4

# The Executor is shared across server request threads (ThreadingHTTPServer)
# and the cluster fan-out pool; OrderedDict move_to_end/popitem is not
# atomic, so all cache bookkeeping runs under one lock. Builds (host concat
# + device upload) happen outside it — a racing duplicate build is benign.
_LOCK = threading.Lock()


def _cache_get(field, group, subset, vers):
    with _LOCK:
        cache = getattr(field, "_stacked_cache", None)
        if cache is None:
            cache = field._stacked_cache = {}
        inner = cache.get(group)
        if inner is None:
            return None
        hit = inner.get(subset)
        if hit is not None and hit[0] == vers:
            inner.move_to_end(subset)
            M.REGISTRY.count(M.METRIC_DEVICE_RESIDENT_HITS)
            return hit[1]
        return None


def _cache_peek(field, group, subset):
    """Latest (vers, stack) for a subset regardless of staleness — the
    merge base for the incremental advance path."""
    with _LOCK:
        cache = getattr(field, "_stacked_cache", None)
        if cache is None:
            return None
        inner = cache.get(group)
        if inner is None:
            return None
        return inner.get(subset)


def _cache_put(field, group, subset, vers, built):
    from pilosa_tpu.storage.txn import in_write_qcx

    # Builds performed inside a write Qcx are NOT published: a concurrent
    # reader's optimistic _cache_get could otherwise observe the write
    # request's intermediate states (Set(a)Set(b)Count() caching a stack
    # after only Set(a)). The writer's own later calls rebuild — bounded
    # to the one request; the post-commit query re-caches normally.
    if in_write_qcx():
        # the stack is request-scoped: drop any budget entries its build
        # or advance already charged and stop future lazy-block charges
        # (otherwise the orphaned LRU entries pin device arrays and
        # evict genuinely cached blocks)
        release = getattr(built, "release_device", None)
        if release is not None:
            built.ephemeral = True
            release()
        return
    dropped = []
    with _LOCK:
        cache = getattr(field, "_stacked_cache", None)
        if cache is None:
            cache = field._stacked_cache = {}
        inner = cache.setdefault(group, OrderedDict())
        old = inner.get(subset)
        if old is not None and old[1] is not built:
            dropped.append(old[1])
        inner[subset] = (vers, built)
        inner.move_to_end(subset)
        while len(inner) > _MAX_SUBSETS_PER_GROUP:
            dropped.append(inner.popitem(last=False)[1][1])
    # budget entries of stacks leaving the cache are released (outside
    # the cache lock; BUDGET has its own)
    for stack in dropped:
        release = getattr(stack, "release_device", None)
        if release is not None:
            release()


def release_field_cache(field) -> None:
    """Clear a field's stacked cache AND the budget entries of every
    resident stack (holder restore / mesh switch / delete paths)."""
    with _LOCK:
        cache = getattr(field, "_stacked_cache", None)
        field._stacked_cache = {}
    if not cache:
        return
    for inner in cache.values():
        for _, stack in inner.values():
            release = getattr(stack, "release_device", None)
            if release is not None:
                release()


# ---------------------------------------------------------------------------
# Incremental write-merge (VERDICT r1 #5; SURVEY §7 "Mutability on device").
# A write between two queries used to invalidate the whole stacked tensor
# and re-upload it. Instead, representable writes (fragment.py _DeltaLog)
# advance the cached device tensor in place:
#   - bit flips on existing rows collapse host-side into final per-(slot,
#     fused-word) OR/ANDNOT masks (ordered, so set-then-clear resolves
#     correctly) and ONE jitted scatter per touched block applies them;
#   - writes to NEW rows append slots in place (streaming ingest of new
#     rows — VERDICT r3 #5): unpaged stacks grow device-side by padding
#     (no host re-upload), paged stacks just extend the lazy block list.
# Transfer cost: a few hundred bytes of indices+masks, not the stack.
# ---------------------------------------------------------------------------


# NOTE: planes is NOT donated — lock-free readers may still hold the old
# stack; donating its buffer would invalidate their in-flight reads.
# Updates use mode="drop": inputs are padded to power-of-2 lengths with
# out-of-bounds word indices (one XLA executable per pow2 bucket instead
# of one per distinct delta count), and dropped pads can't race a real
# entry the way a duplicated in-bounds pad index would.
@platform.guarded_call
@jax.jit
def _apply_bit_deltas(planes, slots, words, orm, anm):
    cur = planes[slots, words]  # pads clamp-read; their writes are dropped
    return planes.at[slots, words].set((cur & ~anm) | orm, mode="drop")


import functools


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("new_rows",))
def _grow_rows_device(planes, new_rows: int):
    """Zero-pad a block/stack with ``new_rows`` extra slots on device —
    an HBM-side copy, no host transfer."""
    return jnp.pad(planes, ((0, new_rows), (0, 0)))


class _MaskAccum:
    """Ordered bit-op collapse into per-(slot, fused word) masks."""

    def __init__(self):
        self.masks: Dict[Tuple[int, int], List[int]] = {}

    def set(self, slot: int, word: int, bit: int) -> None:
        e = self.masks.setdefault((slot, word), [0, 0])
        m = 1 << bit
        e[0] |= m
        e[1] &= ~m

    def clear(self, slot: int, word: int, bit: int) -> None:
        e = self.masks.setdefault((slot, word), [0, 0])
        m = 1 << bit
        e[1] |= m
        e[0] &= ~m

    def apply(self, planes: jax.Array, lo_slot: int = 0,
              hi_slot: Optional[int] = None) -> jax.Array:
        """Scatter the accumulated masks whose slot falls in
        [lo_slot, hi_slot) onto ``planes`` (slot-rebased by lo_slot)."""
        if hi_slot is None:
            hi_slot = lo_slot + planes.shape[0]
        keys = [k for k in self.masks if lo_slot <= k[0] < hi_slot]
        if not keys:
            return planes
        cap = _pow2(len(keys))
        slots = np.zeros(cap, dtype=np.int32)
        # pads point past the word axis: dropped by the scatter
        words = np.full(cap, planes.shape[-1], dtype=np.int32)
        orm = np.zeros(cap, dtype=np.uint32)
        anm = np.zeros(cap, dtype=np.uint32)
        for i, k in enumerate(keys):
            slots[i] = k[0] - lo_slot
            words[i] = k[1]
            orm[i], anm[i] = self.masks[k]
        return _apply_bit_deltas(planes, slots, words, orm, anm)


def _advance_set(stack: "StackedSet", fragments, built_vers) -> Optional["StackedSet"]:
    """Replay pending writes onto a cached StackedSet; None -> rebuild.
    Caller holds the writer lock (fragment versions are quiescent)."""
    from pilosa_tpu.shardwidth import BITS_PER_WORD

    if isinstance(stack, KeyedSet):
        return _advance_keyed(stack, fragments, built_vers)
    acc = _MaskAccum()
    new_rows: List[int] = []
    new_index: Optional[Dict[int, int]] = None

    def slot_of(row: int) -> int:
        nonlocal new_index
        s = stack.row_index.get(row)
        if s is None and new_index is not None:
            s = new_index.get(row)
        if s is None:
            # appended row: assign the next slot in place (VERDICT r3 #5)
            if new_index is None:
                new_index = {}
            s = len(stack.row_ids) + len(new_rows)
            new_rows.append(row)
            new_index[row] = s
        return s

    for si, (frag, built_v) in enumerate(zip(fragments, built_vers)):
        if frag is None:
            if built_v != -1:
                return None  # fragment vanished
            continue
        if built_v == frag.version:
            continue
        if built_v < 0:
            return None  # fragment appeared after the build
        ops = frag.deltas.since(built_v, frag.version)
        if ops is None:
            return None
        lo = si * stack.words
        for row, set_cols, clear_cols in ops:
            slot = slot_of(row)
            for col in set_cols:
                w, b = divmod(col, BITS_PER_WORD)
                acc.set(slot, lo + w, b)
            for col in clear_cols:
                w, b = divmod(col, BITS_PER_WORD)
                acc.clear(slot, lo + w, b)
    if not acc.masks and not new_rows:
        # versions moved with no net representable delta: re-stamp the
        # snapshot (caller holds the writer lock) so a later lazy
        # rebuild of an evicted block doesn't raise a spurious stale
        stack._fragments = list(fragments)
        stack._built_vers = tuple(
            -1 if f is None else f.version for f in fragments)
        return stack
    new = StackedSet.__new__(StackedSet)
    new.shards = stack.shards
    new.words = stack.words
    new.total_words = stack.total_words
    new.serial = next(_stack_serial)
    new.block_rows = stack.block_rows
    new._lock = threading.Lock()
    new._write_lock = stack._write_lock
    new.ephemeral = False
    new._walked = {}
    new._fragments = list(fragments)
    new._built_vers = tuple(
        -1 if f is None else f.version for f in fragments)
    if new_rows:
        new.row_ids = stack.row_ids + new_rows
        new.row_index = dict(stack.row_index)
        new.row_index.update(new_index)
    else:
        new.row_ids = stack.row_ids
        new.row_index = stack.row_index
    if not stack.paged:
        # grow the single block in place (device-side zero pad, pow2
        # capacities so XLA sees few shapes); outgrowing one block means
        # the stack must be rebuilt in paged form
        row_bytes = _row_bytes(stack.total_words)
        need = _pow2(len(new.row_ids))
        if need * row_bytes > _BLOCK_BYTES:
            return None
        new.block_rows = max(stack.block_rows, need)
        new.cap = new.block_rows
        new.paged = False
        blk = stack._blocks[0]
        if blk is None:
            return None  # resident block was evicted: rebuild from host
        # write-hot compressed blocks decay to dense (device-side decode,
        # no host transfer); the next full rebuild recompresses
        blk = _dense(blk)
        if new.cap > stack.cap:
            blk = _grow_rows_device(blk, new.cap - stack.cap)
        blk = acc.apply(blk, 0, new.cap)
        # assign before charging: an eviction cascade can immediately
        # call the new entry's neighbors' callbacks, and new's own
        # callback reads _blocks
        new._blocks = [blk]
        BUDGET.charge((new.serial, 0), device_bytes(blk),
                      lambda s=new: s._drop_block(0))
        return new
    # paged: block_rows is fixed; appends extend the lazy block list.
    # Scatter the masks into each *materialized* block; unmaterialized
    # blocks need no replay (their lazy build reads the new host state,
    # which is consistent with new._built_vers).
    need_cap = max(stack.cap,
                   -(-len(new.row_ids) // stack.block_rows)
                   * stack.block_rows)
    new.cap = need_cap
    new.paged = True
    blocks = list(stack._blocks)
    blocks.extend([None] * (new.cap // new.block_rows - len(blocks)))
    for bi, blk in enumerate(blocks):
        if blk is None:
            continue
        lo_slot = bi * new.block_rows
        hi_slot = lo_slot + new.block_rows
        if isinstance(blk, ctiles.CompressedBlock):
            if not any(lo_slot <= k[0] < hi_slot for k in acc.masks):
                continue  # untouched by the deltas: stays compressed
            # touched: decay to dense device-side; recompressed on the
            # next full rebuild
            blk = _dense(blk)
        blocks[bi] = acc.apply(blk, lo_slot, hi_slot)
    # _blocks must exist before any charge: an eviction cascade can pop
    # one of new's OWN earlier entries, whose callback reads _blocks
    new._blocks = blocks
    for bi, blk in enumerate(blocks):
        if blk is not None:
            BUDGET.charge((new.serial, bi), device_bytes(blk),
                          lambda s=new, i=bi: s._drop_block(i))
    return new


def _record_key(frag, col: int, slot_of) -> int:
    """The key (slot + 1, 0: none) the host fragment holds for column
    ``col`` now; a record in two rows raises, as at a build."""
    from pilosa_tpu.shardwidth import BITS_PER_WORD

    w, b = divmod(col, BITS_PER_WORD)
    n = len(frag.row_ids)
    hit = np.flatnonzero((frag.planes[:n, w] >> np.uint32(b)) & 1)
    if hit.size > 1:
        raise ValueError(f"mutex stack: column {col} is in rows "
                         f"{[frag.row_ids[i] for i in hit]}")
    return slot_of(frag.row_ids[hit[0]]) + 1 if hit.size else 0


def _advance_keyed(stack: "KeyedSet", fragments,
                   built_vers) -> Optional["KeyedSet"]:
    """Replay pending writes onto a key-plane stack: each column a write
    touched gets the key its fragment holds now, as OR/ANDNOT masks on
    the key planes (``_MaskAccum`` deltas apply to planes of any
    meaning); kept derived blocks are dropped. A new row is a new slot;
    one past what the planes' bits can name, an evicted key tensor or a
    fragment that came or went rebuilds. Caller holds the writer lock."""
    from pilosa_tpu.shardwidth import BITS_PER_WORD

    base = stack._keys
    if base is None:
        return None
    row_ids = list(stack.row_ids)
    row_index = dict(stack.row_index)

    def slot_of(row: int) -> int:
        s = row_index.get(row)
        if s is None:
            s = row_index[row] = len(row_ids)
            row_ids.append(row)
        return s

    touched: List[Tuple[int, int]] = []
    for si, (frag, built_v) in enumerate(zip(fragments, built_vers)):
        if frag is None:
            if built_v != -1:
                return None
            continue
        if built_v == frag.version:
            continue
        if built_v < 0:
            return None
        ops = frag.deltas.since(built_v, frag.version)
        if ops is None:
            return None
        cols = set()
        for row, set_cols, clear_cols in ops:
            slot_of(row)
            cols.update(set_cols)
            cols.update(clear_cols)
        touched.extend((si, c) for c in sorted(cols))
    cap = max(stack.cap, -(-len(row_ids) // stack.block_rows)
              * stack.block_rows)
    if keyrows.key_bits(cap) > stack.bits:
        return None  # a new row needs one more key bit: rebuild
    acc = _MaskAccum()
    for si, col in touched:
        key = _record_key(fragments[si], col, slot_of)
        w, b = divmod(col, BITS_PER_WORD)
        for i in range(stack.bits):
            if key >> i & 1:
                acc.set(i, si * stack.words + w, b)
            else:
                acc.clear(i, si * stack.words + w, b)
    new = KeyedSet.__new__(KeyedSet)
    new.shards, new.words = stack.shards, stack.words
    new.total_words = stack.total_words
    new.serial = next(_stack_serial)
    new.block_rows, new.cap, new.paged = stack.block_rows, cap, True
    new.bits = stack.bits
    new.row_ids, new.row_index = row_ids, row_index
    new._lock = threading.Lock()
    new._write_lock = stack._write_lock
    new.ephemeral = False
    new._walked = {}
    new._fragments = list(fragments)
    new._built_vers = tuple(
        -1 if f is None else f.version for f in fragments)
    new._blocks = [None] * (cap // stack.block_rows)
    new._keys = acc.apply(base)
    new._charge_keys()
    return new


def _advance_bsi(stack: "StackedBSI", fragments, built_vers) -> Optional["StackedBSI"]:
    from pilosa_tpu.ops.bsi import EXISTS, OFFSET, SIGN
    from pilosa_tpu.shardwidth import BITS_PER_WORD

    # read the raw tensor: the planes property would try to REBUILD an
    # evicted tensor at the old snapshot and correctly raise StackStale
    # (fragments have advanced — that's why we're here); an evicted base
    # simply means a full rebuild from the current host state
    base = stack._planes
    if base is None:
        return None
    # a compressed-resident tensor decays to dense under writes (decode
    # is device-side); the next full rebuild recompresses
    base = _dense(base)
    n_planes = base.shape[0]
    acc = _MaskAccum()
    for si, (frag, built_v) in enumerate(zip(fragments, built_vers)):
        if frag is None:
            if built_v != -1:
                return None
            continue
        if built_v == frag.version:
            continue
        if built_v < 0:
            return None
        if frag.planes.shape[0] > n_planes:
            return None  # deeper than the stack: rebuild widens it
        ops = frag.deltas.since(built_v, frag.version)
        if ops is None:
            return None
        lo = si * stack.words
        for op in ops:
            if op[0] == "set":
                _, cols, values = op
                for col, val in zip(cols, values):
                    w, b = divmod(col, BITS_PER_WORD)
                    for p in range(n_planes):  # old value fully cleared
                        acc.clear(p, lo + w, b)
                    acc.set(EXISTS, lo + w, b)
                    if val < 0:
                        acc.set(SIGN, lo + w, b)
                    mag = -val if val < 0 else val
                    k = 0
                    while mag:
                        if mag & 1:
                            acc.set(OFFSET + k, lo + w, b)
                        mag >>= 1
                        k += 1
            else:  # ("clear", col)
                _, col = op
                w, b = divmod(col, BITS_PER_WORD)
                for p in range(n_planes):
                    acc.clear(p, lo + w, b)
    if not acc.masks:
        stack._fragments = list(fragments)
        stack._built_vers = tuple(
            -1 if f is None else f.version for f in fragments)
        return stack
    new = StackedBSI.__new__(StackedBSI)
    new.shards = stack.shards
    new.words = stack.words
    new.total_words = stack.total_words
    new.depth = stack.depth
    new.serial = next(_stack_serial)
    new._write_lock = stack._write_lock
    new._lock = threading.Lock()
    new.ephemeral = False
    new._walked = None
    new._fragments = list(fragments)
    new._built_vers = tuple(
        -1 if f is None else f.version for f in fragments)
    new._planes = acc.apply(base)
    new._charge()
    return new


def _writer_lock(field):
    """The holder-wide writer lock threaded down to the field (RLock, so
    writers building a stack mid-request re-enter fine). Standalone fields
    constructed outside an Index (unit tests) have none."""
    lock = getattr(field, "write_lock", None)
    return lock if lock is not None else contextlib.nullcontext()


@contextlib.contextmanager
def writer_wait(lock):
    """``with lock:`` for a read that must exclude writers (a stack or
    block build, the last StackStale retry), counting how long it stood
    behind a writer or a checkpoint. Never on a warm read: cache hits
    take no lock. A counter and no profiler leaf: a wait lasts as long as
    the work it waits for, and the longest event covering an idle gap
    owns it, so a waiting reader would take the checkpoint's seconds
    from ``checkpoint.serialize`` on the writer's thread."""
    t0 = time.perf_counter()
    with lock:
        M.REGISTRY.count(M.METRIC_STACK_WRITER_WAIT_SECONDS,
                         time.perf_counter() - t0)
        M.REGISTRY.count(M.METRIC_STACK_WRITER_WAIT_COUNT)
        yield


def stacked_set(field, shards: Sequence[int], view: str) -> StackedSet:
    """Build-or-reuse the stacked view of ``field``'s ``view`` fragments.

    The fragment fetch + version snapshot + host build run under the
    writer lock: reads themselves are lock-free on cache hits, but a
    *build* walks live host planes and must not observe a half-applied
    write (torn plane) or a mid-resize row index.
    """
    group, subset = ("set", view), tuple(shards)
    # Optimistic lock-free hit: a cached stack is an immutable device
    # array — serving it is always safe, and the dict/version reads here
    # are individually atomic. Only a MISS (which walks live host planes)
    # must serialize against writers.
    fragments = [field.fragment(s, view) for s in shards]
    hit = _cache_get(field, group, subset, _versions(fragments))
    if hit is not None:
        return hit
    with writer_wait(_writer_lock(field)):
        fragments = [field.fragment(s, view) for s in shards]
        vers = _versions(fragments)
        hit = _cache_get(field, group, subset, vers)
        if hit is None:
            hit = _advance_or_rebuild(
                field, group, subset, vers, fragments,
                advance=_advance_set,
                rebuild=lambda: _new_set_stack(field, shards, fragments))
    return hit


def _new_set_stack(field, shards, fragments) -> StackedSet:
    """A fresh stack of ``field``'s fragments, in the form
    :func:`keyed_form` gives it."""
    from pilosa_tpu.core.schema import FieldType

    layout = _slot_layout(fragments, len(shards) * WORDS_PER_SHARD)
    mutex = field.options.type in (FieldType.MUTEX, FieldType.BOOL)
    cls = (KeyedSet if keyed_form(mutex, layout[2],
                                  len(shards) * WORDS_PER_SHARD)
           else StackedSet)
    return cls(shards, fragments, write_lock=_writer_lock(field),
               layout=layout)


def stacked_bsi(field, shards: Sequence[int]) -> StackedBSI:
    group, subset = ("bsi",), tuple(shards)
    fragments = [field.bsi_fragment(s) for s in shards]
    hit = _cache_get(field, group, subset, _versions(fragments))
    if hit is not None:
        return hit
    with writer_wait(_writer_lock(field)):
        fragments = [field.bsi_fragment(s) for s in shards]
        vers = _versions(fragments)
        hit = _cache_get(field, group, subset, vers)
        if hit is None:
            hit = _advance_or_rebuild(
                field, group, subset, vers, fragments,
                advance=_advance_bsi,
                rebuild=lambda: StackedBSI(
                    shards, fragments, write_lock=_writer_lock(field)))
    return hit


def _advance_or_rebuild(field, group, subset, vers, fragments,
                        advance, rebuild):
    """On a version miss: try replaying the pending write deltas onto the
    latest cached stack (one small device scatter); fall back to a full
    host build + upload. Caller holds the writer lock."""
    stale = _cache_peek(field, group, subset)
    built = None
    if stale is not None and stale[0][0] == vers[0]:  # same mesh epoch
        built = advance(stale[1], fragments, stale[0][1:])
    if built is None:
        built = rebuild()
    _cache_put(field, group, subset, vers, built)
    return built
