"""PQL executor: lowers the call tree to batched L0 kernels over stacked
shard tensors, with ONE host round-trip per query.

Reference: executor.go — one ``execute*`` / ``execute*Shard`` pair per call
(dispatch executor.go:679-841), shard fan-out via mapReduce
(executor.go:6449). The reference maps per shard and reduces on the
coordinator; here the per-node "map" is ONE XLA dispatch over all local
shards at once: fragments are stacked along the column/word axis
(core/stacked.py — every kernel reduces over columns, so concatenated
shards ARE the monoid reduce), and results come back in a single deferred
device->host fetch per query (each blocking fetch is a full host-device
round trip).

Key translation happens host-side around kernels (reference:
executor.go:6814 preTranslate, :7519 translateResults) — strings never
reach the device. Cross-node distribution lives in cluster/executor.py and
reuses the same monoid reduce shapes.
"""

from __future__ import annotations

import copy
import datetime as dt
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu import platform
from pilosa_tpu.cache.keys import query_cache_key
from pilosa_tpu.core import timeq
from pilosa_tpu.core.field import Field
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.index import EXISTENCE_ROW, Index
from pilosa_tpu.core.schema import FieldType
from pilosa_tpu.core.stacked import (StackedBSI, StackedSet,
                                     planes_per_block, stacked_bsi,
                                     stacked_set, sync_part, writer_wait)
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.obs.tracing import get_tracer
from pilosa_tpu.ops import bitmap as B
from pilosa_tpu.ops import bsi as S
from pilosa_tpu.ops import topk as T
from pilosa_tpu.ops.groupby import (PAIR_COUNTS_MAX_ROWS, group_planes,
                                    pair_counts, pair_counts_route,
                                    pair_sums)
from pilosa_tpu.pql.ast import Call, Condition, Query, ROW_OPTIONS, unwrap_options
from pilosa_tpu.pql.parser import parse
from pilosa_tpu.pql import programs
from pilosa_tpu.pql import result as R
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD


class PQLError(ValueError):
    pass


_COND_TO_BSI = {"==": S.EQ, "!=": S.NE, "<": S.LT, "<=": S.LE,
                ">": S.GT, ">=": S.GE, "between": S.BETWEEN}

_BITMAP_CALLS = {"Row", "Union", "Intersect", "Difference", "Xor", "Not",
                 "All", "ConstRow", "UnionRows", "Shift", "Distinct", "Limit"}

_WRITE_CALLS = {"Set", "Clear", "ClearRow", "Store", "Delete"}

# Calls whose results stay exact under a per-query shard mask over a
# union stacked layout (superset fusion). Every shard's segment of a
# bitmap expression depends only on that shard's fragments (all plane
# algebra is column-local; Shift carries stop at shard boundaries), so
# masking the columns a reduction sees is equivalent to evaluating over
# the subset's own stack. Host-scan calls (Extract/Apply/Arrow/Sort/...)
# walk fragments directly and are excluded — they run with their own
# shard list instead.
_MASKABLE_CALLS = (_BITMAP_CALLS
                   | {"Count", "Sum", "Min", "Max", "Percentile",
                      "TopN", "TopK", "Rows", "GroupBy"})


def query_maskable(query) -> bool:
    """True when every top-level call of ``query`` can execute under a
    per-query shard mask (see _MASKABLE_CALLS). ``Options`` wrappers are
    transparent UNLESS they carry a ``shards=`` override: that re-scopes
    the call away from the union layout the mask indexes, so such
    queries keep their own shard list (the result cache excludes them
    for the same reason, cache/keys.py is_cacheable)."""
    calls = query.calls if isinstance(query, Query) else [query]
    for call in calls:
        while call.name == "Options" and call.children:
            if call.arg("shards") is not None:
                return False
            call = call.children[0]
        if call.name not in _MASKABLE_CALLS:
            return False
    return True


# Device-resident ShardMask planes, LRU-bounded and keyed by
# (mesh epoch, union layout, subset): masks depend only on shard lists,
# never data, so warm fused dispatches (sched/batch.py) find their mask
# already on device instead of re-building + re-staging a host plane per
# ShardMask construction.
_MASK_CAP = 32
_MASK_PLANES: "OrderedDict[Tuple, jnp.ndarray]" = OrderedDict()
_MASK_LOCK = threading.Lock()


def _mask_plane(shard_list: Tuple[int, ...], subset) -> jnp.ndarray:
    from pilosa_tpu.obs import metrics as M
    from pilosa_tpu.parallel import mesh

    key = (mesh.mesh_epoch(), shard_list, subset)
    with _MASK_LOCK:
        hit = _MASK_PLANES.get(key)
        if hit is not None:
            _MASK_PLANES.move_to_end(key)
    if hit is not None:
        M.REGISTRY.count(M.METRIC_DEVICE_RESIDENT_HITS)
        return hit
    plane = mesh.engine_put(B.shard_mask_plane(shard_list, subset))
    with _MASK_LOCK:
        plane = _MASK_PLANES.setdefault(key, plane)
        _MASK_PLANES.move_to_end(key)
        while len(_MASK_PLANES) > _MASK_CAP:
            _MASK_PLANES.popitem(last=False)
    return plane


class ShardMask:
    """Per-query shard-subset mask over a union stacked layout (superset
    fusion, sched/batch.py): a ``uint32[S*W]`` word plane with all-ones
    words on the query's own shards and zeros elsewhere
    (ops/bitmap.py shard_mask_plane).

    Applied at materialization/aggregation points only — bitmap algebra
    (AND/OR/XOR/ANDNOT) distributes over a per-column mask, so masking
    the final plane equals masking every leaf, and the intermediate
    evaluation stays shared across the whole fused batch."""

    __slots__ = ("shard_list", "subset", "plane")

    def __init__(self, shard_list: Sequence[int], subset):
        self.shard_list = [int(s) for s in shard_list]
        self.subset = frozenset(int(s) for s in subset)
        self.plane = _mask_plane(tuple(self.shard_list), self.subset)


def has_write_calls(query) -> bool:
    """True if any call in the (parsed) query mutates data. Lets the API
    layer skip the Qcx/write-lock for pure reads (the reference's Qcx
    likewise distinguishes read from write Tx, txfactory.go:84)."""

    def walk(call) -> bool:
        if call.name in _WRITE_CALLS:
            return True
        if call.name == "ExternalLookup" and call.arg("write"):
            return True  # write-mode lookups keep single-writer ordering
        return any(walk(c) for c in call.children)

    calls = query.calls if isinstance(query, Query) else [query]
    return any(walk(c) for c in calls)


def _parse_ts(v) -> dt.datetime:
    if isinstance(v, dt.datetime):
        return v
    return dt.datetime.fromisoformat(str(v).replace("Z", "+00:00"))


class _Deferred:
    """A query result whose device arrays haven't been fetched yet.

    ``execute`` starts async copies for every deferred result of the query
    before blocking on any of them, so N top-level calls cost one
    round-trip, not N (the analog of the reference answering all calls of
    a request in one HTTP response)."""

    __slots__ = ("arrays", "finalize")

    def __init__(self, arrays: Sequence[jax.Array], finalize: Callable):
        self.arrays = list(arrays)
        self.finalize = finalize

    def resolve(self):
        return self.finalize(*[np.asarray(a) for a in self.arrays])


def _resolve(value):
    return value.resolve() if isinstance(value, _Deferred) else value


def _concat(parts, axis=0):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


#: GroupBy results up to this many cells (the product of the fields' row
#: capacities, times the magnitude planes of a Sum) are counted whole on
#: the device and fetched once; above it the pruning fold takes over
_DENSE_MAX_CELLS = 1 << 24

#: the ``fields`` label of ``groupby_route_total``: a bounded set
_FIELDS_LABEL = ("1", "2", "3", "4+")


def _group_block(total_words: int) -> int:
    """How many group planes of ``total_words`` words a GroupBy holds at
    a time: one row block's worth (``core/stacked.py``), at least a
    sublane tile and at most what the Pallas pair-count takes as its
    outer side."""
    return max(8, min(PAIR_COUNTS_MAX_ROWS, planes_per_block(total_words)))


def _tag_route(span, planes, blk) -> None:
    """Which pair-count program a level's planes take, and which body of
    the kernel there, on its ``groupby.level`` span."""
    route, body = pair_counts_route(planes, blk)
    span.set_tag("route", route)
    span.set_tag("body", body)


def _made(planes):
    """Count group planes just materialised on the device."""
    M.REGISTRY.count(M.METRIC_GROUPBY_GROUP_PLANE_BYTES, planes.nbytes)
    return sync_part(planes)


def _start_copies(raw) -> None:
    for r in raw:
        if isinstance(r, _Deferred):
            for a in r.arrays:
                try:
                    a.copy_to_host_async()
                except AttributeError:  # non-array leaf
                    pass


class Executor:
    """Reference: executor.go:55 (executor struct).

    ``remote=True`` puts the executor in peer-serving mode (the analog of
    the reference's Remote:true query flag, executor.go:6392 remoteExec):
    results keep raw IDs (no key translation — that happens once at the
    coordinator, executor.go:7519) and rankings/limits are NOT truncated,
    so the coordinator's monoid merge stays exact.
    """

    def __init__(self, holder: Holder, remote: bool = False):
        self.holder = holder
        self.remote = remote
        # result cache (cache/), attached by api.enable_cache(). None
        # keeps the read path byte-identical to the uncached build.
        self.cache = None
        # tenant-scoped cache namespaces (api.enable_tenants): each
        # tenant's results key under its own namespace so one tenant
        # can't evict — or observe timing of — another's working set
        self.tenant_namespaces = False

    # -- public entry (reference: executor.go:183 Execute) --------------------

    def execute(self, index: str, query, shards: Optional[Sequence[int]] = None
                ) -> List[Any]:
        idx = self.holder.index(index)
        if isinstance(query, str):
            query = parse(query)
        if isinstance(query, Call):
            query = Query([query])
        if has_write_calls(query):
            with self.holder.write_lock:
                return self._execute_query(idx, query, shards)
        cache = self.cache
        if cache is not None:
            key = self.cache_key(idx, query, shards)
            if key is None:
                cache.bypass()
            else:
                return cache.run(
                    key, lambda: self._execute_read(idx, query, shards),
                    allow_stale=not self.remote)
        return self._execute_read(idx, query, shards)

    def cache_key(self, index, query,
                  shards: Optional[Sequence[int]] = None) -> Optional[Tuple]:
        """Result-cache key for a read query against this executor (None
        when uncacheable: writes, ExternalLookup, per-call shard
        overrides). Accepts an Index or a name, str/Call queries like
        ``execute``. The namespace pins the result dialect: a
        remote=True executor returns untranslated, untruncated partials
        for the same PQL text (see class docstring)."""
        idx = index if isinstance(index, Index) else self.holder.index(index)
        if isinstance(query, str):
            query = parse(query)
        if isinstance(query, Call):
            query = Query([query])
        if has_write_calls(query):
            return None
        return query_cache_key(
            idx, query, self._shards(idx, shards),
            namespace=self._namespace())

    def _namespace(self) -> str:
        """Cache-key namespace: the result dialect (local/remote), plus
        the current tenant when tenant-scoped namespaces are on."""
        ns = "remote" if self.remote else "local"
        if self.tenant_namespaces:
            from pilosa_tpu.obs.tenants import current_tenant_id

            t = current_tenant_id()
            if t is not None:
                return f"{ns}|{t}"
        return ns

    def _execute_read(self, idx: Index, query: Query, shards) -> List[Any]:
        from pilosa_tpu.core.stacked import StackStale

        # Paged stacks build blocks lazily; a concurrent write landing
        # mid-stream makes the remaining lazy builds StackStale. PQL
        # reads are pure, so retry on a fresh (post-write) stack; the
        # last attempt runs under the writer lock so it cannot be
        # invalidated again. Write queries never retry: their kernels
        # consume blocks eagerly within each call, and re-running a Set
        # would corrupt the changed-flags — they execute once (their
        # surrounding Qcx already excludes concurrent writers).
        for _ in range(3):
            try:
                return self._execute_query(idx, query, shards)
            except StackStale:
                continue
        with writer_wait(self.holder.write_lock):
            return self._execute_query(idx, query, shards)

    def _execute_query(self, idx: Index, query: Query, shards) -> List[Any]:
        raw = [self._execute_call(idx, call, shards) for call in query.calls]
        # Overlap all device->host copies, then block once. A span of
        # the sampled tree only: the profiler already names what runs
        # inside (``np.asarray(jax.Array)``), and an annotation of ours
        # around it would take those seconds from their owner.
        with get_tracer().start_span("pql.fetch"):
            _start_copies(raw)
            return [_resolve(r) for r in raw]

    # Capability flag for the scheduler's superset fusion (sched/batch.py
    # probes it before routing heterogeneous shard sets here).
    supports_shard_masks = True

    def execute_many(self, index: str, queries: Sequence,
                     shards: Optional[Sequence[int]] = None,
                     per_query_shards: Optional[Sequence] = None
                     ) -> List[List[Any]]:
        """Resolve several read queries with ONE blocking device->host
        sync — the fusion primitive behind the micro-batcher (sched/):
        every call of every query dispatches asynchronously, then all
        copies overlap, so N concurrent queries pay one round-trip floor
        exactly like N top-level calls of a single ``execute``.

        ``per_query_shards`` (one shard set per query, overriding
        ``shards``) enables CROSS-shard-set fusion: maskable queries
        evaluate over ONE stacked layout covering the union of all sets,
        each restricted to its own subset by a per-query word-lane mask
        (ShardMask) — still one dispatch + one host sync. Queries the
        mask cannot cover exactly (host-scan calls, Options shards=
        overrides) keep their own shard list within the same fused
        round."""
        idx = self.holder.index(index)
        qs: List[Query] = []
        for q in queries:
            if isinstance(q, str):
                q = parse(q)
            if isinstance(q, Call):
                q = Query([q])
            if has_write_calls(q):
                raise ValueError("execute_many is read-only")
            qs.append(q)
        if per_query_shards is None:
            if self.cache is None:
                return self._execute_many_retry(idx, qs, shards)
            return self._execute_many_cached(idx, qs, shards)
        if len(per_query_shards) != len(qs):
            raise ValueError("per_query_shards must match queries")
        shard_lists = [self._shards(idx, s) for s in per_query_shards]
        if self.cache is None:
            plans = self._fusion_plans(idx, qs, shard_lists)
            return self._execute_many_retry(idx, qs, shards, plans)
        return self._execute_many_cached(idx, qs, shards, shard_lists)

    def _fusion_plans(self, idx: Index, qs: Sequence[Query],
                      shard_lists: Sequence[List[int]]
                      ) -> List[Tuple[List[int], Optional[ShardMask]]]:
        """Per-query (shard_list, mask) execution plans over the union
        layout. Plans are pure host data — safe to reuse across
        StackStale retries. Masks for identical subsets are shared (one
        mask plane per distinct subset, not per query)."""
        union = sorted(set().union(*map(set, shard_lists))) \
            if shard_lists else []
        union_set = set(union)
        masks: Dict[frozenset, ShardMask] = {}
        plans: List[Tuple[List[int], Optional[ShardMask]]] = []
        for q, sl in zip(qs, shard_lists):
            sub = frozenset(sl)
            if sub == union_set:
                plans.append((union, None))
            elif query_maskable(q):
                mask = masks.get(sub)
                if mask is None:
                    mask = masks[sub] = ShardMask(union, sub)
                plans.append((union, mask))
            else:
                plans.append((sl, None))
        return plans

    def _execute_many_retry(self, idx: Index, qs: Sequence[Query],
                            shards, plans=None) -> List[List[Any]]:
        from pilosa_tpu.core.stacked import StackStale

        # same StackStale retry contract as _execute_read (plans are
        # pure host data, safe to reuse across retries)
        for _ in range(3):
            try:
                if plans is None:
                    return self._execute_many(idx, qs, shards)
                return self._execute_many(idx, qs, shards, plans)
            except StackStale:
                continue
        with writer_wait(self.holder.write_lock):
            if plans is None:
                return self._execute_many(idx, qs, shards)
            return self._execute_many(idx, qs, shards, plans)

    def _execute_many_cached(self, idx: Index, qs: Sequence[Query],
                             shards, shard_lists=None) -> List[List[Any]]:
        """Per-query cache fill around ONE fused dispatch: hits and
        single-flight followers drop out of the batch; all remaining
        queries (miss leaders + uncacheable bypasses) still go through
        a single ``_execute_many`` so the fusion amortization is kept.

        With ``shard_lists`` (superset fusion), each query's key uses its
        OWN shard set — a masked execution over the union stack fills
        exact per-query entries, and the fusion plan for the residual
        misses is recomputed over just their (possibly tighter) union."""
        cache = self.cache
        if shard_lists is None:
            shared = self._shards(idx, shards)
            key_lists = [shared] * len(qs)
        else:
            key_lists = shard_lists
        ns = self._namespace()
        results: List[Optional[List[Any]]] = [None] * len(qs)
        to_run: List[Tuple[int, Optional[Tuple]]] = []  # (slot, key|None)
        followers = []  # (slot, future)
        for i, q in enumerate(qs):
            key = query_cache_key(idx, q, key_lists[i], namespace=ns)
            if key is None:
                cache.bypass()
                to_run.append((i, None))
                continue
            state, payload = cache.fetch(key)
            if state == "hit":
                results[i] = payload
            elif state == "leader":
                to_run.append((i, key))
            else:
                followers.append((i, payload))
        if to_run:
            run_qs = [qs[i] for i, _ in to_run]
            plans = None
            if shard_lists is not None:
                plans = self._fusion_plans(
                    idx, run_qs, [key_lists[i] for i, _ in to_run])
            t0 = time.perf_counter()
            try:
                out = self._execute_many_retry(idx, run_qs, shards, plans)
            except BaseException as exc:
                for _, key in to_run:
                    if key is not None:
                        cache.fail(key, exc)
                raise
            cache.observe_dispatch(time.perf_counter() - t0)
            for (i, key), res in zip(to_run, out):
                results[i] = res
                if key is not None:
                    cache.complete(key, res)
        for i, fut in followers:
            results[i] = copy.deepcopy(fut.result())
        return results

    def _execute_many(self, idx: Index, qs: Sequence[Query],
                      shards, plans=None) -> List[List[Any]]:
        if plans is None:
            raw = [[self._execute_call(idx, call, shards) for call in q.calls]
                   for q in qs]
        else:
            raw = [[self._execute_call(idx, call, s, mask)
                    for call in q.calls]
                   for q, (s, mask) in zip(qs, plans)]
        with get_tracer().start_span("pql.fetch"):
            for rq in raw:
                _start_copies(rq)
            return [[_resolve(r) for r in rq] for rq in raw]

    # -- dispatch (reference: executor.go:679 executeCall) --------------------

    def _execute_call(self, idx: Index, call: Call, shards=None,
                      mask: Optional[ShardMask] = None) -> Any:
        name = call.name
        if name == "Options":
            if call.arg("shards") is not None:
                if mask is not None:
                    # query_maskable excludes these before planning; a
                    # mask sized for the union layout cannot index an
                    # arbitrary override set.
                    raise PQLError(
                        "Options(shards=) cannot execute under a shard mask")
                shards = [int(s) for s in call.arg("shards")]
            return self._execute_call(idx, call.children[0], shards, mask)
        if name in _WRITE_CALLS:
            return self._execute_write(idx, call, shards)
        if name == "Count":
            return self._execute_count(idx, call, shards, mask)
        if name in ("Sum", "Min", "Max"):
            return self._execute_bsi_agg(idx, call, shards, mask)
        if name in ("TopN", "TopK"):
            return self._execute_topn(idx, call, shards, mask)
        if name == "Rows":
            return self._execute_rows(idx, call, shards, mask)
        if name == "GroupBy":
            return self._execute_groupby(idx, call, shards, mask)
        if name == "Percentile":
            return self._execute_percentile(idx, call, shards, mask)
        if name in _BITMAP_CALLS:
            return self._materialize_row(idx, call, shards, mask)
        if mask is not None:
            # host-scan calls walk fragments directly; _MASKABLE_CALLS
            # keeps them out of masked plans — reaching here means a
            # caller bypassed query_maskable.
            raise PQLError(f"{name} cannot execute under a shard mask")
        if name == "IncludesColumn":
            return self._execute_includes_column(idx, call)
        if name == "Extract":
            return self._execute_extract(idx, call, shards)
        if name == "Apply":
            return self._execute_apply(idx, call, shards)
        if name == "Arrow":
            return self._execute_arrow(idx, call, shards)
        if name == "Sort":
            return self._execute_sort(idx, call, shards)
        if name == "FieldValue":
            return self._execute_field_value(idx, call)
        if name == "ExternalLookup":
            return self._execute_external_lookup(idx, call)
        raise PQLError(f"unknown call {name!r}")

    # -- shard helpers ---------------------------------------------------------

    def _shards(self, idx: Index, shards) -> List[int]:
        if shards is not None:
            return sorted(shards)
        return sorted(idx.shards())

    def _zero(self, words: int) -> jnp.ndarray:
        # shared bounded cache (ops/bitmap.py) — also the CPU scratch of
        # the resident plane programs, so one buffer serves both
        return B.device_zeros(words)

    def _existence_all(self, idx: Index, shard_list: List[int]) -> jnp.ndarray:
        ex = idx.existence
        if ex is None:
            raise PQLError(
                f"index {idx.name!r} does not track existence; Not/All need it")
        st = stacked_set(ex, shard_list, timeq.VIEW_STANDARD)
        return st.row_plane(EXISTENCE_ROW)

    # -- row/column key resolution ---------------------------------------------

    def _row_id(self, field: Field, value, create=False) -> Optional[int]:
        if field.options.type == FieldType.BOOL:
            if isinstance(value, bool):
                return 1 if value else 0
            return int(value)
        if isinstance(value, str):
            if not field.options.keys:
                raise PQLError(f"field {field.name!r} does not use string keys")
            if create:
                return field.translate.create_keys([value])[value]
            got = field.translate.find_keys([value])
            return got.get(value)
        if isinstance(value, bool):
            raise PQLError(f"field {field.name!r} is not bool")
        return int(value)

    def _col_id(self, idx: Index, value, create=False) -> Optional[int]:
        if isinstance(value, str):
            if not idx.options.keys:
                raise PQLError(f"index {idx.name!r} does not use string keys")
            if create:
                return idx.translate.create_keys([value])[value]
            return idx.translate.find_keys([value]).get(value)
        return int(value)

    # -- batched bitmap evaluation ---------------------------------------------
    # The analog of executor.go:1782 executeBitmapCallShard, but over ALL
    # shards at once: planes are uint32[len(shards)*WORDS_PER_SHARD].

    def _eval_all(self, idx: Index, call: Call, shard_list: List[int],
                  mask: Optional[ShardMask] = None) -> jnp.ndarray:
        # ``mask`` does NOT restrict the planes built here — bitmap
        # algebra is column-local, so callers mask once at their
        # materialization/aggregation point. It threads through only for
        # the restricted-Rows selection below (limit/previous/column pick
        # DIFFERENT rows depending on which columns count as present).
        total_words = len(shard_list) * WORDS_PER_SHARD
        name = call.name
        if name == "Row":
            return self._eval_row(idx, call, shard_list)
        if name == "Union":
            planes = [self._eval_all(idx, c, shard_list, mask)
                      for c in call.children]
            out = planes[0] if planes else self._zero(total_words)
            for p in planes[1:]:
                out = B.plane_or(out, p)
            return out
        if name == "Intersect":
            if not call.children:
                raise PQLError("Intersect requires at least one child")
            planes = [self._eval_all(idx, c, shard_list, mask)
                      for c in call.children]
            out = planes[0]
            for p in planes[1:]:
                out = B.plane_and(out, p)
            return out
        if name == "Difference":
            if not call.children:
                raise PQLError("Difference requires at least one child")
            out = self._eval_all(idx, call.children[0], shard_list, mask)
            for c in call.children[1:]:
                out = B.plane_andnot(
                    out, self._eval_all(idx, c, shard_list, mask))
            return out
        if name == "Xor":
            planes = [self._eval_all(idx, c, shard_list, mask)
                      for c in call.children]
            out = planes[0] if planes else self._zero(total_words)
            for p in planes[1:]:
                out = B.plane_xor(out, p)
            return out
        if name == "Not":
            child = self._eval_all(idx, call.children[0], shard_list, mask)
            return B.plane_andnot(self._existence_all(idx, shard_list), child)
        if name == "All":
            return self._existence_all(idx, shard_list)
        if name == "ConstRow":
            cols = [self._col_id(idx, c) for c in call.arg("columns", [])]
            plane = np.zeros((len(shard_list), WORDS_PER_SHARD), dtype=np.uint32)
            pos = {s: i for i, s in enumerate(shard_list)}
            by_shard: Dict[int, List[int]] = {}
            for c in cols:
                if c is None:
                    continue
                si = pos.get(c // SHARD_WIDTH)
                if si is not None:
                    by_shard.setdefault(si, []).append(c % SHARD_WIDTH)
            for si, locals_ in by_shard.items():
                plane[si] = B.bits_to_plane(locals_)
            return jnp.asarray(plane.reshape(total_words))
        if name == "UnionRows":
            out = self._zero(total_words)
            for c in call.children:
                if c.name != "Rows":
                    raise PQLError("UnionRows children must be Rows calls")
                field = idx.field(self._field_name(c))
                from_a, to_a = c.arg("from"), c.arg("to")
                in_a = c.arg("in")
                restricted = (c.arg("limit") is not None
                              or c.arg("previous") is not None
                              or c.arg("column") is not None)
                if from_a is not None or to_a is not None:
                    # records with ANY matching event in the range: OR of
                    # the selected row planes across the covering quantum
                    # views (the lowering of SQL rangeq(); reference:
                    # view-ranged Rows feeding executeUnionRows)
                    views = field.range_views(
                        _parse_ts(from_a) if from_a is not None else None,
                        _parse_ts(to_a) if to_a is not None else None)
                    # _rows_list honors from/to together with the
                    # in/limit/previous/column options; a bare in= list
                    # needs no device trip at all
                    if restricted:
                        rows = self._rows_list(idx, c, shard_list, mask)
                    elif in_a is not None:
                        rows = self._in_row_ids(field, in_a)
                    else:
                        rows = None
                    for v in views:
                        st = stacked_set(field, shard_list, v)
                        sel = st.row_ids if rows is None else rows
                        out = B.plane_or(out, st.rows_plane(sel))
                    continue
                st = stacked_set(field, shard_list, timeq.VIEW_STANDARD)
                if restricted:
                    rows = self._rows_list(idx, c, shard_list, mask)
                elif in_a is not None:
                    # explicit row selection (the SQL semi-join broadcast:
                    # dimension row ids OR'd into one fact-side filter) —
                    # pure host list, rows_plane skips ids with no plane
                    rows = self._in_row_ids(field, in_a)
                else:
                    rows = st.row_ids  # empty rows OR in nothing
                out = B.plane_or(out, st.rows_plane(rows))
            return out
        if name == "Shift":
            out = self._eval_all(idx, call.children[0], shard_list, mask)
            shaped = out.reshape(len(shard_list), WORDS_PER_SHARD)
            for _ in range(int(call.arg("n", 1))):
                # carries stop at shard boundaries, matching the
                # reference's per-shard executeShiftShard
                shaped = jax.vmap(B.plane_shift)(shaped)
            return shaped.reshape(total_words)
        if name == "Distinct":
            raise PQLError("Distinct cannot be nested inside bitmap calls yet")
        if name == "Limit":
            raise PQLError("Limit is only valid at the top level of a query")
        raise PQLError(f"call {name!r} does not return a bitmap")

    def _eval_row(self, idx: Index, call: Call, shard_list: List[int]
                  ) -> jnp.ndarray:
        fa = call.field_arg(exclude=ROW_OPTIONS)
        if fa is None:
            raise PQLError("Row requires a field argument")
        fname, value = fa
        field = idx.field(fname)
        if isinstance(value, Condition) or field.options.type.is_bsi:
            return self._eval_bsi_row(field, value, shard_list)
        row = self._row_id(field, value)
        total_words = len(shard_list) * WORDS_PER_SHARD
        if row is None:  # unknown key -> empty row
            return self._zero(total_words)
        from_a, to_a = call.arg("from"), call.arg("to")
        if from_a is not None or to_a is not None:
            views = field.range_views(
                _parse_ts(from_a) if from_a is not None else None,
                _parse_ts(to_a) if to_a is not None else None,
            )
            out = self._zero(total_words)
            for v in views:
                st = stacked_set(field, shard_list, v)
                out = B.plane_or(out, st.row_plane(row))
            return out
        st = stacked_set(field, shard_list, timeq.VIEW_STANDARD)
        return st.row_plane(row)

    def _eval_bsi_row(self, field: Field, value, shard_list: List[int]
                      ) -> jnp.ndarray:
        """BSI range predicate (reference: executor.go executeRowShard BSI
        branch -> fragment.rangeOp, fragment.go:937)."""
        if not field.options.type.is_bsi:
            raise PQLError(f"field {field.name!r} is not an int-like field")
        st = stacked_bsi(field, shard_list)
        if not isinstance(value, Condition):
            value = Condition("==", value)
        op = _COND_TO_BSI[value.op]
        # st.compare narrows compressed-resident stacks to active tiles
        # (ops/ctiles.py); dense stacks take the classic bsi_compare
        if value.op == "between":
            lo, hi = value.value
            return st.compare(op, field.to_stored(lo), field.to_stored(hi))
        if value.value is None:
            # `!= null` = exists; `== null` = not exists (needs existence).
            if value.op == "!=":
                return st.exists_plane()
            raise PQLError("== null is not supported; use Not(Row(f != null))")
        return st.compare(op, field.to_stored(value.value))

    # -- top-level materialization --------------------------------------------

    def _materialize_row(self, idx: Index, call: Call, shards,
                         mask: Optional[ShardMask] = None) -> Any:
        limit, offset = None, 0
        if call.name == "Limit":
            limit = call.arg("limit")
            offset = int(call.arg("offset", 0))
            call = call.children[0]
            if self.remote:  # coordinator applies limit/offset after merge
                limit, offset = None, 0
        if call.name == "Distinct":
            return self._execute_distinct(idx, call, shards, mask)
        shard_list = self._shards(idx, shards)
        if not shard_list:
            return self._row_result(idx, [])
        # warm path: one compiled program over resident planes (mask
        # applied in-program); None -> classic per-op evaluation
        plane = programs.run_plane(self, idx, call, shard_list, mask)
        if plane is None:
            plane = self._eval_all(idx, call, shard_list, mask)
            if mask is not None:
                # restrict materialized columns to the query's own shards
                plane = B.plane_and(plane, mask.plane)

        def finalize(plane_np: np.ndarray):
            shaped = plane_np.reshape(len(shard_list), WORDS_PER_SHARD)
            cols: List[int] = []
            for si, shard in enumerate(shard_list):
                base = shard * SHARD_WIDTH
                cols.extend(int(base + c) for c in B.plane_to_bits(shaped[si]))
            if offset:
                cols = cols[offset:]
            if limit is not None:
                cols = cols[: int(limit)]
            return self._row_result(idx, cols)

        return _Deferred([plane], finalize)

    def _row_result(self, idx: Index, cols: List[int]) -> R.RowResult:
        if idx.options.keys and not self.remote:
            m = idx.translate.translate_ids(cols)
            return R.RowResult(columns=[], keys=[m.get(c, str(c)) for c in cols])
        return R.RowResult(columns=cols)

    # -- Count (reference: executor.go:5839 executeCount) ---------------------

    def _execute_count(self, idx: Index, call: Call, shards,
                       mask: Optional[ShardMask] = None) -> Any:
        if len(call.children) != 1:
            raise PQLError("Count requires a single child call")
        child = call.children[0]
        if child.name == "Distinct":
            res = _resolve(self._execute_distinct(idx, child, shards, mask))
            if isinstance(res, R.RowResult):
                return len(res.columns or res.keys or [])
            return len(res)
        shard_list = self._shards(idx, shards)
        if not shard_list:
            return 0
        # warm path: ops + popcount + cross-shard psum in ONE compiled
        # program over resident planes; None -> classic per-op path
        count = programs.run_count(self, idx, child, shard_list, mask)
        if count is None:
            plane = self._eval_all(idx, child, shard_list, mask)
            if mask is None:
                count = B.plane_count(plane)
            else:
                # fused AND+popcount — the mask never materializes on host
                count = B.plane_intersection_count(plane, mask.plane)
        return _Deferred([count], lambda c: int(c))

    # -- BSI aggregates (reference: executor.go executeSum/Min/Max) -----------

    def _agg_filter(self, idx: Index, call: Call, shard_list: List[int],
                    st: StackedBSI, mask: Optional[ShardMask] = None
                    ) -> jnp.ndarray:
        if call.children:
            filt = self._eval_all(idx, call.children[0], shard_list, mask)
        else:
            filt = st.exists_plane()
        return S.mask_filter(filt, mask.plane if mask is not None else None)

    def _execute_bsi_agg(self, idx: Index, call: Call, shards,
                         mask: Optional[ShardMask] = None) -> Any:
        fname = call.arg("field") or call.arg("_field")
        if fname is None:
            raise PQLError(f"{call.name} requires field=")
        field = idx.field(fname)
        if not field.options.type.is_bsi:
            raise PQLError(f"field {fname!r} is not an int-like field")
        shard_list = self._shards(idx, shards)
        if call.name == "Sum":
            if not shard_list:
                return R.ValCount(val=0, count=0)
            st = stacked_bsi(field, shard_list)
            filt = self._agg_filter(idx, call, shard_list, st, mask)
            count, pos, neg = S.bsi_plane_popcounts(st.planes, filt)

            def fin_sum(count_np, pos_np, neg_np):
                total = 0
                for k in range(pos_np.shape[0]):
                    total += (int(pos_np[k]) - int(neg_np[k])) << k
                n = int(count_np)
                # stored = actual - base  =>  sum(actual) = sum(stored)+base*n
                val = total + field.options.base * n
                if field.options.type == FieldType.DECIMAL:
                    val = val / (10 ** field.options.scale)
                return R.ValCount(val=val, count=n)

            return _Deferred([count, pos, neg], fin_sum)
        # Min / Max (reference: executor.go executeMinShard/MaxShard); the
        # stacked layout makes the cross-shard merge implicit.
        if not shard_list:
            return R.ValCount(val=None, count=0)
        want_max = call.name == "Max"
        st = stacked_bsi(field, shard_list)
        filt = self._agg_filter(idx, call, shard_list, st, mask)
        bits, negative, cnt, total = S._minmax_kernel(st.planes, filt, want_max)

        def fin_minmax(bits_np, neg_np, cnt_np, total_np):
            if int(total_np) == 0:
                return R.ValCount(val=None, count=0)
            v = 0
            for k in range(bits_np.shape[0]):
                if bits_np[k]:
                    v |= 1 << k
            if neg_np:
                v = -v
            return R.ValCount(val=field.from_stored(v), count=int(cnt_np))

        return _Deferred([bits, negative, cnt, total], fin_minmax)

    # -- TopN / TopK (reference: executor.go:2357/2535) ------------------------

    def _execute_topn(self, idx: Index, call: Call, shards,
                      mask: Optional[ShardMask] = None) -> Any:
        fname = self._field_name(call)
        field = idx.field(fname)
        n = call.arg("n") or call.arg("k")
        shard_list = self._shards(idx, shards)
        if not shard_list:
            return self._pairs_field(field, [])
        filt = (self._eval_all(idx, call.children[0], shard_list, mask)
                if call.children else None)
        if mask is not None:
            # rank only the subset's columns; zero-count rows drop in
            # finalize, matching a solo run over the subset
            filt = S.mask_filter(filt, mask.plane)
        row_ids, counts = self._ranged_row_counts(field, call, shard_list,
                                                  filt)
        if not row_ids:
            return self._pairs_field(field, [])

        def finalize(counts_np: np.ndarray):
            ranked = [(row, int(counts_np[slot]))
                      for slot, row in enumerate(row_ids)
                      if counts_np[slot]]
            ranked.sort(key=lambda kv: (-kv[1], kv[0]))
            if n is not None and not self.remote:
                return self._pairs_field(field, ranked[: int(n)])
            return self._pairs_field(field, ranked)

        return _Deferred([counts], finalize)

    # Union-row chunk width for multi-view merges: bounds the transient
    # [chunk, S*W] merged tensor the same way row blocks bound stacks.
    _MERGE_CHUNK = 1024

    def _ranged_row_counts(self, field: Field, call: Call,
                           shard_list: List[int], filt):
        """(row_ids, device per-row counts) honoring the call's from/to
        time range — bits from the covering quantum views are OR-merged
        per row so counts match the reference's per-view union
        (executor.go executeTopNShard routing through fragment views;
        VERDICT r1-r3: TopN must not read the standard view when a range
        is given). Streams paged stacks block by block."""
        from_a, to_a = call.arg("from"), call.arg("to")
        if from_a is None and to_a is None:
            st = stacked_set(field, shard_list, timeq.VIEW_STANDARD)
            return st.row_ids, st.row_counts(filt)
        views = field.range_views(
            _parse_ts(from_a) if from_a is not None else None,
            _parse_ts(to_a) if to_a is not None else None)
        stacks = [stacked_set(field, shard_list, v) for v in views]
        stacks = [s for s in stacks if s.row_ids]
        if not stacks:
            return [], None
        if len(stacks) == 1:
            return stacks[0].row_ids, stacks[0].row_counts(filt)
        from pilosa_tpu.core.stacked import sync_part

        row_ids = sorted(set().union(*[s.row_index for s in stacks]))
        parts = []
        for lo in range(0, len(row_ids), self._MERGE_CHUNK):
            chunk = row_ids[lo:lo + self._MERGE_CHUNK]
            merged = None
            for s in stacks:
                sel = s.take_rows(chunk)
                merged = sel if merged is None else jnp.bitwise_or(merged, sel)
            # TopN ranking counts ride the Pallas MXU row-count kernel
            # when eligible (ops/topk.py dispatcher; classic reduce
            # otherwise — bit-identical either way)
            parts.append(sync_part(T.row_counts(merged, filt)))
        return row_ids, _concat(parts)

    def _pairs_field(self, field: Field, ranked: List[Tuple[int, int]]
                     ) -> R.PairsField:
        if field.options.keys and not self.remote:
            keys = field.translate.translate_ids([r for r, _ in ranked])
            pairs = [R.Pair(id=None, key=keys.get(r, str(r)), count=c)
                     for r, c in ranked]
        else:
            pairs = [R.Pair(id=r, key=None, count=c) for r, c in ranked]
        return R.PairsField(pairs=pairs, field=field.name)

    # -- Rows (reference: executor.go executeRows) -----------------------------

    def _field_name(self, call: Call) -> str:
        fname = call.arg("_field") or call.arg("field")
        if fname is None:
            raise PQLError(f"{call.name} requires a field")
        return fname

    def _in_row_ids(self, field: Field, values) -> List[int]:
        """Resolve a ``Rows(f, in=[...])`` selection to row ids. String
        members go through the field translator in one batch; unknown
        keys drop out (an absent dimension member matches no rows — the
        same silence as ``Row(f="missing")`` returning empty)."""
        strs = [v for v in values if isinstance(v, str)]
        if strs and not field.options.keys:
            raise PQLError(f"field {field.name!r} does not use string keys")
        found = field.translate.find_keys(strs) if strs else {}
        out = set()
        for v in values:
            if isinstance(v, str):
                r = found.get(v)
                if r is not None:
                    out.add(r)
            elif isinstance(v, bool):
                out.add(1 if v else 0)
            else:
                out.add(int(v))
        return sorted(out)

    def _rows_list(self, idx: Index, call: Call, shards=None,
                   mask: Optional[ShardMask] = None) -> List[int]:
        field = idx.field(self._field_name(call))
        col = call.arg("column")
        shard_list = self._shards(idx, shards)
        rows: set = set()
        if col is not None:
            # point lookup: host planes, no device trip
            c = self._col_id(idx, col)
            if (c is not None and c // SHARD_WIDTH in shard_list
                    and (mask is None or c // SHARD_WIDTH in mask.subset)):
                shard = c // SHARD_WIDTH
                frag = field.fragment(shard)
                if frag is not None:
                    pos = c % SHARD_WIDTH
                    for row in frag.existing_rows():
                        plane = frag.row_plane(row)
                        if plane[pos // 32] & (np.uint32(1) << np.uint32(pos % 32)):
                            rows.add(row)
        elif shard_list:
            # honors from/to time args (reference: executor.go:4108). A
            # shard mask rides in as the count filter: rows present only
            # outside the subset count zero and drop out, so the listing
            # (and the limit/previous cut below) matches a solo run.
            row_ids, counts = self._ranged_row_counts(
                field, call, shard_list,
                mask.plane if mask is not None else None)
            if row_ids:
                counts = np.asarray(counts)
                rows = {row for slot, row in enumerate(row_ids)
                        if counts[slot]}
        out = sorted(rows)
        in_a = call.arg("in")
        if in_a is not None:
            want = set(self._in_row_ids(field, in_a))
            out = [r for r in out if r in want]
        prev = call.arg("previous")
        if prev is not None:
            prev_id = self._row_id(field, prev)
            out = [r for r in out if prev_id is None or r > prev_id]
        limit = call.arg("limit")
        if limit is not None and not self.remote:
            out = out[: int(limit)]
        return out

    def _execute_rows(self, idx: Index, call: Call, shards,
                      mask: Optional[ShardMask] = None) -> List[Any]:
        field = idx.field(self._field_name(call))
        rows = self._rows_list(idx, call, shards, mask)
        if field.options.keys and not self.remote:
            m = field.translate.translate_ids(rows)
            return [m.get(r, str(r)) for r in rows]
        return rows

    # -- Distinct (reference: executor.go:1952-2153) ---------------------------

    def _execute_distinct(self, idx: Index, call: Call, shards,
                          mask: Optional[ShardMask] = None):
        field = idx.field(self._field_name(call))
        if not field.options.type.is_bsi:
            # Set-like: distinct values are the row IDs present.
            rows = self._rows_list(idx, call, shards, mask)
            if field.options.keys and not self.remote:
                m = field.translate.translate_ids(rows)
                return R.RowResult(columns=[], keys=[m.get(r, str(r)) for r in rows])
            return R.RowResult(columns=rows)
        shard_list = self._shards(idx, shards)
        filt_np = None
        if call.children and shard_list:
            filt_np = np.asarray(
                self._eval_all(idx, call.children[0], shard_list, mask)
            ).reshape(len(shard_list), WORDS_PER_SHARD)
        vals: set = set()
        for si, shard in enumerate(shard_list):
            if mask is not None and shard not in mask.subset:
                continue  # host loop skips non-subset shards outright
            frag = field.bsi_fragment(shard)
            if frag is None:
                continue
            vals.update(self._decode_distinct(
                frag, filt_np[si] if filt_np is not None else None))
        return sorted(field.from_stored(v) for v in vals)

    @staticmethod
    def _decode_distinct(frag, filt: Optional[np.ndarray]) -> set:
        """Host-side unique stored values of a BSI fragment (the pivot
        analog, reference: bsi.go:18 PivotDescending)."""
        exists = frag.planes[S.EXISTS]
        if filt is not None:
            exists = exists & filt
        cols = B.plane_to_bits(exists)
        if cols.size == 0:
            return set()
        w = (cols // 32).astype(np.int64)
        b = (cols % 32).astype(np.uint32)
        vals = np.zeros(cols.size, dtype=np.int64)
        for k in range(frag.depth):
            bits = (frag.planes[S.OFFSET + k][w] >> b) & 1
            vals |= bits.astype(np.int64) << k
        sign = ((frag.planes[S.SIGN][w] >> b) & 1).astype(bool)
        vals[sign] = -vals[sign]
        return set(int(v) for v in vals)

    # -- GroupBy (reference: executor.go:3918 executeGroupByShard) -------------

    def _execute_groupby(self, idx: Index, call: Call, shards,
                         mask: Optional[ShardMask] = None) -> Any:
        if not call.children:
            raise PQLError("GroupBy requires at least one Rows child")
        rows_calls = [c for c in call.children if c.name == "Rows"]
        if len(rows_calls) != len(call.children):
            raise PQLError("GroupBy children must be Rows calls")
        fields = [idx.field(self._field_name(c)) for c in rows_calls]
        filter_call = call.arg("filter")
        agg_call = call.arg("aggregate")
        agg_field = None
        if agg_call is not None:
            if not isinstance(agg_call, Call) or agg_call.name not in ("Sum", "Count"):
                raise PQLError("GroupBy aggregate must be Sum(...) or Count(...)")
            if agg_call.name == "Sum":
                agg_field = idx.field(agg_call.arg("field") or agg_call.arg("_field"))
        limit = call.arg("limit")
        if self.remote:
            limit = None

        shard_list = self._shards(idx, shards)
        if not shard_list:
            return []
        sts = [stacked_set(f, shard_list, timeq.VIEW_STANDARD) for f in fields]
        if any(not st.row_ids for st in sts):
            return []
        filt = (self._eval_all(idx, filter_call, shard_list, mask)
                if filter_call is not None else None)
        if mask is not None:
            # mask folds into the group filter: level-0 planes get ANDed
            # with it, the AND-fold keeps it, and _groupby_emit drops the
            # count==0 groups — identical output to a solo subset run
            filt = S.mask_filter(filt, mask.plane)
        agg_st = stacked_bsi(agg_field, shard_list) if agg_field is not None else None

        dense = self._groupby_dense_ok(sts, agg_st)
        M.REGISTRY.count(M.METRIC_GROUPBY_ROUTE,
                         route="dense" if dense else "fold",
                         fields=_FIELDS_LABEL[min(len(sts), 4) - 1])
        if dense:
            return self._groupby_dense(fields, sts, filt, agg_field, agg_st, limit)
        return self._groupby_fold(fields, sts, filt, agg_field, agg_st, limit)

    @staticmethod
    def _groupby_dense_ok(sts, agg_st) -> bool:
        """The dense path materializes the full count tensor over the
        fields' row capacities (and [D, ...] sum tensors with a Sum
        aggregate) — cap the cell product so high-cardinality GroupBy
        falls back to the pruning fold instead of OOMing HBM (paged
        stacks stream their INPUT blocks and group planes are made a
        block at a time, but the dense OUTPUT is unbounded by either).
        The product chooses the route of a count, whatever the number of
        fields. A Sum over three or more fields stays with the fold:
        dense, ``pair_sums`` scans the magnitude planes of every cell,
        live or not, and the fold sums the live groups only (SSB Q3.1
        keeps 25 of its 625 leading pairs behind its region filters)."""
        cells = 1
        for st in sts:
            cells *= st.cap
        if cells > _DENSE_MAX_CELLS:  # 16M int32 cells = 64MB per tensor
            return False
        if agg_st is None:
            return True
        if len(sts) > 2:
            return False
        return cells * agg_st.planes.shape[0] <= _DENSE_MAX_CELLS

    def _field_row(self, field: Field, row: int) -> R.FieldRow:
        if field.options.keys and not self.remote:
            key = field.translate.translate_ids([row]).get(row, str(row))
            return R.FieldRow(field=field.name, row_key=key)
        return R.FieldRow(field=field.name, row_id=row)

    def _groupby_emit(self, fields: List[Field], keyed_counts, agg_field,
                      limit) -> List[R.GroupCount]:
        out = []
        for key, count, agg in keyed_counts:
            if count == 0:
                continue
            group = [self._field_row(f, r) for f, r in zip(fields, key)]
            out.append(R.GroupCount(
                group=group, count=count,
                agg=agg if agg_field is not None else None))
        if limit is not None:
            out = out[: int(limit)]
        return out

    @staticmethod
    def _agg_masks(agg_st):
        sign = agg_st.planes[S.SIGN]
        mags = agg_st.planes[S.OFFSET:]
        pos_m = B.plane_andnot(agg_st.exists_plane(), sign)
        neg_m = B.plane_and(agg_st.exists_plane(), sign)
        return mags, pos_m, neg_m

    def _groupby_dense(self, fields, sts, filt, agg_field, agg_st, limit):
        """GroupBy whose whole result is a dense count tensor: counted on
        the device block by block and fetched once. The MXU pair-count
        matmul replaces the reference's per-pair container walk
        (executor.go:3176). One field counts its rows; two or more count
        group planes of all fields but the last (:meth:`_group_blocks`)
        against the last field's rows, streamed per row block."""
        if len(sts) == 1:
            # one pass over the blocks computing counts (and, with an
            # aggregate, the signed per-plane pair counts) — a block is
            # ensured once, not once per output tensor
            if agg_st is not None:
                mags, pos_m, neg_m = self._agg_masks(agg_st)
                mp = B.plane_and(mags, pos_m[None, :])
                mn = B.plane_and(mags, neg_m[None, :])
            c_parts, p_parts, ng_parts = [], [], []
            for _, blk in sts[0].iter_blocks():
                if filt is not None:
                    blk = B.plane_and(blk, filt[None, :])
                c_parts.append(sync_part(B.row_counts(blk)))
                if agg_st is not None:
                    p_parts.append(pair_counts(blk, mp))
                    ng_parts.append(sync_part(pair_counts(blk, mn)))
            counts = _concat(c_parts)
            arrays = [counts]
            if agg_st is not None:
                arrays += [_concat(p_parts), _concat(ng_parts)]

            def fin1(counts_np, p_np=None, ng_np=None):
                M.REGISTRY.count(M.METRIC_GROUPBY_HOST_FETCHES)
                keyed = []
                for slot, row in enumerate(sts[0].row_ids):
                    agg = 0
                    if p_np is not None:
                        for k in range(p_np.shape[1]):
                            agg += (int(p_np[slot, k]) - int(ng_np[slot, k])) << k
                    keyed.append(((row,), int(counts_np[slot]), agg))
                keyed.sort(key=lambda kv: kv[0])
                return self._groupby_emit(fields, keyed, agg_field, limit)

            return _Deferred(arrays, fin1)

        if agg_st is not None:
            mags, pos_m, neg_m = self._agg_masks(agg_st)
        last = sts[-1]
        slot_rows, count_rows, p_rows, ng_rows = [], [], [], []

        def count(slots, planes):
            c_cols, p_cols, ng_cols = [], [], []
            for _, b_blk in last.iter_blocks():
                c_cols.append(sync_part(pair_counts(planes, b_blk)))
                if agg_st is not None:
                    p, ng = pair_sums(planes, b_blk, mags, pos_m, neg_m)
                    p_cols.append(sync_part(p))
                    ng_cols.append(ng)
            slot_rows.append(slots)
            count_rows.append(_concat(c_cols, axis=1))
            if agg_st is not None:
                p_rows.append(_concat(p_cols, axis=2))
                ng_rows.append(_concat(ng_cols, axis=2))

        self._group_blocks(sts[:-1], filt, count)
        counts = _concat(count_rows, axis=0)  # [groups, capLast]
        arrays = [counts]
        if agg_st is not None:
            arrays += [_concat(p_rows, axis=1), _concat(ng_rows, axis=1)]

        def fin(counts_np, p_np=None, ng_np=None):
            M.REGISTRY.count(M.METRIC_GROUPBY_HOST_FETCHES)
            slots = np.concatenate(slot_rows)  # [groups, fields - 1]
            gi, gj = np.nonzero(counts_np[:, :len(last.row_ids)])
            ids = [np.asarray(st.row_ids, dtype=np.uint64) for st in sts]
            cols = [ids[f][slots[gi, f]] for f in range(len(sts) - 1)]
            cols.append(ids[-1][gj])
            order = np.lexsort(cols[::-1])
            gi, gj = gi[order], gj[order]
            keys = zip(*(c[order].tolist() for c in cols))
            aggs = [0] * gi.size
            if p_np is not None:
                for n, (i, j) in enumerate(zip(gi, gj)):
                    for k in range(p_np.shape[0]):
                        aggs[n] += (int(p_np[k, i, j]) - int(ng_np[k, i, j])) << k
            keyed = zip(keys, counts_np[gi, gj].tolist(), aggs)
            return self._groupby_emit(fields, keyed, agg_field, limit)

        return _Deferred(arrays, fin)

    def _group_blocks(self, sts, filt, sink) -> None:
        """Feed ``sink(slots, planes)`` the group planes of the row product
        of ``sts`` (filtered), a block at a time: ``planes[g]`` is the AND
        of the rows whose slots ``slots[g]`` names, one per field; planes
        past a field's last row are padding, all zero, and count nothing.
        One field hands its own row blocks on as they are; from the second
        on, each is crossed with the planes so far in blocks of at most
        :func:`_group_block` planes, depth first, so what is on the device
        at any time is a block a level, however many groups there are."""
        st0 = sts[0]
        n0 = len(st0.row_ids)
        for lo, blk in st0.iter_blocks():
            n = min(st0.block_rows, n0 - lo)
            if n <= 0:
                break
            if len(sts) == 1:
                if filt is not None:
                    blk = B.plane_and(blk, filt[None, :])
            elif filt is not None:
                blk = _made(group_planes(filt[None, :], blk, 0, 0, 1, n))
            elif n < blk.shape[0]:
                # pad rows would multiply through every level below
                blk = _made(blk[:n])
            slots = np.arange(lo, lo + blk.shape[0])[:, None]
            self._cross(sts, 1, slots, blk, sink)

    def _cross(self, sts, level, slots, planes, sink) -> None:
        """One level of :meth:`_group_blocks`: every block of ``planes``
        AND rows of ``sts[level]``, handed to the next level as it is
        made."""
        if level == len(sts):
            sink(slots, planes)
            return
        st = sts[level]
        n = len(st.row_ids)
        room = _group_block(st.total_words)
        g = planes.shape[0]
        with get_tracer().start_span(
                "groupby.level", level=level, groups_in=g,
                plane_bytes=planes.nbytes) as span:
            blocks = 0
            for lo, blk in st.iter_blocks():
                r = min(st.block_rows, n - lo)
                if r <= 0:
                    break
                if not blocks and span.recording:
                    _tag_route(span, planes, blk)
                rs = min(r, room)
                gs = max(1, room // rs)
                for g0 in range(0, g, gs):
                    gn = min(gs, g - g0)
                    for r0 in range(0, r, rs):
                        rn = min(rs, r - r0)
                        nxt = _made(group_planes(planes, blk, g0, r0, gn, rn))
                        blocks += 1
                        self._cross(sts, level + 1, np.concatenate(
                            [np.repeat(slots[g0:g0 + gn], rn, axis=0),
                             np.tile(np.arange(lo + r0, lo + r0 + rn),
                                     gn)[:, None]], axis=1), nxt, sink)
            span.set_tag("groups_live", g * n)
            span.set_tag("blocks", blocks)

    def _groupby_fold(self, fields, sts, filt, agg_field, agg_st, limit):
        """GroupBy above the dense cell cap: fold left-to-right keeping
        group planes on device, pruning empty groups between levels (one
        fetch per level — the reference pays a full nested iterator walk
        per shard instead, executor.go:3918). The FIRST field streams per
        row block so a paged (high-cardinality) leading field never
        materializes whole; deeper levels operate on the pruned nonzero
        groups, whose size is data-dependent exactly as in the
        reference's iterator walk."""
        keyed_all: List[Tuple] = []
        n0 = len(sts[0].row_ids)
        for lo, blk in sts[0].iter_blocks():
            hi = min(lo + sts[0].block_rows, n0)
            if hi <= lo:
                break
            group_planes = blk[: hi - lo]
            if filt is not None:
                group_planes = _made(B.plane_and(group_planes, filt[None, :]))
            keys = [(r,) for r in sts[0].row_ids[lo:hi]]
            keyed_all.extend(self._fold_levels(
                sts, group_planes, keys, agg_st))
        keyed_all.sort(key=lambda kv: kv[0])
        return self._groupby_emit(fields, keyed_all, agg_field, limit)

    def _fold_levels(self, sts, group_planes, keys, agg_st) -> List[Tuple]:
        """Fold one batch of level-0 group planes through the remaining
        fields; returns (key, count, agg) triples for nonzero groups."""
        for level, st in enumerate(sts[1:], start=1):
            nb = len(st.row_ids)
            with get_tracer().start_span(
                    "groupby.level", level=level, groups_in=len(keys),
                    plane_bytes=group_planes.nbytes) as span:
                parts = []
                for _, blk in st.iter_blocks():
                    if not parts and span.recording:
                        _tag_route(span, group_planes, blk)
                    parts.append(np.asarray(pair_counts(group_planes, blk)))
                counts_matrix = np.concatenate(parts, axis=1)[:, :nb]
                M.REGISTRY.count(M.METRIC_GROUPBY_HOST_FETCHES)
                gi, gj = np.nonzero(counts_matrix)
                span.set_tag("groups_live", int(gi.size))
                span.set_tag("blocks", st.n_blocks)
            last = level == len(sts) - 1
            if last and agg_st is None:
                return [(keys[g] + (st.row_ids[r],),
                         int(counts_matrix[g, r]), 0)
                        for g, r in zip(gi, gj)]
            if gi.size == 0:
                return []
            group_planes = _made(_made(group_planes[gi]) & st.take_rows(
                [st.row_ids[r] for r in gj]))
            keys = [keys[g] + (st.row_ids[r],) for g, r in zip(gi, gj)]
        counts = np.asarray(B.row_counts(group_planes))
        aggs = [0] * len(keys)
        if agg_st is not None:
            mags, pos_m, neg_m = self._agg_masks(agg_st)
            p = np.asarray(pair_counts(group_planes, mags & pos_m[None, :]))
            ng = np.asarray(pair_counts(group_planes, mags & neg_m[None, :]))
            for g in range(len(keys)):
                total = 0
                for k in range(p.shape[1]):
                    total += (int(p[g, k]) - int(ng[g, k])) << k
                aggs[g] = total
        M.REGISTRY.count(M.METRIC_GROUPBY_HOST_FETCHES)
        return [(keys[g], int(counts[g]), aggs[g]) for g in range(len(keys))]

    # -- Percentile (reference: executor.go:1310) ------------------------------

    def _execute_percentile(self, idx: Index, call: Call, shards,
                            mask: Optional[ShardMask] = None) -> Any:
        fname = call.arg("field") or call.arg("_field")
        field = idx.field(fname)
        nth = call.arg("nth")
        if nth is None:
            raise PQLError("Percentile requires nth=")
        nth = float(nth)
        if not (0 <= nth <= 100):
            raise PQLError("nth must be within [0, 100]")
        filter_call = call.arg("filter")
        shard_list = self._shards(idx, shards)
        if not shard_list:
            return R.ValCount(val=None, count=0)
        st = stacked_bsi(field, shard_list)
        filt = (self._eval_all(idx, filter_call, shard_list, mask)
                if filter_call is not None else st.exists_plane())
        if mask is not None:
            filt = S.mask_filter(filt, mask.plane)
        bits, negative, cnt, total = S._kth_kernel(
            st.planes, filt, jnp.int32(round(nth * 100)))

        def finalize(bits_np, neg_np, cnt_np, total_np):
            if int(total_np) == 0:
                return R.ValCount(val=None, count=0)
            v = 0
            for k in range(bits_np.shape[0]):
                if bits_np[k]:
                    v |= 1 << k
            if neg_np:
                v = -v
            return R.ValCount(val=field.from_stored(v), count=int(cnt_np))

        return _Deferred([bits, negative, cnt, total], finalize)

    # -- IncludesColumn (reference: executor.go executeIncludesColumnCall) -----

    def _execute_includes_column(self, idx: Index, call: Call) -> bool:
        col = call.arg("column")
        if col is None:
            raise PQLError("IncludesColumn requires column=")
        c = self._col_id(idx, col)
        if c is None:
            return False
        shard, pos = divmod(c, SHARD_WIDTH)
        # Evaluate over the full shard list so the probe reuses the same
        # stacked cache entries as every other query — singleton-shard
        # stacks would thrash the subset LRU (core/stacked.py).
        shard_list = self._shards(idx, None)
        if shard not in shard_list:
            return False
        si = shard_list.index(shard)
        plane = np.asarray(
            self._eval_all(idx, call.children[0], shard_list)
        ).reshape(len(shard_list), WORDS_PER_SHARD)[si]
        return bool(plane[pos // 32] & (np.uint32(1) << np.uint32(pos % 32)))

    # -- Extract (reference: executor.go:4711 executeExtract) ------------------

    def _execute_extract(self, idx: Index, call: Call, shards) -> R.ExtractedTable:
        if not call.children:
            raise PQLError("Extract requires a bitmap child")
        bitmap_call = call.children[0]
        rows_calls = call.children[1:]
        fields = [idx.field(self._field_name(c)) for c in rows_calls]
        efields = [R.ExtractedField(name=f.name, type=f.options.type.value)
                   for f in fields]
        columns: List[R.ExtractedColumn] = []
        shard_list = self._shards(idx, shards)
        if not shard_list:
            return R.ExtractedTable(fields=efields, columns=columns)
        planes_np = np.asarray(
            self._eval_all(idx, bitmap_call, shard_list)
        ).reshape(len(shard_list), WORDS_PER_SHARD)
        for si, shard in enumerate(shard_list):
            local = B.plane_to_bits(planes_np[si])
            if local.size == 0:
                continue
            base = shard * SHARD_WIDTH
            w = (local // 32).astype(np.int64)
            b = (local % 32).astype(np.uint32)
            per_field_vals: List[List[Any]] = []
            for f in fields:
                if f.options.type.is_bsi:
                    frag = f.bsi_fragment(shard)
                    vals: List[Any] = [None] * local.size
                    if frag is not None:
                        exists = ((frag.planes[S.EXISTS][w] >> b) & 1).astype(bool)
                        raw = np.zeros(local.size, dtype=np.int64)
                        for k in range(frag.depth):
                            bits = (frag.planes[S.OFFSET + k][w] >> b) & 1
                            raw |= bits.astype(np.int64) << k
                        sgn = ((frag.planes[S.SIGN][w] >> b) & 1).astype(bool)
                        raw[sgn] = -raw[sgn]
                        vals = [f.from_stored(int(v)) if e else None
                                for v, e in zip(raw, exists)]
                    per_field_vals.append(vals)
                else:
                    frag = f.fragment(shard)
                    rows_per_col: List[List[Any]] = [[] for _ in range(local.size)]
                    if frag is not None:
                        for row in frag.existing_rows():
                            rp = frag.row_plane(row)
                            hit = ((rp[w] >> b) & 1).astype(bool)
                            for i in np.nonzero(hit)[0]:
                                rows_per_col[i].append(row)
                        if f.options.keys and not self.remote:
                            all_rows = {r for rs in rows_per_col for r in rs}
                            m = f.translate.translate_ids(all_rows)
                            rows_per_col = [[m.get(r, str(r)) for r in rs]
                                            for rs in rows_per_col]
                        if f.options.type == FieldType.BOOL:
                            rows_per_col = [bool(rs and rs[-1] == 1)
                                            for rs in rows_per_col]
                    per_field_vals.append(rows_per_col)
            key_map = {}
            if idx.options.keys and not self.remote:
                key_map = idx.translate.translate_ids(
                    [int(base + c) for c in local])
            for i, c in enumerate(local):
                col_id = int(base + c)
                columns.append(R.ExtractedColumn(
                    column=col_id,
                    key=key_map.get(col_id) if idx.options.keys else None,
                    rows=[pv[i] for pv in per_field_vals],
                ))
        return R.ExtractedTable(fields=efields, columns=columns)

    # -- Sort (reference: executor.go:9321 executeSort) ------------------------

    def _execute_sort(self, idx: Index, call: Call, shards) -> R.SortedRow:
        """Sort(filter?, field=f, sort-desc=bool): record ids ordered by a
        BSI or bool field's value (reference: executor.go:9387
        executeSortShard + SortedRow.Merge)."""
        field = idx.field(self._field_name(call))
        desc = bool(call.arg("sort-desc", False))
        shard_list = self._shards(idx, shards)
        if not shard_list:
            return R.SortedRow(columns=[], values=[])
        filt_np = None
        if call.children:
            filt_np = np.asarray(
                self._eval_all(idx, call.children[0], shard_list)
            ).reshape(len(shard_list), WORDS_PER_SHARD)
        cols: List[int] = []
        vals: List[Any] = []
        if field.options.type == FieldType.BOOL:
            for si, shard in enumerate(shard_list):
                frag = field.fragment(shard)
                if frag is None:
                    continue
                base = shard * SHARD_WIDTH
                for row, v in ((0, False), (1, True)):
                    plane = frag.row_plane(row).copy()
                    if filt_np is not None:
                        plane &= filt_np[si]
                    for c in B.plane_to_bits(plane):
                        cols.append(int(base + c))
                        vals.append(v)
        elif field.options.type.is_bsi:
            for si, shard in enumerate(shard_list):
                frag = field.bsi_fragment(shard)
                if frag is None:
                    continue
                exists = frag.planes[S.EXISTS]
                if filt_np is not None:
                    exists = exists & filt_np[si]
                base = shard * SHARD_WIDTH
                pos = B.plane_to_bits(exists)
                if pos.size == 0:
                    continue
                # bulk plane decode — one numpy gather per magnitude
                # plane, not a per-column Python walk
                w = (pos // 32).astype(np.int64)
                b = (pos % 32).astype(np.uint32)
                raw = np.zeros(pos.size, dtype=np.int64)
                for k in range(frag.depth):
                    bits = (frag.planes[S.OFFSET + k][w] >> b) & 1
                    raw |= bits.astype(np.int64) << k
                sgn = ((frag.planes[S.SIGN][w] >> b) & 1).astype(bool)
                raw[sgn] = -raw[sgn]
                cols.extend(int(base + p) for p in pos)
                vals.extend(field.from_stored(int(v)) for v in raw)
        else:
            raise PQLError(
                f"Sort supports bool and int-like fields, not "
                f"{field.options.type.value}")
        order = sorted(range(len(cols)),
                       key=lambda i: (vals[i], cols[i]), reverse=desc)
        limit = call.arg("limit")
        if limit is not None and not self.remote:
            order = order[: int(limit)]
        sorted_cols = [cols[i] for i in order]
        keys = None
        if idx.options.keys and not self.remote:
            m = idx.translate.translate_ids(sorted_cols)
            keys = [m.get(c, str(c)) for c in sorted_cols]
        return R.SortedRow(columns=sorted_cols,
                           values=[vals[i] for i in order], keys=keys)

    # -- FieldValue (reference: executor.go:942 executeFieldValueCall) ---------

    def _execute_field_value(self, idx: Index, call: Call) -> R.ValCount:
        fname = call.arg("field") or call.arg("_field")
        if not fname:
            raise PQLError("FieldValue requires field=")
        col = call.arg("column")
        if col is None:
            raise PQLError("FieldValue requires column=")
        field = idx.field(fname)
        c = self._col_id(idx, col)
        if c is None:
            return R.ValCount(val=None, count=0)
        if field.options.type == FieldType.BOOL:
            shard, pos = divmod(c, SHARD_WIDTH)
            frag = field.fragment(shard)
            if frag is None:
                return R.ValCount(val=None, count=0)
            w, b = divmod(pos, 32)
            for row in (1, 0):
                if frag.row_plane(row)[w] & (np.uint32(1) << np.uint32(b)):
                    return R.ValCount(val=bool(row), count=1)
            return R.ValCount(val=None, count=0)
        if not field.options.type.is_bsi:
            raise PQLError("FieldValue requires an int-like or bool field")
        v = field.value(c)
        if v is None:
            return R.ValCount(val=None, count=0)
        return R.ValCount(val=v, count=1)

    # -- ExternalLookup (reference: executor.go executeExternalLookup — a
    #    pass-through to an operator-configured external database) -------------

    external_lookup = None  # plug point: fn(query: str, write: bool) -> Any

    def _execute_external_lookup(self, idx: Index, call: Call) -> Any:
        if self.external_lookup is None:
            raise PQLError(
                "ExternalLookup requires an external lookup backend "
                "(reference: server --lookup-db-dsn); none is configured")
        return self.external_lookup(call.arg("query"),
                                    bool(call.arg("write", False)))

    # -- Apply / Arrow (dataframe; reference: apply.go:121 executeApply,
    #    arrow.go:36 executeArrow) ---------------------------------------------

    _apply_cache: Dict[str, Any] = {}

    def _execute_apply(self, idx: Index, call: Call, shards) -> Any:
        """Apply(filter?, "expr"): the expression (dataframe/expr.py — the
        ivy replacement) compiles once to a fused kernel over shard-stacked
        columns; map + cross-shard reduce are ONE dispatch."""
        import jax as _jax

        from pilosa_tpu.dataframe.expr import compile_expr

        # the expression string may land in _ivy (reference's reserved
        # arg), in _args (after a filter child), or in _col (no filter)
        src = call.arg("_ivy") or call.arg("_args", [None])[0]
        if not isinstance(src, str):
            src = call.arg("_col")
        if not isinstance(src, str):
            raise PQLError('Apply requires an expression string argument')
        if len(call.children) > 1:
            raise PQLError("Apply() accepts a single bitmap filter")
        shard_list = self._shards(idx, shards)
        df_shards = [s for s in shard_list if s in idx.dataframe.frames]
        compiled = self._apply_cache.get(src)
        if compiled is None:
            fn, cols_used, is_red = compile_expr(src)
            compiled = self._apply_cache[src] = (
                platform.guarded_call(_jax.jit(fn)), sorted(cols_used),
                is_red)
            while len(self._apply_cache) > 64:
                self._apply_cache.pop(next(iter(self._apply_cache)))
        fn, cols_used, is_red = compiled
        if not df_shards:
            return R.ApplyResult(value=0 if is_red else [])
        cols, valid, cap = idx.dataframe.device_columns(cols_used, df_shards)
        mask = valid
        if call.children:
            plane = self._eval_all(idx, call.children[0], df_shards)
            mask = mask & self._plane_to_mask(plane, len(df_shards), cap)
        out = fn(cols, mask)

        if is_red:
            def fin_scalar(v):
                x = v.item() if hasattr(v, "item") else v
                return R.ApplyResult(value=x)
            return _Deferred([out], fin_scalar)

        def fin_vector(vec, mask_np):
            vals = vec[mask_np]
            return R.ApplyResult(value=[float(x) for x in vals])

        return _Deferred([out, mask], fin_vector)

    @staticmethod
    def _plane_to_mask(plane: jnp.ndarray, n_shards: int, cap: int
                       ) -> jnp.ndarray:
        """Expand a [S*W] bitmap plane into bool[S, cap] positions (the
        filter side of Apply/Arrow; LSB-first like ops/bitmap.py)."""
        words = plane.reshape(n_shards, WORDS_PER_SHARD)
        need_words = (cap + 31) // 32
        words = words[:, :need_words]
        shifts = jnp.arange(32, dtype=jnp.uint32)
        bits = (words[:, :, None] >> shifts) & jnp.uint32(1)
        return bits.reshape(n_shards, need_words * 32)[:, :cap] != 0

    def _execute_arrow(self, idx: Index, call: Call, shards) -> R.ArrowTable:
        """Arrow(filter?, header=[...]): raw column extraction (reference:
        arrow.go:366 executeArrowShard + header filterColumns)."""
        header = call.arg("header")
        shard_list = self._shards(idx, shards)
        df_shards = [s for s in shard_list if s in idx.dataframe.frames]
        schema = idx.dataframe.schema()
        if header:
            schema = [c for c in schema if c["name"] in set(header)]
        names = [c["name"] for c in schema]
        fields = [R.ExtractedField(name=c["name"], type=c["type"])
                  for c in schema]
        if not df_shards or not names:
            return R.ArrowTable(fields=fields, columns=[[] for _ in names])
        filt_np = None
        if call.children:
            filt_np = np.asarray(
                self._eval_all(idx, call.children[0], df_shards)
            ).reshape(len(df_shards), WORDS_PER_SHARD)
        ids: List[int] = []
        out_cols: List[List[Any]] = [[] for _ in names]
        for si, shard in enumerate(df_shards):
            frame = idx.dataframe.frames[shard]
            n = frame.length()
            present = np.zeros(n, dtype=bool)
            for name in names:
                v = frame.valid.get(name)
                if v is not None:
                    present[: v.size] |= v[:n]
            if filt_np is not None:
                fbits = np.unpackbits(
                    filt_np[si].view(np.uint8), bitorder="little")[:n]
                present &= fbits.astype(bool)
            pos = np.nonzero(present)[0]
            base = shard * SHARD_WIDTH
            ids.extend(int(base + p) for p in pos)
            for ci, name in enumerate(names):
                col = frame.columns.get(name)
                v = frame.valid.get(name)
                for p in pos:
                    if col is not None and p < col.size and v[p]:
                        x = col[p]
                        out_cols[ci].append(
                            int(x) if col.dtype.kind == "i" else float(x))
                    else:
                        out_cols[ci].append(None)
        return R.ArrowTable(fields=fields, columns=out_cols, ids=ids)

    # -- writes (reference: executor.go executeSet/Clear/Store) ----------------

    def _execute_write(self, idx: Index, call: Call, shards=None) -> Any:
        name = call.name
        if name == "Set":
            return self._execute_set(idx, call)
        if name == "Clear":
            return self._execute_clear(idx, call)
        if name == "ClearRow":
            return self._execute_clear_row(idx, call, shards)
        if name == "Store":
            return self._execute_store(idx, call, shards)
        if name == "Delete":
            return self._execute_delete(idx, call, shards)
        raise PQLError(f"write call {name!r} not implemented")

    def _execute_delete(self, idx: Index, call: Call, shards=None) -> int:
        """Delete the records selected by the child bitmap: clear their
        columns from every fragment of every field, the existence field,
        and all BSI planes (reference: executor.go:9050
        executeDeleteRecords). Returns the number of records deleted."""
        if not call.children:
            raise PQLError("Delete requires a bitmap child")
        shard_list = self._shards(idx, shards)
        if not shard_list:
            return 0
        plane = self._eval_all(idx, call.children[0], shard_list)
        if idx.existence is not None:
            # count only records that actually exist (reference:
            # executeDeleteRecords intersects the existence row)
            plane = B.plane_and(plane, self._existence_all(idx, shard_list))
        planes_np = np.asarray(plane).reshape(len(shard_list), WORDS_PER_SHARD)
        deleted = 0
        for si, shard in enumerate(shard_list):
            shard_plane = planes_np[si]
            n = int(B.plane_to_bits(shard_plane).size)
            if n == 0:
                continue
            deleted += n
            idx.delete_columns(shard, shard_plane)
        return deleted

    def _execute_set(self, idx: Index, call: Call) -> bool:
        col = call.arg("_col")
        if col is None:
            raise PQLError("Set requires a column")
        col = self._col_id(idx, col, create=True)
        fa = call.field_arg()
        if fa is None:
            raise PQLError("Set requires field=value")
        fname, value = fa
        field = idx.field(fname)
        if field.options.type.is_bsi:
            field.set_value(col, value)
            idx.add_exists(col)
            return True
        row = self._row_id(field, value, create=True)
        ts = call.arg("_timestamp")
        changed = field.set_bit(row, col,
                                timestamp=_parse_ts(ts) if ts else None)
        idx.add_exists(col)
        return changed

    def _execute_clear(self, idx: Index, call: Call) -> bool:
        col = self._col_id(idx, call.arg("_col"))
        if col is None:
            return False
        fa = call.field_arg()
        if fa is None:
            raise PQLError("Clear requires field=value")
        fname, value = fa
        field = idx.field(fname)
        if field.options.type.is_bsi:
            return field.clear_value(col)
        row = self._row_id(field, value)
        if row is None:
            return False
        return field.clear_bit(row, col)

    def _execute_clear_row(self, idx: Index, call: Call, shards=None) -> bool:
        fa = call.field_arg()
        if fa is None:
            raise PQLError("ClearRow requires field=row")
        fname, value = fa
        field = idx.field(fname)
        row = self._row_id(field, value)
        if row is None:
            return False
        if shards is None:
            return field.clear_row(row)
        changed = False
        shard_set = set(shards) & field.shards()
        for shard in sorted(shard_set):
            for view in list(field.views):
                frag = field.fragment(shard, view)
                if frag is not None and frag.has_row(row):
                    field.write_row_plane(
                        shard, row, np.zeros(frag.words, dtype=np.uint32),
                        clear=True, view=view)
                    changed = True
        return changed

    def _execute_store(self, idx: Index, call: Call, shards=None) -> bool:
        """Store(bitmap, field=row): write the result as a row (reference:
        executor.go executeSetRow)."""
        fa = call.field_arg()
        if fa is None:
            raise PQLError("Store requires field=row")
        fname, value = fa
        field = idx.field(fname)
        if field.options.type.is_bsi:
            raise PQLError("Store targets a set field row")
        row = self._row_id(field, value, create=True)
        shard_list = self._shards(idx, shards)
        if not shard_list:
            return True
        planes_np = np.asarray(
            self._eval_all(idx, call.children[0], shard_list)
        ).reshape(len(shard_list), WORDS_PER_SHARD)
        for si, shard in enumerate(shard_list):
            field.write_row_plane(shard, row, planes_np[si], clear=True)
        return True
