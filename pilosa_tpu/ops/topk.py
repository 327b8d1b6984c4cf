"""Top-N / Top-K over row counts.

The reference maintains per-fragment rank caches and merges per-fragment
count heaps (reference: cache.go:130 rankCache, executor.go:2535
topKFragments / :2586 mergerator). On TPU we skip caches entirely
(SURVEY.md §7 design mapping): counting every row is one fused
popcount-reduce over the fragment tensor and ``jax.lax.top_k`` ranks on
device — recounting is cheaper than cache maintenance.

Pallas path: the per-row masked popcount is one row of the groupby
pair count — A = the filter plane (or all-ones), B = the row planes —
so TopN rides the same kernel (a one-row first operand takes its VPU
body, ops/groupby.pallas_body), then ranks the resulting count vector on
device. The fused XLA reduction stays as the bit-identity oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from pilosa_tpu import platform
from pilosa_tpu.ops import groupby as _gb
from pilosa_tpu.ops import pallas_util as PU
from pilosa_tpu.ops.bitmap import row_counts as _row_counts_xla


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("k",))
def _topk_kernel(planes, filt, k):
    return lax.top_k(_row_counts_xla(planes, filt), k)


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("interpret",))
def _row_counts_pallas(planes, filt, interpret):
    return _gb._pair_counts_traced(filt[None, :], planes, interpret)[0]


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("k",))
def _rank_kernel(counts, k):
    return lax.top_k(counts, k)


def _pallas_counts(planes, filt):
    """Pallas per-row masked popcounts, or None when ineligible / the
    kernel failed (outcome counted on the ops_pallas_* metrics)."""
    why = PU.why_not("topn", planes)
    if why is None and isinstance(filt, jax.core.Tracer):
        why = "tracer"
    if why is None:
        f = filt if filt is not None else jnp.full(
            planes.shape[-1:], 0xFFFFFFFF, dtype=planes.dtype)
        try:
            with PU.kernel_scope("mm", 1, planes.shape[0], 2,
                                 planes.shape[-1]):
                counts = _row_counts_pallas(planes, f, PU.use_interpret())
            PU.dispatched("topn",
                          body=_gb.pallas_body(1, planes.shape[0]))
            return counts
        except Exception as e:
            PU.failed("topn", e)
    else:
        PU.fallback("topn", why)
    return None


def row_counts(planes, filt=None):
    """Dispatching per-row popcount of a fragment tensor ``uint32[R, W]``
    (optionally masked by ``filt``): the Pallas pair count when
    eligible, the fused XLA reduction otherwise."""
    counts = _pallas_counts(planes, filt)
    if counts is not None:
        return counts
    return _row_counts_xla(planes, filt)


def top_rows(planes, k: int, filt=None):
    """(counts, plane_indices) of the k highest-count rows of a fragment
    tensor ``uint32[R, W]``; caller maps plane indices back to row IDs and
    merges across shards (reference: executor.go:2357 executeTopK reduce).
    """
    k = min(int(k), planes.shape[0])
    counts = _pallas_counts(planes, filt)
    if counts is not None:
        return _rank_kernel(counts, k)
    return _topk_kernel(planes, filt, k)
