"""Dense bitmap-plane algebra.

A *plane* is one bitmap row of one shard: ``uint32[WORDS_PER_SHARD]`` where
bit ``b`` of word ``w`` is column ``w*32 + b`` of the shard (LSB-first).
This replaces the reference's adaptive roaring containers
(array/bitmap/RLE, reference: roaring/roaring.go:53-58) with a single dense
representation: boolean algebra becomes elementwise ``uint32`` ops that XLA
fuses and tiles onto the VPU, and popcount becomes
``lax.population_count`` + reduce instead of per-container scalar loops
(reference: roaring/roaring.go:711 IntersectionCount, :736 Intersect,
:1272 Union, :1564 Difference, :1598 Xor, :1629 Shift).

Functions here are shape-polymorphic pure jnp; hot entry points are wrapped
in ``jax.jit`` so repeated query shapes hit the executable cache (the
reference's analog is its per-call Go hot loops; ours is compile-once).
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pilosa_tpu import native, platform
from pilosa_tpu.ops import pallas_util as PU
from pilosa_tpu.shardwidth import BITS_PER_WORD, SHARD_WIDTH, WORDS_PER_SHARD

# ---------------------------------------------------------------------------
# Construction / conversion (host-side helpers, numpy)
# ---------------------------------------------------------------------------


def zero_plane(words: int = WORDS_PER_SHARD) -> np.ndarray:
    return np.zeros(words, dtype=np.uint32)


# One shared all-zeros device plane per word count, LRU-bounded. Absent
# rows, empty unions, and the resident-program scratch all read the SAME
# buffer instead of each caller growing its own per-shape dict (the
# Executor._zeros unbounded-growth fix). Callers must never mutate or
# donate it on a backend that honors donation — platform.donate_argnums
# gates that off on CPU, the only place the shared plane is passed as
# scratch.
_DEVICE_ZEROS_CAP = 8
_DEVICE_ZEROS: "dict" = {}
_DEVICE_ZEROS_LOCK = threading.Lock()


def device_zeros(words: int):
    """Shared device ``uint32[words]`` zeros plane (bounded cache)."""
    with _DEVICE_ZEROS_LOCK:
        z = _DEVICE_ZEROS.get(words)
    if z is None:
        z = jnp.zeros((words,), dtype=jnp.uint32)
        with _DEVICE_ZEROS_LOCK:
            _DEVICE_ZEROS[words] = z
            while len(_DEVICE_ZEROS) > _DEVICE_ZEROS_CAP:
                _DEVICE_ZEROS.pop(next(iter(_DEVICE_ZEROS)))
    return z


def bits_to_plane(cols, words: int = WORDS_PER_SHARD) -> np.ndarray:
    """Build a plane from column offsets (host-side, used by ingest).

    Equivalent of the reference's bulk bit-setting into containers
    (reference: roaring/roaring.go:2380 ImportRoaringBits).
    """
    plane = np.zeros(words, dtype=np.uint32)
    cols = np.asarray(cols, dtype=np.int64)
    if cols.size == 0:
        return plane
    native.scatter_bits(plane, cols)
    return plane


def plane_to_bits(plane) -> np.ndarray:
    """Column offsets set in a plane (host-side; result materialization,
    reference: roaring/roaring.go Slice/iterators)."""
    return native.plane_to_bits(np.asarray(plane, dtype="<u4"))


def shard_mask_plane(shard_list, subset, words: int = WORDS_PER_SHARD
                     ) -> np.ndarray:
    """Word-lane mask over a stacked layout: ``uint32[S*W]`` with
    0xFFFFFFFF on the words of shards in ``subset`` and 0 elsewhere.

    This is the [S] per-query 0/1 shard vector of superset fusion
    (pql/executor.py ShardMask) broadcast to word granularity — shards
    are whole multiples of WORDS_PER_SHARD in the stacked axis, so a
    shard-level mask never splits a word and ``plane & mask`` restricts
    any column-reducing kernel to exactly the subset's columns.
    """
    sel = np.fromiter((s in subset for s in shard_list), dtype=bool,
                      count=len(shard_list))
    full = np.where(sel, np.uint32(0xFFFFFFFF), np.uint32(0))
    return np.repeat(full, words).astype(np.uint32)


# ---------------------------------------------------------------------------
# Boolean algebra (device)
# ---------------------------------------------------------------------------


def plane_and(a, b):
    return jnp.bitwise_and(a, b)


def plane_or(a, b):
    return jnp.bitwise_or(a, b)


def plane_xor(a, b):
    return jnp.bitwise_xor(a, b)


def plane_andnot(a, b):
    """a AND NOT b (reference: roaring/roaring.go:1564 Difference)."""
    return jnp.bitwise_and(a, jnp.bitwise_not(b))


# Aliases matching the reference's verb names.
plane_union = plane_or
plane_difference = plane_andnot


def plane_range_mask(start, end, words: int = WORDS_PER_SHARD):
    """Plane with bits [start, end) set — used for Not/All restricted to a
    shard's column range (reference: roaring.go flipBitmap / fragment
    NotNull paths). start/end may be traced scalars."""
    word_idx = jnp.arange(words, dtype=jnp.int32)
    lo = word_idx * BITS_PER_WORD
    # Per-word count of set bits from `start` and `end` boundaries.
    start_off = jnp.clip(start - lo, 0, BITS_PER_WORD).astype(jnp.uint32)
    end_off = jnp.clip(end - lo, 0, BITS_PER_WORD).astype(jnp.uint32)
    full = jnp.uint32(0xFFFFFFFF)
    # mask of bits >= start_off within the word
    hi_mask = jnp.where(start_off >= 32, jnp.uint32(0), full << start_off)
    lo_mask = jnp.where(end_off >= 32, full, ~(full << end_off))
    return jnp.bitwise_and(hi_mask, lo_mask)


def plane_not(a, existence):
    """NOT within an index: existence ANDNOT a (reference: executor.go
    executeNot — requires the index's `_exists` row; there is no unscoped
    complement)."""
    return plane_andnot(existence, a)


@platform.guarded_call
@jax.jit
def plane_shift(a):
    """Shift all columns by +1 (reference: roaring/roaring.go:1629 Shift).

    Bit i moves to bit i+1; the top bit of each word carries into the next
    word. The bit shifted past the end of the plane is dropped (shard
    boundary, as in the reference's per-shard executeShiftShard)."""
    carry = jnp.concatenate([jnp.zeros((1,), dtype=a.dtype), a[:-1] >> 31])
    return (a << 1) | carry


# ---------------------------------------------------------------------------
# Popcount reductions (device)
# ---------------------------------------------------------------------------


def _popcount_i32(x):
    return lax.population_count(x).astype(jnp.int32)


def zeros_varying_like(ref, shape, dtype):
    """Zeros carrying the same varying-manual-axes type as ``ref`` — the
    correct scan-carry init for code that may trace inside shard_map
    (a literal-zero carry must type-match inputs traced there)."""
    z = jnp.zeros(shape, dtype=dtype)
    vma = jax.typeof(ref).vma
    return lax.pcast(z, tuple(vma), to="varying") if vma else z


def host_popcount(x: np.ndarray) -> int:
    """Host-side total popcount (native kernel; numpy fallback)."""
    return native.popcount(np.ascontiguousarray(x))


@platform.guarded_call
@jax.jit
def plane_count(a):
    """Total set bits (reference: roaring Count / fragment popcount paths).
    Max 2^20 per plane, fits int32 comfortably."""
    return jnp.sum(_popcount_i32(a))


#: lane width of the Pallas popcount reduce's 2-D view of a flat plane
_PALLAS_POP_BW = 512


def _popcount_sum_kernel(x_ref, out_ref):
    from jax.experimental import pallas as pl

    g = pl.program_id(0)
    s = jnp.sum(lax.population_count(x_ref[...]).astype(jnp.int32))

    @pl.when(g == 0)
    def _():
        out_ref[0, 0] = s

    @pl.when(g != 0)
    def _():
        out_ref[0, 0] += s


def pallas_count_eligible(total_words: int) -> bool:
    """Whether a flat plane of ``total_words`` splits into the blocks
    :func:`plane_count_pallas_traced` streams."""
    return (total_words % _PALLAS_POP_BW == 0
            and PU.block_rows(total_words // _PALLAS_POP_BW) is not None)


def plane_count_pallas_traced(plane, interpret: bool):
    """Traceable Pallas popcount-sum of a flat plane (a length
    :func:`pallas_count_eligible` accepts): the count-tape terminal used
    by ``parallel/mesh.compile_tape_count``. A 1-D grid streams
    (rows, 512) VMEM blocks — one shard's 32768 words per step at shard
    width — through the VPU popcount and accumulates into one SMEM
    scalar."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x = plane.reshape(-1, _PALLAS_POP_BW)
    rows = PU.block_rows(x.shape[0])
    out = pl.pallas_call(
        _popcount_sum_kernel,
        grid=(x.shape[0] // rows,),
        in_specs=[pl.BlockSpec((rows, _PALLAS_POP_BW), lambda g: (g, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        interpret=interpret,
    )(x)
    return out[0, 0]


@platform.guarded_call
@jax.jit
def plane_intersection_count(a, b):
    """popcount(a AND b) without materializing the AND on host (reference:
    roaring/roaring.go:711 IntersectionCount — the #1 hot op per
    BASELINE.json config 1). XLA fuses the AND into the reduce."""
    return jnp.sum(_popcount_i32(jnp.bitwise_and(a, b)))


@platform.guarded_call
@jax.jit
def row_counts(planes, filt=None):
    """Per-row popcounts of a fragment tensor ``uint32[R, W]``, optionally
    intersected with a filter plane first (reference: fragment.go:1317 top /
    rank-cache counts; feeds TopN/TopK). jit caches one executable per
    (shape, filtered-or-not)."""
    if filt is not None:
        planes = jnp.bitwise_and(planes, filt[None, :])
    return jnp.sum(_popcount_i32(planes), axis=-1)
