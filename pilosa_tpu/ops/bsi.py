"""Bit-sliced index (BSI) kernels.

Integer/decimal/timestamp values are stored as bit planes over the columns
of a shard (reference: fragment.go:62-66): plane 0 = "exists", plane 1 =
sign, planes 2.. = magnitude bits LSB-first; values are sign-magnitude
relative to a per-field base. Range predicates are bitwise compare circuits
over the planes (reference: fragment.go:963-1305 rangeOp*), Sum is a
per-plane popcount weighted by 2^k (reference: fragment.go:724), Min/Max
walk planes MSB->LSB narrowing a candidate set (reference:
fragment.go:754-857).

TPU-first design notes:
- A BSI fragment is ``uint32[2+depth, W]`` — the whole compare circuit is a
  handful of fused elementwise ops per plane; XLA keeps everything in
  registers/VMEM and the HBM traffic is one stream over the planes.
- Predicate constants are passed as *bit vectors* (host-prepared bool[depth])
  so kernels are traced once per (shape, op) and never recompile per value.
- Exact 64-bit arithmetic (sums, values) is assembled host-side from int32
  per-plane popcounts — device code stays int32 and x64-free.

Plane stack layout used throughout: ``planes[0]`` exists, ``planes[1]``
sign, ``planes[2 + k]`` magnitude bit k.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu import platform
from pilosa_tpu.ops import groupby as _gb
from pilosa_tpu.ops import pallas_util as PU
from pilosa_tpu.ops.bitmap import _popcount_i32 as _pc
from pilosa_tpu.ops.bitmap import bits_to_plane

EXISTS = 0
SIGN = 1
OFFSET = 2  # first magnitude plane (reference: fragment.go:66 bsiOffsetBit)

# Comparison ops (reference: pql/ast.go condition tokens; executor rangeOp
# dispatch fragment.go:937).
EQ, NE, LT, LE, GT, GE, BETWEEN = "eq", "ne", "lt", "le", "gt", "ge", "between"


def _any(plane):
    return jnp.sum(_pc(plane)) > 0


def value_bits(value: int, depth: int):
    """Host-side: split |value| into (bool[depth] LSB-first, overflow, neg).

    ``overflow`` means |value| >= 2^depth i.e. beyond representable
    magnitude; the compare circuits use it to short-circuit exactly like the
    reference's bit-depth clamp (fragment.go:963 rangeOp value clamping).
    """
    neg = value < 0
    mag = -value if neg else value
    bits = np.array([(mag >> k) & 1 for k in range(depth)], dtype=bool)
    overflow = (mag >> depth) != 0
    return bits, overflow, neg


def _mag_compare(mag_planes, candidates, cbits, coverflow):
    """Unsigned magnitude compare of candidate columns against constant c.

    Returns (lt, eq, gt) planes partitioning ``candidates``. Classic bit-
    sliced compare, MSB->LSB (reference: fragment.go:1035 rangeLT et al.)
    — the loop is unrolled at trace time (depth is static).
    """
    depth = mag_planes.shape[0]
    zeros = jnp.zeros_like(candidates)
    eq = candidates
    lt = zeros
    gt = zeros
    for k in range(depth - 1, -1, -1):
        pk = mag_planes[k]
        bit = cbits[k]
        lt = lt | jnp.where(bit, eq & ~pk, zeros)
        gt = gt | jnp.where(bit, zeros, eq & pk)
        eq = eq & jnp.where(bit, pk, ~pk)
    # If |c| exceeds the representable magnitude every candidate is < c.
    lt = jnp.where(coverflow, candidates, lt)
    eq = jnp.where(coverflow, zeros, eq)
    gt = jnp.where(coverflow, zeros, gt)
    return lt, eq, gt


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("op",))
def _compare_kernel(planes, op, cbits, cover, cneg, c2bits, c2over, c2neg):
    exists = planes[EXISTS]
    sign = planes[SIGN]
    mags = planes[OFFSET:]
    zeros = jnp.zeros_like(exists)
    neg_rows = exists & sign
    pos_rows = exists & ~sign

    def signed_partition(cbits, cover, cneg):
        """(lt, eq, gt) of stored values vs signed constant c."""
        # Compare magnitudes within each sign class.
        plt, peq, pgt = _mag_compare(mags, pos_rows, cbits, cover)
        nlt, neq, ngt = _mag_compare(mags, neg_rows, cbits, cover)
        # c >= 0: negatives all < c; positives by magnitude.
        lt_cpos = neg_rows | plt
        eq_cpos = peq
        gt_cpos = pgt
        # c < 0: positives all > c; negatives by *reversed* magnitude.
        lt_cneg = ngt
        eq_cneg = neq
        gt_cneg = pos_rows | nlt
        lt = jnp.where(cneg, lt_cneg, lt_cpos)
        eq = jnp.where(cneg, eq_cneg, eq_cpos)
        gt = jnp.where(cneg, gt_cneg, gt_cpos)
        return lt, eq, gt

    lt, eq, gt = signed_partition(cbits, cover, cneg)
    if op == EQ:
        return eq
    if op == NE:
        return exists & ~eq
    if op == LT:
        return lt
    if op == LE:
        return lt | eq
    if op == GT:
        return gt
    if op == GE:
        return gt | eq
    if op == BETWEEN:
        lt2, eq2, _ = signed_partition(c2bits, c2over, c2neg)
        return (gt | eq) & (lt2 | eq2)
    raise ValueError(f"unknown op {op!r}")


def _compare_pallas_body(op, depth, planes_ref, c_ref, out_ref):
    """Fused VPU compare: one VMEM-tiled pass over all planes of a word
    block. Same circuit as ``_compare_kernel``/``_mag_compare`` (the
    bit-identity oracle), but the whole MSB->LSB walk — both sign
    classes, both BETWEEN sides — runs on (1, BW) VMEM tiles with the
    predicate constants as SMEM scalars: ``c_ref[side] = [bits LSB-
    first..., overflow, neg]``."""
    exists = planes_ref[0:1, :]
    sign = planes_ref[1:2, :]
    zeros = jnp.zeros_like(exists)
    neg_rows = exists & sign
    pos_rows = exists & ~sign

    def mag_compare(cand, side):
        eq, lt, gt = cand, zeros, zeros
        for k in range(depth - 1, -1, -1):
            pk = planes_ref[OFFSET + k:OFFSET + k + 1, :]
            bit = c_ref[side, k] != 0
            lt = lt | jnp.where(bit, eq & ~pk, zeros)
            gt = gt | jnp.where(bit, zeros, eq & pk)
            eq = eq & jnp.where(bit, pk, ~pk)
        over = c_ref[side, depth] != 0
        lt = jnp.where(over, cand, lt)
        eq = jnp.where(over, zeros, eq)
        gt = jnp.where(over, zeros, gt)
        return lt, eq, gt

    def signed_partition(side):
        plt, peq, pgt = mag_compare(pos_rows, side)
        nlt, neq, ngt = mag_compare(neg_rows, side)
        cneg = c_ref[side, depth + 1] != 0
        lt = jnp.where(cneg, ngt, neg_rows | plt)
        eq = jnp.where(cneg, neq, peq)
        gt = jnp.where(cneg, pos_rows | nlt, pgt)
        return lt, eq, gt

    lt, eq, gt = signed_partition(0)
    if op == EQ:
        out = eq
    elif op == NE:
        out = exists & ~eq
    elif op == LT:
        out = lt
    elif op == LE:
        out = lt | eq
    elif op == GT:
        out = gt
    elif op == GE:
        out = gt | eq
    elif op == BETWEEN:
        lt2, eq2, _ = signed_partition(1)
        out = (gt | eq) & (lt2 | eq2)
    else:
        raise ValueError(f"unknown op {op!r}")
    out_ref[...] = out


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def _compare_pallas(planes, cvec, op, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    depth = planes.shape[0] - OFFSET
    nrows, w = planes.shape
    bw = _gb._PALLAS_BW
    pad_w = (-w) % bw
    if pad_w:  # zero words carry no exists bits -> compare to zero there
        planes = jnp.pad(planes, ((0, 0), (0, pad_w)))
    rp = -(-nrows // 8) * 8  # sublane-pad the plane axis
    if rp != nrows:
        planes = jnp.pad(planes, ((0, rp - nrows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_compare_pallas_body, op, depth),
        grid=(planes.shape[1] // bw,),
        in_specs=[
            pl.BlockSpec((rp, bw), lambda g: (0, g)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, bw), lambda g: (0, g)),
        out_shape=jax.ShapeDtypeStruct((1, planes.shape[1]), planes.dtype),
        interpret=interpret,
    )(planes, cvec)
    return out[0, :w]


def bsi_compare(planes, op: str, value: int, value2: int | None = None):
    """Filter columns of a BSI plane stack by a signed predicate.

    ``value``/``value2`` are *stored-space* values (caller subtracts the
    field base first, as the reference does in field.go value ranges).
    Returns a plane of matching columns. Dispatch: eligible concrete
    stacks take the fused Pallas VPU walk; the per-plane XLA circuit is
    the classic path and bit-identity oracle.
    """
    depth = planes.shape[0] - OFFSET
    cbits, cover, cneg = value_bits(int(value), depth)
    if value2 is None:
        c2bits, c2over, c2neg = cbits, cover, cneg
    else:
        c2bits, c2over, c2neg = value_bits(int(value2), depth)
    why = PU.why_not("bsi_compare", planes)
    if why is None:
        cvec = np.zeros((2, depth + 2), dtype=np.int32)
        cvec[0, :depth], cvec[0, depth], cvec[0, depth + 1] = \
            cbits, cover, cneg
        cvec[1, :depth], cvec[1, depth], cvec[1, depth + 1] = \
            c2bits, c2over, c2neg
        try:
            sides = 2 if op == BETWEEN else 1
            with PU.kernel_scope("cmp", depth, sides, OFFSET + depth,
                                 planes.shape[-1]):
                out = _compare_pallas(planes, jnp.asarray(cvec), op,
                                      PU.use_interpret())
            PU.dispatched("bsi_compare")
            return out
        except Exception as e:
            PU.failed("bsi_compare", e)
    else:
        PU.fallback("bsi_compare", why)
    return _compare_kernel(
        planes, op,
        jnp.asarray(cbits), jnp.asarray(cover), jnp.asarray(cneg),
        jnp.asarray(c2bits), jnp.asarray(c2over), jnp.asarray(c2neg),
    )


# ---------------------------------------------------------------------------
# Host-side encode (ingest path)
# ---------------------------------------------------------------------------


def bits_needed(value: int) -> int:
    """Magnitude bit-depth needed to store |value| (reference:
    roaring bitDepth calc in fragment.go importValue)."""
    mag = abs(int(value))
    return max(1, mag.bit_length())


def encode_values(cols, values, depth: int, words: int) -> np.ndarray:
    """Host-side: build a BSI plane stack ``uint32[2+depth, words]`` from
    (column offset, stored value) pairs — the ingest-time analog of the
    reference's importValue (fragment.go:1947) writing exists/sign/magnitude
    rows. Vectorized numpy; later columns win on duplicates is NOT handled
    (callers dedupe, as the reference's batcher does)."""
    cols = np.asarray(cols, dtype=np.int64)
    values = np.asarray(values, dtype=np.int64)
    mags = np.abs(values)
    if values.size and int(mags.max()) >> depth != 0:
        # The reference grows bitDepth on import (fragment.go importValue);
        # callers here must re-encode at a wider depth — never truncate.
        raise ValueError(
            f"value magnitude {int(mags.max())} exceeds bit depth {depth}"
        )
    planes = np.zeros((OFFSET + depth, words), dtype=np.uint32)
    planes[EXISTS] = bits_to_plane(cols, words)
    planes[SIGN] = bits_to_plane(cols[values < 0], words)
    for k in range(depth):
        sel = (mags >> k) & 1 == 1
        if sel.any():
            planes[OFFSET + k] = bits_to_plane(cols[sel], words)
    return planes


def mask_filter(filt, mask_plane):
    """Combine an optional row-filter plane with an optional shard-
    subset mask plane (superset fusion, pql/executor.py ShardMask).

    Every aggregate/rank kernel here and in ops/bitmap.py takes a
    ``filt`` plane it ANDs against candidates first, so a per-query
    shard mask threads through the existing L0 signatures as
    ``filt & mask`` — no kernel recompiles, no new tracing axes. With
    no filter the mask IS the filter (restricting exists/candidates to
    the subset's columns); with no mask the filter passes unchanged.
    """
    if mask_plane is None:
        return filt
    if filt is None:
        return mask_plane
    return jnp.bitwise_and(filt, mask_plane)


# ---------------------------------------------------------------------------
# Aggregates
# ---------------------------------------------------------------------------


@platform.guarded_call
@jax.jit
def _plane_popcounts_xla(planes, filt):
    """Classic per-plane popcount reduction (bit-identity oracle)."""
    exists = planes[EXISTS]
    sign = planes[SIGN]
    mags = planes[OFFSET:]
    rows = exists & filt
    pos = rows & ~sign
    neg = rows & sign
    count = jnp.sum(_pc(rows))
    pos_counts = jnp.sum(_pc(mags & pos[None, :]), axis=-1)
    neg_counts = jnp.sum(_pc(mags & neg[None, :]), axis=-1)
    return count, pos_counts, neg_counts


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("interpret",))
def _plane_popcounts_pallas(planes, filt, interpret):
    """Pair-count formulation: every per-plane popcount is one entry of
    popcount(A_i & B_j) (ops/groupby._pair_counts_traced; two rows take
    its VPU body) — A = the two sign classes, B = the magnitude planes
    plus an all-ones plane whose column recovers the filtered count (pos
    and neg are disjoint, so their popcounts add)."""
    exists = planes[EXISTS]
    sign = planes[SIGN]
    mags = planes[OFFSET:]
    rows = exists & filt
    a = jnp.stack([rows & ~sign, rows & sign])
    ones = jnp.full(filt.shape, 0xFFFFFFFF, dtype=planes.dtype)
    b = jnp.concatenate([mags, ones[None, :]], axis=0)
    c = _gb._pair_counts_traced(a, b, interpret)
    return c[0, -1] + c[1, -1], c[0, :-1], c[1, :-1]


def bsi_plane_popcounts(planes, filt):
    """Per-magnitude-plane popcounts split by sign, plus the filtered count.

    Device returns int32s only; the host assembles the exact 64-bit sum
    ``sum = Σ pos[k]<<k − Σ neg[k]<<k`` with Python ints (reference:
    fragment.go:724 sum — same plane-popcount algorithm, scalar Go loop).
    Returns (count, pos_counts[depth], neg_counts[depth]). Dispatch:
    eligible concrete stacks take the Pallas pair-count kernel; the
    per-plane XLA reduction is the oracle fallback.
    """
    why = PU.why_not("bsi_sum", planes)
    if why is None and isinstance(filt, jax.core.Tracer):
        why = "tracer"
    if why is None:
        try:
            depth = planes.shape[0] - OFFSET
            with PU.kernel_scope("mm", 2, depth + 1, OFFSET + depth,
                                 planes.shape[-1]):
                out = _plane_popcounts_pallas(planes, filt,
                                              PU.use_interpret())
            PU.dispatched("bsi_sum", body=_gb.pallas_body(2, depth + 1))
            return out
        except Exception as e:
            PU.failed("bsi_sum", e)
    else:
        PU.fallback("bsi_sum", why)
    return _plane_popcounts_xla(planes, filt)


def bsi_sum(planes, filt):
    """Exact (sum, count) of stored values over filtered columns."""
    count, pos_counts, neg_counts = bsi_plane_popcounts(planes, filt)
    pos_counts = np.asarray(pos_counts, dtype=np.int64)
    neg_counts = np.asarray(neg_counts, dtype=np.int64)
    total = 0
    for k in range(pos_counts.shape[0]):
        total += (int(pos_counts[k]) - int(neg_counts[k])) << k
    return total, int(count)


def _walk_max_mag(S, mags):
    """Narrow candidate set to columns with maximal magnitude; returns
    (bits MSB-walk decisions as bool[depth] LSB-first, final set)."""
    depth = mags.shape[0]
    bits = [None] * depth
    for k in range(depth - 1, -1, -1):
        t = S & mags[k]
        ne = _any(t)
        S = jnp.where(ne, t, S)
        bits[k] = ne
    return jnp.stack(bits), S


def _walk_min_mag(S, mags):
    """Narrow candidate set to columns with minimal magnitude."""
    depth = mags.shape[0]
    bits = [None] * depth
    for k in range(depth - 1, -1, -1):
        t = S & ~mags[k]
        ne = _any(t)
        S = jnp.where(ne, t, S)
        bits[k] = ~ne  # no candidate with bit clear => all remaining have it set
    return jnp.stack(bits), S


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("want_max",))
def _minmax_kernel(planes, filt, want_max):
    exists = planes[EXISTS]
    sign = planes[SIGN]
    mags = planes[OFFSET:]
    rows = exists & filt
    neg = rows & sign
    pos = rows & ~sign
    has_neg = _any(neg)
    has_pos = _any(pos)
    if want_max:
        # max: largest positive if any, else least-magnitude negative.
        pbits, pS = _walk_max_mag(pos, mags)
        nbits, nS = _walk_min_mag(neg, mags)
        bits = jnp.where(has_pos, pbits, nbits)
        final = jnp.where(has_pos, pS, nS)
        negative = ~has_pos
    else:
        # min: largest-magnitude negative if any, else smallest positive.
        nbits, nS = _walk_max_mag(neg, mags)
        pbits, pS = _walk_min_mag(pos, mags)
        bits = jnp.where(has_neg, nbits, pbits)
        final = jnp.where(has_neg, nS, pS)
        negative = has_neg
    cnt = jnp.sum(_pc(final))
    total = jnp.sum(_pc(rows))
    return bits, negative, cnt, total


def _assemble(bits, negative) -> int:
    v = 0
    b = np.asarray(bits)
    for k in range(b.shape[0]):
        if b[k]:
            v |= 1 << k
    return -v if negative else v


@platform.guarded_call
@jax.jit
def _kth_kernel(planes, filt, nth_times_100):
    """Select the value at percentile ``nth`` (0..100, scaled x100 as an
    int32 to stay float-free) of the filtered columns — entirely on device.

    The reference binary-searches count(<=v) over the value range with one
    query per probe (executor.go:1310 executePercentile) — ~40 host-device
    round trips here. Instead the MSB->LSB bit descent picks each
    result bit with two popcounts, all fused into one dispatch:

    ascending order = negatives by descending magnitude, then positives by
    ascending magnitude; rank r = max(1, ceil(nth/100 * total)). If
    r <= #neg we want the r-th largest magnitude among the negatives
    (rank 1 = most negative), else the (r - #neg)-th smallest magnitude
    among the positives.

    Returns (bits bool[depth] LSB-first, negative, count_of_value, total).
    """
    exists = planes[EXISTS] & filt
    sign = planes[SIGN]
    mags = planes[OFFSET:]
    depth = mags.shape[0]
    neg = exists & sign
    pos = exists & ~sign
    neg_n = jnp.sum(_pc(neg))
    total = neg_n + jnp.sum(_pc(pos))
    # ceil(nth/100 * total) in int32 without overflow: split total into
    # q*10000 + rem so every intermediate stays < max(total, 10^8)
    # (nth_x100 * total directly would wrap int32 past ~215k values).
    q, rem = total // 10000, total % 10000
    rank = nth_times_100 * q + (nth_times_100 * rem + 9999) // 10000
    rank = jnp.clip(rank, 1, total)
    is_neg = rank <= neg_n
    S = jnp.where(is_neg, neg, pos)
    # within-class rank, counted from the large-magnitude end for negatives
    # and the small-magnitude end for positives
    k = jnp.where(is_neg, rank, rank - neg_n)
    bits = []
    for d in range(depth - 1, -1, -1):
        hi = S & mags[d]
        lo = S & ~mags[d]
        c_hi = jnp.sum(_pc(hi))
        c_lo = jnp.sum(_pc(lo))
        # negatives walk large->small (take the bit=1 side first);
        # positives walk small->large (take the bit=0 side first).
        take_hi = jnp.where(is_neg, c_hi >= k, c_lo < k)
        k = jnp.where(take_hi, jnp.where(is_neg, k, k - c_lo),
                      jnp.where(is_neg, k - c_hi, k))
        S = jnp.where(take_hi, hi, lo)
        bits.append(take_hi)
    bits.reverse()
    return jnp.stack(bits), is_neg, jnp.sum(_pc(S)), total


def bsi_min(planes, filt):
    """(min stored value, count achieving it, total filtered count).
    Reference: fragment.go:754 minUnsigned/min."""
    bits, negative, cnt, total = _minmax_kernel(planes, filt, False)
    if int(total) == 0:
        return 0, 0, 0
    return _assemble(bits, bool(negative)), int(cnt), int(total)


def bsi_max(planes, filt):
    """(max stored value, count achieving it, total filtered count).
    Reference: fragment.go:817 maxUnsigned/max."""
    bits, negative, cnt, total = _minmax_kernel(planes, filt, True)
    if int(total) == 0:
        return 0, 0, 0
    return _assemble(bits, bool(negative)), int(cnt), int(total)
