"""GroupBy pair-count kernels — MXU matmul over bit planes.

The reference's GroupBy walks nested row iterators per shard and popcounts
each intersection one pair at a time (reference: executor.go:3918
executeGroupByShard, :3176 groupByIterator). The TPU-native formulation:
the matrix of intersection counts between two row sets

    C[i, j] = popcount(A_i AND B_j)

is exactly a matmul over {0,1} bit lanes: expand each uint32 word into 32
int8 lanes and contract over the 2^20-column axis on the MXU with int32
accumulation — exact for any count, and the v5e MXU runs int8 at 2x bf16
rate (measured ~18% faster end-to-end; the expansion, not the matmul,
bounds this kernel). This turns the reference's scalar hot loop into the
systolic array's native op — the core of BASELINE.json config 3
(TopK+GroupBy on SSB) and the north-star GroupBy speedup.

Column blocking keeps the int8 expansion in VMEM-sized chunks instead of
materializing ``rows x 2^20`` lanes in HBM.

Two forms of the one matmul: a fused Pallas kernel that expands in VMEM
(:func:`_pair_counts_traced`) and an XLA scan that any backend and any
sharding takes (:func:`_pair_counts_xla`). The kernel has two placements:
one chip's program, and the mesh program that runs it on every chip over
the words that chip holds and sums the counts with one small ``psum``
(``parallel/mesh.psum_over_words``; the XLA scan, being a loop over the
sharded axis, would have both operands gathered whole onto every chip).
:func:`pair_counts` and :func:`pair_sums` pick from what
``pallas_util.why_not`` and ``parallel/mesh.engine_placed`` see in their
concrete operands: backend, sharding, rows. No option chooses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from pilosa_tpu import platform
from pilosa_tpu.ops import pallas_util as PU
from pilosa_tpu.ops.bitmap import zeros_varying_like

# Words per column-block of the matmul: 2048 words = 65536 bit-columns
# -> int8 chunk of [R, 65536] = 64KiB per row, MXU-friendly.
BLOCK_WORDS = 2048

# Pallas kernel tile sizes (VMEM-bounded; swept on v5e: BW=512/TR2=256
# beat 1024/256, 512/512, 256/512): per step the expanded int8 lanes are
# [R1p, 16384] + [256, 16384] = a few MB of VMEM.
_PALLAS_BW = 512
_PALLAS_TR2 = 256
_PALLAS_MAX_R1 = 128  # larger outer sides would blow VMEM; swap or scan
#: the most rows the Pallas route of :func:`pair_counts` takes as its
#: first operand: a caller that blocks its group planes blocks them to this
PAIR_COUNTS_MAX_ROWS = _PALLAS_MAX_R1


def _expand_bits_i8(words):
    """uint32[..., Wc] -> int8[..., Wc*32] of 0/1 lanes (LSB-first)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], words.shape[-1] * 32).astype(jnp.int8)


def pair_counts(a, b, block_words: int = BLOCK_WORDS):
    """int32[R1, R2] of pairwise intersection popcounts of two row sets
    ``uint32[R1, W]`` x ``uint32[R2, W]``.

    Used by GroupBy (rows of field1 x rows of field2) and by grouped
    aggregates (group bitmaps x BSI magnitude planes).

    Dispatch: concrete arrays on a TPU backend (or anywhere under
    ``PILOSA_TPU_PALLAS=1``, via the interpreter) take the fused Pallas
    expand+matmul kernel (the expansion stays in VMEM instead of
    staging int8 lanes through HBM): on one chip as one program, and,
    when both operands are sharded as the engine mesh places a stack
    (:func:`_mesh_route`), as the mesh program that counts on every chip
    over its own words and ``psum``s the ``int32[R1, R2]``. Traced
    values (inside jit/shard_map, e.g. ``mesh._groupby_counts``),
    operands sharded any other way, more than ``_PALLAS_MAX_R1`` rows
    and other backends take the XLA scan: a jitted program that wants
    the kernel chooses its route where its operands are still concrete
    and calls :func:`_pair_counts_traced` itself (:func:`pair_sums`,
    ops/bsi.py, ops/topk.py). Outcomes are counted on the
    ``ops_pallas_*`` metrics (ops/pallas_util.py)."""
    why, mesh = _pair_counts_plan(a, b)
    if why is None or mesh is not None:
        try:
            with PU.kernel_scope("mm", a.shape[0], b.shape[0], 2,
                                 a.shape[1]):
                out = (_pair_counts_pallas(a, b) if mesh is None
                       else _pair_counts_mesh(a, b, mesh=mesh))
            PU.dispatched("pair_counts", on_mesh=mesh is not None)
            return out
        except Exception as e:
            PU.failed("pair_counts", e)
    else:
        PU.fallback("pair_counts", why)
    return _pair_counts_xla(a, b, block_words)


def _pair_counts_plan(a, b):
    """``(why, mesh)``: ``why_not``'s answer for these operands and the
    engine mesh when its ``"mesh"`` becomes the mesh route."""
    why = PU.why_not("pair_counts", a, b, max_rows=_PALLAS_MAX_R1)
    return why, _mesh_route(why, a, b)


def pair_counts_route(a, b) -> str:
    """Which program :func:`pair_counts` runs for these operands:
    ``"pallas"`` (the kernel on one chip), ``"mesh"`` (the kernel on
    every chip over its own words) or ``"xla"`` (the scan). Runs and
    counts nothing: for a span's tag."""
    why, mesh = _pair_counts_plan(a, b)
    if why is None:
        return "pallas"
    return "xla" if mesh is None else "mesh"


def _mesh_route(why, *operands):
    """The engine mesh when a call that ``why_not`` refused takes the
    mesh route of the Pallas kernel, else None: the refusal is
    ``"mesh"`` (a compiled ``pallas_call`` cannot be partitioned, and
    that is all that is wrong), the first operand fits the kernel's row
    limit (``why_not`` answers ``"mesh"`` before it looks at shapes) and
    every array operand is placed as the engine places a stack, so a
    ``shard_map`` over that mesh finds each chip's words where they
    already are."""
    if why != "mesh" or operands[0].shape[0] > _PALLAS_MAX_R1:
        return None
    # lazy: parallel/mesh.py imports this module
    from pilosa_tpu.parallel import mesh as PM

    return PM.engine_mesh() if PM.engine_placed(*operands) else None


def _expand_bitmajor(x):
    """uint32[R, BW] -> int8[R, 32*BW] of 0/1 lanes in BIT-MAJOR order
    (block k holds bit k of every word). Any consistent permutation of
    the contraction axis yields the same dot product, and 2D shifts +
    concat vectorize on the VPU where a 3D->2D lane reshape does not
    (Mosaic rejects it)."""
    return jnp.concatenate(
        [((x >> k) & 1).astype(jnp.int8) for k in range(32)], axis=1)


def _pallas_kernel(a_ref, b_ref, out_ref):
    from jax.experimental import pallas as pl

    w = pl.program_id(1)  # innermost: contiguous revisits of the out
    # block, the accumulation-safe grid order on TPU
    blk = jax.lax.dot_general(
        _expand_bitmajor(a_ref[:, :]), _expand_bitmajor(b_ref[:, :]),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)

    @pl.when(w == 0)
    def _():
        out_ref[:, :] = blk

    @pl.when(w != 0)
    def _():
        out_ref[:, :] += blk


def _pair_counts_traced(a, b, interpret: bool):
    """Traceable core of the fused bit-expansion + int8 MXU matmul: the
    expansion lives in VMEM per (512-word x 256-row) tile, so HBM sees
    only the packed uint32 planes (measured 5.6 ms vs 10.7 ms XLA for
    the SSB config-3 contraction on v5e). Shared by bsi_plane_popcounts
    (magnitude-plane popcounts) and TopN row counts — any "popcount of
    pairwise ANDs" is this one matmul."""
    from jax.experimental import pallas as pl

    r1, w_total = a.shape
    r2, _ = b.shape
    pad_w = (-w_total) % _PALLAS_BW
    if pad_w:
        a = jnp.pad(a, ((0, 0), (0, pad_w)))
        b = jnp.pad(b, ((0, 0), (0, pad_w)))
    r1p = max(8, -(-r1 // 8) * 8)  # sublane multiple, not just >= 8
    if r1p != r1:
        a = jnp.pad(a, ((0, r1p - r1), (0, 0)))
    # a second operand narrower than one row tile is one tile of its own
    # (sublane-rounded) height: padded to _PALLAS_TR2, an 8-row block of
    # 66-shard rows (8.65 MB each) would be copied out as 2.2 GB and read
    # back as such
    tr2 = min(_PALLAS_TR2, -(-r2 // 8) * 8)
    r2p = -(-r2 // tr2) * tr2
    if r2p != r2:
        b = jnp.pad(b, ((0, r2p - r2), (0, 0)))
    out = pl.pallas_call(
        _pallas_kernel,
        grid=(r2p // tr2, a.shape[1] // _PALLAS_BW),
        in_specs=[
            pl.BlockSpec((r1p, _PALLAS_BW), lambda t, w: (0, w)),
            pl.BlockSpec((tr2, _PALLAS_BW), lambda t, w: (t, w)),
        ],
        out_specs=pl.BlockSpec((r1p, tr2), lambda t, w: (0, t)),
        out_shape=jax.ShapeDtypeStruct((r1p, r2p), jnp.int32),
        interpret=interpret,
    )(a, b)
    return out[:r1, :r2]


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("interpret",))
def _pair_counts_pallas(a, b, interpret=None):
    if interpret is None:  # static: resolved once per trace
        interpret = PU.use_interpret()
    return _pair_counts_traced(a, b, interpret)


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("mesh", "interpret"))
def _pair_counts_mesh(a, b, mesh, interpret=None):
    """The kernel where the words live: each chip of ``mesh`` counts the
    pairs over its own slice of the word axis (a local width that is no
    multiple of ``_PALLAS_BW`` is padded there) and the partial
    ``int32[R1, R2]`` are summed over the mesh — exact, like the counts
    themselves, up to S * 2^20."""
    from pilosa_tpu.parallel.mesh import psum_over_words

    if interpret is None:  # static: resolved once per trace
        interpret = PU.use_interpret()
    return psum_over_words(
        functools.partial(_pair_counts_traced, interpret=interpret),
        mesh, a, b)


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("block_words",))
def _pair_counts_xla(a, b, block_words: int = BLOCK_WORDS):
    """The XLA scan formulation (shard_map-compatible; all backends)."""
    r1, w = a.shape
    r2, _ = b.shape
    bw = min(block_words, w)
    # Pad W to a multiple of the block (zero words contribute nothing).
    pad = (-w) % bw
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, pad)))
    nblocks = a.shape[1] // bw
    a_blocks = a.reshape(r1, nblocks, bw).transpose(1, 0, 2)
    b_blocks = b.reshape(r2, nblocks, bw).transpose(1, 0, 2)

    def step(acc, ab):
        a_w, b_w = ab
        a_bits = _expand_bits_i8(a_w)  # [R1, bw*32]
        b_bits = _expand_bits_i8(b_w)  # [R2, bw*32]
        # int8 x int8 -> int32 accumulation is exact for any count (no
        # f32-mantissa block-size constraint); shards are concatenated
        # along W so multi-shard counts reach S * 2^20 (core/stacked.py).
        block = jax.lax.dot_general(
            a_bits,
            b_bits,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return acc + block, None

    # Inside shard_map the inputs carry varying-manual-axes type; the scan
    # carry must match or tracing rejects it.
    acc0 = zeros_varying_like(a, (r1, r2), jnp.int32)
    acc, _ = lax.scan(step, acc0, (a_blocks, b_blocks))
    return acc


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("gn", "rn"))
def group_planes(planes, rows, g0, r0, gn: int, rn: int):
    """``uint32[gn * rn, W]``: every AND of one of the ``gn`` planes from
    ``planes[g0]`` on with one of the ``rn`` rows from ``rows[r0]`` on,
    row-major (the planes of one group with all its rows side by side) —
    the group planes a GroupBy of three or more fields counts against the
    next field's rows, made a block at a time. Starts are dynamic, sizes
    static: one program per block shape, whatever the block's place."""
    g = lax.dynamic_slice_in_dim(planes, g0, gn)
    r = lax.dynamic_slice_in_dim(rows, r0, rn)
    return (g[:, None, :] & r[None, :, :]).reshape(gn * rn, planes.shape[1])


@platform.guarded_call
@jax.jit
def masked_pair_counts(a, b, filt):
    """pair_counts with both sides pre-intersected by a filter plane
    (reference: GroupBy's optional filter argument, executor.go:3277)."""
    return pair_counts(a & filt[None, :], b & filt[None, :])


def pair_sums(a, b, mags, pos, neg):
    """Per-magnitude-plane pair counts for two-field GroupBy with a Sum
    aggregate: three-way popcounts as matmuls,

        pos_k[i, j] = popcount(A_i & B_j & M_k & pos)

    The host assembles the exact per-group sum
    ``sum_k 2^k (pos_k - neg_k)`` with Python ints (reference walks group
    bitmaps one at a time through fragment.sum, executor.go:3176 +
    fragment.go:724).

    Dispatch as :func:`pair_counts`, decided here on the concrete
    operands: one scan over the planes either way, each step a fused
    Pallas pair count (:func:`_pair_sums_pallas`, or per chip with the
    two outputs ``psum``med, :func:`_pair_sums_mesh`, when all five
    operands are placed as the engine mesh places a stack) or two XLA
    ones (:func:`_pair_sums_xla`).

    Returns (pos int32[D, R1, R2], neg int32[D, R1, R2]).
    """
    why = PU.why_not("pair_sums", a, b, mags, max_rows=_PALLAS_MAX_R1)
    mesh = _mesh_route(why, a, b, mags, pos, neg)
    if why is None or mesh is not None:
        try:
            with PU.kernel_scope("mm", 2 * mags.shape[0] * a.shape[0],
                                 b.shape[0], 5, a.shape[1]):
                out = (_pair_sums_pallas(a, b, mags, pos, neg)
                       if mesh is None else
                       _pair_sums_mesh(a, b, mags, pos, neg, mesh=mesh))
            PU.dispatched("pair_sums", on_mesh=mesh is not None)
            return out
        except Exception as e:
            PU.failed("pair_sums", e)
    else:
        PU.fallback("pair_sums", why)
    return _pair_sums_xla(a, b, mags, pos, neg)


@platform.guarded_call
@jax.jit
def _pair_sums_xla(a, b, mags, pos, neg):
    """The XLA route of :func:`pair_sums` (partitionable; all backends):
    popcount(P & Q) = sum_c P[c]*Q[c] with P = A_i & sign, Q = B_j & M_k,
    two pair counts a plane, which see tracers and take the XLA scan."""
    ap = a & pos[None, :]
    an = a & neg[None, :]

    def step(_, mk):
        bm = b & mk[None, :]
        return None, (pair_counts(ap, bm), pair_counts(an, bm))

    _, (p, n) = lax.scan(step, None, mags)
    return p, n


def _pair_sums_traced(a, b, mags, pos, neg, interpret: bool):
    """Traceable body of the Pallas route of :func:`pair_sums`: the
    plane's mask goes on the small side, P = A_i & sign & M_k, so ``b``
    reaches the kernel as it is and no ``b & M_k`` is written and read
    back a step; both signs stack into one first operand, so the kernel
    expands ``b`` once a plane, not twice (two calls a step where the
    stack would pass the kernel's row limit)."""
    r1 = a.shape[0]
    firsts = [a & pos[None, :], a & neg[None, :]]
    if 2 * r1 <= _PALLAS_MAX_R1:
        firsts = [jnp.concatenate(firsts)]

    def step(_, mk):
        c = jnp.concatenate([_pair_counts_traced(x & mk[None, :], b, interpret)
                             for x in firsts])
        return None, (c[:r1], c[r1:])

    _, (p, n) = lax.scan(step, None, mags)
    return p, n


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("interpret",))
def _pair_sums_pallas(a, b, mags, pos, neg, interpret=None):
    """The Pallas route of :func:`pair_sums` on one chip."""
    if interpret is None:  # static: resolved once per trace
        interpret = PU.use_interpret()
    return _pair_sums_traced(a, b, mags, pos, neg, interpret)


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("mesh", "interpret"))
def _pair_sums_mesh(a, b, mags, pos, neg, mesh, interpret=None):
    """The Pallas route of :func:`pair_sums` where the words live: the
    same scan on every chip of ``mesh`` over its own words of all five
    operands, the two ``int32[D, R1, R2]`` summed over the mesh."""
    from pilosa_tpu.parallel.mesh import psum_over_words

    if interpret is None:  # static: resolved once per trace
        interpret = PU.use_interpret()
    return psum_over_words(
        functools.partial(_pair_sums_traced, interpret=interpret),
        mesh, a, b, mags, pos, neg)
