"""GroupBy pair-count kernels — popcounts of pairwise ANDs over bit planes.

The reference's GroupBy walks nested row iterators per shard and popcounts
each intersection one pair at a time (reference: executor.go:3918
executeGroupByShard, :3176 groupByIterator). The TPU-native formulation
counts the whole matrix of intersections between two row sets at once,

    C[i, j] = popcount(A_i AND B_j)

in int32, exact for any count up to S * 2^20. This is the core of
BASELINE.json config 3 (TopK+GroupBy on SSB) and the north-star GroupBy
speedup.

Two forms: a Pallas kernel that works a word block at a time in VMEM, so
that HBM sees only the packed uint32 planes (:func:`_pair_counts_traced`),
and an XLA scan that any backend and any sharding takes
(:func:`_pair_counts_xla`: each uint32 word expanded into 32 int8 lanes
and contracted as a matmul, a column block at a time).

The kernel has two bodies, one algorithm with two instruction mixes, and
picks between them from its operands' heights alone (:func:`pallas_body`):

* ``vpu`` — AND every row of the shorter operand with the rows of the
  taller, ``population_count``, add into lane-wise accumulators: 3
  vector operations per pair and 128 words, nothing expanded. The
  cheaper mix while r1 * r2 / (r1 + r2) is small: every shape a served
  GroupBy, its Sum and TopN send. Written as loops around one short
  listing, so that its program costs the server no more to build than
  the MXU body's (a listing is traced, lowered and serialized once a
  shape and a process, before the compile cache can be asked: PERF.md
  section 5, "Set-up of the SSB cells").
* ``mxu`` — expand both operands to int8 0/1 lanes (32 shift/mask/narrow
  passes over every row) and contract on the systolic array: its cost
  goes with r1 + r2, so it keeps the operands with two tall sides.

On a v5e the MXU body is bound by its instruction schedule, not by HBM,
whatever the heights; the VPU body runs the narrow shapes within a sixth
of HBM's floor (PERF.md §5 has the sweep). The kernel has two
placements: one chip's program, and the mesh program that runs it on
every chip over the words that chip holds and sums the counts with one
small ``psum``
(``parallel/mesh.psum_over_words``; the XLA scan, being a loop over the
sharded axis, would have both operands gathered whole onto every chip).
:func:`pair_counts` and :func:`pair_sums` pick the form and the placement
from what ``pallas_util.why_not`` and ``parallel/mesh.engine_placed`` see
in their concrete operands: backend, sharding, rows. No option chooses.
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

# Tile sizes of the kernel's MXU body (VMEM-bounded; swept on v5e:
# BW=512/TR2=256 beat 1024/256, 512/512, 256/512: its schedule grows
# faster than its word block): per step the expanded int8 lanes are
# [R1p, 16384] + [256, 16384] = a few MB of VMEM. Both bodies pad the
# word axis to _PALLAS_BW and tile the second operand by _PALLAS_TR2.
_PALLAS_BW = 512
_PALLAS_TR2 = 256
_PALLAS_MAX_R1 = 128  # larger outer sides would blow VMEM; swap or scan
#: the most rows the Pallas route of :func:`pair_counts` takes as its
#: first operand: a caller that blocks its group planes blocks them to this
PAIR_COUNTS_MAX_ROWS = _PALLAS_MAX_R1
# The VPU body's word block is the widest power-of-two multiple of
# _PALLAS_BW that divides the padded width (a shard is 32,768 words),
# up to _VPU_MAX_BW words and _VPU_INPUT_BYTES of double-buffered input
# (swept on v5e, 24 x 16 rows: 0.69 / 0.58 / 0.52 / 0.49 ms at 2048 /
# 4096 / 8192 / 16384: a grid step costs ~0.35 us whatever it holds).
# Inside it the body is three loops around one listing: over _VPU_SUB
# words (four column chunks, unrolled: what the 4 VALUs' schedule
# needs), over _VPU_GROUP rows of ``b`` (8 vregs a chunk) and over
# _VPU_ROWS rows of ``a``, of which as many are live together as
# _VPU_ACC_VREGS accumulator vregs hold, so that a loaded ``b`` vreg
# serves several rows (a load a pair would bound the loop, not the
# VALUs). A loop step of 16 x 64 rows is 384 bundles of VALU work,
# which Mosaic schedules at 410: 1,640 a 16 x 256 step against 1,553
# for the same rows listed one by one (PERF.md section 5).
_VPU_MAX_BW = 16384
_VPU_INPUT_BYTES = 6 << 20
_VPU_SUB = 512
_VPU_GROUP = 64
_VPU_ROWS = 8
_VPU_ACC_VREGS = 32
#: the VPU body where r1 * r2 < _VPU_MAX_PAIRS_PER_ROW * (r1 + r2)
_VPU_MAX_PAIRS_PER_ROW = 28


def pallas_body(r1: int, r2: int) -> str:
    """``"vpu"`` or ``"mxu"``: which body of the Pallas kernel counts an
    ``r1``-row operand against an ``r2``-row one. A pure function of the
    two heights. The VPU body spends 3 * r1 * r2 / 8 vector operations
    per 128 words, the MXU body about 96 * (r1 + r2) / 8 on expanding
    both operands, so the first wins while r1 * r2 / (r1 + r2) is under
    ~32 by the arithmetic; the sweep on the chip (PERF.md §5) puts the
    line where it wins by more than a tenth: under 28 it takes 0.41 to
    0.80 of the MXU body's time, from 28.4 to 32 it takes 0.90 to 0.93,
    above that more."""
    return ("vpu" if r1 * r2 < _VPU_MAX_PAIRS_PER_ROW * (r1 + r2)
            else "mxu")


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
    ``PILOSA_TPU_PALLAS=1``, via the interpreter) take the Pallas
    kernel (HBM sees only the packed planes; its body, ``vpu`` or
    ``mxu``, goes by the two heights): on one chip as one program, and,
    when both operands are sharded as the engine mesh places a stack
    (:func:`_mesh_route`), as the mesh program that counts on every chip
    over its own words and ``psum``s the ``int32[R1, R2]``. Traced
    values (inside jit/shard_map, e.g. ``mesh._groupby_counts``),
    operands sharded any other way, more than ``_PALLAS_MAX_R1`` rows
    and other backends take the XLA scan: a jitted program that wants
    the kernel chooses its route where its operands are still concrete
    and calls :func:`_pair_counts_traced` itself (:func:`pair_sums`,
    ops/bsi.py, ops/topk.py). Outcomes are counted on the
    ``ops_pallas_*`` metrics (ops/pallas_util.py), the body a dispatch
    took among them."""
    why, mesh = _pair_counts_plan(a, b)
    if why is None or mesh is not None:
        try:
            with PU.kernel_scope("mm", a.shape[0], b.shape[0], 2,
                                 a.shape[1]):
                out = (_pair_counts_pallas(a, b) if mesh is None
                       else _pair_counts_mesh(a, b, mesh=mesh))
            PU.dispatched("pair_counts", on_mesh=mesh is not None,
                          body=pallas_body(a.shape[0], b.shape[0]))
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


def pair_counts_route(a, b):
    """``(route, body)``: which program :func:`pair_counts` runs for
    these operands, ``"pallas"`` (the kernel on one chip), ``"mesh"``
    (the kernel on every chip over its own words) or ``"xla"`` (the
    scan), and which body the kernel takes there (``"vpu"`` | ``"mxu"``;
    ``"none"`` under the scan). Runs and counts nothing: for a span's
    tags."""
    why, mesh = _pair_counts_plan(a, b)
    if why is not None and mesh is None:
        return "xla", "none"
    return ("pallas" if why is None else "mesh",
            pallas_body(a.shape[0], b.shape[0]))


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


def _pad_rows(x, rows: int):
    return x if rows == x.shape[0] else jnp.pad(
        x, ((0, rows - x.shape[0]), (0, 0)))


def _padded_words(a, b):
    """Both operands zero-padded to whole ``_PALLAS_BW``-word blocks
    (zero words count nothing)."""
    pad_w = (-a.shape[1]) % _PALLAS_BW
    if pad_w:
        a = jnp.pad(a, ((0, 0), (0, pad_w)))
        b = jnp.pad(b, ((0, 0), (0, pad_w)))
    return a, b


def _row_tile(r2: int):
    """``(tr2, r2p)``: the row tile of the second operand and its height
    in whole tiles. A second operand narrower than one row tile is one
    tile of its own (sublane-rounded) height: padded to _PALLAS_TR2, an
    8-row block of 66-shard rows (8.65 MB each) would be copied out as
    2.2 GB and read back as such."""
    tr2 = min(_PALLAS_TR2, -(-r2 // 8) * 8)
    return tr2, -(-r2 // tr2) * tr2


def _mxu_kernel(a_ref, b_ref, out_ref):
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


def _pair_counts_mxu(a, b, interpret: bool):
    """The MXU body: both operands expanded to int8 lanes in VMEM per
    (512-word x 256-row) tile and contracted on the systolic array."""
    from jax.experimental import pallas as pl

    r1, r2 = a.shape[0], b.shape[0]
    a, b = _padded_words(a, b)
    r1p = max(8, -(-r1 // 8) * 8)  # sublane multiple, not just >= 8
    tr2, r2p = _row_tile(r2)
    out = pl.pallas_call(
        _mxu_kernel,
        grid=(r2p // tr2, a.shape[1] // _PALLAS_BW),
        in_specs=[
            pl.BlockSpec((r1p, _PALLAS_BW), lambda t, w: (0, w)),
            pl.BlockSpec((tr2, _PALLAS_BW), lambda t, w: (t, w)),
        ],
        out_specs=pl.BlockSpec((r1p, tr2), lambda t, w: (0, t)),
        out_shape=jax.ShapeDtypeStruct((r1p, r2p), jnp.int32),
        interpret=interpret,
    )(_pad_rows(a, r1p), _pad_rows(b, r2p))
    return out[:r1, :r2]


def _vpu_kernel(a_ref, b_ref, out_ref, acc_ref):
    """``acc_ref``: VMEM ``uint32[r1, tr2, 128]`` of lane-wise partial
    counts (a lane sums at most 32 * W / 128, twice that where two row
    groups overlap: exact far beyond S * 2^20), so a word step does no
    cross-lane work.

    The body is three nested loops, over ``_VPU_SUB`` words, over
    ``_VPU_GROUP`` rows of ``b`` and over ``_VPU_ROWS`` rows of ``a``,
    around one listing of at most 24 rows by ``_VPU_SUB / 128`` column
    chunks: the program does not grow with the operands' heights (a
    listing is traced, lowered and serialized once a shape and a
    process, before the compile cache can be asked)."""
    from jax.experimental import pallas as pl

    w = pl.program_id(1)  # innermost, as in the MXU body
    r1, bw = a_ref.shape
    tr2 = b_ref.shape[0]
    nj = min(tr2, _VPU_GROUP)
    groups = -(-tr2 // nj)
    # rows of ``a`` a loop step takes (against a group of under four
    # vregs a chunk all of them: ``a`` is the shorter operand, 24 rows at
    # most there, and a step of eight would be too little work), and
    # those of them whose accumulators are live together: a loaded vreg
    # of ``b`` serves them all
    rows_a = _VPU_ROWS if nj >= 4 * 8 else r1
    ib = min(rows_a, _VPU_ACC_VREGS // (nj // 8))

    @pl.when(w == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def count(off, j0, i0, ni):
        """``ni`` rows of ``a`` from ``i0`` against ``nj`` rows of ``b``
        from ``j0`` over ``_VPU_SUB`` words from ``off``."""
        rows = pl.ds(j0, nj)
        # a traced start is a whole sublane tile's: the row inside the
        # view is static, and its load broadcasts it for free (Mosaic
        # refuses a dynamic load from an unaligned row)
        a_rows, first = ((a_ref, i0) if isinstance(i0, int)
                         else (a_ref.at[pl.ds(i0, ni)], 0))
        for k0 in range(0, ni, ib):
            block = range(k0, min(k0 + ib, ni))
            sums = [acc_ref[i0 + k, rows] for k in block]
            for c in range(0, _VPU_SUB, 128):
                cols = pl.ds(off + c, 128)
                b_rows = b_ref[rows, cols]
                for n, k in enumerate(block):
                    sums[n] += lax.population_count(
                        a_rows[pl.ds(first + k, 1), cols] & b_rows)
            for n, k in enumerate(block):
                acc_ref[i0 + k, rows] = sums[n]

    def words(step, carry):
        off = pl.multiple_of(step * _VPU_SUB, _VPU_SUB)

        def group(j, carry):
            # the last group of a ragged tile starts early and counts
            # the rows it shares with the one before a second time
            j0 = pl.multiple_of(jnp.minimum(j * nj, tr2 - nj), 8)
            _blocks(r1, rows_a, functools.partial(count, off, j0))
            return carry

        if groups == 1:
            _blocks(r1, rows_a, functools.partial(count, off, 0))
        else:
            lax.fori_loop(0, groups, group, None)
        return carry

    lax.fori_loop(0, bw // _VPU_SUB, words, None)

    @pl.when(w == pl.num_programs(1) - 1)
    def _():
        counts = jnp.sum(acc_ref[...].astype(jnp.int32), axis=-1)
        twice = groups * nj - tr2  # rows the last two groups share
        if twice:
            col = lax.broadcasted_iota(jnp.int32, counts.shape, 1)
            shared = (col >= tr2 - nj) & (col < tr2 - nj + twice)
            counts = jnp.where(shared, counts >> 1, counts)
        out_ref[...] = counts


def _blocks(n: int, size: int, body) -> None:
    """``body(start, count)`` over ``range(n)`` in blocks of ``size``: the
    whole blocks under one ``fori_loop`` (``start`` traced) where there
    are several, the rest as a block of its own: however many blocks,
    ``body`` is traced twice at most."""
    from jax.experimental import pallas as pl

    whole = n // size
    if whole == 1:
        body(0, size)
    elif whole:
        def step(i, carry):
            body(pl.multiple_of(i * size, size), size)
            return carry

        lax.fori_loop(0, whole, step, None)
    if n % size:
        body(whole * size, n % size)


def _vpu_block_words(r1: int, tr2: int, padded_words: int) -> int:
    """Words a grid step of the VPU body takes for these heights."""
    bw = _PALLAS_BW
    while (2 * bw <= _VPU_MAX_BW and padded_words % (2 * bw) == 0
           and 2 * 4 * (r1 + tr2) * 2 * bw <= _VPU_INPUT_BYTES):
        bw *= 2
    return bw


def _pair_counts_vpu(a, b, interpret: bool):
    """The VPU body: every row of the shorter operand ANDed with the
    rows of the taller, popcounted and added into lane-wise accumulators
    in VMEM scratch; one cross-lane reduce on the last word step. Both
    operands go in as they are (a one-row TopN filter is one row, not
    eight; no row is copied to be padded): the last row tile of the
    taller hangs over its end, and the counts of the rows that are not
    there are cut off."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r1, r2 = a.shape[0], b.shape[0]
    if r1 > r2:
        # popcount(A_i & B_j) is symmetric, and the kernel lists the
        # rows of its first operand: the shorter one
        return _pair_counts_vpu(b, a, interpret).T
    a, b = _padded_words(a, b)
    tr2, r2p = _row_tile(r2)
    bw = _vpu_block_words(r1, tr2, a.shape[1])
    out = pl.pallas_call(
        _vpu_kernel,
        grid=(r2p // tr2, a.shape[1] // bw),
        in_specs=[
            pl.BlockSpec((r1, bw), lambda t, w: (0, w)),
            pl.BlockSpec((tr2, bw), lambda t, w: (t, w)),
        ],
        out_specs=pl.BlockSpec((r1, tr2), lambda t, w: (0, t)),
        out_shape=jax.ShapeDtypeStruct((r1, r2p), jnp.int32),
        scratch_shapes=[pltpu.VMEM((r1, tr2, 128), jnp.uint32)],
        interpret=interpret,
    )(a, b)
    return out[:, :r2]


def _pair_counts_traced(a, b, interpret: bool):
    """Traceable core of the Pallas pair count: one ``pallas_call`` on
    the two packed operands, its body chosen by :func:`pallas_body` from
    their heights (static under a trace). Both bodies work a word block
    at a time in VMEM, so HBM sees only the packed uint32 planes. Shared
    by ``pair_sums`` (a call a magnitude plane), ``bsi_plane_popcounts``
    (magnitude-plane popcounts) and TopN row counts: any "popcount of
    pairwise ANDs" is this one kernel."""
    if pallas_body(a.shape[0], b.shape[0]) == "vpu":
        return _pair_counts_vpu(a, b, interpret)
    return _pair_counts_mxu(a, b, interpret)


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
            PU.dispatched("pair_sums", on_mesh=mesh is not None,
                          body=pallas_body(_pair_sums_step_rows(a.shape[0]),
                                           b.shape[0]))
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


def _pair_sums_step_rows(r1: int) -> int:
    """Rows of the first operand of a kernel call of ``pair_sums``: both
    signs stacked where the stack fits the kernel's row limit."""
    return 2 * r1 if 2 * r1 <= _PALLAS_MAX_R1 else r1


def _pair_sums_traced(a, b, mags, pos, neg, interpret: bool):
    """Traceable body of the Pallas route of :func:`pair_sums`: the
    plane's mask goes on the small side, P = A_i & sign & M_k, so ``b``
    reaches the kernel as it is and no ``b & M_k`` is written and read
    back a step; both signs stack into one first operand, so the kernel
    reads ``b`` once a plane, not twice (two calls a step where the
    stack would pass the kernel's row limit)."""
    r1 = a.shape[0]
    firsts = [a & pos[None, :], a & neg[None, :]]
    if _pair_sums_step_rows(r1) == 2 * r1:
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
