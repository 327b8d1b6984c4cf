"""Device-mesh shard placement and collective query reduces.

Mapping from the reference's cluster model (SURVEY.md §5.7/§5.8):

- reference: shard -> partition -> node via jump consistent hash
  (disco/hasher.go:13, disco/snapshot.go:117) — here: shard i of a stacked
  fragment tensor ``[S, ..., W]`` lives on mesh axis ``shards`` position
  ``i % n_shard_devices`` (XLA's block sharding; deterministic, no hash
  needed because placement is dense).
- reference: per-call map over shard jobs + application-level reduce over
  HTTP responses (executor.go:6449 mapReduce, internal_client.go) — here:
  one ``shard_map``-ped kernel, reduce is ``lax.psum`` over the mesh axes,
  riding ICI within a slice and DCN across slices.
- the column axis (2^20 bits = 32768 words) can additionally be split over
  a second mesh axis ``cols`` — the analog of sequence/tensor parallelism:
  bitmap algebra is elementwise over words so it shards trivially, and the
  GroupBy matmul contracts over the column axis with psum partial sums
  (the classic TP matmul pattern).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pilosa_tpu import platform
from pilosa_tpu.ops.bitmap import _popcount_i32, zeros_varying_like
from pilosa_tpu.ops.groupby import pair_counts

SHARD_AXIS = "shards"
COL_AXIS = "cols"

# ---------------------------------------------------------------------------
# Engine mesh: the device mesh the PQL executor runs over (VERDICT r1 #2 —
# mesh execution wired into the engine, not a sidecar demo). Stacked
# fragment tensors [..., S*W] shard their fused (shard, word) axis over
# EVERY mesh device: contiguous word-blocks land on devices, which is
# simultaneously shard-parallelism (different shards on different devices)
# and column-parallelism (one shard's 32768 words split across devices) —
# the DB analogs of dp and tp (SURVEY.md §5.7). Who turns a kernel's
# reduction over the word axis into a collective depends on the kernel:
# - elementwise plane algebra and the popcount reduces of ops/ (jnp.sum
#   over words) are left to XLA's SPMD partitioner, which computes on each
#   chip's words and inserts the all-reduce from the input shardings (the
#   scaling-book recipe: annotate shardings, let XLA insert collectives);
# - a ``lax.scan`` over blocks of the word axis is NOT one of those: the
#   partitioner cannot split a loop over the sharded axis and all-gathers
#   the operands whole onto every chip (the XLA pair count,
#   ops/groupby._pair_counts_xla: 134 MB a call on four chips). Nor is a
#   ``pallas_call`` (Mosaic refuses to be partitioned). Those say where
#   the words live themselves, with a ``shard_map`` whose body runs on a
#   chip's own words and a ``psum`` of the small results: the pair-count
#   family through :func:`psum_over_words`, :func:`compile_tape_count`
#   and :class:`ShardPlacement`'s kernels with one of their own;
# - every other Pallas family (bsi_compare, bsi_sum, topn, the tape
#   terminal) still takes its partitionable XLA twin on a mesh
#   (``why="mesh"``, ops/pallas_util.py).
# ---------------------------------------------------------------------------

_ENGINE_MESH: Optional[Mesh] = None
_MESH_EPOCH = 0


def mesh_epoch() -> int:
    """Bumped on every set_engine_mesh call. Stacked caches fold it into
    their version keys, so a mesh switch invalidates every stack built
    under the old placement — mixing placements in one jitted kernel
    would raise 'incompatible devices', not reshard."""
    return _MESH_EPOCH


def engine_mesh() -> Mesh:
    """The process-wide mesh queries execute over. Defaults to all local
    devices on the ``shards`` axis; override with :func:`set_engine_mesh`
    (tests parametrize 1- vs 8-device; multi-host setups pass a global
    mesh)."""
    global _ENGINE_MESH
    if _ENGINE_MESH is None:
        _ENGINE_MESH = analytics_mesh(jax.devices())
    return _ENGINE_MESH


def set_engine_mesh(mesh: Optional[Mesh]) -> None:
    """Install (or with None, reset to default-on-next-use) the engine
    mesh. Bumps the mesh epoch so every cached stack built under the old
    placement is invalidated and rebuilt on next use."""
    global _ENGINE_MESH, _MESH_EPOCH
    _ENGINE_MESH = mesh
    _MESH_EPOCH += 1


_FALLBACK_WARNED: set = set()


def _words_spec(ndim: int) -> P:
    """Leading axes whole, the last (the fused (shard, word) space) split
    over every device of the mesh."""
    return P(*([None] * (ndim - 1)), (SHARD_AXIS, COL_AXIS))


def engine_sharding(ndim: int,
                    last_dim: int) -> Optional[NamedSharding]:
    """Sharding for a stacked engine tensor whose LAST axis is the fused
    (shard, word) space. None when that axis doesn't divide over the mesh
    (callers fall back to single-device placement). The fallback is
    LOUD — a warning per (mesh, shape) plus a metric — because a
    misconfigured mesh silently losing all parallelism is exactly the
    failure an operator needs to see (VERDICT r3 weak #7)."""
    mesh = engine_mesh()
    n = mesh.devices.size
    if n <= 1:
        return None
    if last_dim % n:
        key = (n, last_dim)
        if key not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(key)
            import logging

            logging.getLogger("pilosa_tpu.mesh").warning(
                "stacked tensor word axis %d does not divide over the "
                "%d-device engine mesh; falling back to SINGLE-DEVICE "
                "placement (no query parallelism for this stack)",
                last_dim, n)
        from pilosa_tpu.obs import metrics as M

        M.REGISTRY.count(M.METRIC_MESH_FALLBACK)
        return None
    return NamedSharding(mesh, _words_spec(ndim))


def words_per_device(last_dim: int) -> int:
    """How many of the ``last_dim`` words of a stack's fused (shard,
    word) axis its widest device holds as :func:`engine_sharding` places
    it: an even share when the axis splits over the mesh, all of them on
    one device or where it does not divide. Pure: ticks and logs
    nothing."""
    n = engine_mesh().devices.size
    return last_dim // n if n > 1 and last_dim % n == 0 else last_dim


def engine_placed(*arrays) -> bool:
    """Whether every one of ``arrays`` is a concrete device array laid
    out as :func:`engine_sharding` lays a stack out: on the current
    engine mesh of several devices, leading axes whole, the last axis
    split evenly over every device. Pure: it reads the operands' own
    ``sharding`` and ticks nothing. numpy operands, tracers, arrays on
    one device or replicated, and arrays on another mesh (other devices,
    or the same in another order) are not."""
    mesh = engine_mesh()
    n = mesh.devices.size
    if n <= 1 or not arrays:
        return False
    for x in arrays:
        if (isinstance(x, jax.core.Tracer) or not isinstance(x, jax.Array)
                or x.ndim == 0 or x.shape[-1] % n):
            return False
        want = NamedSharding(mesh, _words_spec(x.ndim))
        if not x.sharding.is_equivalent_to(want, x.ndim):
            return False
    return True


def psum_over_words(body, mesh: Mesh, *operands):
    """``body`` run on every chip of ``mesh`` over that chip's own words
    of each operand (leading axes whole, the last split as the engine
    places it), and the tree of arrays it returns summed over the mesh:
    the replicated result of a reduction that contracts over the word
    axis, for one small all-reduce and no operand moved. Traceable; the
    caller jits it. ``check_vma`` is off because a ``pallas_call``'s
    ``out_shape`` carries no varying-axes type and JAX 0.9.0 refuses
    such a call under the check."""
    @functools.partial(_shard_map, mesh=mesh,
                       in_specs=tuple(_words_spec(x.ndim) for x in operands),
                       out_specs=P(), check_vma=False)
    def f(*local):
        return lax.psum(body(*local), (SHARD_AXIS, COL_AXIS))
    return f(*operands)


def engine_put(host: np.ndarray) -> jax.Array:
    """device_put a stacked tensor with the engine placement (traced as
    a ``device.h2d_copy`` stage — staging cost must be attributable)."""
    sh = engine_sharding(host.ndim, host.shape[-1])
    return platform.h2d_copy(host, sh)


def analytics_mesh(devices: Optional[Sequence] = None,
                   col_parallel: int = 1) -> Mesh:
    """Build the 2D (shards, cols) mesh. ``col_parallel`` > 1 splits the
    column/word axis — use it when single-shard latency matters more than
    shard throughput (few big shards)."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if n % col_parallel:
        raise ValueError(f"{n} devices not divisible by col_parallel={col_parallel}")
    dev_array = np.asarray(devices).reshape(n // col_parallel, col_parallel)
    return Mesh(dev_array, (SHARD_AXIS, COL_AXIS))


class ShardPlacement:
    """Places stacked fragment tensors onto the mesh and runs collective
    query kernels. The single object that replaces the reference's
    cluster+InternalClient pair for query fan-out."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def spec(self, ndim: int) -> P:
        """[S, ..., W]: shards on axis 0, words on the last axis."""
        middle = [None] * (ndim - 2)
        return P(SHARD_AXIS, *middle, COL_AXIS)

    def place(self, arr) -> jax.Array:
        arr = np.asarray(arr)
        return platform.h2d_copy(
            arr, NamedSharding(self.mesh, self.spec(arr.ndim)))

    # -- collective kernels ------------------------------------------------

    def count(self, planes) -> int:
        """Global popcount of [S, W] (reference: executeCount reduce)."""
        return int(_count(self.mesh, planes))

    def intersect_count(self, a, b) -> int:
        return int(_intersect_count(self.mesh, a, b))

    def row_counts(self, planes) -> np.ndarray:
        """[S, R, W] -> global per-row counts [R] (feeds TopN/TopK)."""
        return np.asarray(_row_counts(self.mesh, planes))

    def groupby_counts(self, a, b) -> np.ndarray:
        """[S, G, W] x [S, R, W] -> global pairwise counts [G, R]."""
        return np.asarray(_groupby_counts(self.mesh, a, b))

    def bsi_sum_counts(self, planes, filt):
        """[S, P, W] BSI stacks + [S, W] filter -> (count, per-plane
        popcounts [P]) summed over all shards; host assembles the exact
        64-bit sum as in ops/bsi.py."""
        count, per_plane = _bsi_sum_counts(self.mesh, planes, filt)
        return int(count), np.asarray(per_plane)


def _specs(mesh, *in_ndims, out):
    def spec(nd):
        return P(SHARD_AXIS, *([None] * (nd - 2)), COL_AXIS)
    return dict(mesh=mesh, in_specs=tuple(spec(n) for n in in_ndims),
                out_specs=out)


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("mesh",))
def _count(mesh, planes):
    @functools.partial(_shard_map, **_specs(mesh, 2, out=P()))
    def f(local):
        c = jnp.sum(_popcount_i32(local))
        return lax.psum(c, (SHARD_AXIS, COL_AXIS))
    return f(planes)


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("mesh",))
def _intersect_count(mesh, a, b):
    @functools.partial(_shard_map, **_specs(mesh, 2, 2, out=P()))
    def f(la, lb):
        c = jnp.sum(_popcount_i32(la & lb))
        return lax.psum(c, (SHARD_AXIS, COL_AXIS))
    return f(a, b)


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("mesh",))
def _row_counts(mesh, planes):
    @functools.partial(_shard_map, **_specs(mesh, 3, out=P()))
    def f(local):
        c = jnp.sum(_popcount_i32(local), axis=(0, 2))
        return lax.psum(c, (SHARD_AXIS, COL_AXIS))
    return f(planes)


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("mesh",))
def _groupby_counts(mesh, a, b):
    @functools.partial(_shard_map, **_specs(mesh, 3, 3, out=P()))
    def f(la, lb):
        # Sum pair-count matrices over local shards, then all shards/cols.
        def one(carry, ab):
            sa, sb = ab
            return carry + pair_counts(sa, sb), None
        init = zeros_varying_like(la, (la.shape[1], lb.shape[1]), jnp.int32)
        local, _ = lax.scan(one, init, (la, lb))
        return lax.psum(local, (SHARD_AXIS, COL_AXIS))
    return f(a, b)


# ---------------------------------------------------------------------------
# Per-query-family compiled programs (pql/programs.py). A query family is
# lowered to an op tape — a register machine whose registers start as the
# leaf planes (resident row planes / existence / zeros) and whose ops are
# the four bitmap combinators — and the whole tape plus its terminal
# (popcount-reduce or plane materialization) compiles to ONE executable.
# The warm path then launches exactly one program per query instead of a
# Python loop of per-op dispatches, each paying its own launch.
# ---------------------------------------------------------------------------

def _tape_eval(tape, leaves):
    """Run an op tape over leaf planes. regs[0..n-1] are the leaves; each
    ("and"|"or"|"xor"|"andnot", i, j) op appends a register; the last
    register is the result. Pure jnp — traceable inside jit/shard_map."""
    regs = list(leaves)
    for op, i, j in tape:
        a, b = regs[i], regs[j]
        if op == "and":
            regs.append(a & b)
        elif op == "or":
            regs.append(a | b)
        elif op == "xor":
            regs.append(a ^ b)
        elif op == "andnot":
            regs.append(a & ~b)
        else:  # defensive: an unknown op is a compiler bug, not data
            raise ValueError(f"unknown tape op {op!r}")
    return regs[-1]


def _tape_result(tape, masked, args):
    if masked:
        mask, leaves = args[-1], args[:-1]
    else:
        mask, leaves = None, args
    out = _tape_eval(tape, leaves)
    if masked:
        out = out & mask
    return out


def compile_tape_count(tape, masked: bool, total_words: int):
    """Compile ``popcount(tape-result [& mask])`` into one executable.

    When the fused word axis divides over the engine mesh the reduce is
    an explicit shard_map + ``lax.psum`` over (shards, cols) — the count
    arrives on-device, no host-side merge. Otherwise a plain jit (GSPMD
    still inserts collectives from the leaf shardings when they happen
    to be placed). Callers cache the returned fn per (tape, shape
    bucket, mesh epoch)."""
    from pilosa_tpu.ops import pallas_util as PU
    from pilosa_tpu.ops.bitmap import (pallas_count_eligible,
                                       plane_count_pallas_traced)

    mesh = engine_mesh()
    use_mesh = (mesh.devices.size > 1
                and total_words % mesh.devices.size == 0)

    if use_mesh:
        spec = _words_spec(1)

        @jax.jit
        def fn(*args):
            @functools.partial(_shard_map, mesh=mesh,
                               in_specs=(spec,) * len(args), out_specs=P())
            def f(*largs):
                c = jnp.sum(_popcount_i32(_tape_result(tape, masked, largs)))
                return lax.psum(c, (SHARD_AXIS, COL_AXIS))
            return f(*args)
        PU.fallback("tape_count", "mesh")
    else:
        # Pallas count terminal: the tape's bitwise ops trace as usual,
        # the popcount reduce becomes the grid kernel. Decision happens
        # once per compile; programs.py keys its cache on PU.mode_token
        # so flipping the kill switch recompiles.
        why = PU.why_not("tape_count")
        if why is None and not pallas_count_eligible(total_words):
            why = "shape"
        if why is None:
            interpret = PU.use_interpret()

            @jax.jit
            def fn(*args):
                return plane_count_pallas_traced(
                    _tape_result(tape, masked, args), interpret)

            wrapped = platform.guarded_call(fn)
            wrapped.pallas_terminal = True
            return wrapped

        PU.fallback("tape_count", why)

        @jax.jit
        def fn(*args):
            return jnp.sum(_popcount_i32(_tape_result(tape, masked, args)))

    return platform.guarded_call(fn)


def compile_tape_plane(tape, masked: bool):
    """Compile ``(tape-result [& mask]) | scratch`` into one executable.

    ``scratch`` is an all-zeros plane whose only job is to be the
    donated output buffer: on device backends steady-state queries then
    allocate nothing. On CPU XLA ignores donation (platform.
    donate_argnums gates it off), which is what lets the caller pass the
    long-lived shared zeros plane without it being consumed."""

    @functools.partial(jax.jit,
                       donate_argnums=platform.donate_argnums(0))
    def fn(scratch, *args):
        return _tape_result(tape, masked, args) | scratch

    return platform.guarded_call(fn)


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("mesh",))
def _bsi_sum_counts(mesh, planes, filt):
    from pilosa_tpu.ops.bsi import EXISTS, OFFSET, SIGN

    @functools.partial(_shard_map, **_specs(mesh, 3, 2, out=(P(), P())))
    def f(local, lfilt):
        rows = local[:, EXISTS, :] & lfilt
        count = jnp.sum(_popcount_i32(rows))
        # signed per-plane counts: pos - neg, assembled host-side
        sign = local[:, SIGN, :]
        mags = local[:, OFFSET:, :]
        pos = jnp.sum(_popcount_i32(mags & (rows & ~sign)[:, None, :]), axis=(0, 2))
        neg = jnp.sum(_popcount_i32(mags & (rows & sign)[:, None, :]), axis=(0, 2))
        return (lax.psum(count, (SHARD_AXIS, COL_AXIS)),
                lax.psum(pos - neg, (SHARD_AXIS, COL_AXIS)))
    return f(planes, filt)
