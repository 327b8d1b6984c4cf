"""Device-side ingest sort/scatter kernels.

``field.import_bits → SetFragment.set_many`` is the measured bottleneck
of the pipelined ingest path (the ``fragment_advance`` ingest stage): the
classic path walks rows in Python, calling the native per-row
gather+scatter once per row. The device formulation splits the work:

1. **sort** (host, vectorized numpy): collapse every (plane slot,
   column) pair into a sorted *unique* flat word address plus an OR-mask
   of its bits — ``np.argsort`` + ``np.unique`` + ``bitwise_or.reduceat``
   replace the per-row loop entirely;
2. **scatter** (device): one ``.at[addr].set(masks)`` builds the update
   plane U (addresses are unique, so a plain set is exact), then a
   Pallas VPU kernel fuses ``merged = planes | U`` with the changed-bit
   count ``Σ popcount(U & ~planes)`` in a single pass over (rows, 512)
   VMEM blocks.

The per-row native loop stays as the classic path and bit-identity
oracle; eligibility (size caps + backend/kill-switch rules) lives in
:func:`why_not_ingest` on top of ops/pallas_util.py.
"""

from __future__ import annotations

import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pilosa_tpu import platform
from pilosa_tpu.obs.stages import record_stage
from pilosa_tpu.ops import pallas_util as PU

#: lane width of the merge+count kernel's 2-D view of the flat planes.
#: Flat inputs are padded to a power of two >= _BW, so the row count is a
#: power of two and PU.block_rows always finds a block.
_BW = 512
#: gathered sub-plane words per device round trip. Imports touching more
#: rows than fit one chunk stream through a chunked grid — each chunk
#: gathers its row group, scatters, and writes back, so bulk imports of
#: ANY size stay on-device (the old behavior rejected them wholesale).
MAX_FLAT_WORDS = 1 << 15
#: update pairs per interpret-mode call (with the flat-words cap below,
#: bounds how much work the CI interpreter vehicle is allowed; compiled
#: backends chunk instead of rejecting)
MAX_PAIRS = 1 << 16

#: interpret-mode total budget, in chunks: the interpreter costs seconds
#: per dispatch, so CI keeps the native loop for imports wider than a
#: few chunks (no kernel coverage is lost — the chunk loop is exercised
#: at small scale by the parity tests)
_INTERPRET_CHUNKS = 4


def why_not_ingest(n_pairs: int, n_rows: int, words: int
                   ) -> Optional[str]:
    """``None`` when set_many should take the device scatter path. A
    single row wider than one chunk can't be split (``shape``); on the
    interpreter, imports beyond a few chunks keep the native loop
    (``interpret``). Everything else chunks on-device."""
    why = PU.why_not("ingest_scatter")
    if why is not None:
        return why
    if n_pairs == 0 or words > MAX_FLAT_WORDS:
        return "shape"
    if PU.use_interpret() and (
            n_pairs > _INTERPRET_CHUNKS * MAX_PAIRS
            or n_rows * words > _INTERPRET_CHUNKS * MAX_FLAT_WORDS):
        return "interpret"
    return None


def sort_updates(slots, cols, words: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Host half: (plane slot, column) pairs -> (sorted unique flat word
    addresses int64[M], uint32 OR-masks[M]). Duplicate bits collapse
    into one mask, so the device count never double-counts."""
    slots = np.asarray(slots, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    if slots.size == 0:
        return slots, np.zeros(0, dtype=np.uint32)
    addr = slots * words + (cols >> 5)
    mask = np.uint32(1) << (cols & 31).astype(np.uint32)
    order = np.argsort(addr, kind="stable")
    addr = addr[order]
    mask = mask[order]
    uaddr, starts = np.unique(addr, return_index=True)
    return uaddr, np.bitwise_or.reduceat(mask, starts)


def _merge_count_kernel(p_ref, u_ref, out_ref, cnt_ref):
    from jax.experimental import pallas as pl

    g = pl.program_id(0)
    p = p_ref[...]
    u = u_ref[...]
    out_ref[...] = p | u
    new = jnp.sum(lax.population_count(u & ~p).astype(jnp.int32))

    @pl.when(g == 0)
    def _():
        cnt_ref[0, 0] = new

    @pl.when(g != 0)
    def _():
        cnt_ref[0, 0] += new


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("interpret",))
def _scatter_merge_pallas(flat, addr, masks, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    upd = jnp.zeros_like(flat).at[addr].set(masks)
    x = flat.reshape(-1, _BW)
    u = upd.reshape(-1, _BW)
    rows = PU.block_rows(x.shape[0])
    block = pl.BlockSpec((rows, _BW), lambda g: (g, 0))
    merged, cnt = pl.pallas_call(
        _merge_count_kernel,
        grid=(x.shape[0] // rows,),
        in_specs=[block, block],
        out_specs=[block, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=(jax.ShapeDtypeStruct(x.shape, flat.dtype),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        interpret=interpret,
    )(x, u)
    return merged.reshape(flat.shape), cnt[0, 0]


@platform.guarded_call
@jax.jit
def _scatter_merge_xla(flat, addr, masks):
    """XLA oracle for the merge+count (parity tests)."""
    upd = jnp.zeros_like(flat).at[addr].set(masks)
    return flat | upd, jnp.sum(
        lax.population_count(upd & ~flat).astype(jnp.int32))


def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


def _scatter_chunk(planes: np.ndarray, uslots: np.ndarray,
                   addr: np.ndarray, masks: np.ndarray
                   ) -> Tuple[int, np.ndarray]:
    """One device round trip over the rows ``uslots`` with chunk-rebased
    unique addresses; returns (newly set bits, merged sub-plane). The
    caller writes back so a failing later chunk leaves ``planes``
    untouched (the native fallback then recounts correctly)."""
    sub = np.ascontiguousarray(planes[uslots])
    flat = sub.reshape(-1)
    n = flat.size
    pad = _next_pow2(max(n, _BW)) - n
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, dtype=flat.dtype)])
    t0 = time.perf_counter()
    dev = platform.h2d_copy(flat)
    record_stage("h2d_copy", time.perf_counter() - t0, nbytes=flat.nbytes)
    with PU.kernel_scope("scatter", addr.size, uslots.size, 2,
                         flat.size):
        merged, cnt = _scatter_merge_pallas(
            dev, jnp.asarray(addr.astype(np.int32)), jnp.asarray(masks),
            PU.use_interpret())
        changed = int(cnt)
    return changed, np.asarray(merged)[:n].reshape(sub.shape)


def scatter_new_bits_bulk(planes: np.ndarray, slots, cols) -> int:
    """OR (plane slot, column) updates into host ``planes`` rows through
    the device scatter+merge kernel; returns the number of newly set
    bits — the same contract as summing ``native.scatter_new_bits`` over
    rows. Mutates the touched ``planes`` rows in place.

    Gathers only the touched rows, pads each flattened chunk to a power
    of two (bounds jit shape variants), round-trips through
    ``platform.h2d_copy``, whose seconds and bytes are the ``h2d_copy``
    ingest stage.
    Imports wider than one :data:`MAX_FLAT_WORDS` chunk stream a chunked
    grid — the sort/dedup runs once, the sorted unique addresses
    partition cleanly at row-group boundaries, and per-chunk counts sum
    exactly (no address appears in two chunks). Chunk results are
    buffered and written back only after every chunk succeeded, so a
    dispatch failure mid-stream leaves ``planes`` untouched for the
    native fallback.
    """
    slots = np.asarray(slots, dtype=np.int64)
    uslots = np.unique(slots)
    words = planes.shape[1]
    addr, masks = sort_updates(np.searchsorted(uslots, slots), cols, words)
    rows_per_chunk = max(1, MAX_FLAT_WORDS // words)
    changed = 0
    results = []
    for lo in range(0, uslots.size, rows_per_chunk):
        hi = min(lo + rows_per_chunk, uslots.size)
        a0, a1 = np.searchsorted(addr, (lo * words, hi * words))
        got, merged = _scatter_chunk(
            planes, uslots[lo:hi], addr[a0:a1] - lo * words, masks[a0:a1])
        changed += got
        results.append((uslots[lo:hi], merged))
    for chunk_slots, merged in results:
        planes[chunk_slots] = merged
    PU.dispatched("ingest_scatter")
    return changed
