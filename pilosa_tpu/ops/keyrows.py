"""Key planes: the rows of a mutex field as the bits of one value a record.

A mutex (or bool) field holds at most one row per record, so its dense
stack ``uint32[cap, W]`` is mostly zeros: a record's column is set in one
row at most. Where that stack cannot fit the device budget
(``core/stacked.py``: SSB SF-10's ``p_brand1`` is 7.6 GB against 6.44),
the stack holds the *key planes* instead: record ``c`` stores the value
``slot + 1`` of the row it is in (0: none) in ``k`` bit planes,

    K_i[c] = bit i of (slot(c) + 1)            uint32[k_pad, W]

``k`` = the bit length of the stack's slot capacity, so that every slot,
padding included, has a value of its own; the planes are stored padded
with zero planes to a whole sublane tile (``k_pad``, a multiple of 8), so
no call copies to pad. A row is derived on the device, never sent:

    row(s) = AND over i of (bit i of (s + 1) ? K_i : NOT K_i)

A record whose value differs from ``s + 1`` in any bit drops out; a
record with no value (0) differs from every ``s + 1 >= 1``. Zero padding
planes pass every slot whose value has no bit there, which is every slot
below ``2**k``.

:func:`key_rows` derives a block of consecutive slots: the Pallas kernel
``_key_rows_pallas`` (one program a block height; the block's first slot
is a runtime SMEM scalar, so every block of a stack shares it) or its XLA
twin where ``pallas_util.why_not`` says no. :func:`key_rows_at` derives
any few slots (a point read's rows) on XLA. :func:`reference` is the
plain numpy derivation the tests hold both to.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pilosa_tpu import platform
from pilosa_tpu.ops import pallas_util as PU

#: rows of a sublane tile: key planes are stored padded to a multiple
SUBLANES = 8
#: words a grid step of the kernel takes: the whole width where it is
#: narrower; a word block is worked in chunks of CHUNK_WORDS
BLOCK_WORDS = 16384
CHUNK_WORDS = 512

_ONES = np.uint32(0xFFFFFFFF)


def key_bits(cap: int) -> int:
    """Bit planes that give each of ``cap`` slots a value of its own
    (slot + 1, 0 meaning none)."""
    return max(1, int(cap).bit_length())


def padded_planes(bits: int) -> int:
    """``bits`` rounded up to whole sublane tiles."""
    return -(-bits // SUBLANES) * SUBLANES


def reference(keys: np.ndarray, slots: Sequence[int]) -> np.ndarray:
    """The plain derivation: ``uint32[len(slots), W]`` rows of the key
    planes ``keys`` (numpy, one slot at a time)."""
    keys = np.asarray(keys, dtype=np.uint32)
    out = np.empty((len(slots), keys.shape[1]), dtype=np.uint32)
    for n, s in enumerate(slots):
        v = int(s) + 1
        row = np.full(keys.shape[1], _ONES, dtype=np.uint32)
        for i in range(keys.shape[0]):
            row &= keys[i] if (v >> i) & 1 else ~keys[i]
        out[n] = row
    return out


def _derive_xla(keys, values):
    """Rows of the values ``values`` (uint32[n]): AND over the planes of
    each plane XORed with 0 (bit i of the value set) or all ones (clear),
    written as one elementwise chain so that it fuses into a single pass
    that writes ``[n, W]`` and nothing larger."""
    out = None
    for i in range(keys.shape[0]):
        flip = ((values >> i) & 1) - jnp.uint32(1)
        term = keys[i][None, :] ^ flip[:, None]
        out = term if out is None else out & term
    return out


@platform.guarded_call
@jax.jit
def _key_rows_take(keys, slots):
    """XLA derivation of the rows ``slots`` (int32[n])."""
    return _derive_xla(keys, slots.astype(jnp.uint32) + 1)


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("rows",))
def _key_rows_xla(keys, first, rows: int):
    """The XLA twin of ``_key_rows_pallas``: ``rows`` consecutive slots
    from ``first[0]``; any backend and any sharding (elementwise over
    the words, so a mesh-sharded stack derives where its words live)."""
    slots = first[0] + jnp.arange(rows, dtype=jnp.int32)
    return _derive_xla(keys, slots.astype(jnp.uint32) + 1)


def _key_rows_body(planes: int, chunk: int, first_ref, keys_ref, out_ref):
    """One word block: for each chunk of ``chunk`` words and each tile of
    (up to) 8 rows, AND the ``planes`` key rows, each XORed with the
    rows' flip column, and store the tile."""
    from jax.experimental import pallas as pl

    rows, bw = out_ref.shape
    first = first_ref[0]

    def step(c, carry):
        w0 = pl.multiple_of(c * chunk, chunk)
        keys = keys_ref[:, pl.ds(w0, chunk)]
        for r0 in range(0, rows, SUBLANES):
            n = min(SUBLANES, rows - r0)
            v = (first + (r0 + 1)
                 + lax.broadcasted_iota(jnp.int32, (n, 1), 0))
            acc = None
            for i in range(planes):
                flip = ((v >> i) & 1).astype(jnp.uint32) - jnp.uint32(1)
                term = keys[i:i + 1, :] ^ flip
                acc = term if acc is None else acc & term
            out_ref[r0:r0 + n, pl.ds(w0, chunk)] = acc
        return carry

    lax.fori_loop(0, bw // chunk, step, 0)


def _word_block(words: int) -> int:
    """The word block a grid step takes: the whole width where it is at
    most :data:`BLOCK_WORDS`, else BLOCK_WORDS (the last step may hang
    over the end; its words past the width are not written)."""
    return words if words <= BLOCK_WORDS else BLOCK_WORDS


def _chunk(bw: int) -> int:
    return CHUNK_WORDS if bw % CHUNK_WORDS == 0 else bw


@platform.guarded_call
@functools.partial(jax.jit, static_argnames=("rows", "planes", "interpret"))
def _key_rows_pallas(keys, first, rows: int, planes: int, interpret: bool):
    """``uint32[rows, W]``: slots ``first[0] .. first[0] + rows - 1``
    derived from the key planes ``keys`` (``uint32[k_pad, W]``), of
    which the first ``planes`` are read by the loop (the rest are zero
    padding, which passes every slot below ``2**planes``). ``first`` is
    ``int32[1]`` in SMEM, so one program serves every block of a stack."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k_pad, words = keys.shape
    bw = _word_block(words)
    return pl.pallas_call(
        functools.partial(_key_rows_body, planes, _chunk(bw)),
        grid=(-(-words // bw),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((k_pad, bw), lambda g: (0, g)),
        ],
        out_specs=pl.BlockSpec((rows, bw), lambda g: (0, g)),
        out_shape=jax.ShapeDtypeStruct((rows, words), keys.dtype),
        interpret=interpret,
    )(first, keys)


def key_rows(keys, first_slot: int, rows: int, planes: int) -> jax.Array:
    """``uint32[rows, W]``: the ``rows`` slots from ``first_slot`` derived
    from the key planes on the device, by the Pallas kernel where
    ``pallas_util.why_not`` allows it (``ops_pallas_dispatch_total
    {kernel="key_rows"}``), else by its XLA twin (the fallback counted
    with its reason: ``mesh`` for a stack sharded over chips)."""
    first = jnp.asarray(np.array([first_slot], dtype=np.int32))
    why = PU.why_not("key_rows", keys)
    if why is None:
        try:
            out = _key_rows_pallas(keys, first, rows, planes,
                                   PU.use_interpret())
            PU.dispatched("key_rows")
            return out
        except Exception as e:  # noqa: BLE001 — strike-out policy
            PU.failed("key_rows", e)
    else:
        PU.fallback("key_rows", why)
    return _key_rows_xla(keys, first, rows)


def key_rows_at(keys, slots: Sequence[int]) -> jax.Array:
    """``uint32[len(slots), W]``: any few slots (a point read's rows)
    derived on XLA; the slot list is padded to a power of two of at
    least 8 so that few programs serve every read."""
    n = len(slots)
    cap = SUBLANES
    while cap < n:
        cap *= 2
    idx = np.zeros(cap, dtype=np.int32)
    idx[:n] = slots
    out = _key_rows_take(keys, jnp.asarray(idx))
    return out if cap == n else out[:n]
