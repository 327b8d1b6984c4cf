"""Operations and bytes a kernel call needs, from its shapes alone.

The arithmetic of ``mm``, ``cmp``, ``scatter`` and ``pop`` is copied from
the program's analytic cost model (``pilosa_tpu/obs/devprof.py``
``tape_cost``, the ``pallas`` families) so that the yardstick does not
move with the code it measures; ``pair_sums`` is new here. Conventions:
planes are uint32 words, one word operation is 32 bit operations, and a
0/1 lane product on the MXU counts as two int8 operations (multiply and
add).

A device trace names every operation by its HLO text, shapes included, so
``FROM_TEXT[family](text)`` reads a call's shapes from the event itself
and returns ``(operations, bytes)``, or None when the text has another
form.
"""

import re

WORD_BYTES = 4
BIT_LANES = 32


def mm(d1, d2, total_words):
    """Pair counts C[d1, d2] = popcount(A_i & B_j) as a bit-expanded int8
    matmul contracting 32 * total_words lanes: operands are read packed,
    the int32 result is written once."""
    ops = 2.0 * d1 * d2 * BIT_LANES * total_words
    nbytes = float(WORD_BYTES) * (d1 + d2) * total_words + 4.0 * d1 * d2
    return ops, nbytes


def pair_sums(depth, d1, d2, total_words):
    """Per-plane signed pair counts for a two-field GroupBy with a Sum:
    2 * depth pair counts over the same two row sets. Every operand (both
    row sets, ``depth`` magnitude planes, the two sign masks) need be read
    once; the two int32 [depth, d1, d2] results are written once."""
    ops = 2.0 * depth * mm(d1, d2, total_words)[0]
    nbytes = (float(WORD_BYTES) * (d1 + d2 + depth + 2) * total_words
              + 2 * 4.0 * depth * d1 * d2)
    return ops, nbytes


def cmp(depth, sides, total_words):
    """Fused BSI compare walk: about 6 word operations per (plane, side)
    plus 8 for the sign partition; reads 2 + depth planes and a filter,
    writes one plane."""
    ops = float(BIT_LANES) * (6 * depth * sides + 8) * total_words
    return ops, float(WORD_BYTES) * (3 + depth) * total_words


def scatter(total_words):
    """Ingest merge-and-count pass: reads planes and updates, writes the
    merged planes."""
    return (float(BIT_LANES) * 2.0 * total_words,
            float(WORD_BYTES) * 3.0 * total_words)


def pop(tiles, total_words):
    """Per-row popcount over ``tiles`` payload tiles of total_words words."""
    return (float(BIT_LANES) * 2.0 * tiles * total_words,
            float(WORD_BYTES) * tiles * total_words + 4.0 * tiles)


_SHAPE = r"\[(\d+),(\d+)\](?:\{[^}]*\})?"
_MM_CALL = re.compile(
    rf"custom-call\(u32{_SHAPE} [^,]+, u32{_SHAPE} [^,)]+\)")
_ACC3 = re.compile(r"s32\[(\d+),(\d+),(\d+)\]")
_PLANES = re.compile(r"u32\[(\d+),(\d+)\]")


def mm_from_text(text):
    """A Pallas pair-count call: ``custom-call(u32[d1,W] a, u32[d2,W] b)``."""
    m = _MM_CALL.search(text)
    if m is None:
        return None
    d1, w1, d2, w2 = map(int, m.groups())
    return mm(d1, d2, w1) if w1 == w2 else None


def pair_sums_from_text(text):
    """The scan of ``pair_sums``: a ``while`` that carries two
    ``s32[depth,d1,d2]`` accumulators and the ``u32[rows,W]`` operands."""
    acc = _ACC3.search(text)
    if acc is None or not text.startswith("%while"):
        return None
    depth, d1, d2 = map(int, acc.groups())
    words = max(int(w) for _, w in _PLANES.findall(text))
    return pair_sums(depth, d1, d2, words)


FROM_TEXT = {"mm": mm_from_text, "pair_sums": pair_sums_from_text}
