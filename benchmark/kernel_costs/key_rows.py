"""Cost family ``key_rows``: the Pallas derivation of a row block from a
mutex stack's key planes (``pilosa_tpu/ops/keyrows.py``
``_key_rows_pallas``, PR 38).

A call reads the ``k_pad`` key planes once and writes ``block_rows`` dense
rows, each word of which is an AND over the planes of a plane XORed with
a constant: ``words * block_rows * k_pad`` word operations, far under the
chip's peak, so the call is bound by its bytes.

The device trace names the call ``%_key_rows_pallas... = u32[block_rows,
words]... custom-call(s32[1]... first, u32[k_pad, words]... keys)``; the
compiled text carries the operand shapes in its layout constraints
instead. Either way the result's shape comes first and the key planes are
the one ``u32`` operand of the same width after ``custom-call(``.
"""

import re

WORD_BYTES = 4

_RESULT = re.compile(r"= u32\[(\d+),(\d+)\]")
_PLANES = re.compile(r"u32\[(\d+),(\d+)\]")


def key_rows(k_pad, block_rows, words):
    """(operations, bytes) of one call: the key planes read once, the
    block written once."""
    ops = float(words) * block_rows * k_pad
    nbytes = float(WORD_BYTES) * words * (k_pad + block_rows)
    return ops, nbytes


def from_text(text):
    result = _RESULT.search(text)
    at = text.find("custom-call(")
    if result is None or at < result.end():
        return None
    rows, words = map(int, result.groups())
    for k_pad, w in _PLANES.findall(text[at:]):
        if int(w) == words:
            return key_rows(int(k_pad), rows, words)
    return None
