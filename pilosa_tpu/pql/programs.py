"""Pre-compiled per-query-family programs over resident device planes.

The warm path is one dispatch and one fetch per query: instead of
the executor's per-op Python loop — each ``B.plane_*`` a separate jitted
dispatch, each paying launch setup — a maskable bitmap call tree lowers
to an *op tape* (a register machine whose initial registers are resident
leaf planes and whose ops are the four bitmap combinators), and the tape
plus its terminal (popcount-reduce or plane materialization) compiles to
ONE executable via parallel/mesh.py (shard_map + ``lax.psum`` for
counts, donated scratch for planes). Programs are cached per
(tape, shape-bucket, mesh epoch): query *families* share executables —
``Count(Intersect(Row(f=1), Row(g=2)))`` and
``Count(Intersect(Row(a=7), Row(b=9)))`` lower to the same tape and hit
the same compiled program with different leaf planes.

Lowering never re-stages data: leaves are slices of the budget-managed
resident stacks (core/stacked.py), so a warm query's trace carries no
``stack.build`` / ``device.h2d_copy`` stage at all. Anything the tape
cannot express bit-identically (ConstRow, UnionRows, Shift, Distinct,
host-scan calls) bails to the executor's classic path, which is also the
reference tests/test_resident.py holds the programs to (it sets
``ENABLED`` False for the reference pass).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import List, Optional, Tuple

import jax.numpy as jnp

from pilosa_tpu import platform
from pilosa_tpu.core import timeq
from pilosa_tpu.obs import devprof
from pilosa_tpu.obs.stages import query_stage
from pilosa_tpu.core.stacked import stacked_set
from pilosa_tpu.ops import bitmap as B
from pilosa_tpu.ops import pallas_util as PU
from pilosa_tpu.pql.ast import Condition, ROW_OPTIONS
from pilosa_tpu.shardwidth import WORDS_PER_SHARD

#: Consulted per query: False sends every call tree down the classic
#: per-op path.
ENABLED = True


class _Bail(Exception):
    """Call tree not expressible as a tape — fall back to the classic
    per-op path (which also owns raising the user-visible PQLError for
    genuinely malformed trees, keeping error behavior identical)."""


# ---------------------------------------------------------------------------
# Compiled-program cache: bounded, keyed by query family. The tape is
# structural (ops reference register indices, never data), so the key is
# exactly the (family, shape-bucket) of the issue spec. Mesh epoch is in
# the key because a mesh switch changes placements and collectives.
# ---------------------------------------------------------------------------

_PROGRAMS_CAP = 64
_PROGRAMS: "OrderedDict[Tuple, object]" = OrderedDict()
_PROGRAMS_LOCK = threading.Lock()


def _program(kind: str, tape: Tuple, n_leaves: int, masked: bool,
             total_words: int):
    from pilosa_tpu.parallel import mesh

    # Count terminals may route to the Pallas popcount-reduce; the mode
    # token tracks the routing decision (kill switch / forced interpret
    # / strike-out) so flipping it can't serve a stale executable.
    token = PU.mode_token() if kind == "count" else None
    key = (kind, tape, n_leaves, masked, total_words, mesh.mesh_epoch(),
           token)
    with _PROGRAMS_LOCK:
        fn = _PROGRAMS.get(key)
        if fn is not None:
            _PROGRAMS.move_to_end(key)
            return fn
    if kind == "count":
        fn = mesh.compile_tape_count(tape, masked, total_words)
    else:
        fn = mesh.compile_tape_plane(tape, masked)
    with _PROGRAMS_LOCK:
        fn = _PROGRAMS.setdefault(key, fn)
        _PROGRAMS.move_to_end(key)
        while len(_PROGRAMS) > _PROGRAMS_CAP:
            _PROGRAMS.popitem(last=False)
    return fn


def program_cache_len() -> int:
    with _PROGRAMS_LOCK:
        return len(_PROGRAMS)


def scratch_plane(total_words: int) -> jnp.ndarray:
    """Scratch for the plane terminal. Where donation is real (device
    backends) the buffer is consumed by the program, so it must be
    fresh; on CPU donation is gated off and the shared zeros plane
    serves every query at zero allocations."""
    if platform.backend_supports_donation():
        return jnp.zeros((total_words,), dtype=jnp.uint32)
    return B.device_zeros(total_words)


# ---------------------------------------------------------------------------
# Lowering: call tree -> (tape, leaf sources), then sources -> leaf planes.
# Mirrors executor._eval_all / _eval_row bit-for-bit for the families it
# accepts; everything else bails. The first half is host work alone (the
# read's ``plan`` stage): it walks the tree, resolves fields, keys and
# views, and says what each leaf plane is made from; the second looks the
# stacks up and slices or computes the planes on the device. Leaf refs
# are ("L", i) and op refs ("O", j) during lowering and are remapped to
# flat register indices afterwards (leaves occupy registers [0, n); op j
# lands at n + j).
# ---------------------------------------------------------------------------


def _plan_root(ex, idx, call) -> Tuple[Tuple, List[Tuple]]:
    """``(tape, sources)``: one source a leaf register, in register
    order: ``("row", field, view, row)``, ``("bsi", field, value)``,
    ``("exists",)`` or ``("zeros",)``. Raises ``_Bail`` for a tree the
    tape cannot express."""
    sources: List[Tuple] = []
    tape_raw: List[Tuple] = []

    def leaf(*source):
        sources.append(source)
        return ("L", len(sources) - 1)

    def emit(op, a, b):
        tape_raw.append((op, a, b))
        return ("O", len(tape_raw) - 1)

    def lower_row(c):
        from pilosa_tpu.pql.executor import _parse_ts

        fa = c.field_arg(exclude=ROW_OPTIONS)
        if fa is None:
            raise _Bail  # fallback raises the PQLError
        fname, value = fa
        field = idx.field(fname)
        if isinstance(value, Condition) or field.options.type.is_bsi:
            # the BSI compare circuit is one jitted program of its own;
            # its output plane composes as a leaf
            return leaf("bsi", field, value)
        row = ex._row_id(field, value)
        if row is None:  # unknown key -> empty row
            return leaf("zeros")
        from_a, to_a = c.arg("from"), c.arg("to")
        if from_a is not None or to_a is not None:
            views = field.range_views(
                _parse_ts(from_a) if from_a is not None else None,
                _parse_ts(to_a) if to_a is not None else None)
            out = leaf("zeros")
            for v in views:
                out = emit("or", out, leaf("row", field, v, row))
            return out
        return leaf("row", field, timeq.VIEW_STANDARD, row)

    def lower(c):
        name = c.name
        if name == "Row":
            return lower_row(c)
        if name in ("Union", "Xor"):
            if not c.children:
                return leaf("zeros")
            refs = [lower(ch) for ch in c.children]
            out = refs[0]
            opn = "or" if name == "Union" else "xor"
            for r in refs[1:]:
                out = emit(opn, out, r)
            return out
        if name == "Intersect":
            if not c.children:
                raise _Bail
            refs = [lower(ch) for ch in c.children]
            out = refs[0]
            for r in refs[1:]:
                out = emit("and", out, r)
            return out
        if name == "Difference":
            if not c.children:
                raise _Bail
            out = lower(c.children[0])
            for ch in c.children[1:]:
                out = emit("andnot", out, lower(ch))
            return out
        if name == "Not":
            if len(c.children) != 1:
                raise _Bail
            ex_ref = leaf("exists")
            return emit("andnot", ex_ref, lower(c.children[0]))
        if name == "All":
            return leaf("exists")
        raise _Bail

    root = lower(call)
    n = len(sources)

    def remap(ref):
        return ref[1] if ref[0] == "L" else n + ref[1]

    tape = tuple((op, remap(a), remap(b)) for op, a, b in tape_raw)
    root_idx = remap(root)
    if root_idx != n + len(tape) - 1:
        # the program returns the LAST register; or(x, x) == x pins the
        # root there when it isn't already (bare-leaf roots)
        tape = tape + (("or", root_idx, root_idx),)
    return tape, sources


def _leaf_planes(ex, idx, sources: List[Tuple], shard_list: List[int]):
    """The leaf planes ``sources`` name, in register order: slices of
    the resident stacks, BSI compares, the existence row, zeros."""
    total_words = len(shard_list) * WORDS_PER_SHARD
    planes = []
    for kind, *args in sources:
        if kind == "row":
            field, view, row = args
            planes.append(stacked_set(field, shard_list, view).row_plane(row))
        elif kind == "bsi":
            planes.append(ex._eval_bsi_row(*args, shard_list))
        elif kind == "exists":
            planes.append(ex._existence_all(idx, shard_list))
        else:
            planes.append(B.device_zeros(total_words))
    return planes


def _lower(ex, idx, call, shard_list: List[int]):
    """``(tape, leaf planes)``, or None when the tree bails; the host
    half runs as the read's ``plan`` stage."""
    with query_stage("plan"):
        try:
            tape, sources = _plan_root(ex, idx, call)
        except _Bail:
            return None
    return tape, _leaf_planes(ex, idx, sources, shard_list)


# ---------------------------------------------------------------------------
# Entry points (executor warm path). Return None to mean "not lowered —
# run the classic path"; StackStale and PQLError raised during lowering
# propagate exactly as the classic path would raise them.
# ---------------------------------------------------------------------------


def _invoke(kind: str, tape: Tuple, n_leaves: int, masked: bool,
            total_words: int, fn, *args):
    """Run one compiled program, attributing its device time and
    analytic FLOP/byte cost to the tape's kernel family when the devprof
    plane is on. The flag check is the entire disabled-path cost."""
    if not devprof.ENABLED:
        return fn(*args)
    with devprof.kernel_scope(kind, tape, n_leaves, masked, total_words):
        return fn(*args)


def run_count(ex, idx, call, shard_list: List[int], mask) -> Optional[object]:
    """Device count scalar for ``Count(call)`` via one compiled program,
    or None when lowering bails/is disabled."""
    if not ENABLED or not shard_list:
        return None
    lowered = _lower(ex, idx, call, shard_list)
    if lowered is None:
        return None
    tape, leaves = lowered
    total_words = len(shard_list) * WORDS_PER_SHARD
    masked = mask is not None
    fn = _program("count", tape, len(leaves), masked, total_words)
    args = (*leaves, mask.plane) if masked else tuple(leaves)
    try:
        out = _invoke("count", tape, len(leaves), masked, total_words,
                      fn, *args)
    except Exception as e:
        if not getattr(fn, "pallas_terminal", False):
            raise
        # One strike pins the terminal to the classic reduce: a Pallas
        # lowering bug here would otherwise fail every count family.
        PU.disable_kernel("tape_count")
        PU.failed("tape_count", e)
        fn = _program("count", tape, len(leaves), masked, total_words)
        out = _invoke("count", tape, len(leaves), masked, total_words,
                      fn, *args)
    if getattr(fn, "pallas_terminal", False):
        PU.dispatched("tape_count")
    return out


def run_plane(ex, idx, call, shard_list: List[int], mask) -> Optional[object]:
    """Materialized (masked) plane for a bitmap call via one compiled
    program with donated scratch, or None when lowering bails."""
    if not ENABLED or not shard_list:
        return None
    lowered = _lower(ex, idx, call, shard_list)
    if lowered is None:
        return None
    tape, leaves = lowered
    total_words = len(shard_list) * WORDS_PER_SHARD
    masked = mask is not None
    fn = _program("plane", tape, len(leaves), masked, total_words)
    scratch = scratch_plane(total_words)
    if masked:
        return _invoke("plane", tape, len(leaves), True, total_words,
                       fn, scratch, *leaves, mask.plane)
    return _invoke("plane", tape, len(leaves), False, total_words,
                   fn, scratch, *leaves)
