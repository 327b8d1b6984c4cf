"""Shared dispatch policy for the Pallas L0 kernel plane.

Every Pallas kernel in ``ops/`` (pair counts, BSI sum/compare, TopN row
counts, the ingest scatter, the compressed-tile popcount ``ctile_count``,
and the tape-count terminal) routes its go/no-go decision through
:func:`why_not` so the CPU/interpret/alignment
rules cannot drift per-file, and records the outcome on the metrics
registry so silent degradation to the classic XLA path is visible on the
timeline:

    ops_pallas_dispatch_total{kernel}        successful Pallas dispatches
    ops_pallas_mesh_dispatch_total{kernel}   those of them that ran per
                                             chip under shard_map + psum
    ops_pallas_body_total{kernel,body}       those of the pair-count family
                                             by the kernel body their
                                             operands' heights chose
    ops_pallas_fallback_total{kernel,why}    classic-path fallbacks

Mode selection (``PILOSA_TPU_PALLAS``):

* unset  — Pallas compiled on TPU backends, classic path elsewhere.
* ``0``  — kill switch: classic path everywhere, zero Pallas overhead
  (the fallback counter is deliberately NOT ticked so the switch costs
  nothing; ``PILOSA_TPU_NO_PALLAS=1`` is the legacy spelling).
* ``1``  — force: Pallas even off-TPU, via ``interpret=True`` so tier-1
  CPU runs exercise the exact kernel code path (bit-identity oracle).

A kernel that raises at dispatch time is counted (``why="error"``) and
after :data:`MAX_FAILURES` strikes is disabled for the process — a real
lowering bug must not burn a compile attempt on every query.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

from pilosa_tpu import platform
from pilosa_tpu.obs import metrics as M

log = logging.getLogger(__name__)

#: dispatch failures tolerated per kernel before it is pinned off
MAX_FAILURES = 3

#: interpret-mode width cap (words). Forcing Pallas off-TPU runs the
#: kernels under the interpreter as a bit-identity vehicle; shard-scale
#: widths add no kernel coverage there and cost seconds per dispatch
#: (vs µs classic), so wider inputs stay on the classic path
#: (why="interpret"). The parity battery and the --configs 20 gate
#: exercise every kernel body well under this cap.
INTERPRET_MAX_WORDS = 1 << 13

#: most sublane rows one grid step of a row-blocked kernel takes
#: (64 x 512 uint32 words = one shard plane, 128 KiB of VMEM per buffer)
MAX_BLOCK_ROWS = 64

_FAILURES: dict = {}
_LOCK = threading.Lock()

_OFF = ("0", "false", "no", "off")
_ON = ("1", "true", "yes", "on", "force")


def _env() -> str:
    return os.environ.get("PILOSA_TPU_PALLAS", "").strip().lower()


def disabled() -> bool:
    """Kill switch engaged (``PILOSA_TPU_PALLAS=0`` or the legacy
    ``PILOSA_TPU_NO_PALLAS=1``)."""
    return _env() in _OFF and _env() != "" \
        or bool(os.environ.get("PILOSA_TPU_NO_PALLAS"))


def forced() -> bool:
    """Pallas forced on even off-TPU (``PILOSA_TPU_PALLAS=1``)."""
    return _env() in _ON


def use_interpret() -> bool:
    """Run kernels under the Pallas interpreter (non-TPU backends) —
    same kernel code, no Mosaic, bit-identical by construction."""
    return platform.default_backend() != "tpu"


def why_not(kernel: str, *arrays, max_rows: Optional[int] = None
            ) -> Optional[str]:
    """``None`` when the Pallas path should run for ``kernel``, else the
    fallback reason: ``disabled`` | ``failures`` | ``tracer`` | ``mesh``
    | ``shape`` | ``interpret`` | ``backend``. Shape rules: every array
    2-D with a non-zero minor axis; the first at most ``max_rows`` rows
    when given; in interpret mode no array wider than
    :data:`INTERPRET_MAX_WORDS`. Mesh rule: a compiled ``pallas_call``
    whose operand is sharded over several devices is refused at lowering
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map."), so the answer is ``"mesh"`` and the
    caller takes the partitionable XLA path — unless its family has a
    ``shard_map`` program of its own for operands placed as the engine
    places them (the pair-count family: ``ops/groupby._mesh_route``);
    the answer here is the same either way. The interpreter lowers to
    ordinary XLA ops and is exempt."""
    if disabled():
        return "disabled"
    with _LOCK:
        if _FAILURES.get(kernel, 0) >= MAX_FAILURES:
            return "failures"
    import jax

    for x in arrays:
        if isinstance(x, jax.core.Tracer):
            return "tracer"
    if not use_interpret():
        for x in arrays:
            sharding = getattr(x, "sharding", None)
            if sharding is not None and len(sharding.device_set) > 1:
                return "mesh"
    if arrays:
        a = arrays[0]
        for x in arrays:
            if getattr(x, "ndim", None) != 2 or x.shape[-1] == 0:
                return "shape"
        if max_rows is not None and a.shape[0] > max_rows:
            return "shape"
        if use_interpret() and max(
                x.shape[-1] for x in arrays) > INTERPRET_MAX_WORDS:
            return "interpret"
    if platform.default_backend() == "tpu" or forced():
        return None
    return "backend"


def block_rows(n_rows: int) -> Optional[int]:
    """Sublane rows per grid step for a kernel streaming an
    ``(n_rows, lanes)`` array in row blocks. Mosaic refuses a block whose
    second-minor dim is neither a multiple of 8 nor the whole array dim,
    so: the whole array when it fits one block, else the largest
    power-of-two divisor in 8..:data:`MAX_BLOCK_ROWS` (96 rows stream as
    32, not 48); ``None`` when there is none (caller routes to the
    classic path, ``why="shape"``)."""
    if n_rows <= MAX_BLOCK_ROWS:
        return n_rows
    r = MAX_BLOCK_ROWS
    while r >= 8:
        if n_rows % r == 0:
            return r
        r //= 2
    return None


def mode_token() -> str:
    """Cache-key token for compiled programs whose terminal may route to
    Pallas — changes whenever the routing decision would, so flipping
    the kill switch (or striking out) invalidates stale executables."""
    if why_not("tape_count") is not None:
        return "classic"
    return "interpret" if use_interpret() else "tpu"


def dispatched(kernel: str, on_mesh: bool = False,
               body: Optional[str] = None) -> None:
    """One successful Pallas dispatch; ``on_mesh`` when it was the
    family's per-chip program under ``shard_map`` + ``psum``, so a scrape
    can tell the two placements apart; ``body`` (``vpu`` | ``mxu``) when
    it ran the pair-count kernel, whose body goes by its operands'
    heights (``ops/groupby.pallas_body``)."""
    M.REGISTRY.count(M.METRIC_OPS_PALLAS_DISPATCH, kernel=kernel)
    if on_mesh:
        M.REGISTRY.count(M.METRIC_OPS_PALLAS_MESH_DISPATCH, kernel=kernel)
    if body is not None:
        M.REGISTRY.count(M.METRIC_OPS_PALLAS_BODY, kernel=kernel, body=body)


def fallback(kernel: str, why: str) -> None:
    # the kill switch must cost nothing: not even a counter tick
    if why != "disabled":
        M.REGISTRY.count(M.METRIC_OPS_PALLAS_FALLBACK, kernel=kernel,
                         why=why)


def failed(kernel: str, exc: BaseException) -> None:
    """Record a dispatch-time failure; after MAX_FAILURES the kernel is
    pinned to the classic path for the process."""
    with _LOCK:
        n = _FAILURES[kernel] = _FAILURES.get(kernel, 0) + 1
    log.warning("pallas %s failed (%d/%d): %s — using classic path",
                kernel, n, MAX_FAILURES, exc)
    fallback(kernel, "error")


def disable_kernel(kernel: str) -> None:
    """Pin a kernel to the classic path immediately (used by the tape
    terminal, where one failure means every query of that family)."""
    with _LOCK:
        _FAILURES[kernel] = MAX_FAILURES


def reset_failures() -> None:
    """Test hook: forget strike counts."""
    with _LOCK:
        _FAILURES.clear()


def kernel_scope(op: str, d1: int, d2: int, n_inputs: int,
                 total_words: int):
    """devprof attribution scope for one Pallas dispatch. ``op`` is the
    pallas cost family (``mm`` | ``cmp`` | ``scatter`` | ``pop``),
    ``d1``/``d2``
    its two dimension parameters (see devprof.tape_cost). No-op scope
    when profiling is off."""
    from pilosa_tpu.obs import devprof

    if not devprof.ENABLED:
        return devprof.NULL_SCOPE
    return devprof.kernel_scope(
        "pallas", ((op, int(d1), int(d2)),), n_inputs, False,
        int(total_words))
