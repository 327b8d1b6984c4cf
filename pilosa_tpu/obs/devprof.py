"""Kernel performance attribution plane: device cost model + MFU/roofline.

The tracing plane attributes *latency*; this plane attributes
*efficiency*. The two biggest ROADMAP items — the bulk-bitwise Pallas
rewrite (c3: 15.4 TFLOPS at 3.9% MFU) and the streaming-ingest gap —
need FLOPs, bytes moved, and achieved-vs-peak per kernel and per
pipeline stage. Three pieces:

**Analytic cost model.** A compiled op tape (pql/programs.py) is a
register machine over uint32 word-planes: each binary op touches every
word of ``total_words`` once, and one uint32 word op is 32 bit-lanes of
work. Costs are *conventions*, stated once here so every gauge is
comparable across PRs:

- FLOPs  = 32 * total_words * (len(tape) + mask-AND + popcount pass)
- HBM    = 4 * total_words * (leaf planes read + mask plane
           + scratch write for the plane terminal) [+ 8B count scalar]

The operational intensity (FLOPs/byte) of these tapes sits far below
the backend ridge point, which is the quantitative form of the PIMDAL
argument: the bitmap combinators are memory-bound, so the Pallas work
should chase bytes, not flops.

**KernelProfileRegistry.** Keyed on ``(family, shape_bucket,
mesh_epoch)`` where *family* is a readable tape signature
(``count/2l/and1#a1b2c3``), *shape_bucket* the next power of two of
``total_words``, and *mesh_epoch* from parallel/mesh (a mesh switch
changes placements and collectives, so profiles must not mix). Device
time comes from hooks installed into ``platform.guarded_call``'s
existing dispatch / block_until_ready split and attributed via a
thread-local set by ``kernel_scope`` (the compiled program runs
synchronously on the calling thread). Dispatches outside any scope
(BSI compare circuits, classic-path jits, collectives) aggregate under
an ``other`` bucket so total device-time coverage stays visible.

Ingest stage accounting is not part of this plane any more: it is always
on and lives in ``obs/stages.py``; ``stats_json`` and the timeline probe
still show its snapshot beside the kernels.

Zero-cost when disabled: ``ENABLED`` is False by default
(``PILOSA_TPU_DEVPROF=1`` turns it on), every instrumentation site
guards on the module flag before touching this module's state, and the
platform hooks are only installed while enabled — the disabled path
adds no allocations (``cost_evals()`` + ``KERNELS.allocations`` back
the zero-work assert of tests/test_devprof.py). Hook callbacks run *after* the
dispatch guard is released and do pure in-memory appends, so the
leaf-lock rule is untouched.

Measurement caveat: on CPU the guard blocks until ready, so device time
is real wall time; on async device backends the dispatch wall time is a
launch-overhead floor and MFU is an upper bound unless the caller
blocks on the result inside the measured window.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from pilosa_tpu.analysis import locktrace
from pilosa_tpu import platform
from pilosa_tpu.config import env_bool
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.obs.stages import INGEST

#: Module switch consulted by every kernel instrumentation site
#: (programs, Pallas dispatches). Flip via enable()/disable() so
#: the platform hooks stay in sync; operators use the env var.
ENABLED = env_bool("PILOSA_TPU_DEVPROF", False)

WORD_BYTES = 4   # planes are uint32 words
BIT_LANES = 32   # one uint32 bitwise op = 32 bit-ops ("flops" here)

#: (peak int8 TOP/s, peak HBM GB/s) keyed by ``device_kind`` exactly as
#: JAX reports it. "TPU v5 lite" is one TPU v5e chip: 393 TOP/s int8,
#: 819 GB/s of HBM (Google Cloud documentation, "TPU v5e"). ``cpu`` is an
#: order-of-magnitude host figure: MFU on CPU is a relative gauge for the
#: tests, not a datasheet claim. A device that is not in the table is an
#: error, not a default — read its kind on the chip and add the row with
#: its source. Override per deployment with
#: PILOSA_TPU_DEVPROF_PEAK_TFLOPS / PILOSA_TPU_DEVPROF_PEAK_GBPS.
PEAK_TABLE: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (393.0, 819.0),
    "cpu": (0.5, 25.0),
}

_DEVICE_KIND: Optional[str] = None

# Cost-model evaluation counter: the "exactly zero cost-model work when
# disabled" checks (tests/test_devprof.py, tier1 devprof lane) snapshot it.
_COST_EVALS = 0

_TLS = threading.local()

#: Shared no-op context for disabled-path call sites (never allocate
#: a fresh nullcontext per batch when the plane is off).
NULL_SCOPE = contextlib.nullcontext()


def device_kind() -> str:
    """``device_kind`` of the device this process computes on, resolved
    lazily and cached (jax must not be imported just because devprof
    was)."""
    global _DEVICE_KIND
    if _DEVICE_KIND is None:
        import jax

        _DEVICE_KIND = jax.devices()[0].device_kind
    return _DEVICE_KIND


def peaks_for(kind: str) -> Tuple[float, float]:
    """The table row for ``kind``; an unknown device raises."""
    try:
        return PEAK_TABLE[kind]
    except KeyError:
        raise LookupError(
            f"devprof has no peak figures for device kind {kind!r}; add "
            f"its row (with the source) to PEAK_TABLE") from None


def peaks() -> Tuple[float, float]:
    """(peak int8 TOP/s, peak HBM GB/s) for the active device with env
    overrides applied."""
    tf, gb = peaks_for(device_kind())
    try:
        tf = float(os.environ.get("PILOSA_TPU_DEVPROF_PEAK_TFLOPS", tf))
        gb = float(os.environ.get("PILOSA_TPU_DEVPROF_PEAK_GBPS", gb))
    except (TypeError, ValueError):
        pass
    return tf, gb


def cost_evals() -> int:
    """How many times the cost model has run (0 while disabled)."""
    return _COST_EVALS


def tape_cost(kind: str, tape: Tuple, n_leaves: int, masked: bool,
              total_words: int) -> Tuple[float, float]:
    """Analytic (FLOPs, HBM bytes) for ONE dispatch of a compiled tape
    over ``total_words`` uint32 words (conventions in the module doc)."""
    global _COST_EVALS
    _COST_EVALS += 1
    if kind == "pallas":
        # Pallas kernel-plane families (ops/pallas_util.kernel_scope):
        # one 3-tuple tape entry (op, d1, d2). Conventions:
        #   mm      bit-expand int8 MXU matmul C[d1, d2] contracting
        #           32*total_words 0/1 lanes: 2*d1*d2*32*W FLOPs; HBM =
        #           packed operand streams + the int32 result.
        #   cmp     fused VPU compare walk, d1=depth, d2=constant sides:
        #           ~6 word-ops per (plane, sign class, side) + 8 for
        #           the sign partition/select; reads 2+depth planes,
        #           writes one result plane.
        #   scatter ingest merge+count pass (or + popcount-andnot):
        #           reads planes+updates, writes merged.
        op, d1, d2 = tape[0]
        if op == "mm":
            flops = 2.0 * d1 * d2 * BIT_LANES * total_words
            hbm = float(WORD_BYTES) * (d1 + d2) * total_words \
                + 4.0 * d1 * d2
        elif op == "cmp":
            word_ops = 6 * d1 * d2 + 8
            flops = float(BIT_LANES) * word_ops * total_words
            hbm = float(WORD_BYTES) * (3 + d1) * total_words
        elif op == "scatter":
            flops = float(BIT_LANES) * 2.0 * total_words
            hbm = float(WORD_BYTES) * 3.0 * total_words
        elif op == "pop":
            # ctile_count: per-row popcount reduce over d1 payload tiles
            # of total_words words each; reads the packed payload, writes
            # one int32 per tile
            flops = float(BIT_LANES) * 2.0 * d1 * total_words
            hbm = float(WORD_BYTES) * d1 * total_words + 4.0 * d1
        else:
            raise ValueError(f"unknown pallas cost family {op!r}")
        return flops, hbm
    word_ops = len(tape) + (1 if masked else 0)
    if kind == "count":
        word_ops += 1  # the popcount reduction pass
    flops = float(BIT_LANES) * word_ops * total_words
    planes = n_leaves + (1 if masked else 0) + (1 if kind == "plane" else 0)
    hbm = float(WORD_BYTES) * planes * total_words \
        + (8.0 if kind == "count" else 0.0)
    return flops, hbm


def family_name(kind: str, tape: Tuple, n_leaves: int,
                masked: bool) -> str:
    """Readable per-family label: terminal kind, leaf count, op mix, a
    mask tag, and a short structural digest to keep distinct tapes with
    the same mix apart (``count/2l/and1#a1b2c3``)."""
    mix: Dict[str, int] = {}
    for op, _a, _b in tape:
        mix[op] = mix.get(op, 0) + 1
    ops = "+".join(f"{k}{v}" for k, v in sorted(mix.items())) or "leaf"
    sig = hashlib.sha1(
        repr((kind, tape, n_leaves, masked)).encode()).hexdigest()[:6]
    return f"{kind}/{n_leaves}l/{ops}{'/m' if masked else ''}#{sig}"


def shape_bucket(total_words: int) -> int:
    """Next power of two >= total_words (profiles pool across nearby
    shard counts instead of fragmenting per exact shape)."""
    b = 1
    while b < total_words:
        b <<= 1
    return b


class KernelProfile:
    """Accumulated totals for one (family, shape_bucket, mesh_epoch)."""

    __slots__ = ("family", "bucket", "mesh_epoch", "dispatches",
                 "dispatch_s", "block_s", "flops", "hbm_bytes",
                 "pending_flops", "pending_bytes")

    def __init__(self, family: str, bucket: int, mesh_epoch: int):
        self.family = family
        self.bucket = bucket
        self.mesh_epoch = mesh_epoch
        self.dispatches = 0
        self.dispatch_s = 0.0
        self.block_s = 0.0
        self.flops = 0.0
        self.hbm_bytes = 0.0
        # registry-counter publication lag (flushed every 16th dispatch
        # so the hot hook does 3 registry ops, not 7)
        self.pending_flops = 0.0
        self.pending_bytes = 0.0


class KernelProfileRegistry:
    """Thread-safe accumulator behind the ``device_kernel_*`` series and
    ``GET /internal/stats/kernels``. Process-global, so an in-process
    LocalCluster's coordinator endpoint sees every node's dispatches."""

    def __init__(self) -> None:
        self._lock = locktrace.tracked_lock("obs.devprof.kernels")
        self._profiles: Dict[Tuple[str, int, int], KernelProfile] = {}
        # (kind, tape, n_leaves, masked, total_words, epoch) ->
        # (profile, flops/dispatch, bytes/dispatch); re-derivable, so a
        # plain clear bounds it
        self._by_call: Dict[Tuple, Tuple[KernelProfile, float, float]] = {}
        #: profiles + call-cache entries ever created — the
        #: zero-allocations-when-disabled gate reads this
        self.allocations = 0
        self.other_dispatches = 0
        self.other_device_s = 0.0
        self.h2d_copies = 0
        self.h2d_bytes = 0
        self.h2d_seconds = 0.0

    def entry_for(self, kind: str, tape: Tuple, n_leaves: int,
                  masked: bool, total_words: int, epoch: int):
        ckey = (kind, tape, n_leaves, masked, total_words, epoch)
        with self._lock:
            ent = self._by_call.get(ckey)
            if ent is None:
                fam = family_name(kind, tape, n_leaves, masked)
                flops, nbytes = tape_cost(kind, tape, n_leaves, masked,
                                          total_words)
                pkey = (fam, shape_bucket(total_words), epoch)
                prof = self._profiles.get(pkey)
                if prof is None:
                    prof = KernelProfile(*pkey)
                    self._profiles[pkey] = prof
                    self.allocations += 1
                if len(self._by_call) >= 256:
                    self._by_call.clear()
                ent = (prof, flops, nbytes)
                self._by_call[ckey] = ent
                self.allocations += 1
            return ent

    def record(self, ent, dispatch_s: float, block_s: float) -> None:
        device_s = dispatch_s + block_s
        reg = M.REGISTRY
        if ent is None:
            with self._lock:
                self.other_dispatches += 1
                self.other_device_s += device_s
            reg.count(M.METRIC_KERNEL_DISPATCHES, family="other")
            reg.count(M.METRIC_KERNEL_DEVICE_SECONDS, device_s,
                      family="other")
            return
        prof, flops, nbytes = ent
        with self._lock:
            prof.dispatches += 1
            prof.dispatch_s += dispatch_s
            prof.block_s += block_s
            prof.flops += flops
            prof.hbm_bytes += nbytes
            prof.pending_flops += flops
            prof.pending_bytes += nbytes
            flush = (prof.dispatches - 1) % 16 == 0
            if flush:
                flush_flops = prof.pending_flops
                flush_bytes = prof.pending_bytes
                prof.pending_flops = 0.0
                prof.pending_bytes = 0.0
                total_s = prof.dispatch_s + prof.block_s
                total_flops = prof.flops
                total_bytes = prof.hbm_bytes
        fam = prof.family
        reg.count(M.METRIC_KERNEL_DISPATCHES, family=fam)
        reg.count(M.METRIC_KERNEL_DEVICE_SECONDS, device_s, family=fam)
        reg.observe_bucketed(M.METRIC_KERNEL_DISPATCH_US, device_s * 1e6,
                             M.KERNEL_DISPATCH_BUCKETS_US, family=fam)
        # flop/byte counters and the derived MFU/GB/s gauges publish on
        # the 1st and every 16th dispatch per profile (accumulated deltas
        # flush, so registry totals stay exact with at most 15 dispatches
        # of lag) — the hot hook does 3 registry ops, not 7;
        # snapshot()/stats_json() always derive fresh from the profile
        if flush:
            reg.count(M.METRIC_KERNEL_FLOPS, flush_flops, family=fam)
            reg.count(M.METRIC_KERNEL_HBM_BYTES, flush_bytes, family=fam)
            if total_s > 0:
                peak_tf, peak_gb = peaks()
                reg.gauge(M.METRIC_KERNEL_MFU_PCT,
                          100.0 * (total_flops / total_s / 1e12) / peak_tf,
                          family=fam)
                reg.gauge(M.METRIC_KERNEL_GBPS,
                          total_bytes / total_s / 1e9, family=fam)

    def record_h2d(self, nbytes: int, seconds: float) -> None:
        with self._lock:
            self.h2d_copies += 1
            self.h2d_bytes += nbytes
            self.h2d_seconds += seconds
        reg = M.REGISTRY
        reg.count(M.METRIC_KERNEL_H2D_BYTES, nbytes)
        reg.count(M.METRIC_KERNEL_H2D_SECONDS, seconds)

    def h2d_json(self) -> dict:
        with self._lock:
            copies, nbytes, secs = (self.h2d_copies, self.h2d_bytes,
                                    self.h2d_seconds)
        out = {"copies": copies, "bytes": nbytes,
               "seconds": round(secs, 6)}
        if secs > 0:
            out["achieved_gbps"] = round(nbytes / secs / 1e9, 4)
        return out

    def snapshot(self, limit: Optional[int] = None) -> List[dict]:
        """Per-profile totals plus the derived roofline reads, sorted by
        device time (the 'where is the machine actually going' order)."""
        peak_tf, peak_gb = peaks()
        ridge = (peak_tf * 1e12) / (peak_gb * 1e9)  # FLOPs per byte
        with self._lock:
            profs = list(self._profiles.values())
            rows = [(p.family, p.bucket, p.mesh_epoch, p.dispatches,
                     p.dispatch_s, p.block_s, p.flops, p.hbm_bytes)
                    for p in profs]
        out = []
        for fam, bucket, epoch, n, disp_s, blk_s, flops, nbytes in rows:
            device_s = disp_s + blk_s
            d = {"family": fam, "shape_bucket": bucket,
                 "mesh_epoch": epoch, "dispatches": n,
                 "device_seconds": round(device_s, 6),
                 "dispatch_seconds": round(disp_s, 6),
                 "block_seconds": round(blk_s, 6),
                 "flops": flops, "hbm_bytes": nbytes}
            if nbytes > 0:
                intensity = flops / nbytes
                d["intensity_flops_per_byte"] = round(intensity, 4)
                d["roofline_bound"] = ("memory" if intensity < ridge
                                       else "compute")
            if device_s > 0 and n > 0:
                tflops = flops / device_s / 1e12
                gbps = nbytes / device_s / 1e9
                d["achieved_tflops"] = round(tflops, 6)
                d["achieved_gbps"] = round(gbps, 4)
                d["mfu_pct"] = round(100.0 * tflops / peak_tf, 4)
                d["bw_util_pct"] = round(100.0 * gbps / peak_gb, 4)
                d["us_per_dispatch"] = round(device_s / n * 1e6, 2)
            out.append(d)
        out.sort(key=lambda d: -d["device_seconds"])
        return out[:limit] if limit is not None else out

    def profile_count(self) -> int:
        with self._lock:
            return len(self._profiles)

    def reset(self) -> None:
        with self._lock:
            self._profiles.clear()
            self._by_call.clear()
            self.other_dispatches = 0
            self.other_device_s = 0.0
            self.h2d_copies = 0
            self.h2d_bytes = 0
            self.h2d_seconds = 0.0


KERNELS = KernelProfileRegistry()


# ---------------------------------------------------------------------------
# Attribution scopes + platform hooks
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def kernel_scope(kind: str, tape: Tuple, n_leaves: int, masked: bool,
                 total_words: int):
    """Attribute guarded_call dispatches on this thread to the compiled
    tape's kernel family (callers gate on ``ENABLED`` first). Nests:
    inner scopes win, which is right — the innermost compiled program is
    the one actually launching."""
    from pilosa_tpu.parallel import mesh

    ent = KERNELS.entry_for(kind, tape, n_leaves, masked, total_words,
                            mesh.mesh_epoch())
    prev = getattr(_TLS, "kernel", None)
    _TLS.kernel = ent
    try:
        yield
    finally:
        _TLS.kernel = prev


def _on_dispatch(dispatch_s: float, block_s: float) -> None:
    KERNELS.record(getattr(_TLS, "kernel", None), dispatch_s, block_s)


def _on_h2d(nbytes: int, seconds: float) -> None:
    KERNELS.record_h2d(nbytes, seconds)


def enable() -> None:
    global ENABLED
    ENABLED = True
    platform.set_profile_hooks(_on_dispatch, _on_h2d)


def disable() -> None:
    global ENABLED
    ENABLED = False
    platform.set_profile_hooks(None, None)


def reset() -> None:
    """Clear accumulated profiles/stages. Leaves the enable state and
    the cost-eval counter alone."""
    KERNELS.reset()
    INGEST.reset()


# ---------------------------------------------------------------------------
# Serving: /internal/stats/kernels payload + timeline probe
# ---------------------------------------------------------------------------


def stats_json() -> dict:
    """Payload for ``GET /internal/stats/kernels``."""
    if not ENABLED and not KERNELS.profile_count():
        return {"enabled": False}
    peak_tf, peak_gb = peaks()
    return {
        "enabled": bool(ENABLED),
        "device_kind": device_kind(),
        "peak_tflops": peak_tf,
        "peak_gbps": peak_gb,
        "ridge_flops_per_byte": round((peak_tf * 1e12) / (peak_gb * 1e9),
                                      4),
        "kernels": KERNELS.snapshot(),
        "other": {"dispatches": KERNELS.other_dispatches,
                  "device_seconds": round(KERNELS.other_device_s, 6)},
        "h2d": KERNELS.h2d_json(),
        "ingest": INGEST.snapshot(),
        "cost_evals": cost_evals(),
    }


def timeline_probe() -> dict:
    """Registered on the health plane's sampler so flight-recorder
    bundles capture kernel profiles at anomaly time (top families only —
    bundles are size-bounded)."""
    if not ENABLED:
        return {"enabled": False}
    return {"enabled": True,
            "kernels": KERNELS.snapshot(limit=8),
            "h2d": KERNELS.h2d_json(),
            "ingest": INGEST.snapshot()}


if ENABLED:  # env opt-in: install hooks at import
    platform.set_profile_hooks(_on_dispatch, _on_h2d)
