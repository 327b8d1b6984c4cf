"""JAX platform selection helpers.

One place for the CPU-pinning idiom used by tests and the multichip
dryrun, and for placing the persistent compile cache. JAX
reads ``JAX_PLATFORMS`` once at import, so pinning a process that has
already imported jax must override the ``jax_platforms`` *config* as
well — and it must happen before the first ``jax.devices()`` call
initializes a backend.

A chip belongs to one process: a parent that has touched JAX holds it
and a child that needs it then fails or hangs. Orchestrators
(``chip_smoke.py``, ``benchmark/run.py``) therefore stay off JAX and
give the device to one child; in-process multi-node harnesses
(``cluster`` LocalCluster, ``dax`` DaxCluster) share the one chip by
design.
"""

from __future__ import annotations

import contextlib
import os
import re
import threading
import time

from pilosa_tpu.analysis import locktrace

_COUNT_FLAG = "xla_force_host_platform_device_count"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Every entry point that runs JAX in-process calls this before its
    first compile. An externally set ``JAX_COMPILATION_CACHE_DIR`` wins
    untouched: JAX reads it itself and no other path is set in code.
    Otherwise the cache lives at the fixed ``<checkout>/.jax_cache`` —
    never a temp name, pid or timestamp: the next process looks in the
    same place, so a directory that moves never hits. Wherever it
    lives it keeps every program, not only those that took JAX's
    default 1 s to compile (a served query family is many sub-second
    programs).
    """
    import jax

    _count_program_builds()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_BUILDS = threading.local()
_builds_counted = False


def _count_program_builds() -> None:
    """Count every program this process builds, once registered for good:
    ``device_programs_built_total{source, program}`` and
    ``device_program_build_seconds_total``. JAX reports ``_BUILD_EVENT``
    for every program built, compiled or fetched from the persistent
    cache, with the function's name; a fetch reports ``_CACHE_EVENT``
    first, on the same thread."""
    global _builds_counted
    if _builds_counted:
        return
    _builds_counted = True
    import jax.monitoring

    from pilosa_tpu.obs import metrics as M

    def on_duration(event, seconds, **kw):
        if event == _CACHE_EVENT:
            _BUILDS.fetched = True
        elif event == _BUILD_EVENT:
            fetched = getattr(_BUILDS, "fetched", False)
            _BUILDS.fetched = False
            # the name of a function of this program or of a jnp
            # operation it calls eagerly: as many values as the code has
            M.REGISTRY.count(M.METRIC_DEVICE_PROGRAMS_BUILT,
                             source="cache" if fetched else "compiled",
                             program=kw.get("fun_name", ""))
            M.REGISTRY.count(M.METRIC_DEVICE_PROGRAM_BUILD_SECONDS, seconds)

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def device_facts() -> dict:
    """What JAX reports about the devices this process serves from:
    ``platform`` and ``deviceKind`` of device 0 plus every device's
    string. Initializes the backend; raises when it cannot."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "deviceKind": devs[0].device_kind,
            "devices": [str(d) for d in devs]}


def ensure_virtual_devices(n_devices: int) -> None:
    """Ensure XLA_FLAGS requests >= n_devices virtual host devices.

    Only effective before the CPU backend initializes; parses and raises
    an existing count rather than silently keeping a too-small one.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"--{_COUNT_FLAG}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --{_COUNT_FLAG}={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--{_COUNT_FLAG}={n_devices}")


def force_cpu_platform(n_devices: int | None = None):
    """Pin this process to the CPU platform and return its devices.

    Optionally requests ``n_devices`` virtual devices first (must run
    before backend init to take effect).
    """
    if n_devices is not None:
        ensure_virtual_devices(n_devices)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax.devices("cpu")


# ---------------------------------------------------------------------------
# Host-side dispatch serialization.
#
# The engine mesh (parallel/mesh.py) shards stacked fragment tensors over
# every local device, so the jitted kernels compile to cross-module
# collectives. XLA:CPU runs those participants on a bounded host thread
# pool; two executables launched concurrently from different Python
# threads (cluster fan-out legs loop back into the same in-process
# harness) can interleave their rendezvous — each run's participants
# occupy pool threads waiting for co-participants that can no longer be
# scheduled, stalling both runs (observed as repeated "may be stuck ...
# waiting for all participants to arrive" and >30s query legs on small
# hosts). Serializing executable launches process-wide removes the
# interleaving. On non-CPU backends the runtime orders collectives on
# per-device queues, so the guard degrades to a no-op there.
#
# Serializing launches alone is NOT enough on CPU: dispatch is async, so
# a kernel launched under the lock keeps executing after release, and a
# second thread's kernel can still interleave rendezvous with it (two
# reader threads each end up blocked — one in block_until_ready, one in
# np.asarray — on programs stuck waiting for each other's pool threads).
# guarded_call therefore also blocks on the launched computation BEFORE
# releasing the lock on CPU, so at most one sharded program is ever in
# flight. TPU keeps fully async launches.
#
# The dispatch lock is strictly a LEAF lock: it is taken only around an
# individual compiled-kernel invocation (guarded_call) or device_put,
# where the holder can block on nothing but its own launch — never
# around query/build phases that acquire holder.write_lock or perform
# network I/O. That rule is what makes it deadlock-free by construction:
# wrapping whole read paths instead inverts against writers (reads take
# guard -> stale-block rebuild takes write_lock, while writers take
# write_lock -> launch takes guard: AB-BA), and holding it across
# loopback-HTTP fan-out starves the serving threads.
#
# Persistent executables (the per-query-family compiled programs in
# pql/programs.py, cached across queries) compose with the guard the
# same way ad-hoc jits do: the cache lookup is lock-free, and only the
# *invocation* of the cached executable runs under guarded_call — so the
# warm path pays one leaf-lock acquisition per launch, never a
# recompile, and CPU still sees at most one sharded program in flight.
# ---------------------------------------------------------------------------

# dispatch_ok: the dispatch lock is the one lock that MUST be held
# across the launch — that is its whole job; the tracer flags every
# OTHER lock held at a dispatch site (the leaf-lock rule, enforced).
_DISPATCH_LOCK = locktrace.tracked_lock("platform.dispatch", rlock=True,
                                        dispatch_ok=True)
_NULL_GUARD = contextlib.nullcontext()
_GUARD_IS_LOCK: bool | None = None

# Kernel-profiling hooks (obs/devprof.py installs these while the
# devprof plane is enabled; None means the un-instrumented fast path —
# guarded_call/h2d_copy do no extra work at all). The dispatch hook
# receives (dispatch_s, block_s) wall times, the h2d hook
# (nbytes, seconds); both are invoked AFTER the dispatch guard is
# released, so the leaf-lock rule is untouched.
_DISPATCH_HOOK = None
_H2D_HOOK = None


def set_profile_hooks(dispatch_hook, h2d_hook) -> None:
    """Install (or with None, remove) the kernel-profiling callbacks."""
    global _DISPATCH_HOOK, _H2D_HOOK
    _DISPATCH_HOOK = dispatch_hook
    _H2D_HOOK = h2d_hook


def dispatch_guard():
    """Context manager serializing sharded-executable launches across
    host threads: the process-wide dispatch lock on the CPU backend, a
    no-op context elsewhere."""
    global _GUARD_IS_LOCK
    if _GUARD_IS_LOCK is None:
        _GUARD_IS_LOCK = default_backend() == "cpu"
    return _DISPATCH_LOCK if _GUARD_IS_LOCK else _NULL_GUARD


def default_backend() -> str:
    """Active JAX backend name. One resolver for the Pallas dispatch
    predicates so eligibility rules can't fork per call site. A backend
    that cannot initialize raises: serving from a stand-in platform
    would hide exactly the failure an operator needs to see."""
    import jax

    return jax.default_backend()


def backend_supports_donation() -> bool:
    """Whether ``donate_argnums`` actually reuses buffers here.

    XLA:CPU ignores donation (and warns per-compile), so donated scratch
    is only wired on device backends; callers that share a long-lived
    zeros plane as scratch rely on this — a *real* donation would
    consume the shared buffer.
    """
    dispatch_guard()  # resolves _GUARD_IS_LOCK (cpu <=> lock)
    return not _GUARD_IS_LOCK


def donate_argnums(*nums: int):
    """``donate_argnums`` tuple for ``jax.jit``, empty on CPU where XLA
    cannot honor donation (avoids both the per-compile warning and
    consuming buffers the caller still holds)."""
    return nums if backend_supports_donation() else ()


def h2d_copy(host, sharding=None):
    """Host→device transfer under the dispatch guard, traced as a
    ``device.h2d_copy`` span tagged with the byte count.

    Every staging path (mesh.engine_put, fragment.device_planes) routes
    through here so transfer-vs-dispatch attribution shows up in
    `profile=true` traces: a warm resident query must have NO
    device.h2d_copy stage at all.
    """
    import jax
    import numpy as np

    from pilosa_tpu.obs.tracing import get_tracer

    arr = np.asarray(host)
    if locktrace.ACTIVE is not None:
        locktrace.ACTIVE.note_dispatch("platform.h2d_copy")
    hook = _H2D_HOOK
    if hook is None:
        with dispatch_guard():
            with get_tracer().start_span("device.h2d_copy",
                                         nbytes=arr.nbytes):
                if sharding is not None:
                    return jax.device_put(arr, sharding)
                return jax.device_put(arr)
    with dispatch_guard():
        with get_tracer().start_span("device.h2d_copy", nbytes=arr.nbytes):
            t0 = time.perf_counter()
            out = (jax.device_put(arr, sharding) if sharding is not None
                   else jax.device_put(arr))
            dt = time.perf_counter() - t0
    hook(arr.nbytes, dt)
    return out


def guarded_call(fn):
    """Wrap a compiled/jitted callable so every invocation holds the
    dispatch guard (the leaf-lock rule above). Decorate below ``jax.jit``
    so the lock spans trace+launch of one call, not the cache.

    Traced queries see the async-dispatch split here: ``device.dispatch``
    is the launch (trace+enqueue), ``device.block_until_ready`` the
    device-side wait. Span bookkeeping is pure in-memory appends, so it
    respects the leaf-lock rule (no I/O under the dispatch lock)."""
    import functools

    from pilosa_tpu.obs.tracing import get_tracer

    @functools.wraps(fn)
    def call(*args, **kwargs):
        guard = dispatch_guard()
        if locktrace.ACTIVE is not None:
            locktrace.ACTIVE.note_dispatch("platform.guarded_call")
        tracer = get_tracer()
        hook = _DISPATCH_HOOK
        if hook is None:
            with guard:
                with tracer.start_span("device.dispatch"):
                    out = fn(*args, **kwargs)
                if guard is _DISPATCH_LOCK:
                    import jax

                    with tracer.start_span("device.block_until_ready"):
                        jax.block_until_ready(out)
                return out
        with guard:
            t0 = time.perf_counter()
            with tracer.start_span("device.dispatch"):
                out = fn(*args, **kwargs)
            t1 = time.perf_counter()
            if guard is _DISPATCH_LOCK:
                import jax

                with tracer.start_span("device.block_until_ready"):
                    jax.block_until_ready(out)
            t2 = time.perf_counter()
        hook(t1 - t0, t2 - t1)
        return out

    call.__wrapped__ = fn
    return call
