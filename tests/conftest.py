"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The analog of the reference's in-process multi-node cluster harness
(reference: test/cluster.go:748 MustRunCluster boots N servers in one
process): we boot N virtual XLA CPU devices so mesh/sharding tests run
without TPU hardware.

``force_cpu_platform`` overrides the `jax_platforms` config as well as
the env var, so the suite stays off the hardware even when jax was
imported before conftest ran. Set PILOSA_TPU_TEST_REAL=1 to run the suite
on the attached devices instead.
"""

import os

from pilosa_tpu.platform import ensure_virtual_devices, force_cpu_platform

ensure_virtual_devices(8)
if not os.environ.get("PILOSA_TPU_TEST_REAL"):
    force_cpu_platform()

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def pallas_as_compiled(monkeypatch):
    """``pallas_util.why_not`` answers as it does where kernels are
    compiled (an operand sharded over several devices: ``"mesh"``; no
    interpreter width cap) while the programs themselves keep running
    under the Pallas interpreter, which is all this backend has."""
    from pilosa_tpu.ops import pallas_util as PU

    monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
    real = PU.why_not

    def why_not(*args, **kwargs):
        with monkeypatch.context() as m:
            m.setattr(PU, "use_interpret", lambda: False)
            return real(*args, **kwargs)

    monkeypatch.setattr(PU, "why_not", why_not)
    PU.reset_failures()
    yield
    PU.reset_failures()


@pytest.fixture
def per_device():
    """``nbytes`` of a stack block as one device of the engine mesh holds
    them: the residency plane (core/stacked.py) caps bytes per device,
    so a test that sizes ``_BLOCK_BYTES`` or a ``DeviceBudget`` by whole
    blocks states the whole and passes it through this."""
    from pilosa_tpu.parallel.mesh import engine_mesh

    n = engine_mesh().devices.size
    return lambda nbytes: nbytes // n


@pytest.fixture(autouse=True)
def _budget_leak_audit():
    """Post-test accounting audit (the reference's testhook auditors,
    testhook/hook.go:22: every test leaves shared registries
    consistent)."""
    yield
    from pilosa_tpu.core import stacked as _stx

    _stx.BUDGET.audit()


@pytest.fixture(autouse=True)
def _lock_discipline_audit():
    """Lock-tracer audit (scripts/tier1.sh analysis lane sets
    PILOSA_TPU_LOCKCHECK=1): after every test the process-wide lock
    tracer must show zero NEW violations — a lock-order cycle or a lock
    held across device dispatch / blocking I/O is a latent deadlock no
    matter which test's interleaving exposed it, and failing the test
    that CREATED the edge points straight at the offending call path."""
    from pilosa_tpu.analysis import locktrace

    reg = locktrace.ACTIVE
    before = len(reg.violations()) if reg is not None else 0
    yield
    if reg is None or reg is not locktrace.ACTIVE:
        return
    fresh = reg.violations()[before:]
    assert not fresh, (
        "lock-discipline violations recorded during this test: "
        + "; ".join(v["message"] for v in fresh))


@pytest.fixture(autouse=True)
def _span_leak_audit():
    """Tracing-lane leak check (scripts/tier1.sh sets PILOSA_TPU_TRACE=1):
    after every test the main thread's span scope must be empty — a span
    left unfinished would silently re-parent every later trace in the
    process."""
    yield
    if not os.environ.get("PILOSA_TPU_TRACE"):
        return
    from pilosa_tpu.obs.tracing import current_span

    leaked = current_span()
    assert leaked is None, \
        f"span {leaked.name!r} leaked out of the test's trace scope"
