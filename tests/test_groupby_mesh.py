"""The mesh route of the pair-count family: the Pallas kernel run on every
device over the words that device holds, under ``shard_map``, its counts
summed with one ``psum`` (``ops/groupby._pair_counts_mesh`` /
``_pair_sums_mesh``, ``parallel/mesh.psum_over_words``).

On the virtual CPU devices of ``conftest.py`` the kernel bodies run under
the Pallas interpreter, so this file checks the program's answers against
a numpy popcount, its form (a ``shard_map`` holding a ``pallas_call`` and a
``psum``; no ``all-gather`` once compiled), the placement test that
chooses it, the counters, and the fall back to the XLA route. That Mosaic
takes the per-chip call at the served width is ``chip_smoke.py``'s to
show, on four chips.
"""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pilosa_tpu.obs import metrics as M
from pilosa_tpu.ops import groupby as G
from pilosa_tpu.ops import pallas_util as PU
from pilosa_tpu.parallel import mesh as PM

N_DEV = 4


@pytest.fixture
def mesh4():
    """An engine mesh of four of the eight virtual devices."""
    mesh = PM.analytics_mesh(jax.devices()[:N_DEV])
    PM.set_engine_mesh(mesh)
    yield mesh
    PM.set_engine_mesh(None)


def popcount(x):
    return np.unpackbits(np.ascontiguousarray(x).view(np.uint8),
                         axis=-1).sum(-1, dtype=np.int32)


def pair_counts_numpy(a, b):
    return popcount(a[:, None, :] & b[None, :, :])


def pair_sums_numpy(a, b, mags, pos, neg):
    ab = a[:, None, :] & b[None, :, :]
    return (np.stack([popcount(ab & (m & pos)) for m in mags]),
            np.stack([popcount(ab & (m & neg)) for m in mags]))


def operands(rng, r1, r2, d, local_words, zero_dev=None):
    """``a``, ``b``, ``d`` magnitude planes and two disjoint sign masks,
    ``local_words`` words a device; ``zero_dev``: that device's words of
    every operand are all zero."""
    w = N_DEV * local_words

    def planes(rows):
        return rng.integers(0, 1 << 32, size=(rows, w), dtype=np.uint32)

    exists, sign = planes(2)
    ops = [planes(r1), planes(r2), planes(d), exists & ~sign, exists & sign]
    if zero_dev is not None:
        for x in ops:
            x[..., zero_dev * local_words:(zero_dev + 1) * local_words] = 0
    return ops


def value(metric, **labels):
    return M.REGISTRY.value(metric, **labels)


SHAPES = [
    # r1, r2, local words, all-zero device
    (7, 100, 600, None),   # 7 x 100 rows, a local width that is no
    #                        multiple of the kernel's 512-word block
    (7, 300, 512, None),   # two row tiles of the second operand
    (8, 128, 512, None),   # the served block: tile-aligned
    (1, 1, 1, None),       # one word a device
    (128, 8, 16, None),    # the kernel's row limit
    (7, 100, 520, 2),      # one device holds no bit at all
    # the shapes above all take the kernel's VPU body; so does a taxi Q4
    # call, here with a local width of two of its word blocks ...
    (24, 16, 2 * 2048 + 512, None),
    (64, 128, 512, None),  # ... and two tall sides take the MXU body
]


@pytest.mark.parametrize("r1,r2,lw,zero_dev", SHAPES)
def test_mesh_pair_counts_equals_numpy(rng, mesh4, r1, r2, lw, zero_dev):
    a, b = operands(rng, r1, r2, 1, lw, zero_dev)[:2]
    got = G._pair_counts_mesh(PM.engine_put(a), PM.engine_put(b),
                              mesh=mesh4, interpret=True)
    assert got.shape == (r1, r2) and got.dtype == np.int32
    assert got.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(got), pair_counts_numpy(a, b))


@pytest.mark.parametrize("r1,r2,lw,zero_dev", SHAPES)
def test_mesh_pair_sums_equals_numpy(rng, mesh4, r1, r2, lw, zero_dev):
    ops = operands(rng, r1, r2, 3, lw, zero_dev)
    got = G._pair_sums_mesh(*map(PM.engine_put, ops), mesh=mesh4,
                            interpret=True)
    for g, want in zip(got, pair_sums_numpy(*ops)):
        assert g.shape == (3, r1, r2) and g.dtype == np.int32
        np.testing.assert_array_equal(np.asarray(g), want)


def _eqns(jaxpr, name):
    return [e for e in jaxpr.eqns if e.primitive.name == name]


def _primitives(jaxpr, seen=None):
    """Every primitive name in ``jaxpr`` and the jaxprs nested in it."""
    seen = set() if seen is None else seen
    for e in jaxpr.eqns:
        seen.add(e.primitive.name)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, seen)
    return seen


@pytest.mark.parametrize("kernel", ["pair_counts", "pair_sums"])
def test_mesh_program_counts_locally_and_reduces_once(rng, mesh4, kernel):
    """The program's form: one ``shard_map`` over the mesh whose body
    holds the ``pallas_call`` and the ``psum``; compiled, its only
    collective is the all-reduce of the counts: no operand is gathered."""
    n = 2 if kernel == "pair_counts" else 5
    ops = [PM.engine_put(x) for x in operands(rng, 8, 128, 3, 512)[:n]]
    prog = {"pair_counts": G._pair_counts_mesh,
            "pair_sums": G._pair_sums_mesh}[kernel].__wrapped__
    outer = jax.make_jaxpr(
        lambda *xs: prog(*xs, mesh=mesh4, interpret=True))(*ops).jaxpr
    (jit,) = [e for e in outer.eqns if e.primitive.name in ("pjit", "jit")]
    (smap,) = _eqns(jit.params["jaxpr"].jaxpr, "shard_map")
    assert smap.params["mesh"].shape_tuple == mesh4.shape_tuple
    body = smap.params["jaxpr"]
    assert _eqns(body, "psum") or _eqns(body, "psum_invariant")
    assert "pallas_call" in _primitives(body)
    # each device sees its own quarter of the words, whole rows
    assert [v.aval.shape[-1] for v in body.invars] == [512] * n
    text = prog.lower(*ops, mesh=mesh4, interpret=True).compile().as_text()
    assert "all-reduce" in text
    assert "all-gather" not in text and "all-to-all" not in text


def test_xla_scan_gathers_its_operands(mesh4):
    """What the mesh route is for: the XLA pair count scans blocks of the
    word axis, the axis the engine shards, and the partitioner's answer
    to a loop over a sharded axis is to gather both operands whole."""
    a, b = (jax.ShapeDtypeStruct(
        (r, N_DEV * 2 * G.BLOCK_WORDS), np.uint32,
        sharding=NamedSharding(mesh4, PM._words_spec(2))) for r in (8, 128))
    text = G._pair_counts_xla.__wrapped__.lower(a, b).compile().as_text()
    assert "all-gather" in text


def _placements(rng):
    engine = PM.engine_mesh()
    words = P(None, (PM.SHARD_AXIS, PM.COL_AXIS))
    a = rng.integers(0, 1 << 32, size=(8, 64), dtype=np.uint32)
    placed = PM.engine_put(a)
    devs = jax.devices()
    return {
        "engine-placed": ((placed, PM.engine_put(a[:3])), True),
        "engine-placed-1d": ((placed, PM.engine_put(a[0])), True),
        # rows of a placed stack, sliced eagerly, stay where they are
        "engine-placed-sliced": ((placed[2:5], placed), True),
        "named-equal-mesh": ((jax.device_put(a, NamedSharding(
            PM.analytics_mesh(devs[:N_DEV]), words)),), True),
        "mixed-numpy": ((placed, a), False),
        "mixed-one-device": ((placed, jax.device_put(a, devs[0])), False),
        "numpy": ((a,), False),
        "one-device": ((jax.device_put(a, devs[0]),), False),
        "replicated": ((jax.device_put(a, NamedSharding(engine, P())),),
                       False),
        "rows-split": ((jax.device_put(a, NamedSharding(
            engine, P((PM.SHARD_AXIS, PM.COL_AXIS), None))),), False),
        "foreign-devices": ((jax.device_put(a, NamedSharding(
            PM.analytics_mesh(devs[N_DEV:2 * N_DEV]), words)),), False),
        "foreign-order": ((jax.device_put(a, NamedSharding(
            PM.analytics_mesh(devs[:N_DEV][::-1]), words)),), False),
        "wider-mesh": ((jax.device_put(a, NamedSharding(
            PM.analytics_mesh(devs), words)),), False),
        "no-operand": ((), False),
    }


@pytest.mark.parametrize("case", [
    "engine-placed", "engine-placed-1d", "engine-placed-sliced",
    "named-equal-mesh", "mixed-numpy", "mixed-one-device", "numpy",
    "one-device", "replicated", "rows-split", "foreign-devices",
    "foreign-order", "wider-mesh", "no-operand"])
def test_engine_placed(rng, mesh4, case):
    arrays, want = _placements(rng)[case]
    before = dict(M.REGISTRY.snapshot()["counters"])
    assert PM.engine_placed(*arrays) is want
    # pure: nothing counted, the loud placement fallback least of all
    assert M.REGISTRY.snapshot()["counters"] == before


def test_engine_placed_refuses_tracers_and_one_device_meshes(rng, mesh4):
    a = PM.engine_put(
        rng.integers(0, 1 << 32, size=(8, 64), dtype=np.uint32))
    seen = []
    jax.jit(lambda x: seen.append(PM.engine_placed(x)) or x)(a)
    assert seen == [False]
    PM.set_engine_mesh(PM.analytics_mesh(jax.devices()[:1]))
    assert not PM.engine_placed(a)


def _call(kernel, ops):
    if kernel == "pair_counts":
        return [np.asarray(G.pair_counts(*ops[:2]))]
    return [np.asarray(x) for x in G.pair_sums(*ops)]


def _want(kernel, ops):
    if kernel == "pair_counts":
        return [pair_counts_numpy(*ops[:2])]
    return list(pair_sums_numpy(*ops))


def _ticks(kernel):
    return (value(M.METRIC_OPS_PALLAS_DISPATCH, kernel=kernel),
            value(M.METRIC_OPS_PALLAS_MESH_DISPATCH, kernel=kernel),
            value(M.METRIC_OPS_PALLAS_FALLBACK, kernel=kernel, why="mesh"),
            value(M.METRIC_OPS_PALLAS_FALLBACK, kernel=kernel, why="error"))


@pytest.mark.parametrize("kernel", ["pair_counts", "pair_sums"])
def test_engine_placed_operands_take_the_mesh_route(rng, mesh4, pallas_as_compiled,
                                                    kernel):
    ops = operands(rng, 7, 100, 3, 600)
    placed = [PM.engine_put(x) for x in ops]
    d0, m0, f0, e0 = _ticks(kernel)
    got = _call(kernel, placed)
    # a Pallas dispatch, and one of the mesh series: once a call each
    assert _ticks(kernel) == (d0 + 1, m0 + 1, f0, e0)
    for g, want in zip(got, _want(kernel, ops)):
        np.testing.assert_array_equal(g, want)
    # on one device nothing is sharded: the one-chip program, as before
    one = [jax.device_put(x, jax.devices()[0]) for x in ops]
    _call(kernel, one)
    assert _ticks(kernel) == (d0 + 2, m0 + 1, f0, e0)


@pytest.mark.parametrize("r1,r2,lw,body", [
    (24, 16, 2048, "vpu"),   # a taxi Q4 call: 16-row blocks, 24 planes
    (1, 80, 600, "vpu"),
    (64, 128, 512, "mxu"),
])
def test_the_mesh_route_equals_one_device_under_either_body(
        rng, mesh4, pallas_as_compiled, r1, r2, lw, body):
    """The body goes by the heights, and a chip sees the heights the
    whole operands have: the mesh program takes the body the one-chip
    program takes, ticks it once, and both give the same integers."""
    a, b = operands(rng, r1, r2, 1, lw)[:2]
    assert G.pallas_body(r1, r2) == body
    route = G.pair_counts_route(PM.engine_put(a), PM.engine_put(b))
    assert route == ("mesh", body)
    assert G.pair_counts_route(a, b) == ("pallas", body)

    def bodies():
        return {k: value(M.METRIC_OPS_PALLAS_BODY, kernel="pair_counts",
                         body=k) for k in ("vpu", "mxu")}

    before, m0 = bodies(), _ticks("pair_counts")[1]
    on_mesh = np.asarray(G.pair_counts(PM.engine_put(a), PM.engine_put(b)))
    assert _ticks("pair_counts")[1] == m0 + 1
    one = np.asarray(G.pair_counts(jax.device_put(a, jax.devices()[0]),
                                   jax.device_put(b, jax.devices()[0])))
    assert _ticks("pair_counts")[1] == m0 + 1
    before[body] += 2
    assert bodies() == before
    np.testing.assert_array_equal(on_mesh, one)
    np.testing.assert_array_equal(one, pair_counts_numpy(a, b))


@pytest.mark.parametrize("kernel,case", [
    (k, c) for k in ("pair_counts", "pair_sums")
    for c in ("mixed", "foreign-mesh", "rows")])
def test_other_sharded_operands_keep_the_xla_route(rng, mesh4, pallas_as_compiled,
                                                   kernel, case):
    """Sharded, but not as the engine places a stack (or too many rows
    for the kernel): ``why="mesh"`` and the XLA scan, as before."""
    ops = operands(rng, 130 if case == "rows" else 7, 20, 2, 64)
    placed = [PM.engine_put(x) for x in ops]
    if case == "mixed":
        placed[0] = ops[0]
    elif case == "foreign-mesh":
        other = PM.analytics_mesh(jax.devices()[N_DEV:2 * N_DEV])
        placed = [jax.device_put(x, NamedSharding(
            other, PM._words_spec(x.ndim))) for x in ops]
    d0, m0, f0, e0 = _ticks(kernel)
    got = _call(kernel, placed)
    assert _ticks(kernel) == (d0, m0, f0 + 1, e0)
    for g, want in zip(got, _want(kernel, ops)):
        np.testing.assert_array_equal(g, want)


@pytest.mark.parametrize("kernel", ["pair_counts", "pair_sums"])
def test_a_raise_in_the_mesh_route_falls_back_to_xla(rng, mesh4, pallas_as_compiled,
                                                     monkeypatch, kernel):
    def boom(*args, **kwargs):
        raise RuntimeError("Mosaic said no")

    monkeypatch.setattr(G, f"_{kernel}_mesh", boom)
    ops = operands(rng, 7, 20, 2, 64)
    placed = [PM.engine_put(x) for x in ops]
    d0, m0, f0, e0 = _ticks(kernel)
    got = _call(kernel, placed)
    assert _ticks(kernel) == (d0, m0, f0, e0 + 1)
    for g, want in zip(got, _want(kernel, ops)):
        np.testing.assert_array_equal(g, want)
    # three strikes pin the kernel off: the refusal is then "failures"
    for _ in range(PU.MAX_FAILURES - 1):
        _call(kernel, placed)
    assert PU.why_not(kernel, *placed[:2]) == "failures"
    assert G._mesh_route("failures", *placed) is None


def test_mesh_dispatch_series_on_the_exposition(rng, mesh4, pallas_as_compiled):
    a, b = (PM.engine_put(x) for x in operands(rng, 4, 4, 1, 16)[:2])
    G.pair_counts(a, b)
    text = M.REGISTRY.prometheus_text()
    assert 'ops_pallas_mesh_dispatch_total{kernel="pair_counts"}' in text
    assert 'ops_pallas_dispatch_total{kernel="pair_counts"}' in text
