"""Bit-identity battery: every Pallas L0 kernel vs its classic oracle.

The Pallas plane's contract is *bit-identical or bust* — these tests
force the kernels on in interpret mode (``PILOSA_TPU_PALLAS=1`` on the
CPU backend runs the exact kernel bodies under the Pallas interpreter)
and compare against the classic XLA/numpy paths across the edge shapes
that historically break tiled kernels: empty filters, all-set planes, a
single word, row counts that are not a multiple of any tile, negative
BSI values, and BETWEEN ranges straddling zero. The same calls run once
more with the kill switch thrown to pin the zero-dispatch guarantee.
"""

import numpy as np
import pytest

from pilosa_tpu.obs import metrics as M
from pilosa_tpu.ops import bitmap as B
from pilosa_tpu.ops import bsi as S
from pilosa_tpu.ops import groupby as G
from pilosa_tpu.ops import pallas_util as PU
from pilosa_tpu.ops import scatter as SC
from pilosa_tpu.ops import topk as T

WORDS = 1 << 9
NBITS = WORDS * 32


@pytest.fixture(autouse=True)
def _clean_strikes():
    """Strike counters must not leak between tests (a kernel pinned off
    by an earlier failure would silently skip the parity assertion)."""
    PU.reset_failures()
    yield
    PU.reset_failures()


@pytest.fixture
def forced(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
    monkeypatch.delenv("PILOSA_TPU_NO_PALLAS", raising=False)


@pytest.fixture
def killed(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "0")


def rand_planes(rng, rows, words=WORDS):
    return rng.integers(0, 1 << 32, size=(rows, words), dtype=np.uint32)


def dispatch_count(kernel):
    return M.REGISTRY.value(M.METRIC_OPS_PALLAS_DISPATCH, kernel=kernel)


# ---------------------------------------------------------------------------
# pair_counts (GroupBy)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("r1,r2,w", [
    (1, 1, 1),      # single word
    (3, 5, 7),      # nothing aligned
    (37, 37, 512),  # rows not a multiple of any tile
    (8, 256, 512),  # exactly tile-aligned
])
def test_pair_counts_parity(rng, forced, r1, r2, w):
    a, b = rand_planes(rng, r1, w), rand_planes(rng, r2, w)
    before = dispatch_count("pair_counts")
    got = np.asarray(G.pair_counts(a, b))
    assert dispatch_count("pair_counts") == before + 1
    want = np.asarray(G._pair_counts_xla(a, b))
    np.testing.assert_array_equal(got, want)


def test_pair_counts_all_set_and_empty(rng, forced):
    ones = np.full((4, WORDS), 0xFFFFFFFF, dtype=np.uint32)
    zeros = np.zeros((4, WORDS), dtype=np.uint32)
    np.testing.assert_array_equal(
        np.asarray(G.pair_counts(ones, ones)),
        np.full((4, 4), NBITS, dtype=np.int32))
    np.testing.assert_array_equal(
        np.asarray(G.pair_counts(ones, zeros)), np.zeros((4, 4), np.int32))


# ---------------------------------------------------------------------------
# pair_sums (two-field GroupBy with a Sum)
# ---------------------------------------------------------------------------


def sum_operands(rng, r1, r2, d, w):
    """Two row sets, ``d`` magnitude planes and the two sign masks (pos
    and neg disjoint, neither empty, some columns in neither)."""
    exists, sign = rand_planes(rng, 2, w)
    return (rand_planes(rng, r1, w), rand_planes(rng, r2, w),
            rand_planes(rng, d, w), exists & ~sign, exists & sign)


def pair_sums_numpy(a, b, mags, pos, neg):
    """popcount(A_i & B_j & M_k & sign), nothing from the program."""
    def popcount(x):
        return np.unpackbits(x.view(np.uint8), axis=-1).sum(
            -1, dtype=np.int32)

    ab = a[:, None, :] & b[None, :, :]
    return (np.stack([popcount(ab & (m & pos)) for m in mags]),
            np.stack([popcount(ab & (m & neg)) for m in mags]))


def fallback_count(kernel, why):
    return M.REGISTRY.value(M.METRIC_OPS_PALLAS_FALLBACK, kernel=kernel,
                            why=why)


@pytest.mark.parametrize("r1,r2,d,w,why", [
    (1, 1, 1, 1, None),        # single word, single plane
    (7, 25, 3, 7, None),       # r1 not a sublane multiple, nothing aligned
    (8, 256, 4, 512, None),    # the served shape: tile-aligned
    (8, 300, 2, 512, None),    # two row tiles of the second operand
    (70, 9, 2, 40, None),      # 2 * r1 > 128 >= r1: two calls a step
    (128, 8, 1, 16, None),     # the kernel's row limit
    (130, 4, 2, 16, "shape"),  # past it: the XLA scan
])
def test_pair_sums_parity(rng, forced, r1, r2, d, w, why):
    ops = sum_operands(rng, r1, r2, d, w)
    assert ops[3].any() and ops[4].any()
    before = dispatch_count("pair_sums")
    refused = fallback_count("pair_sums", "shape")
    got = [np.asarray(x) for x in G.pair_sums(*ops)]
    assert dispatch_count("pair_sums") == before + (why is None)
    assert fallback_count("pair_sums", "shape") == refused + (
        why == "shape")
    assert [x.shape for x in got] == [(d, r1, r2)] * 2
    assert [x.dtype for x in got] == [np.int32] * 2
    for g, x, n in zip(got, G._pair_sums_xla(*ops), pair_sums_numpy(*ops)):
        np.testing.assert_array_equal(g, np.asarray(x))
        np.testing.assert_array_equal(g, n)


def test_pair_sums_all_set_and_empty_planes(rng, forced):
    ones = np.full((4, WORDS), 0xFFFFFFFF, dtype=np.uint32)
    pos, neg = rand_planes(rng, 2)
    neg &= ~pos
    # plane 0 all set, plane 1 empty
    mags = np.stack([ones[0], np.zeros(WORDS, dtype=np.uint32)])
    p, n = (np.asarray(x) for x in G.pair_sums(ones, ones, mags, pos, neg))
    for got, mask in ((p, pos), (n, neg)):
        bits = int(np.unpackbits(mask.view(np.uint8)).sum())
        np.testing.assert_array_equal(
            got[0], np.full((4, 4), bits, dtype=np.int32))
        np.testing.assert_array_equal(got[1], np.zeros((4, 4), np.int32))
    # no negative value at all: the neg half of the stacked operand is empty
    p, n = G.pair_sums(ones, ones, mags, pos, np.zeros_like(pos))
    assert not np.asarray(n).any() and np.asarray(p)[0, 0, 0] > 0


def test_pair_sums_keeps_the_scan_the_roofline_reads(rng):
    """``pair_sums_roofline`` (benchmark/layer_metrics) finds the call by
    its ``%while`` and reads D, R1, R2 from the stacked accumulators the
    loop carries: the Pallas route is still one scan over the D planes
    that stacks two ``int32[D, R1, R2]``, its step one kernel call."""
    import jax

    d, r1, r2 = 5, 8, 16
    ops = sum_operands(rng, r1, r2, d, WORDS)
    jaxpr = jax.make_jaxpr(
        lambda *xs: G._pair_sums_pallas.__wrapped__(*xs, interpret=False)
    )(*ops).jaxpr
    (inner,) = [e for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit")]
    eqns = inner.params["jaxpr"].jaxpr.eqns
    (scan,) = [e for e in eqns if e.primitive.name == "scan"]
    assert not [e for e in eqns if e.primitive.name == "while"]
    assert scan.params["length"] == d and scan.params["num_carry"] == 0
    assert [(v.aval.shape, v.aval.dtype) for v in scan.outvars] == [
        ((d, r1, r2), np.int32)] * 2
    body = [e.primitive.name for e in scan.params["jaxpr"].jaxpr.eqns]
    assert body.count("pallas_call") == 1
    # the second operand reaches the kernel as the program's own argument
    assert "scan" not in body and "while" not in body


def test_pair_sums_mesh_sharded_operand_takes_xla(rng, forced, monkeypatch):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pilosa_tpu.parallel import mesh

    m = mesh.analytics_mesh(jax.devices())
    a, b, mags, pos, neg = sum_operands(rng, 8, 16, 3, WORDS)
    sharded = jax.device_put(
        b, NamedSharding(m, P(None, (mesh.SHARD_AXIS, mesh.COL_AXIS))))
    want = pair_sums_numpy(a, b, mags, pos, neg)
    monkeypatch.setattr(PU, "use_interpret", lambda: False)
    before = dispatch_count("pair_sums")
    refused = fallback_count("pair_sums", "mesh")
    got = G.pair_sums(a, sharded, mags, pos, neg)
    assert dispatch_count("pair_sums") == before
    assert fallback_count("pair_sums", "mesh") == refused + 1
    for g, n in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), n)


# ---------------------------------------------------------------------------
# BSI sum / plane popcounts
# ---------------------------------------------------------------------------


def encode(rng, n=2000, lo=-5000, hi=5000):
    cols = np.unique(rng.integers(0, NBITS, size=n))
    vals = rng.integers(lo, hi, size=cols.size)
    depth = max(S.bits_needed(int(vals.min())),
                S.bits_needed(int(vals.max())))
    return cols, vals, S.encode_values(cols, vals, depth, WORDS)


def test_bsi_sum_parity_negative_values(rng, forced):
    cols, vals, planes = encode(rng)
    filt = np.asarray(planes[S.EXISTS])
    before = dispatch_count("bsi_sum")
    total, count = S.bsi_sum(planes, planes[S.EXISTS])
    assert dispatch_count("bsi_sum") == before + 1
    assert (total, count) == (int(vals.sum()), cols.size)
    # plane popcounts against the classic reduction, element by element
    got = S.bsi_plane_popcounts(planes, planes[S.EXISTS])
    want = S._plane_popcounts_xla(planes, planes[S.EXISTS])
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    del filt


def test_bsi_sum_empty_filter(rng, forced):
    _, _, planes = encode(rng)
    total, count = S.bsi_sum(planes, B.device_zeros(WORDS))
    assert (total, count) == (0, 0)


# ---------------------------------------------------------------------------
# BSI compare
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", [S.EQ, S.NE, S.LT, S.LE, S.GT, S.GE])
@pytest.mark.parametrize("c", [-6000, -1, 0, 42, 6000])
def test_bsi_compare_parity(rng, forced, monkeypatch, op, c):
    cols, vals, planes = encode(rng)
    before = dispatch_count("bsi_compare")
    got = np.asarray(S.bsi_compare(planes, op, c))
    assert dispatch_count("bsi_compare") == before + 1
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "0")
    want = np.asarray(S.bsi_compare(planes, op, c))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("a,b", [
    (-100, 100),     # straddles zero
    (0, 0), (-5000, 5000), (40, 30), (-5000, -4000), (-6000, 6000),
])
def test_bsi_between_parity(rng, forced, monkeypatch, a, b):
    cols, vals, planes = encode(rng)
    got = np.asarray(S.bsi_compare(planes, S.BETWEEN, a, b))
    expect = set(int(x) for x in cols[(vals >= a) & (vals <= b)])
    assert set(int(x) for x in B.plane_to_bits(got)) == expect
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "0")
    want = np.asarray(S.bsi_compare(planes, S.BETWEEN, a, b))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# TopN row counts / ranking
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows", [1, 37, 64])
def test_row_counts_parity(rng, forced, rows):
    planes = rand_planes(rng, rows)
    filt = rand_planes(rng, 1)[0]
    for f in (None, filt):
        got = np.asarray(T.row_counts(planes, f))
        want = np.asarray(B.row_counts(planes, f))
        np.testing.assert_array_equal(got, want)


def test_top_rows_parity(rng, forced):
    planes = rand_planes(rng, 37)
    filt = rand_planes(rng, 1)[0]
    for f in (None, filt):
        gc, gi = T.top_rows(planes, 5, f)
        wc, wi = T._topk_kernel(planes, f if f is not None else None, 5)
        np.testing.assert_array_equal(np.asarray(gc), np.asarray(wc))
        # indices may tie-break differently only among equal counts
        counts = np.asarray(B.row_counts(planes, f))
        np.testing.assert_array_equal(counts[np.asarray(gi)],
                                      np.asarray(gc))
        del wi


# ---------------------------------------------------------------------------
# Ingest scatter
# ---------------------------------------------------------------------------


def test_sort_updates_collapses_duplicates():
    slots = np.array([0, 0, 1, 0], dtype=np.int64)
    cols = np.array([0, 0, 33, 31], dtype=np.int64)
    addr, masks = SC.sort_updates(slots, cols, words=4)
    np.testing.assert_array_equal(addr, [0, 5])
    np.testing.assert_array_equal(masks, [0x80000001, 0x2])
    a0, m0 = SC.sort_updates([], [], words=4)
    assert a0.size == 0 and m0.size == 0


def test_scatter_merge_parity(rng, forced):
    import jax.numpy as jnp

    flat = rng.integers(0, 1 << 32, size=1024, dtype=np.uint32)
    addr, masks = SC.sort_updates(
        np.zeros(300, dtype=np.int64),
        rng.integers(0, 1024 * 32, size=300), words=1024)
    dev = jnp.asarray(flat)
    ai = jnp.asarray(addr.astype(np.int32))
    mi = jnp.asarray(masks)
    gm, gc = SC._scatter_merge_pallas(dev, ai, mi, True)
    wm, wc = SC._scatter_merge_xla(dev, ai, mi)
    np.testing.assert_array_equal(np.asarray(gm), np.asarray(wm))
    assert int(gc) == int(wc)


def test_set_many_device_vs_classic(rng, forced, monkeypatch):
    from pilosa_tpu.core.fragment import SetFragment

    rows = rng.integers(0, 8, size=500)
    cols = rng.integers(0, NBITS, size=500)
    dev = SetFragment(0, words=WORDS)
    before = dispatch_count("ingest_scatter")
    ch_dev = dev.set_many(rows, cols)
    assert dispatch_count("ingest_scatter") == before + 1
    assert dev.set_many(rows, cols) == 0  # idempotent re-apply

    monkeypatch.setenv("PILOSA_TPU_PALLAS", "0")
    classic = SetFragment(0, words=WORDS)
    ch_cl = classic.set_many(rows, cols)
    assert ch_dev == ch_cl
    assert sorted(dev.existing_rows()) == sorted(classic.existing_rows())
    for r in dev.existing_rows():
        np.testing.assert_array_equal(dev.row_plane(r),
                                      classic.row_plane(r))


# ---------------------------------------------------------------------------
# Tape-count terminal (resident program popcount reduce)
# ---------------------------------------------------------------------------


def tape_count_on_one_device(rng):
    """An AND tape's count program compiled for a one-device engine
    mesh: (has a Pallas terminal, its count, numpy's count)."""
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.parallel import mesh

    mesh.set_engine_mesh(mesh.analytics_mesh([jax.devices()[0]]))
    try:
        total_words = 1024
        leaves = [jnp.asarray(rand_planes(rng, 1, total_words)[0])
                  for _ in range(2)]
        fn = mesh.compile_tape_count((("and", 0, 1),), False, total_words)
        want = int(np.bitwise_count(
            np.asarray(leaves[0] & leaves[1])).sum())
        return (getattr(fn, "pallas_terminal", False), int(fn(*leaves)),
                want)
    finally:
        mesh.set_engine_mesh(None)


def test_tape_count_terminal_parity(rng, forced):
    pallas, got, want = tape_count_on_one_device(rng)
    assert pallas and got == want


def test_plane_count_pallas_2d(rng, forced):
    import jax.numpy as jnp

    x = rand_planes(rng, 4, 512)
    got = int(B.plane_count_pallas_traced(jnp.asarray(x), True))
    assert got == int(np.unpackbits(x.view(np.uint8)).sum())


def test_block_rows_always_lowerable():
    """Mosaic takes a row block only when it is the whole array or a
    multiple of 8 rows — never the (1, 512) row the kernels once used."""
    assert PU.block_rows(1) == 1  # whole array
    assert PU.block_rows(64) == 64
    assert PU.block_rows(6 * 64) == 64  # 6 shards of 64 x 512 words
    assert PU.block_rows(72) == 8
    assert PU.block_rows(96) == 32  # power-of-two divisors only, not 48
    assert PU.block_rows(65) is None
    assert B.pallas_count_eligible(6 * 32768)
    assert not B.pallas_count_eligible(65 * 512)
    assert not B.pallas_count_eligible(100)


def test_sharded_operands_route_to_xla_when_compiled(rng, forced,
                                                     monkeypatch):
    """A compiled pallas_call refuses mesh-sharded operands ("Mosaic
    kernels cannot be automatically partitioned"), so why_not answers
    "mesh" for them; the interpreter (plain XLA ops) keeps running."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pilosa_tpu.parallel import mesh

    m = mesh.analytics_mesh(jax.devices())
    assert m.devices.size > 1
    sharded = jax.device_put(
        rand_planes(rng, 8, WORDS),
        NamedSharding(m, P(None, (mesh.SHARD_AXIS, mesh.COL_AXIS))))
    local = jax.device_put(rand_planes(rng, 8, WORDS), jax.devices()[0])
    assert PU.why_not("pair_counts", sharded, local) is None  # interpret
    monkeypatch.setattr(PU, "use_interpret", lambda: False)
    assert PU.why_not("pair_counts", sharded, local) == "mesh"
    assert PU.why_not("pair_counts", local, local) is None


# ---------------------------------------------------------------------------
# Kill switch + metrics exposition
# ---------------------------------------------------------------------------


def _fallback_total():
    return sum(v for key, v in M.REGISTRY.snapshot()["counters"].items()
               if key.startswith(M.METRIC_OPS_PALLAS_FALLBACK))


def test_kill_switch_zero_dispatch_zero_overhead(rng, killed):
    a, b = rand_planes(rng, 4), rand_planes(rng, 4)
    snap_d = M.REGISTRY.value(M.METRIC_OPS_PALLAS_DISPATCH,
                              kernel="pair_counts")
    snap_f = _fallback_total()
    np.testing.assert_array_equal(np.asarray(G.pair_counts(a, b)),
                                  np.asarray(G._pair_counts_xla(a, b)))
    S.bsi_compare(encode(np.random.default_rng(7))[2], S.GT, 0)
    ops = sum_operands(rng, 4, 4, 2, WORDS)
    sums_d = dispatch_count("pair_sums")
    for got, want in zip(G.pair_sums(*ops), pair_sums_numpy(*ops)):
        np.testing.assert_array_equal(np.asarray(got), want)
    assert dispatch_count("pair_sums") == sums_d
    # a count program compiled under the switch has no Pallas terminal
    pallas, got, want = tape_count_on_one_device(rng)
    assert not pallas and got == want
    assert M.REGISTRY.value(M.METRIC_OPS_PALLAS_DISPATCH,
                            kernel="pair_counts") == snap_d
    # the switch must not even tick the fallback counter
    assert _fallback_total() == snap_f


def test_legacy_no_pallas_env_still_disables(rng, monkeypatch):
    monkeypatch.delenv("PILOSA_TPU_PALLAS", raising=False)
    monkeypatch.setenv("PILOSA_TPU_NO_PALLAS", "1")
    assert PU.disabled()
    assert PU.why_not("pair_counts") == "disabled"


def test_metrics_exposition(rng, forced):
    a, b = rand_planes(rng, 2), rand_planes(rng, 2)
    G.pair_counts(a, b)
    PU.fallback("pair_counts", "shape")
    text = M.REGISTRY.prometheus_text()
    assert 'ops_pallas_dispatch_total{kernel="pair_counts"}' in text
    assert 'ops_pallas_fallback_total{' in text
    assert 'why="shape"' in text


def test_failure_strikeout(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
    assert PU.why_not("demo_kernel") is None
    PU.failed("demo_kernel", RuntimeError("boom"))
    assert PU.why_not("demo_kernel") is None  # one strike: still on
    for _ in range(PU.MAX_FAILURES):
        PU.failed("demo_kernel", RuntimeError("boom"))
    assert PU.why_not("demo_kernel") == "failures"
    PU.reset_failures()
    assert PU.why_not("demo_kernel") is None


def test_mode_token_tracks_kill_switch(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
    on = PU.mode_token()
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "0")
    off = PU.mode_token()
    assert on != off and off == "classic"
