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


# -- the kernel's two bodies: one answer, whichever the heights choose ------


def body_count(kernel, body):
    return M.REGISTRY.value(M.METRIC_OPS_PALLAS_BODY, kernel=kernel,
                            body=body)


@pytest.mark.parametrize("r1,r2,body", [
    (24, 16, "vpu"),     # a taxi Q4 call
    (8, 16, "vpu"),
    (1, 80, "vpu"),      # TopN: one filter row
    (1, 2, "vpu"),
    (2, 14, "vpu"),      # a BSI Sum: two sign classes x (depth + 1)
    (16, 256, "vpu"),    # a pair_sums step of groupby-closed
    (8, 1000, "vpu"),    # the paged 1000-row stack
    (8, 512, "vpu"),
    (31, 256, "vpu"),    # just under the line ...
    (48, 64, "vpu"),
    (56, 56, "mxu"),     # ... on it ...
    (32, 256, "mxu"),    # ... within a tenth either way: stays
    (64, 64, "mxu"),
    (64, 256, "mxu"),
    (128, 128, "mxu"),
    (128, 256, "mxu"),   # two tall sides
])
def test_pallas_body_is_a_pure_function_of_the_two_heights(r1, r2, body):
    assert G.pallas_body(r1, r2) == body
    assert G.pallas_body(r2, r1) == body  # AND commutes; so does the rule
    import inspect
    assert list(inspect.signature(G.pallas_body).parameters) == ["r1", "r2"]


#: both sides of the rule, and every way the VPU body walks its rows: one
#: group of ``b`` and one block of ``a``, several groups (256 rows), a
#: ragged last group that overlaps the one before (232, 90, 1000 -> 232),
#: more rows of ``a`` than a loop step takes, with and without a rest
PARITY_SHAPES = [
    (1, 80), (2, 14), (8, 16), (24, 16), (16, 256), (25, 232), (31, 256),
    (8, 1000), (100, 17), (128, 24), (40, 90), (32, 150), (128, 256)]


def assert_bodies_equal_the_scan(a, b):
    """The body the rule picks and the MXU body both equal the XLA scan."""
    import jax

    want = np.asarray(G._pair_counts_xla(a, b))
    for body in (G._pair_counts_traced, G._pair_counts_mxu):
        got = jax.jit(lambda x, y: body(x, y, True))(a, b)
        assert got.shape == want.shape and got.dtype == np.int32
        np.testing.assert_array_equal(np.asarray(got), want)
    return want


@pytest.mark.parametrize("w", [1, 7, 512, 2055, 8192])
@pytest.mark.parametrize("r1,r2", PARITY_SHAPES)
def test_pair_counts_bodies_parity(rng, r1, r2, w):
    """Both sides of the rule at every width class (a word, nothing
    aligned, one block, a padded odd width, the widest the interpreter
    takes), an all-ones and an empty plane on each side."""
    a, b = rand_planes(rng, r1, w), rand_planes(rng, r2, w)
    a[0] = 0xFFFFFFFF
    b[0], b[-1] = 0xFFFFFFFF, 0
    if r1 > 1:
        a[-1] = 0
    want = assert_bodies_equal_the_scan(a, b)
    assert want[0, 0] == 32 * w and not want[:, -1].any()


@pytest.mark.parametrize("r1,r2", [(24, 16), (25, 232), (100, 17)])
def test_pair_counts_bodies_parity_at_a_shard(rng, r1, r2):
    """One whole shard, 32,768 words: several grid steps of the VPU
    body's widest word block, all-ones planes to the accumulators' top."""
    a, b = rand_planes(rng, r1, 32768), rand_planes(rng, r2, 32768)
    a[0] = b[0] = b[r2 // 2] = 0xFFFFFFFF
    want = assert_bodies_equal_the_scan(a, b)
    assert want[0, 0] == want[0, r2 // 2] == 1 << 20


def kernel_listing(r1, r2, words=1024, masked=False):
    """Equations of the kernel's jaxpr, its loops' bodies included: what
    is traced, lowered and serialized once a shape and a process
    (``masked``: the VPU body that ANDs a plane of 17 into ``a``)."""
    import jax
    from jax.extend import core as jcore

    def count(jaxpr):
        n = len(jaxpr.eqns)
        for eqn in jaxpr.eqns:
            for v in eqn.params.values():
                for j in v if isinstance(v, (tuple, list)) else (v,):
                    if isinstance(j, jcore.ClosedJaxpr):
                        j = j.jaxpr
                    if isinstance(j, jcore.Jaxpr):
                        n += count(j)
        return n

    spec = [jax.ShapeDtypeStruct((r, words), np.uint32) for r in (r1, r2)]
    if masked:
        spec += [jax.ShapeDtypeStruct((17, words), np.uint32),
                 jax.ShapeDtypeStruct((1,), np.int32)]
        closed = jax.make_jaxpr(
            lambda a, b, m, k: G._pair_counts_vpu(a, b, True, m, k))(*spec)
    else:
        closed = jax.make_jaxpr(
            lambda a, b: G._pair_counts_traced(a, b, True))(*spec)
    call, = [e for e in closed.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    return count(call.params["jaxpr"])


#: the most equations a VPU-body kernel may list, whatever its heights
#: (the MXU body lists ~120). A listing is paid once a shape and a
#: process before the compile cache can be asked, on the benchmark's
#: host 2-6 ms an AND + popcount + add inside the server: PR 34's
#: unrolled body (3,000 equations at 31 x 256, 2,400 at 100 x 17) added
#: 13 s to groupby-closed's warm-up of 8 (PERF.md section 5)
MAX_LISTING = 500


@pytest.mark.parametrize("r1,r2", [
    (8, 16), (24, 16), (24, 24), (16, 256), (25, 232), (31, 256), (8, 1000),
    (100, 17), (128, 24), (127, 30), (47, 40), (55, 56), (27, 1000)])
def test_the_vpu_body_lists_a_bounded_program(r1, r2):
    assert G.pallas_body(r1, r2) == "vpu"
    assert kernel_listing(r1, r2) <= MAX_LISTING


@pytest.mark.parametrize("r1,r2", [(16, 32), (16, 256), (14, 25), (65, 32)])
def test_the_masked_vpu_body_lists_what_the_plain_one_does_and_a_little(
        r1, r2):
    """``pair_sums``' masked body adds one listing of the mask's rotation
    and AND a sub-step, whatever the heights: no program grows with them."""
    plain = kernel_listing(r1, r2)
    assert plain < kernel_listing(r1, r2, masked=True) <= plain + 60


def test_the_vpu_body_does_not_unroll_over_rows():
    """The next edit that unrolls over the rows of either operand fails
    here, not in a chip check of set-up seconds. Four times the rows of
    the first operand and sixteen times those of the second list about
    twice the program (the rows of one loop step and of a rest); more
    tiles, more words or a taller first operand list nothing more; and
    the kernel lists the rows of the shorter operand, whichever it is."""
    small = kernel_listing(8, 16)
    assert kernel_listing(31, 256) <= 2.2 * small
    assert kernel_listing(16, 256) <= 1.3 * small
    assert kernel_listing(8, 256) <= 1.2 * small
    assert kernel_listing(8, 1000) == kernel_listing(8, 256)
    assert kernel_listing(16, 256, 8192) == kernel_listing(16, 256)
    assert kernel_listing(47, 60) == kernel_listing(31, 60)
    assert kernel_listing(100, 17) == kernel_listing(17, 100)
    assert kernel_listing(128, 8) == kernel_listing(8, 128) <= 1.2 * small


def test_vpu_body_takes_the_widest_block_that_divides_and_fits():
    # a shard is 32,768 words: whole-shard operands never pad past 512
    assert G._vpu_block_words(24, 16, 66 * 32768) == G._VPU_MAX_BW
    assert G._vpu_block_words(24, 16, 5 * 512) == 512
    assert G._vpu_block_words(24, 16, 12 * 512) == 2048
    # double-buffered inputs stay under the byte budget
    for r1, tr2 in [(1, 80), (16, 256), (31, 256), (128, 32)]:
        bw = G._vpu_block_words(r1, tr2, 1 << 20)
        assert 512 <= bw <= G._VPU_MAX_BW and (1 << 20) % bw == 0
        assert 2 * 4 * (r1 + tr2) * bw <= G._VPU_INPUT_BYTES


@pytest.mark.parametrize("r1,r2,body", [(24, 16, "vpu"), (128, 256, "mxu")])
def test_pair_counts_dispatch_names_its_body(rng, forced, r1, r2, body):
    a, b = rand_planes(rng, r1), rand_planes(rng, r2)
    other = "mxu" if body == "vpu" else "vpu"
    before = (dispatch_count("pair_counts"),
              body_count("pair_counts", body),
              body_count("pair_counts", other))
    got = np.asarray(G.pair_counts(a, b))
    assert (dispatch_count("pair_counts"), body_count("pair_counts", body),
            body_count("pair_counts", other)) == (
                before[0] + 1, before[1] + 1, before[2])
    np.testing.assert_array_equal(got, np.asarray(G._pair_counts_xla(a, b)))


def test_a_fallback_names_no_body(rng, killed):
    before = sum(body_count("pair_counts", b) for b in ("vpu", "mxu"))
    G.pair_counts(rand_planes(rng, 8), rand_planes(rng, 16))
    assert sum(body_count("pair_counts", b)
               for b in ("vpu", "mxu")) == before


def test_the_family_dispatches_name_their_bodies(rng, forced):
    """``pair_sums`` (both signs stacked: 2 * r1 rows a step), TopN (one
    filter row) and the BSI Sum (two sign classes) reach the kernel
    through ``_pair_counts_traced`` and tick the body its rule picks
    for the shapes they send, once a dispatch."""
    before = {k: (dispatch_count(k), body_count(k, "vpu"),
                  body_count(k, "mxu"))
              for k in ("pair_sums", "topn", "bsi_sum")}
    ops = sum_operands(rng, 8, 256, 3, WORDS)
    got = G.pair_sums(*ops)
    for g, n in zip(got, pair_sums_numpy(*ops)):
        np.testing.assert_array_equal(np.asarray(g), n)
    planes, filt = rand_planes(rng, 80), rand_planes(rng, 1)[0]
    np.testing.assert_array_equal(
        np.asarray(T.row_counts(planes, filt)),
        np.asarray(B.row_counts(planes, filt)))
    np.testing.assert_array_equal(
        np.asarray(T._row_counts_pallas(planes, filt, True)),
        np.asarray(B.row_counts(planes, filt)))
    cols, vals, bsi = encode(rng)
    assert S.bsi_sum(bsi, bsi[S.EXISTS]) == (int(vals.sum()), cols.size)
    for g, x in zip(S._plane_popcounts_pallas(bsi, bsi[S.EXISTS], True),
                    S._plane_popcounts_xla(bsi, bsi[S.EXISTS])):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
    assert G.pallas_body(16, 256) == G.pallas_body(1, 80) == "vpu"
    for k, (d, vpu, mxu) in before.items():
        assert (dispatch_count(k), body_count(k, "vpu"),
                body_count(k, "mxu")) == (d + 1, vpu + 1, mxu)


def test_pair_sums_past_the_stacking_limit_names_the_body_of_its_calls(
        rng, forced):
    # 2 * 70 > 128: two calls of 70 x 140 rows a step, the MXU body's
    assert G._pair_sums_step_rows(70) == 70
    assert G._pair_sums_step_rows(64) == 128
    assert G.pallas_body(70, 140) == "mxu"
    ops = sum_operands(rng, 70, 140, 2, 40)
    before = body_count("pair_sums", "mxu")
    got = G.pair_sums(*ops)
    assert body_count("pair_sums", "mxu") == before + 1
    for g, n in zip(got, pair_sums_numpy(*ops)):
        np.testing.assert_array_equal(np.asarray(g), n)


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


@pytest.mark.parametrize("r1,r2,d,w", [
    (7, 25, 17, 1000),   # r1 odd, a width no multiple of the word block
    (8, 32, 17, 1536),   # SF-10's call: 16 x 32 a step
    (7, 232, 5, 1024),   # a ragged last row group of the second operand
    (8, 256, 17, 1024),  # SF-1's call: 16 x 256 a step, four row groups
    (8, 25, 3, 512),     # fewer planes than the mask's sublane tile
    (65, 32, 4, 512),    # 2 * r1 > 128: two masked calls a step
])
def test_pair_sums_kernel_that_masks_in_vmem_is_exact(rng, r1, r2, d, w):
    """The VPU body ANDs each magnitude plane into its operand itself:
    its answer equals the XLA route's and numpy's, with values of both
    signs and one plane all zero."""
    assert G._pair_sums_fuses_mask(r1, r2)
    a, b, mags, pos, neg = sum_operands(rng, r1, r2, d, w)
    mags[d // 2] = 0
    assert pos.any() and neg.any()
    got = G._pair_sums_pallas(a, b, mags, pos, neg, interpret=True)
    want = pair_sums_numpy(a, b, mags, pos, neg)
    for g, x, n in zip(got, G._pair_sums_xla(a, b, mags, pos, neg), want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(g), n)
    assert not np.asarray(got[0])[d // 2].any()


def fused_mask_count(kernel):
    return M.REGISTRY.value(M.METRIC_OPS_PALLAS_FUSED_MASK, kernel=kernel)


@pytest.mark.parametrize("r1,r2,d,w,fused", [
    (8, 256, 3, WORDS, True),   # the VPU body takes the mask itself
    (70, 140, 2, 40, False),    # the MXU body is given the masked operand
])
def test_pair_sums_counts_the_dispatches_whose_kernel_took_the_mask(
        rng, forced, r1, r2, d, w, fused):
    assert G._pair_sums_fuses_mask(r1, r2) == fused
    ops = sum_operands(rng, r1, r2, d, w)
    before = dispatch_count("pair_sums"), fused_mask_count("pair_sums")
    got = G.pair_sums(*ops)
    assert (dispatch_count("pair_sums"), fused_mask_count("pair_sums")) == (
        before[0] + 1, before[1] + fused)
    for g, n in zip(got, pair_sums_numpy(*ops)):
        np.testing.assert_array_equal(np.asarray(g), n)


def test_the_mask_block_counts_in_the_word_block_rule():
    # SF-10's step: 16 + 32 rows fit the widest block, 8 more do not
    words = 58 * 32768
    assert G._vpu_block_words(16, 32, words) == G._VPU_MAX_BW
    assert G._vpu_block_words(16, 32, words, masked=True) == 8192
    for r1, tr2 in [(16, 256), (14, 32), (1, 80)]:
        bw = G._vpu_block_words(r1, tr2, 1 << 20, masked=True)
        assert 2 * 4 * (r1 + tr2 + G._MASK_ROWS) * bw <= G._VPU_INPUT_BYTES


#: sha1 of ``_pair_counts_pallas``' jaxpr (interpreted, the kernel's
#: source location left out) before the kernel learnt to take a mask: a
#: caller that passes none runs the program it ran then
UNMASKED_JAXPRS = {
    (24, 16, 4096): "bff9c5e14a53",   # a taxi Q4 call
    (1, 80, 4096): "c108e228a8b3",    # TopN: one filter row
    (16, 256, 1024): "9e21da09e51c",  # SF-1's pair_sums step, unmasked
    (8, 1000, 1024): "47c7b4a12f24",  # four row tiles of the second
    (25, 232, 1536): "e4873b3cf1a6",  # a ragged last row group
    (100, 17, 1024): "ed394c9d1d47",  # swapped: the shorter goes first
    (128, 256, 1024): "274bcf04ec3b", # the MXU body
    (48, 1, 4096): "b6274f740e00",    # plane popcounts against a filter
}


@pytest.mark.parametrize("shape", sorted(UNMASKED_JAXPRS))
def test_pair_counts_without_a_mask_lowers_to_the_program_it_did(shape):
    import hashlib
    import re

    import jax
    import jax.numpy as jnp

    r1, r2, w = shape
    text = str(jax.make_jaxpr(
        lambda a, b: G._pair_counts_pallas.__wrapped__(a, b, interpret=True)
    )(jax.ShapeDtypeStruct((r1, w), jnp.uint32),
      jax.ShapeDtypeStruct((r2, w), jnp.uint32)))
    text = re.sub(r"name_and_src_info=\S+ at \S+", "", text)
    assert hashlib.sha1(text.encode()).hexdigest()[:12] == (
        UNMASKED_JAXPRS[shape])


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
