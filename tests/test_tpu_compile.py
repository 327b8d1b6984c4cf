"""The pair-count kernel compiled for a TPU v5e that is described, not
attached: what Mosaic accepts at the served widths, and the face the
benchmark's roofline readers match (``benchmark/harness/kernel_cost.py``:
an instruction named after the jitted program, one ``tpu_custom_call``
on the two packed operands). Nothing runs, so nothing here is a time.

The topology is described inside a fixture and only in this file: one
process at a time may load the TPU's library, and every xdist worker
imports every test file.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from pilosa_tpu.ops import groupby as G

TAXI_WORDS = 66 * 32768   # one chip's share of a taxi stack
SSB_WORDS = 6 * 32768     # SSB SF-1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as env:
        # no metadata server here: say what the described host is
        env.setenv("TPU_SKIP_MDS_QUERY", "1")
        env.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        env.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        env.setenv("TPU_WORKER_ID", "0")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A program compiled for a described chip cannot be read back from
    the persistent cache without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def compiled_text(one_chip, r1, r2, words):
    a = jax.ShapeDtypeStruct((r1, words), jnp.uint32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((r2, words), jnp.uint32, sharding=one_chip)
    return G._pair_counts_pallas.__wrapped__.lower(
        a, b, interpret=False).compile().as_text()


@pytest.mark.parametrize("r1,r2,words,body", [
    (24, 16, TAXI_WORDS, "vpu"),    # a taxi Q4 call
    (1, 80, TAXI_WORDS, "vpu"),     # TopN speed_mph: one real row
    (16, 256, SSB_WORDS, "vpu"),    # a pair_sums step of groupby-closed
    (8, 1000, SSB_WORDS, "vpu"),    # four row tiles of the second operand
    (31, 256, SSB_WORDS, "vpu"),    # the tallest accumulator the rule sends
    (25, 232, SSB_WORDS, "vpu"),    # a ragged last row group: it overlaps
    (100, 17, SSB_WORDS, "vpu"),    # a loop over row blocks of ``a``: a
                                    # traced, tile-aligned start into its ref
    (128, 256, SSB_WORDS, "mxu"),   # two tall sides
])
def test_pair_counts_compiles_for_v5e_with_the_face_the_readers_match(
        one_chip, r1, r2, words, body):
    assert G.pallas_body(r1, r2) == body
    text = compiled_text(one_chip, r1, r2, words)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1, calls
    call = calls[0].strip()
    # the name the trace shows and kernel_cost._MM_CALL's two operands
    assert re.match(r"(ROOT )?%_pair_counts_pallas(\.\d+)? = s32\[",
                    call), call
    # (the trace prints the operands with their shapes; this text has
    # them in the call's layout constraints)
    operands = re.search(r"custom-call\(([^)]*)\)", call).group(1)
    assert len(operands.split(", ")) == 2, operands
    layouts = call.split("operand_layout_constraints=")[1].split("}}")[0]
    shapes = re.findall(r"u32\[(\d+),(\d+)\]", layouts)
    assert len(shapes) == 2, call
    assert [int(w) for _, w in shapes] == [words, words]
    # the VPU body takes both operands as they are, the shorter first;
    # the MXU body pads both to whole sublane tiles
    rows = [int(r) for r, _ in shapes]
    if body == "vpu":
        assert rows == sorted([r1, r2])
    else:
        assert rows[0] == -(-r1 // 8) * 8 and rows[1] >= r2


def test_key_rows_compiles_for_v5e_with_the_face_the_reader_matches(one_chip):
    """The derivation of one 32-row block of SF-10's brand stack from its
    11 key bits, stored as 16 planes over 58 shards: one
    ``tpu_custom_call`` named ``%_key_rows_pallas``, whose shapes ``benchmark/kernel_costs/key_rows.py`` reads to the bytes a
    call moves."""
    from benchmark.harness import kernel_cost, manifest
    from pilosa_tpu.ops import keyrows as K

    words = 58 * 32768
    keys = jax.ShapeDtypeStruct((16, words), jnp.uint32, sharding=one_chip)
    first = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    text = K._key_rows_pallas.__wrapped__.lower(
        keys, first, rows=32, planes=11, interpret=False).compile().as_text()
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 1, calls
    assert re.match(r"(ROOT )?%_key_rows_pallas(\.\d+)? = u32\[32,",
                    calls[0]), calls[0]
    cost = kernel_cost.family("key_rows", manifest.BENCH)
    assert cost(calls[0]) == (words * 32.0 * 16, 4.0 * words * (16 + 32))


@pytest.mark.parametrize("rows,words", [
    (128, 32768),   # chip_smoke's key-rows step on four chips: one shard
    (256, 32768),
    (512, 8192),    # narrower than a word block, taller than VMEM takes
])
def test_key_rows_compiles_for_blocks_taller_than_sf10s(one_chip, rows, words):
    """A block of more rows than SF-10's 32 takes fewer words a grid
    step, so that its double-buffered blocks fit VMEM: Mosaic refused
    128 x 16,384 words (out of VMEM) before the word block knew the
    rows."""
    from pilosa_tpu.ops import keyrows as K

    keys = jax.ShapeDtypeStruct((16, words), jnp.uint32, sharding=one_chip)
    first = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    text = K._key_rows_pallas.__wrapped__.lower(
        keys, first, rows=rows, planes=9, interpret=False).compile().as_text()
    assert len([ln for ln in text.splitlines()
                if "tpu_custom_call" in ln]) == 1
    assert 2 * 4 * (rows + 16) * K._word_block(words, rows, 16) <= (
        K.BLOCK_BYTES)


@pytest.mark.parametrize("r2,words", [
    (32, 58 * 32768),   # SF-10: a derived 32-row block of the brand stack
    (256, SSB_WORDS),   # SF-1: a 256-row block
    # the live brand rows gathered: Q2.1's last chunk and Q2.2-Q4.1's at
    # SF-10, Q2.1's 40 rows padded to 64 and the others' 8 at SF-1
    (8, 58 * 32768),
    (64, SSB_WORDS),
    (8, SSB_WORDS),
])
def test_pair_sums_masks_each_plane_inside_the_kernel(one_chip, r2, words):
    """A Q2.x Sum's ``pair_sums`` call (8 years, 17 magnitude planes):
    still one ``%while`` that ``pair_sums_roofline`` reads as the same
    work, whose step hands the kernel the whole ``mags`` beside the plane
    index, so no masked ``u32[16, W]`` is made in the loop."""
    from benchmark.harness import kernel_cost

    def planes(rows):
        return jax.ShapeDtypeStruct((rows, words), jnp.uint32,
                                    sharding=one_chip)

    sign = jax.ShapeDtypeStruct((words,), jnp.uint32, sharding=one_chip)
    text = G._pair_sums_pallas.__wrapped__.lower(
        planes(8), planes(r2), planes(17), sign, sign,
        interpret=False).compile().as_text()
    loops = [ln.strip() for ln in text.splitlines()
             if re.match(r"\s*%while(\.\d+)? = .*\bwhile\(", ln)]
    assert len(loops) == 1, loops
    assert kernel_cost.pair_sums_from_text(loops[0]) == (
        kernel_cost.pair_sums(17, 8, r2, words))
    body_name = re.search(r"body=(%[\w.\-]+)", loops[0]).group(1)
    body = re.search(r"\n" + re.escape(body_name) + r" .*?\n}\n", text,
                     re.S).group(0)
    calls = [ln for ln in body.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1, calls
    layouts = calls[0].split("operand_layout_constraints=")[1].split("}}")[0]
    # the kernel lists the shorter operand first, and masks that one: a
    # plane ANDed into either side counts the same pairs
    short, tall = sorted((16, r2))
    assert re.findall(r"u32\[(\d+),(\d+)\]", layouts) == [
        (str(short), str(words)), (str(tall), str(words)),
        ("17", str(words))]
    made = [ln.strip() for ln in body.splitlines()
            if re.search(rf"= u32\[16,{words}\]", ln)
            and "get-tuple-element(" not in ln]
    assert not made, made
