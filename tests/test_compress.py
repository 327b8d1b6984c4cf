"""Block-compressed device-resident bitmap tiles (ops/ctiles.py +
core/stacked.py integration).

The invariants are the real ones: every compressed read path is
bit-identical to the dense oracle (decode, tile-skipping row_counts, the
active-tile BSI compare, the full executor battery), the
``PILOSA_TPU_COMPRESS=0`` kill switch does zero work (no compressed
blocks, no metric ticks), and the chunked ingest scatter matches the
per-row native loop for imports wider than one chunk.
"""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.core import FieldOptions, FieldType, Holder
from pilosa_tpu.core import stacked as stx
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.ops import bitmap as B
from pilosa_tpu.ops import bsi as S
from pilosa_tpu.ops import ctiles as C
from pilosa_tpu.ops import pallas_util as PU
from pilosa_tpu.ops import scatter as SC
from pilosa_tpu.pql import Executor
from pilosa_tpu.shardwidth import SHARD_WIDTH


@pytest.fixture(autouse=True)
def _clean_strikes():
    PU.reset_failures()
    yield
    PU.reset_failures()


@pytest.fixture
def forced(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_COMPRESS", "1")


@pytest.fixture
def killed(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_COMPRESS", "0")


def dispatch_count(kernel: str) -> float:
    return M.REGISTRY.value(M.METRIC_OPS_PALLAS_DISPATCH,
                            kernel=kernel) or 0.0


def fallback_count(kernel: str, why: str) -> float:
    return M.REGISTRY.value(M.METRIC_OPS_PALLAS_FALLBACK, kernel=kernel,
                            why=why) or 0.0


def _sparse_block(rng, rows, words, n_bits=40):
    host = np.zeros((rows, words), dtype=np.uint32)
    host[rng.integers(0, rows, n_bits), rng.integers(0, words, n_bits)] = \
        rng.integers(1, 2 ** 32, n_bits, dtype=np.uint32)
    return host


# ---------------------------------------------------------------------------
# classify / decode round trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(2, 1), (3, 7), (8, 512), (16, 1000),
                                   (5, 2048), (1, 4096)])
def test_decode_roundtrip(forced, shape):
    rng = np.random.default_rng(hash(shape) % 2 ** 31)
    host = _sparse_block(rng, *shape)
    host[0, :] = 0  # guarantee at least one all-zero row
    cb = C.maybe_compress(host, kind="set")
    assert cb is not None
    assert np.array_equal(np.asarray(cb.decode()), host)
    # row-subset decode
    sub = [0, shape[0] - 1]
    assert np.array_equal(np.asarray(cb.decode(rows=sub)), host[sub])


def test_tags_zero_run_dense(forced):
    words = 2048
    zero = np.zeros((4, words), dtype=np.uint32)
    cb = C.maybe_compress(zero, kind="set")
    assert cb.dense_tiles == 0 and cb.run_tiles == 0 and cb.zero_tiles > 0
    assert np.asarray(cb.row_counts()).tolist() == [0] * 4

    ones = np.full((4, words), 0xFFFFFFFF, dtype=np.uint32)
    cb = C.maybe_compress(ones, kind="set")
    assert cb.dense_tiles == 0 and cb.run_tiles == 4 * cb.n_tiles
    assert cb.const_uniform
    assert np.array_equal(np.asarray(cb.decode()), ones)
    assert np.asarray(cb.row_counts()).tolist() == [words * 32] * 4

    rng = np.random.default_rng(3)
    mixed = np.zeros((4, words), dtype=np.uint32)
    mixed[1] = 0xFFFFFFFF
    mixed[2, :100] = rng.integers(1, 2 ** 32, 100, dtype=np.uint32)
    cb = C.maybe_compress(mixed, kind="set")
    assert cb.zero_tiles and cb.run_tiles and cb.dense_tiles
    assert np.array_equal(np.asarray(cb.decode()), mixed)


def test_unaligned_width_run_rows_stay_exact(forced):
    # a non-tile-multiple width zero-pads the last tile: an all-ones row
    # must still decode and count exactly (its last tile is dense, not
    # a truncated run)
    words = C.TILE_WORDS + 100
    host = np.full((3, words), 0xFFFFFFFF, dtype=np.uint32)
    cb = C.maybe_compress(host, kind="set")
    assert np.array_equal(np.asarray(cb.decode()), host)
    assert np.asarray(cb.row_counts()).tolist() == [words * 32] * 3


# ---------------------------------------------------------------------------
# tile-skipping row_counts vs the dense oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("filtered", [False, True])
def test_row_counts_parity(forced, monkeypatch, filtered):
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
    rng = np.random.default_rng(7)
    host = _sparse_block(rng, 16, 4096, n_bits=200)
    cb = C.maybe_compress(host, kind="set")
    filt = None
    if filtered:
        filt = jnp.asarray(rng.integers(
            0, 2 ** 32, 4096, dtype=np.uint32).astype(np.uint32))
    d0 = dispatch_count("ctile_count")
    got = np.asarray(cb.row_counts(filt))
    want = np.asarray(B.row_counts(host, filt))
    assert np.array_equal(got, want)
    assert dispatch_count("ctile_count") == d0 + 1, \
        "forced mode must take the Pallas ctile_count kernel"


def test_row_counts_parity_pallas_killed(forced, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "0")
    rng = np.random.default_rng(8)
    host = _sparse_block(rng, 16, 4096, n_bits=200)
    cb = C.maybe_compress(host, kind="set")
    d0 = dispatch_count("ctile_count")
    got = np.asarray(cb.row_counts())
    assert np.array_equal(got, np.asarray(B.row_counts(host)))
    assert dispatch_count("ctile_count") == d0, \
        "XLA compressed path must not tick the Pallas dispatch counter"


def test_nonuniform_const_filter_falls_back_exact(forced):
    # whole-tile runs of an arbitrary word have no closed form under a
    # filter: the scan must decode and still be bit-identical
    host = np.full((4, 2048), 0xDEADBEEF, dtype=np.uint32)
    cb = C.maybe_compress(host, kind="set")
    assert not cb.const_uniform
    rng = np.random.default_rng(9)
    filt = jnp.asarray(rng.integers(
        0, 2 ** 32, 2048, dtype=np.uint32).astype(np.uint32))
    f0 = M.REGISTRY.value(M.METRIC_COMPRESS_FALLBACK, why="const",
                          kind="scan") or 0.0
    got = np.asarray(cb.row_counts(filt))
    assert np.array_equal(got, np.asarray(B.row_counts(host, filt)))
    assert (M.REGISTRY.value(M.METRIC_COMPRESS_FALLBACK, why="const",
                             kind="scan") or 0.0) == f0 + 1


# ---------------------------------------------------------------------------
# policy: ratio rule, size floor, kill switch
# ---------------------------------------------------------------------------


@pytest.fixture
def single_device_mesh():
    # auto-mode policy tests: conftest boots 8 virtual devices, whose
    # mesh guard would mask the size/ratio rules under scrutiny
    from pilosa_tpu.parallel import mesh as PM
    import jax

    PM.set_engine_mesh(PM.analytics_mesh(jax.devices()[:1]))
    yield
    PM.set_engine_mesh(None)


def test_incompressible_block_stays_dense(single_device_mesh, monkeypatch):
    monkeypatch.delenv("PILOSA_TPU_COMPRESS", raising=False)
    rng = np.random.default_rng(10)
    host = rng.integers(0, 2 ** 32, (32, 1024),
                        dtype=np.uint32).astype(np.uint32)  # 128 KiB random
    f0 = M.REGISTRY.value(M.METRIC_COMPRESS_FALLBACK, why="ratio",
                          kind="set") or 0.0
    assert C.maybe_compress(host, kind="set") is None
    assert (M.REGISTRY.value(M.METRIC_COMPRESS_FALLBACK, why="ratio",
                             kind="set") or 0.0) == f0 + 1


def test_small_block_stays_dense_by_default(monkeypatch):
    monkeypatch.delenv("PILOSA_TPU_COMPRESS", raising=False)
    host = np.zeros((8, 32), dtype=np.uint32)  # 1 KiB << MIN_BYTES
    assert C.maybe_compress(host, kind="set") is None


def test_multi_device_mesh_stays_dense_in_auto_mode(monkeypatch):
    # conftest's 8 virtual devices: auto mode must keep mesh-sharded
    # stacks dense (placement rule), metered as why="mesh"
    monkeypatch.delenv("PILOSA_TPU_COMPRESS", raising=False)
    from pilosa_tpu.parallel.mesh import engine_mesh

    if engine_mesh().devices.size <= 1:
        pytest.skip("needs the virtual multi-device mesh")
    host = np.zeros((16, 65536), dtype=np.uint32)
    f0 = M.REGISTRY.value(M.METRIC_COMPRESS_FALLBACK, why="mesh",
                          kind="set") or 0.0
    assert C.maybe_compress(host, kind="set") is None
    assert M.REGISTRY.value(M.METRIC_COMPRESS_FALLBACK, why="mesh",
                            kind="set") == f0 + 1


def _compress_series(snap: dict) -> dict:
    return {k: v for section in ("counters", "gauges")
            for k, v in snap[section].items()
            if k.startswith("device_compress")}


def test_kill_switch_zero_work_zero_ticks(killed):
    before = _compress_series(M.REGISTRY.snapshot())
    host = np.zeros((64, 4096), dtype=np.uint32)  # would compress hugely
    assert C.maybe_compress(host, kind="set") is None
    assert _compress_series(M.REGISTRY.snapshot()) == before, \
        "the kill switch must not move any compress metric"


# ---------------------------------------------------------------------------
# stacked integration: the full read surface, compressed vs kill switch
# ---------------------------------------------------------------------------


QUERIES = [
    "Count(Row(f=3))",
    "TopN(f, n=10)",
    "Count(Row(v > 5))",
    "Count(Row(v < -20))",
    "Count(Row(v == 7))",
    "Count(Row(v != 7))",
    "Count(Row(v >= -100))",
    "Count(Row(-10 < v < 20))",
    "Count(Intersect(Row(f=1), Row(v >= 0)))",
    "GroupBy(Rows(f))",
    "Min(field=v)",
    "Max(field=v)",
    "Sum(field=v)",
]


def _battery(monkeypatch, mode: str):
    monkeypatch.setenv("PILOSA_TPU_COMPRESS", mode)
    h = Holder()
    e = Executor(h)
    h.create_index("i").create_field("f")
    h.index("i").create_field(
        "v", FieldOptions(type=FieldType.INT, min=-100, max=100))
    f = h.index("i").field("f")
    v = h.index("i").field("v")
    rng = np.random.default_rng(5)
    for s in range(2):
        rows = rng.integers(0, 30, 400)
        cols = s * SHARD_WIDTH + rng.integers(0, SHARD_WIDTH, 400)
        f.import_bits(rows.tolist(), cols.tolist())
        vc = s * SHARD_WIDTH + rng.integers(0, SHARD_WIDTH, 200)
        v.set_values(vc.tolist(), rng.integers(-100, 100, 200).tolist())
    out = [e.execute("i", q) for q in QUERIES]
    # a write between queries exercises the advance path (compressed
    # blocks decay to dense device-side), then the battery again
    e.execute("i", "Set(12345, f=3)")
    out.extend(e.execute("i", q) for q in QUERIES)
    return h, f, repr(out)


def _built_blocks():
    return (M.REGISTRY.value(M.METRIC_COMPRESS_BLOCKS, kind="set"),
            M.REGISTRY.value(M.METRIC_COMPRESS_BLOCKS, kind="bsi"))


def test_executor_battery_bit_identical(monkeypatch):
    c0 = _built_blocks()
    _, _, compressed = _battery(monkeypatch, "1")
    c1 = _built_blocks()
    assert c1[0] > c0[0] and c1[1] > c0[1], \
        "forced mode built no compressed-resident blocks"
    _, _, dense = _battery(monkeypatch, "0")
    assert _built_blocks() == c1, "kill switch still built compressed blocks"
    assert compressed == dense


def test_compressed_stack_charges_fewer_bytes(monkeypatch):
    d0 = M.REGISTRY.value(M.METRIC_COMPRESS_DENSE_BYTES)
    s0 = M.REGISTRY.value(M.METRIC_COMPRESS_STORED_BYTES)
    _battery(monkeypatch, "1")
    dense = M.REGISTRY.value(M.METRIC_COMPRESS_DENSE_BYTES) - d0
    stored = M.REGISTRY.value(M.METRIC_COMPRESS_STORED_BYTES) - s0
    # every random bit densifies its whole tile, so this fixture is a
    # worst case for tiling; 2x is still a clear win (clustered rows:
    # test_clustered_rows_store_ten_times_smaller)
    assert dense > 0 and stored < dense / 2, \
        "sparse fixture should compress at least 2x"
    # the budget gauge mirrors the compressed accounting
    assert M.REGISTRY.value(M.METRIC_DEVICE_BUDGET_RESIDENT_BYTES) \
        == stx.BUDGET.used


def test_clustered_rows_store_ten_times_smaller(forced):
    """Rows that light one or two word tiles of a wide block, the shape
    a high-cardinality field has: the DeviceBudget is charged the stored
    bytes, so ten times the rows stay resident under one cap."""
    rng = np.random.default_rng(21)
    rows, words = 256, 1 << 14
    t = C.tile_words(words)
    host = np.zeros((rows, words), dtype=np.uint32)
    for r in range(rows):
        for _ in range(int(rng.integers(1, 3))):
            lo = int(rng.integers(0, words // t)) * t \
                + int(rng.integers(0, t - 16))
            n = int(rng.integers(4, 16))
            host[r, lo:lo + n] = rng.integers(1, 1 << 32, n,
                                              dtype=np.uint32)
    cb = C.maybe_compress(host, kind="set")
    assert cb.dense_nbytes == host.nbytes
    assert cb.dense_nbytes >= 10 * cb.nbytes
    np.testing.assert_array_equal(np.asarray(cb.decode()), host)


def test_bsi_compare_fast_path_parity(forced):
    rng = np.random.default_rng(11)
    depth, words = 7, 8192
    cols = rng.integers(0, words * 32, 300)
    vals = rng.integers(-50, 50, 300)
    planes = np.asarray(S.encode_values(
        np.asarray(cols), np.asarray(vals), depth, words))
    cb = C.maybe_compress(planes, kind="bsi")
    assert cb is not None
    dense = jnp.asarray(planes)
    for op, v, v2 in [("eq", 3, None), ("ne", 3, None), ("lt", 0, None),
                      ("le", -5, None), ("gt", 10, None), ("ge", -49, None),
                      ("between", -10, 20)]:
        want = np.asarray(S.bsi_compare(dense, op, v, v2))
        got = np.asarray(C.bsi_compare_compressed(cb, op, v, v2))
        assert np.array_equal(got, want), op


def test_bsi_compare_empty_stack_short_circuits(forced):
    planes = np.zeros((S.OFFSET + 3, 4096), dtype=np.uint32)
    cb = C.maybe_compress(planes, kind="bsi")
    assert cb.active_tiles.size == 0
    out = np.asarray(C.bsi_compare_compressed(cb, "eq", 0))
    assert not out.any()


def test_metrics_exposition(monkeypatch):
    # satellite: DeviceBudget's own gauges/counters + the compress series
    # must all render as prometheus exposition
    monkeypatch.setenv("PILOSA_TPU_COMPRESS", "1")
    monkeypatch.setattr(stx, "BUDGET", stx.DeviceBudget(1 << 20))
    rng = np.random.default_rng(12)
    for seed in range(3):  # several stacks force evictions under 1 MiB
        host = _sparse_block(rng, 16, 65536, n_bits=100)
        cb = C.maybe_compress(host, kind="set")
        stx.BUDGET.charge(("t", seed), cb.dense_nbytes, lambda: None)
        cb.row_counts()
    text = M.REGISTRY.prometheus_text()
    for name in ("device_budget_resident_bytes",
                 "device_budget_evictions_total",
                 "device_compress_blocks_total",
                 "device_compress_dense_bytes_total",
                 "device_compress_stored_bytes_total",
                 "device_compress_ratio",
                 "device_compress_tiles_skipped_total"):
        assert name in text, name


# ---------------------------------------------------------------------------
# satellite: chunked ingest scatter
# ---------------------------------------------------------------------------


def test_why_not_ingest_chunk_rules(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
    assert SC.why_not_ingest(0, 1, 512) == "shape"
    assert SC.why_not_ingest(10, 1, SC.MAX_FLAT_WORDS * 2) == "shape"
    # multi-chunk totals are now eligible (the old caps rejected them)
    rows = 2 * (SC.MAX_FLAT_WORDS // 512)
    assert SC.why_not_ingest(100, rows, 512) is None
    # ... but the interpreter keeps the native loop beyond a few chunks
    huge = 100 * (SC.MAX_FLAT_WORDS // 512)
    assert SC.why_not_ingest(100, huge, 512) == "interpret"


def test_scatter_chunked_matches_native_oracle(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
    monkeypatch.setattr(SC, "MAX_FLAT_WORDS", 1 << 10)  # 2 rows per chunk
    chunks = []
    real = SC._scatter_chunk

    def spy(planes, uslots, addr, masks):
        chunks.append(len(uslots))
        return real(planes, uslots, addr, masks)

    monkeypatch.setattr(SC, "_scatter_chunk", spy)
    rng = np.random.default_rng(13)
    words, rows = 512, 9
    planes = np.zeros((rows, words), dtype=np.uint32)
    planes[rng.integers(0, rows, 50), rng.integers(0, words, 50)] = \
        rng.integers(1, 2 ** 32, 50, dtype=np.uint32)
    want = planes.copy()
    slots = rng.integers(0, rows, 400)
    cols = rng.integers(0, words * 32, 400)
    d0 = dispatch_count("ingest_scatter")
    changed = SC.scatter_new_bits_bulk(planes, slots, cols)
    newbits = 0
    for s, c in zip(slots, cols):
        w, b = divmod(int(c), 32)
        if not (want[s, w] >> np.uint32(b)) & 1:
            newbits += 1
            want[s, w] |= np.uint32(1 << b)
    assert changed == newbits
    assert np.array_equal(planes, want)
    assert len(chunks) >= 4, chunks  # 9 touched rows, 2 per chunk
    assert all(c <= 2 for c in chunks), chunks
    assert dispatch_count("ingest_scatter") == d0 + 1


def test_import_bits_multi_row_stays_on_device(monkeypatch):
    # 3 distinct rows x WORDS_PER_SHARD used to be rejected wholesale
    # (n_rows*words over the flat cap); the chunked grid keeps it
    # on-device and bit-identical
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
    h = Holder()
    e = Executor(h)
    h.create_index("i").create_field("f")
    f = h.index("i").field("f")
    rng = np.random.default_rng(14)
    rows = rng.integers(0, 3, 90).tolist()
    cols = rng.integers(0, SHARD_WIDTH, 90).tolist()
    d0 = dispatch_count("ingest_scatter")
    f.import_bits(rows, cols)
    assert dispatch_count("ingest_scatter") > d0, \
        "multi-row import fell off the device scatter path"
    want = {r: len({c for rr, c in zip(rows, cols) if rr == r})
            for r in set(rows)}
    for r, n in want.items():
        assert e.execute("i", f"Count(Row(f={r}))")[0] == n
