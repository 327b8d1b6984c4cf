"""Key planes (ops/keyrows.py, core/stacked.KeyedSet): a mutex stack too
tall for the device budget held as the bits of each record's slot + 1,
its rows derived on the device.

The kernel against the plain numpy derivation under the Pallas
interpreter; the served answers of a key-plane stack against the dense
form's and numpy's, for every read the SSB cells send and for writes;
the rule that picks the form, which every configuration of the benchmark
but SF-10 must leave dense; and an evictor racing a walk.
"""

import json
import pathlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.api import API
from pilosa_tpu.core import FieldOptions, FieldType
from pilosa_tpu.core import stacked as stx
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.ops import keyrows as K
from pilosa_tpu.ops import pallas_util as PU
from pilosa_tpu.parallel import mesh as PM
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD

ROOT = pathlib.Path(__file__).resolve().parents[1]


def key_planes(values, bits, words):
    """Key planes of a value per column (0: none), ``bits`` planes
    padded to a sublane tile, as numpy."""
    cols = np.asarray(values, dtype=np.int64).reshape(words, 32)
    out = np.zeros((K.padded_planes(bits), words), dtype=np.uint32)
    weights = np.uint32(1) << np.arange(32, dtype=np.uint32)
    for i in range(bits):
        out[i] = (((cols >> i) & 1).astype(np.uint32) * weights).sum(
            axis=1, dtype=np.uint64).astype(np.uint32)
    return out


# -- the kernel ---------------------------------------------------------------

#: the kernel's Python function under ``guarded_call`` and ``jax.jit``
PALLAS = K._key_rows_pallas.__wrapped__.__wrapped__


@pytest.mark.parametrize("bits", [1, 2, 5, 10, 11])
@pytest.mark.parametrize("rows", [1, 8, 32])
def test_kernel_equals_the_numpy_reference(bits, rows, monkeypatch):
    rng = np.random.default_rng(bits * 100 + rows)
    # 2.75 word blocks of 1,024: the last grid step hangs over the end
    monkeypatch.setattr(K, "BLOCK_WORDS", 1024)
    words = 2 * 1024 + 768
    cap = max(rows, (1 << bits) - 1)
    keys = key_planes(rng.integers(0, cap + 1, words * 32), bits, words)
    for first in sorted({0, rows, cap - rows, cap - 1}):
        if first < 0:
            continue
        want = K.reference(keys, range(first, first + rows))
        planes = max(bits, K.key_bits(first + rows))
        first_a = jnp.asarray(np.array([first], np.int32))
        # a fresh program: a cached one would keep its first word block
        got = jax.jit(lambda k, f: PALLAS(k, f, rows, planes, True))(
            jnp.asarray(keys), first_a)
        assert np.array_equal(np.asarray(got), want), first
        twin = K._key_rows_xla(jnp.asarray(keys), first_a, rows)
        assert np.array_equal(np.asarray(twin), want), first
        at = K.key_rows_at(jnp.asarray(keys), list(range(first,
                                                         first + rows)))
        assert np.array_equal(np.asarray(at), want), first


def test_reference_is_the_mutex_rows():
    rng = np.random.default_rng(5)
    words = 256
    values = rng.integers(0, 41, words * 32)
    keys = key_planes(values, K.key_bits(40), words)
    rows = K.reference(keys, range(48))
    bits = np.unpackbits(rows.view(np.uint8), axis=1, bitorder="little")
    for s in range(48):
        assert np.array_equal(np.flatnonzero(bits[s]),
                              np.flatnonzero(values == s + 1)), s


def test_dispatch_counter_and_mesh_fallback(pallas_as_compiled):
    rng = np.random.default_rng(9)
    keys = key_planes(rng.integers(0, 33, 4096 * 32), 6, 4096)
    want = K.reference(keys, range(8, 16))
    one = jax.device_put(keys, jax.devices()[0])
    d0 = M.REGISTRY.value(M.METRIC_OPS_PALLAS_DISPATCH, kernel="key_rows")
    assert np.array_equal(np.asarray(K.key_rows(one, 8, 8, 6)), want)
    assert M.REGISTRY.value(M.METRIC_OPS_PALLAS_DISPATCH,
                            kernel="key_rows") == d0 + 1
    # a stack split over several chips: the XLA twin, counted why=mesh
    PM.set_engine_mesh(PM.analytics_mesh(jax.devices()[:4]))
    try:
        placed = PM.engine_put(keys)
        f0 = M.REGISTRY.value(M.METRIC_OPS_PALLAS_FALLBACK,
                              kernel="key_rows", why="mesh")
        got = K.key_rows(placed, 8, 8, 6)
        assert np.array_equal(np.asarray(got), want)
        assert M.REGISTRY.value(M.METRIC_OPS_PALLAS_FALLBACK,
                                kernel="key_rows", why="mesh") == f0 + 1
        assert got.sharding.is_equivalent_to(placed.sharding, 2)
    finally:
        PM.set_engine_mesh(None)
        PU.reset_failures()


# -- served answers -------------------------------------------------------------

SHARDS = 2
RECORDS = 40_000
REVENUE = 3       # two magnitude planes: a Sum here pays per plane
BRANDS = [f"MFGR#{m}{c}{b}" for m in range(1, 6) for c in range(1, 6)
          for b in range(1, 41)]


def ssb_columns(seed=11):
    rng = np.random.default_rng(seed)
    cols = rng.choice(SHARDS * SHARD_WIDTH, RECORDS, replace=False)
    brand = rng.integers(0, 1000, RECORDS)
    return {"cols": cols, "brand": brand, "category": brand // 40,
            "year": rng.integers(0, 7, RECORDS),
            "region": rng.integers(0, 5, RECORDS),
            "revenue": rng.integers(0, REVENUE + 1, RECORDS)}


def serve(d):
    api = API()
    idx = api.holder.create_index("ssb")
    mutex = FieldOptions(type=FieldType.MUTEX)
    for name in ("lo_year", "p_category", "s_region"):
        idx.create_field(name, mutex)
    brand = idx.create_field(
        "p_brand1", FieldOptions(type=FieldType.MUTEX, keys=True))
    ids = brand.translate.create_keys(BRANDS)
    brand.import_bits([ids[BRANDS[b]] for b in d["brand"]],
                      d["cols"].tolist())
    for name, col in (("lo_year", "year"), ("p_category", "category"),
                      ("s_region", "region")):
        idx.field(name).import_bits(d[col].tolist(), d["cols"].tolist())
    idx.create_field("lo_revenue", FieldOptions(
        type=FieldType.INT, min=0, max=REVENUE))
    api.import_values("ssb", "lo_revenue", d["cols"].tolist(),
                      d["revenue"].tolist())
    return api


def groups(d, by, sel=None, agg=False):
    """{(slot, ...): count or (count, revenue sum)} of the records
    ``sel`` picks, grouped by the columns ``by``."""
    sel = np.ones(RECORDS, bool) if sel is None else sel
    out = {}
    for i in np.flatnonzero(sel):
        key = tuple(int(d[c][i]) for c in by)
        n, s = out.get(key, (0, 0))
        out[key] = (n + 1, s + int(d["revenue"][i]))
    return out if agg else {k: n for k, (n, _) in out.items()}


def from_groups(res):
    out = {}
    for g in res:
        key = tuple(BRANDS.index(f["rowKey"]) if "rowKey" in f
                    else f["rowID"] for f in g["group"])
        out[key] = (g["count"], g["agg"]) if "agg" in g else g["count"]
    return out


def reads(d):
    """(pql, numpy answer in the served JSON's terms) for every read the
    SSB cells send of a 1000-row brand stack."""
    b0, b1 = 123, 17
    name = BRANDS
    in_b0 = d["brand"] == b0
    r1 = d["region"] == 1
    cat = d["category"] == 7
    q22 = (d["brand"] >= 200) & (d["brand"] < 208) & (d["region"] == 2)
    top = np.bincount(d["brand"][r1], minlength=1000)
    order = sorted(range(1000), key=lambda b: (-top[b], b))[:10]
    by_yb = ("year", "brand")
    return [
        (f'Row(p_brand1="{name[b0]}")',
         lambda r: sorted(r["columns"]) == sorted(d["cols"][in_b0].tolist())),
        (f'Count(Row(p_brand1="{name[b1]}"))',
         lambda r: r == int((d["brand"] == b1).sum())),
        ("Rows(p_brand1)",
         lambda r: sorted(r) == sorted(
             name[b] for b in np.unique(d["brand"]))),
        ("TopN(p_brand1, Row(s_region=1), n=10)",
         lambda r: [p["count"] for p in r["rows"]]
         == [int(top[b]) for b in order]
         and all(top[BRANDS.index(p["key"])] == p["count"]
                 for p in r["rows"])),
        ("GroupBy(Rows(lo_year), Rows(p_brand1))",
         lambda r: from_groups(r) == groups(d, by_yb)),
        ("GroupBy(Rows(lo_year), Rows(p_brand1), "
         "aggregate=Sum(field=lo_revenue))",
         lambda r: from_groups(r) == groups(d, by_yb, agg=True)),
        ("GroupBy(Rows(lo_year), Rows(s_region), Rows(p_brand1))",
         lambda r: from_groups(r) == groups(
             d, ("year", "region", "brand"))),
        # three fields and a Sum take the fold: the brand rows of the
        # live groups are derived a few at a time (take_rows)
        ("GroupBy(Rows(lo_year), Rows(s_region), Rows(p_brand1), "
         "filter=Row(p_category=7), aggregate=Sum(field=lo_revenue))",
         lambda r: from_groups(r) == groups(
             d, ("year", "region", "brand"), cat, True)),
        # SSB Q2.1, Q2.2, Q2.3 as benchmark/queries/ssb-q2.*.json send them
        ("GroupBy(Rows(lo_year), Rows(p_brand1), filter=Intersect("
         "Row(p_category=7), Row(s_region=1)), "
         "aggregate=Sum(field=lo_revenue))",
         lambda r: from_groups(r) == groups(d, by_yb, cat & r1, True)),
        ("GroupBy(Rows(lo_year), Rows(p_brand1), filter=Intersect(Union("
         + ", ".join(f'Row(p_brand1="{name[b]}")' for b in range(200, 208))
         + "), Row(s_region=2)), aggregate=Sum(field=lo_revenue))",
         lambda r: from_groups(r) == groups(d, by_yb, q22, True)),
        ("GroupBy(Rows(lo_year), Rows(p_brand1), filter=Intersect("
         f'Row(p_brand1="{name[b0]}"), Row(s_region=1)), '
         "aggregate=Sum(field=lo_revenue))",
         lambda r: from_groups(r) == groups(d, by_yb, in_b0 & r1, True)),
    ]


def answers(api, d):
    out = []
    for pql, ok in reads(d):
        res = api.query_json("ssb", pql)["results"][0]
        assert ok(res), (pql, json.dumps(res)[:300])
        out.append(res)
    return out


@pytest.fixture
def one_chip(monkeypatch):
    """One device, as the one-chip cells serve (the suite's eight-device
    mesh pays the CPU's collectives on every pair count), and row blocks
    of ``rows`` rows at two shards (SF-10 has 32)."""
    PM.set_engine_mesh(PM.analytics_mesh(jax.devices()[:1]))

    def blocks_of(rows, shards=SHARDS):
        monkeypatch.setattr(stx, "_BLOCK_BYTES",
                            rows * 2 * shards * WORDS_PER_SHARD * 4)

    yield blocks_of
    PM.set_engine_mesh(None)


def brand_stack(api):
    return stx.stacked_set(api.holder.index("ssb").field("p_brand1"),
                           list(range(SHARDS)), "standard")


def test_key_form_answers_as_the_dense_form_and_numpy(monkeypatch, one_chip):
    one_chip(128)
    d = ssb_columns()
    monkeypatch.setattr(stx, "BUDGET", stx.DeviceBudget(1 << 30))
    dense_api = serve(d)
    dense = answers(dense_api, d)
    assert type(brand_stack(dense_api)) is stx.StackedSet
    # 1024 slots x 2 shards = 256 MB dense against a cap of 80 MB: a
    # block of the eight (32 MB each) stays where there is room beside
    # the key planes and the other stacks; the rest are derived on
    # every walk
    monkeypatch.setattr(stx, "BUDGET", stx.DeviceBudget(80 << 20))
    api = serve(d)
    # a walk's whole blocks and a point read's few rows are both derived
    derived = {"block": 0, "rows": 0}

    def counted(kind, fn):
        def derive(*args):
            derived[kind] += 1
            return fn(*args)
        return derive

    monkeypatch.setattr(K, "key_rows", counted("block", K.key_rows))
    monkeypatch.setattr(K, "key_rows_at", counted("rows", K.key_rows_at))
    b0 = M.REGISTRY.value(M.METRIC_STACK_KEY_ROWS_BYTES)
    keyed = answers(api, d)
    st = brand_stack(api)
    assert type(st) is stx.KeyedSet
    assert (st.block_rows, st.cap, st.bits) == (128, 1024, 11)
    assert st._keys.shape == (16, SHARDS * WORDS_PER_SHARD)
    assert keyed == dense
    assert derived["block"] > 0 and derived["rows"] > 0
    assert M.REGISTRY.value(M.METRIC_STACK_KEY_ROWS_BYTES) > b0
    assert stx.BUDGET.used <= stx.BUDGET.cap
    stx.BUDGET.audit()


def test_writes_read_back_on_the_key_form(monkeypatch, one_chip):
    one_chip(32)
    d = ssb_columns(seed=12)
    monkeypatch.setattr(stx, "BUDGET", stx.DeviceBudget(64 << 20))
    api = serve(d)
    # builds the stack and keeps some derived blocks
    api.query("ssb", "TopN(p_brand1, n=5)")
    before = brand_stack(api)
    assert any(b is not None for b in before._blocks)
    moved, cleared = int(d["cols"][0]), int(d["cols"][1])
    src, dst = int(d["brand"][0]), (int(d["brand"][0]) + 500) % 1000
    api.query("ssb", f'Set({moved}, p_brand1="{BRANDS[dst]}")')
    api.query("ssb", f'Clear({cleared}, p_brand1="{BRANDS[d["brand"][1]]}")')
    after = brand_stack(api)
    # advanced in place on the device: the key planes moved, the kept
    # blocks (derived from the old keys) went
    assert type(after) is stx.KeyedSet and after is not before
    assert after._keys is not None and all(b is None for b in after._blocks)
    d["brand"][0] = dst
    keep = np.arange(RECORDS) != 1
    d = {k: v[keep] for k, v in d.items()}
    for b in (src, dst, int(d["brand"][1])):
        assert api.query("ssb", f'Count(Row(p_brand1="{BRANDS[b]}"))')[0] \
            == int((d["brand"] == b).sum())
    res = api.query_json("ssb", "GroupBy(Rows(lo_year), Rows(p_brand1))")
    want = {}
    for y, b in zip(d["year"], d["brand"]):
        want[(int(y), int(b))] = want.get((int(y), int(b)), 0) + 1
    assert from_groups(res["results"][0]) == want


def test_a_new_row_that_needs_one_more_bit_rebuilds(monkeypatch, one_chip):
    one_chip(32)
    monkeypatch.setattr(stx, "BUDGET", stx.DeviceBudget(4 << 20))
    api = API()
    f = api.holder.create_index("i").create_field(
        "m", FieldOptions(type=FieldType.MUTEX))
    rng = np.random.default_rng(4)
    cols = rng.choice(SHARDS * SHARD_WIDTH, 3000, replace=False)
    rows = rng.integers(0, 32, cols.size)
    f.import_bits(rows.tolist(), cols.tolist())
    st = stx.stacked_set(f, list(range(SHARDS)), "standard")
    assert type(st) is stx.KeyedSet and (st.cap, st.bits) == (32, 6)
    col = int(cols[0])
    api.query("i", f"Set({col}, m=32)")   # slot 32: cap 64, seven bits
    assert api.query("i", "Count(Row(m=32))")[0] == 1
    assert api.query("i", f"Count(Row(m={int(rows[0])}))")[0] \
        == int((rows == rows[0]).sum()) - 1
    wider = stx.stacked_set(f, list(range(SHARDS)), "standard")
    assert type(wider) is stx.KeyedSet and (wider.cap, wider.bits) == (64, 7)
    top = api.query("i", "TopN(m, n=40)")[0]
    want = np.bincount(rows, minlength=33)
    want[rows[0]] -= 1
    want[32] += 1
    assert {p.id: p.count for p in top.pairs} == {
        r: int(n) for r, n in enumerate(want) if n}


def test_a_record_in_two_rows_raises():
    from pilosa_tpu.core.fragment import SetFragment

    f = SetFragment(0)
    f.set_bit(3, 10)
    f.set_bit(5, 10)   # a set fragment allows it; a mutex stack cannot
    with pytest.raises(ValueError, match="two rows"):
        stx.KeyedSet([0], [f])


# -- the rule --------------------------------------------------------------------

class _Frag:
    def __init__(self, rows):
        self.row_index = {r: i for i, r in enumerate(range(rows))}


def _configs():
    for path in sorted((ROOT / "benchmark" / "configs").glob("*.json")):
        yield json.loads(path.read_text())


@pytest.mark.parametrize("config", [c["name"] for c in _configs()])
def test_only_sf10_takes_the_key_form_at_the_default_budget(config):
    from benchmark.harness.manifest import load_dataset

    cfg = next(c for c in _configs() if c["name"] == config)
    chips = cfg.get("chips", 1)
    shards = cfg["shards"]
    PM.set_engine_mesh(PM.analytics_mesh(jax.devices()[:chips]))
    try:
        budget = stx.DeviceBudget(stx._budget_bytes())
        with pytest.MonkeyPatch.context() as m:
            m.setattr(stx, "BUDGET", budget)
            keyed = []
            for fld in load_dataset(cfg["dataset"]).fields():
                if fld["type"] != "mutex":
                    continue
                frags = [_Frag(fld["rows"])] * shards
                words = shards * WORDS_PER_SHARD
                _, _, cap = stx._slot_layout(frags, words)
                if stx.keyed_form(True, cap, words):
                    keyed.append(fld["name"])
    finally:
        PM.set_engine_mesh(None)
    assert budget.cap == 6144 << 20
    assert keyed == (["p_brand1"] if config == "ssb-flat-sf10" else [])


# -- a race --------------------------------------------------------------------

def test_eviction_racing_a_walk_of_a_derived_stack(monkeypatch, one_chip):
    """An evictor dropping derived blocks and the key planes while
    ``row_counts`` and ``iter_blocks`` walk the stack: every pass derives
    again and stays bit-identical; no write, so never StackStale."""
    from pilosa_tpu.ops import bitmap as B

    one_chip(32)
    monkeypatch.setattr(stx, "BUDGET", stx.DeviceBudget(64 << 20))
    d = ssb_columns(seed=13)
    api = serve(d)
    st = brand_stack(api)
    assert type(st) is stx.KeyedSet and st.n_blocks == 32
    ids = api.holder.index("ssb").field("p_brand1").translate.find_keys(
        BRANDS)
    want = np.zeros(st.cap, dtype=np.int64)
    for b, n in zip(*np.unique(d["brand"], return_counts=True)):
        want[st.row_index[ids[BRANDS[b]]]] = n
    retries0 = stx.PAGING_STATS["stale_retries"]
    builds0 = stx.PAGING_STATS["block_builds"]
    stop = threading.Event()

    def evictor():
        erng = np.random.default_rng(11)
        while not stop.wait(0.002):
            bi = int(erng.integers(-1, st.n_blocks))
            if bi < 0:
                st._drop_keys()
                stx.BUDGET.release((st.serial, st._KEYS))
            else:
                st._drop_block(bi)
                stx.BUDGET.release((st.serial, bi))

    t = threading.Thread(target=evictor)
    t.start()
    try:
        for _ in range(3):
            got = np.asarray(st.row_counts()).astype(np.int64)
            assert np.array_equal(got, want)
        total = 0
        for _, blk in st.iter_blocks():
            total += int(np.asarray(B.row_counts(blk)).sum())
        assert total == RECORDS
    finally:
        stop.set()
        t.join(timeout=30)
    assert not t.is_alive()
    assert stx.PAGING_STATS["stale_retries"] == retries0
    assert stx.PAGING_STATS["block_builds"] > builds0, \
        "the evictor never dropped the key planes"


# -- the benchmark's configuration, cell and readers ------------------------------

CELL = "ssb-flat-sf10.groupby-closed"


def test_the_benchmark_takes_the_configuration_and_its_cell():
    from benchmark.harness import kernel_cost, manifest

    man = manifest.Manifest()
    man.check()
    cfg = man.configs["ssb-flat-sf10"]
    sf1 = man.configs["ssb-flat-sf1"]
    assert (cfg["shards"], cfg["setup_budget_s"], cfg["reduced"]) == (
        58, 240, [])
    # the SF-1 file but for what names the scale
    same = set(sf1) - {"name", "shards", "scale_factor", "setup_budget_s",
                       "source", "deployment", "reduced", "reduced_why",
                       "assumed"}
    assert {k: cfg[k] for k in same} == {k: sf1[k] for k in same}
    assert cfg["assumed"][1:] == sf1["assumed"][1:]
    assert man.cells[CELL] == {
        "name": CELL, "config": "ssb-flat-sf10", "traffic": "groupby-closed",
        "chips": 1, "why": man.cells[CELL]["why"]}
    lists = {m["name"]: m.get("workloads", [])
             for m in man.bench["end_to_end"] + man.bench["per_layer"]}
    for name in ("read_qps", "kernel_ms_per_read", "pallas_fallbacks_per_read",
                 "pair_counts_roofline", "pair_sums_roofline",
                 "read_median_ms", "programs_built_in_window",
                 "pair_counts_vpu_share", "stack_evictions_per_read",
                 "stack_build_mb_per_read", "key_rows_mb_per_read"):
        assert CELL in lists[name], name
    assert lists["key_rows_mb_per_read"] == [CELL]
    # the cost family is in place for a ``key_rows_roofline`` reader, which
    # waits for benchmark/tests/test_readers.py to pin membership (PERF.md §7)
    assert "key_rows_roofline" not in man.readers
    cost = kernel_cost.family("key_rows", manifest.BENCH)
    # as a v5e's trace names a call: 32 rows from 16 key planes, SF-10 wide
    words = 58 * WORDS_PER_SHARD
    text = (f"%_key_rows_pallas.1 = u32[32,{words}]{{1,0:T(8,128)}} "
            f"custom-call(s32[1]{{0:T(128)}} %copy, "
            f"u32[16,{words}]{{1,0:T(8,128)}} %keys.1)")
    assert cost(text) == (float(words) * 32 * 16, 4.0 * words * (16 + 32))
    assert cost(text.replace("custom-call", "fusion")) is None


def test_a_walk_waits_for_the_block_two_back(monkeypatch, one_chip):
    """A derived block the budget cannot keep is charged to nothing, so a
    walk (``iter_blocks``, and ``row_counts`` through it) waits for the
    block ``_AHEAD`` places back before it hands out the next: at most
    three derived blocks of a walk are on the device at once."""
    one_chip(32)
    # room for the key planes (4 MB) and no block (8 MB): every block is
    # derived anew
    monkeypatch.setattr(stx, "BUDGET", stx.DeviceBudget(6 << 20))
    api = serve(ssb_columns(seed=17))
    st = brand_stack(api)
    assert type(st) is stx.KeyedSet and st._AHEAD == 2
    waited = []

    class Jax:
        """``jax`` as core/stacked.py sees it, its waits recorded (the
        CPU's dispatch guard waits on every call besides)."""

        def __getattr__(self, name):
            return getattr(jax, name)

        def block_until_ready(self, x):
            waited.append(x)
            return jax.block_until_ready(x)

    monkeypatch.setattr(stx, "jax", Jax())
    handed = []

    def blocks_waited():
        return [next(i for i, h in enumerate(handed) if h is b)
                for b in waited if any(h is b for h in handed)]

    for lo, blk in st.iter_blocks():
        handed.append(blk)
        assert blocks_waited() == list(range(len(handed) - 2))
    assert len(handed) == st.n_blocks and st._blocks == [None] * 32
    waited.clear()
    handed.clear()
    real = stx.StackedSet.iter_blocks
    monkeypatch.setattr(stx.StackedSet, "iter_blocks", lambda self: (
        handed.append(b) or (lo, b) for lo, b in real(self)))
    counts = np.asarray(st.row_counts())
    assert counts.shape == (st.cap,)
    assert blocks_waited() == list(range(st.n_blocks - 2))


def test_a_profiled_read_names_the_build_and_each_derivation(monkeypatch,
                                                             one_chip):
    one_chip(32, shards=1)
    # 128 slots x 128 KiB = 16 MB dense against 8 MB
    monkeypatch.setattr(stx, "BUDGET", stx.DeviceBudget(8 << 20))
    api = API()
    f = api.holder.create_index("i").create_field(
        "m", FieldOptions(type=FieldType.MUTEX))
    rng = np.random.default_rng(6)
    cols = rng.choice(SHARD_WIDTH, 5000, replace=False)
    f.import_bits(rng.integers(0, 100, cols.size).tolist(), cols.tolist())
    b0 = M.REGISTRY.value(M.METRIC_STACK_KEY_ROWS_BYTES)
    tree = api.query_json("i", "TopN(m, n=3)", profile=True)["profile"]

    def spans(node, name):
        own = [node] if node.get("name") == name else []
        return own + [s for c in node.get("children", [])
                      for s in spans(c, name)]

    built = spans(tree, "stack.build")
    assert [s["tags"]["form"] for s in built] == ["keys"]
    derived = spans(tree, "stack.derive")
    # 100 rows in 4 blocks of 32, each derived once by the walk
    assert [s["tags"]["block"] for s in derived] == [0, 1, 2, 3]
    assert {(s["tags"]["rows"], s["tags"]["key_planes"], s["tags"]["bytes"])
            for s in derived} == {(32, 8, 32 * WORDS_PER_SHARD * 4)}
    assert M.REGISTRY.value(M.METRIC_STACK_KEY_ROWS_BYTES) - b0 \
        == 4 * 32 * WORDS_PER_SHARD * 4
