"""GroupBy of one to four fields on time-ordered, skewed data, both routes
(the dense count tensor below the cell cap, the pruning fold above it)
held to one plain numpy reference written here, and what the tracing
says of them: the route tag on the sampled tree, the ``groupby.level``
spans, the host fetches, the group-plane bytes and the decode counters."""

import numpy as np
import pytest

from pilosa_tpu.api import API
from pilosa_tpu.core import FieldOptions, FieldType, Holder
from pilosa_tpu.core import stacked as stx
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.obs import tracing as T
from pilosa_tpu.ops import ctiles
from pilosa_tpu.ops import pallas_util as PU
from pilosa_tpu.pql import Executor
from pilosa_tpu.pql import executor as ex
from pilosa_tpu.shardwidth import SHARD_WIDTH

SHARDS = 3
PER_SHARD = 1500
#: field -> row ids, in the order a GroupBy names them
ROWS = {"p": list(range(6)), "y": [2009, 2010, 2011, 2012],
        "d": list(range(12)), "w": [0, 1, 2]}
ORDER = ("p", "y", "d", "w")


def rides(seed=29):
    """Seeded records, time-ordered and skewed: the year follows the
    record's place in the table (a year row is empty in most shards), one
    ``p`` row holds most records and one holds none, short ``d`` wins."""
    rng = np.random.default_rng(seed)
    n = SHARDS * PER_SHARD
    place = np.arange(n)
    cols = (place // PER_SHARD) * SHARD_WIDTH + place % PER_SHARD
    slots = {
        "p": rng.choice(6, n, p=[0.02, 0.70, 0.15, 0.08, 0.05, 0.0]),
        "y": np.minimum(place * 4 // n, 3),
        "d": np.minimum(rng.geometric(0.35, n) - 1, 11),
        "w": (place // 50) % 3,
    }
    v = rng.integers(-40, 500, n)
    return cols, slots, v


def load(holder):
    cols, slots, v = rides()
    idx = holder.create_index("t")
    for name, ids in ROWS.items():
        f = idx.create_field(name)
        f.import_bits([ids[s] for s in slots[name]], cols.tolist())
    idx.create_field("v", FieldOptions(type=FieldType.INT)).set_values(
        cols.tolist(), v.tolist())
    return cols, slots, v


def reference(slots, v, names, where=None, with_sum=False, limit=None,
              rows=ROWS):
    """[(row ids, count, sum)] of the non-empty groups in row-id order:
    a bincount over ravel_multi_index, nothing from the program."""
    sel = np.ones(v.size, dtype=bool) if where is None else where
    dims = [len(rows[f]) for f in names]
    flat = np.ravel_multi_index([slots[f][sel] for f in names], dims)
    counts = np.bincount(flat, minlength=int(np.prod(dims)))
    sums = np.bincount(flat, minlength=int(np.prod(dims)),
                       weights=v[sel].astype(np.float64))
    out = []
    for g in np.flatnonzero(counts):
        key = tuple(rows[f][s] for f, s in
                    zip(names, np.unravel_index(g, dims)))
        out.append((key, int(counts[g]), int(sums[g]) if with_sum else None))
    return out[:limit]


def answer(result):
    return [(tuple(fr.row_id for fr in gc.group), gc.count, gc.agg)
            for gc in result]


MODES = {
    # name: (filter, sum, limit, compressed, paged, above the cap)
    "plain": (False, False, None, False, False, False),
    "filter": (True, False, None, False, False, False),
    "sum": (False, True, None, False, False, False),
    "filter-sum": (True, True, None, False, False, False),
    "limit": (False, False, 5, False, False, False),
    "compressed": (True, False, None, True, False, False),
    "compressed-sum": (False, True, None, True, False, False),
    "paged": (False, False, None, False, True, False),
    "paged-filter-sum": (True, True, None, False, True, False),
    "above-cap": (False, False, None, False, False, True),
    "above-cap-filter-sum": (True, True, None, False, False, True),
    "above-cap-compressed-paged": (True, False, 7, True, True, True),
    # the kernels' own bodies under the Pallas interpreter, at this width
    # (two fields only: pair_sums' case, seconds a read)
    "pallas-filter-sum": (True, True, None, False, False, False),
}


@pytest.mark.parametrize("n_fields,mode", [
    (n, mode) for mode in sorted(MODES) for n in (1, 2, 3, 4)
    if n == 2 or not mode.startswith("pallas")])
def test_groupby_equals_the_numpy_reference(n_fields, mode, monkeypatch,
                                            per_device):
    filtered, with_sum, limit, compressed, paged, above = MODES[mode]
    if compressed:
        monkeypatch.setenv("PILOSA_TPU_COMPRESS", "1")
    if paged:
        # 8-row blocks at three shards, and a budget of about four of them
        monkeypatch.setattr(stx, "_BLOCK_BYTES", per_device(4 << 20))
        monkeypatch.setattr(stx, "BUDGET",
                            stx.DeviceBudget(per_device(14 << 20)))
    if above:
        monkeypatch.setattr(ex, "_DENSE_MAX_CELLS", 2)
    if mode.startswith("pallas"):
        monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
        monkeypatch.setattr(PU, "INTERPRET_MAX_WORDS",
                            SHARDS * SHARD_WIDTH // 32)
        PU.reset_failures()
    sums0 = M.REGISTRY.value(M.METRIC_OPS_PALLAS_DISPATCH,
                             kernel="pair_sums")
    h = Holder()
    cols, slots, v = load(h)
    names = ORDER[:n_fields]
    # a Sum over three or more fields is the fold's at any size
    route = "fold" if above or (with_sum and n_fields > 2) else "dense"
    text = "GroupBy(" + ", ".join(f"Rows({f})" for f in names)
    # the filter names a field the GroupBy does not group by
    by = "w" if n_fields < 4 else "y"
    where = None
    if filtered:
        row = ROWS[by][1]
        text += f", filter=Row({by}={row})"
        where = slots[by] == 1
    if with_sum:
        text += ", aggregate=Sum(field=v)"
    if limit is not None:
        text += f", limit={limit}"
    tracer = T.Tracer(enabled=True, sample_rate=1.0)
    prev = T.get_tracer()
    T.set_tracer(tracer)
    try:
        with tracer.start_trace("query.pql") as root:
            got = answer(Executor(h).execute("t", text + ")")[0])
    finally:
        T.set_tracer(prev)
    assert got == reference(slots, v, names, where, with_sum, limit)
    assert len(got) > 1
    assert root.tags["groupby_route"] == route
    # a two-field Sum is pair_sums': one kernel dispatch a pair of blocks
    assert M.REGISTRY.value(
        M.METRIC_OPS_PALLAS_DISPATCH, kernel="pair_sums") - sums0 == (
            mode.startswith("pallas") and n_fields == 2)
    if paged and "d" in names:
        d = h.index("t").field("d")
        assert any(st.paged for inner in d._stacked_cache.values()
                   for _, st in inner.values())


@pytest.mark.parametrize("query,kernel", [
    ("GroupBy(Rows(p), Rows(d))", "pair_counts"),
    ("GroupBy(Rows(p), Rows(d), filter=Row(w=1), aggregate=Sum(field=v))",
     "pair_sums"),
    ("GroupBy(Rows(p), Rows(y), Rows(d))", "pair_counts"),
])
def test_groupby_on_a_mesh_of_four_equals_the_one_device_answer(
        query, kernel, pallas_as_compiled):
    """Under an engine mesh of four devices the stacks are sharded over
    the word axis and the pair-count family takes its mesh route (the
    kernel on every device's own words, one ``psum``): same answers as
    on one device and as numpy, as many dispatches, all of them the mesh
    program's, and nothing left to the XLA scan."""
    import jax

    from pilosa_tpu.parallel import mesh as PM

    family = ("pair_counts", "pair_sums")

    def ticks(name, **labels):
        return np.array([M.REGISTRY.value(name, kernel=k, **labels)
                         for k in family])

    def counters():
        return (ticks(M.METRIC_OPS_PALLAS_DISPATCH),
                ticks(M.METRIC_OPS_PALLAS_MESH_DISPATCH),
                ticks(M.METRIC_OPS_PALLAS_FALLBACK, why="mesh")
                + ticks(M.METRIC_OPS_PALLAS_FALLBACK, why="error"))

    h = Holder()
    cols, slots, v = load(h)
    try:
        PM.set_engine_mesh(PM.analytics_mesh(jax.devices()[:1]))
        d0, m0, _ = counters()
        one = answer(Executor(h).execute("t", query)[0])
        d1, m1, f1 = counters()
        PM.set_engine_mesh(PM.analytics_mesh(jax.devices()[:4]))
        four = answer(Executor(h).execute("t", query)[0])
        d4, m4, f4 = counters()
    finally:
        PM.set_engine_mesh(None)
    names = ("p", "y", "d") if "Rows(y)" in query else ("p", "d")
    with_sum = kernel == "pair_sums"
    assert four == one == reference(
        slots, v, names, slots["w"] == 1 if with_sum else None, with_sum)
    assert (d1 - d0)[family.index(kernel)] > 0
    assert (m1 == m0).all()         # one device: never the mesh program
    assert (d4 - d1 == d1 - d0).all() and (m4 - m1 == d1 - d0).all()
    assert (f4 == f1).all()


#: the brand-like last field of the live-row cases: 40 rows in blocks of 8
#: when paged, a category of 8 brands each and a parity beside it
BRAND_ROWS = {"y": ROWS["y"], "b": list(range(40))}
#: name: (filter, brand rows it leaves live, rows of each pair_sums call,
#: calls on gathered rows)
LIVE = {
    # non-adjacent rows in four blocks: one gather, padded to 8
    "scattered": ("Union(Row(b=3), Row(b=12), Row(b=29), Row(b=38))",
                  [3, 12, 29, 38], [8], 1),
    # block 2 all live among dead blocks is gathered with the two rows
    # of others: the chunks of 8 and 2 are those of any 10 live rows
    "block-and-rows": ("Union(Row(c=2), Row(b=5), Row(b=30))",
                       [5, *range(16, 24), 30], [8, 8], 2),
    # 20 rows, none a whole block: chunks of 8, 8 and 4 padded to 8
    "odd": ("Row(o=1)", list(range(1, 40, 2)), [8, 8, 8], 3),
    # no filter: every block as it is, today's calls
    "all": (None, list(range(40)), [8] * 5, 0),
    # no row live: no call at all
    "none": ("Intersect(Row(b=3), Row(b=4))", [], [], 0),
}


def load_brands(holder):
    """Seeded records over SHARDS shards: a mutex brand ``b`` of 40 rows
    with its category ``c`` (``b // 8``) and parity ``o``, the year ``y``
    as :func:`rides` has it, and an int ``v``."""
    rng = np.random.default_rng(41)
    n = SHARDS * PER_SHARD
    place = np.arange(n)
    cols = (place // PER_SHARD) * SHARD_WIDTH + place % PER_SHARD
    b = rng.integers(0, 40, n)
    slots = {"y": np.minimum(place * 4 // n, 3), "b": b}
    v = rng.integers(-40, 500, n)
    idx = holder.create_index("t")
    for name, ids in (("y", [ROWS["y"][s] for s in slots["y"]]),
                      ("b", b), ("c", b // 8), ("o", b % 2)):
        opts = FieldOptions(type=FieldType.MUTEX) if name == "b" else None
        idx.create_field(name, opts).import_bits(
            np.asarray(ids).tolist(), cols.tolist())
    idx.create_field("v", FieldOptions(type=FieldType.INT)).set_values(
        cols.tolist(), v.tolist())
    return slots, v


def _live_query(case):
    filt, live, *_ = LIVE[case]
    text = "GroupBy(Rows(y), Rows(b)"
    if filt is not None:
        text += f", filter={filt}"
    return text + ", aggregate=Sum(field=v))", live


def _sum_rows():
    return np.array([M.REGISTRY.value(M.METRIC_GROUPBY_SUM_ROWS, state=k)
                     for k in ("summed", "skipped")])


@pytest.mark.parametrize("form", ["dense", "keyed"])
@pytest.mark.parametrize("case", sorted(LIVE))
def test_a_sum_groupby_sums_the_live_rows_of_its_last_field(
        case, form, monkeypatch, per_device):
    """A dense two-field Sum counts first and runs pair_sums over the
    rows of the last field that some group holds: the blocks as they
    are where every row is live, else the live rows gathered (on a
    key-plane stack, derived by ``key_rows_at`` alone) in chunks of at
    most a block padded to a power of two of 8 or more. The answer is
    the numpy reference's and the fold's; dead rows are counted as
    skipped and cost no call."""
    from pilosa_tpu.ops import keyrows as K

    text, live = _live_query(case)
    *_, calls, gathers = LIVE[case]
    monkeypatch.setattr(stx, "_BLOCK_BYTES", per_device(4 << 20))
    # the brand stack is 40 rows x 384 KB: a budget under that holds it
    # as key planes
    budget = (14 if form == "keyed" else 64) << 20
    monkeypatch.setattr(stx, "BUDGET", stx.DeviceBudget(per_device(budget)))
    h = Holder()
    slots, v = load_brands(h)
    seen, derived = [], []

    def pair_sums(a, b, *rest):
        seen.append((a.shape[0], b.shape[0]))
        return real_sums(a, b, *rest)

    def key_rows_at(keys, at):
        derived.append(len(at))
        return real_at(keys, at)

    real_sums, real_at = ex.pair_sums, K.key_rows_at
    monkeypatch.setattr(ex, "pair_sums", pair_sums)
    monkeypatch.setattr(K, "key_rows_at", key_rows_at)
    rows0 = _sum_rows()
    tracer = T.Tracer(enabled=True, sample_rate=1.0)
    prev = T.get_tracer()
    T.set_tracer(tracer)
    try:
        with tracer.start_trace("query.pql") as root:
            got = answer(Executor(h).execute("t", text)[0])
    finally:
        T.set_tracer(prev)
    st = stx.stacked_set(h.index("t").field("b"), list(range(SHARDS)),
                         "standard")
    assert type(st) is (stx.KeyedSet if form == "keyed" else stx.StackedSet)
    assert (st.block_rows, st.n_blocks) == (8, 5)
    assert root.tags["groupby_route"] == "dense"
    want = reference(slots, v, ("y", "b"), np.isin(slots["b"], live), True,
                     rows=BRAND_ROWS)
    assert got == want and (len(got) > 1) == bool(live)
    assert [b for _, b in seen] == calls and len({a for a, _ in seen}) <= 1
    assert (_sum_rows() - rows0).tolist() == [len(live), 40 - len(live)]
    # the gathered rows alone are derived a chunk at a time (a filter's
    # Row(b=..) derives its one row)
    assert derived.count(8) == (gathers if form == "keyed" else 0)
    monkeypatch.setattr(ex, "_DENSE_MAX_CELLS", 2)
    assert answer(Executor(h).execute("t", text)[0]) == want


@pytest.mark.parametrize("case", ["scattered", "block-and-rows"])
def test_live_rows_gathered_on_a_mesh_of_four_keep_the_mesh_route(
        case, pallas_as_compiled, monkeypatch):
    """On an engine mesh of four devices the gathered rows keep the
    stack's word sharding (whether they come from one block or several),
    so every pair_sums call takes the kernel's mesh route: the
    one-device answer, and one mesh dispatch a call."""
    import jax

    from pilosa_tpu.parallel import mesh as PM

    text, live = _live_query(case)
    calls = len(LIVE[case][2])
    h = Holder()
    slots, v = load_brands(h)

    def ticks():
        return np.array([M.REGISTRY.value(name, kernel="pair_sums", **lbl)
                         for name, lbl in (
                             (M.METRIC_OPS_PALLAS_DISPATCH, {}),
                             (M.METRIC_OPS_PALLAS_MESH_DISPATCH, {}),
                             (M.METRIC_OPS_PALLAS_FALLBACK, {"why": "mesh"}),
                             (M.METRIC_OPS_PALLAS_FALLBACK,
                              {"why": "error"}))])

    answers = []
    try:
        for devices in (1, 4):
            PM.set_engine_mesh(PM.analytics_mesh(jax.devices()[:devices]))
            # blocks of 8 brand rows on either mesh
            monkeypatch.setattr(stx, "_BLOCK_BYTES", (4 << 20) // devices)
            t0 = ticks()
            answers.append(answer(Executor(h).execute("t", text)[0]))
            assert (ticks() - t0).tolist() == [
                calls, calls if devices == 4 else 0, 0, 0]
    finally:
        PM.set_engine_mesh(None)
    assert answers[0] == answers[1] == reference(
        slots, v, ("y", "b"), np.isin(slots["b"], live), True,
        rows=BRAND_ROWS)


def _profiled(api, text):
    out = api.query_json("t", text, profile=True)
    spans = []

    def walk(s):
        if s.get("name") == "groupby.level":
            spans.append(s)
        for c in s.get("children", ()):
            walk(c)

    walk(out["profile"])
    return out["results"][0], spans


def _wide_api(rows, per_row=8):
    """Three fields of ``rows``, ``rows`` and 4 rows over two shards."""
    api = API()
    api.create_index("t")
    rng = np.random.default_rng(rows)
    n = rows * rows * per_row
    cols = rng.choice(2 * SHARD_WIDTH, n, replace=False)
    a, b = np.arange(n) % rows, np.arange(n) // rows % rows
    c = np.arange(n) // (rows * rows) % 4
    for name, slots in (("a", a), ("b", b), ("c", c)):
        api.create_field("t", name)
        api.holder.index("t").field(name).import_bits(
            slots.tolist(), cols.tolist())
    return api, n


@pytest.mark.parametrize("rows", [4, 8, 16])
def test_group_planes_held_stay_under_a_block_as_groups_grow(rows,
                                                             monkeypatch,
                                                             per_device):
    """A 3-field GroupBy below the cap makes rows x rows group planes, a
    block at a time: no level ever holds more than a row block's bytes,
    it fetches once, and the planes it made are those of the groups."""
    # 8 planes of 2 shards
    monkeypatch.setattr(stx, "_BLOCK_BYTES", per_device(2 << 20))
    api, n = _wide_api(rows)
    plane = 2 * SHARD_WIDTH // 8
    made0 = M.REGISTRY.value(M.METRIC_GROUPBY_GROUP_PLANE_BYTES)
    fetch0 = M.REGISTRY.value(M.METRIC_GROUPBY_HOST_FETCHES)
    groups, spans = _profiled(api, "GroupBy(Rows(a), Rows(b), Rows(c))")
    assert sum(g["count"] for g in groups) == n
    assert len(groups) == rows * rows * 4
    assert spans and all(s["tags"]["level"] == 1 for s in spans)
    assert max(s["tags"]["plane_bytes"] for s in spans) <= 2 << 20
    assert sum(s["tags"]["blocks"] for s in spans) >= rows * rows // 8
    assert sum(s["tags"]["groups_in"] for s in spans) == rows
    assert sum(s["tags"]["groups_live"] for s in spans) == rows * rows
    assert M.REGISTRY.value(M.METRIC_GROUPBY_HOST_FETCHES) == fetch0 + 1
    assert (M.REGISTRY.value(M.METRIC_GROUPBY_GROUP_PLANE_BYTES) - made0
            == rows * rows * plane)


def test_the_fold_fetches_per_level_and_says_what_lived(monkeypatch):
    monkeypatch.setattr(ex, "_DENSE_MAX_CELLS", 2)
    api, n = _wide_api(4)
    fetch0 = M.REGISTRY.value(M.METRIC_GROUPBY_HOST_FETCHES)
    made0 = M.REGISTRY.value(M.METRIC_GROUPBY_GROUP_PLANE_BYTES)
    groups, spans = _profiled(api, "GroupBy(Rows(a), Rows(b), Rows(c))")
    assert sum(g["count"] for g in groups) == n
    assert [s["tags"]["level"] for s in spans] == [1, 2]
    assert [s["tags"]["groups_in"] for s in spans] == [4, 16]
    assert [s["tags"]["groups_live"] for s in spans] == [16, 64]
    # no kernel on this backend: the scan, which has no body to name
    assert [(s["tags"]["route"], s["tags"]["body"]) for s in spans] == [
        ("xla", "none")] * 2
    assert M.REGISTRY.value(M.METRIC_GROUPBY_HOST_FETCHES) == fetch0 + 2
    # the sixteen live pairs, gathered and then ANDed
    assert (M.REGISTRY.value(M.METRIC_GROUPBY_GROUP_PLANE_BYTES) - made0
            == 2 * 16 * 2 * SHARD_WIDTH // 8)


def _decodes():
    return (M.REGISTRY.value(M.METRIC_COMPRESS_DECODE, kind="set"),
            M.REGISTRY.value(M.METRIC_COMPRESS_DECODE, kind="bsi"),
            M.REGISTRY.value(M.METRIC_COMPRESS_DECODE_BYTES))


def _groupby_counters():
    snap = M.REGISTRY.snapshot()["counters"]
    return {k: v for k, v in snap.items()
            if k.startswith(("groupby_", "device_compress_decode"))}


def test_a_compressed_block_is_densified_once_where_the_budget_has_room(
        monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_COMPRESS", "1")
    h = Holder()
    load(h)
    e = Executor(h)
    still = _groupby_counters()
    assert e.execute("t", "Count(Row(y=2010))")[0] == SHARDS * PER_SHARD // 4
    assert e.execute("t", "Count(Intersect(Row(y=2010), Row(p=1)))")[0] > 0
    # a point read decodes the rows it names and moves none of these
    assert _groupby_counters() == still
    y = h.index("t").field("y")
    (_, st), = [e for inner in y._stacked_cache.values()
                for e in inner.values()]
    assert isinstance(st._blocks[0], ctiles.CompressedBlock)
    used = stx.BUDGET.used
    s0, b0, bytes0 = _decodes()
    first = e.execute("t", "GroupBy(Rows(y), Rows(p))")[0]
    s1, b1, bytes1 = _decodes()
    assert (s1 - s0, b1 - b0) == (2, 0)         # y's block and p's
    assert bytes1 - bytes0 == 2 * 8 * SHARDS * SHARD_WIDTH // 8
    # the small form stays for point reads, the dense words beside it
    assert isinstance(st._blocks[0], ctiles.CompressedBlock)
    assert st._walked[0][0] is st._blocks[0]
    assert stx.BUDGET.used >= used + 2 * 8 * SHARDS * SHARD_WIDTH // 8
    stx.BUDGET.audit()
    assert e.execute("t", "GroupBy(Rows(y), Rows(p))")[0] == first
    assert e.execute("t", "TopN(p, Row(y=2011), n=3)")[0].pairs
    assert _decodes() == (s1, b1, bytes1)       # once per residency
    # the int field's stack: a Sum walks it whole
    total = e.execute("t", "Sum(field=v)")[0]
    assert _decodes()[1] == b1 + 1
    assert e.execute("t", "Sum(Row(y=2009), field=v)")[0].count \
        == SHARDS * PER_SHARD // 4
    assert e.execute("t", "Sum(field=v)")[0] == total
    assert _decodes()[1] == b1 + 1
    # evicted, the block comes back small and its next walk decodes again
    stx.BUDGET.release((st.serial, 0))
    st._drop_block(0)
    assert not st._walked
    assert e.execute("t", "GroupBy(Rows(y), Rows(p))")[0] == first
    assert _decodes()[0] == s1 + 1 and st._walked[0][0] is st._blocks[0]
    stx.BUDGET.audit()


def test_a_budget_with_no_room_keeps_the_small_form_and_decodes_per_walk(
        monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_COMPRESS", "1")
    monkeypatch.setattr(stx, "BUDGET", stx.DeviceBudget(1 << 20))
    h = Holder()
    _, slots, v = load(h)
    e = Executor(h)
    want = reference(slots, v, ("y", "p"))
    s0 = _decodes()[0]
    assert answer(e.execute("t", "GroupBy(Rows(y), Rows(p))")[0]) == want
    s1 = _decodes()[0]
    assert answer(e.execute("t", "GroupBy(Rows(y), Rows(p))")[0]) == want
    assert s1 - s0 >= 2 and _decodes()[0] - s1 == s1 - s0
    assert stx.BUDGET.used <= 1 << 20 or len(stx.BUDGET._lru) == 1
    stx.BUDGET.audit()


def test_the_decode_is_a_leaf_on_the_profilers_clock_while_one_collects(
        monkeypatch, tmp_path):
    """``stack.decode`` shows in the host plane of a profiler session
    around a GroupBy that densifies compressed blocks, and only there:
    the same walk outside a session leaves nothing behind."""
    import glob

    import jax
    from jax.profiler import ProfileData

    monkeypatch.setenv("PILOSA_TPU_COMPRESS", "1")
    monkeypatch.setattr(stx, "BUDGET", stx.DeviceBudget(1 << 20))
    h = Holder()
    load(h)
    e = Executor(h)
    text = "GroupBy(Rows(y), Rows(p))"
    first = e.execute("t", text)[0]            # compiles, no session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        s0 = _decodes()[0]
        assert e.execute("t", text)[0] == first
        decoded = _decodes()[0] - s0
    finally:
        jax.profiler.stop_trace()
    assert decoded >= 2                        # no room: decoded per walk
    pb, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                        / "*.xplane.pb"))
    leaves = [ev.name for plane in ProfileData.from_file(pb).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name == "stack.decode"]
    assert len(leaves) == decoded
