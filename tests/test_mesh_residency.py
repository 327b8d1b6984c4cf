"""A node whose engine mesh spans four devices: the residency plane's two
byte limits count bytes per device (``core/stacked.py``), and an N-field
GroupBy over stacks placed on that mesh stays on the pair-count kernel's
mesh route end to end (``pql/executor._groupby_dense``,
``ops/groupby.group_planes``).

The devices are four of ``conftest.py``'s eight virtual CPU devices; the
kernel bodies run under the Pallas interpreter while ``why_not`` answers
as it does where kernels are compiled (``pallas_as_compiled``).
"""

import jax
import numpy as np
import pytest

from pilosa_tpu.api import API
from pilosa_tpu.core import FieldOptions, FieldType
from pilosa_tpu.core import stacked as stx
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.ops import groupby as G
from pilosa_tpu.parallel import mesh as PM
from pilosa_tpu.pql import Executor
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD

N_DEV = 4
MB = 1 << 20


def _engine_mesh_of(n):
    m = PM.analytics_mesh(jax.devices()[:n])
    PM.set_engine_mesh(m)
    yield m
    PM.set_engine_mesh(None)


@pytest.fixture(params=[N_DEV, 1], ids=["mesh4", "one-device"])
def mesh(request):
    """An engine mesh of four of the eight virtual devices, or of one."""
    yield from _engine_mesh_of(request.param)


@pytest.fixture
def mesh4():
    yield from _engine_mesh_of(N_DEV)


def block(rows, words=N_DEV * 1024):
    return PM.engine_put(np.zeros((rows, words), dtype=np.uint32))


# -- the budget ----------------------------------------------------------------

def test_a_block_costs_each_device_what_it_holds_there(mesh):
    n = mesh.devices.size
    ids = [d.id for d in mesh.devices.flat]
    split = block(8)
    assert stx.device_bytes(split) == {i: split.nbytes // n for i in ids}
    whole = jax.device_put(np.zeros((8, 1024), np.uint32), jax.devices()[0])
    assert stx.device_bytes(whole) == {ids[0]: whole.nbytes}
    # two blocks of one entry add up device by device
    both = stx.device_bytes(split, whole)
    assert both[ids[0]] == split.nbytes // n + whole.nbytes
    assert all(both[i] == split.nbytes // n for i in ids[1:])


def test_a_sharded_block_charges_a_quarter_a_one_device_block_all(mesh4):
    b = stx.DeviceBudget(4 * MB)
    ids = [d.id for d in mesh4.devices.flat]
    split = block(256)                      # 4 MB, 1 MB a device
    b.charge(("s", 0), stx.device_bytes(split), lambda: None)
    assert b._used == {i: MB for i in ids} and b.used == MB
    assert b.room() == 3 * MB
    whole = jax.device_put(np.zeros((256, 1024), np.uint32),
                           jax.devices()[0])    # 1 MB on device 0
    b.charge(("w", 0), stx.device_bytes(whole), lambda: None)
    assert b._used[ids[0]] == 2 * MB and b._used[ids[1]] == MB
    assert b.used == 2 * MB and b.room() == 2 * MB
    # a plain byte count is bytes on the default device
    b.charge(("n", 0), MB, lambda: None)
    assert b._used[jax.devices()[0].id] == 3 * MB
    b.audit()
    b.release(("w", 0))
    b.release(("n", 0))
    assert b._used == {i: MB for i in ids}
    b.audit()
    assert M.REGISTRY.value(M.METRIC_DEVICE_BUDGET_RESIDENT_BYTES) == MB


def test_eviction_starts_only_when_one_device_is_over_the_cap(mesh4):
    """Sixteen MB of sharded blocks under a cap of four: a budget that
    counted global bytes would have evicted three of the four."""
    b = stx.DeviceBudget(4 * MB)
    dropped = []
    ev0 = M.REGISTRY.value(M.METRIC_DEVICE_BUDGET_EVICTIONS)
    for i in range(4):
        b.charge(("s", i), stx.device_bytes(block(256)),
                 lambda i=i: dropped.append(i))
    assert dropped == [] and b.used == 4 * MB and b.room() == 0
    # a fifth puts every device over: the oldest goes, once
    b.charge(("s", 4), stx.device_bytes(block(256)),
             lambda: dropped.append(4))
    assert dropped == [0] and b.used == 4 * MB
    # two MB on device 0 alone: two sharded blocks go before it is under,
    # and the entry being inserted never goes
    b.charge(("w", 0), stx.device_bytes(jax.device_put(
        np.zeros((512, 1024), np.uint32), jax.devices()[0])),
        lambda: dropped.append("w"))
    assert dropped == [0, 1, 2]
    assert b._used[jax.devices()[0].id] == 4 * MB
    assert b._used[jax.devices()[1].id] == 2 * MB
    assert M.REGISTRY.value(M.METRIC_DEVICE_BUDGET_EVICTIONS) == ev0 + 3
    b.audit()


def test_eviction_spares_entries_that_free_nothing_on_the_full_device(mesh4):
    b = stx.DeviceBudget(2 * MB)
    dropped = []
    d0, d1 = jax.devices()[:2]
    one_mb = np.zeros((256, 1024), np.uint32)
    b.charge(("other", 0), stx.device_bytes(jax.device_put(one_mb, d1)),
             lambda: dropped.append("other"))
    for i in range(3):
        b.charge(("d0", i), stx.device_bytes(jax.device_put(one_mb, d0)),
                 lambda i=i: dropped.append(i))
    # device 0 went over: its own oldest entry went, not device 1's older
    assert dropped == [0]
    b.audit()


def test_on_one_device_the_budget_counts_every_byte(mesh):
    """The same four blocks under the same cap: one device evicts as it
    always did, a mesh of four keeps them all."""
    n = mesh.devices.size
    b = stx.DeviceBudget(4 * MB)
    dropped = []
    for i in range(4):
        b.charge(("s", i), stx.device_bytes(block(256)),
                 lambda i=i: dropped.append(i))
    assert b.used == 4 * MB
    assert dropped == ([0, 1, 2] if n == 1 else [])
    assert sum(b._used.values()) == (4 * MB if n == 1 else 16 * MB)
    b.audit()


# -- the block size --------------------------------------------------------------

def test_planes_per_block_counts_the_widest_devices_bytes(mesh, monkeypatch):
    n = mesh.devices.size
    monkeypatch.setattr(stx, "_BLOCK_BYTES", 8 * MB)
    words = 8 * WORDS_PER_SHARD                     # 1 MB a plane
    assert PM.words_per_device(words) == words // n
    assert stx.planes_per_block(words) == 8 * n
    # a width that does not divide over the mesh lies whole on one device
    assert PM.words_per_device(words + 1) == words + 1
    assert stx.planes_per_block(words + 1) == 7
    before = dict(M.REGISTRY.snapshot()["counters"])
    PM.words_per_device(words + 1)
    assert M.REGISTRY.snapshot()["counters"] == before      # pure


class _Frag:
    """What StackedSet reads of a fragment."""

    def __init__(self, rows):
        self.row_index = {r: i for i, r in enumerate(rows)}
        self.planes = np.zeros((len(rows), WORDS_PER_SHARD), np.uint32)
        self.version = 0


def test_block_rows_by_per_device_bytes(mesh, monkeypatch):
    """1000 rows over 8 shards (1 MB a row): 256 MB hold 256 rows a block
    on one device (128, a power of two under them) and 1024 over four."""
    n = mesh.devices.size
    monkeypatch.setattr(stx, "BUDGET", stx.DeviceBudget(1 << 40))
    monkeypatch.setattr(stx.StackedSet, "_ensure_block",
                        lambda self, bi: None)
    st = stx.StackedSet(range(8), [_Frag(range(1000))] * 8)
    assert st.paged
    assert st.block_rows == (128 if n == 1 else 512)
    assert st.n_blocks == (8 if n == 1 else 2)
    per_device = st.block_rows * PM.words_per_device(st.total_words) * 4
    assert per_device <= stx._BLOCK_BYTES


# -- the N-field GroupBy on the mesh ---------------------------------------------

SHARDS = 4
PER_SHARD = 700
ROWS = {"p": list(range(5)), "y": [2009, 2010, 2011], "d": list(range(9))}


def rides():
    rng = np.random.default_rng(33)
    n = SHARDS * PER_SHARD
    place = np.arange(n)
    cols = (place // PER_SHARD) * SHARD_WIDTH + place % PER_SHARD
    slots = {"p": rng.choice(5, n, p=[0.05, 0.7, 0.15, 0.1, 0.0]),
             "y": np.minimum(place * 3 // n, 2),
             "d": np.minimum(rng.geometric(0.4, n) - 1, 8)}
    return cols, slots, rng.integers(0, 500, n), place % 7


@pytest.fixture
def served(mesh4, pallas_as_compiled):
    cols, slots, v, w = rides()
    api = API()
    idx = api.holder.create_index("t")
    for name, ids in ROWS.items():
        idx.create_field(name).import_bits(
            [ids[s] for s in slots[name]], cols.tolist())
    idx.create_field("w").import_bits(w.tolist(), cols.tolist())
    idx.create_field("v", FieldOptions(type=FieldType.INT)).set_values(
        cols.tolist(), v.tolist())
    return api, slots, v, w


def spans_of(api, text, name):
    """The spans called ``name`` in the profile of one read."""
    out = []

    def walk(s):
        if s.get("name") == name:
            out.append(s)
        for c in s.get("children", ()):
            walk(c)

    walk(api.query_json("t", text, profile=True)["profile"])
    return out


def reference(slots, v, names, where, with_sum):
    dims = [len(ROWS[f]) for f in names]
    flat = np.ravel_multi_index([slots[f][where] for f in names], dims)
    size = int(np.prod(dims))
    counts = np.bincount(flat, minlength=size)
    sums = np.bincount(flat, minlength=size, weights=v[where].astype(float))
    return [(tuple(ROWS[f][s] for f, s in
                   zip(names, np.unravel_index(g, dims))),
             int(counts[g]), int(sums[g]) if with_sum else None)
            for g in np.flatnonzero(counts)]


def _pair_count_ticks():
    v = M.REGISTRY.value
    return (v(M.METRIC_OPS_PALLAS_DISPATCH, kernel="pair_counts"),
            v(M.METRIC_OPS_PALLAS_MESH_DISPATCH, kernel="pair_counts"),
            v(M.METRIC_OPS_PALLAS_FALLBACK, kernel="pair_counts",
              why="mesh"),
            v(M.METRIC_OPS_PALLAS_FALLBACK, kernel="pair_counts",
              why="error"))


@pytest.mark.parametrize("text,names,filtered,with_sum", [
    ("GroupBy(Rows(p), Rows(y), Rows(d))", ("p", "y", "d"), False, False),
    ("GroupBy(Rows(p), Rows(y), Rows(d), filter=Row(w=3))",
     ("p", "y", "d"), True, False),
    ("GroupBy(Rows(p), aggregate=Sum(field=v))", ("p",), False, True),
], ids=["3-field", "3-field-filter", "1-field-sum"])
def test_groupby_over_mesh_placed_stacks_stays_on_the_mesh_route(
        served, monkeypatch, text, names, filtered, with_sum):
    api, slots, v, w = served
    calls = []
    real = G.pair_counts
    monkeypatch.setattr("pilosa_tpu.pql.executor.pair_counts",
                        lambda a, b: calls.append(
                            (PM.engine_placed(a), PM.engine_placed(b)))
                        or real(a, b))
    d0, m0, f0, e0 = _pair_count_ticks()
    got = Executor(api.holder).execute("t", text)[0]
    where = (w == 3) if filtered else np.ones(v.size, dtype=bool)
    assert [(tuple(fr.row_id for fr in gc.group), gc.count, gc.agg)
            for gc in got] == reference(slots, v, names, where, with_sum)
    # every call saw both operands where the engine places a stack and
    # ran per device: one mesh tick a call, none refused, none failed
    assert calls and all(calls) and all(map(all, calls))
    assert _pair_count_ticks() == (d0 + len(calls), m0 + len(calls), f0, e0)


def test_group_planes_come_out_placed_and_gather_nothing(rng, mesh4):
    """The compiled program ANDs each device's own words: its output is
    laid out as the engine lays a stack out, and no operand moves."""
    w = N_DEV * 2048
    planes = rng.integers(0, 1 << 32, size=(6, w), dtype=np.uint32)
    rows = rng.integers(0, 1 << 32, size=(16, w), dtype=np.uint32)
    a, b = PM.engine_put(planes), PM.engine_put(rows)
    out = G.group_planes(a, b, 2, 4, 3, 8)
    assert PM.engine_placed(out)
    np.testing.assert_array_equal(
        np.asarray(out),
        (planes[2:5, None, :] & rows[None, 4:12, :]).reshape(24, w))
    text = G.group_planes.__wrapped__.lower(
        a, b, 2, 4, gn=3, rn=8).compile().as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text


def test_a_groupby_level_says_which_route_its_counts_took(served):
    levels = spans_of(served[0], "GroupBy(Rows(p), Rows(y), Rows(d))",
                      "groupby.level")
    assert levels and all(s["tags"]["route"] == "mesh" for s in levels)
    # ... and which body of the kernel its heights chose there
    assert all(s["tags"]["body"] == "vpu" for s in levels)


def test_a_stack_build_says_where_its_bytes_went(mesh, monkeypatch):
    # dense on one device too, where a block this sparse would compress
    monkeypatch.setenv("PILOSA_TPU_COMPRESS", "0")
    n = mesh.devices.size
    api = API()
    f = api.holder.create_index("t").create_field("f")
    f.import_bits([1, 2, 3, 1], [0, SHARD_WIDTH + 1, 2 * SHARD_WIDTH + 5,
                                 3 * SHARD_WIDTH + 9])
    sent0 = M.REGISTRY.value(M.METRIC_STACK_BUILD_BYTES)
    (build,) = spans_of(api, "Count(Row(f=1))", "stack.build")
    nbytes = 8 * 4 * WORDS_PER_SHARD * 4        # 8 slots over 4 shards
    assert build["tags"]["devices"] == n
    assert build["tags"]["bytes_per_device"] == nbytes // n
    assert M.REGISTRY.value(M.METRIC_STACK_BUILD_BYTES) == sent0 + nbytes
