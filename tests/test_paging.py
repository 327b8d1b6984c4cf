"""Row-block paging under an HBM budget (VERDICT r3 #2; SURVEY §7
"ragged row counts").

High-cardinality fields page into fixed-shape row blocks, built lazily
and LRU-evicted under a byte cap — where the reference's roaring adapts
per container (roaring/roaring.go:53-58). Tests shrink the block size so
paging engages at test scale; the invariants are the real ones: results
bit-identical to the unpaged oracle, budget never exceeded, evictions
rebuild transparently, stale lazy builds retry.
"""

import numpy as np
import pytest

from pilosa_tpu.core import FieldOptions, FieldType, Holder
from pilosa_tpu.core import stacked as stx
from pilosa_tpu.pql import Executor
from pilosa_tpu.shardwidth import SHARD_WIDTH


ROWS = 600          # distinct values (row ids)
SHARDS = 2
BLOCK_BYTES = 4 << 20   # -> 16-row blocks at 2 shards: 38 blocks
BUDGET_BYTES = 20 << 20  # ~5 blocks resident


@pytest.fixture
def paged_env(monkeypatch, per_device):
    monkeypatch.setattr(stx, "_BLOCK_BYTES", per_device(BLOCK_BYTES))
    monkeypatch.setattr(stx, "BUDGET",
                        stx.DeviceBudget(per_device(BUDGET_BYTES)))
    h = Holder()
    e = Executor(h)
    h.create_index("i").create_field("f")
    f = h.index("i").field("f")
    rng = np.random.default_rng(7)
    oracle = {}
    # one bulk import per shard: ~ROWS/SHARDS distinct rows per shard,
    # a few bits each (the high-cardinality shape: many rows, sparse)
    for s in range(SHARDS):
        rows, cols = [], []
        for r in range(s, ROWS, SHARDS):
            n = int(rng.integers(1, 6))
            for c in rng.integers(0, SHARD_WIDTH, n):
                rows.append(r)
                cols.append(s * SHARD_WIDTH + int(c))
                oracle.setdefault(r, set()).add(cols[-1])
        f.import_bits(rows, cols)
    return h, e, f, oracle


def test_high_cardinality_topn_under_budget(paged_env):
    h, e, f, oracle = paged_env
    top = e.execute("i", f"TopN(f, n={ROWS})")[0]
    got = {p.id: p.count for p in top.pairs}
    want = {r: len(cs) for r, cs in oracle.items()}
    assert got == want
    # the stack actually paged and stayed under the cap
    stacks = [st for inner in f._stacked_cache.values()
              for (_, st) in inner.values()]
    assert any(st.paged and st.n_blocks > 1 for st in stacks)
    assert stx.BUDGET.used <= stx.BUDGET.cap
    assert stx.PAGING_STATS["evictions"] > 0, "budget never forced eviction"


def test_point_reads_touch_one_block(paged_env):
    h, e, f, oracle = paged_env
    builds0 = stx.PAGING_STATS["block_builds"]
    r = sorted(oracle)[3]
    got = e.execute("i", f"Count(Row(f={r}))")[0]
    assert got == len(oracle[r])
    assert stx.PAGING_STATS["block_builds"] - builds0 <= 2, \
        "a point read materialized more than its own block"


def test_groupby_on_paged_stack_matches_oracle(paged_env):
    h, e, f, oracle = paged_env
    h.index("i").create_field("g")
    g = h.index("i").field("g")
    rng = np.random.default_rng(8)
    g_oracle = {0: set(), 1: set()}
    for s in range(SHARDS):
        rows, cols = [], []
        for c in rng.integers(0, SHARD_WIDTH, 500):
            gr = int(c) % 2
            rows.append(gr)
            cols.append(s * SHARD_WIDTH + int(c))
            g_oracle[gr].add(cols[-1])
        g.import_bits(rows, cols)
    groups = e.execute("i", "GroupBy(Rows(g), Rows(f))")[0]
    gmap = {(grp[0].row_id, grp[1].row_id): n
            for grp, n in [(gc.group, gc.count) for gc in groups]}
    for gr in (0, 1):
        for r, cs in oracle.items():
            want = len(g_oracle[gr] & cs)
            assert gmap.get((gr, r), 0) == want, (gr, r)
    assert stx.BUDGET.used <= stx.BUDGET.cap


def test_eviction_rebuilds_transparently(paged_env):
    h, e, f, oracle = paged_env
    q = f"TopN(f, n={ROWS})"
    first = {p.id: p.count for p in e.execute("i", q)[0].pairs}
    # a second full scan re-streams evicted blocks with identical results
    second = {p.id: p.count for p in e.execute("i", q)[0].pairs}
    assert first == second


def test_stale_lazy_build_raises_and_query_retries(paged_env):
    h, e, f, oracle = paged_env
    from pilosa_tpu.core.stacked import StackStale, stacked_set

    st = stacked_set(f, [0, 1], "standard")
    assert st.paged
    # find an unbuilt block, then move a member fragment past the snapshot
    unbuilt = next(i for i, b in enumerate(st._blocks) if b is None)
    f.fragment(0).set_bit(0, 99)
    with pytest.raises(StackStale):
        st._ensure_block(unbuilt)
    # the executor-level read retries against a fresh stack and succeeds
    r0 = sorted(oracle)[0]
    want = len(oracle[r0] | {99}) if r0 == 0 else len(oracle[r0])
    assert e.execute("i", f"Count(Row(f={r0}))")[0] == want


def test_appends_on_paged_stack(paged_env):
    """Streaming new rows onto an already-paged stack appends slots
    without a full rebuild and stays correct."""
    h, e, f, oracle = paged_env
    e.execute("i", f"TopN(f, n={ROWS})")  # build the paged stack
    up0 = stx.UPLOAD_STATS["count"]
    bytes0 = stx.UPLOAD_STATS["bytes"]
    for k in range(5):
        e.execute("i", f"Set({k}, f={ROWS + 1000 + k})")
        assert e.execute("i", f"Count(Row(f={ROWS + 1000 + k}))")[0] == 1
    # appends may lazily build the (new) tail block but never re-upload
    # the whole stack: bound the extra transfer to a few tail blocks
    stacks = [st for inner in f._stacked_cache.values()
              for (_, st) in inner.values()]
    block_bytes = max(st.block_rows * st.total_words * 4 for st in stacks)
    assert stx.UPLOAD_STATS["count"] - up0 <= 6, \
        "appends re-uploaded more than the tail blocks"
    assert stx.UPLOAD_STATS["bytes"] - bytes0 <= 6 * block_bytes, \
        "append transfer exceeded a few blocks' worth of bytes"


def test_write_qcx_stack_releases_budget(paged_env):
    """A stack built inside a write Qcx is request-scoped: its budget
    entries must be released (not orphaned in the LRU) and later lazy
    blocks must not charge."""
    from pilosa_tpu.core.stacked import stacked_set
    from pilosa_tpu.storage.txn import TxFactory

    h, e, f, oracle = paged_env
    e.execute("i", f"TopN(f, n={ROWS})")  # warm the cached stack
    used_before = stx.BUDGET.used
    txf = TxFactory(h)
    with txf.qcx():
        f.fragment(0).set_bit(0, 7)  # dirty the field mid-request
        st = stacked_set(f, [0, 1], "standard")
        for _ in st.iter_blocks():
            pass
        assert st.ephemeral
    assert stx.BUDGET.used <= used_before, (
        "write-qcx stack leaked budget entries")


def test_executor_retries_stack_stale_midstream(paged_env, monkeypatch):
    """A writer landing AFTER the executor fetched its stack snapshot but
    BEFORE a lazy block build must surface as StackStale and be retried
    transparently — the full-scan result includes the racing write."""
    h, e, f, oracle = paged_env
    retries0 = stx.PAGING_STATS["stale_retries"]
    orig = stx.StackedSet._ensure_block
    state = {"armed": True}

    def racing_write(self, bi):
        if state["armed"] and bi > 0 and self._blocks[bi] is None:
            state["armed"] = False
            f.fragment(0).set_bit(0, 123)  # the concurrent writer
        return orig(self, bi)

    monkeypatch.setattr(stx.StackedSet, "_ensure_block", racing_write)
    top = e.execute("i", f"TopN(f, n={ROWS})")[0]
    assert not state["armed"], "the race never fired"
    assert stx.PAGING_STATS["stale_retries"] > retries0, \
        "the mid-stream write did not trip the StackStale protocol"
    oracle.setdefault(0, set()).add(123)
    got = {p.id: p.count for p in top.pairs}
    assert got == {r: len(cs) for r, cs in oracle.items()}


def test_eviction_racing_iter_blocks_reader(paged_env):
    """A budget evictor hammering _drop_block concurrently with an
    iter_blocks()/row_counts() reader: every pass rebuilds transparently
    and stays bit-identical (no writes, so never StackStale)."""
    import threading

    from pilosa_tpu.core.stacked import stacked_set
    from pilosa_tpu.ops import bitmap as B

    h, e, f, oracle = paged_env
    st = stacked_set(f, [0, 1], "standard")
    assert st.paged and st.n_blocks > 2
    want = np.zeros(len(st.row_ids), dtype=np.int64)
    for r, cs in oracle.items():
        want[st.row_index[r]] = len(cs)
    retries0 = stx.PAGING_STATS["stale_retries"]
    builds0 = stx.PAGING_STATS["block_builds"]
    stop = threading.Event()

    def evictor():
        erng = np.random.default_rng(11)
        while not stop.is_set():
            bi = int(erng.integers(0, st.n_blocks))
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
        assert total == int(want.sum())
    finally:
        stop.set()
        t.join()
    assert stx.PAGING_STATS["stale_retries"] == retries0, \
        "eviction (not staleness) was under test — no writes happened"
    assert stx.PAGING_STATS["block_builds"] > builds0, \
        "the evictor never forced a rebuild"


def test_advance_under_tiny_budget_no_crash(monkeypatch, per_device):
    """_advance_set must assign _blocks before charging: an eviction
    cascade can pop the new stack's own earlier entries."""
    monkeypatch.setattr(stx, "_BLOCK_BYTES", per_device(4 << 20))
    # budget fits ~1 block: every charge evicts the previous entries
    monkeypatch.setattr(stx, "BUDGET",
                        stx.DeviceBudget(per_device(3 << 20)))
    h = Holder()
    e = Executor(h)
    h.create_index("i").create_field("f")
    f = h.index("i").field("f")
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 100, 2000)
    cols = rng.integers(0, SHARD_WIDTH, 2000)
    f.import_bits(rows.tolist(), cols.tolist())
    top = e.execute("i", "TopN(f, n=100)")[0]
    base_total = sum(p.count for p in top.pairs)
    # advance path: a genuinely new bit between queries on the paged stack
    newcol = SHARD_WIDTH - 1
    changed = e.execute("i", f"Set({newcol}, f=3)")[0]
    top2 = e.execute("i", "TopN(f, n=100)")[0]
    assert sum(p.count for p in top2.pairs) == base_total + int(changed)
    # eviction cascades under the tiny cap never left the budget over by
    # more than the entry being inserted
    assert stx.BUDGET.used <= stx.BUDGET.cap + per_device(4 << 20)
