"""Benchmark driver: all five BASELINE.json configs at (near-)reference
scale, each against a single-host numpy control that mirrors the
reference's algorithm on the same data layout.

Emits one JSON line per config —
    {"metric", "value", "unit", "vs_baseline"}
— ``vs_baseline`` is the speedup over the numpy control (>1 = this
engine is faster). The LAST line is the north-star config (#3,
multi-shard TopK+GroupBy at SSB SF-1 scale; reference hot paths
executor.go:2357 topK / :3918 executeGroupByShard), which the round
driver records as the headline.

Configs (BASELINE.json):
  1. single-shard Set field: Intersect+Count over a 1M-row CSV import
     (+ the ingest rate itself); ref: ctl/import.go, executor.go:5357
  2. BSI int field: Range+Sum over 10M rows; ref: fragment.go:724,963
  4. time-quantum Row+Count across 256 shards; ref: time.go:158
  5. dataframe Apply float aggregation; ref: apply.go
  3. multi-shard TopK+GroupBy at SSB SF-1 scale (6M columns); headline

Runs on the accelerator JAX finds. With ``JAX_PLATFORMS=cpu`` set
explicitly (every scripts/tier1.sh lane sets it) configs run on the CPU,
scaled down and labelled so; otherwise a config that cannot run on the
device fails the run — nothing is re-run on the CPU in its place.
"""

import gc
import itertools
import json
import os
import random
import statistics
import subprocess
import sys
import time

import numpy as np

QUERY_ITERS = 20

# CPU scaling: under an explicit JAX_PLATFORMS=cpu the suite must still
# finish inside the tier-1 budget, so configs shrink and the metric
# labels say so (a scaled CPU number is a smoke signal, not a perf
# claim).
SCALE = 1.0
SCALED = ""


def _apply_cpu_scale() -> None:
    global SCALE, SCALED, QUERY_ITERS
    SCALE = 0.125
    SCALED = " cpu-scaled"
    QUERY_ITERS = 5


def _n(x: int) -> int:
    return max(1, int(x * SCALE))


#: Every record _emit printed this run — the profile dump
#: (PILOSA_BENCH_PROFILE_OUT) rewrites them to a file scripts/
#: bench_compare.py can diff against a previous run.
_EMITTED = []


def _emit(metric: str, value: float, unit: str, vs_baseline: float,
          **extra) -> None:
    rec = {
        "metric": metric,
        "value": round(value, 3),
        "unit": unit,
        "vs_baseline": round(vs_baseline, 3),
    }
    rec.update({k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in extra.items()})
    _EMITTED.append(rec)
    print(json.dumps(rec), flush=True)


def _dump_profile(path: str, device: str) -> None:
    """Append this run's emitted records + kernel profiles as JSON lines
    (append mode: the orchestrator's children share one file)."""
    from pilosa_tpu.obs import devprof

    with open(path, "a") as f:
        for rec in _EMITTED:
            f.write(json.dumps(rec) + "\n")
        f.write(json.dumps({"metric": "__kernels__", "device": device,
                            "profile": devprof.stats_json()}) + "\n")


_FLOOR_MS = None


def dispatch_floor_ms() -> float:
    """p50 of one trivial dispatch + scalar fetch — the per-query latency
    floor the runtime imposes regardless of work (decomposes the
    latency-bound configs: a query within ~2x of this floor is
    dispatch-bound, not kernel-bound)."""
    global _FLOOR_MS
    if _FLOOR_MS is None:
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda x: x + 1)
        x = jnp.uint32(1)
        float(f(x))  # warm: compile
        times = []
        for _ in range(10):
            t0 = time.perf_counter()
            float(f(x))  # dispatch + device round-trip + scalar fetch
            times.append(time.perf_counter() - t0)
        _FLOOR_MS = statistics.median(times) * 1e3
    return _FLOOR_MS


def _p50_ms(fn, iters: int = 0) -> float:
    iters = iters or QUERY_ITERS  # read the global at CALL time so the
    # CPU rescale actually applies (a default arg binds at
    # import, before _apply_cpu_scale runs)
    fn()  # warm: compile + upload
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3


def _np_popcount(words: np.ndarray) -> int:
    """Single-pass host popcount via byte table (the numpy analog of the
    reference's container popcount loops, roaring/roaring.go:711)."""
    return int(_BYTE_POP[words.view(np.uint8)].sum())


_BYTE_POP = np.array([bin(i).count("1") for i in range(256)], dtype=np.uint16)


def _rand_planes(rng, rows: int, words: int) -> np.ndarray:
    return rng.integers(0, 1 << 32, size=(rows, words), dtype=np.uint32)


# ---------------------------------------------------------------------------
# Config 1 — 1M-row CSV import, then Intersect+Count (single shard)
# ---------------------------------------------------------------------------

def bench_config1(device: str) -> None:
    from pilosa_tpu.api import API
    from pilosa_tpu.ingest.ingest import Ingester
    from pilosa_tpu.ingest.source import CSVSource

    rng = np.random.default_rng(1)
    n = _n(1_000_000)
    city = rng.integers(0, 1000, n)
    dev = rng.integers(0, 10, n)
    lines = ["id,city__IS,device__IS"]
    lines.extend(f"{i},{city[i]},{dev[i]}" for i in range(n))
    csv_text = "\n".join(lines)

    # control: the raw single-threaded CSV parse alone (the unavoidable
    # host cost the ingest path adds batching/translation/import on top of)
    import csv as _csv
    import io as _io
    t0 = time.perf_counter()
    for _ in _csv.reader(_io.StringIO(csv_text)):
        pass
    parse_s = time.perf_counter() - t0

    api = API()
    t0 = time.perf_counter()
    got = Ingester(api, "taxi", CSVSource(csv_text, inline=True),
                   batch_size=131072).run()
    ingest_s = time.perf_counter() - t0
    assert got == n, got
    _emit(f"c1_csv_ingest_1M_rows{SCALED} ({device})", n / ingest_s,
          "rows/s", (n / ingest_s) / (n / parse_s))

    # query: Intersect+Count of two rows (executor.go:5357 hot path)
    q = "Count(Intersect(Row(city=7), Row(device=3)))"
    want = int(np.sum((city == 7) & (dev == 3)))
    assert api.query("taxi", q)[0] == want
    p50 = _p50_ms(lambda: api.query("taxi", q))

    # control: numpy AND+popcount over the same planes (fragment.row +
    # roaring IntersectionCount)
    fld = api.holder.index("taxi")
    pa = fld.field("city").fragment(0).row_plane(7)
    pb = fld.field("device").fragment(0).row_plane(3)
    t0 = time.perf_counter()
    for _ in range(QUERY_ITERS):
        _np_popcount(pa & pb)
    base_ms = (time.perf_counter() - t0) / QUERY_ITERS * 1e3
    nbytes = pa.nbytes + pb.nbytes
    _emit(f"c1_intersect_count_p50_1shard_1Mrows{SCALED} ({device})", p50,
          "ms", base_ms / p50, hbm_bytes=nbytes,
          gbps=nbytes / p50 / 1e6, floor_ms=dispatch_floor_ms())


# ---------------------------------------------------------------------------
# Config 2 — BSI Range+Sum over 10M rows (10 shards)
# ---------------------------------------------------------------------------

def bench_config2(device: str) -> None:
    from pilosa_tpu.core import FieldOptions, FieldType, Holder
    from pilosa_tpu.ops import bsi as bsiops
    from pilosa_tpu.pql import Executor
    from pilosa_tpu.shardwidth import WORDS_PER_SHARD

    rng = np.random.default_rng(2)
    shards, depth = _n(10), 20
    h = Holder()
    idx = h.create_index("b")
    idx.create_field("amount", FieldOptions(type=FieldType.INT))
    f = idx.field("amount")
    host = {}
    for s in range(shards):
        frag = f.bsi_fragment(s, create=True)
        frag._ensure_depth(depth)
        planes = np.zeros_like(frag.planes)
        planes[bsiops.EXISTS] = 0xFFFFFFFF  # every column exists
        planes[bsiops.OFFSET:] = _rand_planes(rng, depth, WORDS_PER_SHARD)
        frag.planes = planes
        frag.version += 1
        host[s] = planes
    e = Executor(h)

    threshold = 1 << (depth - 1)
    q = f"Sum(Row(amount > {threshold}), field=amount)"
    res = e.execute("b", q)[0]
    p50 = _p50_ms(lambda: e.execute("b", q))

    # control: numpy bit-plane descent compare (fragment.go:963 rangeOp)
    # + per-plane masked popcount sum (fragment.go:724)
    t0 = time.perf_counter()
    total, count = 0, 0
    for s in range(shards):
        planes = host[s]
        mags = planes[bsiops.OFFSET:]
        gt = np.zeros(WORDS_PER_SHARD, dtype=np.uint32)
        eq = planes[bsiops.EXISTS].copy()
        for k in range(depth - 1, -1, -1):
            want = np.uint32(0xFFFFFFFF) if (threshold >> k) & 1 else np.uint32(0)
            gt |= eq & mags[k] & ~want
            eq &= ~(mags[k] ^ want)
        for k in range(depth):
            total += _np_popcount(mags[k] & gt) << k
        count += _np_popcount(gt)
    base_ms = (time.perf_counter() - t0) * 1e3
    assert res.count == count and res.val == total, (res, count, total)
    # unique plane bytes the query reads: exists + depth magnitude planes
    nbytes = shards * (1 + depth) * WORDS_PER_SHARD * 4
    _emit(f"c2_bsi_range_sum_p50_10Mrows_{depth}bit{SCALED} ({device})",
          p50, "ms", base_ms / p50, hbm_bytes=nbytes,
          gbps=nbytes / p50 / 1e6, floor_ms=dispatch_floor_ms())


# ---------------------------------------------------------------------------
# Config 4 — time-quantum Row+Count across 256 shards
# ---------------------------------------------------------------------------

def bench_config4(device: str) -> None:
    from pilosa_tpu.core import FieldOptions, FieldType, Holder
    from pilosa_tpu.pql import Executor
    from pilosa_tpu.shardwidth import WORDS_PER_SHARD

    rng = np.random.default_rng(4)
    shards, rows = _n(256), 4
    months = [f"standard_2010{m:02d}" for m in range(1, 13)]
    h = Holder()
    idx = h.create_index("t")
    idx.create_field("cab", FieldOptions(type=FieldType.TIME,
                                         time_quantum="YMD"))
    f = idx.field("cab")
    host = {}
    for view in months:
        planes = _rand_planes(rng, rows, shards * WORDS_PER_SHARD)
        host[view] = planes
        for s in range(shards):
            frag = f.fragment(s, view, create=True)
            for r in range(rows):
                frag.import_row_plane(
                    r, planes[r, s * WORDS_PER_SHARD:(s + 1) * WORDS_PER_SHARD])
    e = Executor(h)

    # four covering monthly views (time.go:158 viewsByTimeRange)
    q = ("Count(Row(cab=1, from='2010-03-01T00:00', to='2010-07-01T00:00'))")
    got = e.execute("t", q)[0]
    p50 = _p50_ms(lambda: e.execute("t", q))

    t0 = time.perf_counter()
    acc = host["standard_201003"][1].copy()
    for m in ("standard_201004", "standard_201005", "standard_201006"):
        acc |= host[m][1]
    want = _np_popcount(acc)
    base_ms = (time.perf_counter() - t0) * 1e3
    assert got == want, (got, want)
    # four covering monthly view planes, one row each, across all shards
    nbytes = 4 * shards * WORDS_PER_SHARD * 4
    _emit(f"c4_timequantum_row_count_p50_256shards{SCALED} ({device})",
          p50, "ms", base_ms / p50, hbm_bytes=nbytes,
          gbps=nbytes / p50 / 1e6, floor_ms=dispatch_floor_ms())


# ---------------------------------------------------------------------------
# Config 5 — dataframe Apply float aggregation (64 shards, 67M rows)
# ---------------------------------------------------------------------------

def bench_config5(device: str) -> None:
    from pilosa_tpu.api import API
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng(5)
    shards = _n(64)
    api = API()
    api.create_index("df")
    cols = {}
    for s in range(shards):
        fare = rng.random(SHARD_WIDTH, dtype=np.float32) * 100
        dist = rng.random(SHARD_WIDTH, dtype=np.float32) * 30
        cols[s] = (fare, dist)
        api.import_dataframe("df", s, np.arange(SHARD_WIDTH),
                             {"fare": fare, "dist": dist})

    q = 'Apply("sum(fare + dist * 2)")'
    got = api.query("df", q)[0]
    p50 = _p50_ms(lambda: api.query("df", q))

    t0 = time.perf_counter()
    want = 0.0
    for fare, dist in cols.values():
        want += float(np.sum(fare + dist * 2))
    base_ms = (time.perf_counter() - t0) * 1e3
    assert abs(got.value - want) / abs(want) < 1e-3, (got.value, want)
    nbytes = 2 * shards * SHARD_WIDTH * 4  # two f32 columns per shard
    _emit(f"c5_dataframe_apply_sum_p50_67Mrows{SCALED} ({device})", p50,
          "ms", base_ms / p50, hbm_bytes=nbytes,
          gbps=nbytes / p50 / 1e6, floor_ms=dispatch_floor_ms())


# ---------------------------------------------------------------------------
# Config 6 — concurrent QPS: 64 intersect-count queries, scheduler on/off
# ---------------------------------------------------------------------------

def bench_config6(device: str) -> None:
    """64 concurrent Intersect+Count queries through the sched/ micro-
    batcher vs the sequential path. Each query alone is dispatch-bound
    (within ~2x of floor_ms), so the batcher's fused dispatches are where
    the QPS headroom lives; results must stay bit-identical."""
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.api import API

    rng = np.random.default_rng(6)
    n = _n(1_000_000)
    city = rng.integers(0, 50, n)
    dev = rng.integers(0, 10, n)
    api = API()
    api.create_index("c6")
    api.create_field("c6", "city")
    api.create_field("c6", "device")
    cols = np.arange(n)
    api.import_bits("c6", "city", rows=city, cols=cols)
    api.import_bits("c6", "device", rows=dev, cols=cols)

    nq = 64
    queries = [f"Count(Intersect(Row(city={i % 50}), Row(device={i % 10})))"
               for i in range(nq)]
    # numpy oracle: the bit-identical ground truth for BOTH paths
    want = [int(np.sum((city == i % 50) & (dev == i % 10)))
            for i in range(nq)]
    api.query("c6", queries[0])  # warm: compile + upload planes

    def timed(q):
        t0 = time.perf_counter()
        r = api.query("c6", q)[0]
        return r, time.perf_counter() - t0

    # scheduler OFF: the sequential baseline
    t0 = time.perf_counter()
    off = [timed(q) for q in queries]
    off_wall = time.perf_counter() - t0
    assert [r for r, _ in off] == want

    # scheduler ON: all 64 in flight, coalesced into fused dispatches
    api.enable_scheduler(window_ms=2.0, max_batch=nq)
    try:
        with ThreadPoolExecutor(nq) as pool:
            t0 = time.perf_counter()
            on = list(pool.map(timed, queries))
            on_wall = time.perf_counter() - t0
    finally:
        api.disable_scheduler()
    assert [r for r, _ in on] == want  # bit-identical under batching

    off_lat = sorted(s for _, s in off)
    on_lat = sorted(s for _, s in on)

    def pct(lat, p):
        return lat[min(len(lat) - 1, int(p * len(lat)))] * 1e3

    qps_on, qps_off = nq / on_wall, nq / off_wall
    _emit(f"c6_concurrent_qps_64q{SCALED} ({device})", qps_on, "qps",
          qps_on / qps_off, qps_off=qps_off,
          p50_ms=pct(on_lat, 0.5), p99_ms=pct(on_lat, 0.99),
          p50_off_ms=pct(off_lat, 0.5), p99_off_ms=pct(off_lat, 0.99),
          floor_ms=dispatch_floor_ms())


# ---------------------------------------------------------------------------
# Config 7 — result cache: intersect-count across cold/warm/write phases
# ---------------------------------------------------------------------------

def bench_config7(device: str) -> None:
    """Repeated intersect-count through the version-keyed result cache
    (cache/). Three phases, every read oracle-checked against numpy:
    cold (flush before each read — full dispatch), warm (identical
    repeat — hit, skips the ~floor_ms dispatch entirely), and
    write-invalidated (a Set between reads structurally invalidates the
    entry, so each read re-dispatches and must return the post-write
    count — a stale hit fails the assert). Cache-off baseline included."""
    from pilosa_tpu.api import API

    rng = np.random.default_rng(7)
    n = _n(1_000_000)
    city = rng.integers(0, 50, n)
    dev = rng.integers(0, 10, n)
    api = API()
    api.create_index("c7")
    api.create_field("c7", "city")
    api.create_field("c7", "device")
    cols = np.arange(n)
    api.import_bits("c7", "city", rows=city, cols=cols)
    api.import_bits("c7", "device", rows=dev, cols=cols)

    q = "Count(Intersect(Row(city=3), Row(device=7)))"
    want = int(np.sum((city == 3) & (dev == 7)))
    api.query("c7", q)  # warm: compile + upload planes
    iters = max(QUERY_ITERS, 5)

    def timed():
        t0 = time.perf_counter()
        r = api.query("c7", q)[0]
        return r, time.perf_counter() - t0

    # cache OFF: the unmodified read path
    off = []
    for _ in range(iters):
        r, s = timed()
        assert r == want
        off.append(s)

    cache = api.enable_cache()
    try:
        # cold: flush before every read, each pays the dispatch floor
        cold = []
        for _ in range(iters):
            cache.flush()
            r, s = timed()
            assert r == want
            cold.append(s)
        # warm: identical repeats are hits
        timed()  # fill
        warm = []
        for _ in range(iters * 4):
            r, s = timed()
            assert r == want
            warm.append(s)
        # write-invalidated: interleave writes with reads; fragment
        # versions in the key force a re-dispatch with the fresh count
        inval = []
        exp = want
        for i in range(iters):
            c = n + i
            api.query("c7", f"Set({c}, city=3)Set({c}, device=7)")
            exp += 1
            r, s = timed()
            assert r == exp, (r, exp)
            inval.append(s)
    finally:
        api.disable_cache()

    def pct(lat, p):
        lat = sorted(lat)
        return lat[min(len(lat) - 1, int(p * len(lat)))] * 1e3

    warm_p50 = pct(warm, 0.5)
    _emit(f"c7_cache_warm_intersect_count_p50{SCALED} ({device})",
          warm_p50, "ms", pct(cold, 0.5) / max(warm_p50, 1e-6),
          cold_p50_ms=pct(cold, 0.5), cold_p99_ms=pct(cold, 0.99),
          warm_p99_ms=pct(warm, 0.99),
          warm_qps=len(warm) / max(sum(warm), 1e-9),
          inval_p50_ms=pct(inval, 0.5), inval_p99_ms=pct(inval, 0.99),
          off_p50_ms=pct(off, 0.5), floor_ms=dispatch_floor_ms())


# ---------------------------------------------------------------------------
# Config 8 — cross-shard-set fusion: random subsets, fused vs unfused
# ---------------------------------------------------------------------------

def bench_config8(device: str) -> None:
    """32 concurrent count queries over random 4-of-8 shard subsets.
    Without superset fusion nearly every subset is its own GroupKey, so
    the micro-batcher degrades to ~32 serialized dispatches; with
    fusion (sched/scheduler.py superset merge + pql/executor.py shard
    masks) overlapping subsets pad onto one union stack and the whole
    wave collapses to a couple of dispatches. Both paths are oracle-
    checked against numpy, so the masked results are provably
    bit-identical to unfused execution."""
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.api import API
    from pilosa_tpu.obs.metrics import MetricsRegistry
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng(8)
    n_shards, per_shard = 8, _n(200_000)
    api = API()
    api.create_index("c8")
    api.create_field("c8", "city")
    api.create_field("c8", "device")
    city_by_shard, dev_by_shard = [], []
    for shard in range(n_shards):
        base = shard * SHARD_WIDTH
        city = rng.integers(0, 50, per_shard)
        dev = rng.integers(0, 10, per_shard)
        cols = base + np.arange(per_shard)
        api.import_bits("c8", "city", rows=city, cols=cols)
        api.import_bits("c8", "device", rows=dev, cols=cols)
        city_by_shard.append(city)
        dev_by_shard.append(dev)

    nq = 32
    subsets = [sorted(rng.choice(n_shards, size=4, replace=False).tolist())
               for _ in range(nq)]
    queries = [f"Count(Intersect(Row(city={i % 50}), Row(device={i % 10})))"
               for i in range(nq)]
    # numpy oracle over each query's OWN subset: ground truth for both
    # the unfused and the masked-superset path
    want = [int(sum(np.sum((city_by_shard[s] == i % 50)
                           & (dev_by_shard[s] == i % 10))
                    for s in subsets[i]))
            for i in range(nq)]
    # warm both stacked widths (4-shard subset + 8-shard union) so the
    # timed phases measure dispatch, not XLA compiles
    api.query("c8", queries[0], shards=subsets[0])
    api.executor.execute_many("c8", queries[:2],
                              per_query_shards=subsets[:2])

    def timed(i):
        t0 = time.perf_counter()
        r = api.query("c8", queries[i], shards=subsets[i])[0]
        return r, time.perf_counter() - t0

    def run_wave(fuse_waste_ratio):
        reg = MetricsRegistry()
        api.enable_scheduler(window_ms=2.0, max_batch=nq,
                             fuse_waste_ratio=fuse_waste_ratio,
                             registry=reg)
        try:
            with ThreadPoolExecutor(nq) as pool:
                t0 = time.perf_counter()
                out = list(pool.map(timed, range(nq)))
                wall = time.perf_counter() - t0
        finally:
            api.disable_scheduler()
        assert [r for r, _ in out] == want  # bit-identical to the oracle
        counters = reg.as_json()["counters"]
        dispatches = sum(v for k, v in counters.items()
                         if k.startswith("sched_batches_total"))
        merges = sum(v for k, v in counters.items()
                     if k.startswith("sched_superset_merges_total"))
        return sorted(s for _, s in out), wall, dispatches, merges

    def pct(lat, p):
        return lat[min(len(lat) - 1, int(p * len(lat)))] * 1e3

    # unfused: waste ratio 0 disables superset merging; only exact
    # same-subset queries may still share a dispatch
    off_lat, off_wall, off_disp, _ = run_wave(0.0)
    on_lat, on_wall, on_disp, on_merges = run_wave(2.0)

    on_p50 = pct(on_lat, 0.5)
    _emit(f"c8_fused_subset_p50_32q_4of8{SCALED} ({device})", on_p50,
          "ms", pct(off_lat, 0.5) / max(on_p50, 1e-6),
          p50_unfused_ms=pct(off_lat, 0.5), p99_ms=pct(on_lat, 0.99),
          p99_unfused_ms=pct(off_lat, 0.99),
          dispatches_fused=on_disp, dispatches_unfused=off_disp,
          superset_merges=on_merges,
          wall_fused_s=on_wall, wall_unfused_s=off_wall,
          qps_fused=nq / on_wall, qps_unfused=nq / off_wall,
          floor_ms=dispatch_floor_ms())


# ---------------------------------------------------------------------------
# Config 9 — fan-out under an injected straggler: hedged vs unhedged
# ---------------------------------------------------------------------------

def bench_config9(device: str) -> None:
    """3-node in-process cluster (replica_n=2) with a FaultPlan delaying
    every RPC to one non-coordinator node by ~10x the healthy leg
    latency. Unhedged fan-out pays the full delay on every query (its
    p99 IS the injected straggle); with resilience attached the slow leg
    hedges onto the replica after the rolling per-node percentile and
    the hedge wave wins. Every read in every phase is asserted
    bit-identical to the no-fault result."""
    from pilosa_tpu.cluster import FaultPlan, LocalCluster
    from pilosa_tpu.obs.metrics import MetricsRegistry
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng(9)
    plan = FaultPlan(seed=9)
    c = LocalCluster(3, replica_n=2, fault_plan=plan)
    try:
        co = c.coordinator
        co.create_index("c9")
        co.create_field("c9", "f")
        n_shards, per_shard = 6, _n(50_000)
        for shard in range(n_shards):
            rows = rng.integers(0, 8, per_shard)
            cols = shard * SHARD_WIDTH + np.arange(per_shard)
            # remote portions of a cluster import ride HTTP+JSON: plain ints
            co.import_bits("c9", "f", rows=rows.tolist(), cols=cols.tolist())
        q = "Count(Row(f=3))"
        want = co.query("c9", q)  # no-fault ground truth
        victim = next(n.node.id for n in c.nodes[1:]
                      if n.holder.index("c9").shards())
        iters = max(QUERY_ITERS, 5)

        def timed():
            t0 = time.perf_counter()
            r = co.query("c9", q)
            return r, time.perf_counter() - t0

        healthy = []
        for _ in range(iters):
            r, s = timed()
            assert r == want
            healthy.append(s)
        delay_s = min(max(10 * statistics.median(healthy), 0.25), 2.0)

        # unhedged: the plain fan-out waits out the straggler every time
        plan.delay(victim, delay_s)
        unhedged = []
        for _ in range(iters):
            r, s = timed()
            assert r == want  # correct, just slow
            unhedged.append(s)
        plan.clear()

        # hedged: warm the latency tracker fault-free, then re-inject.
        # Huge breaker threshold isolates the hedging effect — the
        # breaker would otherwise open and route around the victim,
        # which also beats the straggle but isn't what's measured here.
        reg = MetricsRegistry()
        co.enable_resilience(registry=reg, hedge_min_ms=1.0,
                             breaker_threshold=1 << 30)
        for _ in range(iters):
            r, s = timed()
            assert r == want
        plan.delay(victim, delay_s)
        hedged = []
        for _ in range(iters):
            r, s = timed()
            assert r == want  # bit-identical under the straggler
            hedged.append(s)
        plan.clear()
        co.disable_resilience()
        counters = reg.as_json()["counters"]
        hedges = sum(v for k, v in counters.items()
                     if k.startswith("cluster_hedges_total"))
        wins = sum(v for k, v in counters.items()
                   if k.startswith("cluster_hedge_wins_total"))
    finally:
        c.close()

    def pct(lat, p):
        lat = sorted(lat)
        return lat[min(len(lat) - 1, int(p * len(lat)))] * 1e3

    hedged_p99 = pct(hedged, 0.99)
    _emit(f"c9_hedged_straggler_fanout_p99{SCALED} ({device})", hedged_p99,
          "ms", pct(unhedged, 0.99) / max(hedged_p99, 1e-6),
          p99_unhedged_ms=pct(unhedged, 0.99),
          p50_hedged_ms=pct(hedged, 0.5),
          p50_unhedged_ms=pct(unhedged, 0.5),
          p50_healthy_ms=pct(healthy, 0.5),
          injected_delay_ms=delay_s * 1e3,
          hedges=hedges, hedge_wins=wins,
          floor_ms=dispatch_floor_ms())


# ---------------------------------------------------------------------------
# Config 10 — remote-leg stale-read window: gossip invalidation vs TTL
# ---------------------------------------------------------------------------

def bench_config10(device: str) -> None:
    """2-node cluster; a remote shard's Count is cached on the
    coordinator, then the OWNER node is written directly (bypassing the
    coordinator, so the write-epoch gate never fires). The stale-read
    window is the time from write completion until a polling read on
    the coordinator sees the new count. TTL-only caching rides out the
    TTL; gossip-keyed caching invalidates as soon as an anti-entropy
    round (or piggyback) delivers the owner's new version — measurably
    smaller, with zero TTL reliance."""
    from pilosa_tpu.cluster import LocalCluster
    from pilosa_tpu.obs.metrics import MetricsRegistry
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng(10)
    ttl_ms, gossip_interval_ms, trials = 300.0, 10.0, 8
    c = LocalCluster(2)
    try:
        co = c.coordinator
        co.create_index("c10")
        co.create_field("c10", "f")
        n_shards, per_shard = 4, _n(20_000)
        for shard in range(n_shards):
            rows = rng.integers(0, 8, per_shard)
            cols = shard * SHARD_WIDTH + np.arange(per_shard)
            co.import_bits("c10", "f", rows=rows.tolist(),
                           cols=cols.tolist())
        owner = next(n for n in c.nodes[1:]
                     if n.holder.index("c10").shards())
        shard = sorted(owner.holder.index("c10").shards())[0]
        q = "Count(Row(f=3))"
        next_col = [shard * SHARD_WIDTH + per_shard]

        def stale_window() -> float:
            """Warm the cache, write on the owner, poll until fresh."""
            want = co.query("c10", q)[0] + 1
            col, next_col[0] = next_col[0], next_col[0] + 1
            owner.api.import_bits("c10", "f", rows=[3], cols=[col])
            owner._announce_shards("c10")
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 5.0:
                if co.query("c10", q)[0] >= want:
                    return time.perf_counter() - t0
                time.sleep(0.002)
            return 5.0  # bailed: count the full budget as stale

        # phase 1: TTL-only remote-leg caching (the pre-gossip gate)
        co.enable_cache(ttl_ms=ttl_ms, registry=MetricsRegistry())
        ttl_windows = [stale_window() for _ in range(trials)]
        co.disable_cache()

        # phase 2: gossip fingerprint keying, TTL knob at ZERO
        c.enable_gossip(interval_ms=gossip_interval_ms, start=True,
                        registry=MetricsRegistry())
        c.run_gossip_rounds(3)  # converge before measuring
        co.enable_cache(ttl_ms=0, registry=MetricsRegistry())
        gossip_windows = [stale_window() for _ in range(trials)]
    finally:
        c.close()

    def pct(lat, p):
        lat = sorted(lat)
        return lat[min(len(lat) - 1, int(p * len(lat)))] * 1e3

    g_p50 = pct(gossip_windows, 0.5)
    _emit(f"c10_gossip_invalidation_p50{SCALED} ({device})", g_p50,
          "ms", pct(ttl_windows, 0.5) / max(g_p50, 1e-6),
          p50_ttl_ms=pct(ttl_windows, 0.5),
          p99_gossip_ms=pct(gossip_windows, 0.99),
          p99_ttl_ms=pct(ttl_windows, 0.99),
          ttl_ms=ttl_ms, gossip_interval_ms=gossip_interval_ms,
          trials=trials)


# ---------------------------------------------------------------------------
# Config 11 — crash recovery: WAL replay time + zero-loss vs WAL size
# ---------------------------------------------------------------------------

def bench_config11(device: str) -> None:
    """The crash-consistent recovery plane (storage/recovery.py): for
    growing WAL tail sizes, commit a write stream, sever the holder's
    file handles WITHOUT flushing python buffers (abandon_holder — the
    honest crash), reopen, and measure recovery wall time. Every
    recovered state is asserted bit-identical to the pre-crash checksum
    (zero loss), and the control is re-ingesting the same stream through
    the API — the price you'd pay without a WAL. A seeded kill point
    then exercises the injected-crash path end-to-end against its
    oracle prefixes."""
    import shutil
    import tempfile

    from pilosa_tpu.api import API
    from pilosa_tpu.storage.recovery import (
        CrashPlan, abandon_holder, crash_workload, oracle_checksums,
        run_crash_point,
    )

    rng = np.random.default_rng(11)
    base = tempfile.mkdtemp(prefix="pilosa-bench-c11-")
    sizes = []
    try:
        for n_commits in (_n(64), _n(256), _n(1024)):
            path = os.path.join(base, f"wal{n_commits}")
            api = API(path)
            api.create_index("r", {"trackExistence": False})
            api.create_field("r", "f")
            api.save()  # schema checkpoint: the WAL tail is all data
            rows = rng.integers(0, 8, size=(n_commits, 32))
            cols = rng.integers(0, 1 << 20, size=(n_commits, 32))
            t0 = time.perf_counter()
            for i in range(n_commits):
                api.import_bits("r", "f", rows=rows[i].tolist(),
                                cols=cols[i].tolist())
            ingest_s = time.perf_counter() - t0
            want = api.checksum()
            wal_bytes = api.holder.wal_bytes()
            api.holder.flush_wals()
            abandon_holder(api.holder)
            t0 = time.perf_counter()
            recovered = API(path)  # replays checkpoint + WAL tail
            recover_s = time.perf_counter() - t0
            assert recovered.checksum() == want, \
                f"recovery lost data at {n_commits} commits"
            sizes.append((n_commits, wal_bytes, recover_s, ingest_s))

        # injected crash: a seeded kill point must recover to an exact
        # committed prefix covering everything acked
        kp = os.path.join(base, "killpoint")
        batches = crash_workload(n_batches=8, seed=11)
        oracle = oracle_checksums(kp, batches)
        res = run_crash_point(kp, CrashPlan.seeded(11), batches,
                              checkpoint_bytes=1)
        assert res["checksum"] in oracle
        assert oracle.index(res["checksum"]) >= res["acked"]
    finally:
        shutil.rmtree(base, ignore_errors=True)

    n_commits, wal_bytes, recover_s, ingest_s = sizes[-1]
    per_size = {f"recover_ms_{n}c": r * 1e3 for n, _w, r, _i in sizes}
    per_size.update({f"wal_kb_{n}c": w / 1024 for n, w, _r, _i in sizes})
    _emit(f"c11_wal_recovery_{n_commits}commits{SCALED} ({device})",
          recover_s * 1e3, "ms", ingest_s / recover_s,
          wal_bytes=wal_bytes,
          replay_mbps=wal_bytes / max(recover_s, 1e-9) / 1e6,
          reingest_ms=ingest_s * 1e3,
          zero_loss_points=len(sizes) + 1,
          crash_site=(res["fired"][0] if res["fired"] else "none"),
          **per_size)


# ---------------------------------------------------------------------------
# Config 12 — distributed tracing overhead: off / sampled / always-on
# ---------------------------------------------------------------------------

def bench_config12(device: str) -> None:
    """Tracing-plane overhead on the single-node query path. Four phases
    over one fixed workload: untraced (the default NopTracer), tracing
    configured-but-off, 10% head sampling, and always-on with the trace
    store. Emits p50 per phase and overhead ratios vs untraced; HARD
    asserts are correctness, not timing (CPU timing is too noisy to
    gate): results stay bit-identical across phases, the disabled path
    returns the one shared no-op span, and the off phase allocates ZERO
    Span objects."""
    from pilosa_tpu.api import API
    from pilosa_tpu.obs import tracing as T
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng(12)
    api = API()
    api.create_index("c12")
    api.create_field("c12", "f")
    per_shard = _n(40_000)
    for shard in range(2):
        rows = rng.integers(0, 8, per_shard)
        cols = shard * SHARD_WIDTH + np.arange(per_shard)
        api.import_bits("c12", "f", rows=rows.tolist(), cols=cols.tolist())
    queries = ["Count(Row(f=3))", "Intersect(Row(f=1), Row(f=2))",
               "TopN(f, n=4)"]

    def workload() -> list:
        return [api.query_json("c12", q) for q in queries]

    prev = T.get_tracer()
    phases = {}
    results = {}
    try:
        # phase: untraced (the seed default — the comparison baseline)
        T.set_tracer(T.NopTracer())
        results["untraced"] = workload()
        phases["untraced"] = _p50_ms(workload)

        # phase: configured but off — must be allocation-free: count
        # Span constructions across the whole phase
        T.set_tracer(T.Tracer(enabled=False))
        nop = T.get_tracer().start_span("probe")
        assert nop is T.NOP_SPAN and nop is T.get_tracer().start_trace("p")
        orig_init = T.Span.__init__
        allocs = [0]

        def counting_init(self, *a, **k):
            allocs[0] += 1
            orig_init(self, *a, **k)

        T.Span.__init__ = counting_init
        try:
            results["off"] = workload()
            phases["off"] = _p50_ms(workload)
        finally:
            T.Span.__init__ = orig_init
        assert allocs[0] == 0, f"disabled tracing allocated {allocs[0]} spans"

        # phase: 10% head sampling
        T.set_tracer(T.Tracer(enabled=True, sample_rate=0.1,
                              store=T.TraceStore(64),
                              rng=random.Random(12)))
        results["sampled"] = workload()
        phases["sampled"] = _p50_ms(workload)

        # phase: always-on, full span trees into the store
        T.set_tracer(T.Tracer(enabled=True, sample_rate=1.0,
                              store=T.TraceStore(64)))
        results["always"] = workload()
        phases["always"] = _p50_ms(workload)
        stored = len(T.get_tracer().store)
        assert stored > 0, "always-on tracing stored no traces"
    finally:
        T.set_tracer(prev)

    for name in ("off", "sampled", "always"):
        assert results[name] == results["untraced"], \
            f"tracing phase {name!r} changed query results"

    base = phases["untraced"]

    def pct_over(name: str) -> float:
        return (phases[name] / max(base, 1e-9) - 1.0) * 100.0

    _emit(f"c12_tracing_always_on_p50{SCALED} ({device})",
          phases["always"], "ms", base / max(phases["always"], 1e-9),
          untraced_ms=base, off_ms=phases["off"],
          sampled_ms=phases["sampled"],
          off_overhead_pct=pct_over("off"),
          sampled_overhead_pct=pct_over("sampled"),
          always_overhead_pct=pct_over("always"),
          spans_allocated_off=allocs[0], traces_stored=stored,
          queries=len(queries))


# ---------------------------------------------------------------------------
# Config 13 — device residency: cold (re-staged) vs warm (resident) path
# ---------------------------------------------------------------------------

def bench_config13(device: str) -> None:
    """The dispatch-floor kill shot (ISSUE 8 acceptance): the same query
    battery timed COLD (field stacks released before every workload pass,
    so each query re-stages host fragments: stack.build + device.h2d_copy
    every time) and WARM (budget-resident planes + compiled per-family
    programs). HARD asserts: warm results bit-identical to the
    non-resident classic-path oracle, warm p50 >= 5x below cold on CPU,
    and NO warm query's trace contains a staging stage."""
    from pilosa_tpu.api import API
    from pilosa_tpu.obs import tracing as T
    from pilosa_tpu.pql import programs
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng(13)
    api = API()
    api.create_index("c13")
    api.create_field("c13", "f")
    api.create_field("c13", "g")
    api.create_field("c13", "v", {"type": "int"})
    per_shard = _n(120_000)
    for shard in range(2):
        cols = shard * SHARD_WIDTH + np.arange(per_shard)
        # enough distinct rows that re-staging the stacks (the cold tax)
        # costs what a realistic working set costs: ~64-row planes at
        # [rows, 2*words] assemble + upload on every cold pass
        api.import_bits("c13", "f",
                        rows=rng.integers(0, 64, per_shard).tolist(),
                        cols=cols.tolist())
        api.import_bits("c13", "g",
                        rows=rng.integers(0, 32, per_shard).tolist(),
                        cols=cols.tolist())
        api.holder.index("c13").field("v").set_values(
            cols[:_n(4_000)].tolist(),
            rng.integers(-50, 50, _n(4_000)).tolist())
    queries = [
        "Count(Row(f=3))",
        "Count(Intersect(Row(f=1), Row(g=1)))",
        "Count(Union(Row(f=2), Row(g=3), Row(f=5)))",
        "Count(Difference(Row(f=4), Row(g=0)))",
        "Count(Not(Row(f=6)))",
        "Count(Intersect(Row(v > 0), Row(g=2)))",
        "Intersect(Row(f=1), Row(g=1))",
    ]

    def workload() -> list:
        return [api.query_json("c13", q) for q in queries]

    def release_stacks() -> None:
        from pilosa_tpu.core.stacked import release_field_cache

        # what a non-resident engine pays per query: every stack leaves
        # HBM (budget entries released, not orphaned) and the next read
        # re-assembles + re-uploads from host fragments
        for fld in api.holder.index("c13").fields.values():
            release_field_cache(fld)

    # oracle: the classic per-op path on freshly staged stacks — the
    # bit-identity reference for the fused resident programs
    programs.ENABLED = False
    release_stacks()
    oracle = workload()
    programs.ENABLED = True

    def cold_pass() -> list:
        # release before EVERY query, not once per pass: each cold query
        # pays its own staging, exactly what a non-resident engine pays
        out = []
        for q in queries:
            release_stacks()
            out.append(api.query_json("c13", q))
        return out

    cold_ms = _p50_ms(cold_pass)

    api.holder.prewarm("c13")
    warm_results = workload()
    assert warm_results == oracle, \
        "resident programs diverged from the classic-path oracle"
    warm_ms = _p50_ms(workload)

    # trace-walk: a warm query must never stage (no stack.build, no
    # device.h2d_copy anywhere in its span tree)
    def span_names(doc, acc):
        acc.append(doc.get("name", ""))
        for c in doc.get("children", ()):
            span_names(c, acc)
        return acc

    prev = T.get_tracer()
    T.set_tracer(T.Tracer(enabled=True, sample_rate=1.0,
                          store=T.TraceStore(64)))
    try:
        for q in queries:
            with T.get_tracer().start_trace("q13") as root:
                api.query_json("c13", q)
            names = span_names(root.to_json(), [])
            assert "device.h2d_copy" not in names, \
                f"warm query re-staged to device: {q}"
            assert "stack.build" not in names, \
                f"warm query rebuilt a stack: {q}"
    finally:
        T.set_tracer(prev)

    stats = api.holder.residency_stats()
    speedup = cold_ms / max(warm_ms, 1e-9)
    # the ISSUE 8 acceptance bar — holds on CPU, so it holds everywhere
    # staging is costlier than a dispatch
    assert speedup >= 5.0, \
        f"warm resident path only {speedup:.1f}x over cold (<5x)"
    _emit(f"c13_resident_warm_p50{SCALED} ({device})",
          warm_ms, "ms", speedup,
          cold_p50_ms=cold_ms, warm_p50_ms=warm_ms,
          floor_ms=dispatch_floor_ms(),
          resident_bytes=int(stats["resident_bytes"]),
          programs_cached=programs.program_cache_len(),
          queries=len(queries))


# ---------------------------------------------------------------------------
# Config 14 — coalesced fan-out: batched vs unbatched node RPCs at 64-way
# ---------------------------------------------------------------------------

def bench_config14(device: str) -> None:
    """3-node cluster (replica_n=2), 64 concurrent mixed-shard Count
    queries released through a barrier. Unbatched, every query's fan-out
    ships one /internal/query RPC per remote primary node; with the
    per-node coalescer (ISSUE 9) concurrent legs to the same node ride
    ONE /internal/query-batch RPC through the remote execute_many
    superset-merge. HARD asserts: every result in every phase equals a
    numpy bincount oracle, and the batched pass ships >=8x fewer
    per-node RPCs than the unbatched pass for the same workload. A
    final chaos wave (FaultPlan delay scoped op="query_batch" on one
    node + hedging) re-asserts bit-identity when batches straggle and
    hedged batch legs race replicas."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.cluster import FaultPlan, LocalCluster
    from pilosa_tpu.obs.metrics import MetricsRegistry
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng(14)
    plan = FaultPlan(seed=14)  # unarmed until the chaos wave
    c = LocalCluster(3, replica_n=2, fault_plan=plan)
    try:
        co = c.coordinator
        co.create_index("c14")
        co.create_field("c14", "f")
        n_shards, n_rows, per_shard = 6, 8, _n(40_000)
        row_counts = []
        for shard in range(n_shards):
            rows = rng.integers(0, n_rows, per_shard)
            cols = shard * SHARD_WIDTH + np.arange(per_shard)
            co.import_bits("c14", "f", rows=rows.tolist(),
                           cols=cols.tolist())
            row_counts.append(np.bincount(rows, minlength=n_rows))

        # 64 mixed-shard queries: row varies with i, each reads its own
        # random shard subset — so concurrent legs hit the same nodes
        # with DIFFERENT (pql, shards) pairs and only the coalescer (not
        # dedup or caching) can collapse the wire traffic
        nq = 64
        queries = []
        for i in range(nq):
            row = i % n_rows
            subset = sorted(int(s) for s in rng.choice(
                n_shards, size=int(rng.integers(2, n_shards)),
                replace=False))
            want = int(sum(row_counts[s][row] for s in subset))
            queries.append((f"Count(Row(f={row}))", subset, want))

        def run_wave(batch) -> list:
            """All queries released at once; per-query wall latency."""
            barrier = threading.Barrier(len(batch))

            def one(entry):
                pql, subset, want = entry
                barrier.wait()
                t0 = time.perf_counter()
                r = co.query("c14", pql, shards=subset)
                dt = time.perf_counter() - t0
                assert r == [want], f"{pql} over {subset}: {r} != [{want}]"
                return dt

            with ThreadPoolExecutor(max_workers=len(batch)) as pool:
                return list(pool.map(one, batch))

        waves = 3
        co.query("c14", queries[0][0], shards=queries[0][1])  # warm placement

        sent0 = dict(co.client.op_counts)
        unbatched = []
        for _ in range(waves):
            unbatched.extend(run_wave(queries))
        solo_rpcs = co.client.op_counts.get("query", 0) - \
            sent0.get("query", 0)

        co.enable_cluster_batch()
        sent0 = dict(co.client.op_counts)
        batched = []
        for _ in range(waves):
            batched.extend(run_wave(queries))
        batch_rpcs = co.client.op_counts.get("query_batch", 0) - \
            sent0.get("query_batch", 0)
        assert co.client.op_counts.get("query", 0) == \
            sent0.get("query", 0), \
            "batched pass leaked legs onto the solo /internal/query RPC"

        reduction = solo_rpcs / max(batch_rpcs, 1)
        assert batch_rpcs > 0 and reduction >= 8.0, \
            f"coalescer only cut per-node RPCs {reduction:.1f}x " \
            f"({solo_rpcs} solo vs {batch_rpcs} batched; <8x)"

        # chaos wave: delay every batch RPC to one remote primary; the
        # hedged batch leg races the replicas and every demuxed member
        # must still match the oracle bit-for-bit
        reg = MetricsRegistry()
        # hedge well before the 0.3s injected delay but not instantly
        # (16 concurrent queries would hedge EVERYTHING at 1ms), and
        # floor the adaptive leg timeout high enough that a healthy but
        # GIL-contended replica leg is never reaped — a reaped primary
        # plus a reaped hedge exhausts both owners and fails the query
        co.enable_resilience(registry=reg, hedge_min_ms=30.0,
                             timeout_min_ms=5000.0,
                             breaker_threshold=1 << 30)
        for _ in range(2):  # warm the per-node latency tracker
            run_wave(queries[:16])
        victim = next(n.node.id for n in c.nodes[1:]
                      if n.holder.index("c14").shards())
        plan.delay(victim, 0.3, op="query_batch")
        run_wave(queries[:16])  # asserts oracle equality inside
        plan.clear()
        co.disable_resilience()
        co.disable_cluster_batch()
        counters = reg.as_json()["counters"]
        hedges = sum(v for k, v in counters.items()
                     if k.startswith("cluster_hedges_total"))
    finally:
        c.close()

    def pct(lat, p):
        lat = sorted(lat)
        return lat[min(len(lat) - 1, int(p * len(lat)))] * 1e3

    batched_p99 = pct(batched, 0.99)
    _emit(f"c14_batched_fanout_p99_64way{SCALED} ({device})", batched_p99,
          "ms", pct(unbatched, 0.99) / max(batched_p99, 1e-6),
          p99_unbatched_ms=pct(unbatched, 0.99),
          p50_batched_ms=pct(batched, 0.5),
          p50_unbatched_ms=pct(unbatched, 0.5),
          rpcs_unbatched=solo_rpcs, rpcs_batched=batch_rpcs,
          rpc_reduction=reduction, chaos_hedges=hedges,
          queries=nq, waves=waves, floor_ms=dispatch_floor_ms())


# ---------------------------------------------------------------------------
# Config 15 — health-plane overhead on the single-node query path
# ---------------------------------------------------------------------------

def bench_config15(device: str) -> None:
    """Health-plane overhead on the single-node query path. Two phases
    over one fixed workload: plane disabled (the seed default) and the
    always-on piggyback mode (`PILOSA_TPU_OBS_TIMELINE=1`: SLO
    accounting per request + cadence-gated timeline samples, zero
    background threads). Emits p50 per phase and the overhead ratio;
    like the tracing gate (config 12) the HARD asserts are correctness,
    not timing: results stay bit-identical, the disabled phase does zero
    health-plane work, and the enabled phase actually sampled."""
    from pilosa_tpu.api import API
    from pilosa_tpu.obs import metrics as M
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng(15)
    api = API()
    api.create_index("c15")
    api.create_field("c15", "f")
    per_shard = _n(40_000)
    for shard in range(2):
        rows = rng.integers(0, 8, per_shard)
        cols = shard * SHARD_WIDTH + np.arange(per_shard)
        api.import_bits("c15", "f", rows=rows.tolist(), cols=cols.tolist())
    queries = ["Count(Row(f=3))", "Intersect(Row(f=1), Row(f=2))",
               "TopN(f, n=4)"]

    def workload() -> list:
        return [api.query_json("c15", q) for q in queries]

    phases = {}
    results = {}

    # phase: disabled (the seed default) — no plane object exists, the
    # query path's only cost is one `is None` check per surface
    assert api.health is None, "health plane must be off by default"
    before = M.REGISTRY.value(M.METRIC_TIMELINE_SAMPLES)
    results["disabled"] = workload()
    phases["disabled"] = _p50_ms(workload)
    assert M.REGISTRY.value(M.METRIC_TIMELINE_SAMPLES) == before, \
        "disabled health plane took timeline samples"

    # phase: always-on piggyback (interval clamped low so the cadence
    # check actually fires during the run, not just once)
    hp = api.enable_health(interval_ms=10.0)
    try:
        results["always"] = workload()
        phases["always"] = _p50_ms(workload)
        sampled = len(hp.timeline)
        assert sampled > 0, "always-on health plane never sampled"
        events = {r["surface"]: r["events_fast"]
                  for r in hp.slo.burn_rates()}
        assert events.get("query", 0) > 0, \
            "query surface never reached the SLO tracker"
    finally:
        api.disable_health()

    assert results["always"] == results["disabled"], \
        "health plane changed query results"

    base = phases["disabled"]
    _emit(f"c15_health_plane_always_on_p50{SCALED} ({device})",
          phases["always"], "ms", base / max(phases["always"], 1e-9),
          disabled_ms=base,
          always_overhead_pct=(phases["always"] / max(base, 1e-9)
                               - 1.0) * 100.0,
          timeline_samples=sampled, queries=len(queries))


# ---------------------------------------------------------------------------
# Config 16 — kernel-attribution (devprof) overhead + correctness gate
# ---------------------------------------------------------------------------

def bench_config16(device: str) -> None:
    """Devprof-plane gate on the warm resident query path. Two phases
    over one fixed workload: disabled (the seed default — HARD assert:
    exactly zero cost-model evaluations and zero profile allocations)
    and enabled via devprof.enable() (HARD asserts: bit-identical
    results, and a profile with positive MFU/GB/s for every distinct
    query family the battery compiles). Like configs 12/15 the hard
    asserts are correctness/allocation, not timing — the overhead pct is
    emitted for the ≤3% acceptance read. Interleaved decomposition shows
    the disabled path (no hooks installed) measures 0% within noise; the
    enabled cost is the fixed per-dispatch registry publication (~30us),
    which is a few percent against sub-millisecond CPU dispatches and
    vanishes against real device dispatch times."""
    from pilosa_tpu.api import API
    from pilosa_tpu.obs import devprof
    from pilosa_tpu.pql import programs
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng(16)
    api = API()
    api.create_index("c16")
    api.create_field("c16", "f")
    api.create_field("c16", "g")
    per_shard = _n(80_000)
    for shard in range(2):
        cols = shard * SHARD_WIDTH + np.arange(per_shard)
        api.import_bits("c16", "f",
                        rows=rng.integers(0, 32, per_shard).tolist(),
                        cols=cols.tolist())
        api.import_bits("c16", "g",
                        rows=rng.integers(0, 16, per_shard).tolist(),
                        cols=cols.tolist())
    # four distinct tapes -> four compiled families to attribute
    queries = [
        "Count(Row(f=3))",
        "Count(Intersect(Row(f=1), Row(g=1)))",
        "Count(Union(Row(f=2), Row(g=3), Row(f=5)))",
        "Intersect(Row(f=1), Row(g=2))",
    ]
    api.holder.prewarm("c16")

    def workload() -> list:
        return [api.query_json("c16", q) for q in queries]

    assert not devprof.ENABLED, \
        "devprof must be off for the disabled phase (unset " \
        "PILOSA_TPU_DEVPROF)"
    evals0 = devprof.cost_evals()
    allocs0 = devprof.KERNELS.allocations
    results_off = workload()
    _p50_ms(workload)  # warm both paths before the paired timing below
    assert devprof.cost_evals() == evals0, \
        "disabled devprof evaluated the cost model"
    assert devprof.KERNELS.allocations == allocs0, \
        "disabled devprof allocated kernel profiles"

    devprof.enable()
    try:
        devprof.reset()
        results_on = workload()
        assert results_on == results_off, "devprof changed query results"
        # paired interleaved timing: host noise on a shared CPU dwarfs
        # the hook cost when the phases run in separate blocks, so each
        # iteration times both states back-to-back
        off_t, on_t = [], []
        for _ in range(max(24, QUERY_ITERS)):
            devprof.disable()
            t0 = time.perf_counter()
            workload()
            off_t.append(time.perf_counter() - t0)
            devprof.enable()
            t0 = time.perf_counter()
            workload()
            on_t.append(time.perf_counter() - t0)
        off_ms = statistics.median(off_t) * 1e3
        on_ms = statistics.median(on_t) * 1e3
        profiles = devprof.KERNELS.snapshot()
        assert len(profiles) >= len(queries), \
            f"{len(profiles)} kernel profiles for {len(queries)} families"
        for p in profiles:
            assert p["dispatches"] > 0, p
            assert p.get("mfu_pct", 0.0) > 0.0, p
            assert p.get("achieved_gbps", 0.0) > 0.0, p
    finally:
        devprof.disable()

    overhead_pct = (on_ms / max(off_ms, 1e-9) - 1.0) * 100.0
    _emit(f"c16_devprof_overhead_p50{SCALED} ({device})",
          on_ms, "ms", off_ms / max(on_ms, 1e-9),
          disabled_ms=off_ms, overhead_pct=overhead_pct,
          kernel_profiles=len(profiles),
          programs_cached=programs.program_cache_len(),
          cost_evals=devprof.cost_evals(), queries=len(queries))


# ---------------------------------------------------------------------------
# Config 17 — sustained-rate streaming ingest (stream/)
# ---------------------------------------------------------------------------

def bench_config17(device: str) -> None:
    """Streaming-ingest gate (stream/): three phases over one 2M-row
    workload.

    1. control — the current c1 ingest path (columnar CSV through the
       classic single-threaded Ingester), best-of-2: the rows/s the
       acceptance bar doubles.
    2. pipelined — the same rows as chunked stream messages
       (broker.make_chunk, the Kafka batch-per-message production
       shape) through PipelinedIngester, best-of-3. HARD asserts:
       >= 2x the control rows/s AND a bit-identical checksum vs the
       classic Ingester draining the SAME broker stream (the oracle).
    3. read protection — heavy GroupBy p50/p99 with the admission
       scheduler on, alone vs under a concurrent full-rate re-ingest
       churn (the churn re-applies the same rows, so the read working
       set stays fixed and the ratio isolates contention). HARD
       asserts: p50 and p99 within 1.5x, churn actually overlapped the
       reads, and the checksum is unchanged after the churn (idempotent
       re-application).
    """
    import threading

    from pilosa_tpu.api import API
    from pilosa_tpu.ingest.ingest import Ingester
    from pilosa_tpu.ingest.source import CSVSource, _parse_header
    from pilosa_tpu.stream.broker import (BrokerSource, StreamBroker,
                                          make_chunk)
    from pilosa_tpu.stream.pipeline import PipelinedIngester

    rng = np.random.default_rng(17)
    n = _n(2_000_000)
    city = rng.integers(0, 100, n)
    dev = rng.integers(0, 10, n)

    # phase 1: the control — what `bench.py --configs 1` measures today
    lines = ["id,city__IS,device__IS"]
    lines.extend(f"{i},{city[i]},{dev[i]}" for i in range(n))
    csv_text = "\n".join(lines)
    c1_rows_s = 0.0
    for _ in range(2):
        api = API()
        t0 = time.perf_counter()
        got = Ingester(api, "s17", CSVSource(csv_text, inline=True),
                       batch_size=131072).run()
        c1_rows_s = max(c1_rows_s, n / (time.perf_counter() - t0))
        assert got == n, got
    del csv_text, lines

    # the stream: chunked messages, produced once, drained by the
    # classic oracle and the timed pipelined runs as separate groups
    chunk = 8192
    broker = StreamBroker(partitions=1, seed=17)
    ids = np.arange(n)
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        broker.produce("s17", make_chunk({
            "id": ids[lo:hi], "city": city[lo:hi], "device": dev[lo:hi]}))
    schema = _parse_header(["city__IS", "device__IS"])

    api_cl = API()
    got = Ingester(api_cl, "s17",
                   BrokerSource(broker.consumer("classic", ["s17"]),
                                schema),
                   batch_size=131072).run()
    assert got == n, got
    oracle = api_cl.checksum()

    # phase 2: pipelined over the same stream, best-of-3
    piped_rows_s, api_rd = 0.0, None
    for t in range(3):
        api_pp = API()
        p = PipelinedIngester(api_pp, "s17",
                              broker.consumer(f"piped{t}", ["s17"]),
                              schema=schema, batch_rows=32)
        t0 = time.perf_counter()
        got = p.run()
        piped_rows_s = max(piped_rows_s, n / (time.perf_counter() - t0))
        assert got == n, got
        assert api_pp.checksum() == oracle, \
            "pipelined ingest diverged from the classic Ingester oracle"
        api_rd = api_pp
    assert piped_rows_s >= 2.0 * c1_rows_s, \
        f"pipelined {piped_rows_s:,.0f} rows/s < 2x classic " \
        f"{c1_rows_s:,.0f} rows/s"
    _emit(f"c17_stream_pipelined_ingest_2M_rows{SCALED} ({device})",
          piped_rows_s, "rows/s", piped_rows_s / c1_rows_s,
          classic_rows_s=c1_rows_s, chunk_rows=chunk)

    # phase 3: read p50/p99 alone vs under full-rate ingest churn
    api_rd.enable_scheduler()
    q = "GroupBy(Rows(city), Rows(device), limit=100)"
    want = int(np.sum((city == 7) & (dev == 3)))
    assert api_rd.query(
        "s17", "Count(Intersect(Row(city=7), Row(device=3)))")[0] == want

    def percentiles(iters):
        api_rd.query("s17", q)  # warm
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            api_rd.query("s17", q)
            times.append(time.perf_counter() - t0)
        return (float(np.percentile(times, 50)) * 1e3,
                float(np.percentile(times, 99)) * 1e3)

    iters = max(25, QUERY_ITERS * 5)
    p50_alone, p99_alone = percentiles(iters)

    stop = threading.Event()
    churned = [0]

    def churn():
        w = 0
        while not stop.is_set():
            w += 1
            c = PipelinedIngester(
                api_rd, "s17", broker.consumer(f"churn{w}", ["s17"]),
                schema=schema, batch_rows=8, group=f"churn{w}")
            churned[0] += c.run()

    th = threading.Thread(target=churn, daemon=True)
    th.start()
    while churned[0] == 0:  # ensure the churn is live before timing
        time.sleep(0.005)
    p50_busy, p99_busy = percentiles(iters)
    still_churning = th.is_alive()
    stop.set()
    th.join(timeout=30)
    assert still_churning and churned[0] >= n, \
        f"churn did not overlap the reads ({churned[0]} rows)"
    assert api_rd.checksum() == oracle, \
        "idempotent re-ingest changed the checksum"
    assert p50_busy <= 1.5 * p50_alone, \
        f"read p50 {p50_busy:.1f}ms vs {p50_alone:.1f}ms alone"
    assert p99_busy <= 1.5 * p99_alone, \
        f"read p99 {p99_busy:.1f}ms vs {p99_alone:.1f}ms alone"
    _emit(f"c17_read_p50_under_full_rate_ingest{SCALED} ({device})",
          p50_busy, "ms", p50_alone / p50_busy,
          p50_alone_ms=p50_alone, p99_alone_ms=p99_alone,
          p99_busy_ms=p99_busy, churn_rows=churned[0],
          floor_ms=dispatch_floor_ms())


# ---------------------------------------------------------------------------
# Config 18 — multi-tenant noisy-neighbor isolation (obs/tenants.py)
# ---------------------------------------------------------------------------

def bench_config18(device: str) -> None:
    """Tenant-plane gate: noisy-neighbor isolation under chaos.

    One 3-node LocalCluster (replica 2) under a seeded FaultPlan delay
    plan, serving three well-behaved tenants and one abuser over real
    HTTP with X-Tenant attribution.

    1. plane off — the well-behaved read suite over HTTP: the results
       oracle. HARD asserts: zero tenant context switches while
       disabled (SCOPE_COUNT unchanged) — off means free.
    2. plane on (quotas + fair share + health), no abuser — per-tenant
       baseline p99. HARD asserts: results bit-identical to the oracle.
    3. abuser on — "mallory" floods a separate index with queries (a
       third of them erroring) and imports, capped by per-tenant
       quotas. HARD asserts: every well-behaved tenant's p99 <= 1.5x
       its no-abuser baseline, results STILL bit-identical, the abuser
       was actually rejected (429 + Retry-After), the abuser is burning
       its SLO error budget while no well-behaved tenant is,
       /internal/tenants reports all four tenants, per-tenant burn
       gauges landed in /metrics, and a tenant_burn flight bundle
       captured the incident.
    """
    import json as _json
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from pilosa_tpu.cluster.harness import LocalCluster
    from pilosa_tpu.cluster.resilience import FaultPlan
    from pilosa_tpu.obs import tenants as tenants_mod

    rng = np.random.default_rng(18)
    n = _n(400_000)
    city = rng.integers(0, 50, n)
    dev = rng.integers(0, 8, n)
    wb = ("alpha", "bravo", "charlie")
    suite = [
        "GroupBy(Rows(city), Rows(device), limit=100)",
        "Count(Intersect(Row(city=7), Row(device=3)))",
        "TopN(city, n=5)",
    ]
    iters = max(10, QUERY_ITERS * 2)

    plan = (FaultPlan(seed=18)
            .delay("node1", 0.002, prob=0.2, op="query")
            .delay("node2", 0.002, prob=0.2, op="query"))

    with tempfile.TemporaryDirectory(prefix="bench18") as tmp, \
            LocalCluster(3, replica_n=2, base_path=tmp,
                         fault_plan=plan) as cluster:
        coord = cluster.coordinator
        uri = coord.node.uri

        def req(path, data=None, tenant=None, method=None, ctype=None):
            r = urllib.request.Request(uri + path, data=data,
                                       method=method)
            if tenant is not None:
                r.add_header("X-Tenant", tenant)
            if ctype is not None:
                r.add_header("Content-Type", ctype)
            try:
                with urllib.request.urlopen(r, timeout=60) as resp:
                    return (resp.status, _json.loads(resp.read()),
                            dict(resp.headers))
            except urllib.error.HTTPError as e:
                return e.code, _json.loads(e.read()), dict(e.headers)

        def run_suite(tenant):
            results, times = [], []
            for q in suite:
                st, body, _ = req("/index/mt/query", q.encode(),
                                  tenant)  # warm
                assert st == 200, body
                for _ in range(iters):
                    t0 = time.perf_counter()
                    st, body, _ = req("/index/mt/query", q.encode(),
                                      tenant)
                    times.append(time.perf_counter() - t0)
                    assert st == 200, body
                results.append(body["results"])
            return results, float(np.percentile(times, 99)) * 1e3

        coord.create_index("mt")
        coord.create_field("mt", "city", {"type": "set"})
        coord.create_field("mt", "device", {"type": "set"})
        cols = list(range(n))
        coord.import_bits("mt", "city", rows=city.tolist(), cols=cols)
        coord.import_bits("mt", "device", rows=dev.tolist(), cols=cols)

        # phase 1: plane off — the oracle, and proof that off is free
        scope0 = tenants_mod.SCOPE_COUNT
        assert coord.tenants is None
        oracle, _ = run_suite(None)
        assert tenants_mod.SCOPE_COUNT == scope0, \
            "tenant context touched while the plane is disabled"

        # phase 2: plane on, no abuser — per-tenant baselines
        regs = cluster.enable_tenants()
        cluster.enable_health()
        for node in cluster.nodes:
            node.enable_scheduler()
        # quota only binds the abuser; well-behaved tenants stay
        # unlimited (rate 0) — attribution without enforcement
        regs[0].set_quota("mallory", qps=5.0, ingest_rows_s=400.0)
        baseline = {}
        for t in wb:
            res, baseline[t] = run_suite(t)
            assert res == oracle, f"tenant {t} diverged with plane on"

        # phase 3: the abuser saturates a SEPARATE index while the
        # well-behaved tenants re-run their suites
        coord.create_index("abuse")
        coord.create_field("abuse", "f", {"type": "set"})
        stop = threading.Event()
        stats = {"attempts": 0, "rejected": 0, "retry_after": 0}
        imp = _json.dumps({"field": "f", "rows": [1] * 200,
                           "cols": list(range(200))}).encode()

        def abuser():
            k = 0
            while not stop.is_set():
                k += 1
                if k % 4 == 0:
                    st, _, h = req("/index/abuse/import", imp, "mallory",
                                   ctype="application/json")
                elif k % 3 == 0:
                    # SLO damage: a query that errors (missing field)
                    st, _, h = req("/index/abuse/query",
                                   b"Row(missing=1)", "mallory")
                else:
                    st, _, h = req("/index/abuse/query", b"Row(f=1)",
                                   "mallory")
                stats["attempts"] += 1
                if st == 429:
                    stats["rejected"] += 1
                    if h.get("Retry-After"):
                        stats["retry_after"] += 1
                    # a shed request is nearly free server-side, but
                    # un-paced urllib would turn the loop into a raw
                    # connection flood (accept + thread per request) —
                    # a layer below what tenant quotas meter. Pace like
                    # a client that ignores most of the Retry-After.
                    time.sleep(0.02)
                else:
                    time.sleep(0.005)

        threads = [threading.Thread(target=abuser, daemon=True)
                   for _ in range(2)]
        for th in threads:
            th.start()
        while stats["attempts"] < 50:  # saturate before measuring
            time.sleep(0.005)
        busy = {}
        for t in wb:
            res, busy[t] = run_suite(t)
            assert res == oracle, f"tenant {t} diverged under abuse"
        # trigger evaluation rides timeline samples; force one while
        # the burn state is hot
        coord.health.timeline.sample()
        stop.set()
        for th in threads:
            th.join(timeout=30)

        assert stats["rejected"] > 0, "abuser was never rejected"
        assert stats["retry_after"] > 0, "429s carried no Retry-After"
        for t in wb:
            assert busy[t] <= 1.5 * baseline[t], \
                f"tenant {t} p99 {busy[t]:.1f}ms vs " \
                f"{baseline[t]:.1f}ms no-abuser baseline"
        burn = coord.health.slo.tenant_burn_rates()
        alerting = {r["tenant"] for r in burn if r["alerting"]}
        assert "mallory" in alerting, \
            f"abuser not burning (rows: {burn})"
        assert not (alerting & set(wb)), \
            f"well-behaved tenant burning: {alerting}"
        st, tj, _ = req("/internal/tenants")
        assert st == 200 and tj["enabled"]
        seen = set(tj["tenants"])
        assert set(wb) | {"mallory"} <= seen, seen
        assert tj["tenants"]["mallory"]["rejected"] > 0
        with urllib.request.urlopen(uri + "/metrics",
                                    timeout=30) as resp:
            prom = resp.read().decode()
        assert 'slo_burn_rate{' in prom and 'tenant="mallory"' in prom, \
            "per-tenant burn gauges missing from /metrics"
        bundles = coord.health.flight.summaries()
        assert any(b["trigger"] == "tenant_burn" for b in bundles), \
            f"no tenant_burn flight bundle (got {bundles})"

        for t in wb:
            _emit(f"c18_wb_p99{{tenant={t}}}{SCALED} ({device})",
                  busy[t], "ms", baseline[t] / busy[t],
                  baseline_p99_ms=baseline[t])
        worst = max(wb, key=lambda t: busy[t] / baseline[t])
        _emit(f"c18_noisy_neighbor_wb_p99{SCALED} ({device})",
              busy[worst], "ms", baseline[worst] / busy[worst],
              baseline_p99_ms=baseline[worst],
              abuser_attempts=stats["attempts"],
              abuser_rejected=stats["rejected"],
              tenants_tracked=tj["tracked"])


# ---------------------------------------------------------------------------
# Config 3 — TopK + GroupBy at SSB SF-1 scale (headline, printed last)
# ---------------------------------------------------------------------------

def bench_config3(device: str) -> None:
    from pilosa_tpu.api import API
    from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_SHARD

    rng = np.random.default_rng(3)
    # lineorder SF-1: ~6M rows (scaled down under JAX_PLATFORMS=cpu).
    # SSB-shaped: every lineorder row belongs to exactly ONE year (of 7,
    # d_year 1992-98) and ONE brand (of 1000, p_brand1 MFGR#xxxx) —
    # mutex-distributed like the real dimension join keys, NOT 50%-dense
    # random planes. Loaded through the real import path (mutex bulk
    # import + brand KEY TRANSLATION + existence tracking), not direct
    # plane pokes.
    shards, years, brands = max(2, _n(6)), 7, _n(1000)
    n = shards * SHARD_WIDTH
    year_of = rng.integers(0, years, n)
    brand_of = rng.integers(0, brands, n)
    brand_names = np.array([f"MFGR#{1000 + b}" for b in range(brands)])
    api = API()
    api.create_index("ssb")
    api.create_field("ssb", "year", {"type": "mutex"})
    api.create_field("ssb", "brand", {"type": "mutex", "keys": True})
    cols = np.arange(n, dtype=np.int64)
    t0 = time.perf_counter()
    api.import_bits("ssb", "year", rows=year_of, cols=cols)
    api.import_bits("ssb", "brand", cols=cols,
                    row_keys=brand_names[brand_of])
    load_s = time.perf_counter() - t0
    print(f"bench: c3 SSB-shaped load {n} rows in {load_s:.1f}s "
          f"({n / load_s:,.0f} rows/s incl. key translation)",
          file=sys.stderr)

    q = "GroupBy(Rows(year), Rows(brand), limit=100)TopN(brand, n=10)"
    groups, top = api.query("ssb", q)
    assert len(groups) == 100 and len(top.pairs) == 10
    # oracle-check a few group counts against the generator
    fy = api.holder.index("ssb").field("year")
    fb = api.holder.index("ssb").field("brand")
    for gc in groups[:3]:
        y = gc.group[0].row_id
        name = gc.group[1].row_key or fb.translate.id_to_key[
            gc.group[1].row_id]
        b = int(name.split("#")[1]) - 1000
        want = int(np.sum((year_of == y) & (brand_of == b)))
        assert gc.count == want, (y, b, gc.count, want)
    p50 = _p50_ms(lambda: api.query("ssb", q))

    # host planes for the control + kernel runs, FROM the loaded store
    # (same data both sides)
    ya = {s: np.stack([fy.fragment(s).row_plane(r) for r in range(years)])
          for s in range(shards)}
    brand_ids = sorted(
        set().union(*[fb.fragment(s).existing_rows() for s in range(shards)]))
    ba = {s: np.stack([fb.fragment(s).row_plane(r) for r in brand_ids])
          for s in range(shards)}
    n_brand_rows = len(brand_ids)

    # Kernel-only decomposition: the GroupBy pair-count matmul alone, on
    # device-resident stacked planes (no executor machinery).
    # kernel_ms   = one call incl. dispatch + result fetch (what a single
    #               query pays);
    # amortized   = per-iteration device time from an in-jit loop (1-iter
    #               vs K-iter difference), i.e. the kernel alone — MFU is
    #               computed from this.
    import jax
    import jax.numpy as jnp
    from jax import lax as jlax

    from pilosa_tpu.ops import groupby as G
    y_all = jnp.asarray(np.concatenate([ya[s] for s in range(shards)], axis=1))
    b_all = jnp.asarray(np.concatenate([ba[s] for s in range(shards)], axis=1))
    # pin ONE implementation (pallas on TPU, else the XLA scan) so the
    # single-call and in-jit amortized numbers measure the same kernel
    if G._pallas_eligible(y_all, b_all):
        pair_counts, kernel_kind = G._pair_counts_pallas, "pallas"
    else:
        pair_counts, kernel_kind = G._pair_counts_xla, "xla"
    jax.block_until_ready(pair_counts(y_all, b_all))  # warm
    times = []
    for _ in range(QUERY_ITERS):
        t0 = time.perf_counter()
        np.asarray(pair_counts(y_all, b_all))
        times.append(time.perf_counter() - t0)
    kernel_ms = statistics.median(times) * 1e3

    def _loop_fn(iters):
        @jax.jit
        def f(a, b):
            def body(i, acc):
                return acc + pair_counts(a ^ i.astype(jnp.uint32), b)
            return jlax.fori_loop(
                0, iters, body,
                jnp.zeros((years, n_brand_rows), jnp.int32))
        return f

    def _t(f):
        np.asarray(f(y_all, b_all))  # warm/compile
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(f(y_all, b_all))
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts) * 1e3

    k_iters = 5
    amortized_ms = max(0.001,
                       (_t(_loop_fn(k_iters)) - _t(_loop_fn(1)))
                       / (k_iters - 1))
    # MXU work: C[y, b] = sum_c Y[y,c] * B[b,c] over shards*2^20 bit lanes
    bit_cols = shards * WORDS_PER_SHARD * 32
    flops = 2.0 * years * n_brand_rows * bit_cols
    tflops = flops / (amortized_ms / 1e3) / 1e12
    # int8 MXU peak of this device (the kernel contracts int8 lanes);
    # an unknown device kind raises — no default row. The cpu row is a
    # relative gauge, not a datasheet peak, so no MFU is reported there.
    from pilosa_tpu.obs import devprof

    kind = jax.devices()[0].device_kind
    peak = 0.0 if kind == "cpu" else devprof.peaks_for(kind)[0]

    # control: the best single-host dense algorithm for the same job —
    # blocked BLAS matmul over unpacked bit lanes (strictly faster than
    # the reference's per-pair container walk on this dense layout),
    # plus the TopN recount.
    t0 = time.perf_counter()
    for s in range(shards):
        yl = np.unpackbits(
            ya[s].view(np.uint8), bitorder="little").reshape(years, -1)
        bl = np.unpackbits(
            ba[s].view(np.uint8), bitorder="little").reshape(n_brand_rows, -1)
        np.dot(yl.astype(np.float32), bl.astype(np.float32).T)
        _BYTE_POP[ba[s].view(np.uint8)].sum(axis=-1)
    base_ms = (time.perf_counter() - t0) * 1e3
    nbytes = (years + n_brand_rows) * shards * WORDS_PER_SHARD * 4
    _emit(f"c3_groupby_topk_p50_ssb_sf1_{shards}shards_{years}x{brands}"
          f"{SCALED} ({device})", p50, "ms", base_ms / p50,
          hbm_bytes=nbytes, gbps=nbytes / p50 / 1e6,
          kernel_ms=kernel_ms, kernel_amortized_ms=amortized_ms,
          kernel=kernel_kind, tflops=tflops,
          mfu_pct=(tflops / peak * 100 if peak else 0.0),
          floor_ms=dispatch_floor_ms())


# ---------------------------------------------------------------------------
# Config 19 — elastic serverless (DAX) plane under chaos (dax/)
# ---------------------------------------------------------------------------

def bench_config19(device: str) -> None:
    """Serverless-plane gate: a 3-computer DaxCluster (HTTP serving path:
    scheduler admission + directive-versioned result cache) under mixed
    read/write load while one computer is killed, another silenced, and
    the fleet scales up mid-flight.

    Every write batch is retried until acked, then mirrored to a plain
    single-node API — the oracle. HARD asserts:

    - interleaved reads agree with the oracle throughout the chaos;
    - a restarted computer (RESET wipe behind the controller's back)
      answers the next diff with a resync and is rebuilt by a FULL
      directive (the resync counter must grow) — and its prewarm ran;
    - zero lost writes: a FRESH computer directed over ALL shards of
      the shared writelog replays to a checksum bit-identical to the
      oracle;
    - warm handoff: a freshly-directed node serves cache-miss reads at
      p99 <= 2x the warm fleet's, measured within 5s of its directive.
    """
    import copy
    import shutil

    from pilosa_tpu.api import API
    from pilosa_tpu.dax.computer import Computer
    from pilosa_tpu.dax.directive import Directive, METHOD_FULL, METHOD_RESET
    from pilosa_tpu.dax.harness import DaxCluster
    from pilosa_tpu.obs import metrics as obs_metrics
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    reg = obs_metrics.REGISTRY
    rng = np.random.default_rng(19)
    shards_n, rows_n = 12, 16
    n_sets = _n(4800)
    batch = 8

    cluster = DaxCluster(3, dead_after_s=1.0, snapshot_every=64,
                         serving=True)
    fields = [{"name": "f", "options": {"type": "set"}},
              {"name": "v", "options": {"type": "int"}}]
    cluster.controller.create_table("e", {}, fields=fields)
    oracle = API()
    oracle.create_index("e", {})
    oracle.create_field("e", "f", {"type": "set"})
    oracle.create_field("e", "v", {"type": "int"})

    alive = {0, 1, 2}

    def _beat():
        for i in alive:
            cluster.controller.checkin(cluster.computers[i].node.id)

    def _retry(fn, what, tries=300):
        last = None
        for _ in range(tries):
            try:
                return fn()
            except Exception as exc:  # noqa: BLE001 — the chaos window
                last = exc
                _beat()
                cluster.step()
                time.sleep(0.02)
        raise AssertionError(f"{what} never recovered: {last!r}")

    # -- phase 1: mixed load with a kill, a silence, a scale-up ------------
    cols = rng.integers(0, 4096, n_sets)
    rowv = rng.integers(0, rows_n, n_sets)
    shardv = rng.integers(0, shards_n, n_sets)
    n_batches = n_sets // batch
    kill_at, silence_at, grow_at = (int(n_batches * f)
                                    for f in (0.3, 0.5, 0.7))
    vals_total = 0
    for bi in range(n_batches):
        if bi == kill_at:
            cluster.kill(0)
            alive.discard(0)
        if bi == silence_at:
            cluster.silence(1)
            alive.discard(1)
        if bi == grow_at:
            cluster.scale_up()
            alive.add(len(cluster.computers) - 1)
        lo = bi * batch
        pql = "".join(
            f"Set({int(shardv[i]) * SHARD_WIDTH + int(cols[i])},"
            f" f={int(rowv[i])})" for i in range(lo, lo + batch))
        _retry(lambda: cluster.queryer.query("e", pql), "write batch")
        oracle.query("e", pql)  # mirror ONLY once the cluster acked
        if bi % 12 == 5:  # sprinkle int-value writes through the queryer
            vc = [int(shardv[lo]) * SHARD_WIDTH + k for k in range(12)]
            vv = [int(x) for x in rng.integers(-50, 50, 12)]
            _retry(lambda: cluster.queryer.import_values("e", "v", vc, vv),
                   "value import")
            oracle.import_values("e", "v", cols=vc, values=vv)
            vals_total += 12
        if bi % 10 == 7:  # interleaved read must agree with the oracle
            q = f"Count(Row(f={bi % rows_n}))"
            got = _retry(lambda: cluster.queryer.query("e", q),
                         "read")[0]
            assert got == oracle.query("e", q)[0], (bi, got)
        _beat()
        if bi % 10 == 0:
            cluster.step()

    # -- phase 2: restart-behind-the-controller forces a FULL resync -------
    live = cluster.controller.live_ids()
    victim = next(c for c in cluster.computers if c.node.id in live)
    r0 = reg.value(obs_metrics.METRIC_DAX_FULL_RESYNCS)
    victim.apply_directive(Directive(
        version=0, method=METHOD_RESET, schema=[], assigned=[]).to_json())
    cluster.controller.create_field("e", "aux", {"type": "set"})
    oracle.create_field("e", "aux", {"type": "set"})
    assert reg.value(obs_metrics.METRIC_DAX_FULL_RESYNCS) > r0, \
        "restarted computer was not rebuilt via a FULL resync"
    q = "Count(Row(f=3))"
    assert _retry(lambda: cluster.queryer.query("e", q),
                  "post-resync read")[0] == oracle.query("e", q)[0]

    # -- phase 3: warm handoff — fresh node p99 <= 2x warm, within 5s ------
    def _p99(pool, tag):
        pairs = [(r, s) for s in pool for r in range(2 * rows_n)]
        times = []
        for i in range(min(60, len(pairs))):  # distinct -> all cache MISSES
            r, s = pairs[i]
            t0 = time.perf_counter()
            _retry(lambda: cluster.queryer.query(
                "e", f"Count(Row(f={r}))", shards=[s]), tag)
            times.append((time.perf_counter() - t0) * 1e3)
        return float(np.percentile(times, 99))

    assign = cluster.controller.assignment()
    p99_warm = _p99(sorted({s for (_, s) in assign}), "warm read")
    w0 = reg.value(obs_metrics.METRIC_DAX_PREWARM_STACKS)
    new_shards: list = []
    for _ in range(3):  # jump hash may (rarely) move nothing: grow again
        t_dir = time.perf_counter()
        cluster.scale_up()
        alive.add(len(cluster.computers) - 1)
        new_id = cluster.computers[-1].node.id
        new_shards = sorted(
            s for (_, s), nid in cluster.controller.assignment().items()
            if nid == new_id)
        if new_shards:
            break
    assert new_shards, "scale-up moved no shards after 3 attempts"
    assert reg.value(obs_metrics.METRIC_DAX_PREWARM_STACKS) > w0, \
        "new owner acked without prewarming the hot fields"
    p99_fresh = _p99(new_shards, "fresh read")
    within_s = time.perf_counter() - t_dir
    assert within_s <= 5.0, f"measurement window {within_s:.1f}s > 5s"
    # the 2ms floor keeps the ratio meaningful in the sub-ms HTTP regime
    assert p99_fresh <= 2.0 * max(p99_warm, 2.0), \
        f"fresh node p99 {p99_fresh:.1f}ms vs warm {p99_warm:.1f}ms"
    _emit(f"c19_dax_fresh_node_read_p99{SCALED} ({device})",
          p99_fresh, "ms", p99_warm / max(p99_fresh, 1e-9),
          warm_p99_ms=p99_warm, within_s=round(within_s, 2),
          moved_shards=len(new_shards))

    # -- phase 4: zero-loss gate — replay everything, compare checksums ----
    shards_all = sorted(cluster.controller.shards_of("e"))
    assert len(shards_all) == shards_n, shards_all
    check = Computer("c19-check", cluster.dir)
    out = check.apply_directive(Directive(
        version=1, method=METHOD_FULL,
        schema=copy.deepcopy(cluster.controller.schema),
        assigned=[("e", s) for s in shards_all]).to_json())
    assert out["applied"], out
    got, want = check.api.checksum(), oracle.checksum()
    assert got == want, \
        "writes acked by the elastic fleet were lost: replayed checksum " \
        f"{got!r} != oracle {want!r}"
    _emit(f"c19_dax_elastic_zero_loss{SCALED} ({device})",
          float(n_sets + vals_total), "ops", 1.0,
          shards=shards_n, kills=1, silences=1,
          resyncs=int(reg.value(obs_metrics.METRIC_DAX_FULL_RESYNCS) - r0))
    check.close()
    cluster.close()
    shutil.rmtree(cluster.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# Config 20 — Pallas L0 kernel-plane gate (ops/pallas_util.py)
# ---------------------------------------------------------------------------

def bench_config20(device: str) -> None:
    """Pallas kernel-plane gate: three phases over one fixed workload.

    1. kill switch (``PILOSA_TPU_PALLAS=0``) — run every routed family;
       HARD asserts: zero Pallas dispatches AND zero fallback-counter
       movement (the switch must cost nothing, not even a metric tick).
       These results are the classic oracle.
    2. forced (``PILOSA_TPU_PALLAS=1``; interpret mode off-TPU) — same
       inputs through the Pallas kernels; HARD asserts: bit-identical
       results for EVERY family and a dispatch-counter tick per family.
    3. speedup — p50 classic vs Pallas for the bsi_sum and pair-count
       matmul kernels. On TPU backends HARD assert >= 1.3x; on CPU the
       interpreter is a correctness vehicle, so the ratio is emitted
       ungated.
    """
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.core.fragment import SetFragment
    from pilosa_tpu.obs import metrics as obs_metrics
    from pilosa_tpu.ops import bsi as S
    from pilosa_tpu.ops import groupby as G
    from pilosa_tpu.ops import pallas_util as PU
    from pilosa_tpu.ops import topk as T
    from pilosa_tpu.parallel import mesh

    rng = np.random.default_rng(20)
    words = 512
    nbits = words * 32
    a = rng.integers(0, 1 << 32, size=(8, words), dtype=np.uint32)
    b = rng.integers(0, 1 << 32, size=(16, words), dtype=np.uint32)
    cols = np.unique(rng.integers(0, nbits, size=2000))
    vals = rng.integers(-5000, 5000, size=cols.size)
    depth = max(S.bits_needed(int(vals.min())),
                S.bits_needed(int(vals.max())))
    planes = S.encode_values(cols, vals, depth, words)
    frag_rows = rng.integers(0, 8, size=500)
    frag_cols = rng.integers(0, nbits, size=500)
    tape = (("and", 0, 1),)
    leaves = [jnp.asarray(a[0]), jnp.asarray(a[1])]

    reg = obs_metrics.REGISTRY

    def pallas_counter_totals():
        snap = reg.snapshot()["counters"]
        disp = sum(v for k, v in snap.items()
                   if k.startswith(obs_metrics.METRIC_OPS_PALLAS_DISPATCH))
        fall = sum(v for k, v in snap.items()
                   if k.startswith(obs_metrics.METRIC_OPS_PALLAS_FALLBACK))
        return disp, fall

    def families(label):
        """One result per routed family, all host-side values."""
        out = {}
        out["pair_counts"] = np.asarray(G.pair_counts(a, b))
        out["bsi_sum"] = S.bsi_sum(planes, planes[S.EXISTS])
        out["bsi_compare"] = np.asarray(
            S.bsi_compare(planes, S.BETWEEN, -100, 100))
        tc, ti = T.top_rows(a, 5)
        out["topn"] = (np.asarray(tc), np.asarray(ti))
        frag = SetFragment(0, words=words)
        out["ingest_scatter"] = (
            frag.set_many(frag_rows, frag_cols),
            {r: frag.row_plane(r).copy() for r in frag.existing_rows()})
        fn = mesh.compile_tape_count(tape, False, words)
        out["tape_count"] = (int(fn(*leaves)),
                             bool(getattr(fn, "pallas_terminal", False)))
        return out

    saved = os.environ.get("PILOSA_TPU_PALLAS")
    PU.reset_failures()
    try:
        # -- phase 1: kill switch — classic oracle, zero-overhead gate -----
        os.environ["PILOSA_TPU_PALLAS"] = "0"
        d0, f0 = pallas_counter_totals()
        oracle = families("killswitch")
        d1, f1 = pallas_counter_totals()
        assert d1 == d0, "kill switch still dispatched a pallas kernel"
        assert f1 == f0, "kill switch ticked the fallback counter"
        assert oracle["tape_count"][1] is False, \
            "kill switch compiled a pallas tape terminal"

        # -- phase 2: forced — bit-identity + dispatch accounting ----------
        os.environ["PILOSA_TPU_PALLAS"] = "1"
        got = families("forced")
        d2, _ = pallas_counter_totals()
        assert d2 >= d1 + 5, \
            f"forced phase dispatched {d2 - d1} pallas kernels, want >=5"
        assert got["tape_count"][1] is True, \
            "forced phase did not compile the pallas tape terminal"
        np.testing.assert_array_equal(got["pair_counts"],
                                      oracle["pair_counts"])
        assert got["bsi_sum"] == oracle["bsi_sum"]
        np.testing.assert_array_equal(got["bsi_compare"],
                                      oracle["bsi_compare"])
        np.testing.assert_array_equal(got["topn"][0], oracle["topn"][0])
        assert got["ingest_scatter"][0] == oracle["ingest_scatter"][0]
        for r, plane in oracle["ingest_scatter"][1].items():
            np.testing.assert_array_equal(
                got["ingest_scatter"][1][r], plane)
        assert got["tape_count"][0] == oracle["tape_count"][0]
        verified = 6

        # -- phase 3: speedup (hard-gated on TPU only) ---------------------
        wide = rng.integers(0, 1 << 32, size=(64, _n(32768)),
                            dtype=np.uint32)
        filt = wide[0]

        def classic():
            G._pair_counts_xla(wide[:8], wide)
            S._plane_popcounts_xla(
                jnp.asarray(planes), jnp.asarray(planes[S.EXISTS]))

        def pallas():
            G.pair_counts(wide[:8], wide)
            S.bsi_plane_popcounts(planes, planes[S.EXISTS])

        on_tpu = jax.devices()[0].platform == "tpu"
        if on_tpu:
            classic_ms = _p50_ms(classic)
            pallas_ms = _p50_ms(pallas)
            speedup = classic_ms / max(pallas_ms, 1e-9)
            assert speedup >= 1.3, \
                f"pallas bsi_sum/pair_counts speedup {speedup:.2f}x < 1.3x"
        else:
            # interpret mode is a correctness vehicle, not a fast path:
            # time one round trip each so the ratio is visible, ungated
            t0 = time.perf_counter()
            classic()
            classic_ms = (time.perf_counter() - t0) * 1e3
            t0 = time.perf_counter()
            pallas()
            pallas_ms = (time.perf_counter() - t0) * 1e3
            speedup = classic_ms / max(pallas_ms, 1e-9)
        del filt
    finally:
        if saved is None:
            os.environ.pop("PILOSA_TPU_PALLAS", None)
        else:
            os.environ["PILOSA_TPU_PALLAS"] = saved
        PU.reset_failures()

    _emit(f"c20_pallas_parity{SCALED} ({device})",
          float(verified), "families", 1.0,
          dispatches=int(d2 - d1), killswitch_dispatches=int(d1 - d0),
          classic_ms=classic_ms, pallas_ms=pallas_ms,
          speedup=speedup, speedup_gated=on_tpu)


def bench_config21(device: str) -> None:
    """Compressed-residency gate: three phases over one sparse workload
    (clustered rows — each row lights up 1-2 word tiles of a wide block,
    the high-cardinality shape the DeviceBudget LRU thrashes on when
    every block is dense).

    1. kill switch (``PILOSA_TPU_COMPRESS=0``) — HARD asserts:
       ``maybe_compress`` returns None, zero compress-metric movement,
       zero ``ctile_count`` dispatches. These results are the dense
       oracle.
    2. forced (``PILOSA_TPU_COMPRESS=1``) — same blocks compressed;
       HARD asserts: bit-identical decode, row_counts (plain and
       filtered) and BSI compare vs the oracle, AND the headline: >= 10x
       resident rows under the same DeviceBudget byte cap.
    3. scan p50 — tile-skipping compressed scan vs the dense scan on the
       same sparse rows. On TPU HARD assert no worse (>= 1.0x); on CPU
       the ratio is emitted ungated (interpret/XLA-gather costs differ).
    """
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.core import stacked as stx
    from pilosa_tpu.obs import metrics as obs_metrics
    from pilosa_tpu.ops import bitmap as B
    from pilosa_tpu.ops import bsi as S
    from pilosa_tpu.ops import ctiles as C
    from pilosa_tpu.ops import pallas_util as PU

    rng = np.random.default_rng(21)
    rows = _n(256)
    words = 1 << 14  # unscaled: the per-row width is the compression axis
    host = np.zeros((rows, words), dtype=np.uint32)
    t = C.tile_words(words)
    for r in range(rows):
        # 1-2 clustered runs per row, each within one tile
        for _ in range(int(rng.integers(1, 3))):
            tile = int(rng.integers(0, words // t))
            lo = tile * t + int(rng.integers(0, t - 16))
            n = int(rng.integers(4, 16))
            host[r, lo:lo + n] = rng.integers(1, 1 << 32, n,
                                              dtype=np.uint32)
    filt = rng.integers(0, 1 << 32, words, dtype=np.uint32)
    depth = 6
    bcols = np.unique(rng.integers(0, words * 32, 2000))
    bvals = rng.integers(-30, 30, bcols.size)
    bsi_host = np.asarray(S.encode_values(bcols, bvals, depth, words))

    reg = obs_metrics.REGISTRY

    def compress_series():
        snap = reg.snapshot()
        return {k: v for section in ("counters", "gauges")
                for k, v in snap[section].items()
                if k.startswith("device_compress")}

    def ctile_dispatches():
        snap = reg.snapshot()["counters"]
        return sum(v for k, v in snap.items()
                   if k.startswith(obs_metrics.METRIC_OPS_PALLAS_DISPATCH)
                   and "ctile" in k)

    saved = os.environ.get("PILOSA_TPU_COMPRESS")
    PU.reset_failures()
    try:
        # -- phase 1: kill switch — dense oracle, zero-overhead gate -------
        os.environ["PILOSA_TPU_COMPRESS"] = "0"
        series0 = compress_series()
        d0 = ctile_dispatches()
        assert C.maybe_compress(host, kind="set") is None
        assert C.maybe_compress(bsi_host, kind="bsi") is None
        dense = jnp.asarray(host)
        oracle_counts = np.asarray(B.row_counts(dense))
        oracle_filt = np.asarray(B.row_counts(dense, jnp.asarray(filt)))
        oracle_cmp = np.asarray(S.bsi_compare(
            jnp.asarray(bsi_host), S.BETWEEN, -10, 10))
        assert compress_series() == series0, \
            "kill switch moved a compress metric"
        assert ctile_dispatches() == d0, \
            "kill switch dispatched the compressed-scan kernel"

        # -- phase 2: forced — bit-identity + the 10x residency headline ---
        os.environ["PILOSA_TPU_COMPRESS"] = "1"
        cb = C.maybe_compress(host, kind="set")
        bcb = C.maybe_compress(bsi_host, kind="bsi")
        assert cb is not None and bcb is not None
        np.testing.assert_array_equal(np.asarray(cb.decode()), host)
        np.testing.assert_array_equal(
            np.asarray(cb.row_counts()), oracle_counts)
        np.testing.assert_array_equal(
            np.asarray(cb.row_counts(jnp.asarray(filt))), oracle_filt)
        np.testing.assert_array_equal(
            np.asarray(C.bsi_compare_compressed(bcb, S.BETWEEN, -10, 10)),
            oracle_cmp)
        # residency: rows resident under the SAME DeviceBudget byte cap
        cap = stx.BUDGET.cap
        dense_rows_resident = cap // (words * 4)
        comp_rows_resident = cap * rows // max(cb.nbytes, 1)
        rows_ratio = comp_rows_resident / max(dense_rows_resident, 1)
        assert rows_ratio >= 10.0, (
            f"compressed residency {rows_ratio:.1f}x < 10x "
            f"(stored {cb.nbytes} vs dense {cb.dense_nbytes})")

        # -- phase 3: scan p50, compressed vs dense (gated on TPU) ---------
        jfilt = jnp.asarray(filt)

        def dense_scan():
            jax.block_until_ready(B.row_counts(dense, jfilt))

        def compressed_scan():
            jax.block_until_ready(cb.row_counts(jfilt))

        on_tpu = jax.devices()[0].platform == "tpu"
        dense_ms = _p50_ms(dense_scan)
        comp_ms = _p50_ms(compressed_scan)
        scan_ratio = dense_ms / max(comp_ms, 1e-9)
        if on_tpu:
            assert scan_ratio >= 1.0, (
                f"compressed scan {comp_ms:.3f}ms slower than dense "
                f"{dense_ms:.3f}ms on sparse rows")
    finally:
        if saved is None:
            os.environ.pop("PILOSA_TPU_COMPRESS", None)
        else:
            os.environ["PILOSA_TPU_COMPRESS"] = saved
        PU.reset_failures()

    _emit(f"c21_compress_resident_rows{SCALED} ({device})",
          float(rows_ratio), "x", float(rows_ratio),
          stored_bytes=int(cb.nbytes), dense_bytes=int(cb.dense_nbytes),
          bytes_ratio=float(cb.dense_nbytes) / max(cb.nbytes, 1),
          dense_scan_ms=dense_ms, compressed_scan_ms=comp_ms,
          scan_ratio=scan_ratio, scan_gated=on_tpu)


def bench_config22(device: str) -> None:
    """Open-loop standing-load soak + graceful-degradation gate.

    A 3-node LocalCluster (replica 2, gossip invalidation) under a
    seeded FaultPlan, driven by the coordinated-omission-free loadgen
    harness (pilosa_tpu/loadgen/): every op has an *intended* send time
    and its latency is measured from that, so backlog shows up as
    latency — never as a silently dropped sample.

    1. degrade OFF — a mixed burst; HARD asserts: no degrade_* series
       in /metrics, zero stale serves (off means free).
    2. standing soak (mixed scenario traffic, 10^5 synthetic tenants)
       with chaos + membership churn mid-run: fault-plan delays/drops,
       a node paused and unpaused. HARD asserts: SLO burn stays below
       the shed edge, the ladder never passes SHED_BATCH, every 429
       carried Retry-After.
    3. write oracle — every bulk write the cluster ACKED (plus redriven
       un-acked writes after heal) must be bit-identical to a no-chaos
       shadow copy, row by row.
    4. overload ramp to >2x measured capacity; HARD asserts: the ladder
       engages IN ORDER (batch shed strictly before interactive shed,
       an intermediate level observed before SATURATED), brownout
       serves stale-tagged reads, interactive good-put under overload
       stays >= 50% of the pre-overload baseline, and the ladder
       recovers to NORMAL after the load stops.
    5. bounded-table caps: tenant registry, scheduler vtime, result
       caches, compiled-program/mask/zeros pools, flight ring — all at
       or under their caps after the whole soak.
    """
    import json as _json
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from pilosa_tpu.cluster.harness import LocalCluster
    from pilosa_tpu.cluster.resilience import FaultPlan
    from pilosa_tpu.loadgen import (
        ChaosSchedule, KIND_BULK_IMPORT, KIND_INTERACTIVE, KIND_SQL,
        OpenLoopDriver, ScenarioMix, SyntheticTenants,
    )

    fault_seed = int(os.environ.get("PILOSA_TPU_FAULT_SEED", "22"))
    rng = np.random.default_rng(22)
    n = _n(200_000)
    base_rows = rng.integers(0, 40, n)
    base_cols = rng.integers(0, 1 << 22, n)
    g_rows = rng.integers(0, 200, n)

    plan = FaultPlan(seed=fault_seed)

    with tempfile.TemporaryDirectory(prefix="bench22") as tmp, \
            LocalCluster(3, replica_n=2, base_path=tmp,
                         fault_plan=plan) as cluster:
        coord = cluster.coordinator
        uri = coord.node.uri
        cluster.enable_gossip()
        cluster.enable_tenants()
        for node in cluster.nodes:
            node.enable_scheduler(max_queue=32, adaptive_window=True)
            node.enable_cache()
        # short SLO fast window: burn must reflect *current* conditions
        # so the ladder can step back down after the overload clears (a
        # 300s window would pin fast_burn high for minutes post-burst)
        cluster.enable_health(interval_ms=100, slo_fast_window_s=5.0,
                              start=True)

        def req(path, data=None, tenant=None, method=None):
            r = urllib.request.Request(uri + path, data=data,
                                       method=method)
            if tenant is not None:
                r.add_header("X-Tenant", tenant)
            try:
                with urllib.request.urlopen(r, timeout=60) as resp:
                    return (resp.status, _json.loads(resp.read()),
                            dict(resp.headers))
            except urllib.error.HTTPError as e:
                return e.code, _json.loads(e.read() or b"{}"), \
                    dict(e.headers)

        st, body, _ = req("/index/soak", b'{"options": {}}')
        assert st == 200, body
        for fname in ("f", "g"):
            st, body, _ = req(f"/index/soak/field/{fname}",
                              b'{"options": {"type": "set"}}')
            assert st == 200, body
        coord.import_bits("soak", "f", rows=base_rows.tolist(),
                          cols=base_cols.tolist())
        coord.import_bits("soak", "g", rows=g_rows.tolist(),
                          cols=base_cols.tolist())
        # the no-chaos shadow: row -> every column the cluster ever ACKs
        oracle = {r: set() for r in range(40)}
        for r, cc in zip(base_rows.tolist(), base_cols.tolist()):
            oracle[r].add(cc)

        st, _, _ = req("/index/streamidx", b'{"options": {}}')
        assert st == 200
        svc = coord.api.enable_stream("streamidx", batch_rows=64,
                                      queue_depth=4,
                                      max_backlog_rows=2048)
        svc.start(0.02)

        # ---- phase 1: degrade off is free --------------------------------
        for i in range(10):
            st, body, _ = req("/index/soak/query",
                              f"Count(Row(f={i % 40}))".encode())
            assert st == 200, body
        st, body, _ = req("/sql", b"SELECT COUNT(*) FROM soak")
        assert st == 200, body
        st, metrics_text, _hdr = 0, "", None
        with urllib.request.urlopen(uri + "/metrics", timeout=30) as resp:
            metrics_text = resp.read().decode()
        assert "degrade_" not in metrics_text, \
            "degrade metrics moved while the plane was disabled"
        for node in cluster.nodes:
            assert node.cache.stats()["stale_serves"] == 0
        zero_cost_ok = True

        # warm every query shape the soak uses (cold XLA compiles burn
        # minutes of SLO budget in one hit; real deployments warm up
        # before enabling burn-driven shedding, and so does this gate)
        for a, b in ((1, 2), (3, 4)):
            req("/index/soak/query",
                f"Count(Intersect(Row(f={a}), Row(g={b})))".encode())
        req("/index/soak/query", b"Row(f=1)")

        # uncached-query capacity: unique Intersect combos, sequential
        t0 = time.perf_counter()
        cap_iters = 24
        for i in range(cap_iters):
            st, body, _ = req(
                "/index/soak/query",
                f"Count(Intersect(Row(f={i % 40}), "
                f"Row(g={100 + i})))".encode())
            assert st == 200, body
        qps_base = cap_iters / max(time.perf_counter() - t0, 1e-6)

        # concurrent capacity: with many requests in flight the cluster
        # absorbs far more than the sequential rate (fan-out overlap), so
        # an overload ramp scaled off qps_base never fills the admission
        # window. Measure what 16 closed-loop probes sustain and scale
        # the ramp off that instead. Each probe owns a g-stripe so no
        # combo repeats (cache hits would inflate the estimate).
        cap_out = {}

        def _cap_worker(tid, stop_at):
            n = 0
            while time.perf_counter() < stop_at:
                st, _b, _h = req(
                    "/index/soak/query",
                    f"Count(Intersect(Row(f={n % 40}), "
                    f"Row(g={tid})))".encode())
                if st == 200:
                    n += 1
            cap_out[tid] = n

        stop_at = time.perf_counter() + 1.2
        cap_threads = [threading.Thread(target=_cap_worker,
                                        args=(t, stop_at))
                       for t in range(16)]
        t0 = time.perf_counter()
        for th in cap_threads:
            th.start()
        for th in cap_threads:
            th.join()
        qps_conc = max(qps_base, sum(cap_out.values())
                       / max(time.perf_counter() - t0, 1e-6))

        cluster.enable_degrade(
            queue_shed=0.30, queue_brownout=0.55, queue_saturate=0.80,
            burn_shed=60.0, burn_brownout=90.0, burn_saturate=130.0,
            miss_rate_brownout=1e9, eviction_rate_shed=1e9,
            exit_ratio=0.6, up_hold=1, down_hold=2, min_dwell_s=0.25)

        # ---- shared op bindings + shed bookkeeping -----------------------
        lock = threading.Lock()
        first_degrade_shed = {}  # priority -> monotonic ts of first shed
        missing_retry_after = [0]
        unacked = []  # (row, col) bulk writes the cluster never ACKed
        run_t0 = [time.monotonic()]

        def note_shed(kind, body, headers):
            msg = str(body.get("error", ""))
            if "Retry-After" not in headers:
                with lock:
                    missing_retry_after[0] += 1
            if "degrade" in msg:
                pri = ("batch" if "batch" in msg else "interactive")
                with lock:
                    first_degrade_shed.setdefault(pri, time.monotonic())

        def execute(op):
            oid = op.op_id
            if op.kind == KIND_INTERACTIVE:
                st, body, hdr = req("/index/soak/query",
                                    f"Count(Row(f={oid % 40}))".encode(),
                                    tenant=op.tenant)
                if st == 200:
                    return {"outcome": "ok",
                            "stale": bool(body.get("stale"))}
                if st == 429:
                    note_shed(op.kind, body, hdr)
                    return "shed"
                return "error"
            if op.kind == KIND_SQL:
                st, body, hdr = req("/sql",
                                    b"SELECT COUNT(*) FROM soak",
                                    tenant=op.tenant)
                if st == 200:
                    return {"outcome": "ok",
                            "stale": bool(body.get("stale"))}
                if st == 429:
                    note_shed(op.kind, body, hdr)
                    return "shed"
                return "error"
            if op.kind == KIND_BULK_IMPORT:
                row, col = oid % 40, 4_200_000 + oid
                payload = _json.dumps({"field": "f", "rows": [row],
                                       "cols": [col]}).encode()
                st, body, hdr = req("/index/soak/import", payload,
                                    tenant=op.tenant)
                if st == 200:
                    with lock:
                        oracle[row].add(col)
                    return "ok"
                with lock:
                    unacked.append((row, col))
                if st == 429:
                    note_shed(op.kind, body, hdr)
                    return "shed"
                return "error"
            if op.kind == "stream_push":
                svc.push([{"id": 1000 + oid}])  # AdmissionError -> shed
                return "ok"
            # quota churn: a deep-tail tenant touches its registry row
            st, body, hdr = req("/index/soak/query", b"Count(Row(f=0))",
                                tenant=f"t{(oid * 7919) % 100_000:07d}")
            if st == 429:
                note_shed("interactive", body, hdr)
                return "shed"
            return "ok" if st == 200 else "error"

        # heavier uncached combos for the overload ramp — the counter is
        # global across sub-phases so no combo ever repeats (a repeat
        # would cache-hit and carry no queue pressure)
        ramp_i = itertools.count()

        def execute_ramp(op):
            if op.kind == KIND_INTERACTIVE:
                i = next(ramp_i)
                if i % 3 == 0:
                    # hot cached read: this is the traffic brownout keeps
                    # alive (stale-served straight from cache even at
                    # SATURATED) while cold queries below are shed
                    st, body, hdr = req("/sql",
                                        b"SELECT COUNT(*) FROM soak",
                                        tenant=op.tenant)
                else:
                    a, b = i % 40, 25 + (i // 40) % 175
                    st, body, hdr = req(
                        "/index/soak/query",
                        f"Count(Intersect(Row(f={a}), "
                        f"Row(g={b})))".encode(),
                        tenant=op.tenant)
                if st == 200:
                    return {"outcome": "ok",
                            "stale": bool(body.get("stale"))}
                if st == 429:
                    note_shed(op.kind, body, hdr)
                    return "shed"
                return "error"
            return execute(op)

        # ---- degrade-state poller (runs across soak + ramp) --------------
        poll_stop = threading.Event()
        poll_samples = []  # (monotonic_ts, level, fast_burn, queue_frac)
        stale_seen = [False]
        stale_probe_col = [5_000_000]

        def poll_loop():
            while not poll_stop.is_set():
                try:
                    with urllib.request.urlopen(uri + "/internal/degrade",
                                                timeout=5) as resp:
                        d = _json.loads(resp.read())
                    sig = d.get("signals", {})
                    poll_samples.append(
                        (time.monotonic(), int(d.get("level", 0)),
                         float(sig.get("fast_burn", 0.0)),
                         float(sig.get("queue_frac", 0.0))))
                    if d.get("level", 0) >= 2 and not stale_seen[0]:
                        # brownout: move the fingerprint (direct write:
                        # the HTTP surface sheds batch) and re-read a
                        # cached entry -> must come back tagged stale
                        stale_probe_col[0] += 1
                        coord.import_bits("soak", "f", rows=[0],
                                          cols=[stale_probe_col[0]])
                        oracle[0].add(stale_probe_col[0])
                        st, body, _ = req("/sql",
                                          b"SELECT COUNT(*) FROM soak")
                        if st == 200 and body.get("stale"):
                            stale_seen[0] = True
                except Exception:
                    pass
                poll_stop.wait(0.04)

        poller = threading.Thread(target=poll_loop, daemon=True)
        poller.start()

        # ---- phase 2: standing soak with chaos + membership churn --------
        standing_rate = min(60.0, max(8.0, 0.35 * qps_base))
        standing_s = 6.0
        chaos = (ChaosSchedule(plan=plan, cluster=cluster)
                 .delay(0.1 * standing_s, "node1", 0.002, prob=0.3,
                        op="query")
                 .drop(0.25 * standing_s, "node2", prob=0.1, op="query")
                 .heal(0.45 * standing_s)
                 .pause(0.50 * standing_s, 2)
                 .unpause(0.75 * standing_s, 2))
        tenants = SyntheticTenants(100_000, seed=22)
        driver = OpenLoopDriver(execute, rate_per_s=standing_rate,
                                duration_s=standing_s, tenants=tenants,
                                seed=fault_seed, arrivals="poisson",
                                max_workers=16, chaos=chaos)
        soak_t0 = time.monotonic()
        rep_std = driver.run()
        soak_t1 = time.monotonic()
        plan.heal()

        std_window = [s for s in poll_samples
                      if soak_t0 <= s[0] <= soak_t1]
        std_max_level = max((s[1] for s in std_window), default=0)
        std_max_burn = max((s[2] for s in std_window), default=0.0)
        assert std_max_level < 2, (
            f"standing load should not pass SHED_BATCH "
            f"(saw level {std_max_level})")
        assert std_max_burn < 60.0, (
            f"SLO fast burn unbounded under standing load: "
            f"{std_max_burn:.1f}x")
        ok_frac = rep_std.ok / max(rep_std.total, 1)
        assert ok_frac >= 0.5, rep_std.summary()
        goodput_std = rep_std.count("ok", kind=KIND_INTERACTIVE) \
            / standing_s
        p99_std_ms = rep_std.latency_quantile(
            0.99, kind=KIND_INTERACTIVE) * 1e3

        # ---- phase 3: redrive un-acked writes, verify the oracle ---------
        deadline = time.monotonic() + 25.0
        while time.monotonic() < deadline:
            if poll_samples and poll_samples[-1][1] == 0:
                break
            time.sleep(0.1)
        with lock:
            pending = list(unacked)
            unacked.clear()
        for row, col in pending:
            payload = _json.dumps({"field": "f", "rows": [row],
                                   "cols": [col]}).encode()
            acked = False
            for _ in range(80):
                st, body, hdr = req("/index/soak/import", payload)
                if st == 200:
                    acked = True
                    with lock:
                        oracle[row].add(col)
                    break
                wait = hdr.get("Retry-After")
                time.sleep(min(0.5, float(wait) if wait else 0.1))
            assert acked, f"write ({row},{col}) never ACKed after heal"
        for row in range(40):
            st, body, _ = req("/index/soak/query",
                              f"Row(f={row})".encode())
            assert st == 200 and not body.get("stale"), body
            got = set(body["results"][0]["columns"])
            assert got == oracle[row], (
                f"row {row}: cluster has {len(got)} cols, oracle "
                f"{len(oracle[row])} (diff "
                f"{len(got ^ oracle[row])}) — acked writes lost or "
                f"phantom writes appeared")

        # ---- phase 4: overload ramp — ladder order + brownout + recovery -
        ramp_mix = ScenarioMix({KIND_INTERACTIVE: 0.8,
                                KIND_BULK_IMPORT: 0.2})
        ramp_reps = []
        ramp_t0 = time.monotonic()
        for factor, dur in ((0.7, 2.0), (1.3, 2.0), (2.4, 2.5)):
            d = OpenLoopDriver(execute_ramp,
                               rate_per_s=max(20.0, factor * qps_conc),
                               duration_s=dur, mix=ramp_mix,
                               tenants=tenants, seed=fault_seed + 1,
                               arrivals="uniform", max_workers=32)
            ramp_reps.append(d.run())
        ramp_t1 = time.monotonic()

        ramp_window = [s for s in poll_samples
                       if ramp_t0 <= s[0] <= ramp_t1 + 1.0]
        max_level = max((s[1] for s in ramp_window), default=0)
        assert max_level == 3, (
            f"2.4x overload never saturated the ladder "
            f"(max level {max_level}; qps_conc {qps_conc:.0f}/s)")
        t_sat = min(s[0] for s in ramp_window if s[1] == 3)
        assert any(s[0] < t_sat and s[1] in (1, 2)
                   for s in ramp_window), \
            "ladder jumped to SATURATED without passing SHED_BATCH/" \
            "BROWNOUT"
        with lock:
            t_batch = first_degrade_shed.get("batch")
            t_inter = first_degrade_shed.get("interactive")
        assert t_batch is not None, "no batch work was ever shed"
        assert t_inter is not None, "saturation never shed interactive"
        assert t_batch < t_inter, (
            "ladder order violated: interactive shed before batch")
        assert missing_retry_after[0] == 0, (
            f"{missing_retry_after[0]} 429s lacked Retry-After")
        assert stale_seen[0] or any(r.stale for r in ramp_reps), \
            "brownout never served a stale-tagged read"
        sat_rep = ramp_reps[-1]
        goodput_sat = sat_rep.count("ok", kind=KIND_INTERACTIVE) / 2.5
        assert goodput_sat >= 0.5 * goodput_std, (
            f"good-put collapsed under overload: {goodput_sat:.1f}/s "
            f"vs pre-overload {goodput_std:.1f}/s")

        deadline = time.monotonic() + 25.0
        recovered = False
        while time.monotonic() < deadline:
            if poll_samples and poll_samples[-1][1] == 0:
                recovered = True
                break
            time.sleep(0.1)
        assert recovered, "ladder never recovered to NORMAL after load"
        poll_stop.set()
        poller.join(timeout=5)

        # ---- phase 5: every bounded table at or under its cap ------------
        from pilosa_tpu.ops import bitmap as _bm
        from pilosa_tpu.pql import executor as _pqlx
        from pilosa_tpu.pql import programs as _progs

        for node in cluster.nodes:
            sched = node.scheduler
            assert len(sched._tenant_vtime) <= 256
            cs = node.cache.stats()
            assert cs["entries"] <= node.cache.max_entries
        reg = coord.tenants
        assert len(reg._stats) <= reg.max_tracked + 1, (
            f"tenant registry unbounded: {len(reg._stats)} rows")
        assert len(_progs._PROGRAMS) <= _progs._PROGRAMS_CAP
        assert len(_pqlx._MASK_PLANES) <= _pqlx._MASK_CAP
        assert len(_bm._DEVICE_ZEROS) <= _bm._DEVICE_ZEROS_CAP
        flight = coord.api.health.flight
        assert len(flight.summaries()) <= 16
        deg = coord.degrade
        probe = deg.probe()
        assert probe["transitions"] >= 2

        coord.api.disable_stream()

    burn_headroom = 60.0 / max(std_max_burn, 0.01)
    _emit(f"c22_soak_goodput{SCALED} ({device})",
          float(goodput_std), "ops/s", float(goodput_std),
          zero_cost_off=zero_cost_ok, standing_rate=standing_rate,
          qps_base=qps_base, ok=rep_std.ok, shed=rep_std.shed,
          errors=rep_std.errors, total=rep_std.total,
          sat_goodput=goodput_sat, transitions=probe["transitions"],
          fault_seed=fault_seed)
    _emit(f"c22_soak_p99_intended{SCALED} ({device})",
          float(p99_std_ms), "ms", float(p99_std_ms),
          p50_ms=rep_std.latency_quantile(
              0.50, kind=KIND_INTERACTIVE) * 1e3,
          open_loop=True, coordinated_omission_free=True)
    _emit(f"c22_soak_burn_headroom{SCALED} ({device})",
          float(burn_headroom), "x", float(burn_headroom),
          max_fast_burn=std_max_burn, max_level_standing=std_max_level,
          max_level_ramp=max_level,
          stale_served=bool(stale_seen[0]
                            or any(r.stale for r in ramp_reps)))


def bench_config23(device: str) -> None:
    """Star Schema Benchmark over the bitwise semi-join plane.

    Loads a seeded SSB dataset (lineorder + date/customer/supplier/
    part) and runs all 13 queries Q1.1-Q4.3 three ways, gating each:

    1. single node, semi-join plane ON: every query bit-identical to
       the independent numpy oracle (HARD assert, row multisets plus
       ORDER BY key order),
    2. 3-node LocalCluster under a seeded FaultPlan: same 13 queries,
       same bit-identity gate — dim bitmap broadcast + fan-out legs
       must not change a single row,
    3. semi-join vs PILOSA_TPU_SEMIJOIN=0 (the hash-join fallback,
       i.e. the materialized-loop baseline) on the Q2/Q3 flights:
       HARD assert p50 semi <= p50 hash / 2 (the >=2x claim),
    4. zero extra cost when no JOIN: a single-table aggregate must not
       touch the join plane at all (sql_join_* counters frozen).
    """
    import statistics
    import tempfile

    from pilosa_tpu.api import API
    from pilosa_tpu.cluster.harness import LocalCluster
    from pilosa_tpu.cluster.resilience import FaultPlan
    from pilosa_tpu.loadgen import ssb
    from pilosa_tpu.obs import metrics as M

    # 15k lineorder rows is the smallest scale where host-side hash-join
    # work dominates fixed per-query cost (below it the >=2x comparison
    # measures planner overhead, not the join strategies)
    data = ssb.generate(max(_n(120_000), 15_000), seed=7)
    fault_seed = int(os.environ.get("PILOSA_TPU_FAULT_SEED", "23"))

    # -- 1. single node: all 13 queries vs the oracle -------------------
    api = API()
    t0 = time.perf_counter()
    ssb.load(lambda q: api.sql(q), data)
    load_s = time.perf_counter() - t0
    oracles = {}
    for qid, q in ssb.QUERIES.items():
        oracles[qid] = ssb.oracle(data, qid)
        err = ssb.verify(data, qid, api.sql(q).data,
                         expected=oracles[qid])
        assert err is None, f"single-node {err}"

    # -- 4. zero extra cost when no JOIN --------------------------------
    def _join_counters():
        c = M.REGISTRY.snapshot()["counters"]
        return tuple(c.get(k, 0) for k in
                     ("sql_join_queries_total", "sql_join_fallback_total"))

    before = _join_counters()
    api.sql("SELECT d_year, COUNT(*) FROM ssb_date GROUP BY d_year")
    api.sql("SELECT SUM(lo_revenue) FROM lineorder WHERE lo_discount = 3")
    assert _join_counters() == before, \
        "no-JOIN queries touched the join plane"

    # -- 3. semi-join vs hash-fallback p50 on Q2/Q3 ---------------------
    flights = [q for q in ssb.QUERIES if q.startswith(("Q2", "Q3"))]

    def _p50(qid):
        times = []
        for _ in range(QUERY_ITERS):
            t0 = time.perf_counter()
            api.sql(ssb.QUERIES[qid])
            times.append(time.perf_counter() - t0)
        return statistics.median(times) * 1e3

    semi_p50, hash_p50 = {}, {}
    for qid in flights:
        api.sql(ssb.QUERIES[qid])  # warm compile caches
        semi_p50[qid] = _p50(qid)
    os.environ["PILOSA_TPU_SEMIJOIN"] = "0"
    try:
        for qid in flights:
            api.sql(ssb.QUERIES[qid])
            hash_p50[qid] = _p50(qid)
    finally:
        del os.environ["PILOSA_TPU_SEMIJOIN"]
    speedups = {q: hash_p50[q] / max(semi_p50[q], 1e-6) for q in flights}
    worst = min(speedups, key=speedups.get)
    assert speedups[worst] >= 2.0, (
        f"semi-join p50 speedup on {worst} is {speedups[worst]:.2f}x "
        f"(semi={semi_p50[worst]:.2f}ms hash={hash_p50[worst]:.2f}ms), "
        "want >=2x on every Q2/Q3 flight")

    # -- 2. 3-node cluster under faults: same bit-identity gate ---------
    plan = FaultPlan(seed=fault_seed)
    with tempfile.TemporaryDirectory(prefix="bench23") as tmp, \
            LocalCluster(3, replica_n=2, base_path=tmp,
                         fault_plan=plan) as cluster:
        coord = cluster.coordinator
        ssb.load(lambda q: coord.sql(q), data)
        for qid, q in ssb.QUERIES.items():
            err = ssb.verify(data, qid, coord.sql(q).data,
                             expected=oracles[qid])
            assert err is None, f"3-node {err}"

    snap = M.REGISTRY.snapshot()["counters"]
    _emit(f"c23_ssb_q21_semi_p50{SCALED} ({device})",
          float(semi_p50["Q2.1"]), "ms", float(semi_p50["Q2.1"]),
          hash_p50_ms=hash_p50["Q2.1"], rows=len(data.lineorder["_id"]),
          load_s=load_s)
    _emit(f"c23_ssb_q31_semi_p50{SCALED} ({device})",
          float(semi_p50["Q3.1"]), "ms", float(semi_p50["Q3.1"]),
          hash_p50_ms=hash_p50["Q3.1"])
    _emit(f"c23_ssb_semi_speedup{SCALED} ({device})",
          float(speedups[worst]), "x", float(speedups[worst]),
          worst_flight=worst, queries_verified=len(ssb.QUERIES),
          cluster_verified=True, fault_seed=fault_seed,
          join_queries=int(snap.get("sql_join_queries_total", 0)),
          join_fallbacks=int(snap.get("sql_join_fallback_total", 0)),
          broadcast_bytes=int(
              snap.get("sql_join_broadcast_bytes_total", 0)))


_CONFIGS = {
    "1": bench_config1,
    "2": bench_config2,
    "4": bench_config4,
    "5": bench_config5,
    "6": bench_config6,
    "7": bench_config7,
    "8": bench_config8,
    "9": bench_config9,
    "10": bench_config10,
    "11": bench_config11,
    "12": bench_config12,
    "13": bench_config13,
    "14": bench_config14,
    "15": bench_config15,
    "16": bench_config16,
    "17": bench_config17,
    "18": bench_config18,
    "19": bench_config19,
    "20": bench_config20,
    "21": bench_config21,
    "22": bench_config22,
    "23": bench_config23,
    "3": bench_config3,  # headline LAST so its line is what the driver parses
}


#: child exit code when the process found no accelerator and was not
#: told to use the CPU — the orchestrator stops the suite on it
_RC_NO_DEVICE = 3


def main(which: str) -> int:
    """Child: run ONE config (or 'all') on the already-selected backend."""
    from pilosa_tpu import platform

    cpu_pinned = os.environ.get("JAX_PLATFORMS") == "cpu"
    if cpu_pinned:
        platform.force_cpu_platform()  # pin the config too, not just env
    platform.configure_compile_cache()
    import jax

    device = jax.devices()[0].device_kind
    if jax.devices()[0].platform == "cpu":
        if not cpu_pinned:
            # JAX found no accelerator and fell back by itself: a CPU
            # number must never appear under a device run's name
            print("bench: no accelerator found and JAX_PLATFORMS=cpu not "
                  "set: refusing to measure on the CPU", file=sys.stderr)
            return _RC_NO_DEVICE
        _apply_cpu_scale()
    failed = 0
    names = list(_CONFIGS) if which == "all" else [which]
    for name in names:
        cfg = _CONFIGS[name]
        t0 = time.perf_counter()
        try:
            cfg(device)
        except Exception as exc:
            print(f"bench: {cfg.__name__} failed: {exc!r}", file=sys.stderr)
            failed = 1
            if name == "3":
                # the driver records the LAST line as the headline; a
                # failed headline must be visibly failed, not silently
                # replaced by whichever config printed last
                _emit(f"c3_groupby_topk_FAILED ({device})", 0.0, "ms", 0.0)
        print(f"bench: {cfg.__name__} wall {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        gc.collect()
    profile_out = os.environ.get("PILOSA_BENCH_PROFILE_OUT")
    if profile_out:
        _dump_profile(profile_out, device)
    return failed


# ---------------------------------------------------------------------------
# Orchestrator: one child process per config, one process per chip. The
# orchestrator never imports jax (a parent that touched JAX would hold
# the chip its children need) and each child inherits the environment
# unchanged: the platform a config runs on is the one the caller chose.
# A child that fails fails the run; it is never re-run elsewhere.
# ---------------------------------------------------------------------------

def _run_child(cfg_name: str, timeout: float):
    """Run one config in a child; returns (rc, failure_reason)."""
    proc = subprocess.Popen(
        [sys.executable, __file__],
        env=dict(os.environ, PILOSA_BENCH_CHILD=cfg_name),
        start_new_session=True)
    try:
        return proc.wait(timeout=timeout), None
    except subprocess.TimeoutExpired:
        import signal

        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
        return None, f"timed out after {timeout:.0f}s"


def orchestrate() -> int:
    budget = int(os.environ.get("PILOSA_BENCH_TIMEOUT", "900"))
    deadline = time.monotonic() + budget
    worst = 0
    names = list(_CONFIGS)
    for i, name in enumerate(names):
        # per-config share of what's left, floored so a late config still
        # gets a usable slice: one wedged child must not starve the rest
        share = max(90.0, (deadline - time.monotonic()) / (len(names) - i))
        rc, why = _run_child(name, share)
        if rc == _RC_NO_DEVICE:
            return rc
        if rc != 0:
            print(f"bench: config {name} child "
                  f"{why or f'failed (rc={rc})'}", file=sys.stderr)
            worst = 1
            if name == "3":
                # A SIGKILLed child emits nothing, so the failed-headline
                # sentinel must come from here — otherwise the driver
                # parses whichever config printed last as the headline.
                _emit("c3_groupby_topk_FAILED (none)", 0.0, "ms", 0.0)
    return worst


if __name__ == "__main__":
    child = os.environ.get("PILOSA_BENCH_CHILD")
    if not child and "--configs" in sys.argv[1:]:
        # `bench.py --configs 7` runs one config in-process (same as the
        # child env var, minus the orchestrator's per-config time slice)
        child = sys.argv[sys.argv.index("--configs") + 1]
    if child:
        sys.exit(main(child))
    sys.exit(orchestrate())
