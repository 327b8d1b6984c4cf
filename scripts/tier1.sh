#!/usr/bin/env bash
# Tier-1 verification: syntax smoke, the analysis lanes, the suites
# re-run under each plane's environment switch and under fixed fault /
# crash / gossip / hash seeds, then the full test suite with the command
# the PR driver runs (/root/TESTS_LAST_RUN.json, `commands`; the driver
# also sets ALLOW_MULTIPLE_LIBTPU_LOAD=1 on its sandbox, which a repo
# file must not). The driver runs only that last command; every lane
# before it is run by hand.
#
# The determinism gate runs tests/test_cache.py under two different
# PYTHONHASHSEED values: result-cache keys embed fragment-version
# fingerprints that MUST be built from sorted iteration, never dict/set
# order — a hash-order-dependent key caches under one seed and misses
# (or worse, collides) under another.
set -u -o pipefail

cd "$(dirname "$0")/.."

echo "== compileall syntax smoke =="
python -m compileall -q pilosa_tpu || exit $?

echo "== analysis lane: project-invariant linter =="
# Static half of the concurrency-correctness plane: every rule runs
# against the checked-in ratcheted baseline — any NEW violation (raw
# time in clock modules, bare locks in migrated packages, callbacks
# under locks, device calls outside platform, unreset contextvars,
# unbounded metric labels) fails the build. --selftest first proves the
# gate logic itself (one positive + one negative fixture per rule).
python scripts/lint_invariants.py --selftest || exit $?
python scripts/lint_invariants.py \
    --baseline pilosa_tpu/analysis/baseline.json || exit $?

echo "== analysis lane: lock tracer (PILOSA_TPU_LOCKCHECK=1) =="
# Dynamic half: the sched/cache/cluster-batch/recovery suites re-run
# with every tracked lock feeding the acquisition-order graph; the
# conftest audit fixture fails any test that records a lock-order cycle
# or a lock held across device dispatch / blocking socket I/O.
PILOSA_TPU_LOCKCHECK=1 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_sched.py tests/test_cache.py \
    tests/test_cluster_batch.py tests/test_recovery.py \
    tests/test_locktrace.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || exit $?

echo "== cache determinism gate (PYTHONHASHSEED=0 / 1) =="
for seed in 0 1; do
    PYTHONHASHSEED=$seed JAX_PLATFORMS=cpu \
        python -m pytest tests/test_cache.py -q -p no:cacheprovider \
        -p no:xdist -p no:randomly || exit $?
done

echo "== fault-injection lane (PILOSA_TPU_FAULT_SEED=1 / 7) =="
# The resilience tests must hold for ANY fault seed (seeds steer only
# prob-gated rules); two fixed seeds keep the chaos reproducible while
# still exercising two distinct injected-fault schedules.
for seed in 1 7; do
    PILOSA_TPU_FAULT_SEED=$seed JAX_PLATFORMS=cpu \
        python -m pytest tests/test_resilience.py -q -p no:cacheprovider \
        -p no:xdist -p no:randomly || exit $?
done

echo "== gossip-determinism lane (PILOSA_TPU_GOSSIP_SEED=1 / 7) =="
# Gossip convergence must hold for ANY peer-selection seed (the seed
# only steers which peer an anti-entropy round contacts); two fixed
# seeds exercise two distinct exchange schedules reproducibly.
for seed in 1 7; do
    PILOSA_TPU_GOSSIP_SEED=$seed JAX_PLATFORMS=cpu \
        python -m pytest tests/test_gossip.py -q -p no:cacheprovider \
        -p no:xdist -p no:randomly || exit $?
done

echo "== membership-chaos lane (PILOSA_TPU_FAULT_SEED=1 / 7) =="
# SWIM membership must converge for ANY fault seed: partition plans in
# test_membership are deterministic cuts (no prob rules), so the seed
# only steers the other suites' prob-gated faults; the lane proves the
# suspect/confirm/refute machinery and the cluster fan-out both hold
# under two distinct injected-fault schedules.
for seed in 1 7; do
    PILOSA_TPU_FAULT_SEED=$seed JAX_PLATFORMS=cpu \
        python -m pytest tests/test_membership.py tests/test_cluster.py \
        -q -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
done

echo "== crash-injection lane (PILOSA_TPU_CRASH_SEED=1 / 7) =="
# Crash recovery must hold for ANY seeded kill point (the seed picks the
# kill site and hit count); two fixed seeds exercise two distinct crash
# schedules through the storage write path reproducibly.
for seed in 1 7; do
    PILOSA_TPU_CRASH_SEED=$seed JAX_PLATFORMS=cpu \
        python -m pytest tests/test_recovery.py -q -p no:cacheprovider \
        -p no:xdist -p no:randomly || exit $?
done

echo "== stream crash lane (PILOSA_TPU_CRASH_SEED=1 / 7) =="
# Exactly-once streaming ingest must hold for ANY seeded kill point: the
# seed draws a site/hit-count from the stream stage-boundary tuple
# (handoff/apply/commit), disjoint from the storage sites so the lane
# above is unchanged. test_recovery.py rides along to prove the storage
# crash matrix still holds with the stream subsystem loaded.
for seed in 1 7; do
    PILOSA_TPU_CRASH_SEED=$seed JAX_PLATFORMS=cpu \
        python -m pytest tests/test_stream.py tests/test_recovery.py \
        -q -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
done

echo "== cluster-batch lane (PILOSA_TPU_CLUSTER_BATCH=1, fault seeds) =="
# The cluster suites re-run with the per-node leg coalescer attached to
# every node (the env flag ISSUE 9 ships): results must stay
# bit-identical when every remote read leg rides a multi-query batch
# RPC, including under the seeded FaultPlan chaos in test_cluster_batch
# (seeds steer only prob-gated rules, same contract as the fault lane).
for seed in 1 7; do
    PILOSA_TPU_CLUSTER_BATCH=1 PILOSA_TPU_FAULT_SEED=$seed \
        JAX_PLATFORMS=cpu \
        python -m pytest tests/test_cluster_batch.py tests/test_cluster.py \
        -q -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
done

echo "== tracing lane (PILOSA_TPU_TRACE=1, sample rate 1.0) =="
# Every query in these suites runs under a live always-sampling tracer:
# results must stay bit-identical to the untraced runs above, and the
# conftest span-leak fixture asserts the context scope is empty after
# each test (a leaked span would silently re-parent later traces).
PILOSA_TPU_TRACE=1 PILOSA_TPU_TRACE_SAMPLE_RATE=1.0 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_sched.py tests/test_cluster.py \
    tests/test_cache.py tests/test_tracing.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || exit $?

echo "== obs-timeline lane (PILOSA_TPU_OBS_TIMELINE=1, 10ms cadence) =="
# The health plane rides every API/node in these suites in piggyback
# mode (SLO accounting per request, cadence-gated timeline samples,
# zero background threads); the clamped interval forces the sampler,
# burn-rate evaluation, and flight-recorder trigger paths to actually
# fire under the full tracing/cluster/scheduler suites while results
# stay bit-identical.
PILOSA_TPU_OBS_TIMELINE=1 PILOSA_TPU_OBS_TIMELINE_INTERVAL_MS=10 \
    JAX_PLATFORMS=cpu \
    python -m pytest tests/test_tracing.py tests/test_cluster.py \
    tests/test_sched.py tests/test_health.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || exit $?

echo "== device-budget lane (PILOSA_TPU_DEVICE_BUDGET clamped) =="
# The residency plane must stay correct when HBM is scarce: an 8MB cap
# with 4MB blocks forces paging AND eviction of resident planes on the
# same suites that assert bit-exact results and budget accounting.
PILOSA_TPU_DEVICE_BUDGET=$((8 << 20)) PILOSA_TPU_BLOCK_BYTES_MB=4 \
    JAX_PLATFORMS=cpu \
    python -m pytest tests/test_resident.py tests/test_paging.py \
    tests/test_stacked_merge.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || exit $?

echo "== devprof lane (PILOSA_TPU_DEVPROF=1) =="
# The kernel-attribution plane rides every compiled dispatch in these
# suites: results must stay bit-identical with profiling on, and the
# suites assert exactly zero cost-model work when the flag is off.
PILOSA_TPU_DEVPROF=1 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_resident.py tests/test_tracing.py \
    tests/test_health.py tests/test_devprof.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || exit $?

echo "== tenant lane (PILOSA_TPU_TENANTS=1, fault seeds 1 / 7) =="
# The tenant attribution plane bootstraps on every API in these suites
# (attribution-only defaults: quotas 0, no enforcement): results must
# stay bit-identical with per-tenant accounting, tenant-scoped cache
# namespaces, and the scheduler's fair-share ordering live; the seeds
# steer the prob-gated faults the cluster suites inject underneath.
for seed in 1 7; do
    PILOSA_TPU_TENANTS=1 PILOSA_TPU_FAULT_SEED=$seed JAX_PLATFORMS=cpu \
        python -m pytest tests/test_tenants.py tests/test_sched.py \
        tests/test_health.py -q -p no:cacheprovider \
        -p no:xdist -p no:randomly || exit $?
done

echo "== dax crash lane (PILOSA_TPU_CRASH_SEED=1 / 7) =="
# The elastic serverless plane must replay to bit-identical state for
# ANY seeded kill point: the seed draws a site/hit-count from the dax
# tuple (wl.append / snap.replace / directive.mid), disjoint from the
# storage AND stream sites so those lanes are unchanged. test_dax.py
# rides along to prove the seed-era serverless surface still holds.
for seed in 1 7; do
    PILOSA_TPU_CRASH_SEED=$seed JAX_PLATFORMS=cpu \
        python -m pytest tests/test_dax.py tests/test_dax_elastic.py \
        -q -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
done

echo "== pallas-interpret lane (PILOSA_TPU_PALLAS=1) =="
# Every Pallas kernel body executes on CPU via interpret=True across the
# ops, resident, and fusion suites plus the dedicated parity battery:
# results must stay bit-identical to the classic XLA paths those same
# suites assert. Widths above pallas_util.INTERPRET_MAX_WORDS stay on
# the classic path (why="interpret") — the interpreter adds no kernel
# coverage at shard scale and costs seconds per dispatch.
PILOSA_TPU_PALLAS=1 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_topk_groupby.py tests/test_bsi.py \
    tests/test_resident.py tests/test_fusion.py \
    tests/test_pallas_parity.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || exit $?

echo "== pallas kill-switch lane (PILOSA_TPU_PALLAS=0) =="
# The same ops suites with the kill switch engaged: classic path
# everywhere, and the parity battery's kill-switch tests assert zero
# dispatches and zero fallback ticks (the switch must cost nothing).
PILOSA_TPU_PALLAS=0 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_topk_groupby.py tests/test_bsi.py \
    tests/test_pallas_parity.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || exit $?

echo "== compressed-residency lane (PILOSA_TPU_COMPRESS=1 + PALLAS=1) =="
# Every stacked read path (point reads, TopN/row_counts streaming,
# GroupBy, BSI compare, the paging/eviction/advance protocols) consumes
# compressed-resident blocks, with the ctile_count Pallas kernel forced
# through the interpreter: results must stay bit-identical to the dense
# suites above. Forced mode overrides the size/ratio/mesh policy so the
# virtual 8-device test mesh exercises the compressed format too.
PILOSA_TPU_COMPRESS=1 PILOSA_TPU_PALLAS=1 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_compress.py tests/test_paging.py \
    tests/test_resident.py tests/test_pallas_parity.py \
    tests/test_stacked_merge.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || exit $?

echo "== compress kill-switch lane (PILOSA_TPU_COMPRESS=0) =="
# The same stacked/paging suites with compression disabled: every block
# stays a dense jax.Array and test_compress's kill-switch tests assert
# zero compress-metric movement (the switch must cost nothing).
PILOSA_TPU_COMPRESS=0 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_compress.py tests/test_paging.py \
    -q -p no:cacheprovider -p no:xdist -p no:randomly || exit $?

echo "== degrade lane (PILOSA_TPU_DEGRADE=1) =="
# The graceful-degradation controller bootstraps on every API in these
# suites (default edges, so a healthy test workload never escalates):
# results must stay bit-identical with the ladder armed, and the
# dedicated suites prove hysteresis, shed ordering, brownout stale
# tagging, and the DEGRADE=0 zero-cost contract.
PILOSA_TPU_DEGRADE=1 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_degrade.py tests/test_sched.py \
    tests/test_cache.py tests/test_health.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || exit $?

echo "== soak smoke lane (PILOSA_TPU_FAULT_SEED=1 / 7) =="
# The bounded-table churn audit and the degradation ladder must hold
# for ANY fault seed (seeds steer only prob-gated chaos rules); two
# fixed seeds keep the runs reproducible.
for seed in 1 7; do
    PILOSA_TPU_FAULT_SEED=$seed JAX_PLATFORMS=cpu \
        python -m pytest tests/test_bounded.py \
        tests/test_degrade.py -q -p no:cacheprovider \
        -p no:xdist -p no:randomly || exit $?
done

echo "== ssb lane (tiny-scale flights vs numpy oracle) =="
# All 13 SSB queries at tiny scale must be bit-identical to the
# independent numpy oracle on one node (semi-join plane AND the
# PILOSA_TPU_SEMIJOIN=0 hash fallback) and on a 3-node cluster that
# drops one request per query, plus the JOIN grammar battery and the
# semi-join plane's own test file.
JAX_PLATFORMS=cpu python -m pytest tests/test_ssb.py \
    tests/test_sql_parser.py tests/test_sql_joins.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit $?

echo "== tier-1 test suite =="
rm -rf /tmp/_t1.log /tmp/_t1.xml
timeout -k 10 1470 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
    --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 \
    | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' \
    /tmp/_t1.xml 2>/dev/null | head -n 1 \
    | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)}
echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null)
exit $rc
