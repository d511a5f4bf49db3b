#!/bin/sh
# CI entry point: both test tiers with per-tier wall budgets.
#
# Analog of the reference's CI stages (reference: Dockerfile.test.cpu:86
# runs the parallel suite under mpirun; docker-compose.test.yml +
# .buildkite fan the heavyweight matrix out to separate stages): tier 1
# is the default `pytest tests/` run, tier 2 holds the heavyweight
# integration jobs whose code paths tier 1 already covers.
#
# Usage: ci/run_tests.sh [analysis|flightrec|fleet|ops|tier1|tier2|all]
set -e
cd "$(dirname "$0")/.."

TIER="${1:-all}"

# Analysis lane: cross-language contract checkers + native static
# analyzer (docs/static_analysis.md). Runs BEFORE the test lanes and
# fails fast — a drifted knob registry or counter bridge is cheaper to
# catch in ~5 min of analysis than in a wedged multi-process test. The
# checkers take seconds; the budget is dominated by gcc -fanalyzer
# (controller.cc needs call-summary mode, see core/src/Makefile).
run_analysis() {
    echo "=== analysis: per-checker smoke (tools/analysis --checker) ==="
    # One scoped run per checker BEFORE the combined run: a checker
    # that crashes (rather than finds) then fails with its own name in
    # the log. Each run is a fresh process, so the tree is re-parsed
    # per checker (~4 s each, ~40 s for the loop — noise next to the
    # fanalyzer budget below); run_all also names a crashing checker,
    # this loop just guarantees the attribution shows up as the LAST
    # lane header even if the combined run is skipped or wrapped.
    for checker in knobs counters ctypes metrics excepts \
                   locks journal jaxcompat testtier spmd \
                   deadlock blocking; do
        echo "--- checker: $checker"
        timeout 60 python -m tools.analysis --checker "$checker"
    done
    echo "=== analysis: contract checkers (tools/analysis, all) ==="
    timeout 120 python -m tools.analysis
    echo "=== analysis: native analyzer (make analyze) ==="
    timeout "${HVD_CI_ANALYSIS_BUDGET:-900}" \
        make -C horovod_tpu/core/src analyze
}

# Flightrec lane: the forensics pipeline (ring recorders, dump
# merge/clock alignment, tools.trace diagnosis) plus a native-analyzer
# pass over the recorder TU. Fail-fast: a broken recorder means the
# next production failure leaves no evidence behind, which is cheaper
# to catch here than at the post-mortem that finds empty dumps.
run_flightrec() {
    echo "=== flightrec: ring/merge/diagnosis units (tests/test_flightrec.py) ==="
    timeout "${HVD_CI_FLIGHTREC_BUDGET:-240}" \
        python -m pytest tests/test_flightrec.py -q -p no:cacheprovider
    echo "=== flightrec: native analyzer over the recorder TU ==="
    timeout 300 make -C horovod_tpu/core/src analyze-flightrec.cc
}

# Tier-1 wall budget: the r5 suite (288 tests; adds runner-selection,
# per-binding sweep launchers, fake contracts, spark convert) measured
# 876.79s on this quiet 1-core host (r4: 253 tests, 690.75s). 1200s
# keeps ~37% headroom for loaded CI machines — the r2 margin (636s vs
# 720s) proved too thin. (Final r5 suite, 316 tests, cold cache:
# 868.40s — holds.)
run_tier1() {
    run_flightrec
    echo "=== tier 1: planner fast-fail (cost-model units + planner-swept dryrun smoke) ==="
    # The sharding planner (docs/planner.md) owns layout for every
    # multi-axis training run and for the MULTICHIP dryrun's mesh
    # choices; a broken cost model or a sweep that stops composing
    # should fail in seconds, before the full tier burns its wall
    # budget. Cost-model units are pure Python (~1 s); the smoke
    # executes the 5-scenario planner sweep on the 8 virtual devices
    # (a few seconds warm, tens cold) — both far inside the budget.
    timeout "${HVD_CI_PLAN_BUDGET:-240}" \
        python -m pytest tests/test_costmodel.py \
        "tests/test_planner.py::test_planner_swept_dryrun_smoke" \
        -q -p no:cacheprovider
    echo "=== tier 1: autotune fast-fail (online tuner loop + guardrail) ==="
    # The online tuner (docs/autotune.md) mutates live knobs on every
    # training/serving job that sets HVD_TUNE; a broken guardrail
    # would let a regressing move stick, and a broken journal replay
    # would re-search from cold on every restart. The whole lane is
    # fake-clock units — seconds, no fleets. The guardrail-revert case
    # runs FIRST by name so a regression there is attributed before
    # the rest of the lane runs.
    timeout "${HVD_CI_TUNE_BUDGET:-240}" \
        python -m pytest \
        "tests/test_online_tuner.py::test_guardrail_reverts_injected_regression" \
        tests/test_online_tuner.py -q -p no:cacheprovider
    echo "=== tier 1: MFU fast-fail (bucketing math) ==="
    # The bucketed gradient path is a pure-Python contract
    # (docs/mfu.md) that every in-graph training run leans on; a
    # broken bucket assignment should fail in seconds, before the full
    # tier burns its wall budget.
    timeout "${HVD_CI_MFU_BUDGET:-240}" \
        python -m pytest tests/test_bucketing.py \
        -q -p no:cacheprovider
    echo "=== tier 1: wire-compression fast-fail (codec math + lossy equality) ==="
    # The quantized wire (docs/wire.md#compression) rewrites every fp32
    # ring payload once a codec is staged; a broken codec corrupts
    # gradients SILENTLY (training still runs, numbers are wrong), so
    # the codec matrix fails in seconds before the full tier burns its
    # wall budget: in-process codec math vs the shared tolerance table,
    # the lossy np=2/3 equality runs, the codec=none bit-exact pin, the
    # bf16 tx-bytes discount, and the heal-under-compression hash pin.
    timeout "${HVD_CI_COMPRESS_BUDGET:-240}" \
        python -m pytest tests/test_wire.py -q -p no:cacheprovider \
        -k "codec"
    echo "=== tier 1: metrics subsystem fast-fail ==="
    # The metrics registry underpins scrape-based dashboards and the
    # /metrics route every runner HTTP server exposes; if it is broken,
    # fail in seconds before the full tier burns its wall budget. The
    # np=2 bridge test is excluded here — the full tier runs it.
    timeout "${HVD_CI_METRICS_BUDGET:-180}" \
        python -m pytest tests/test_metrics.py -q -p no:cacheprovider \
        -k "not bridge"
    echo "=== tier 1 (default suite, includes tests/test_metrics.py) ==="
    timeout "${HVD_CI_TIER1_BUDGET:-1200}" \
        python -m pytest tests/ -q -p no:cacheprovider
}

# Tier-2 wall budget: re-measured whenever the tier grows (the r3
# budget breach on a cold cache taught that lesson; r4 re-measured 26
# tests at 756-762s cold). The r5 tier is 43 tests (new example
# smokes, per-binding sweeps, elastic crossovers); a cold-cache run
# (`rm -rf .jax_cache`, quiet 1-core host) measured
# 1401.27s at 40 tests, plus 78.4s measured for the three elastic
# shrink/blacklist/reset-limit cases added after ≈ 1480s. 1800s keeps
# ~21% headroom over that worst cold run. (Final r5 suite, 43 tests,
# consecutive cold-cache quiet-host runs: 1231.18s, 1258.37s,
# 1346.19s — worst holds with ~25%.)
#
# ISSUE 3 adds the chaos matrix (tests/test_chaos.py: sigstop np=2/3,
# kill -9, injected half-close/stall ≈ 110s measured warm) and a
# fault-injection TSAN smoke (jax-free workers; the sanitized core is
# built in-test BEFORE the preloaded workers launch — forking make
# under libtsan deadlocks). Budget bumped 1800 -> 2100 to keep the
# headroom ratio.
#
# ISSUE 4 adds the ASan/UBSan smokes (tests/test_sanitizers.py, same
# jax-free prebuild discipline): ~11s warm, ~60s cold for the two
# instrumented core builds — absorbed by the existing headroom.
#
# ISSUE 6 adds the wire-bench smoke (one tiny np=2 loopback sweep
# through bench_wire.py, ~15s warm) so a broken data-plane bench lane
# is caught before anyone needs it for an A/B, plus the pipelined-ring
# chaos pair and the np=4 sweep inside the tier-2 pytest run (~70s
# combined warm) — absorbed by the existing headroom.
#
# ISSUE 5 adds the elastic control-plane chaos pair
# (tests/test_chaos_elastic.py: SIGKILL the driver with journaling ->
# replay + checkpoint auto-resume; SIGSTOP a worker -> heartbeat
# liveness replacement; ~150-250s combined warm). The driver-kill case
# runs FIRST as a fail-fast smoke — a broken journal/fencing path
# wedges jobs in production, so it is cheaper to catch before the full
# tier burns its budget. Budget bumped 2100 -> 2400 to keep headroom.
# ISSUE 8 adds the serving lane: a jax-free bench_serve.py smoke (one
# tiny identity-model fleet, proves router + replicas + micro-batcher
# end-to-end in seconds) and the serving chaos test (real checkpoint,
# kill -9 replica + SIGKILL router, ~35s warm) run FAIL-FAST before
# the full tier — a broken serving plane is a user-facing outage, so
# it is cheaper to catch before the tier burns its budget. The chaos
# test is then deselected from the full tier run (driver-kill
# precedent). Combined warm cost ~60s — absorbed by the existing
# headroom.
# ISSUE 12 adds the chaos forensics pair (test_chaos.py
# test_chaos_forensics_names_culprit: sigstop np=2 + injected stall
# np=3, each asserting tools.trace names the culprit from the dumps;
# ~12s combined warm) — absorbed by the existing headroom.
# ISSUE 15 adds the self-healing-wire lane: a bench_wire --fault reset
# recovery smoke + the np=3 mid-chunk heal drive run FAIL-FAST (the
# heal drive is then deselected from the full tier, driver-kill
# precedent), and the storm/legacy-pin chaos pair rides the full tier
# (~8s combined warm) — absorbed by the existing headroom.
# Fleet lane (ISSUE 18): one jax-free cardinality smoke through
# bench_fleet.py — a 64-rank stub world bootstrapped, churned, KV-
# stormed and served end-to-end with the scaling-curve extraction that
# BENCH_fleet.json rides (docs/fleet.md). Minutes-cheap (thread
# workers, no processes); the 500-rank acceptance storm lives in the
# tier-2 pytest run as test_fleet_storm_500_zero_lost.
run_fleet() {
    echo "=== fleet: cardinality smoke (bench_fleet.py --quick, n=64) ==="
    timeout "${HVD_CI_FLEET_BUDGET:-600}" \
        python bench_fleet.py --quick --sizes 64 --no-storm > /dev/null
}

# Ops lane (ISSUE 20): the zero-downtime fleet operations — a rolling
# checkpoint upgrade over a 64-identity stub fleet under closed-loop
# load (zero lost requests) and a kill -9 of the active router
# MID-ROLL with a hot standby resuming the upgrade from the journal.
# Fail-fast: a broken drain/roll/failover path turns every planned
# operation into an outage, which is cheaper to catch here than during
# one. Jax-free (thread-stub replicas, real sockets/journal) — tens of
# seconds warm; the SIGTERM-storm and kill-mid-drain chaos variants
# carry tier2+slow and ride the full tier run.
run_ops() {
    echo "=== ops: rolling upgrade + router failover (tests/test_ops.py, n=64) ==="
    timeout "${HVD_CI_OPS_BUDGET:-600}" python -m pytest \
        tests/test_ops.py::test_ops_rolling_upgrade_n64_zero_lost \
        tests/test_ops.py::test_ops_router_failover_resumes_roll_n64 \
        -q -p no:cacheprovider --override-ini 'addopts='
}

run_tier2() {
    run_fleet
    run_ops
    echo "=== tier 2: serving smoke (bench_serve.py, jax-free fleet) ==="
    timeout "${HVD_CI_SERVE_BUDGET:-600}" \
        python bench_serve.py --np 2 --duration 2 --threads 4 \
        > /dev/null
    echo "=== tier 2: serving chaos smoke (replica kill -9 + router SIGKILL) ==="
    timeout "${HVD_CI_SERVE_BUDGET:-600}" python -m pytest \
        tests/test_chaos_serve.py -q -p no:cacheprovider \
        --override-ini 'addopts='
    echo "=== tier 2: wire microbenchmark smoke (bench_wire.py) ==="
    # Smoke only: proves the jax-free bench lane runs end-to-end (two
    # sizes, handful of iters). Real A/B numbers need interleaved
    # pre/post trials — see docs/wire.md.
    timeout "${HVD_CI_WIRE_BUDGET:-180}" \
        python bench_wire.py --np 2 --sizes 65536,4194304 \
        --iters 4 --warmup 1 > /dev/null
    echo "=== tier 2: self-healing wire smoke (reset recovery + fail-fast heal) ==="
    # ISSUE 15 fail-fast pair: the recovery-latency lane of bench_wire
    # (a hard RST mid-sweep must heal and report break->resume timing)
    # and the np=3 mid-pipelined-chunk heal drive. A broken reconnect
    # path turns every transient blip back into a full world teardown,
    # so it is cheaper to catch before the tier burns its budget.
    timeout "${HVD_CI_RECONNECT_BUDGET:-300}" \
        python bench_wire.py --np 2 --fault reset --sizes 4194304 \
        --iters 4 --warmup 1 > /dev/null
    timeout "${HVD_CI_RECONNECT_BUDGET:-300}" python -m pytest \
        tests/test_chaos.py::test_chaos_reset_heals_in_place \
        -q -p no:cacheprovider --override-ini 'addopts='
    echo "=== tier 2: driver-kill chaos smoke (journal + auto-resume) ==="
    timeout 600 python -m pytest \
        tests/test_chaos_elastic.py::test_driver_kill9_journal_resume \
        -q -p no:cacheprovider --override-ini 'addopts='
    echo "=== tier 2 (heavyweight integration, incl. chaos suite) ==="
    timeout "${HVD_CI_TIER2_BUDGET:-2400}" \
        python -m pytest tests/ -q -p no:cacheprovider \
        --override-ini 'addopts=' -m tier2 \
        --deselect tests/test_chaos_elastic.py::test_driver_kill9_journal_resume \
        --deselect tests/test_chaos_serve.py::test_serve_chaos_replica_kill9_then_router_sigkill \
        --deselect tests/test_chaos.py::test_chaos_reset_heals_in_place \
        --deselect tests/test_ops.py::test_ops_rolling_upgrade_n64_zero_lost \
        --deselect tests/test_ops.py::test_ops_router_failover_resumes_roll_n64
}

case "$TIER" in
    analysis) run_analysis ;;
    flightrec) run_flightrec ;;
    fleet) run_fleet ;;
    ops) run_ops ;;
    tier1) run_tier1 ;;
    tier2) run_tier2 ;;
    all) run_analysis; run_tier1; run_tier2 ;;
    *) echo "usage: $0 [analysis|flightrec|fleet|ops|tier1|tier2|all]" >&2
       exit 2 ;;
esac
