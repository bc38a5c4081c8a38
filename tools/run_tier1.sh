#!/usr/bin/env bash
# Tier-1 verify gate — the EXACT command from ROADMAP.md, wrapped so
# builders and CI invoke the same gate (same pipefail discipline, same
# DOTS_PASSED report) instead of each reassembling it by hand.
#
# Usage:  tools/run_tier1.sh [extra pytest args...]
#   e.g.  tools/run_tier1.sh tests/test_guardrails.py
# Exit status is pytest's (pipefail-preserved through the tee).
set -u
set -o pipefail

cd "$(dirname "$0")/.."

LOG="${TIER1_LOG:-/tmp/_t1.log}"
TIMEOUT_S="${TIER1_TIMEOUT:-870}"

rm -f "$LOG"
timeout -k 10 "$TIMEOUT_S" env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly \
    "$@" 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)"

# Serve smoke pass (TIER1_SERVE=0 to skip): one InferenceSession behind a
# DynamicBatcher, 32 concurrent requests — asserts correct results, a p99
# latency bound, zero recompiles after warmup, and clean shutdown.
if [[ "${TIER1_SERVE:-1}" != "0" ]]; then
    timeout -k 10 120 env JAX_PLATFORMS=cpu \
        python tools/serve_smoke.py
    serve_rc=$?
    if [[ "$rc" -eq 0 && "$serve_rc" -ne 0 ]]; then
        rc=$serve_rc
    fi
fi
# Chaos soak smoke (TIER1_CHAOS=0 to skip): ~15s of 64 concurrent
# mixed-priority clients under a seeded fault plan — asserts exactly-once
# future settlement, no silent late completions, batch-class-only sheds,
# bounded interactive p99, clean drain, and a warm (zero-recompile) hot
# swap. The full soak lives in tests/test_serve_chaos.py behind -m slow.
if [[ "${TIER1_CHAOS:-1}" != "0" ]]; then
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
        python tools/chaos_soak.py --duration "${TIER1_CHAOS_S:-6}" --clients 64
    chaos_rc=$?
    if [[ "$rc" -eq 0 && "$chaos_rc" -ne 0 ]]; then
        rc=$chaos_rc
    fi
fi
# Trace pass (TIER1_TRACE=1 to enable): re-run the serve smoke with
# request tracing + the flight recorder on. Asserts (a) the injected
# serve:execute fault leaves a recorder dump naming the failing site
# (serve_smoke --trace-out exits nonzero otherwise) and (b) the dumped
# chrome trace is well-formed with one connected per-request lane
# (tools/trace_check.py --expect-lane).
if [[ "${TIER1_TRACE:-0}" != "0" ]]; then
    TRACE_DIR="$(mktemp -d /tmp/_t1_trace.XXXXXX)"
    timeout -k 10 120 env JAX_PLATFORMS=cpu \
        MXNET_TRACE=1 MXNET_FLIGHT_RECORDER=1 \
        MXNET_FLIGHT_RECORDER_DIR="$TRACE_DIR" \
        python tools/serve_smoke.py --trace-out "$TRACE_DIR/trace.json"
    trace_rc=$?
    if [[ "$trace_rc" -eq 0 ]]; then
        python tools/trace_check.py --expect-lane "$TRACE_DIR/trace.json"
        trace_rc=$?
    fi
    if [[ "$rc" -eq 0 && "$trace_rc" -ne 0 ]]; then
        rc=$trace_rc
    fi
fi
# Decode-rung pass (TIER1_DECODE=1 to enable): run the serve smoke's
# --decode-path mode over every rung of the decode ladder — baseline
# (strict PR-5 ops), pallas (fused decode-attention), int8 (int8 KV
# rings), spec (speculative decoding). Each rung drives 8 concurrent
# generate() clients and asserts identical greedy output, zero
# recompiles, and the 503 (drain/resume) + 504 (past-deadline) taxonomy.
if [[ "${TIER1_DECODE:-0}" != "0" ]]; then
    for dp in baseline pallas int8 spec; do
        timeout -k 10 180 env JAX_PLATFORMS=cpu \
            python tools/serve_smoke.py --decode-path "$dp"
        decode_rc=$?
        if [[ "$rc" -eq 0 && "$decode_rc" -ne 0 ]]; then
            rc=$decode_rc
        fi
    done
fi
# Prefix-cache pass (TIER1_PREFIX=1 to enable): serve_smoke --prefix —
# 8 ContinuousEngine clients sharing a 20-token system prompt must get
# token-identical greedy output with the radix prefix cache on vs off,
# with prefix_hit_rate > 0, zero recompiles, and no page leaks; then
# two fresh subprocesses warm one MXNET_COMPILE_CACHE_DIR and the
# second must replay the whole lattice from disk (disk_hits > 0,
# disk_misses == 0) with identical stable signature keys. Re-run under
# MXNET_LOCKDEP=1 to pin the trie-outside-pool lock order.
if [[ "${TIER1_PREFIX:-0}" != "0" ]]; then
    timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python tools/serve_smoke.py --prefix
    prefix_rc=$?
    if [[ "$rc" -eq 0 && "$prefix_rc" -ne 0 ]]; then
        rc=$prefix_rc
    fi
    timeout -k 10 600 env JAX_PLATFORMS=cpu MXNET_LOCKDEP=1 \
        python tools/serve_smoke.py --prefix
    prefix_rc=$?
    if [[ "$rc" -eq 0 && "$prefix_rc" -ne 0 ]]; then
        rc=$prefix_rc
    fi
fi
# Multi-step decode pass (TIER1_MULTISTEP=1 to enable): serve_smoke
# --multistep — 8 concurrent ContinuousEngine clients on the PR-19
# device-side super-step loop (MXNET_SERVE_DECODE_STEPS iterations per
# host visit) must get greedy output token-identical to the classic
# one-visit-per-token engine, with exactly one compiled super-step
# signature, zero recompiles, and a mid-stream deadline settling as 504
# within one super-step (not one request). Re-run under MXNET_LOCKDEP=1:
# the settle loop walks pool + metrics locks per super-step and must
# stay cycle-free.
if [[ "${TIER1_MULTISTEP:-0}" != "0" ]]; then
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        python tools/serve_smoke.py --multistep
    ms_rc=$?
    if [[ "$rc" -eq 0 && "$ms_rc" -ne 0 ]]; then
        rc=$ms_rc
    fi
    timeout -k 10 300 env JAX_PLATFORMS=cpu MXNET_LOCKDEP=1 \
        python tools/serve_smoke.py --multistep
    ms_rc=$?
    if [[ "$rc" -eq 0 && "$ms_rc" -ne 0 ]]; then
        rc=$ms_rc
    fi
fi
# Fleet soak smoke (TIER1_FLEET=0 to skip): ~8s of 64 mixed-priority
# clients through a Router over 3 replicas under a seeded fault plan,
# with one deterministic replica kill mid-traffic — asserts fleet-wide
# exactly-once settlement (failover requeue + generation fencing), a
# closed outcome taxonomy, batch-only sheds, bounded interactive p99,
# an all-warm zero-drop rollout, and graceful-drain scale down. The
# 8-seed kill-phase sweep lives in tests/test_fleet.py behind -m slow.
if [[ "${TIER1_FLEET:-1}" != "0" ]]; then
    timeout -k 10 240 env JAX_PLATFORMS=cpu \
        python tools/chaos_soak.py --fleet \
        --duration "${TIER1_FLEET_S:-6}" --clients 64
    fleet_rc=$?
    if [[ "$rc" -eq 0 && "$fleet_rc" -ne 0 ]]; then
        rc=$fleet_rc
    fi
fi
# Continuous-batching soak smoke (TIER1_CB=1 to enable): a
# ContinuousEngine over 8 slots takes ~4s of mixed-length traffic (two
# always-on 48-token batch-class decode lanes + interactive shorts) and
# a fatal serve:decode sub-leg — asserts no interactive short ever waits
# more than one scheduler iteration for admission (no head-of-line
# blocking), exactly-once settlement, zero recompiles across hundreds of
# admit/retire cycles, full KV-page recycling, and per-request fault
# isolation. The assertion-level suite is tests/test_continuous_batching.py.
if [[ "${TIER1_CB:-0}" != "0" ]]; then
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
        python tools/chaos_soak.py --cb --duration "${TIER1_CB_S:-4}"
    cb_rc=$?
    if [[ "$rc" -eq 0 && "$cb_rc" -ne 0 ]]; then
        rc=$cb_rc
    fi
fi
# Static-analysis gate (TIER1_LINT=0 to skip): tools/mxlint over the
# whole tree — lock-order cycles (L001), blocking calls under held locks
# (L002), flag/fault-site/counter registry drift (L003), and thread
# hygiene (L004). Exits nonzero on any finding not covered by
# tools/mxlint/baseline.json; see TOOLING.md for the rule catalog.
if [[ "${TIER1_LINT:-1}" != "0" ]]; then
    timeout -k 10 120 env JAX_PLATFORMS=cpu \
        python -m tools.mxlint mxnet_tpu tools bench.py
    lint_rc=$?
    if [[ "$rc" -eq 0 && "$lint_rc" -ne 0 ]]; then
        rc=$lint_rc
    fi
fi
# Lockdep pass (TIER1_LOCKDEP=0 to skip): re-run the serve smoke and the
# fleet + continuous-batching soaks with the runtime lock-order
# sanitizer on (MXNET_LOCKDEP=1). Every threading.Lock/RLock/Condition
# created after startup is wrapped; the sanitizer records the
# acquisition-order graph, dumps any cycle or blocking-under-lock
# violation through the flight recorder, and smoke_gate() escalates the
# exit status on cycles (the LOCKDEP= summary line is printed either
# way).
if [[ "${TIER1_LOCKDEP:-1}" != "0" ]]; then
    timeout -k 10 120 env JAX_PLATFORMS=cpu MXNET_LOCKDEP=1 \
        python tools/serve_smoke.py
    ld_rc=$?
    if [[ "$rc" -eq 0 && "$ld_rc" -ne 0 ]]; then
        rc=$ld_rc
    fi
    timeout -k 10 240 env JAX_PLATFORMS=cpu MXNET_LOCKDEP=1 \
        python tools/chaos_soak.py --fleet \
        --duration "${TIER1_FLEET_S:-6}" --clients 64
    ld_rc=$?
    if [[ "$rc" -eq 0 && "$ld_rc" -ne 0 ]]; then
        rc=$ld_rc
    fi
    timeout -k 10 180 env JAX_PLATFORMS=cpu MXNET_LOCKDEP=1 \
        python tools/chaos_soak.py --cb --duration "${TIER1_CB_S:-4}"
    ld_rc=$?
    if [[ "$rc" -eq 0 && "$ld_rc" -ne 0 ]]; then
        rc=$ld_rc
    fi
fi
# SLO smoke (TIER1_SLO=1 to enable): the healthy 32-client serve smoke
# with a declarative SLO monitor attached (itl/ttft p99, goodput,
# error-rate burn objectives) — asserts no objective burns, the monitor
# health stays "ok", and the flight recorder produces zero slo_burn
# dumps (the guard's false-positive contract). Re-run under
# MXNET_LOCKDEP=1: the monitor's observe/evaluate path runs on the
# metrics-observing threads and must stay cycle-free.
if [[ "${TIER1_SLO:-0}" != "0" ]]; then
    timeout -k 10 120 env JAX_PLATFORMS=cpu \
        python tools/serve_smoke.py --slo
    slo_rc=$?
    if [[ "$rc" -eq 0 && "$slo_rc" -ne 0 ]]; then
        rc=$slo_rc
    fi
    timeout -k 10 120 env JAX_PLATFORMS=cpu MXNET_LOCKDEP=1 \
        python tools/serve_smoke.py --slo
    slo_rc=$?
    if [[ "$rc" -eq 0 && "$slo_rc" -ne 0 ]]; then
        rc=$slo_rc
    fi
fi
# Perf-regression gate (TIER1_PERFGUARD=1 to enable): the spread-aware
# gate over the checked-in BENCH_r*/MULTICHIP_r* history
# (tools/perf_regression.py). With TIER1_PERFGUARD_FRESH=<file> the
# gate compares that fresh bench emission against the full history;
# without it the newest checked-in round plays the candidate
# (self-check — must stay green on the committed files). The tool
# SKIPs cleanly (exit 0) when there is nothing to compare.
if [[ "${TIER1_PERFGUARD:-0}" != "0" ]]; then
    if [[ -n "${TIER1_PERFGUARD_FRESH:-}" ]]; then
        timeout -k 10 60 python tools/perf_regression.py \
            --fresh "$TIER1_PERFGUARD_FRESH"
    else
        timeout -k 10 60 python tools/perf_regression.py
    fi
    perf_rc=$?
    if [[ "$rc" -eq 0 && "$perf_rc" -ne 0 ]]; then
        rc=$perf_rc
    fi
fi
# Collective overlap smoke (TIER1_OVERLAP=1 to enable): a dp4 training
# loop with gradient bucketing + overlapped priority-ordered flushes on
# (MXNET_KVSTORE_BUCKET_MB / MXNET_KVSTORE_OVERLAP) — asserts bitwise
# parameter parity vs the unbucketed baseline, zero steady-state
# recompiles at every ablation point, front-first bucket settle order,
# and bounded 2-bit compression divergence. The assertion-level suite is
# tests/test_bucketing.py.
if [[ "${TIER1_OVERLAP:-0}" != "0" ]]; then
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
        python tools/overlap_smoke.py
    overlap_rc=$?
    if [[ "$rc" -eq 0 && "$overlap_rc" -ne 0 ]]; then
        rc=$overlap_rc
    fi
fi
# Elastic soak smoke (TIER1_ELASTIC=0 to skip): one seeded
# kill/lag/corrupt sweep through a dp8 training loop — asserts the
# chip-loss dp8->dp4 resume lands bitwise on the dp4 reference run,
# straggler blame, and desync detection within the audit cadence. The
# full 8-seed sweep lives in tests/test_elastic.py behind -m slow.
if [[ "${TIER1_ELASTIC:-1}" != "0" ]]; then
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
        python tools/elastic_soak.py --seeds "${TIER1_ELASTIC_SEEDS:-1}"
    elastic_rc=$?
    if [[ "$rc" -eq 0 && "$elastic_rc" -ne 0 ]]; then
        rc=$elastic_rc
    fi
fi
# Composed-mesh elastic smoke (TIER1_ELASTIC3D=1 to enable): the
# kill-one-chip dp2xtp2 leg alone — a coordinate-addressed chip_loss
# rebuilds the mesh to dp1xtp2 (tp extent pinned, touched dp-group
# dropped) and reshards the layout-carrying sharded checkpoint onto the
# survivors; asserts no MeshDegraded escapes and the resumed run lands
# bitwise on a clean dp1xtp2 run from the same checkpoint. Re-run under
# MXNET_LOCKDEP=1: recovery walks checkpoint-manager and mesh-registry
# locks from the failure path and must stay cycle-free.
if [[ "${TIER1_ELASTIC3D:-0}" != "0" ]]; then
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
        python tools/elastic_soak.py --legs 3d \
        --seeds "${TIER1_ELASTIC_SEEDS:-1}"
    e3d_rc=$?
    if [[ "$rc" -eq 0 && "$e3d_rc" -ne 0 ]]; then
        rc=$e3d_rc
    fi
    timeout -k 10 180 env JAX_PLATFORMS=cpu MXNET_LOCKDEP=1 \
        python tools/elastic_soak.py --legs 3d \
        --seeds "${TIER1_ELASTIC_SEEDS:-1}"
    e3d_rc=$?
    if [[ "$rc" -eq 0 && "$e3d_rc" -ne 0 ]]; then
        rc=$e3d_rc
    fi
fi
# Preemption smoke (TIER1_PREEMPT=1 to enable): interrupt a training
# epoch mid-way via the deterministic preempt:deliver site (the
# SIGTERM-equivalent), force-save through the async checkpoint writer,
# resume in a fresh estimator/iterator — asserts the epoch's sample
# sequence is consumed exactly once across the cut and the final params
# land bitwise on the uninterrupted reference. The assertion-level suite
# is tests/test_preemption.py.
if [[ "${TIER1_PREEMPT:-0}" != "0" ]]; then
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
        python tools/preempt_smoke.py --seeds "${TIER1_PREEMPT_SEEDS:-1}"
    preempt_rc=$?
    if [[ "$rc" -eq 0 && "$preempt_rc" -ne 0 ]]; then
        rc=$preempt_rc
    fi
fi
# Input-pipeline smoke (TIER1_DATA=1 to enable): a synthetic crc-indexed
# .rec streamed through sharded RecordPipelines ×4 decode workers under
# a seeded io:read plan (transient + torn + worker kill) — asserts
# exactly-once sample delivery (delivered ∪ quarantined, no dupes, kill
# requeued + respawned), worker-count-independent delivery order,
# sample-exact 2->1 reshard resume, zero recompiles through the
# DeviceFeeder double-buffer, and the io.* export surface. Re-run under
# MXNET_LOCKDEP=1: the worker pool's queue/lock traffic must stay
# cycle-free with no blocking calls under the pipeline lock.
if [[ "${TIER1_DATA:-0}" != "0" ]]; then
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
        python tools/data_smoke.py
    data_rc=$?
    if [[ "$rc" -eq 0 && "$data_rc" -ne 0 ]]; then
        rc=$data_rc
    fi
    timeout -k 10 180 env JAX_PLATFORMS=cpu MXNET_LOCKDEP=1 \
        python tools/data_smoke.py
    data_rc=$?
    if [[ "$rc" -eq 0 && "$data_rc" -ne 0 ]]; then
        rc=$data_rc
    fi
fi
exit "$rc"
