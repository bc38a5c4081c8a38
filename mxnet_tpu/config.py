"""Environment-flag registry with introspection.

The reference configures itself through ~100 ``MXNET_*`` env vars read via
``dmlc::GetEnv`` at use sites, documented centrally in
``docs/.../env_var.md``, plus self-describing ``dmlc::Parameter`` structs.
This module is the TPU build's equivalent: every flag the framework reads
is registered here with its type, default, and doc, and
``mx.config.describe()`` prints the live table (value, source) the way
``__getdoc__`` exposes Parameter fields.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict, NamedTuple


class Flag(NamedTuple):
    name: str
    default: Any
    doc: str
    parse: Callable[[str], Any]


_FLAGS: Dict[str, Flag] = {}


def _bool(s: str) -> bool:
    return s not in ("0", "false", "False", "")


def register_flag(name, default, doc, parse=str):
    _FLAGS[name] = Flag(name, default, doc, parse)
    return _FLAGS[name]


def get(name):
    """Typed value of a registered flag (env wins over default)."""
    flag = _FLAGS[name]
    raw = os.environ.get(name)
    if raw is None:
        return flag.default
    return flag.parse(raw)


def is_set(name) -> bool:
    return name in os.environ


def list_flags():
    """All registered flag names (env_var.md table analog)."""
    return sorted(_FLAGS)


def describe(file=None):
    """Print name / current value / default / doc for every flag."""
    import sys

    out = file or sys.stdout
    for name in list_flags():
        f = _FLAGS[name]
        cur = get(name)
        src = "env" if is_set(name) else "default"
        print(f"{name} = {cur!r} ({src}; default {f.default!r})\n"
              f"    {f.doc}", file=out)


# ---------------------------------------------------------------------------
# The flags this framework reads (each registered next to its semantics;
# reference: docs/static_site/src/pages/api/faq/env_var.md)
# ---------------------------------------------------------------------------

register_flag(
    "MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice",
    "Execution engine. 'NaiveEngine' blocks after every op (serialized "
    "debugging, reference src/engine/naive_engine.cc); the default maps to "
    "XLA async dispatch.")
register_flag(
    "MXNET_EAGER_JIT_CACHE", True,
    "Cache one jax.jit executable per (op, static config) for imperative "
    "dispatch (SURVEY §7 hard part 2). 0 disables.", _bool)
register_flag(
    "MXNET_WAITALL_FULL", False,
    "mx.npx.waitall() sweeps every live array (exhaustive, slow) instead "
    "of the recently-dispatched set.", _bool)
register_flag(
    "MXNET_TPU_PEAK_FLOPS", None,
    "Override the chip peak FLOP/s used as the MFU denominator in "
    "bench.py (default: by device_kind).",
    float)
register_flag(
    "MXNET_TPU_NO_NATIVE", False,
    "Disable the ctypes native library (native/recordio.cc prefetcher); "
    "pure-Python fallbacks are used.", _bool)
register_flag(
    "MXNET_TPU_COORDINATOR", None,
    "host:port of process 0 for jax.distributed.initialize; set by "
    "tools/launch.py (reference DMLC_PS_ROOT_URI/PORT).")
register_flag(
    "MXNET_TPU_NUM_PROCS", None,
    "World size for multi-process SPMD (reference DMLC_NUM_WORKER).", int)
register_flag(
    "MXNET_TPU_PROC_ID", None,
    "This process's rank (reference DMLC_WORKER_ID).", int)
register_flag(
    "MXNET_RNG_IMPL", "rbg",
    "JAX PRNG implementation (rbg / unsafe_rbg / threefry2x32). rbg "
    "drives the chip's hardware RNG for bulk bits (3x faster dropout "
    "masks on v5e); threefry2x32 restores bitwise key-stream "
    "reproducibility across backends. Read at import, before config "
    "is loadable.")
register_flag(
    "MXNET_LOCKDEP", False,
    "Runtime lock-order sanitizer (resilience.lockdep): instruments "
    "threading.Lock/RLock/Condition, records the acquisition-order "
    "graph, reports cycles and blocking-under-lock through the flight "
    "recorder. Off = nothing is patched (zero overhead).", _bool)
register_flag(
    "MXNET_PROFILER_AUTOSTART", False,
    "Start the telemetry event bus (mxnet_tpu.profiler) at import; "
    "reference MXNET_PROFILER_AUTOSTART contract.", _bool)
register_flag(
    "MXNET_PROFILER_IMPERATIVE", False,
    "Opt into per-op imperative dispatch counters "
    "(profiler.set_config(profile_imperative=True)).", _bool)
register_flag(
    "MXNET_CACHEDOP_SIG_LIMIT", 16,
    "Distinct-signature count above which one CachedOp warns about a "
    "recompile storm (varying shapes/dtypes/static args defeating the "
    "executable cache).", int)
register_flag(
    "MXNET_FAULT_PLAN", None,
    "Fault-injection plan for the resilience subsystem: inline JSON or "
    "@/path/to/plan.json (mxnet_tpu.resilience.faults docstring has the "
    "schema). Installed lazily on first use; unset disables injection.")
register_flag(
    "MXNET_COLLECTIVE_TIMEOUT", 0.0,
    "Seconds before the dist_tpu collective watchdog declares a hung "
    "collective and raises CollectiveTimeoutError (then the circuit "
    "breaker degrades to the eager fallback). 0 disables the watchdog "
    "(zero overhead).", float)
register_flag(
    "MXNET_COMPILE_MAX_RETRIES", 2,
    "Extra attempts for a transiently-failing XLA compile (CachedOp "
    "build, dist_tpu AOT lower().compile()).", int)
register_flag(
    "MXNET_COLLECTIVE_MAX_RETRIES", 2,
    "Extra attempts for a transiently-failing dist_tpu collective before "
    "it counts as a fast-path failure (degradation + breaker).", int)
register_flag(
    "MXNET_RETRY_BASE_DELAY_MS", 5.0,
    "First retry backoff delay in ms; doubles per attempt.", float)
register_flag(
    "MXNET_RETRY_MAX_DELAY_MS", 250.0,
    "Backoff delay ceiling in ms.", float)
register_flag(
    "MXNET_COLLECTIVE_BREAKER_THRESHOLD", 3,
    "Consecutive dist_tpu fast-path failures that trip the circuit "
    "breaker open (eager fallback only until cooldown).", int)
register_flag(
    "MXNET_COLLECTIVE_BREAKER_COOLDOWN", 8,
    "Fast-path queries the breaker stays open before letting one "
    "half-open probe re-test the collective path.", int)
register_flag(
    "MXNET_NAN_QUARANTINE", False,
    "Pre-collective non-finite sentinel in dist_tpu.allreduce: a gradient "
    "with NaN/Inf is caught BEFORE it poisons the whole mesh's allreduce. "
    "Costs one fused isfinite reduction + host sync per reduced tensor, "
    "so off by default.", _bool)
register_flag(
    "MXNET_NAN_QUARANTINE_MODE", "skip",
    "What the quarantine does on trip: 'skip' raises NonFiniteGradError "
    "(GuardrailHandler turns it into a skipped step); 'drop' excludes the "
    "poisoned replicas and sums the clean ones, rescaled by "
    "n_total/n_clean to keep the expected gradient magnitude.")
register_flag(
    "MXNET_GUARDRAIL_SPIKE_WINDOW", 32,
    "Rolling-window length for the guardrail loss-spike detector "
    "(resilience.guardrails.SpikeDetector).", int)
register_flag(
    "MXNET_GUARDRAIL_SPIKE_ZSCORE", 6.0,
    "Z-score over the rolling window above which a loss value counts as "
    "a spike (plus a 2x relative-jump floor for flat windows).", float)
register_flag(
    "MXNET_GUARDRAIL_WARMUP", 8,
    "Steps the spike detector only builds statistics for before it may "
    "flag (the initial loss cliff is expected, not an anomaly).", int)
register_flag(
    "MXNET_GUARDRAIL_MAX_SKIPS", 3,
    "Consecutive guardrail skip-steps before escalation to "
    "rewind-and-skip (GuardrailHandler).", int)
register_flag(
    "MXNET_GUARDRAIL_MAX_REWINDS", 2,
    "Rewind-and-skip recoveries before GuardrailHandler gives up and "
    "raises DivergenceError.", int)
register_flag(
    "MXNET_SERVE_BATCH_TIMEOUT_MS", 5.0,
    "DynamicBatcher flush deadline: an admitted request waits at most this "
    "long for batch-mates before the partial batch dispatches "
    "(mxnet_tpu.serve.batcher).", float)
register_flag(
    "MXNET_SERVE_MAX_BATCH", 8,
    "DynamicBatcher flush size: a batch dispatches immediately once this "
    "many requests are queued (should match the serving session's largest "
    "batch bucket).", int)
register_flag(
    "MXNET_SERVE_MAX_QUEUE", 64,
    "Admission-control cap on DynamicBatcher queue depth: submissions "
    "beyond it fast-reject with ServiceUnavailable (503) instead of "
    "building an unbounded backlog.", int)
register_flag(
    "MXNET_SERVE_TIMEOUT_MS", 0.0,
    "Per-execution watchdog for serve.InferenceSession: a hung executable "
    "becomes a fast ServiceUnavailable (503) after this many ms instead "
    "of wedging the serving thread. 0 disables (zero overhead).", float)
register_flag(
    "MXNET_SERVE_BREAKER_THRESHOLD", 3,
    "Consecutive InferenceSession execution failures that trip the "
    "session circuit breaker open (requests fast-reject until cooldown).",
    int)
register_flag(
    "MXNET_SERVE_BREAKER_COOLDOWN", 8,
    "Rejected calls the serve breaker stays open before letting one "
    "half-open probe re-test the session.", int)
register_flag(
    "MXNET_SERVE_METRICS_WINDOW", 2048,
    "Ring-buffer sample count backing the serve p50/p95/p99 latency "
    "percentiles (serve.metrics).", int)
register_flag(
    "MXNET_SERVE_DEADLINE_MS", 0.0,
    "Default request deadline attached at DynamicBatcher.submit when the "
    "caller passes none: expired requests are cancelled at every stage "
    "boundary (admission, queue sweep, post-execute settle) with "
    "DeadlineExceeded (504) instead of completing late. 0 disables — no "
    "deadline checks anywhere (the original semantics).", float)
register_flag(
    "MXNET_SERVE_DEADLINE_GRACE_MS", 0.0,
    "Slack past a request's deadline within which a completed result is "
    "still delivered (counted as a late_completion against goodput); "
    "beyond deadline+grace the result is discarded and the future "
    "settles with DeadlineExceeded.", float)
register_flag(
    "MXNET_SERVE_BATCH_QUEUE_SHARE", 1.0,
    "Fraction of MXNET_SERVE_MAX_QUEUE the batch priority class may "
    "occupy; batch-class submits beyond it shed with 503 so interactive "
    "traffic always finds queue headroom. 1.0 (default) reserves "
    "nothing.", float)
register_flag(
    "MXNET_SERVE_RATE_LIMIT", 0.0,
    "Token-bucket refill rate (requests/s) gating batch-class admission "
    "in DynamicBatcher.submit; interactive traffic is never rate-"
    "limited. 0 disables the bucket.", float)
register_flag(
    "MXNET_SERVE_RATE_BURST", 16,
    "Token-bucket capacity for MXNET_SERVE_RATE_LIMIT: the batch-class "
    "burst admitted from an idle bucket before the rate applies.", int)
register_flag(
    "MXNET_SERVE_STRICT_PARITY", False,
    "Pin serve.Generator to the PR-5 strict decode path: shape-stable "
    "mul+reduce ops, bitwise prefill/decode parity, overriding any decode_path argument or "
    "MXNET_SERVE_DECODE_PATH. Off (default): the fast rungs carry a "
    "tolerance-based parity contract instead.", _bool)
register_flag(
    "MXNET_SERVE_DECODE_PATH", "auto",
    "Default decode rung for serve.Generator when the constructor passes "
    "none: auto (= pallas), baseline (strict PR-5 ops), pallas (fused "
    "decode-attention kernel), int8 (pallas + int8 KV-cache rings and "
    "weights).", str)
register_flag(
    "MXNET_SERVE_DECODE_INT8_WEIGHTS", "auto",
    "On the int8 decode rung, also pre-quantize the model's serving "
    "projection weights to per-channel int8 (ops.nn.quantized_dense). "
    "auto (default): only on backends with int8 matrix units (tpu) "
    "— on CPU the per-step int8->f32 weight convert costs more than the "
    "f32 gemm saves, so auto keeps weights f32 there. 1/0 force it "
    "on/off; the KV-cache rings stay int8 either way.", str)
register_flag(
    "MXNET_SERVE_KV_PAGED", False,
    "Back serve.Generator KV state with the paged block pool "
    "(serve.kv_blocks.PagedKVPool, fully assigned) instead of contiguous "
    "per-bucket rings. serve.scheduler.ContinuousEngine is always paged "
    "regardless of this flag.", _bool)
register_flag(
    "MXNET_SERVE_KV_PAGE_SIZE", 0,
    "KV page width in tokens for the paged block allocator. 0 (default): "
    "the Pallas decode kernel's natural block (128) clamped to max_seq, "
    "so the kernel's block-skip masking skips whole unreached pages. "
    "max_seq must be a whole number of pages.", int)
register_flag(
    "MXNET_SERVE_KV_PAGES", 0,
    "Paged-KV pool capacity in pages (including the reserved null page). "
    "0 (default): auto-size to full capacity — every slot can hold "
    "max_seq and exhaustion is impossible. Smaller values oversubscribe: "
    "admission queues on PoolExhausted (503) until retirements recycle "
    "pages.", int)
register_flag(
    "MXNET_SERVE_SLOTS", 8,
    "Decode lanes for serve.scheduler.ContinuousEngine: the ONE compiled "
    "decode width. Requests are admitted into free lanes and retired "
    "from finished ones between decode steps; idle lanes ride along on "
    "the null KV page.", int)
register_flag(
    "MXNET_SERVE_PREFILL_CHUNK", 0,
    "Prompt tokens prefilled per continuous-batching scheduler iteration "
    "at the fixed (1, chunk) signature. 0 (default): one KV page. "
    "Bounds how long a long prompt can stall live decode streams (one "
    "chunk per iteration).", int)
register_flag(
    "MXNET_SERVE_PREFIX_CACHE", False,
    "Cross-request KV prefix reuse (serve.prefix_cache.PrefixCache): a "
    "radix trie over prompt token ids maps matched prefixes to "
    "refcounted pages in the paged KV pool, so admission skips the "
    "matched portion of chunked prefill. Shared pages are read-only "
    "(copy-on-extend at page granularity); LRU eviction reclaims cached "
    "prefixes only under pool pressure. Greedy outputs stay "
    "token-identical to a cache-off run.", _bool)
register_flag(
    "MXNET_COMPILE_CACHE_DIR", "",
    "Directory backing the persistent compile cache "
    "(mxnet_tpu.compile_cache, JAX persistent compilation cache "
    "substrate): executables keyed on the stable serialization of "
    "CachedOp signature keys + compiler options land on disk, so "
    "warmup() in a fresh process replays the bucket lattice from disk "
    "instead of recompiling (cache_stats() grows disk_hits/disk_misses). "
    "Empty (default) disables. JAX_COMPILATION_CACHE_DIR, when set, "
    "places the cache instead and this flag has no say.", str)
register_flag(
    "MXNET_SERVE_MAX_MODELS", 4,
    "Resident-model budget for serve.tenancy.ModelRegistry: at most "
    "this many named models (executables + per-tenant KV pool + prefix "
    "trie) stay loaded per process; loading past the budget LRU-evicts "
    "the coldest idle tenant. Evicted models reload via load() — warm "
    "from the disk compile cache when MXNET_COMPILE_CACHE_DIR is "
    "set.", int)
register_flag(
    "MXNET_SERVE_SPEC_TOKENS", 4,
    "Draft tokens proposed per speculative-decoding round "
    "(serve.SpeculativeGenerator's default k): each round costs k draft "
    "steps plus one k+1-wide target verify step.", int)
register_flag(
    "MXNET_SERVE_MULTISTEP", False,
    "Run the decode loop as device-side multi-step super-steps: one "
    "compiled lax.while_loop executes up to MXNET_SERVE_DECODE_STEPS "
    "decode iterations (model forward + in-trace sampling + EOS/budget "
    "masking) per host visit, and the host settles the returned "
    "(slots, N) token block in one pass. Off (default): one host visit "
    "per token (the PR-10 behavior).", _bool)
register_flag(
    "MXNET_SERVE_DECODE_STEPS", 8,
    "Decode iterations per multi-step super-step (the compiled loop's "
    "static trip-count ceiling N). The host can lower the per-call "
    "limit down to 1 through the same executable — tight deadlines "
    "auto-degrade to single-step so 504 retirement latency stays "
    "bounded by one iteration.", int)
register_flag(
    "MXNET_FLEET_HEDGE_MS", 0.0,
    "Hedged-retry delay for serve.fleet.Router: an *interactive* request "
    "dispatched to a replica flagged straggling gets a second (hedge) "
    "dispatch to the next-best replica after this many ms unless it has "
    "already settled; first settle wins, the loser is cancelled and "
    "counted. Batch-class requests are never hedged, and a request is "
    "never hedged twice. 0 (default) disables hedging.", float)
register_flag(
    "MXNET_FLEET_STRAGGLER_MS", 150.0,
    "Per-replica latency-lag EWMA (vs the fleet median, "
    "resilience.elastic.StragglerMonitor) above which the Router flags a "
    "replica as straggling — the precondition for arming a hedge timer. "
    "0: track only, never flag (hedging never fires).", float)
register_flag(
    "MXNET_FLEET_MAX_FAILOVERS", 2,
    "Times the Router will re-dispatch one request to a surviving "
    "replica after replica deaths/quarantines before failing it with "
    "ServiceUnavailable (bounds the work a poisonous request can burn "
    "while the fleet is melting).", int)
register_flag(
    "MXNET_FLEET_PROBE_MS", 25.0,
    "Router supervisor probe interval: how often each replica's "
    "liveness (flusher thread) and session breaker are checked so a "
    "replica that died *between* dispatches is still detected and its "
    "in-flight work failed over. 0 disables the supervisor thread "
    "(detection then only happens at dispatch boundaries).", float)
register_flag(
    "MXNET_FLEET_BREAKER_THRESHOLD", 2,
    "Consecutive replica-attributed dispatch/settle failures that "
    "quarantine a replica behind the Router's per-replica circuit "
    "breaker (dispatch routes around it until a half-open probe "
    "heals it).", int)
register_flag(
    "MXNET_FLEET_BREAKER_COOLDOWN", 8,
    "Dispatch picks a quarantined replica sits out before the Router's "
    "per-replica breaker goes half-open and routes one probe request "
    "through it.", int)
register_flag(
    "MXNET_ELASTIC", False,
    "Elastic multichip training (resilience.elastic): dist_tpu classifies "
    "collective failures that look like a LOST DEVICE GROUP (injected "
    "chip_loss, dead-peer runtime errors) as MeshDegraded instead of "
    "degrading to the eager fallback, so an ElasticTrainingHandler can "
    "shrink the mesh and resume from a sharded checkpoint. Off (default): "
    "every failure keeps the PR-2 degrade/retry semantics bitwise.", _bool)
register_flag(
    "MXNET_ELASTIC_MAX_RESTARTS", 2,
    "Mesh-loss restarts an ElasticTrainingHandler absorbs before "
    "re-raising MeshDegraded (a mesh shedding chips repeatedly is a "
    "hardware incident, not a recoverable blip).", int)
register_flag(
    "MXNET_ELASTIC_MIN_REPLICAS", 1,
    "Fewest surviving data-parallel replicas an elastic restart will "
    "resume on; fewer survivors re-raises MeshDegraded.", int)
register_flag(
    "MXNET_ELASTIC_REBUILD", True,
    "Composed-mesh (dp×tp(×pp)) elasticity: on chip loss, "
    "ElasticTrainingHandler.recover_sharded rebuilds the mesh with "
    "parallel.mesh.rebuild_mesh (tp/pp extents pinned, touched dp-groups "
    "dropped) and reshards the newest layout-carrying sharded checkpoint "
    "onto the survivors. 0: composed-mesh losses re-raise (the pre-rebuild "
    "degrade path), pure-dp shrink_mesh elasticity is unaffected.", _bool)
register_flag(
    "MXNET_ELASTIC_MIN_DP_GROUPS", 1,
    "Fewest surviving data-parallel GROUPS (dp extent of the rebuilt "
    "composed mesh) recover_sharded will resume on; fewer survivors "
    "re-raises the mesh loss.", int)
register_flag(
    "MXNET_DESYNC_CHECK_STEPS", 0,
    "Cadence (in batches) of the cross-replica parameter-fingerprint "
    "desync audit (resilience.elastic.DesyncAuditHandler). 0 (default) "
    "disables the audit — one int compare per batch.", int)
register_flag(
    "MXNET_DESYNC_MAX_RESYNCS", 2,
    "Resync-from-peer repairs the desync audit performs before "
    "escalating to rewind (then DivergenceError).", int)
register_flag(
    "MXNET_STRAGGLER_THRESHOLD_MS", 0.0,
    "Per-replica collective-arrival-lag EWMA (ms) above which the "
    "straggler monitor flags a replica (resilience.stragglers counter + "
    "rate-limited warning). 0 (default): tracking-only, never flags.",
    float)
register_flag(
    "MXNET_CKPT_ASYNC", False,
    "Async checkpointing (resilience.checkpoint): CheckpointManager.save "
    "stalls only for the synchronous host snapshot of params/trainer/"
    "data state, then packs, CRCs and atomically writes on a background "
    "thread; the generation is advertised only after its commit lands, "
    "and every manager read fences on the in-flight write. Off "
    "(default): the whole save happens in the caller (PR-4 semantics).",
    _bool)
register_flag(
    "MXNET_CKPT_STALL_BUDGET_MS", 0.0,
    "Budget (ms) for an async save's synchronous stall (the host "
    "snapshot). Exceeding it counts resilience.ckpt_stall_overruns and "
    "warns, rate-limited — the stall is the part the step loop actually "
    "feels, so overruns mean the snapshot itself got too slow. 0 "
    "(default): unbudgeted.", float)
register_flag(
    "MXNET_PREEMPT_GRACE_S", 30.0,
    "Grace window (seconds) a preempted process has to drain "
    "(resilience.preemption): the serving-side drain (fleet Routers, "
    "registered batchers) is bounded by it; training uses it as the "
    "budget between the SIGTERM and the force-saved checkpoint's "
    "commit.", float)
register_flag(
    "MXNET_LOSS_SCALE_MIN", 1.0,
    "Lower clamp for the dynamic LossScaler (amp.py): repeated overflows "
    "can never drive the scale to 0.", float)
register_flag(
    "MXNET_LOSS_SCALE_MAX", 2.0 ** 24,
    "Upper clamp for the dynamic LossScaler: a long overflow-free run "
    "can never drive the scale to inf.", float)
register_flag(
    "MXNET_TRACE", False,
    "Enable request-scoped tracing (profiler.trace): serving submits and "
    "training steps get per-request Trace ids whose spans are emitted as "
    "chrome async/flow events when the profiler bus records. Off: one "
    "bool check per instrumented site.", _bool)
register_flag(
    "MXNET_TRACE_MAX", 1024,
    "Bounded in-process trace registry size (oldest traces evicted); the "
    "profiler.trace.summary(trace_id) lookback window.", int)
register_flag(
    "MXNET_FLIGHT_RECORDER", True,
    "Always-on flight recorder (profiler.recorder): a bounded ring of "
    "recent warnings/faults/escalations dumped to JSON automatically at "
    "DivergenceError / MeshDegraded / checkpoint quarantine / "
    "breaker-open / watchdog timeout. 0 disables (ring writes become one "
    "bool check).", _bool)
register_flag(
    "MXNET_FLIGHT_RECORDER_SIZE", 512,
    "Flight-recorder ring capacity (most recent N notes kept).", int)
register_flag(
    "MXNET_FLIGHT_RECORDER_DIR", None,
    "Directory for automatic flight-recorder dumps "
    "(flightrec-<utc>-<reason>.json). Default: the system tempdir.")
register_flag(
    "MXNET_FLIGHT_RECORDER_MAX_DUMPS", 16,
    "Per-process cap on automatic flight-recorder dump files (first "
    "escalations win; later ones only land in the ring).", int)
register_flag(
    "MXNET_KVSTORE_BUCKET_MB", 0.0,
    "Coalesce per-parameter collectives into flat fusion buffers of this "
    "many MB (kvstore.bucketing.GradBucketer): gradient pushpull in "
    "gluon.Trainer and the ZeRO param all-gathers in ShardedTrainer both "
    "collapse to one collective per bucket. 0 (default): per-parameter "
    "collectives, the pre-bucketing behavior.", float)
register_flag(
    "MXNET_KVSTORE_OVERLAP", True,
    "With bucketing on, dispatch every bucket's collective async "
    "(front-layer buckets first) and let the engine overlap them with "
    "compute; 0 blocks after each bucket flush — the ablation baseline, "
    "not a correctness knob (both settings are bitwise-identical).",
    _bool)
register_flag(
    "MXNET_GRADIENT_COMPRESSION", "",
    "Gradient compression for dist_tpu pushpull: '2bit' quantizes every "
    "pushed grad to {-threshold, 0, +threshold} with per-(key, replica) "
    "error-feedback residuals (kvstore.gradient_compression). Empty "
    "(default): off — compression is approximate; opt in per run.")
register_flag(
    "MXNET_METRICS_PORT", 0,
    "Serve the unified telemetry surface (profiler.export) over stdlib "
    "HTTP on this port: /metrics (Prometheus text), /healthz (serving "
    "health JSON), /snapshot (full JSON). Unset (default): no server. "
    "Explicitly set to 0: bind an EPHEMERAL port (no CI port-collision "
    "flakes) and report it back via a MXNET_METRICS_PORT_BOUND=<port> "
    "line on stderr + profiler.export.server_port().", int)
register_flag(
    "MXNET_ATTRIBUTION", False,
    "Decode critical-path attribution (profiler.attribution): split "
    "every decode iteration's wall time into host / dispatch / device / "
    "wait phases, tag engine:wait stalls with the active phase, and "
    "publish serve.<name>.host_overhead_fraction / device_ms_per_token "
    "gauges. Off: one bool check per instrumented site.", _bool)
register_flag(
    "MXNET_ATTRIBUTION_WINDOW", 512,
    "Rolling window (decode iterations) of the attribution ledger's "
    "steady-state gauges.", int)
register_flag(
    "MXNET_SLO_WINDOW_S", 60.0,
    "Default slow evaluation window (seconds) for SLO objectives "
    "(profiler.slo.SLO) constructed without an explicit window; the "
    "fast window defaults to 1/12 of it (the SRE 1h/5m shape).", float)
register_flag(
    "MXNET_SLO_BURN_THRESHOLD", 14.4,
    "Default error-budget burn-rate alert threshold: an objective burns "
    "only when BOTH its fast and slow windows exceed this (14.4 is the "
    "classic fast-page rate).", float)
register_flag(
    "MXNET_SLO_EVAL_INTERVAL_S", 0.25,
    "Minimum seconds between passive SLO burn-rate evaluations on the "
    "observing thread (amortizes the window walk).", float)
register_flag(
    "MXNET_SLO_MIN_EVENTS", 12,
    "Minimum fast-window events before an SLO objective may alert — a "
    "sparse healthy run cannot false-alarm.", int)
register_flag(
    "MXNET_IO_WORKERS", 4,
    "Default decode-pool width of io.pipeline.RecordPipeline: named "
    "daemon worker threads pulling record ranges, decoding and "
    "batchifying into the bounded output queue (the reference's "
    "iter_image_recordio_2.cc decode-thread pool).", int)
register_flag(
    "MXNET_IO_QUEUE_DEPTH", 8,
    "Bounded output-queue depth (batches) of the RecordPipeline decode "
    "pool — workers block (backpressure) once this many decoded batches "
    "are waiting for the consumer.", int)
register_flag(
    "MXNET_IO_SHUFFLE_BUFFER", 1024,
    "Window size of the seedable streaming shuffle in RecordPipeline "
    "and ShardedRecordDataset epoch-order draws: records are shuffled "
    "within a sliding window of this many entries (bounded-memory "
    "approximate shuffle; <= 1 disables shuffling beyond epoch seed "
    "order).", int)
register_flag(
    "MXNET_IO_DEVICE_BUFFERS", 2,
    "Batches the io.pipeline.DeviceFeeder keeps device-resident via "
    "async device_put — K=2 double-buffers H2D for batch k+1 under "
    "step k's compute.", int)
register_flag(
    "MXNET_IO_CHECK_INDEX", True,
    "Integrity-check every RecordIO .idx at open (4-byte-aligned, "
    "strictly increasing offsets that fit the .rec size); a corrupt "
    "index raises MXNetError naming the file instead of serving wrong "
    "records. 0 skips the check (e.g. for deliberately exotic "
    "hand-built indexes).", _bool)
