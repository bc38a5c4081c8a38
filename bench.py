"""Headline benchmark suite: training MFU, inference, KVStore bandwidth.

North star (BASELINE.md targets): ResNet-50 + BERT-base *training* at
>=50% MFU with `dist_tpu_sync`/SPMD step, plus KVStore push/pull bandwidth.
Reference protocol: `docs/.../perf.md:252-254` (train_imagenet.py, synthetic
data) and `benchmark_score.py` for inference; V100 fp32 numbers are the
`vs_baseline` denominators (BASELINE.md).

MFU accounting: numerator = XLA `cost_analysis()['flops']` of the compiled
step (exact algebraic FLOPs of the program actually executed), denominator =
chip peak (bf16 MXU rate, by `device_kind`, overridable via
MXNET_TPU_PEAK_FLOPS).

Timing methodology: every measurement runs the SAME loop at two iteration
counts, each ended by an actual host fetch, and takes the difference — the
fetch round-trip, dispatch tails, and any lazy-execution slack cancel
exactly.

Prints one JSON row per metric as it completes; the FINAL line is the
headline (bf16 ResNet-50 training) row with an `extra` dict carrying all
rows, for the driver's single-line parse.

Round-3 findings baked into the rows (per-op device profiles via
profiler.device_op_table):

* ResNet-50 train bs256@224 sits at the efficiency ceiling of XLA's
  conv kernels for these shapes on v5e (round-4 finding,
  exp/conv_chain_probe.py): per-shape isolated measurements put the
  forward 3x3 stage convs at 52-87% MXU and the 1x1 bottleneck pairs at
  22-41%, all below BOTH rooflines. The round-3 "HBM-saturated, bound
  0.294" reading was an artifact: cost-analysis 'bytes accessed' counts
  convolutions at ~2x their fusion-boundary traffic (elementwise: 1.0x),
  so the step's true arithmetic intensity is ~2x the raw figure. Rows
  carry `cost_analysis_mfu_floor` (the raw, conservative figure) and the
  fused row names the real limiter.
* BERT-base seq128 is MXU-bound and hits >=0.5 MFU once per-step host
  dispatch is amortized (`step_n` fused rows): matmul fusions run at ~83%
  of peak; dropout uses the rbg hardware RNG; attention at seq 128 takes
  the XLA path (flash kernel wins only past the ~1024-token crossover).
* Single-dispatch rows pay a per-execute round-trip (0.7-30 ms in
  r1-r3, 117 ms observed in r4); fused rows amortize it 8-16x. Rows whose
  rtt_ms
  exceeds WEATHER_RTT_THRESHOLD_MS are flagged `weather_dominated` and
  must not be compared across rounds.
* Round-5: the llama long-seq rows are where the Pallas flash kernel is
  ACTIVE in a headline workload (seq 2048/4096 > the 1024-crossover;
  the route is asserted, and each row carries its own XLA-attention
  ablation arm: flash wins 1.7x at seq 2048, 2.4x at 4096 end-to-end).
"""
from __future__ import annotations

import json
import sys
import time

# chip peaks + MFU accounting live in the telemetry subsystem
# (mxnet_tpu/profiler/metrics.py) since the telemetry PR; these are the
# bench-local spellings older rows referenced.
from mxnet_tpu.profiler.metrics import (  # noqa: E402
    TrainingMetrics,
    chip_peak as _chip_peak,
    peak_flops as _peak_flops,
)

BASE_INFER_IMG_S = 1076.81   # V100 fp32 bs32 inference, perf.md:193
BASE_TRAIN_IMG_S = 363.69    # V100 fp32 bs128 training, perf.md:254


def _emit(row):
    # every row carries the unified telemetry snapshot (OBSERVABILITY.md):
    # the cache/collective/serve/resilience counters that explain the
    # number ride along with it instead of needing a re-run to recover
    try:
        from mxnet_tpu.profiler import export as _export

        row["export_snapshot"] = _export.snapshot(include_aggregates=False)
    except Exception as e:  # noqa: BLE001 -- telemetry must not kill a row
        print(f"# export snapshot unavailable: {e}", file=sys.stderr)
    print(json.dumps(row), flush=True)
    return row


_LAST_SAMPLES = None  # per-iteration seconds of the most recent _timed_diff


def _timed_diff(step, fetch, k1, k2, repeats=3):
    """Per-iteration seconds of `step`, by the two-loop difference: run k1
    iterations + fetch, then k2, and divide the extra time by (k2-k1).
    Cancels fetch RTT / lazy-dispatch artifacts.

    Returns the median of ``repeats`` samples; all samples land in
    ``_LAST_SAMPLES`` so rows can report n/spread (r3 verdict item 4:
    a reader must be able to tell regression from host noise)."""
    global _LAST_SAMPLES

    def run(k):
        t0 = time.perf_counter()
        r = None
        for _ in range(k):
            r = step()
        fetch(r)
        return time.perf_counter() - t0
    diffs = []
    for _ in range(repeats):
        d1 = run(k1)
        d2 = run(k2)
        if d2 > d1:
            diffs.append((d2 - d1) / (k2 - k1))
    if not diffs:
        raise RuntimeError(
            f"degenerate timing: {k2}-iter loops never exceeded {k1}-iter "
            f"loops — queue not drained before timing?")
    diffs.sort()
    _LAST_SAMPLES = list(diffs)
    return diffs[len(diffs) // 2]


def _spread(unit_scale=1.0, invert_for=None):
    """n/min/max of the last timing's samples, in the row's own unit.
    ``invert_for=X`` reports X/dt rates (min rate from max dt)."""
    if not _LAST_SAMPLES:
        return {}
    s = sorted(_LAST_SAMPLES)
    if invert_for is not None:
        return {"n": len(s),
                "spread": [round(invert_for / s[-1], 2),
                           round(invert_for / s[0], 2)]}
    return {"n": len(s), "spread": [round(s[0] * unit_scale, 4),
                                    round(s[-1] * unit_scale, 4)]}


_RTT_MS = None

# single-dispatch rows are dispatch-weather-dominated above this RTT: the
# healthy band observed across r1-r3 was 0.7-30 ms; r4 recorded 117 ms
# and its fp32-infer spread swung -47%. Above 10 ms the per-step
# dispatch tax, not the chip, sets the number — such rows must not be
# compared across rounds (PERF.md "Benchmark variance").
WEATHER_RTT_THRESHOLD_MS = 10.0


def _dispatch_meta():
    """rtt_ms + weather_dominated flag for single-dispatch rows, making
    the JSON self-interpreting (r4 verdict Next #7)."""
    rtt = _measure_rtt_ms()
    meta = {"rtt_ms": rtt}
    if rtt is not None:
        meta["weather_dominated"] = bool(rtt > WEATHER_RTT_THRESHOLD_MS)
    return meta


def _memory_meta():
    """Allocator peak SINCE PROCESS START (jax memory_stats never resets),
    from the telemetry subsystem — an upper bound on the row's footprint,
    named accordingly; empty on backends that don't report (CPU)."""
    from mxnet_tpu.profiler.metrics import process_peak_bytes_in_use

    try:
        peak = process_peak_bytes_in_use()
    except Exception:
        peak = 0
    return {"process_peak_hbm_gb": round(peak / 2**30, 2)} if peak else {}


def _measure_rtt_ms():
    """Median host<->device fetch round-trip of a 4-byte scalar: the
    dispatch tax every single-dispatch row pays per step. Reported once per bench run on dispatch-bound rows so their
    variance can be attributed (r3 verdict item 4)."""
    global _RTT_MS
    if _RTT_MS is not None:
        return _RTT_MS
    try:
        import jax
        import jax.numpy as jnp
        import numpy as onp

        x = jnp.zeros(())
        x.block_until_ready()
        ts = []
        for _ in range(7):
            t0 = time.perf_counter()
            onp.asarray(x + 1.0)
            ts.append(time.perf_counter() - t0)
        ts.sort()
        _RTT_MS = round(ts[len(ts) // 2] * 1e3, 2)
    except Exception:
        _RTT_MS = None
    return _RTT_MS


def _chain_diff(run, n_fuse, repeats=3):
    """Two-loop differential timing of a scan-chained dispatch: ``run(m)``
    must execute m chained device iterations and block on a host fetch.
    Times n_fuse- vs 4*n_fuse-iteration dispatches and divides the
    difference — fetch RTT and dispatch tails cancel. Returns
    per-iteration seconds (median of ``repeats``); samples land in
    ``_LAST_SAMPLES`` for the row's n/spread. ONE definition: three bench
    rows share this protocol, and a prior review round caught a bug born
    of it being copy-pasted."""
    import time

    run(n_fuse)          # compile + drain both static signatures
    run(4 * n_fuse)
    diffs = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        run(n_fuse)
        d1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        run(4 * n_fuse)
        d2 = time.perf_counter() - t0
        if d2 > d1:
            diffs.append((d2 - d1) / (3 * n_fuse))
    if not diffs:
        raise RuntimeError("degenerate chained timing")
    diffs.sort()
    global _LAST_SAMPLES
    _LAST_SAMPLES = list(diffs)
    return diffs[len(diffs) // 2]


def _infer_rate_fused(net, x_host, n_fuse=16):
    """Per-inference seconds with n_fuse forwards fused into ONE dispatch
    (lax.scan on device). Single-dispatch inference at bs32 is dispatch-RTT
    bound (~10 ms of dispatch against ~2-5 ms of device work), so the
    un-fused rows under-report the chip; the scan chains each forward on a
    negligible function of the previous logits so XLA cannot elide or
    reorder the iterations."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as onp

    from mxnet_tpu.parallel.functional import functionalize

    apply_fn, params = functionalize(net, train_mode=False)

    @functools.partial(jax.jit, static_argnums=2)
    def run(params, x, m):
        def body(carry, _):
            out = apply_fn(params, x + carry)
            logits = jax.tree_util.tree_leaves(out)[0]
            # serialize iterations: next input nudged by the last logits
            return jnp.mean(logits).astype(x.dtype) * 1e-12, None

        c, _ = jax.lax.scan(body, jnp.zeros((), x.dtype), None, length=m)
        return c

    x = jnp.asarray(x_host)
    return _chain_diff(lambda m: onp.asarray(run(params, x, m)), n_fuse)


def bench_resnet_infer():
    """ResNet-50 v1 fp32 inference, batch 32 — benchmark_score.py protocol
    through the user-facing path: model_zoo net -> hybridize() -> XLA."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu import np as mnp

    BATCH, SIZE = 32, 224
    try:
        ctx = mx.tpu()
        ctx.jax_device()
    except Exception:
        ctx = mx.cpu()

    net = gluon.model_zoo.vision.resnet50_v1()
    net.initialize(ctx=mx.cpu())
    small = mnp.array(onp.zeros((1, 3, 64, 64), dtype="float32"), ctx=mx.cpu())
    with autograd.predict_mode():
        net(small)
    if ctx.device_type != "cpu":
        net.reset_ctx(ctx)
    net.hybridize(static_alloc=True)

    x = mnp.array(
        onp.random.uniform(-1, 1, (BATCH, 3, SIZE, SIZE)).astype("float32"),
        ctx=ctx)
    with autograd.predict_mode():
        net(x).asnumpy()  # compile AND drain (lazy runtime: fetch forces it)
        dt = _timed_diff(lambda: net(x),
                         lambda out: out.asnumpy(), 3, 18)
    img_s = BATCH / dt
    row = _emit({
        "metric": "resnet50_v1_infer_bs32_fp32",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASE_INFER_IMG_S, 3),
        **_dispatch_meta(),
        **_spread(invert_for=BATCH),
    })
    # fused probe AFTER the stable row is out, and non-fatal: a
    # fused-timing flake must not cost the protocol metric
    global _FP32_INFER_FUSED_S
    try:
        with autograd.predict_mode():
            dt_fused = _infer_rate_fused(net, x._data)
        _FP32_INFER_FUSED_S = dt_fused
        _emit({
            "metric": "resnet50_v1_infer_bs32_fp32_fused16",
            "value": round(BATCH / dt_fused, 2),
            "unit": "img/s",
            "vs_baseline": round(BATCH / dt_fused / BASE_INFER_IMG_S, 3),
            **_spread(invert_for=BATCH),
        })
    except Exception as e:
        print(f"# fp32 fused probe failed: {e}", file=sys.stderr)
    return row


_FP32_INFER_FUSED_S = None


def bench_resnet_infer_int8():
    """ResNet-50 INT8 inference, batch 32 (contrib.quantization int8 path;
    v5e MXU int8 peak is 2x bf16). vs_baseline: the V100 fp16 row
    (perf.md:208, 2085.51 img/s) — the reference's reduced-precision
    inference analog."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu import np as mnp
    from mxnet_tpu.contrib.quantization import quantize_net

    BATCH, SIZE = 32, 224
    net = gluon.model_zoo.vision.resnet50_v1()
    net.initialize(ctx=mx.cpu())
    # materialize + calibrate on CPU (eager resnet on the chip would
    # pay per-op dispatch), then move to the chip for the timed int8 path
    with autograd.predict_mode():
        net(mnp.array(onp.zeros((1, 3, 64, 64), dtype="float32"),
                      ctx=mx.cpu()))
    xc = mnp.array(
        onp.random.uniform(-1, 1, (8, 3, SIZE, SIZE)).astype("float32"),
        ctx=mx.cpu())
    # bf16 inter-layer activations: the reference's reduced-precision
    # protocol feeds fp16 inputs to its fp16 rows (perf.md:208); same here
    quantize_net(net, calib_data=xc, calib_mode="naive",
                 activation_dtype="bfloat16")
    try:
        ctx = mx.tpu()
        ctx.jax_device()
        net.reset_ctx(ctx)
    except Exception:
        ctx = mx.cpu()
    x = mnp.array(
        onp.random.uniform(-1, 1, (BATCH, 3, SIZE, SIZE)).astype("float32"),
        ctx=ctx).astype("bfloat16")
    net.hybridize(static_alloc=True)
    with autograd.predict_mode():
        net(x).asnumpy()  # compile + drain
        dt = _timed_diff(lambda: net(x), lambda out: out.asnumpy(), 3, 18)
    img_s = BATCH / dt
    _emit({
        "metric": "resnet50_v1_infer_bs32_int8",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / 2085.51, 3),
        **_dispatch_meta(),
        **_spread(invert_for=BATCH),
    })
    with autograd.predict_mode():
        dt_fused = _infer_rate_fused(net, x._data)
    int8_spread = _spread(invert_for=BATCH)  # snapshot BEFORE any fp32
    # fallback probe below overwrites _LAST_SAMPLES (review finding r4)
    # the perf contract int8 exists for: >=1.5x the fp32 rate measured the
    # same (fused, dispatch-amortized) way — a slower int8 path FAILS the
    # bench rather than shipping a number that quietly lost to fp32. If
    # the fp32 bench didn't leave its fused rate (row order / flake), the
    # gate measures it here rather than silently waiving the contract.
    fp32_s = _FP32_INFER_FUSED_S
    if fp32_s is None:
        fnet = gluon.model_zoo.vision.resnet50_v1()
        fnet.initialize(ctx=mx.cpu())
        with autograd.predict_mode():
            fnet(mnp.array(onp.zeros((1, 3, 64, 64), dtype="float32"),
                           ctx=mx.cpu()))
        if ctx.device_type != "cpu":
            fnet.reset_ctx(ctx)
        with autograd.predict_mode():
            fp32_s = _infer_rate_fused(
                fnet, x._data.astype("float32"))
    speedup = (fp32_s / dt_fused) if fp32_s else None
    row = _emit({
        "metric": "resnet50_v1_infer_bs32_int8_fused16",
        "value": round(BATCH / dt_fused, 2),
        "unit": "img/s",
        "vs_baseline": round(BATCH / dt_fused / 2085.51, 3),
        "speedup_vs_fp32": round(speedup, 3) if speedup else None,
        **int8_spread,
    })
    if speedup is not None and speedup < 1.5:
        raise RuntimeError(
            f"int8 fused inference is only {speedup:.2f}x fp32 (>=1.5x "
            f"required): the int8 path is not earning its existence")
    return row


def bench_resnet_infer_pallas_fused(n_fuse=16):
    """ResNet-50 bf16 inference through contrib.pallas_fuse (NHWC
    trunk, folded BN) — the transform is the headline (13.7k+ img/s vs
    5.9k plain fp32); the conv1x1_pair-kernel boundary arm
    (use_pallas=True) is re-measured as `pallas_kernel_img_s` each
    round with its measured in-graph verdict: the kernel wins 2.52x on
    the isolated probe shape but LOSES end-to-end because a custom-call
    is a fusion barrier (PERF.md round-5). Scan-chained dispatch (same
    n_fuse protocol as the int8 row)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as onp

    from mxnet_tpu.contrib.pallas_fuse import fuse_resnet_v1

    BATCH, SIZE = 32, 224
    net = _make_resnet()  # initialized + shapes materialized
    x = jnp.asarray(onp.random.uniform(
        -1, 1, (BATCH, 3, SIZE, SIZE)).astype("float32"))

    def rate(fused):
        @functools.partial(jax.jit, static_argnums=1)
        def run(xd, m):
            def body(carry, _):
                logits = fused._forward(xd + carry)
                return jnp.mean(logits).astype(xd.dtype) * 1e-12, None

            c, _ = jax.lax.scan(body, jnp.zeros((), xd.dtype), None,
                                length=m)
            return c

        return _chain_diff(lambda m: onp.asarray(run(x, m)), n_fuse)

    dt_pal = rate(fuse_resnet_v1(net, use_pallas=True))
    pal_spread = _spread(invert_for=BATCH)
    dt_xla = rate(fuse_resnet_v1(net))  # default: XLA boundaries
    return _emit({
        "metric": f"resnet50_v1_infer_bs32_bf16_fusedpairs{n_fuse}",
        "value": round(BATCH / dt_xla, 2),
        "unit": "img/s",
        "vs_baseline": round(BATCH / dt_xla / BASE_INFER_IMG_S, 3),
        "pallas_kernel_img_s": round(BATCH / dt_pal, 2),
        "pallas_kernel_ratio": round(dt_xla / dt_pal, 3),
        "pallas_kernel_spread": pal_spread.get("spread"),
        **_spread(invert_for=BATCH),
    })


def _train_bench(net, loss_fn, optimizer, opt_params, data, labels,
                 rules=None, dtype=None, k1=3, k2=15, fuse=None):
    """Shared training-step timer: ShardedTrainer (SPMD step over the device
    mesh — the dist_tpu_sync execution model), XLA-counted FLOPs -> MFU.

    ``fuse=N``: time ``step_n`` windows of N steps in one dispatch (the
    bulk-exec path); the returned dt is per WINDOW (divide by N for
    per-step)."""
    import jax
    import numpy as onp

    from mxnet_tpu.parallel import ShardedTrainer, ShardingRules, make_mesh

    mesh = make_mesh({"dp": len(jax.devices())})
    trainer = ShardedTrainer(net, loss_fn, optimizer, opt_params, mesh=mesh,
                             rules=rules or ShardingRules(default_axis=None),
                             dtype=dtype)
    # place the synthetic batch on the mesh ONCE — steps must time the chip,
    # not host->device transfers of the same bytes every iteration
    from jax.sharding import NamedSharding, PartitionSpec as P

    def place_tree(tree, spec):
        sh = NamedSharding(mesh, spec)
        return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), tree)

    if fuse:
        stack = lambda a: onp.broadcast_to(  # noqa: E731
            a[None], (fuse,) + a.shape).copy()
        data = jax.tree_util.tree_map(stack, data)
        labels = jax.tree_util.tree_map(stack, labels)
        data = place_tree(data, P(None, "dp"))
        labels = place_tree(labels, P(None, "dp"))
        step = lambda: trainer.step_n(data, labels)  # noqa: E731
        fetch = lambda ls: float(ls.asnumpy().reshape(-1)[-1])  # noqa: E731
    else:
        data = place_tree(data, P("dp"))
        labels = place_tree(labels, P("dp"))
        step = lambda: trainer.step(data, labels)  # noqa: E731
        fetch = lambda loss: float(loss.asnumpy().reshape(-1)[0])  # noqa: E731
    # compile AND drain: only a host fetch
    # guarantees compilation + execution happened before the timed loops
    fetch(step())
    dt = _timed_diff(step, fetch, k1, k2)
    # MFU accounting via the telemetry subsystem: feed every timing sample
    # into a TrainingMetrics (median step time x XLA-counted FLOPs against
    # the chip peak) so BENCH rows and profiler.step_marker agree by
    # construction. step_flops is per-step; a fused window executes
    # `fuse` steps per dt.
    flops = (trainer.step_flops or 0) * (fuse or 1)
    tm = TrainingMetrics(flops_per_step=flops or None)
    for d in (_LAST_SAMPLES or [dt]):
        tm.record_step(d)
    return dt, tm.mfu, trainer


def _roofline(trainer):
    """MFU bound from XLA cost-analysis arithmetic intensity — WITH the
    round-4 correction (exp/conv_chain_probe.py): 'bytes accessed'
    counts convolutions at ~2x their fusion-boundary traffic (measured:
    conv+relu reports 392 MiB for 196 MiB of boundary bytes, while
    elementwise fusions count exactly 1.0x), so the RAW cost-analysis AI
    UNDERSTATES conv-dominated programs and the r3 'bound 0.294, chip
    HBM-saturated' reading was wrong. The r4 per-shape probe shows the
    actual limiter is XLA conv-kernel efficiency at these shapes
    (fwd 3x3: 52-87% MXU; 1x1 pairs: 22-41%; stem: 7% — all well below
    BOTH rooflines in isolation). The raw figure is still emitted, as
    `cost_analysis_mfu_floor`: a conservative floor on the HBM bound,
    not a ceiling the program has hit.
    """
    try:
        ca = trainer.step_cost_analysis
        flops = ca.get("flops")
        bytes_acc = ca.get("bytes accessed")
        peak = _peak_flops()
        hbm = _chip_peak("hbm")
        if not (flops and bytes_acc and peak and hbm):
            return None
        return round(min(1.0, (flops / bytes_acc) / (peak / hbm)), 3)
    except Exception:
        return None


def _make_resnet():
    import numpy as onp

    from mxnet_tpu import autograd, gluon
    from mxnet_tpu import np as mnp

    net = gluon.model_zoo.vision.resnet50_v1()
    net.initialize()
    with autograd.predict_mode():
        net(mnp.array(onp.zeros((1, 3, 64, 64), dtype="float32")))
    return net


def bench_resnet_train(dtype=None):
    """ResNet-50 v1 training step, batch 256, SGD+momentum —
    train_imagenet.py protocol (synthetic data; the reference's largest
    published train batch is 128, perf.md:254, which stays the
    vs_baseline denominator). With dtype='bfloat16': AMP bf16 compute,
    fp32 master weights. Batch 256 measured ~28%% MFU on v5e vs ~20%% at
    128 (deeper per-step pipeline amortizes dispatch + memory stalls)."""
    import numpy as onp

    from mxnet_tpu import gluon

    BATCH = 256
    net = _make_resnet()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = onp.random.uniform(-1, 1, (BATCH, 3, 224, 224)).astype("float32")
    y = onp.random.randint(0, 1000, (BATCH,)).astype("int32")
    dt, mfu, trainer = _train_bench(
        net, loss_fn, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}, x, y,
        dtype=dtype)
    img_s = BATCH / dt
    tag = "bf16_amp" if dtype else "fp32"
    return _emit({
        "metric": f"resnet50_v1_train_bs256_{tag}",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASE_TRAIN_IMG_S, 3),
        "mfu": round(mfu, 4) if mfu else None,
        "cost_analysis_mfu_floor": _roofline(trainer),
        **_dispatch_meta(),
        **_memory_meta(),
        **_spread(invert_for=BATCH),
    })


def bench_resnet_train_fused(n_fuse=8):
    """ResNet-50 bf16 training with N steps fused into one dispatch
    (`ShardedTrainer.step_n` lax.scan window — the bulk-exec path):
    removes per-step host dispatch, showing the
    framework's compute ceiling. The measured MFU lands at ~90% of the
    program's HBM roofline bound (see `_roofline`): this workload is
    memory-bandwidth-bound on v5e, not compute- or dispatch-bound."""
    import numpy as onp

    from mxnet_tpu import gluon

    BATCH = 256
    net = _make_resnet()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = onp.random.uniform(-1, 1, (BATCH, 3, 224, 224)).astype("float32")
    y = onp.random.randint(0, 1000, (BATCH,)).astype("int32")
    dt, mfu, trainer = _train_bench(
        net, loss_fn, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}, x, y,
        dtype="bfloat16", fuse=n_fuse, k1=2, k2=8)
    img_s = n_fuse * BATCH / dt
    return _emit({
        "metric": f"resnet50_v1_train_bs256_bf16_fused{n_fuse}",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASE_TRAIN_IMG_S, 3),
        "mfu": round(mfu, 4) if mfu else None,
        "cost_analysis_mfu_floor": _roofline(trainer),
        "limiter": "xla-conv-kernel-efficiency at these shapes, NOT HBM "
                   "saturation (exp/conv_chain_probe.json; the r3 "
                   "roofline_mfu_bound read cost-analysis bytes that "
                   "double-count convs)",
        **_memory_meta(),
        **_spread(invert_for=n_fuse * BATCH),
    })


def _bert_setup():
    """BERT-base MLM+NSP pretraining pieces, batch 64, seq 128, Adam, AMP
    bf16 — the GluonNLP pretraining config named in BASELINE.json.

    Attention at seq 128 runs the XLA path by design: the Pallas flash
    kernel only wins past the ~1024-token crossover (see
    ops/pallas/flash_attention._supports_pallas for measured numbers);
    dropout masks ride the rbg hardware RNG (3x over threefry, see
    mxnet_tpu/__init__). Batch 64 is the measured MFU sweet spot on v5e
    (bs128 fused8 measured 0.513 vs 0.591 at bs64)."""
    import numpy as onp

    from mxnet_tpu import autograd, gluon
    from mxnet_tpu import np as mnp
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.models.bert import BERTForPretrain, get_bert_model

    BATCH, SEQ = 64, 128

    class PretrainStep(HybridBlock):
        """Single-input wrapper: derives valid_length from the pad mask so
        the whole example (tokens only) flows through one SPMD step."""

        def __init__(self, model):
            super().__init__()
            self.model = model

        def forward(self, tokens):
            valid_length = (tokens != 0).sum(axis=1)
            return self.model(tokens, valid_length=valid_length)

    net = PretrainStep(BERTForPretrain(get_bert_model("bert_12_768_12")))
    net.initialize()
    tokens = onp.random.randint(1, 30000, (BATCH, SEQ)).astype("int32")
    # a few padded tails so the valid-length mask path is exercised
    tokens[::4, SEQ - 16:] = 0
    with autograd.predict_mode():
        net(mnp.array(tokens[:1, :16]))  # tiny: just materializes shapes

    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(outs, labels):
        mlm_scores, nsp_scores = outs
        mlm_labels, nsp_labels = labels
        return ce(mlm_scores, mlm_labels).mean() + \
            ce(nsp_scores, nsp_labels).mean()

    mlm_labels = onp.random.randint(1, 30000, (BATCH, SEQ)).astype("int32")
    nsp_labels = onp.random.randint(0, 2, (BATCH,)).astype("int32")
    return net, loss_fn, tokens, (mlm_labels, nsp_labels), BATCH


def bench_bert_train():
    """Single-dispatch-per-step BERT row. No published reference BERT
    throughput exists in-repo (BASELINE.md), so ``vs_baseline`` is null;
    ``vs_mfu_target`` is mfu / 0.5 against the BASELINE.json >=50% MFU
    north star (the label Weak #9 of the r2 verdict asked for)."""
    net, loss_fn, tokens, labels, BATCH = _bert_setup()
    dt, mfu, _tr = _train_bench(
        net, loss_fn, "adam", {"learning_rate": 1e-4}, tokens,
        labels, dtype="bfloat16")
    samples_s = BATCH / dt
    return _emit({
        "metric": "bert_base_train_bs64_seq128_bf16_amp",
        "value": round(samples_s, 2),
        "unit": "samples/s",
        "vs_baseline": None,
        "vs_mfu_target": round(mfu / 0.5, 3) if mfu else None,
        "mfu": round(mfu, 4) if mfu else None,
        **_dispatch_meta(),
        **_memory_meta(),
        **_spread(invert_for=BATCH),
    })


def bench_bert_train_fused(n_fuse=8):
    """BERT with N steps fused into one dispatch (`step_n` lax.scan
    window). The compiled step's device time is ~47 ms (per-op profile:
    matmul fusions at ~83% of MXU peak); single-dispatch rows additionally
    pay a per-execute round-trip, which the fused window amortizes —
    this row is the chip's real per-step rate."""
    net, loss_fn, tokens, labels, BATCH = _bert_setup()
    dt, mfu, _tr = _train_bench(
        net, loss_fn, "adam", {"learning_rate": 1e-4}, tokens,
        labels, dtype="bfloat16", fuse=n_fuse, k1=2, k2=8)
    samples_s = n_fuse * BATCH / dt
    return _emit({
        "metric": f"bert_base_train_bs64_seq128_bf16_fused{n_fuse}",
        "value": round(samples_s, 2),
        "unit": "samples/s",
        "vs_baseline": None,
        "vs_mfu_target": round(mfu / 0.5, 3) if mfu else None,
        "mfu": round(mfu, 4) if mfu else None,
        **_memory_meta(),
        **_spread(invert_for=n_fuse * BATCH),
    })


def _llama_lm_setup(seq, batch):
    """Decoder-only llama-block LM for the long-context row: 12 layers,
    units 1024 (16 heads x d64), SwiGLU 2816, vocab 32k, per-layer remat
    — sized so fp32 masters + Adam states + seq-2048 activations fit one
    v5e chip. Causal LM loss over shifted tokens."""
    import numpy as onp

    from mxnet_tpu import autograd, gluon
    from mxnet_tpu import np as mnp
    from mxnet_tpu.models.llama import get_llama

    net = get_llama("llama2_7b", units=1024, hidden_size=2816,
                    num_layers=12, num_heads=16, num_kv_heads=16,
                    vocab_size=32000, remat=True)
    net.initialize()
    rng = onp.random.RandomState(7)
    tokens = rng.randint(1, 32000, (batch, seq)).astype("int32")
    labels = onp.concatenate(
        [tokens[:, 1:], tokens[:, :1]], axis=1).astype("int32")
    with autograd.predict_mode():
        net(mnp.array(tokens[:1, :16]))  # materialize shapes
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(logits, y):
        return ce(logits, y).mean()

    return net, loss_fn, tokens, labels


def _llama_lm_flops(seq, batch, layers=12, units=1024, hidden=2816,
                    vocab=32000):
    """Analytic per-step train FLOPs (fwd x3 for fwd+bwd), PaLM-style
    counting: projections 8BTU^2, attention scores+AV 4BT^2U (full T^2;
    causality not discounted — identical in both arms), SwiGLU 6BTUH,
    LM head 2BTUV. Used for MFU instead of XLA cost_analysis because the
    flash path's pallas custom-call FLOPs are invisible to cost_analysis
    — the analytic count is the only denominator that treats the flash
    and ablation arms identically (remat recompute is NOT counted:
    model FLOPs, not hardware FLOPs)."""
    b, t, u = batch, seq, units
    fwd = layers * (8 * b * t * u * u + 4 * b * t * t * u
                    + 6 * b * t * u * hidden) + 2 * b * t * u * vocab
    return 3.0 * fwd


def bench_llama_long_seq(n_fuse=4, seq=2048, batch=4):
    """Long-context training row (VERDICT r4 Next #2): a llama-block LM
    at seq 2048 where attention ACTUALLY routes to the Pallas flash
    kernel (tq*tk = 4x the crossover), trained end-to-end with the
    ShardedTrainer fused-window path, plus the same model with
    `force_path('xla')` as the ablation arm. The route is asserted from
    `flash_attention.last_path()` after the traced step executes — if
    the router stops picking the kernel this row FAILS, it does not
    silently degrade. Emits tokens/s + MFU (analytic FLOPs; see
    `_llama_lm_flops`) and the flash-vs-XLA end-to-end speedup."""
    from mxnet_tpu.ops.pallas import flash_attention as fa

    flops = _llama_lm_flops(seq, batch)
    peak = _peak_flops()
    arms = {}
    for arm, forced in (("flash", None), ("xla_ablation", "xla")):
        fa.force_path(forced)
        try:
            net, loss_fn, tokens, labels = _llama_lm_setup(seq, batch)
            dt, _mfu, _tr = _train_bench(
                net, loss_fn, "adam", {"learning_rate": 1e-4}, tokens,
                labels, dtype="bfloat16", fuse=n_fuse, k1=1, k2=5)
            want = "pallas" if forced is None else "xla"
            got = fa.last_path()
            if got != want:
                raise RuntimeError(
                    f"attention path assertion failed: arm {arm!r} "
                    f"traced {got!r}, wanted {want!r}")
            # dt is per DISPATCH = n_fuse steps; flops is per step.
            # tokens/s + MFU via the telemetry subsystem's accounting.
            tm = TrainingMetrics(flops_per_step=n_fuse * flops,
                                 tokens_per_step=n_fuse * batch * seq,
                                 peak_flops=peak)
            for d in (_LAST_SAMPLES or [dt]):
                tm.record_step(d)
            arms[arm] = {
                "tokens_s": round(tm.tokens_per_sec, 1),
                "mfu": round(tm.mfu, 4) if tm.mfu else None,
                **_spread(invert_for=n_fuse * batch * seq),
            }
        finally:
            fa.force_path(None)
    row = {
        "metric": f"llama12L_train_bs{batch}_seq{seq}_bf16_fused{n_fuse}",
        "value": arms["flash"]["tokens_s"],
        "unit": "tokens/s",
        "vs_baseline": None,
        "mfu": arms["flash"]["mfu"],
        "attention_path": "pallas (asserted from last_path())",
        "flash_speedup_vs_xla": round(
            arms["flash"]["tokens_s"] / arms["xla_ablation"]["tokens_s"],
            3),
        "n": arms["flash"].get("n"),
        "spread": arms["flash"].get("spread"),
        "xla_ablation": arms["xla_ablation"],
    }
    return _emit(row)


def bench_lenet_eager():
    """Imperative (non-hybridized) LeNet training — the reference's eager
    LeNet/MNIST config. Exercises per-op dispatch + the eager jit cache
    (SURVEY §7 hard part 2); reports the cached rate and the uncached rate.

    Diagnosis of the r2 eager gap (the measurement this round's >=2x fix
    came from): the r2 bench built its arrays on the DEFAULT context, i.e.
    jax-CPU, where a single LeNet conv *backward* costs ~7 ms of genuine
    single-host compute (the 129 ms step was device-bound, not
    dispatch-bound — the jit cache rightly bought only 8%). On the TPU
    context the per-op device time is negligible and the cost structure
    inverts: the runtime drained ~0.7-4 ms per executed op, so the
    step is dispatch-round-trip-bound, exactly SURVEY §7 hard part 2's
    prediction. Two fixes: (1) this bench now runs on mx.tpu() like every
    other row; (2) recorded ops now run their forward through the cached
    per-op executable and their backward through a cached compiled vjp
    (registry._make_cached_vjp) instead of per-step jax.vjp retracing +
    Python transpose interpretation — 2.3x the r2 rate; the remaining time
    is ~50 dispatch round-trips that only op-graph batching could remove."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu import np as mnp
    from mxnet_tpu.ops import registry

    BATCH = 64
    try:
        ctx = mx.tpu()
        ctx.jax_device()
    except Exception:
        ctx = mx.cpu()
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(6, 5, activation="relu"), gluon.nn.MaxPool2D(2),
            gluon.nn.Conv2D(16, 5, activation="relu"), gluon.nn.MaxPool2D(2),
            gluon.nn.Flatten(), gluon.nn.Dense(120, activation="relu"),
            gluon.nn.Dense(84, activation="relu"), gluon.nn.Dense(10))
    net.initialize(ctx=ctx)
    x = mnp.array(onp.random.randn(BATCH, 1, 28, 28).astype("float32"),
                  ctx=ctx)
    y = mnp.array(onp.random.randint(0, 10, (BATCH,)), ctx=ctx)
    with autograd.predict_mode():
        net(x)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})

    def step():
        with autograd.record():
            l = loss_fn(net(x), y).mean()
        l.backward()
        tr.step(1)
        return l

    def dispatches_per_step():
        from mxnet_tpu import engine

        float(step().asnumpy())  # settle caches for THIS config
        before = engine.dispatch_count()
        float(step().asnumpy())
        return engine.dispatch_count() - before

    rates = {}
    prev_enabled = registry._eager_jit_enabled
    from mxnet_tpu import engine as _engine

    prev_bulk = _engine.set_bulk_size(0)  # this row measures PER-OP dispatch
    try:
        for flag in (False, True):
            registry.set_eager_jit(flag)
            registry._EAGER_JIT_CACHE.clear()
            registry._EAGER_BWD_CACHE.clear()
            for _ in range(3):
                float(step().asnumpy())  # drain + warm fwd AND bwd caches
            dt = _timed_diff(step, lambda l: float(l.asnumpy()), 3, 18)
            rates[flag] = BATCH / dt
        dps = dispatches_per_step()
    finally:
        registry.set_eager_jit(prev_enabled)
        _engine.set_bulk_size(prev_bulk)
    return _emit({
        "metric": "lenet_eager_train_bs64",
        "value": round(rates[True], 2),
        "unit": "img/s",
        "vs_baseline": None,
        "uncached_img_s": round(rates[False], 2),
        "dispatches_per_step": dps,
        **_dispatch_meta(),
        **_spread(invert_for=BATCH),
    })


def bench_trace_overhead():
    """Observability cost contract (OBSERVABILITY.md): the eager LeNet
    microloop under the production-default stack — profiler hooks
    installed but stopped, flight recorder ON, request tracing disabled —
    vs the fully unhooked baseline. The two arms are interleaved
    (min-of-rounds) so machine drift hits both equally; the row ASSERTS
    <5% overhead, mirroring tests/test_observability.py, so a hot-path
    regression fails a BENCH round loudly instead of shaving every
    other row quietly."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, engine, gluon, profiler
    from mxnet_tpu import np as mnp
    from mxnet_tpu.ops import registry
    from mxnet_tpu.profiler import recorder, trace

    BATCH = 64
    try:
        ctx = mx.tpu()
        ctx.jax_device()
    except Exception:
        ctx = mx.cpu()
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(6, 5, activation="relu"), gluon.nn.MaxPool2D(2),
            gluon.nn.Conv2D(16, 5, activation="relu"), gluon.nn.MaxPool2D(2),
            gluon.nn.Flatten(), gluon.nn.Dense(120, activation="relu"),
            gluon.nn.Dense(84, activation="relu"), gluon.nn.Dense(10))
    net.initialize(ctx=ctx)
    x = mnp.array(onp.random.randn(BATCH, 1, 28, 28).astype("float32"),
                  ctx=ctx)
    y = mnp.array(onp.random.randint(0, 10, (BATCH,)), ctx=ctx)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})

    def step():
        with autograd.record():
            l = loss_fn(net(x), y).mean()
        l.backward()
        tr.step(1)
        return l

    def loop(n=12):
        t0 = time.perf_counter()
        for _ in range(n):
            l = step()
        float(l.asnumpy())
        return time.perf_counter() - t0

    saved = registry._PROF, engine._PROF
    was_traced, was_recording = trace.ENABLED, recorder.ENABLED

    def measure(rounds=5):
        base = hooked = float("inf")
        for _ in range(rounds):
            registry._PROF = None
            engine._PROF = None
            trace.disable()
            recorder.disable()
            base = min(base, loop())
            profiler.set_state("run")
            profiler.set_state("stop")
            recorder.enable()  # production default; trace stays disabled
            hooked = min(hooked, loop())
        return base, hooked

    try:
        loop(4)  # warm fwd/bwd caches before either arm
        base, hooked = measure()
        if hooked > base * 1.05:  # timing noise: one clean re-measure
            base, hooked = measure(rounds=7)
    finally:
        registry._PROF, engine._PROF = saved
        (trace.enable if was_traced else trace.disable)()
        (recorder.enable if was_recording else recorder.disable)()
    overhead = hooked / base - 1.0
    assert overhead <= 0.05, (
        f"disabled trace+recorder overhead {overhead:.1%} on the eager "
        f"LeNet microloop (baseline {base:.3f}s, hooked {hooked:.3f}s)")
    return _emit({
        "metric": "trace_overhead_lenet_eager",
        "value": round(overhead * 100, 2),
        "unit": "%",
        "vs_baseline": None,
        "base_steps_s": round(12 / base, 1),
        "hooked_steps_s": round(12 / hooked, 1),
        "arm": "recorder on + trace off (production default) vs unhooked",
    })


def bench_guardrail_overhead():
    """Numerical-guardrail cost on a small dense train step (PERF.md
    'measured guardrail overhead'): baseline trainer vs one running the
    full sentinel stack — LossScaler overflow check + global-norm clip per
    step (the two per-step device-sync guardrails). The *disabled* cost
    (no scaler, no clip — the production default) is a pair of `is None`
    tests and is bounded separately by
    tests/test_guardrails.py::test_disabled_guardrail_overhead_under_5pct."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import amp, autograd, gluon
    from mxnet_tpu import np as mnp

    BATCH = 32
    try:
        ctx = mx.tpu()
        ctx.jax_device()
    except Exception:
        ctx = mx.cpu()
    x = mnp.array(onp.random.randn(BATCH, 64).astype("float32"), ctx=ctx)
    y = mnp.array(onp.random.randn(BATCH, 1).astype("float32"), ctx=ctx)
    loss_fn = gluon.loss.L2Loss()

    def make(guarded):
        net = gluon.nn.Dense(1, in_units=64)
        net.initialize(ctx=ctx)
        net(x)
        kw = {"loss_scaler": amp.LossScaler(),
              "clip_global_norm": 1e6} if guarded else {}
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 1e-3}, **kw)

        def step():
            with autograd.record():
                l = tr.scale_loss(loss_fn(net(x), y).mean())
            l.backward()
            tr.step(1)
            return l
        return step

    rates = {}
    for guarded in (False, True):
        step = make(guarded)
        for _ in range(5):
            float(step().asnumpy())
        dt = _timed_diff(step, lambda l: float(l.asnumpy()), 5, 30)
        rates[guarded] = 1.0 / dt
    overhead = rates[False] / rates[True] - 1.0
    return _emit({
        "metric": "guardrail_overhead_dense_step",
        "value": round(overhead * 100, 2),
        "unit": "%",
        "vs_baseline": None,
        "base_steps_s": round(rates[False], 1),
        "guarded_steps_s": round(rates[True], 1),
        **_spread(),
    })


def bench_ckpt_stall():
    """Async-checkpoint stall row (resilience.checkpoint): the training
    stall of an ``async_write=True`` save — the synchronous host-snapshot
    phase — vs the full synchronous save wall time, over a llama-8B-class
    parameter census (same tensor count/shape mix: embedding, per-layer
    qkv/out/mlp/norm) scaled to a dev box (~220 MB fp32). Reports the
    async stall in ms (lower is better; the perf gate treats ``ms`` rows
    as lower-better automatically) and fails loudly if the stall exceeds
    10% of the sync save — the acceptance bound async checkpointing
    exists to hold."""
    import os
    import tempfile

    import numpy as onp

    from mxnet_tpu import nd
    from mxnet_tpu.resilience import checkpoint as ckpt

    rng = onp.random.RandomState(0)
    H, V, L = 512, 8192, 16
    params = {"embed.weight": nd.array(rng.randn(V, H).astype("float32"))}
    for i in range(L):
        for nme, shape in (("attn_qkv", (3 * H, H)), ("attn_out", (H, H)),
                           ("mlp_up", (4 * H, H)), ("mlp_down", (H, 4 * H)),
                           ("norm", (H,))):
            params[f"layers.{i}.{nme}.weight"] = nd.array(
                rng.randn(*shape).astype("float32"))
    nbytes = sum(int(onp.prod(s)) for s in
                 [v.shape for v in params.values()]) * 4

    d = tempfile.mkdtemp(prefix="bench_ckpt_stall_")
    sync_ms, stall_ms = [], []
    for r in range(3):
        t0 = time.perf_counter()
        ckpt.save_checkpoint(os.path.join(d, f"sync{r}.ckpt"),
                             params=params, meta={"step": r})
        sync_ms.append((time.perf_counter() - t0) * 1e3)
        h = ckpt.save_checkpoint(os.path.join(d, f"async{r}.ckpt"),
                                 params=params, meta={"step": r},
                                 async_write=True)
        if not h.join():
            raise RuntimeError(f"async checkpoint write failed: {h.error}")
        stall_ms.append(h.stall_ms)
    sync = sorted(sync_ms)[1]
    stall = sorted(stall_ms)[1]
    frac = stall / sync
    if frac > 0.10:
        raise RuntimeError(
            f"async save stall {stall:.1f}ms is {frac:.1%} of the "
            f"{sync:.0f}ms sync save — the <10% stall bound regressed")
    return _emit({
        "metric": "ckpt_stall_ms",
        "value": round(stall, 3),
        "unit": "ms",
        "vs_baseline": None,
        "sync_save_ms": round(sync, 1),
        "stall_frac": round(frac, 4),
        "params_mb": round(nbytes / 1e6, 1),
    })


def bench_elastic_resume():
    """MULTICHIP elastic row (resilience.elastic): a dp8 training run on
    the 8-device mesh killed mid-step by an injected chip_loss, resumed
    at dp4 from its own sharded checkpoint. Reports the recovery
    wall-time (MeshDegraded catch → mesh shrink → kvstore rebind →
    reshard-on-resume restore) and the steps lost to the kill; the
    bitwise dp4-reference parity check runs inside the leg and fails the
    row loudly on any divergence."""
    from tools.elastic_soak import run_kill_reshard

    violations, row = run_kill_reshard(seed=7, n_batches=12)
    if violations:
        raise RuntimeError(f"elastic kill-and-reshard violated: "
                           f"{violations}")
    return _emit({
        "metric": "elastic_kill_reshard_recovery_ms",
        "value": round(row["recovery_wall_s"] * 1e3, 2),
        "unit": "ms",
        "vs_baseline": None,
        "steps_lost": row["steps_lost"],
        "dp": f"{row['dp_from']}->{row['dp_to']}",
        "killed_replica": row["killed_replica"],
        "parity": "bitwise",
    })


def bench_elastic_resume_3d():
    """MULTICHIP composed-mesh elastic row (resilience.elastic): a
    dp2×tp2 ShardedTrainer run killed mid-step by a coordinate-addressed
    chip_loss, rebuilt to dp1×tp2 (tp extent pinned, the touched
    dp-group dropped) and resumed from its layout-carrying sharded
    checkpoint resharded onto the survivor mesh. Reports the recovery
    wall-time (classify → rebuild_mesh → trainer rebind → cross-layout
    restore) and steps lost; the bitwise parity check against a clean
    dp1×tp2 run from the same checkpoint runs inside the leg and fails
    the row loudly on any divergence."""
    from tools.elastic_soak import run_kill_reshard_3d

    violations, row = run_kill_reshard_3d(seed=7, n_batches=10)
    if violations:
        raise RuntimeError(f"elastic 3d kill-and-reshard violated: "
                           f"{violations}")
    return _emit({
        "metric": "elastic_resume_3d_recovery_ms",
        "value": round(row["recovery_wall_s"] * 1e3, 2),
        "unit": "ms",
        "vs_baseline": None,
        "steps_lost": row["steps_lost"],
        "dp": f"{row['dp_from']}->{row['dp_to']}",
        "tp": row["tp"],
        "killed_device": row["killed_device"],
        "parity": row["resume_parity"],
    })


def bench_collective_overlap():
    """MULTICHIP collective row (kvstore.bucketing): the bucketing ×
    overlap × compression ablation grid over a dp4 training loop —
    unbucketed baseline, bucketed (sync per bucket), bucketed+overlapped
    (one grouped priority-ordered dispatch), and bucketed+overlapped+
    2-bit. Parity is asserted inside the leg (bitwise for the
    uncompressed points, bounded for 2-bit) along with ZERO steady-state
    recompiles at every point. On the CPU sim the fusion buffers can run
    FLAT-to-slower vs per-param pushpull: host emulation pays the
    concat/slice-back but hides no interconnect latency (there is none
    to hide) — the collapse that matters is collective COUNT (the
    llama-8B ZeRO lowering pins 1829 → ~131 all-gathers), which turns
    into step time only on a real ICI fabric. See PERF.md."""
    from tools.overlap_smoke import run_ablation

    violations, rows = run_ablation(steps=10, seed=0)
    if violations:
        raise RuntimeError(f"collective overlap ablation violated: "
                           f"{violations}")
    base = rows["base"]["step_ms"]
    bo = rows["bucket_overlap"]["step_ms"]
    return _emit({
        "metric": "collective_overlap_step_ms",
        "value": bo,
        "unit": "ms",
        "vs_baseline": round(base / bo, 3) if bo else None,
        "ablation": rows,
        "parity": rows["bucket_overlap"].get("parity"),
        "recompiles": sum(r["recompiles"] for r in rows.values()),
    })


def bench_llama_decode(max_new=32, reps=3, batch=16, spec_k=4):
    """Serving row (mxnet_tpu.serve): the ``decode_tokens_s`` ladder —
    every decode rung measured on the same 12L llama serve config, same
    prompts, same (batch, seq) bucket:

    * ``baseline`` — PR-5 strict path (shape-stable mul+reduce attention;
      the bitwise-parity contract)
    * ``pallas``   — fused Pallas decode-attention kernel
    * ``int8``     — pallas + int8 KV-cache rings (plus int8 projection
      weights on backends with int8 matrix units)
    * ``spec``     — SpeculativeGenerator (2-layer draft, k proposals per
      round) stacked on the int8 rung

    Rates are steady-state (the prefill-sampled first token of each row
    is excluded; decode wall only). The target model's layers >= 2 get
    zeroed o_proj/down_proj: runtime call args XLA cannot constant-fold,
    so every rung still pays the full 12-deep gemm/cache cost, while the
    2-layer copied-prefix draft predicts the (now 2-layer-equivalent)
    target almost perfectly — the spec rung's acceptance rate reflects
    draft quality, which a synthetic random model cannot provide.
    Each rung asserts ZERO recompiles after warmup — a recompile here is
    a perf bug, not noise, and fails the row loudly."""
    import numpy as onp

    from mxnet_tpu import numpy as mnp
    from mxnet_tpu.models.llama import get_llama
    from mxnet_tpu.profiler import attribution as _attr
    from mxnet_tpu.serve import Generator, SpeculativeGenerator

    attr_was_on = _attr.ENABLED
    _attr.enable()
    target = get_llama("llama_serve_12l_test")
    target.initialize()
    for blk in target._blocks[2:]:
        for p in (blk.attention.o_proj.weight, blk.ffn.down_proj.weight):
            p.set_data(mnp.zeros(p.shape, dtype="float32"))
    draft = get_llama("llama_serve_12l_test", num_layers=2)
    draft.initialize()
    tparams = dict(target.collect_params().items())
    for name, p in draft.collect_params().items():
        p.set_data(tparams[name].data())

    rng = onp.random.RandomState(0)
    prompts = [rng.randint(1, 500, size=int(rng.randint(4, 13))).tolist()
               for _ in range(batch)]

    def measure(gen):
        warm = gen.warmup()
        best, extra = 0.0, {}
        for _ in range(reps):
            outs, info = gen.generate(prompts, max_new_tokens=max_new)
            # steady-state rate: each row's FIRST token is sampled from
            # prefill logits, so it rides prefill wall, not decode wall
            toks = sum(len(o) for o in outs) - len(outs)
            rate = toks / (info["decode_ms"] / 1e3)
            if rate > best:
                best = rate
                extra = {k: info[k] for k in ("acceptance_rate", "rounds")
                         if k in info}
        gen.assert_no_recompiles()
        # critical-path attribution (Generator rungs only: the spec
        # round loop is not a fixed-width decode, its ledger stays
        # empty): one reconcile rep on a FRESH ledger so the 4-phase
        # sum + schedule bucket must cover THAT rep's decode wall —
        # >10% daylight means the partition is lying, fail loudly
        # exactly like a recompile
        attr = None
        if type(gen) is Generator:
            gen.ledger = _attr.Ledger(gen.ledger.name)
            _, info = gen.generate(prompts, max_new_tokens=max_new)
            snap = gen.ledger.snapshot()
            phase_ms = (snap["host_ms"] + snap["dispatch_ms"]
                        + snap["device_ms"] + snap["wait_ms"])
            coverage = ((phase_ms + snap["schedule_ms"])
                        / info["decode_ms"]) if info["decode_ms"] else 0.0
            assert 0.90 <= coverage <= 1.10, (
                f"{gen.ledger.name}: attribution phases cover "
                f"{coverage:.1%} of the decode wall (want 90-110%)")
            attr = {
                "host_overhead_fraction":
                    round(snap["host_overhead_fraction"], 4),
                "device_ms_per_token":
                    round(snap["device_ms_per_token"], 4),
                "phase_coverage": round(coverage, 3),
            }
        return round(best, 1), extra, round(warm["wall_s"], 2), attr

    ladder, warm_s, spec_extra, attribution = {}, {}, {}, {}
    for path in ("baseline", "pallas", "int8"):
        gen = Generator(target, max_seq=64, batch_buckets=(batch,),
                        prompt_buckets=(16,), name=f"llama_decode_{path}",
                        decode_path=path)
        ladder[path], _, warm_s[path], attribution[path] = measure(gen)
    spec = SpeculativeGenerator(
        target, draft, k=spec_k, max_seq=64, batch_buckets=(batch,),
        prompt_buckets=(16,), name="llama_decode_spec", decode_path="int8")
    ladder["spec"], spec_extra, warm_s["spec"], _ = measure(spec)
    attribution.pop("spec", None)
    if not attr_was_on:
        _attr.disable()

    base = ladder["baseline"]
    order = ("baseline", "pallas", "int8", "spec")
    speedups = {p: round(ladder[p] / base, 2) if base else None
                for p in order}
    # 2% tolerance: adjacent rungs can sit within run-to-run CPU noise
    monotone = all(ladder[b] >= ladder[a] * 0.98
                   for a, b in zip(order, order[1:]))
    return _emit({
        "metric": "llama_decode_tokens_s",
        "value": ladder["spec"],
        "unit": "tokens/s",
        "vs_baseline": speedups["spec"],
        "ladder": ladder,
        "speedups": speedups,
        "monotone": monotone,
        "acceptance_rate": round(spec_extra.get("acceptance_rate", 0.0), 3),
        "spec_k": spec_k,
        "batch": batch,
        "max_new_tokens": max_new,
        "warmup_s": warm_s,
        # critical-path readout from the fastest fixed-width rung: how
        # much of each decode iteration is host overhead vs device work
        "host_overhead_fraction":
            attribution["int8"]["host_overhead_fraction"],
        "device_ms_per_token":
            attribution["int8"]["device_ms_per_token"],
        "attribution": attribution,
    })


def bench_llama_multistep_decode(max_new=32, reps=2, batch=16, spec_k=4):
    """Serving row (tentpole PR 19): the device-side multi-step decode
    ladder — the same 12L llama serve config, prompts, and (batch, seq)
    bucket as ``bench_llama_decode``, but the token loop runs as one
    compiled ``while_loop`` super-step of N decode iterations per host
    visit (``MXNET_SERVE_MULTISTEP`` / ``MXNET_SERVE_DECODE_STEPS``):

    * ``baseline``/``pallas``/``int8`` x N in {1, 4, 8} — each multistep
      rung must be greedy token-identical to its single-step Generator,
      compile exactly one extra signature (the super-step), and never
      recompile
    * ``spec`` — SpeculativeGenerator with the whole draft-propose phase
      of a round as ONE draft super-step (2 host visits per round
      instead of k+2), stacked on the int8 rung

    ``host_visits_per_token`` is the ladder's reason to exist: at N=8 a
    32-token row takes ~4 device visits instead of ~31, and the row
    asserts visits/token <= 1/4 AND tokens/s strictly above the same
    path's single-step rate — if killing the host round-trip doesn't
    show up in the rate, the super-step is broken, fail loudly."""
    import numpy as onp

    from mxnet_tpu import numpy as mnp
    from mxnet_tpu.models.llama import get_llama
    from mxnet_tpu.serve import Generator, SpeculativeGenerator

    target = get_llama("llama_serve_12l_test")
    target.initialize()
    for blk in target._blocks[2:]:
        for p in (blk.attention.o_proj.weight, blk.ffn.down_proj.weight):
            p.set_data(mnp.zeros(p.shape, dtype="float32"))
    draft = get_llama("llama_serve_12l_test", num_layers=2)
    draft.initialize()
    tparams = dict(target.collect_params().items())
    for name, p in draft.collect_params().items():
        p.set_data(tparams[name].data())

    rng = onp.random.RandomState(0)
    prompts = [rng.randint(1, 500, size=int(rng.randint(4, 13))).tolist()
               for _ in range(batch)]

    def measure(gen, ref_outs=None, label=""):
        warm = gen.warmup()
        best, hv, outs = 0.0, None, None
        for _ in range(reps):
            outs, info = gen.generate(prompts, max_new_tokens=max_new)
            if ref_outs is not None:
                assert outs == ref_outs, (
                    f"{label}: multistep greedy output diverged from "
                    f"the single-step reference")
            # steady-state: each row's first token rides prefill wall
            toks = sum(len(o) for o in outs) - len(outs)
            rate = toks / (info["decode_ms"] / 1e3)
            best = max(best, rate)
            if "decode_visits" in info:
                hv = info["decode_visits"] / max(toks, 1)
        gen.assert_no_recompiles()
        return round(best, 1), hv, outs, round(warm["wall_s"], 2)

    steps_ladder = (1, 4, 8)
    ladder, visits, warm_s, refs = {}, {}, {}, {}
    for path in ("baseline", "pallas", "int8"):
        single = Generator(target, max_seq=64, batch_buckets=(batch,),
                           prompt_buckets=(16,),
                           name=f"llama_ms_{path}_single",
                           decode_path=path, multistep=False)
        rate1, _, ref_outs, w = measure(single, label=f"{path}/single")
        ladder[path] = {"single": rate1}
        visits[path] = {"single": 1.0}
        warm_s[f"{path}_single"] = w
        refs[path] = ref_outs
        for n in steps_ladder:
            gen = Generator(target, max_seq=64, batch_buckets=(batch,),
                            prompt_buckets=(16,),
                            name=f"llama_ms_{path}_n{n}",
                            decode_path=path, multistep=True,
                            decode_steps=n)
            rate, hv, _, w = measure(gen, ref_outs=ref_outs,
                                     label=f"{path}/N={n}")
            ladder[path][f"n{n}"] = rate
            visits[path][f"n{n}"] = round(hv, 4)
            warm_s[f"{path}_n{n}"] = w
        assert visits[path]["n8"] <= 0.25, (
            f"{path}: N=8 host_visits_per_token "
            f"{visits[path]['n8']:.3f} > 1/4 — the super-step is not "
            f"amortizing the host round-trip")
        # the headline rung (int8) must be STRICTLY faster than
        # single-step; the others get the same 2% run-to-run noise
        # tolerance as bench_llama_decode's monotone check
        floor = ladder[path]["single"] * (1.0 if path == "int8" else 0.98)
        assert ladder[path]["n8"] > floor, (
            f"{path}: N=8 rate {ladder[path]['n8']} tok/s not above the "
            f"single-step rate {ladder[path]['single']} — killing the "
            f"host round-trip must show up in throughput")

    # spec rung: draft-round-as-super-step, stacked on int8. Greedy
    # speculative decoding is defined by emitting the target's greedy
    # sequence, so the int8 single-step reference is its identity oracle.
    spec = SpeculativeGenerator(
        target, draft, k=spec_k, max_seq=64, batch_buckets=(batch,),
        prompt_buckets=(16,), name="llama_ms_spec", decode_path="int8",
        multistep=True)
    spec_warm = spec.warmup()
    spec_best, spec_info = 0.0, {}
    for _ in range(reps):
        outs, info = spec.generate(prompts, max_new_tokens=max_new)
        assert outs == refs["int8"], (
            "spec: draft-super-step output diverged from the int8 "
            "single-step greedy reference")
        toks = sum(len(o) for o in outs) - len(outs)
        spec_best = max(spec_best, toks / (info["decode_ms"] / 1e3))
        spec_info = info
    spec.assert_no_recompiles()
    ladder["spec"] = {"single": ladder["int8"]["single"],
                      "n8": round(spec_best, 1)}
    warm_s["spec"] = round(spec_warm["wall_s"], 2)

    speedup_vs_single = {
        p: round(ladder[p]["n8"] / ladder[p]["single"], 2)
        for p in ("baseline", "pallas", "int8")}
    return _emit({
        "metric": "llama_multistep_decode_tokens_s",
        "value": ladder["int8"]["n8"],
        "unit": "tokens/s",
        "vs_baseline": round(ladder["int8"]["n8"]
                             / ladder["baseline"]["single"], 2),
        "decode_steps": 8,
        "ladder": ladder,
        "host_visits_per_token": visits["int8"]["n8"],
        "visits": visits,
        "speedup_vs_single": speedup_vs_single,
        "acceptance_rate": round(spec_info.get("acceptance_rate", 0.0), 3),
        "spec_k": spec_k,
        "batch": batch,
        "max_new_tokens": max_new,
        "warmup_s": warm_s,
    })


def bench_llama_continuous_batching(reps=2):
    """Serving row (serve.scheduler): continuous batching vs the static
    bucket ladder on the same 12L llama serve config and the same mixed
    open-ended traffic — a burst of 32 requests interleaved
    ``[long, short, short, short] x 8`` (8 batch-class 48-token decodes
    among 24 interactive 4-token requests).

    The static side is the PR-6/PR-10 stack at its best bucket: batches
    of 8 in arrival order, each batch running until its LONGEST request
    finishes — the interactive shorts ride out all 48 steps
    (head-of-line blocking) and their lanes decode dead air after step 4.
    The continuous side admits/retires between decode steps over 8 paged
    slots, so a retired short's slot immediately decodes the next
    request. Same decode-rung executables on both sides, per rung.

    Reported per rung: aggregate USEFUL tokens/s (requested tokens only —
    the static side gets no credit for dead-lane tokens) and client-side
    interactive p99 from burst arrival. The row hard-fails unless
    continuous batching beats static on BOTH metrics on every rung, and
    every engine asserts zero recompiles."""
    import threading

    import numpy as onp

    from mxnet_tpu.models.llama import get_llama
    from mxnet_tpu.serve import ContinuousEngine, Generator, percentile

    net = get_llama("llama_serve_12l_test")
    net.initialize()

    rng = onp.random.RandomState(0)
    reqs = []  # (prompt, max_new, priority) in arrival order
    for _ in range(8):
        reqs.append((rng.randint(1, 500, size=8).tolist(), 48, "batch"))
        for _ in range(3):
            reqs.append((rng.randint(
                1, 500, size=int(rng.randint(4, 13))).tolist(), 4,
                "interactive"))
    useful = sum(m for _, m, _ in reqs)

    ladder = {}
    for path in ("baseline", "pallas", "int8"):
        gen = Generator(net, max_seq=64, batch_buckets=(8,),
                        prompt_buckets=(16,), decode_path=path,
                        name=f"cb_static_{path}")
        gen.warmup()
        st_rate, st_p99 = 0.0, None
        for _ in range(reps):
            t0 = time.monotonic()
            lat = []
            for g in range(0, len(reqs), 8):
                grp = reqs[g:g + 8]
                gen.generate([p for p, _, _ in grp],
                             max_new_tokens=max(m for _, m, _ in grp))
                done = (time.monotonic() - t0) * 1e3
                lat += [done for _, _, pr in grp if pr == "interactive"]
            rate = useful / (time.monotonic() - t0)
            if rate > st_rate:
                st_rate, st_p99 = rate, percentile(lat, 99)
        gen.assert_no_recompiles()

        eng = ContinuousEngine(net, max_seq=64, num_slots=8, page_size=16,
                               prefill_chunk=16, decode_path=path,
                               name=f"cb_engine_{path}", max_queue=64)
        eng.start()
        cb_rate, cb_p99 = 0.0, None
        for _ in range(reps):
            done_t, lock = {}, threading.Lock()

            def stamp(i):
                def cb(_f):
                    with lock:
                        done_t[i] = time.monotonic()
                return cb

            t0 = time.monotonic()
            futs = []
            for i, (p, m, pr) in enumerate(reqs):
                f = eng.submit(p, max_new_tokens=m, priority=pr)
                f.add_done_callback(stamp(i))
                futs.append(f)
            for f in futs:
                f.result(timeout=600)
            rate = useful / (time.monotonic() - t0)
            lat = [(done_t[i] - t0) * 1e3
                   for i, (_, _, pr) in enumerate(reqs)
                   if pr == "interactive"]
            if rate > cb_rate:
                cb_rate, cb_p99 = rate, percentile(lat, 99)
        eng.assert_no_recompiles()
        eng.close()

        if cb_rate <= st_rate or cb_p99 >= st_p99:
            raise RuntimeError(
                f"continuous batching lost to static buckets on the "
                f"{path} rung: tokens/s {cb_rate:.1f} vs {st_rate:.1f}, "
                f"interactive p99 {cb_p99:.0f}ms vs {st_p99:.0f}ms")
        ladder[path] = {
            "cb_tokens_s": round(cb_rate, 1),
            "static_tokens_s": round(st_rate, 1),
            "speedup": round(cb_rate / st_rate, 2),
            "cb_interactive_p99_ms": round(cb_p99, 1),
            "static_interactive_p99_ms": round(st_p99, 1),
            "p99_improvement": round(st_p99 / cb_p99, 2),
        }

    best = ladder["int8"]
    return _emit({
        "metric": "llama_cb_tokens_s",
        "value": best["cb_tokens_s"],
        "unit": "tokens/s",
        "vs_baseline": best["speedup"],
        "ladder": ladder,
        "traffic": "8x[48-tok batch] + 24x[4-tok interactive], burst",
        "slots": 8,
        "page_size": 16,
    })


def bench_llama_prefix_cache(reps=2):
    """Serving row (serve.prefix_cache + mxnet_tpu.compile_cache): the
    PR-14 "never redo prior work" stack on the 12L llama serve config.

    Traffic is the prefix-cache sweet spot production chat exhibits: a
    burst of 32 requests sharing one 32-token system prompt with 8
    unique tail tokens each (80 % shared). Reported: TTFT p99 with the
    radix trie on vs off (same engine config, same burst — the on-side
    skips the shared prefill), the prefill tokens skipped, and the
    cold-start split — warming the same engine lattice twice against
    one persistent compile cache dir, where the second warmup must
    replay entirely from disk (disk hits, no new compiles) and beat the
    cold wall time. Hard-fails unless the trie actually hits, TTFT p99
    improves, outputs stay token-identical, and the disk-warm run
    compiles nothing new."""
    import os
    import shutil
    import subprocess
    import tempfile

    import numpy as onp

    from mxnet_tpu.models.llama import get_llama
    from mxnet_tpu.serve import ContinuousEngine, percentile

    net = get_llama("llama_serve_12l_test")
    net.initialize()

    rng = onp.random.RandomState(0)
    system = rng.randint(1, 500, size=32).tolist()
    reqs = [system + rng.randint(1, 500, size=8).tolist()
            for _ in range(32)]

    def build(name, prefix_on):
        eng = ContinuousEngine(net, max_seq=64, num_slots=8, page_size=16,
                               prefill_chunk=16, decode_path="pallas",
                               prefix_cache=prefix_on, name=name,
                               max_queue=64)
        eng.start()
        return eng

    def drive(prefix_on):
        eng = build("px_bench", prefix_on)
        best_p99, tokens = None, None
        for _ in range(reps):
            if prefix_on:
                # one settled request seeds the trie before the burst
                eng.submit(reqs[0], max_new_tokens=8).result(600)
            futs = [eng.submit(p, max_new_tokens=8) for p in reqs]
            outs = [f.result(600) for f in futs]
            p99 = percentile([o["ttft_ms"] for o in outs], 99)
            if best_p99 is None or p99 < best_p99:
                best_p99 = p99
            tokens = [o["tokens"] for o in outs]
        eng.assert_no_recompiles()
        snap = eng.metrics.snapshot()
        eng.close()
        return best_p99, tokens, snap

    base_p99, base_tokens, _ = drive(False)
    px_p99, px_tokens, snap = drive(True)
    if px_tokens != base_tokens:
        raise RuntimeError(
            "prefix-cache-on greedy output diverged from cache-off")
    if not snap["prefix_hit_rate"] > 0 or not snap["prefix_tokens_skipped"]:
        raise RuntimeError(
            f"80%-shared burst produced no trie reuse: "
            f"hit_rate={snap['prefix_hit_rate']} "
            f"skipped={snap['prefix_tokens_skipped']}")
    if px_p99 >= base_p99:
        raise RuntimeError(
            f"prefix cache lost on TTFT p99: {px_p99:.0f}ms on vs "
            f"{base_p99:.0f}ms off")

    # cold-start split: same lattice, one persistent cache dir, two
    # FRESH processes — in-process remeasurement would be flattered by
    # jax's in-memory compilation memo (identical HLO never reaches the
    # disk layer twice in one process), so each start pays exactly what
    # a scaled-up replica or reloaded tenant pays
    child_code = (
        "import json, os, sys, time\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import compile_cache\n"
        "from mxnet_tpu.models.llama import get_llama\n"
        "from mxnet_tpu.serve import ContinuousEngine\n"
        "compile_cache.enable(sys.argv[1])\n"
        "mx.random.seed(0)\n"
        "net = get_llama('llama_serve_12l_test')\n"
        "net.initialize()\n"
        "t0 = time.monotonic()\n"
        "eng = ContinuousEngine(net, max_seq=64, num_slots=8,\n"
        "                       page_size=16, prefill_chunk=16,\n"
        "                       decode_path='pallas', name='px_cold',\n"
        "                       max_queue=64)\n"
        "eng.start()\n"
        "warmup_s = time.monotonic() - t0\n"
        "eng.close()\n"
        "print('PX_COLD=' + json.dumps({\n"
        "    'warmup_s': warmup_s,\n"
        "    'disk_hits': compile_cache.disk_hits(),\n"
        "    'disk_misses': compile_cache.disk_misses()}))\n")
    d = tempfile.mkdtemp(prefix="mxtpu_ccbench_")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.dirname(os.path.abspath(__file__))]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    try:
        docs = []
        for _ in range(2):
            proc = subprocess.run(
                [sys.executable, "-c", child_code, d], env=env,
                capture_output=True, text=True, timeout=600)
            line = [ln for ln in proc.stdout.splitlines()
                    if ln.startswith("PX_COLD=")]
            if proc.returncode != 0 or not line:
                raise RuntimeError(
                    f"cold-start child failed rc={proc.returncode}: "
                    f"{proc.stderr[-2000:]}")
            docs.append(json.loads(line[0].split("=", 1)[1]))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    cold, warm = docs
    cold_s, warm_s = cold["warmup_s"], warm["warmup_s"]
    cold_misses = cold["disk_misses"]
    warm_hits, warm_misses = warm["disk_hits"], warm["disk_misses"]
    if not warm_hits or warm_misses:
        raise RuntimeError(
            f"disk-warm engine did not replay the lattice from the "
            f"persistent cache: hits={warm_hits} misses={warm_misses}")
    if warm_s >= cold_s:
        raise RuntimeError(
            f"disk-warm start ({warm_s:.2f}s) did not beat cold "
            f"({cold_s:.2f}s)")

    return _emit({
        "metric": "llama_prefix_ttft_p99_ms",
        "value": round(px_p99, 1),
        "unit": "ms",
        "vs_baseline": round(base_p99 / px_p99, 2),
        "ttft_p99_cache_off_ms": round(base_p99, 1),
        "prefill_tokens_skipped": snap["prefix_tokens_skipped"],
        "prefix_hit_rate": round(snap["prefix_hit_rate"], 3),
        "traffic": "32 reqs, 32-tok shared system + 8-tok unique tails",
        "cold_start": {
            "cold_warmup_s": round(cold_s, 2),
            "disk_warmup_s": round(warm_s, 2),
            "speedup": round(cold_s / warm_s, 2),
            "cold_disk_misses": cold_misses,
            "warm_disk_hits": warm_hits,
        },
    })


def bench_bandwidth():
    """KVStore push/pull bandwidth (tools/bandwidth parity, perf.md:263).

    On a 1-chip run the all-reduce degenerates to an HBM read+write of the
    buffer, so the row is labeled ``hbm_roundtrip`` and ``vs_peak`` compares
    against the chip's HBM bandwidth; on a real multi-chip mesh the label
    becomes ``ici_collective`` and ``vs_peak`` is vs ICI. The probe raises
    on degenerate timings instead of clamping (the r2 number was
    bytes/1e-9 garbage; see measure_pushpull_bandwidth)."""
    import jax

    from mxnet_tpu.kvstore.dist_tpu import measure_pushpull_bandwidth

    # 512 MB: bigger than VMEM, so the scanned reduce really rides HBM (a
    # 64 MB carry stays VMEM-resident and reads >HBM-peak "bandwidth");
    # iters sized so the loop holds the device ~0.3 s per measurement —
    # the two-loop difference must dwarf fetch RTT jitter
    gbs = measure_pushpull_bandwidth(size_mb=512, iters=200)
    n = len(jax.devices())
    if n == 1:
        kind = "hbm_roundtrip"
        peak = _chip_peak("hbm")
    else:
        kind = "ici_collective"
        peak = _chip_peak("ici")
    return _emit({
        "metric": "kvstore_pushpull_bw_512mb",
        "value": round(gbs, 2),
        "unit": "GB/s",
        "vs_baseline": None,
        "kind": kind,
        "vs_peak": round(gbs * 1e9 / peak, 3) if peak else None,
    })


def bench_resnet_input_pipeline(batch=32, n_batches=12, size=128, reps=3):
    """ResNet-50 forward fed live by the sharded RecordIO pipeline
    (RecordPipeline decode workers -> DeviceFeeder double-buffer) vs the
    SAME batches pre-materialized on device — the PR-20 input-pipeline
    overhead row. The feeder issues batch k+1's host pull + H2D before
    returning batch k, so with the model compute dominating, the
    pipeline-fed rate must land within a few percent of pre-materialized
    and the steady-state input stall near zero (what the overlap could
    not hide is `input_stall_ms`, also attributed to the profiler's
    `input` phase)."""
    import os
    import tempfile

    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu import np as mnp
    from mxnet_tpu import recordio
    from mxnet_tpu.io.pipeline import DeviceFeeder, RecordPipeline

    try:
        ctx = mx.tpu()
        ctx.jax_device()
    except Exception:
        ctx = mx.cpu()

    net = gluon.model_zoo.vision.resnet50_v1()
    net.initialize(ctx=mx.cpu())
    small = mnp.array(onp.zeros((1, 3, 64, 64), dtype="float32"),
                      ctx=mx.cpu())
    with autograd.predict_mode():
        net(small)
    if ctx.device_type != "cpu":
        net.reset_ctx(ctx)
    net.hybridize(static_alloc=True)

    # raw uint8 CHW images in the .rec (a realistic decode: bytes ->
    # float32/255 on the worker pool), crc-indexed
    rng = onp.random.RandomState(0)
    imgs = rng.randint(0, 256, (batch * n_batches, 3, size, size),
                       dtype=onp.uint8)

    def decode(payload):
        return onp.frombuffer(payload, dtype=onp.uint8) \
            .reshape(3, size, size).astype("float32") / 255.0

    def batchify(items):
        return mnp.array(onp.stack(items), ctx=mx.cpu())

    def run_epoch(batches):
        out = None
        for xb in batches:
            with autograd.predict_mode():
                out = net(xb)
        out.asnumpy()  # drain: the lazy runtime settles at the fetch

    with tempfile.TemporaryDirectory(prefix="bench_io.") as d:
        recf = os.path.join(d, "bench.rec")
        w = recordio.MXIndexedRecordIO(os.path.join(d, "bench.idx"),
                                       recf, "w")
        for i, img in enumerate(imgs):
            w.write_idx(i, img.tobytes())
        w.close()

        # pre-materialized arm: every batch already resident on device
        device = [mnp.array(imgs[i * batch:(i + 1) * batch]
                            .astype("float32") / 255.0, ctx=ctx)
                  for i in range(n_batches)]
        run_epoch(device)  # compile
        pre_walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            run_epoch(device)
            pre_walls.append(time.perf_counter() - t0)

        pipe = RecordPipeline([recf], batch_size=batch,
                              decode_fn=decode, batchify_fn=batchify,
                              name="bench-input")
        feeder = DeviceFeeder(pipe, ctx=ctx, name="bench-input-feeder")
        run_epoch(feeder)  # same program, warm; also warms the pool
        pipe_walls, stalls = [], []
        for _ in range(reps):
            feeder.reset()
            s0 = feeder.stats()["stall_ms"]
            t0 = time.perf_counter()
            run_epoch(feeder)
            pipe_walls.append(time.perf_counter() - t0)
            stalls.append(feeder.stats()["stall_ms"] - s0)
        pipe_stats = pipe.stats()
        pipe.close()

    n_img = batch * n_batches
    pre_img_s = n_img / min(pre_walls)
    pipe_img_s = n_img / min(pipe_walls)
    stall_ms = sorted(stalls)[len(stalls) // 2]
    row = _emit({
        "metric": f"resnet50_v1_input_pipeline_bs{batch}",
        "value": round(pipe_img_s, 2),
        "unit": "img/s",
        "vs_baseline": None,
        "pre_materialized_img_s": round(pre_img_s, 2),
        "vs_pre_materialized": round(pipe_img_s / pre_img_s, 4),
        "io_workers": pipe_stats["workers"],
        "io_worker_utilization": pipe_stats["worker_utilization"],
        "io_bytes_per_s": pipe_stats["bytes_per_s"],
        **_dispatch_meta(),
    })
    _emit({
        "metric": f"resnet50_v1_input_pipeline_bs{batch}_stall_ms",
        "value": round(stall_ms, 3),
        "unit": "ms",
        "vs_baseline": None,
        "per_batch_stall_ms": round(stall_ms / n_batches, 3),
    })
    return row


def main():
    rows = {}
    failures = {}
    for name, fn in [("infer", bench_resnet_infer),
                     ("infer_int8", bench_resnet_infer_int8),
                     ("infer_pallas_fused", bench_resnet_infer_pallas_fused),
                     ("bandwidth", bench_bandwidth),
                     ("guardrail_overhead", bench_guardrail_overhead),
                     ("ckpt_stall", bench_ckpt_stall),
                     ("elastic_resume", bench_elastic_resume),
                     ("elastic_resume_3d", bench_elastic_resume_3d),
                     ("collective_overlap", bench_collective_overlap),
                     ("lenet_eager", bench_lenet_eager),
                     ("trace_overhead", bench_trace_overhead),
                     ("bert", bench_bert_train),
                     ("bert_fused", bench_bert_train_fused),
                     ("llama_decode", bench_llama_decode),
                     ("llama_multistep_decode", bench_llama_multistep_decode),
                     ("llama_continuous_batching",
                      bench_llama_continuous_batching),
                     ("llama_prefix_cache", bench_llama_prefix_cache),
                     ("llama_long_seq", bench_llama_long_seq),
                     ("llama_long_seq4k",
                      lambda: bench_llama_long_seq(seq=4096, batch=2)),
                     ("resnet_input_pipeline", bench_resnet_input_pipeline),
                     ("resnet_train_bf16",
                      lambda: bench_resnet_train("bfloat16")),
                     ("resnet_train_fused", bench_resnet_train_fused)]:
        try:
            rows[name] = fn()
        except Exception as e:  # keep the suite alive; report what ran
            msg = f"{type(e).__name__}: {e}"
            # transport drops (remote_compile connection resets)
            # are transient — one retry before recording a failure
            if "remote_compile" in str(e) or "INTERNAL" in str(e):
                print(f"# bench {name}: transient error, retrying once: {msg}",
                      file=sys.stderr)
                try:
                    rows[name] = fn()
                    continue
                except Exception as e2:
                    msg = f"{type(e2).__name__}: {e2}"
            failures[name] = msg
            print(f"# bench {name} failed: {failures[name]}", file=sys.stderr)
    head = rows.get("resnet_train_fused") or rows.get("resnet_train_bf16") \
        or rows.get("bert_fused") or rows.get("bert") or rows.get("infer")
    if head is None:
        _emit({"metric": "bench_failed", "value": 0, "unit": "",
               "vs_baseline": 0, "errors": failures})
        return 1
    final = dict(head)
    final["extra"] = {k: v for k, v in rows.items()}
    if failures:
        final["errors"] = failures
    # resilience counters next to the telemetry numbers: BENCH rounds track
    # robustness cost (retries/degradations should be 0 on a healthy chip;
    # nonzero values explain a slow row before anyone re-runs it)
    try:
        from mxnet_tpu.resilience import resilience_stats

        final["resilience"] = resilience_stats()
    except Exception as e:
        print(f"# resilience stats unavailable: {e}", file=sys.stderr)
    _emit(final)
    return 0


if __name__ == "__main__":
    sys.exit(main())
