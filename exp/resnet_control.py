#!/usr/bin/env python
"""Framework-free ResNet-50 v1 training control (VERDICT r4 Next #1a).

The question this answers: is the repo's ResNet-50 train MFU
(0.2996-0.3071 in BENCH_r04.json) a ceiling imposed by this framework's
code, or by XLA's conv kernels at these shapes?  The control is an
idiomatic, hand-rolled pure-JAX ResNet-50 v1 train step with ZERO
framework imports — plain dicts of arrays, `lax.conv_general_dilated`,
`value_and_grad`, donated buffers — at the exact bench config:
batch 256 @ 224x224, bf16 compute / fp32 master weights, SGD momentum
0.9 + wd 1e-4, softmax CE, and the same two-loop timing (run k1 steps +
host fetch, then k2, divide the difference — fetch RTT cancels).

Variants:
  * nchw        — the framework's own layout (gluon NCHW), single dispatch
  * nhwc        — TPU-native layout, single dispatch
  * fused       — 8 steps chained in one `lax.scan` dispatch (mirrors the
                  bench's `step_n` fused8 row: amortizes per-step dispatch)
  * s2d         — MLPerf-style 2x2 space-to-depth stem: input
                  (B,112,112,12), conv0 re-expressed as a 4x4 s1 matmul-
                  friendly conv (the 7x7s2 stem measures 0.07 MXU in
                  exp/conv_chain_probe.json; this is the known remedy).
                  NOTE round-3's exp/resnet_bound.py s2d variant was
                  wrong (4x4 s2d + stride 2 collapsed the network to
                  1/16 spatial, 1.6 GF/img); this one keeps the true
                  FLOP count (22.4 -> 22.5 GF/img, stem kernel 8x8/49).

MFU accounting matches bench.py: numerator = XLA cost_analysis flops of
the compiled SINGLE step (the fused variant multiplies by the window —
XLA counts a scan body once), denominator = v5e bf16 peak 197 TF/s.

Writes exp/resnet_control.json; interpreted in PERF.md ("ResNet-50
limiter"). Run: python exp/resnet_control.py [all|nchw|nhwc|s2d]
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as onp

PEAK = float(os.environ.get("MXNET_TPU_PEAK_FLOPS", 197e12))
BATCH = 256
LR, MOM, WD = 0.1, 0.9, 1e-4

# resnet50 v1 stages: (blocks, mid_channels, first_stride)
STAGES = [(3, 64, 1), (4, 128, 2), (6, 256, 2), (3, 512, 2)]


def init_params(nhwc, s2d=False):
    p = {}
    rng = onp.random.RandomState(0)

    def conv_w(name, cin, cout, k):
        w = rng.randn(k, k, cin, cout) * (2.0 / (k * k * cin)) ** 0.5
        if not nhwc:
            w = w.transpose(3, 2, 0, 1)  # HWIO -> OIHW
        p[name] = w.astype("float32")

    def bn(name, c):
        p[name + ".g"] = onp.ones(c, "float32")
        p[name + ".b"] = onp.zeros(c, "float32")

    if s2d:
        # 7x7x3 stem padded to 8x8x3, blocked 2x2 -> 4x4x12 on the 112 grid
        conv_w("conv0", 12, 64, 4)
    else:
        conv_w("conv0", 3, 64, 7)
    bn("bn0", 64)
    cin = 64
    for si, (blocks, mid, _stride) in enumerate(STAGES):
        cout = mid * 4
        for bi in range(blocks):
            pre = f"s{si}b{bi}"
            conv_w(pre + ".c1", cin, mid, 1)
            bn(pre + ".n1", mid)
            conv_w(pre + ".c2", mid, mid, 3)
            bn(pre + ".n2", mid)
            conv_w(pre + ".c3", mid, cout, 1)
            bn(pre + ".n3", cout)
            if bi == 0:
                conv_w(pre + ".cd", cin, cout, 1)
                bn(pre + ".nd", cout)
            cin = cout
    p["fc.w"] = (rng.randn(2048, 1000) * 0.01).astype("float32")
    p["fc.b"] = onp.zeros(1000, "float32")
    return {k: jnp.array(v) for k, v in p.items()}


def make_fwd(nhwc, s2d=False):
    dn = ("NHWC", "HWIO", "NHWC") if nhwc else ("NCHW", "OIHW", "NCHW")
    caxis = 3 if nhwc else 1

    def conv(x, w, stride=1, pad=None):
        k = w.shape[0] if nhwc else w.shape[2]
        if pad is None:
            pad = ((k - 1) // 2, (k - 1) // 2)
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [pad, pad], dimension_numbers=dn)

    def bnorm(x, g, b):
        axes = tuple(i for i in range(4) if i != caxis)
        m = jnp.mean(x, axis=axes)
        v = jnp.var(x, axis=axes)
        sh = [1, 1, 1, 1]
        sh[caxis] = x.shape[caxis]
        inv = (g / jnp.sqrt(v + 1e-5)).reshape(sh)
        return (x - m.reshape(sh)) * inv + b.reshape(sh)

    def fwd(p, x):
        if s2d:
            # x is (B,112,112,12); 4x4 s1 conv == padded-to-8x8 7x7s2 on
            # 224. pad (2,1): output j must read rows 2j-3..2j+4 of the
            # original grid = blocks j-2+1..j+2 with the kernel's first
            # block row zero — i.e. two lead blocks of padding, one tail
            x = conv(x, p["conv0"], 1, pad=(2, 1))
        else:
            x = conv(x, p["conv0"], 2, pad=(3, 3))
        x = jax.nn.relu(bnorm(x, p["bn0.g"], p["bn0.b"]))
        if nhwc:
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                [(0, 0), (1, 1), (1, 1), (0, 0)])
        else:
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                [(0, 0), (0, 0), (1, 1), (1, 1)])
        for si, (blocks, mid, stride) in enumerate(STAGES):
            for bi in range(blocks):
                st = stride if bi == 0 else 1
                pre = f"s{si}b{bi}"
                idn = x
                # v1 bottleneck: stride on the FIRST 1x1 (matches the
                # framework's BottleneckV1, model_zoo/vision/resnet.py:58
                # — v1.5 strides the 3x3 instead and does ~7% more FLOPs)
                y = jax.nn.relu(bnorm(conv(x, p[pre + ".c1"], st),
                                      p[pre + ".n1.g"], p[pre + ".n1.b"]))
                y = jax.nn.relu(bnorm(conv(y, p[pre + ".c2"]),
                                      p[pre + ".n2.g"], p[pre + ".n2.b"]))
                y = bnorm(conv(y, p[pre + ".c3"]),
                          p[pre + ".n3.g"], p[pre + ".n3.b"])
                if bi == 0:
                    idn = bnorm(conv(idn, p[pre + ".cd"], st),
                                p[pre + ".nd.g"], p[pre + ".nd.b"])
                x = jax.nn.relu(y + idn)
        x = jnp.mean(x, axis=(1, 2) if nhwc else (2, 3))
        return x @ p["fc.w"] + p["fc.b"]

    return fwd


def make_step(fwd):
    def loss_of(params, x, y):
        pb = {k: v.astype(jnp.bfloat16) if v.dtype == jnp.float32 else v
              for k, v in params.items()}
        logits = fwd(pb, x.astype(jnp.bfloat16)).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, y[:, None], 1))

    def sgd(params, mom, grads):
        newp, newm = {}, {}
        for k in params:
            m = MOM * mom[k] + grads[k] + WD * params[k]
            newm[k] = m
            newp[k] = params[k] - LR * m
        return newp, newm

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, mom, x, y):
        l, g = jax.value_and_grad(loss_of)(params, x, y)
        newp, newm = sgd(params, mom, g)
        return newp, newm, l

    @functools.partial(jax.jit, donate_argnums=(0, 1), static_argnums=4)
    def step_n(params, mom, x, y, n):
        def body(carry, _):
            p, m = carry
            l, g = jax.value_and_grad(loss_of)(p, x, y)
            return sgd(p, m, g), l

        (p, m), ls = jax.lax.scan(body, (params, mom), None, length=n)
        return p, m, ls[-1]

    return loss_of, step, step_n


def timed_diff(run, fetch, k1, k2, repeats=3):
    def loop(k):
        t0 = time.perf_counter()
        r = None
        for _ in range(k):
            r = run()
        fetch(r)
        return time.perf_counter() - t0

    diffs = []
    for _ in range(repeats):
        d1, d2 = loop(k1), loop(k2)
        if d2 > d1:
            diffs.append((d2 - d1) / (k2 - k1))
    if not diffs:
        raise RuntimeError("degenerate timing")
    diffs.sort()
    return diffs


def compile_step(step, params, mom, x, y):
    """AOT-compile once; returns (executable, flops). The executable is
    reused for the timed loop — the plain jit call path would NOT reuse
    it and would pay a second full compile."""
    compiled = step.lower(params, mom, x, y).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return compiled, (ca or {}).get("flops", 0)


def run_variant(nhwc, s2d=False, fuse=8):
    tag = ("nhwc" if nhwc else "nchw") + ("_s2d" if s2d else "")
    fwd = make_fwd(nhwc, s2d)
    params = init_params(nhwc, s2d)
    mom = {k: jnp.zeros_like(v) for k, v in params.items()}
    if s2d:
        shape = (BATCH, 112, 112, 12)
    else:
        shape = (BATCH, 224, 224, 3) if nhwc else (BATCH, 3, 224, 224)
    rng = onp.random.RandomState(1)
    x = jnp.array(rng.uniform(-1, 1, shape).astype("float32"))
    y = jnp.array(rng.randint(0, 1000, (BATCH,)).astype("int32"))
    _, step, step_n = make_step(fwd)

    compiled, flops = compile_step(step, params, mom, x, y)
    rows = []

    # -- single dispatch ---------------------------------------------
    state = [params, mom]

    def run1():
        p, m, l = compiled(state[0], state[1], x, y)
        state[0], state[1] = p, m
        return l

    float(run1())  # drain
    diffs = timed_diff(run1, float, 3, 15)
    dt = diffs[len(diffs) // 2]
    rows.append({
        "variant": tag, "img_s": round(BATCH / dt, 1),
        "ms_per_step": round(dt * 1e3, 2),
        "mfu": round(flops / dt / PEAK, 4),
        "counted_gf_per_img": round(flops / 1e9 / BATCH, 1),
        "n": len(diffs),
        "spread_img_s": [round(BATCH / diffs[-1], 1),
                         round(BATCH / diffs[0], 1)],
    })

    # -- fused: `fuse` steps per dispatch (bench fused8 protocol) ----
    # `state` still holds the live post-step buffers (the originals were
    # donated away by the single-dispatch loop)

    def runf():
        p, m, l = step_n(state[0], state[1], x, y, fuse)
        state[0], state[1] = p, m
        return l

    float(runf())
    diffs = timed_diff(runf, float, 2, 8)
    dt = diffs[len(diffs) // 2] / fuse
    rows.append({
        "variant": f"{tag}_fused{fuse}", "img_s": round(BATCH / dt, 1),
        "ms_per_step": round(dt * 1e3, 2),
        "mfu": round(flops / dt / PEAK, 4),
        "counted_gf_per_img": round(flops / 1e9 / BATCH, 1),
        "n": len(diffs),
        "spread_img_s": [round(fuse * BATCH / diffs[-1], 1),
                         round(fuse * BATCH / diffs[0], 1)],
    })
    for r in rows:
        print(json.dumps(r), flush=True)
    return rows


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which not in ("all", "nchw", "nhwc", "s2d"):
        sys.exit(f"unknown variant {which!r}: use all|nchw|nhwc|s2d")
    dev = jax.devices()[0]
    print(f"# device: {dev.device_kind}", file=sys.stderr)
    rows = []
    if which in ("all", "nchw"):
        rows += run_variant(False)
    if which in ("all", "nhwc"):
        rows += run_variant(True)
    if which in ("all", "s2d"):
        rows += run_variant(True, s2d=True)
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "resnet_control.json")
    prior = []
    if os.path.exists(out) and which != "all":
        with open(out) as f:
            prior = [r for r in json.load(f)
                     if not any(r["variant"] == n["variant"] for n in rows)]
    with open(out, "w") as f:
        json.dump(prior + rows, f, indent=2)
    print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
