#!/usr/bin/env python
"""Pallas attempt on the probe's worst conv shape (VERDICT r4 Next #1b).

`exp/conv_chain_probe.json` names the bottleneck 1x1 expand/reduce
pairs as the shapes where XLA's conv kernels leave the most on the
table (stage2 pair: 0.22 MXU).  This probe measures THREE formulations
of the same relu-chained pair cycle, same protocol as the conv probe
(on-device lax.scan chain so XLA cannot elide iterations, two-loop
timing, 5 samples):

  xla_conv    — NCHW `conv_general_dilated` pair (the baseline the
                framework's ResNet actually runs; re-measured here so
                all arms share one session's host noise)
  xla_matmul  — channels-last (M, C) layout, the pair as two `jnp.dot`s
                (what a layout-rewrite alone would buy, no Pallas)
  pallas      — `mxnet_tpu.ops.pallas.conv1x1.conv1x1_pair`: both
                matmuls in ONE kernel, mid-channel intermediate pinned
                in VMEM (block_rows tuned per shape from a short sweep)

Fused-pair HBM floor: per row the pair does 4*C1*Cm flops against
4*C1 bytes of x-in + y-out traffic — AI = Cm flops/byte.  stage2
(Cm=128, machine balance 240) is HBM-bound with a fused ceiling of
~0.53 MXU; stage1 (Cm=256) sits right at the balance point.  Writes
exp/pallas_1x1_probe.json with the win/loss verdict per shape.

    python exp/pallas_1x1_probe.py
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as onp

from mxnet_tpu.ops.pallas.conv1x1 import conv1x1_pair

BF16 = jnp.bfloat16
PEAK = float(os.environ.get("MXNET_TPU_PEAK_FLOPS", 197e12))

# (name, batch, hw, C1, Cm): pair cycles C1 -> Cm -> C1
SHAPES = [
    ("stage1_1x1_pair", 256, 56, 64, 256),
    ("stage2_1x1_pair", 256, 28, 512, 128),
]


@functools.partial(jax.jit, static_argnums=(3,))
def chain_conv(x, w1, w2, n):
    def body(y, _):
        for w in (w1, w2):
            y = jax.lax.conv_general_dilated(
                y, w, (1, 1), "SAME",
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                preferred_element_type=BF16)
            y = jax.nn.relu(y)
        return y, None

    out, _ = jax.lax.scan(body, x, None, length=n)
    return jnp.sum(out.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(3,))
def chain_matmul(x, w1, w2, n):
    def body(y, _):
        h = jax.nn.relu(jnp.dot(y, w1, preferred_element_type=BF16))
        return jax.nn.relu(jnp.dot(h, w2, preferred_element_type=BF16)), None

    out, _ = jax.lax.scan(body, x, None, length=n)
    return jnp.sum(out.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(3, 4))
def chain_pallas(x, w1, w2, n, block_rows):
    def body(y, _):
        return conv1x1_pair(y, w1, w2, block_rows=block_rows), None

    out, _ = jax.lax.scan(body, x, None, length=n)
    return jnp.sum(out.astype(jnp.float32))


def measure(run_n, target_s=0.4):
    """Two-loop chain timing, probe protocol: returns (ms, samples)."""
    n0 = 8
    onp.asarray(run_n(n0))
    t0 = time.perf_counter()
    onp.asarray(run_n(n0))
    per = max((time.perf_counter() - t0) / n0, 1e-5)
    n = max(n0, int(target_s / per))
    onp.asarray(run_n(n))
    onp.asarray(run_n(2 * n))

    def t(m):
        t1 = time.perf_counter()
        onp.asarray(run_n(m))
        return time.perf_counter() - t1

    diffs = []
    for _ in range(5):
        d1, d2 = t(n), t(2 * n)
        if d2 > d1:
            diffs.append((d2 - d1) / n)
    if not diffs:
        raise RuntimeError("degenerate timing")
    diffs.sort()
    return diffs[len(diffs) // 2], diffs, n


def probe_shape(name, b, hw, c1, cm):
    rng = onp.random.RandomState(0)
    m = b * hw * hw
    he1 = (2.0 / c1) ** 0.5
    he2 = (2.0 / cm) ** 0.5
    w1 = jnp.asarray(rng.randn(c1, cm) * he1, dtype=BF16)
    w2 = jnp.asarray(rng.randn(cm, c1) * he2, dtype=BF16)
    w1_oihw = jnp.asarray(onp.asarray(w1, "float32").T
                          .reshape(cm, c1, 1, 1), dtype=BF16)
    w2_oihw = jnp.asarray(onp.asarray(w2, "float32").T
                          .reshape(c1, cm, 1, 1), dtype=BF16)
    x_nchw = jnp.asarray(rng.randn(b, c1, hw, hw) * 0.1, dtype=BF16)
    x_rows = jnp.asarray(
        onp.asarray(x_nchw, "float32").transpose(0, 2, 3, 1)
        .reshape(m, c1), dtype=BF16)
    flops = 2.0 * 2 * m * c1 * cm

    rows = {}
    ms, diffs, n = measure(
        lambda k: chain_conv(x_nchw, w1_oihw, w2_oihw, k))
    rows["xla_conv"] = {"ms": round(ms * 1e3, 3),
                        "mxu": round(flops / ms / PEAK, 3),
                        "spread_ms": [round(diffs[0] * 1e3, 3),
                                      round(diffs[-1] * 1e3, 3)],
                        "n_chain": n, "n_samples": len(diffs)}
    ms, diffs, n = measure(
        lambda k: chain_matmul(x_rows, w1, w2, k))
    rows["xla_matmul"] = {"ms": round(ms * 1e3, 3),
                          "mxu": round(flops / ms / PEAK, 3),
                          "spread_ms": [round(diffs[0] * 1e3, 3),
                                        round(diffs[-1] * 1e3, 3)],
                          "n_chain": n, "n_samples": len(diffs)}

    # short block_rows sweep, then the full measurement at the winner
    best_br, best_t = None, None
    for br in (512, 1024, 2048, 4096):
        if m % br:
            continue
        try:
            # warm BOTH static signatures the timed comparison uses —
            # (3,4) are static_argnums, so n=8 and n=24 compile
            # separately and an unwarmed n=24 would time compilation
            onp.asarray(chain_pallas(x_rows, w1, w2, 8, br))
            onp.asarray(chain_pallas(x_rows, w1, w2, 24, br))
        except Exception as e:  # VMEM OOM at large tiles: skip
            print(f"#   block_rows={br}: {type(e).__name__} (skipped)",
                  file=sys.stderr)
            continue
        t0 = time.perf_counter()
        onp.asarray(chain_pallas(x_rows, w1, w2, 24, br))
        dt = time.perf_counter() - t0
        print(f"#   block_rows={br}: {dt*1e3/24:.3f} ms", file=sys.stderr)
        if best_t is None or dt < best_t:
            best_br, best_t = br, dt
    if best_br is None:
        raise RuntimeError(
            f"{name}: no feasible block_rows candidate (M={m})")
    ms, diffs, n = measure(
        lambda k: chain_pallas(x_rows, w1, w2, k, best_br))
    rows["pallas"] = {"ms": round(ms * 1e3, 3),
                      "mxu": round(flops / ms / PEAK, 3),
                      "block_rows": best_br,
                      "spread_ms": [round(diffs[0] * 1e3, 3),
                                    round(diffs[-1] * 1e3, 3)],
                      "n_chain": n, "n_samples": len(diffs)}

    # fused HBM floor: x-in + y-out only
    fused_bytes = 2.0 * 2 * m * c1
    hbm_floor_ms = fused_bytes / 819e9 * 1e3
    out = {
        "shape": name,
        "cycle": f"{c1}->{cm}->{c1}",
        "rows_M": m,
        "flops_per_cycle_G": round(flops / 1e9, 2),
        "fused_hbm_floor_ms": round(hbm_floor_ms, 3),
        "arms": rows,
        "speedup_pallas_vs_conv": round(
            rows["xla_conv"]["ms"] / rows["pallas"]["ms"], 2),
        "speedup_pallas_vs_matmul": round(
            rows["xla_matmul"]["ms"] / rows["pallas"]["ms"], 2),
    }
    out["verdict"] = ("win" if out["speedup_pallas_vs_conv"] > 1.05
                      else "loss" if out["speedup_pallas_vs_conv"] < 0.95
                      else "tie")
    return out


def main():
    print(f"# device: {jax.devices()[0].device_kind}", file=sys.stderr)
    results = []
    for spec in SHAPES:
        r = probe_shape(*spec)
        results.append(r)
        print(json.dumps(r), flush=True)
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "pallas_1x1_probe.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
