"""Probe: achievable bf16 matmul/conv rates on the real chip.

Microbench discipline: loop ON DEVICE via lax.scan
(output fed back as input to serialize), run at two scan lengths, and take
the time difference — one dispatch per measurement, RTT cancels, device time
dominates.
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as onp

PEAK = 197e12


def scan_rate(make_step, x0, flops_per_iter, m1=20, m2=120, reps=3):
    """make_step: x -> x (same shape/dtype). Returns seconds/iter."""

    @functools.partial(jax.jit, static_argnums=1)
    def run(x, m):
        def body(c, _):
            return make_step(c), None
        out, _ = jax.lax.scan(body, x, None, length=m)
        return out

    # compile both lengths, drain
    onp.asarray(jax.tree_util.tree_leaves(run(x0, m1))[0].reshape(-1)[0])
    onp.asarray(jax.tree_util.tree_leaves(run(x0, m2))[0].reshape(-1)[0])

    def t(m):
        t0 = time.perf_counter()
        r = run(x0, m)
        onp.asarray(jax.tree_util.tree_leaves(r)[0].reshape(-1)[0])
        return time.perf_counter() - t0

    diffs = []
    for _ in range(reps):
        d1 = t(m1)
        d2 = t(m2)
        if d2 > d1:
            diffs.append((d2 - d1) / (m2 - m1))
    diffs.sort()
    dt = diffs[len(diffs) // 2]
    return dt, flops_per_iter / dt


def probe_matmul():
    n = 4096
    a = jnp.array(onp.random.randn(n, n), dtype=jnp.bfloat16)

    w = jnp.array(onp.random.randn(n, n), dtype=jnp.bfloat16)

    def step(x):
        y = x @ w
        return y * (1.0 / n)  # keep magnitudes sane

    dt, rate = scan_rate(step, a, 2 * n**3)
    print(f"matmul {n} bf16: {dt*1e3:.3f} ms/iter {rate/1e12:.1f} TF/s "
          f"({rate/PEAK*100:.1f}%)")


def probe_conv(layout, B=256, C=256, H=14, ksz=3):
    if layout == "NCHW":
        x = jnp.array(onp.random.randn(B, C, H, H), dtype=jnp.bfloat16)
        dn = ("NCHW", "OIHW", "NCHW")
        w = jnp.array(onp.random.randn(C, C, ksz, ksz), dtype=jnp.bfloat16)
    else:
        x = jnp.array(onp.random.randn(B, H, H, C), dtype=jnp.bfloat16)
        dn = ("NHWC", "HWIO", "NHWC")
        w = jnp.array(onp.random.randn(ksz, ksz, C, C), dtype=jnp.bfloat16)
    p = ksz // 2

    def conv(x):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), [(p, p), (p, p)], dimension_numbers=dn)

    def step(x):
        return conv(x) * 0.01

    fl = 2 * B * H * H * C * C * ksz * ksz
    dt, rate = scan_rate(step, x, fl)
    print(f"conv {layout} B{B} C{C} H{H} k{ksz}: {dt*1e3:.3f} ms "
          f"{rate/1e12:.1f} TF/s ({rate/PEAK*100:.1f}%)")

    # fwd+bwd via vjp inside scan: carry x, apply grad-shaped update
    def stepg(x):
        y, vjp = jax.vjp(conv, x)
        (dx,) = vjp(y)
        return x + dx * 1e-6

    dt, rate = scan_rate(stepg, x, 3 * fl)
    print(f"conv {layout} f+b: {dt*1e3:.3f} ms {rate/1e12:.1f} TF/s "
          f"({rate/PEAK*100:.1f}%)")


if __name__ == "__main__":
    print("device:", jax.devices()[0].device_kind)
    probe_matmul()
    for lay in ("NCHW", "NHWC"):
        probe_conv(lay)
    # first resnet conv: 7x7 s2 C3 -> poor MXU fit
    for lay in ("NCHW", "NHWC"):
        B, H = 256, 224
        if lay == "NCHW":
            x = jnp.array(onp.random.randn(B, 3, H, H), dtype=jnp.bfloat16)
            dn = ("NCHW", "OIHW", "NCHW")
            w = jnp.array(onp.random.randn(64, 3, 7, 7), dtype=jnp.bfloat16)
        else:
            x = jnp.array(onp.random.randn(B, H, H, 3), dtype=jnp.bfloat16)
            dn = ("NHWC", "HWIO", "NHWC")
            w = jnp.array(onp.random.randn(7, 7, 3, 64), dtype=jnp.bfloat16)

        def conv0(x, w=w, dn=dn):
            return jax.lax.conv_general_dilated(
                x, w, (2, 2), [(3, 3), (3, 3)], dimension_numbers=dn)

        f = jax.jit(conv0)
        y = f(x)
        onp.asarray(y.reshape(-1)[0])

        def t(k):
            t0 = time.perf_counter()
            r = None
            for _ in range(k):
                r = f(x)
            onp.asarray(r.reshape(-1)[0])
            return time.perf_counter() - t0

        diffs = []
        for _ in range(3):
            d1, d2 = t(10), t(110)
            if d2 > d1:
                diffs.append((d2 - d1) / 100)
        diffs.sort()
        dt = diffs[len(diffs) // 2]
        fl = 2 * B * 112 * 112 * 64 * 3 * 49
        print(f"conv0 7x7s2 {lay}: {dt*1e3:.3f} ms {fl/dt/1e12:.1f} TF/s "
              f"({fl/dt/PEAK*100:.1f}%)")
