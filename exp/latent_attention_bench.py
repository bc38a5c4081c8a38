"""Microbenchmark of latent attention over pages on the chip (issue 39):
the paged Pallas kernel in its latent form at a decode step's shapes, and
``decode_attention._xla_blocks`` at a prefill chunk's, with the pool
stored 576 wide (one whole-dimension block) and 640 wide (whole lane
tiles, zeros after channel 575), and the chunk's loop at several block
widths.

    python3 exp/latent_attention_bench.py [--tiny]

One layer at the ``axk1_docqa_closed_c8`` cell's widths: 64 query heads on
one array a position whose first 512 channels are the values, 8 lanes of
85 pages of 128, float32 at ``stored_precision``; decode lanes spread over
the cell's contexts (3,572 to 9,347), chunks at three positions. Prints
one JSON line a candidate: milliseconds a call (median of timed calls that
end in ``block_until_ready``) and the widest gap to the 576-wide result.
The pool is an argument of the jitted call, as it is of the step.
"""
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from mxnet_tpu.ops.pallas import decode_attention as da  # noqa: E402

V = 512


def timed(fn, args, reps):
    out = jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append((time.perf_counter() - t) * 1e3)
    return statistics.median(ms), out


def main():
    tiny = "--tiny" in sys.argv
    heads, lanes, pages, page, reps = (4, 2, 6, 8, 2) if tiny \
        else (64, 8, 85, 128, 20)
    v = 32 if tiny else V
    real = v + (8 if tiny else 64)
    if tiny:
        da.use_interpret(True)
    rs = np.random.RandomState(0)
    n_pool = lanes * pages + 1
    table = jnp.asarray(1 + np.arange(lanes * pages, dtype=np.int32)
                        .reshape(lanes, pages))
    lo, hi = (page, pages * page - 1) if tiny else (3572, 9347)
    sp = jnp.asarray(np.linspace(lo, hi, lanes).astype(np.int32))
    base = rs.randn(n_pool, 1, page, real).astype(np.float32)
    q_dec = rs.randn(lanes, heads, 1, real).astype(np.float32)
    t_chunk = page
    q_chk = rs.randn(1, heads, t_chunk, real).astype(np.float32)
    want = {}
    for width in (real, da.latent_width(real)):
        pad = ((0, 0), (0, 0), (0, 0), (0, width - real))
        pool = jnp.asarray(np.pad(base, pad))

        def decode(q, pool, table, sp):
            return da.paged_decode_attention(q, pool, None, table, sp,
                                             scale=0.13, v_width=v)

        ms, out = timed(jax.jit(decode),
                        (jnp.asarray(np.pad(q_dec, pad)), pool, table, sp),
                        reps)
        gap = float(jnp.abs(out - want.setdefault("decode", out)).max())
        print(json.dumps({"call": "decode", "stored": width,
                          "path": da.last_path(), "ms": round(ms, 4),
                          "gap": gap}), flush=True)
        for keys in (256, 512, 1024, 2048):
            if tiny and keys > 512:
                continue
            da._BLOCK_KEYS = keys if not tiny else keys // 16
            for start in (0, (pages // 2) * page, (pages - 1) * page):
                def chunk(q, pool, table, sp):
                    return da.paged_decode_attention(
                        q, pool, None, table, sp, scale=0.13, v_width=v)

                ms, out = timed(
                    jax.jit(chunk),
                    (jnp.asarray(np.pad(q_chk, pad)), pool, table[:1],
                     jnp.asarray([start], jnp.int32)), max(2, reps // 4))
                gap = float(jnp.abs(
                    out - want.setdefault(("chunk", start), out)).max())
                print(json.dumps({"call": "chunk", "stored": width,
                                  "block_keys": da._BLOCK_KEYS,
                                  "start": start, "ms": round(ms, 4),
                                  "gap": gap}), flush=True)
        da._BLOCK_KEYS = 512


if __name__ == "__main__":
    main()
