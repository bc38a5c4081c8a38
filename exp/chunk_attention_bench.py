"""Microbenchmark of a prefill chunk's attention over pages on the chip
(issue 38): ``decode_attention._xla_blocks`` (a loop over the key blocks
the chunk's queries can see) at several block widths, against the form it
replaced, written out here: every column of the row's table gathered, the
scores for all of them formed, masked, softmaxed and multiplied whole.

    python3 exp/chunk_attention_bench.py [--tiny] [CELL ...]

One (1, T = 128) chunk of one layer at each cell's widths (float32,
``stored_precision``), a full layer and, where the model has one, a
window layer over its ring, at chunk positions from the first to the
table's last. Prints one JSON line a shape, position and candidate:
milliseconds a call (median of ``reps`` timed calls that end in
``block_until_ready``), the blocks walked, and the widest gap to the
whole form's result. ``--tiny`` runs small shapes on any platform (a
rehearsal).
"""
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from mxnet_tpu.ops import nn as ops  # noqa: E402
from mxnet_tpu.ops.pallas import decode_attention as da  # noqa: E402

# heads, KV heads, pages of the full table, (window, ring columns) or None
CELLS = {
    "command_a_plus": (128, 8, 56, (4096, 33)),
    "mellum2": (32, 4, 28, (1024, 9)),
    "mistral": (32, 8, 16, None),
    "falcon_h1": (20, 4, 4, None),
}
TINY = {"tiny": (8, 2, 12, (32, 5))}


def whole(q, k_pool, v_pool, table, sp, scale, window):
    """The parent's form: the table's every column in logical order, the
    scores whole."""
    b, h, t, d = q.shape
    kv, page = k_pool.shape[1], k_pool.shape[2]
    n = table.shape[1]
    first = jnp.zeros_like(sp)
    if window is not None:
        first = jnp.maximum((sp + (t - 1)) // page - (n - 1), 0)
        logical = first[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
        table = jnp.take_along_axis(table, logical % n, axis=1)
    k, v = ops.gather_pages(k_pool, table), ops.gather_pages(v_pool, table)
    prec = ops.stored_precision(q, k, v)
    qg = q.reshape(b, kv, h // kv, t, d)
    s = jnp.einsum("bngtd,bnsd->bngts", qg, k, precision=prec) * scale
    pos = (sp[:, None] + jnp.arange(t, dtype=jnp.int32))[:, :, None]
    kpos = ((first * page)[:, None]
            + jnp.arange(n * page, dtype=jnp.int32)[None, :])[:, None, :]
    seen = kpos <= pos
    if window is not None:
        seen = seen & (kpos > pos - window)
    s = jnp.where(seen[:, None, None], s, da._NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bngts,bnsd->bngtd", w, v, precision=prec)
    return out.reshape(b, h, t, d)


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
    cells = TINY if tiny else CELLS
    named = [a for a in sys.argv[1:] if a in cells]
    t, d, page = (8, 16, 8) if tiny else (128, 128, 128)
    widths = (16, 32) if tiny else (256, 512, 1024, 2048)
    reps = 3 if tiny else 20
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind}))
    rs = np.random.RandomState(0)
    for name in named or cells:
        h, kv, n_full, ring = cells[name]
        for window, n in [(None, n_full)] + ([ring] if ring else []):
            k_pool, v_pool = (jnp.asarray(
                rs.randn(n + 1, kv, page, d).astype(np.float32))
                for _ in "kv")
            table = jnp.asarray(1 + rs.permutation(n)[None].astype(np.int32))
            q = jnp.asarray(rs.randn(1, h, t, d).astype(np.float32))
            scale = d ** -0.5
            base = jax.jit(lambda *a, w=window: whole(*a, scale, w))
            # one trace a width: _BLOCK_KEYS is read while tracing
            loops = {bk: jax.jit(lambda *a, w=window, bk=bk: da._xla_blocks(
                *a, scale, w)) for bk in widths}
            # a chunk's positions: the first, and on to the table's last
            # (a ring has no last: as far as the full table would reach)
            chunks = n_full * page // t
            for at in sorted({0, chunks // 8, chunks // 4, chunks // 2,
                              3 * chunks // 4, chunks - 1}):
                sp = jnp.asarray([at * t], jnp.int32)
                args = (q, k_pool, v_pool, table, sp)
                base_ms, want = timed(base, args, reps)
                line = {"cell": name, "window": window, "pages": n,
                        "start_pos": at * t}
                print(json.dumps({**line, "form": "whole",
                                  "ms": round(base_ms, 4)}), flush=True)
                for bk in widths:
                    da._BLOCK_KEYS = bk
                    _, turns, c = da.block_range(
                        np.asarray([at * t]), t, page, n, window)
                    ms, got = timed(loops[bk], args, reps)
                    gap = float(jnp.max(jnp.abs(got - want)))
                    print(json.dumps({
                        **line, "form": "blocks", "bk": c * page,
                        "blocks": int(turns),
                        "ms": round(ms, 4), "gap": gap}), flush=True)

if __name__ == "__main__":
    main()
