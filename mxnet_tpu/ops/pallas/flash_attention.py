"""Flash attention: Pallas TPU kernels (fwd + bwd) + XLA reference fallback.

The reference framework has no attention op at all — only fused matmul
helpers (``src/operator/contrib/transformer.cc``); SURVEY.md §5 requires the
TPU build to introduce memory-efficient attention natively.

Design (flash-attention-2 schedule adapted to TPU tiling):

* forward: grid ``(batch*heads, q_blocks, k_blocks)``; K/V blocks stream
  from HBM through VMEM with running max/sum accumulators in fp32 VMEM
  scratch; the log-sum-exp per query row is a second output so the backward
  can recompute probabilities blockwise.
* backward: two Pallas kernels — ``dq`` over ``(bh, q_blocks, k_blocks)``
  and ``dk/dv`` over ``(bh, k_blocks, q_blocks)`` — each recomputing the
  probability block from (q, k, lse) in VMEM, so training memory stays
  O(T·block) instead of the O(T²) score materialization.
* masking: *valid-length* masking (the BERT ``valid_length`` path) happens
  inside the kernel from a ``(B, 1)`` int32 SMEM input — no dense (T, T)
  mask is ever materialized on the flash path. Arbitrary dense masks fall
  back to the XLA reference implementation.
* shapes: head_dim is zero-padded to the 128 lane width (so the model-zoo
  head_dim 64 runs on the MXU at full tile) and sequence lengths are padded
  to the 128 block size; padded key columns are masked via the same
  valid-length mechanism and padded query rows are sliced off.

Set ``use_interpret(True)`` to run the same kernels through the Pallas
interpreter on CPU (used by the test suite on the virtual device mesh).
"""
from __future__ import annotations

import functools
import math

_NEG_INF = -1e30  # finite "minus infinity": keeps fully-masked rows NaN-free
_BLOCK = 128      # MXU tile edge: minimum q/k block size and lane padding
_MAX_BLOCK_FWD = 1024   # VMEM-bounded: scores tile 1024^2 f32 = 4 MB
_MAX_BLOCK_BWD = 512    # bwd holds 3 score-sized tiles (p, dp, ds)

# trace-time record of which implementation the last attention() call chose
# ("pallas" | "xla"); tests and bench assert the flash path actually ran.
_LAST_PATH = None

_INTERPRET = False


def use_interpret(flag: bool) -> None:
    """Force Pallas interpreter mode (CPU testing of the TPU kernels)."""
    global _INTERPRET
    _INTERPRET = bool(flag)


# bench/test override of the empirical crossover routing: None (measured
# routing), "xla" (force fallback — the bench ablation arm), or "pallas"
# (force the kernel where it supports the shape).
_FORCE_PATH = None


def force_path(path) -> None:
    """Override attention path selection: None | 'xla' | 'pallas'."""
    global _FORCE_PATH
    if path not in (None, "xla", "pallas"):
        raise ValueError(f"force_path: {path!r} not in (None,'xla','pallas')")
    _FORCE_PATH = path


def last_path():
    return _LAST_PATH


def _reference_attention(q, k, v, mask=None, causal=False, scale=None,
                         valid_length=None):
    """XLA attention: materializes scores; fallback for dense masks/CPU."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * s
    tq, tk = scores.shape[-2], scores.shape[-1]
    if causal:
        cm = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        scores = jnp.where(cm, scores, _NEG_INF)
    if valid_length is not None:
        kpos = jnp.arange(tk).reshape(1, 1, 1, tk)
        vl = valid_length.astype(jnp.int32).reshape(-1, 1, 1, 1)
        scores = jnp.where(kpos < vl, scores, _NEG_INF)
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, _NEG_INF)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    # fully-masked rows (e.g. valid_length 0, or causal with tq > tk) emit
    # zeros — not a uniform average over keys the mask excluded; this is the
    # semantics the flash kernels implement and gradients stay zero too
    alive = jnp.max(scores, axis=-1, keepdims=True) > _NEG_INF / 2
    w = jnp.where(alive, w, 0)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def _round_up(x, m):
    return (x + m - 1) // m * m


def _pick_block(t, maxb):
    """Largest block ≤ maxb whose T-padding wastes ≤12.5%: big blocks keep
    the MXU busy (measured 30→50 TF/s going 512→1024 at T=8192), small
    sequences shouldn't pay for block-rounding."""
    tp = _round_up(t, _BLOCK)
    c = maxb
    while c > _BLOCK:
        if _round_up(tp, c) <= 1.125 * tp:
            return c
        c //= 2
    return _BLOCK


def _pad_qkv(q, k, v, bq, bk):
    """Zero-pad (B,H,T,D) to block-aligned (B,H,Tp,Dp); zeros are masked
    out by the in-kernel valid-length clamp, so padding never leaks."""
    import jax.numpy as jnp

    b, h, tq, d = q.shape
    tk = k.shape[2]
    tqp, tkp, dp = _round_up(tq, bq), _round_up(tk, bk), _round_up(d, _BLOCK)

    def pad(x, tp):
        t = x.shape[2]
        if t == tp and x.shape[3] == dp:
            return x
        return jnp.pad(x, ((0, 0), (0, 0), (0, tp - t), (0, dp - x.shape[3])))

    return pad(q, tqp), pad(k, tkp), pad(v, tkp)


def _kvalid_array(valid_length, b, tk):
    """(B,) int32 of per-batch valid key counts (clamped to true Tk)."""
    import jax.numpy as jnp

    if valid_length is None:
        return jnp.full((b,), tk, dtype=jnp.int32)
    vl = jnp.minimum(valid_length.astype(jnp.int32), tk)
    return vl.reshape(b)


def _score_mask(sc, qi, ki, kvalid, causal, causal_off, block_q, block_k):
    """Apply causal + valid-length masking to one (block_q, block_k) tile."""
    import jax
    import jax.numpy as jnp

    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = kpos < kvalid
    if causal:
        keep = jnp.logical_and(keep, kpos <= qpos + causal_off)
    return jnp.where(keep, sc, jnp.float32(_NEG_INF))


def _flash_fwd(q, k, v, kvalid, causal, causal_off, scale, bq, bk):
    """Pallas forward on padded (B,H,Tp,Dp); returns (out, lse)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk = k.shape[2]
    n_q, n_k = tq // bq, tk // bk

    def kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
               m_scr, l_scr, acc_scr):
        qi, ki = pl.program_id(1), pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        # whole (B,) lengths vector lives in SMEM; pick this program's batch
        kvalid = vl_ref[jax.lax.div(pl.program_id(0), jnp.int32(h))]
        run = ki * bk < kvalid
        if causal:
            run = jnp.logical_and(run, ki * bk <= qi * bq + bq - 1 + causal_off)

        @pl.when(run)
        def _body():
            qb = q_ref[0].astype(jnp.float32) * jnp.float32(scale)
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            sc = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            sc = _score_mask(sc, qi, ki, kvalid, causal, causal_off, bq, bk)
            m_prev = m_scr[:]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            # dead rows (everything masked): exp(-1e30 - -1e30) would give 1;
            # zero them so l stays 0 and the output row is exactly 0
            alive = m_new > jnp.float32(_NEG_INF / 2)
            p = jnp.where(alive, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[:] = m_new

        @pl.when(ki == n_k - 1)
        def _finish():
            l = l_scr[:]
            lsafe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_scr[:] / lsafe).astype(o_ref.dtype)
            # dead rows keep lse = _NEG_INF: the bwd kernels key off it
            lse = jnp.where(l == 0.0, jnp.float32(_NEG_INF),
                            m_scr[:] + jnp.log(lsafe))
            lse_ref[0, 0] = lse[:, 0]

    q3 = q.reshape(b * h, tq, d)
    k3 = k.reshape(b * h, tk, d)
    v3 = v.reshape(b * h, tk, d)

    def qix(bh, qi, ki):
        del ki
        return (bh, qi, jnp.int32(0))

    def kix(bh, qi, ki):
        del qi
        return (bh, ki, jnp.int32(0))

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((b,), lambda *_: (jnp.int32(0),),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), qix),
            pl.BlockSpec((1, bk, d), kix),
            pl.BlockSpec((1, bk, d), kix),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), qix),
            # (B*H, 1, T) so the block's last two dims are (1, 128): the
            # TPU lowering rejects a (1, 128) block over a 2D (B*H, T) array
            pl.BlockSpec((1, 1, bq), lambda bh, qi, ki: (bh, jnp.int32(0), qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_INTERPRET,
    )(kvalid, q3, k3, v3)
    return out.reshape(b, h, tq, d), lse.reshape(b * h, tq)


def _flash_bwd_dq(q, k, v, g, lse, delta, kvalid, causal, causal_off, scale, bq, bk):
    """dq on padded shapes: one pass over K blocks per Q block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk = k.shape[2]
    n_k = tk // bk

    def kernel(vl_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, dl_ref,
               dq_ref, dq_scr):
        qi, ki = pl.program_id(1), pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            dq_scr[:] = jnp.zeros_like(dq_scr)

        # whole (B,) lengths vector lives in SMEM; pick this program's batch
        kvalid = vl_ref[jax.lax.div(pl.program_id(0), jnp.int32(h))]
        run = ki * bk < kvalid
        if causal:
            run = jnp.logical_and(run, ki * bk <= qi * bq + bq - 1 + causal_off)

        @pl.when(run)
        def _body():
            qb = q_ref[0].astype(jnp.float32) * jnp.float32(scale)
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            gb = g_ref[0].astype(jnp.float32)
            sc = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            sc = _score_mask(sc, qi, ki, kvalid, causal, causal_off, bq, bk)
            lse_row = lse_ref[0, 0][:, None]
            p = jnp.where(lse_row > jnp.float32(_NEG_INF / 2),
                          jnp.exp(sc - lse_row), 0.0)
            dp = jax.lax.dot_general(
                gb, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dl_ref[0, 0][:, None])
            dq_scr[:] += jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * jnp.float32(scale)

        @pl.when(ki == n_k - 1)
        def _finish():
            dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)

    def qix(bh, qi, ki):
        del ki
        return (bh, qi, jnp.int32(0))

    def kix(bh, qi, ki):
        del qi
        return (bh, ki, jnp.int32(0))

    def rix(bh, qi, ki):
        del ki
        return (bh, jnp.int32(0), qi)

    dq = pl.pallas_call(
        kernel,
        grid=(b * h, tq // bq, n_k),
        in_specs=[
            pl.BlockSpec((b,), lambda *_: (jnp.int32(0),),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), qix),
            pl.BlockSpec((1, bk, d), kix),
            pl.BlockSpec((1, bk, d), kix),
            pl.BlockSpec((1, bq, d), qix),
            pl.BlockSpec((1, 1, bq), rix),
            pl.BlockSpec((1, 1, bq), rix),
        ],
        out_specs=pl.BlockSpec((1, bq, d), qix),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_INTERPRET,
    )(kvalid, q.reshape(b * h, tq, d), k.reshape(b * h, tk, d),
      v.reshape(b * h, tk, d), g.reshape(b * h, tq, d),
      lse.reshape(b * h, 1, tq), delta.reshape(b * h, 1, tq))
    return dq.reshape(b, h, tq, d)


def _flash_bwd_dkv(q, k, v, g, lse, delta, kvalid, causal, causal_off, scale, bq, bk):
    """dk, dv on padded shapes: one pass over Q blocks per K block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk = k.shape[2]
    n_q = tq // bq

    def kernel(vl_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, dl_ref,
               dk_ref, dv_ref, dk_scr, dv_scr):
        ki, qi = pl.program_id(1), pl.program_id(2)

        @pl.when(qi == 0)
        def _init():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

        kvalid = vl_ref[jax.lax.div(pl.program_id(0), jnp.int32(h))]
        run = ki * bk < kvalid
        if causal:
            run = jnp.logical_and(run, qi * bq + bq - 1 >= ki * bk - causal_off)

        @pl.when(run)
        def _body():
            qb = q_ref[0].astype(jnp.float32) * jnp.float32(scale)
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            gb = g_ref[0].astype(jnp.float32)
            sc = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            sc = _score_mask(sc, qi, ki, kvalid, causal, causal_off, bq, bk)
            lse_row = lse_ref[0, 0][:, None]
            p = jnp.where(lse_row > jnp.float32(_NEG_INF / 2),
                          jnp.exp(sc - lse_row), 0.0)
            dv_scr[:] += jax.lax.dot_general(
                p, gb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                gb, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dl_ref[0, 0][:, None])
            dk_scr[:] += jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(qi == n_q - 1)
        def _finish():
            dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    def qix(bh, ki, qi):
        del ki
        return (bh, qi, jnp.int32(0))

    def kix(bh, ki, qi):
        del qi
        return (bh, ki, jnp.int32(0))

    def rix(bh, ki, qi):
        del ki
        return (bh, jnp.int32(0), qi)

    dk, dv = pl.pallas_call(
        kernel,
        grid=(b * h, tk // bk, n_q),
        in_specs=[
            pl.BlockSpec((b,), lambda *_: (jnp.int32(0),),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), qix),
            pl.BlockSpec((1, bk, d), kix),
            pl.BlockSpec((1, bk, d), kix),
            pl.BlockSpec((1, bq, d), qix),
            pl.BlockSpec((1, 1, bq), rix),
            pl.BlockSpec((1, 1, bq), rix),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), kix),
            pl.BlockSpec((1, bk, d), kix),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_INTERPRET,
    )(kvalid, q.reshape(b * h, tq, d), k.reshape(b * h, tk, d),
      v.reshape(b * h, tk, d), g.reshape(b * h, tq, d),
      lse.reshape(b * h, 1, tq), delta.reshape(b * h, 1, tq))
    return dk.reshape(b, h, tk, d), dv.reshape(b, h, tk, d)


def _platform_of(x):
    """Platform the op will execute on: a concrete array's own device (an
    eager CPU array next to an idle TPU chip must NOT pick the TPU kernel);
    tracers have no devices — they lower for the default backend."""
    import jax

    try:
        return next(iter(x.devices())).platform
    except Exception:
        return jax.default_backend()


def _supports_pallas(q, k):
    if not (_INTERPRET or _platform_of(q) == "tpu"):
        return False
    if q.ndim != 4 or q.shape[-1] > 256:
        return False
    if _INTERPRET:
        # CPU kernel tests: exercise the pallas path on small shapes (below
        # half a block the padded-T waste makes even the interpreter moot)
        return q.shape[2] * k.shape[2] >= (_BLOCK // 2) ** 2
    # On hardware the crossover is empirical (v5e, B64 H12 D64, fwd+bwd):
    # XLA wins 3.3x at T=128 (0.39 vs 1.27 ms) and still ~1.2x at T=512;
    # flash wins 1.5x at T=2048 (7.3 vs 10.8 ms) and its O(T^2)->O(T*block)
    # memory is what makes long context fit at all. Route to flash only
    # where it pays.
    return q.shape[2] * k.shape[2] > 1024 * 1024


# -- Pallas path (custom vjp over the flash kernels) ------------------------
# The path choice (pallas vs xla) depends only on trace-static facts
# (shapes, backend, mask presence), so it happens in attention() before the
# custom_vjp boundary; residuals stay pure JAX arrays.


@functools.partial(
    __import__("jax").custom_vjp, nondiff_argnums=(4, 5)
)
def _flash_core(q, k, v, valid_length, causal, scale):
    out, _ = _flash_core_fwd(q, k, v, valid_length, causal, scale)
    return out


def _flash_core_fwd(q, k, v, valid_length, causal, scale):
    b, h, tq, d = q.shape
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = _pick_block(tq, _MAX_BLOCK_FWD)
    bk = _pick_block(k.shape[2], _MAX_BLOCK_FWD)
    qp, kp, vp = _pad_qkv(q, k, v, bq, bk)
    kvalid = _kvalid_array(valid_length, b, k.shape[2])
    # causal offset from UNPADDED lengths: padded tq/tk shift the diagonal
    causal_off = k.shape[2] - tq
    outp, lse = _flash_fwd(qp, kp, vp, kvalid, causal, causal_off, s, bq, bk)
    out = outp[:, :, :tq, :d]
    # q/k/v saved unpadded: bwd re-pads (cheap) and shapes stay recoverable
    return out, (q, k, v, lse, kvalid, outp)


def _flash_core_bwd(causal, scale, res, g):
    import jax.numpy as jnp

    q, k, v, lse, kvalid, outp = res
    b, h, tq, d = q.shape
    tk = k.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    # bwd re-picks (smaller) blocks: it keeps 3 score-sized tiles in VMEM.
    # Its padded Tq never exceeds the fwd padding, so lse/out just slice.
    bq = _pick_block(tq, _MAX_BLOCK_BWD)
    bk = _pick_block(tk, _MAX_BLOCK_BWD)
    qp, kp, vp = _pad_qkv(q, k, v, bq, bk)
    tqp, dp = qp.shape[2], qp.shape[3]
    gp = jnp.pad(g, ((0, 0), (0, 0), (0, tqp - tq), (0, dp - d)))
    lse_b = lse[:, :tqp]
    outp_b = outp[:, :, :tqp, :]
    # delta_i = rowsum(dO_i * O_i): cheap elementwise reduce in XLA
    delta = jnp.sum(gp.astype(jnp.float32) * outp_b.astype(jnp.float32),
                    axis=-1).reshape(b * h, tqp)
    causal_off = tk - tq
    dq = _flash_bwd_dq(qp, kp, vp, gp.astype(qp.dtype), lse_b, delta,
                       kvalid, causal, causal_off, s, bq, bk)
    dk, dv = _flash_bwd_dkv(qp, kp, vp, gp.astype(qp.dtype), lse_b, delta,
                            kvalid, causal, causal_off, s, bq, bk)
    return (dq[:, :, :tq, :d], dk[:, :, :tk, :d], dv[:, :, :tk, :d], None)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# -- XLA fallback path (recompute-in-backward to match flash memory) --------


@functools.partial(
    __import__("jax").custom_vjp, nondiff_argnums=(5, 6)
)
def _xla_core(q, k, v, mask, valid_length, causal, scale):
    return _reference_attention(q, k, v, mask, causal=causal, scale=scale,
                                valid_length=valid_length)


def _xla_core_fwd(q, k, v, mask, valid_length, causal, scale):
    out = _reference_attention(q, k, v, mask, causal=causal, scale=scale,
                               valid_length=valid_length)
    return out, (q, k, v, mask, valid_length)


def _xla_core_bwd(causal, scale, res, g):
    import jax

    q, k, v, mask, valid_length = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _reference_attention(
            q_, k_, v_, mask, causal, scale, valid_length=valid_length),
        q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None


_xla_core.defvjp(_xla_core_fwd, _xla_core_bwd)


def attention(q, k, v, mask=None, causal=False, scale=None, use_flash=True,
              valid_length=None):
    """Public entry: (B, H, T, D) scaled-dot-product attention.

    ``valid_length`` — (B,) int key lengths; the flash path masks in-kernel
    without materializing a (T, T) mask. ``mask`` — arbitrary dense boolean
    mask, broadcastable against (B, H, Tq, Tk); forces the XLA path.
    """
    global _LAST_PATH
    want_flash = use_flash and _FORCE_PATH != "xla" and (
        _supports_pallas(q, k)
        or (_FORCE_PATH == "pallas" and q.ndim == 4
            and q.shape[-1] <= 256))
    if mask is None and want_flash:
        _LAST_PATH = "pallas"
        return _flash_core(q, k, v, valid_length, causal, scale)
    _LAST_PATH = "xla"
    return _xla_core(q, k, v, mask, valid_length, causal, scale)
