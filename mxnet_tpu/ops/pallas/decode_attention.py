"""Fused decode attention: single-query-timestep attention over the KV ring.

The serving decode step (``Generator``'s T=1 call) spends its time in
``ops/nn.cached_attention`` — the PR-5 mul+reduce formulation that buys
bitwise prefill/decode parity by materializing a (B, H, T, S, D) broadcast.
This module is the fast rung behind it: a flash-style Pallas kernel that
streams the KV ring through VMEM in 128-wide blocks with the valid-length
mask (``position <= start_pos``) applied in-kernel, plus a fused-einsum XLA
fallback for shapes/platforms the kernel does not cover (T>1 verify blocks,
CPU without interpret mode).

Layout: GQA is handled natively — the kernel takes *unexpanded* K/V of
shape (B, KV, S, D) and puts the G = H // KV query heads of each KV group
on the sublane axis, so head_dim 64/128 models run full (8, 128) f32 tiles
without materializing the head-repeated K/V that the baseline path needs.

int8 KV rings dequantize in-kernel: pass ``k_scale``/``v_scale`` of shape
(B, KV, S) (per-token-per-head scales from ``ops/nn.kv_cache_write_q``) and
the kernel widens int8 blocks to f32 right next to the MXU dot, so the ring
stays half-size in HBM end to end.

Paged form (``paged_decode_attention``): the serving step of
``ContinuousEngine`` keeps K/V in page pools (P, KV, page, D) and never
builds a ring for a decode step. The page table and ``start_pos`` arrive
as scalar-prefetch operands; the K/V index maps pick pool page
``table[b, min(j, start_pos[b] // page)]``, so a page past a sequence's
end repeats the last block index and is not fetched at all. One program
is one lane with all its KV heads (a page with its heads is one
contiguous block of the pool); per head the arithmetic and the block
order are the ring kernel's. What it cannot tile (a page that is neither
a multiple of the block nor smaller than it; T > 1: a prefill chunk, a
verify block) walks, in plain XLA, the key blocks that some query of the
call can see, a few pages a turn with the kernels' running max and sum
(``_xla_blocks``): its trip count follows ``start_pos``, T, the window
and the table's width, and neither the whole table nor the whole scores
are ever formed. int8 pools alone still read the pages gathered into
rings through the ring paths below.

A window (``paged_decode_attention(..., window=W)``): the query at
position ``t`` sees the keys at ``t - W + 1 .. t`` alone, and the page
table's N columns are a ring of pages, logical page ``j`` in column
``j mod N`` (``serve.kv_blocks``). The kernel's grid then runs over the
blocks that hold those positions and no others: the index maps start at
the block of ``max(0, t - W + 1)``, pages before it are never fetched and
the first visible block is masked inside. ``window=None`` compiles the
kernel it always compiled.

Latent form (``paged_decode_attention(q, pool, None, ..., v_width=W)``):
a layer that keeps one array a position (latent attention in its absorbed
form: the normalised latent and the shared rotated key side by side) has
no V pool. Its values are the keys' first ``W`` channels: the kernel and
the chunk's loop take them as a slice of the key block they have already
fetched, the output is ``W`` wide, and the key block spans the pool's
whole last dimension, which need not be a multiple of the lane width.

Introspection follows ``flash_attention``'s conventions: ``last_path()``
reports which implementation the last call traced ("pallas_paged" |
"xla_blocks" | "pallas" | "xla"),
``force_path()`` overrides routing, ``use_interpret(True)`` runs the kernel
through the Pallas interpreter on CPU. Decode-shaped calls (T == 1) that
land on the XLA fallback additionally record a flight-recorder note and
bump the ``serve.decode_fallbacks`` counter so silent slow-path serving is
diagnosable from ``/metrics``.

Loop-carried ``start_pos`` (multi-step decode, PR 19): every input —
including ``start_pos`` — may be a traced value inside a
``lax.while_loop`` body, advancing per iteration while the kernel stays
the SAME compiled program. The contract that makes this work: routing
(``_supports_pallas``) depends only on static shapes/dtypes/platform,
never on start_pos values; the valid-length mask and the block-skip
predicate consume start_pos as data (SMEM scalars / in-kernel compares);
and the path/fallback records fire at TRACE time, so one super-step
compile records exactly one path decision no matter how many iterations
the loop later runs. ``reset_fallbacks()`` rezeroes the cumulative
counter for tests/bench rungs that assert a clean kernel run.
"""
from __future__ import annotations

import math

from ...profiler.core import device_scope as _device_scope

_NEG_INF = -1e30  # finite "minus infinity": keeps fully-masked rows NaN-free
_BLOCK = 128      # lane width / KV stream block size


def natural_block() -> int:
    """The kernel's KV stream block width (lane tile) — the natural page
    size for the paged KV allocator (``serve.kv_blocks``): a pool page
    that matches it means the kernel's block-skip mask
    (``run = si * bk <= sp``) skips whole unreached pages, so a slot
    only ever pays compute for pages its sequence has actually
    reached."""
    return _BLOCK

# trace-time record of which implementation the last call chose
# ("pallas" | "xla"); tests and bench assert the kernel actually ran.
_LAST_PATH = None

_INTERPRET = False


def use_interpret(flag: bool) -> None:
    """Force Pallas interpreter mode (CPU testing of the TPU kernel)."""
    global _INTERPRET
    _INTERPRET = bool(flag)


_FORCE_PATH = None


def force_path(path) -> None:
    """Override decode-attention path selection: None | 'xla' | 'pallas'."""
    global _FORCE_PATH
    if path not in (None, "xla", "pallas"):
        raise ValueError(f"force_path: {path!r} not in (None,'xla','pallas')")
    _FORCE_PATH = path


def last_path():
    return _LAST_PATH


# Cumulative count of decode-shaped (T == 1) calls that fell back to the
# XLA path. Trace-time, so steady-state serving bumps it once per compiled
# signature, not once per step — a nonzero value after warmup means the
# fast rung is not actually serving from the kernel.
_FALLBACKS = 0


def fallback_count() -> int:
    return _FALLBACKS


def reset_fallbacks() -> None:
    """Rezero the cumulative decode-fallback counter (tests / bench
    rungs that assert a specific trace produced zero fallbacks — the
    counter is trace-time, so differencing around a cached replay would
    always read 0 even on a fallback path)."""
    global _FALLBACKS
    _FALLBACKS = 0


def _record_fallback(reason, shape):
    global _FALLBACKS
    _FALLBACKS += 1
    from ...profiler import core as _prof
    from ...profiler import recorder as _recorder

    args = {"reason": reason, "shape": "x".join(str(d) for d in shape)}
    _recorder.note("fallback", "serve.decode_fallback", args)
    _prof.incr_counter("serve.decode_fallbacks", cat="serve")
    _prof.record_instant("serve.decode_fallback", cat="serve", args=args)


def _round_up(x, m):
    return (x + m - 1) // m * m


def _platform_of(x):
    try:
        return list(x.devices())[0].platform
    except Exception:
        import jax
        return jax.default_backend()


def _supports_pallas(q, k, widest=256):
    """Kernel coverage: one query timestep, lane-width-bounded head_dim,
    grouped heads, and a TPU (or interpreter) underneath."""
    if q.ndim != 4 or k.ndim != 4:
        return False
    b, h, t, d = q.shape
    if t != 1 or d > widest:
        return False
    if h % k.shape[1] != 0:
        return False
    if _INTERPRET:
        return True
    return _platform_of(q) == "tpu"


@_device_scope("attn.scores")
def _xla_decode(q, k, v, start_pos, scale, k_scale, v_scale):
    """Fused-einsum fallback: grouped-heads attention over the ring with
    the same ``position <= start_pos + t`` mask as the kernel. Handles any
    T (the speculative verify block reuses it at T = k+1) and dequantizes
    int8 rings inline."""
    import jax
    import jax.numpy as jnp

    from ..nn import stored_precision

    b, h, t, d = q.shape
    kv, s = k.shape[1], k.shape[2]
    g = h // kv
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[..., None].astype(jnp.float32)
    if v_scale is not None:
        vf = vf * v_scale[..., None].astype(jnp.float32)
    prec = stored_precision(q, k, v)
    qg = q.astype(jnp.float32).reshape(b, kv, g, t, d)
    scores = jnp.einsum("bngtd,bnsd->bngts", qg, kf, precision=prec) * scale
    pos = start_pos.astype(jnp.int32)[:, None] + jnp.arange(t, dtype=jnp.int32)
    valid = jnp.arange(s, dtype=jnp.int32)[None, None, :] <= pos[:, :, None]
    scores = jnp.where(valid[:, None, None, :, :], scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngts,bnsd->bngtd", w, vf, precision=prec)
    return out.reshape(b, h, t, d).astype(q.dtype)


def _flash_update(sc, vb, prec, m_prev, l_prev, acc_prev):
    """Masked scores ``sc`` (..., R, bk) and their values ``vb`` (..., bk,
    D) into the flash accumulators (max, sum, acc) of the R query rows;
    the leading dimensions, if any, are batch dimensions of the product.
    A masked score is ``_NEG_INF``, finite: rows that have seen no key yet
    weigh such a block evenly, and their first visible key wipes that
    (``alpha`` is 0 there), so a masked key contributes an exact zero."""
    import jax
    import jax.numpy as jnp

    m_cur = jnp.max(sc, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(sc - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    lead = tuple(range(sc.ndim - 2))
    pv = jax.lax.dot_general(
        p, vb, (((sc.ndim - 1,), (sc.ndim - 2,)), (lead, lead)),
        precision=prec, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_prev * alpha + pv


def _flash_block(q, k, v, k_scale, v_scale, first, sp, scale, prec,
                 m_prev, l_prev, acc_prev, window=None):
    """One K/V block of one KV head into the flash accumulators: q
    (Gp, D) against k, v (bk, D) whose first position is ``first``, masked
    to positions <= ``sp``; int8 blocks are widened by their (bk,) scale
    rows right next to the dots. Returns the new (max, sum, acc). The
    ring kernel and the paged kernel share it, so a lane's result comes
    from the same arithmetic whichever reads its blocks. With a
    ``window`` the positions at or before ``sp - window`` are masked
    too."""
    import jax
    import jax.numpy as jnp

    qb = q.astype(jnp.float32)                     # (Gp, D)
    kb = k.astype(jnp.float32)                     # (bk, D)
    vb = v.astype(jnp.float32)
    if k_scale is not None:
        kb = kb * k_scale[:, None]
        vb = vb * v_scale[:, None]
    sc = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32) * scale   # (Gp, bk)
    kpos = first + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    seen = kpos <= sp
    if window is not None:
        seen = seen & (kpos > sp - jnp.int32(window))
    sc = jnp.where(seen, sc, jnp.float32(_NEG_INF))
    return _flash_update(sc, vb, prec, m_prev, l_prev, acc_prev)


def _decode_kernel(quant, kv, g, d, bk, n_k, scale, prec,
                   sp_ref, q_ref, k_ref, v_ref, *rest):
    """One (batch·kv_head) program: stream S in ``bk`` blocks with flash
    running-max/sum accumulators; the G grouped query heads sit on the
    sublane axis so the whole group shares each K/V block load."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest

    si = pl.program_id(1)
    sp = sp_ref[jax.lax.div(pl.program_id(0), jnp.int32(kv))]

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block needed iff its first position is still <= start_pos
    run = si * bk <= sp

    @pl.when(run)
    def _body():
        m_ref[...], l_ref[...], acc_ref[...] = _flash_block(
            q_ref[0], k_ref[0], v_ref[0],
            ks_ref[0, 0] if quant else None, vs_ref[0, 0] if quant else None,
            si * bk, sp, scale, prec, m_ref[...], l_ref[...], acc_ref[...])

    @pl.when(si == n_k - 1)
    def _finish():
        l = l_ref[...]
        # padded sublane rows: emit zeros
        l = jnp.where(l == 0.0, jnp.float32(1.0), l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@_device_scope("attn.kernel")
def _pallas_decode(q, k, v, start_pos, scale, k_scale, v_scale):
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..nn import stored_precision

    b, h, _, d = q.shape
    kv, s = k.shape[1], k.shape[2]
    g = h // kv
    quant = k_scale is not None

    bk = _BLOCK
    sp_len = _round_up(s, bk)
    dp = _round_up(d, _BLOCK)
    gp = _round_up(g, 8)  # f32 sublane tile

    q4 = q.reshape(b, kv, g, d).reshape(b * kv, g, d)
    q4 = jnp.pad(q4, ((0, 0), (0, gp - g), (0, dp - d)))
    k3 = k.reshape(b * kv, s, d)
    v3 = v.reshape(b * kv, s, d)
    k3 = jnp.pad(k3, ((0, 0), (0, sp_len - s), (0, dp - d)))
    v3 = jnp.pad(v3, ((0, 0), (0, sp_len - s), (0, dp - d)))
    n_k = sp_len // bk

    # index maps return int32 throughout: jax_enable_x64 is on, so a bare
    # 0 would arrive as an i64 Mosaic cannot return
    def head_map(i, j):
        return (i, jnp.int32(0), jnp.int32(0))

    def block_map(i, j):
        return (i, j, jnp.int32(0))

    def scale_map(i, j):
        return (i, jnp.int32(0), j)

    in_specs = [
        pl.BlockSpec((b,), lambda i, j: (jnp.int32(0),),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((1, gp, dp), head_map),
        pl.BlockSpec((1, bk, dp), block_map),
        pl.BlockSpec((1, bk, dp), block_map),
    ]
    args = [start_pos.astype(jnp.int32), q4, k3, v3]
    if quant:
        ks3 = k_scale.astype(jnp.float32).reshape(b * kv, 1, s)
        vs3 = v_scale.astype(jnp.float32).reshape(b * kv, 1, s)
        ks3 = jnp.pad(ks3, ((0, 0), (0, 0), (0, sp_len - s)))
        vs3 = jnp.pad(vs3, ((0, 0), (0, 0), (0, sp_len - s)))
        # (1, 1, bk) block over the 3D scale array — same shape trick as
        # the flash kernel's lse output: TPU rejects a 2D (1, bk) block.
        in_specs += [pl.BlockSpec((1, 1, bk), scale_map),
                     pl.BlockSpec((1, 1, bk), scale_map)]
        args += [ks3, vs3]

    kernel = functools.partial(_decode_kernel, quant, kv, g, d, bk, n_k,
                               scale, stored_precision(q, k, v))
    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid=(b * kv, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, gp, dp), head_map),
        out_shape=jax.ShapeDtypeStruct((b * kv, gp, dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((gp, 1), jnp.float32),
                        pltpu.VMEM((gp, 1), jnp.float32),
                        pltpu.VMEM((gp, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_INTERPRET,
    )(*args)
    out = out[:, :g, :d].reshape(b, kv, g, d).reshape(b, h, 1, d)
    return out


def decode_attention(q, k, v, start_pos, scale=None,
                     k_scale=None, v_scale=None):
    """Attention for the serving decode step.

    q: (B, H, T, D); k/v: (B, KV, S, D) *unexpanded* GQA rings (f32, or
    int8 with (B, KV, S) ``k_scale``/``v_scale``); start_pos: (B,) int32.
    Position ``s`` attends iff ``s <= start_pos[b] + t``. Returns
    (B, H, T, D).
    """
    global _LAST_PATH
    d = q.shape[-1]
    sc = scale if scale is not None else 1.0 / math.sqrt(d)

    use_pallas = _supports_pallas(q, k)
    if _FORCE_PATH == "xla":
        use_pallas = False
    elif _FORCE_PATH == "pallas":
        if not use_pallas:
            raise ValueError(
                f"force_path('pallas'): unsupported decode shape "
                f"q={q.shape} k={k.shape} on {_platform_of(q)}")
        use_pallas = True

    if use_pallas:
        _LAST_PATH = "pallas"
        return _pallas_decode(q, k, v, start_pos, sc, k_scale, v_scale)
    _LAST_PATH = "xla"
    if q.shape[2] == 1:  # decode-shaped call missed the kernel: diagnose
        reason = ("interpret_off_cpu" if _platform_of(q) != "tpu"
                  else "unsupported_shape")
        if _FORCE_PATH == "xla":
            reason = "forced_xla"
        _record_fallback(reason, q.shape)
    return _xla_decode(q, k, v, start_pos, sc, k_scale, v_scale)


# ---------------------------------------------------------------------------
# Paged form: K/V read where they live, through the page table
# ---------------------------------------------------------------------------


def _paged_block(page):
    """The stream block inside a pool page: the ring kernel's 128 where
    it tiles the page, the page itself (a whole-dimension block) where
    the page is narrower, None where neither holds."""
    if page % _BLOCK == 0:
        return _BLOCK
    return page if page < _BLOCK else None


def latent_width(width):
    """The width a latent pool stores a position at: ``width`` rounded up
    to the lane tile, zeros after the last real channel. XLA:TPU lays an
    array whose last dimension is no multiple of the tile out with another
    dimension innermost and copies the whole pool to the kernel's layout
    and back around every call (576: seen in the compiled step,
    tests/test_chip_compile.py); stored at a whole number of tiles the
    pool is read where it lies, for 640 / 576 = 11% more bytes and score
    products (PERF.md section 6, PR 39)."""
    return _round_up(int(width), _BLOCK)


# the widest key a latent pool may hold a position (VMEM: two buffers of a
# block of 128 such keys beside the queries and the accumulator)
_LATENT_WIDEST = 1024


def _supports_paged(q, k_pool, v_width=None):
    """Coverage of the paged kernel: the ring kernel's (the pool has its
    KV heads where a ring has them) and a page the stream block tiles. On
    the chip the pool's head_dim is the block's lane extent and cannot be
    padded without a copy of the pool. A latent pool's block spans its
    whole last dimension, whatever that is; its values, a slice of the
    block, must end on a lane tile."""
    if v_width is not None:
        return (_supports_pallas(q, k_pool, _LATENT_WIDEST)
                and _paged_block(k_pool.shape[2]) is not None
                and (_INTERPRET or v_width % _BLOCK == 0))
    return (_supports_pallas(q, k_pool)
            and _paged_block(k_pool.shape[2]) is not None
            and (_INTERPRET or q.shape[-1] % _BLOCK == 0))


def _paged_kernel(quant, kv, bk, n_blk, scale, prec, window, v_width,
                  tbl_ref, sp_ref, q_ref, k_ref, *rest):
    """One lane: stream its reached pages in ``bk`` blocks, every KV head
    of a block in turn through :func:`_flash_block` (the G grouped query
    heads on the sublane axis, as in ``_decode_kernel``). With a
    ``window`` grid step ``si`` is block ``lo + si`` of the lane, ``lo``
    the block of its first visible position. With a ``v_width`` there is
    no V block: a key block's first ``v_width`` channels are its values."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    del tbl_ref  # the index maps' alone
    v_ref = None
    if v_width is None:
        v_ref, *rest = rest
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest

    si = pl.program_id(1)
    sp = sp_ref[pl.program_id(0)]

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = si
    if window is not None:
        blk = si + jax.lax.div(jnp.maximum(sp - jnp.int32(window - 1), 0),
                               jnp.int32(bk))

    # block needed iff its first position is still <= start_pos
    @pl.when(blk * bk <= sp)
    def _body():
        for n in range(kv):
            kb = k_ref[0, n]
            m_ref[n], l_ref[n], acc_ref[n] = _flash_block(
                q_ref[0, n], kb,
                kb[:, :v_width] if v_ref is None else v_ref[0, n],
                ks_ref[0, n] if quant else None,
                vs_ref[0, n] if quant else None,
                blk * bk, sp, scale, prec, m_ref[n], l_ref[n], acc_ref[n],
                window)

    @pl.when(si == n_blk - 1)
    def _finish():
        l = l_ref[...]
        # padded sublane rows: emit zeros
        l = jnp.where(l == 0.0, jnp.float32(1.0), l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@_device_scope("attn.kernel")
def _pallas_paged_decode(q, k_pool, v_pool, page_table, start_pos, scale,
                         k_scale, v_scale, window=None, v_width=None):
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..nn import stored_precision

    b, h, _, d = q.shape
    kv, page = k_pool.shape[1], k_pool.shape[2]
    n_pages = page_table.shape[1]
    g = h // kv
    quant = k_scale is not None
    dv = d if v_width is None else int(v_width)      # the values' width
    pools = [k_pool] if v_pool is None else [k_pool, v_pool]
    bk = _paged_block(page)
    sub = page // bk                 # stream blocks a page
    n_blk = n_pages * sub
    if window is not None:
        # the blocks that W consecutive positions can lie in
        n_blk = min(n_blk, (window + bk - 2) // bk + 1)
    gp = _round_up(g, 8)             # f32 sublane tile

    q4 = jnp.pad(q.reshape(b, kv, g, d), ((0, 0), (0, 0), (0, gp - g), (0, 0)))

    # index maps return int32 throughout (jax_enable_x64 is on). A block
    # past the lane's last reached one repeats that one's index: the
    # pipeline fetches a block only when its index moves.
    def lane_map(i, j, tbl, sp):
        return (i, jnp.int32(0), jnp.int32(0), jnp.int32(0))

    def _at(i, j, tbl, sp):
        if window is not None:
            # the lane's first visible block, and the ring's column of
            # the page it lies in
            j = j + jax.lax.div(
                jnp.maximum(sp[i] - jnp.int32(window - 1), 0), jnp.int32(bk))
        jc = jnp.minimum(j, jax.lax.div(sp[i], jnp.int32(bk)))
        col = jax.lax.div(jc, jnp.int32(sub))
        if window is not None:
            col = jax.lax.rem(col, jnp.int32(n_pages))
        pid = tbl[i * jnp.int32(n_pages) + col]
        return pid, jax.lax.rem(jc, jnp.int32(sub))

    def page_map(i, j, tbl, sp):
        pid, blk = _at(i, j, tbl, sp)
        return (pid, jnp.int32(0), blk, jnp.int32(0))

    def scale_map(i, j, tbl, sp):
        pid, blk = _at(i, j, tbl, sp)
        return (pid, jnp.int32(0), blk)

    in_specs = [pl.BlockSpec((1, kv, gp, d), lane_map)] \
        + [pl.BlockSpec((1, kv, bk, d), page_map)] * len(pools)
    args = [q4] + pools
    if quant:
        in_specs += [pl.BlockSpec((1, kv, bk), scale_map),
                     pl.BlockSpec((1, kv, bk), scale_map)]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

    kernel = functools.partial(_paged_kernel, quant, kv, bk, n_blk, scale,
                               stored_precision(q, *pools), window, v_width)
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_blk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kv, gp, dv), lane_map),
            scratch_shapes=[pltpu.VMEM((kv, gp, 1), jnp.float32),
                            pltpu.VMEM((kv, gp, 1), jnp.float32),
                            pltpu.VMEM((kv, gp, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, kv, gp, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_INTERPRET,
    )(page_table.astype(jnp.int32).reshape(-1), start_pos.astype(jnp.int32),
      *args)
    return out[:, :, :g, :].reshape(b, h, 1, dv)


# keys of one turn of :func:`_xla_blocks`' loop, at most (settled on the
# chip at the Command A+ cell's widths: exp/chunk_attention_bench.py)
_BLOCK_KEYS = 512


def block_range(start_pos, t, page, n_pages, window=None):
    """The pages that :func:`_xla_blocks` walks for a call of ``t``
    positions a row from ``start_pos`` (B,) over a table of ``n_pages``
    pages of ``page`` keys: ``(first, turns, c)``, ``turns`` turns of
    ``c`` pages (``c * page`` keys) from logical page ``first`` on, as
    far as the last page a query sees. Without a window a row's keys start
    at 0 and end at the table's extent (a last chunk's padding may run
    past it); with one they start at the first key its first query sees,
    and the pages are the logical ring's, unbounded. The range is the
    union over the rows. ``c`` divides what one row can see (the table;
    the pages a window and ``t`` positions can lie in, which a ring holds
    no more of) into the fewest even turns of at most ``_BLOCK_KEYS``
    keys. On numpy arrays (the scheduler's count of the keys a chunk
    visits) as on traced ones (the loop's bounds)."""
    hi = start_pos + (t - 1)
    if window is None:
        span = n_pages
        lo, hi = start_pos * 0, hi.clip(None, n_pages * page - 1)
    else:
        span = min(n_pages, (window + t + page - 3) // page + 1)
        lo = (start_pos - (window - 1)).clip(0, None)
    c = -(-span // -(-span // max(1, _BLOCK_KEYS // page)))
    first = lo.min() // page
    return first, (hi.max() // page - first) // c + 1, c


@_device_scope("attn.scores")
def _xla_blocks(q, k_pool, v_pool, page_table, start_pos, scale, window,
                v_width=None):
    """Attention over float32 pages in plain XLA (a prefill chunk, a
    verify block, a decode step the kernel does not cover): a loop over
    the pages of :func:`block_range`, the ones some query of the call can
    see, a block of ``c`` a turn, with :func:`_flash_update`'s running
    max, sum and accumulator. A turn takes its block's pages for every
    row from the table (with a ``window`` the table is a ring: logical
    page ``j`` in column ``j mod N``, so the column a chunk has just
    written over is read as the newest page), multiplies the (B, KV, G*T,
    D) queries with them and masks by position, per query row: never the
    whole table, and never the whole scores, unless the table is no more
    than a block. Without a ``v_pool`` a block's values are its keys'
    first ``v_width`` channels (the latent form)."""
    import jax
    import jax.numpy as jnp

    from ..nn import gather_pages, stored_precision

    b, h, t, d = q.shape
    dv = int(v_width) if v_pool is None else v_pool.shape[-1]
    kv, page = k_pool.shape[1], k_pool.shape[2]
    n_pages = page_table.shape[1]
    g = h // kv
    sp = start_pos.astype(jnp.int32)
    first, turns, c = block_range(sp, t, page, n_pages, window)
    whole = window is None and c == n_pages     # the table is one block
    if whole:
        first = 0
    table = page_table.astype(jnp.int32)
    prec = stored_precision(q, k_pool) if v_pool is None \
        else stored_precision(q, k_pool, v_pool)
    qg = q.reshape(b, kv, g * t, d)
    # row g * T + t of a KV head's queries is at position sp + t
    pos = jnp.tile(sp[:, None] + jnp.arange(t, dtype=jnp.int32), (1, g))

    def block(i, carry):
        at = first + i * c
        cols = at + jnp.arange(c, dtype=jnp.int32)
        if window is not None:
            ids = jnp.take(table, cols % n_pages, axis=1)
        else:       # a column past the table is the null page
            ids = jnp.take(table, cols, axis=1, mode="fill", fill_value=0)
        k = gather_pages(k_pool, ids)
        v = k[..., :dv] if v_pool is None else gather_pages(v_pool, ids)
        sc = jnp.einsum("bnrd,bnsd->bnrs", qg, k, precision=prec,
                        preferred_element_type=jnp.float32) * scale
        kpos = at * page + jnp.arange(c * page, dtype=jnp.int32)
        seen = kpos <= pos[:, :, None]                  # (B, G*T, c*page)
        if window is not None:
            seen = seen & (kpos > pos[:, :, None] - window)
        sc = jnp.where(seen[:, None], sc, jnp.float32(_NEG_INF))
        return _flash_update(sc, v, prec, *carry)

    rows = (b, kv, g * t)
    none = (jnp.full(rows + (1,), _NEG_INF, jnp.float32),
            jnp.zeros(rows + (1,), jnp.float32),
            jnp.zeros(rows + (dv,), jnp.float32))
    # one turn needs no loop, and no bounds worked out on the device
    _, l, acc = block(0, none) if whole \
        else jax.lax.fori_loop(0, turns, block, none)
    return (acc / l).reshape(b, h, t, dv).astype(q.dtype)


def paged_decode_attention(q, k_pool, v_pool, page_table, start_pos,
                           scale=None, k_scale=None, v_scale=None,
                           window=None, v_width=None):
    """:func:`decode_attention` over K/V that stay in their page pools.

    q: (B, H, T, D); k_pool/v_pool: (P, KV, page, D) (f32, or int8 with
    (P, KV, page) scale pools); page_table: (B, N) int32 pool page ids of
    each row's logical pages (0 = the null page); start_pos: (B,) int32.
    The new rows of this call are in the pools already. A decode-shaped
    call (T == 1) the paged kernel covers reads the pages in place. Every
    other call on float32 pools walks the key blocks its queries can see
    (:func:`_xla_blocks`, ``last_path()`` ``"xla_blocks"``; a
    decode-shaped one counts as a fallback). int8 pools alone, which carry
    scales, still gather the rows' every page into rings and go through
    :func:`decode_attention`.

    ``window``: position ``s`` attends iff ``start_pos[b] + t - window <
    s <= start_pos[b] + t``, and ``page_table``'s N columns are a ring of
    pages (logical page ``j`` in column ``j mod N``; ``N`` pages must
    hold a window and a page, ``N >= ceil(window / page) + 1``). float32
    pools alone.

    ``v_pool=None`` with ``v_width``: the latent form. ``k_pool`` is
    (P, 1, page, D), one array a position for all H query heads, whose
    first ``v_width`` channels are the values: the result is (B, H, T,
    ``v_width``). float32 pools alone, no window.
    """
    global _LAST_PATH
    sc = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    decode = q.ndim == 4 and q.shape[2] == 1
    latent = v_pool is None
    if latent != (v_width is not None) or (latent and not (
            k_scale is None and window is None
            and 0 < v_width <= k_pool.shape[-1])):
        raise ValueError(
            "the latent form takes one float32 pool and the width of its "
            f"values (v_pool None with v_width), no scales and no window; "
            f"got v_width={v_width}, pool {k_pool.shape}")
    if window is not None:
        page, n = k_pool.shape[2], page_table.shape[1]
        if k_scale is not None or n * page < window + page:
            raise ValueError(
                f"a window of {window} needs float32 pools and a ring of "
                f"ceil(window / page) + 1 pages of {page}; got {n}")
    if decode and _FORCE_PATH != "xla" \
            and _supports_paged(q, k_pool, v_width):
        _LAST_PATH = "pallas_paged"
        return _pallas_paged_decode(q, k_pool, v_pool, page_table,
                                    start_pos, sc, k_scale, v_scale, window,
                                    v_width)
    if k_scale is None:
        if _FORCE_PATH == "pallas":
            raise ValueError(
                f"force_path('pallas'): unsupported paged shape "
                f"q={q.shape} pool={k_pool.shape} on {_platform_of(q)}")
        _LAST_PATH = "xla_blocks"
        if decode:  # decode-shaped call missed the kernel: diagnose
            if _FORCE_PATH == "xla":
                reason = "forced_xla"
            elif _supports_pallas(q, k_pool,
                                  _LATENT_WIDEST if latent else 256):
                reason = "page_untiled"
            else:
                reason = ("interpret_off_cpu" if _platform_of(q) != "tpu"
                          else "unsupported_shape")
            _record_fallback(reason, q.shape)
        return _xla_blocks(q, k_pool, v_pool, page_table, start_pos, sc,
                           window, v_width)
    from ..nn import gather_pages

    k, v = gather_pages(k_pool, page_table), gather_pages(v_pool, page_table)
    k_scale = gather_pages(k_scale, page_table)
    v_scale = gather_pages(v_scale, page_table)
    if decode and _FORCE_PATH != "xla" and _supports_pallas(q, k):
        # the ring kernel serves it; the xla path records its own
        _record_fallback("page_untiled", q.shape)
    return decode_attention(q, k, v, start_pos, sc, k_scale, v_scale)
