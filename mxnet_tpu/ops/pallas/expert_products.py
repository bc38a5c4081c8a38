"""The routed experts' products as one Pallas TPU kernel over the sorted
assignments (``ops.nn.grouped_expert_products`` on a TPU).

The caller sorts a call's (token, expert) assignments by expert and lays
each expert's rows out from a sublane-aligned start (``starts``), padded
to a whole sublane tile with zero rows. The route's result arrives as
scalar-prefetch operands: each expert's start and count, the number of
experts hit and the hit experts in ascending order, the list padded to
``E`` with its last entry.

The grid is (hit-expert visit, block of the expert width F). ``gate`` and
``up`` are read in (1, H, tf) blocks at (hit[v], 0, j), ``down`` in
(1, tf, H) at (hit[v], j, 0): the stacks themselves, in their own types,
are the kernel's operands. Pallas' pipeline double-buffers the blocks, so
the next block's weights, and the next expert's, stream in under this
block's products. Each hit expert's weights are read once a call; a
padded visit maps to the block already resident and fetches nothing; an
expert nobody picked is never read.

At an expert's first block its rows are copied into VMEM, one sublane
tile at a time and only those that hold its rows (a token takes an expert
at most once, so ``rows`` = the call's tokens bounds them). The products
``swiglu(rows @ gate, rows @ up) @ down`` run over as few tiles as the
expert's count (read from SMEM) needs: tiles of the widest size, then at
most one of each of its halves (:func:`tile_plan`); they accumulate in
float32 over the F blocks, and at the last block the expert's own sublane
tiles are written back to the padded layout. Rows of the
layout that belong to no expert are left unwritten: the caller reads
only the rows of assignments it computed.

``use_interpret(True)`` runs the kernel through the Pallas interpreter
(CPU tests); off a TPU without it, and for a call whose rows would not
fit VMEM, ``supports`` says no and the caller keeps its XLA loop.
"""
from __future__ import annotations

import functools
import math

from .decode_attention import _platform_of

_LANE = 128
# both buffers of the three weight blocks (gate, up, down) of one step; the
# widest block that fits is taken: 128 of Mellum-2's 896, 256 of Command
# A+'s 4096, 128 of A.X-K1's and GigaChat 3.5's 2048. Twice the budget
# (Mellum-2's whole expert a block) ran no faster on a v5e and took its
# compiler six times as long: Mosaic unrolls each tile's products
_WEIGHT_VMEM = 24 << 20
# the widest tile of rows; the rest of an expert's rows takes at most one
# of each of its halves down to a sublane tile. Tiles of 8 alone were
# 20-50% slower where an expert has tens of rows, and tiles of 128 two and
# a half times slower at 4096 x 4096 experts than tiles of 32, which are
# within 3% of the best at every shape measured; rounding the rest up to a
# wider tile moved no shape by more than 2% either way
# (exp/moe_products_bench.py --tune on a v5e, PERF.md)
_ROW_TILE = 32
# room for what Mosaic keeps on its stack beside the blocks and the
# scratch (the tiles' intermediates, the splits of a six-pass product):
# compiled for a described v5e at the cells' widths, 16 MiB was refused
# and 32 is taken (tests/test_chip_compile.py)
_STACK_VMEM = 32 << 20
# a v5e TensorCore's VMEM, which the blocks, the rows' scratch and the
# stack room are held to: the scratch grows with the call's rows, and a
# call whose rows do not fit takes the XLA loop (``supports``). Compiled
# for a described v5e, 2,048 rows at 7168 x 2048 were refused for using
# 144 of its 128 MiB (tests/test_chip_compile.py)
_VMEM = 128 << 20

_INTERPRET = False


def use_interpret(flag: bool) -> None:
    """Run the kernel through the Pallas interpreter (CPU testing)."""
    global _INTERPRET
    _INTERPRET = bool(flag)


def sublane_rows(dtype) -> int:
    """Rows of one sublane tile of ``dtype``: 8 for 32-bit, 16 for 16-bit."""
    import numpy as np

    return max(8, 32 // np.dtype(dtype).itemsize)


def padded_rows(assignments, experts, sub):
    """Rows of the padded layout: every expert's rows rounded up to whole
    tiles of ``sub``, at most ``sub - 1`` more an expert that has any."""
    n = assignments + (sub - 1) * min(experts, assignments)
    return -(-n // sub) * sub


def block_width(h, f, itemsize):
    """The widest block of the expert width whose three weight blocks fit
    ``_WEIGHT_VMEM`` twice over: a multiple of the lane width that divides
    ``f`` (``f`` itself where it is not a multiple: the interpreter), or
    None where not even one lane width fits."""
    if f % _LANE:
        return f
    best = None
    for tf in range(_LANE, f + 1, _LANE):
        if f % tf == 0 and 2 * 3 * h * tf * itemsize <= _WEIGHT_VMEM:
            best = tf
    return best


def vmem_bytes(rows, h, f, x_dtype, w_dtype):
    """The VMEM a call of ``rows`` rows asks for: both buffers of the three
    weight blocks, the scratch (the rows, their float32 sums and a staging
    copy for rows narrower than float32, in whole sublane tiles) and
    ``_STACK_VMEM``; None where no block fits."""
    import numpy as np

    tf = block_width(h, f, np.dtype(w_dtype).itemsize)
    if tf is None:
        return None
    sub = sublane_rows(x_dtype)
    r = -(-int(rows) // sub) * sub
    return (2 * 3 * h * tf * np.dtype(w_dtype).itemsize
            + r * h * (2 * np.dtype(x_dtype).itemsize + 4) + _STACK_VMEM)


def supports(x, gate):
    """Kernel coverage, from static shapes and the platform alone: rows and
    3-D stacks of one width whose blocks and rows fit ``_VMEM``,
    lane-aligned widths, and a TPU (or the interpreter) underneath."""
    if gate.ndim != 3 or x.shape[-1] != gate.shape[1]:
        return False
    h, f = gate.shape[1], gate.shape[2]
    need = vmem_bytes(math.prod(x.shape[:-1]), h, f, x.dtype, gate.dtype)
    if need is None or need > _VMEM:
        return False
    if _INTERPRET:
        return True
    return _platform_of(x) == "tpu" and h % _LANE == 0 and f % _LANE == 0


def tile_plan(rows, sub):
    """The tile sizes of an expert's products, widest first: powers of two
    from the widest tile that ``rows`` rows fill (at most ``_ROW_TILE``)
    down to ``sub``."""
    top = sub
    while 2 * top <= min(rows, _ROW_TILE):
        top *= 2
    sizes = [top]
    while sizes[-1] > sub:
        sizes.append(sizes[-1] // 2)
    return sizes


def _kernel(limit, prec, sub, sizes, starts_ref, counts_ref, hit_ref,
            nhit_ref, xs_hbm, g_ref, u_ref, d_ref, ys_hbm, rows, acc, sem,
            *stage):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..nn import swiglu

    v, j = pl.program_id(0), pl.program_id(1)
    e = hit_ref[v]
    live = v < nhit_ref[0]
    start, n = starts_ref[e], counts_ref[e]
    i32 = jnp.int32       # jax_enable_x64 is on: bare ints would be i64
    zero = i32(0)
    tiles_in = jax.lax.div(n + i32(sub - 1), i32(sub))   # that hold its rows

    def copies(src, dst, start_src, start_dst):
        def one(c):
            at = c * i32(sub)
            return pltpu.make_async_copy(
                src.at[pl.ds(pl.multiple_of(start_src + at, sub), sub)],
                dst.at[pl.ds(pl.multiple_of(start_dst + at, sub), sub)], sem)

        def go(c, carry):
            one(c).start()
            return carry

        def wait(c, carry):
            one(c).wait()
            return carry

        jax.lax.fori_loop(zero, tiles_in, go, zero)
        jax.lax.fori_loop(zero, tiles_in, wait, zero)

    def product(off, size):
        """The tile of ``size`` rows from ``off``: its part of the products
        over this block of the expert width, added to what the earlier
        blocks gave (the first block writes over the scratch)."""
        r = pl.ds(pl.multiple_of(off, sub), size)
        x = rows[r, :]
        a = jnp.dot(x, g_ref[0], precision=prec,
                    preferred_element_type=jnp.float32)
        b = jnp.dot(x, u_ref[0], precision=prec,
                    preferred_element_type=jnp.float32)
        y = jnp.dot(swiglu(a, b, limit).astype(d_ref.dtype), d_ref[0],
                    precision=prec, preferred_element_type=jnp.float32)
        acc[r, :] = jnp.where(j == 0, 0.0, acc[r, :]) + y

    @pl.when(live & (j == 0))
    def _():
        copies(xs_hbm, rows, start, zero)

    @pl.when(live)
    def _():
        widest = sizes[0]
        left = tiles_in * i32(sub)
        full = jax.lax.div(left, i32(widest))

        def whole(t, carry):
            product(t * i32(widest), widest)
            return carry

        jax.lax.fori_loop(zero, full, whole, zero)
        off, rest = full * i32(widest), left - full * i32(widest)
        for size in sizes[1:]:   # the rest, under a widest tile
            take = rest >= size

            @pl.when(take)
            def _(off=off, size=size):
                product(off, size)

            off = jnp.where(take, off + i32(size), off)
            rest = jnp.where(take, rest - i32(size), rest)

    @pl.when(live & (j == pl.num_programs(1) - 1))
    def _():
        out = acc
        if stage:           # rows narrower than float32
            out = stage[0]
            out[...] = acc[...].astype(out.dtype)
        copies(out, ys_hbm, zero, start)


def expert_products(xs, starts, counts, hit, nhit, gate, up, down, rows,
                    limit=None, precision=None):
    """The products of every hit expert with its rows, on raw arrays:
    ``xs`` (P, H) the sorted rows in the padded layout (expert ``e``'s
    ``counts[e]`` rows from ``starts[e]``, a multiple of
    :func:`sublane_rows`), ``hit`` (E,) int32 the hit experts in ascending
    order padded with the last, ``nhit`` (1,) int32 their number,
    ``gate``/``up`` (E, H, F) and ``down`` (E, F, H); ``rows`` bounds any
    expert's count. Returns (P, H): every expert's rows' products where
    its rows are, in ``xs``' type; the other rows are not written."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p, h = xs.shape
    e, _, f = gate.shape
    sub = sublane_rows(xs.dtype)
    tf = block_width(h, f, gate.dtype.itemsize)
    nf = f // tf
    r = -(-int(rows) // sub) * sub
    sizes = tile_plan(r, sub)

    # index maps return int32 (jax_enable_x64 is on); a visit past the hit
    # experts stays on the last one's last block, which is resident
    def block(v, j, starts, counts, hit, nhit):
        return jnp.where(v < nhit[0], j, jnp.int32(nf - 1)).astype(jnp.int32)

    def gu_map(v, j, *pre):
        return (pre[2][v], jnp.int32(0), block(v, j, *pre))

    def d_map(v, j, *pre):
        return (pre[2][v], block(v, j, *pre), jnp.int32(0))

    stage = [] if xs.dtype == jnp.float32 else [pltpu.VMEM((r, h), xs.dtype)]
    vmem = vmem_bytes(rows, h, f, xs.dtype, gate.dtype)
    kernel = functools.partial(_kernel, limit, precision, sub, sizes)
    return pl.pallas_call(
        kernel,
        name="grouped_expert_products",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(e, nf),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((1, h, tf), gu_map),
                      pl.BlockSpec((1, h, tf), gu_map),
                      pl.BlockSpec((1, tf, h), d_map)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((r, h), xs.dtype),
                            pltpu.VMEM((r, h), jnp.float32),
                            pltpu.SemaphoreType.DMA(()), *stage]),
        out_shape=jax.ShapeDtypeStruct((p, h), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=_INTERPRET,
    )(starts.astype(jnp.int32), counts.astype(jnp.int32),
      hit.astype(jnp.int32), nhit.astype(jnp.int32), xs, gate, up, down)
