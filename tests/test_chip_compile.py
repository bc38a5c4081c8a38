"""The main path's kernels and serving ops, compiled for a described TPU v5e.

Nothing runs: the chip's own compiler (Mosaic + XLA:TPU, installed with
libtpu) lowers each kernel at the widths ``chip_smoke.py`` serves and
trains, for a device that is described rather than attached. What it
refuses here it would refuse on the chip — interpret-mode tests cannot
see f64/i64 leaks from ``jax_enable_x64``, tiling or VMEM limits.

The topology is described inside a module-scoped fixture (never at
import: one process may hold libtpu, and every xdist worker imports every
test file), and all such compiles live in this one file so one worker
owns the library. What crossed four chips and broke goes here too.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.models.llama import _LLAMA_CONFIGS
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.ops.pallas import decode_attention as da
from mxnet_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pylint: disable=broad-except
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable can be written to the persistent cache
    # but never read back without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


_8B = _LLAMA_CONFIGS["llama3_8b"]
_12L = _LLAMA_CONFIGS["llama_serve_12l_test"]


@pytest.mark.parametrize("cfg,b,s,ring", [
    (_8B, 8, 2048, jnp.float32),
    (_8B, 8, 2048, jnp.bfloat16),
    (_8B, 8, 2048, jnp.int8),
    (_12L, 2, 64, jnp.float32),
], ids=["8b-f32", "8b-bf16", "8b-int8", "serve12l-f32"])
def test_decode_attention_compiles_for_v5e(one_chip, cfg, b, s, ring):
    h, kv = cfg["num_heads"], cfg["num_kv_heads"]
    d = cfg["units"] // h
    int8 = ring == jnp.int8

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = sds((b, h, 1, d), jnp.float32 if int8 else ring)
    k = sds((b, kv, s, d), ring)
    scales = [sds((b, kv, s), jnp.float32)] * 2 if int8 else []

    def fn(q, k, v, sp, *scales):
        return da._pallas_decode(q, k, v, sp, d ** -0.5,
                                 *(scales or (None, None)))

    text = _compile(fn, q, k, k, sds((b,), jnp.int32), *scales)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_compiles_for_v5e(one_chip, grad):
    qkv = jax.ShapeDtypeStruct((2, 32, 2048, 128), jnp.bfloat16,
                               sharding=one_chip)
    vl = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)

    def fwd(q, k, v, vl):
        return fa._flash_core(q, k, v, vl, True, 128 ** -0.5)

    def loss(q, k, v, vl):
        return fwd(q, k, v, vl).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    assert "tpu_custom_call" in _compile(fn, qkv, qkv, qkv, vl)


# falcon_h1_34b's mixer (chipbench/configs/falcon_h1_34b.json): d_ssm 4096
# in 32 heads of 128, state 256, 2 groups, conv 4 over x, B and C together
_H, _P, _N, _G, _K, _LANES = 32, 128, 256, 2, 4, 32
_CONV = _H * _P + 2 * _G * _N


def _ssd_scan(b, t):
    f32, i32 = jnp.float32, jnp.int32
    args = [((b, t, _H, _P), f32), ((b, t, _H), f32), ((_H,), f32),
            ((b, t, _G, _N), f32), ((b, t, _G, _N), f32), ((_H,), f32),
            ((b, _H, _P, _N), f32), ((b,), i32), ((b,), i32), ((b,), bool)]
    return (lambda *a: ops.ssd_scan(*a, chunk=128), args,
            [(b, t, _H, _P), (b, _H, _P, _N)])


def _causal_conv1d(b, t):
    f32, i32 = jnp.float32, jnp.int32
    args = [((b, t, _CONV), f32), ((_CONV, _K), f32), ((_CONV,), f32),
            ((b, _K - 1, _CONV), f32), ((b,), i32), ((b,), i32), ((b,), bool)]
    return ops.causal_conv1d, args, [(b, t, _CONV), (b, _K - 1, _CONV)]


def _state_rows_scatter():
    """The (1, chunk) prefill writes its one row back into every lane's."""
    args = [((_LANES, _H, _P, _N), jnp.float32), ((1,), jnp.int32),
            ((1, _H, _P, _N), jnp.float32)]
    return ops.state_rows_scatter, args, [(_LANES, _H, _P, _N)]


@pytest.mark.parametrize("case", [
    lambda: _ssd_scan(1, 128), lambda: _ssd_scan(_LANES, 1),
    lambda: _causal_conv1d(1, 128), lambda: _causal_conv1d(_LANES, 1),
    _state_rows_scatter,
], ids=["ssd_scan-prefill", "ssd_scan-decode", "causal_conv1d-prefill",
        "causal_conv1d-decode", "state_rows_scatter"])
def test_recurrent_state_op_compiles_for_v5e(one_chip, case):
    """The Mamba-2 mixer's serving ops at ``falcon_h1_34b``'s widths, the
    (1, 128) prefill chunk and the (32, 1) decode step: plain XLA, so what
    the chip's compiler can refuse is their size and their layouts."""
    op, args, out_shapes = case()
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]

    def fn(*xs):
        out = op(*(NDArray(x) for x in xs))
        return [o._data for o in (out if isinstance(out, tuple) else (out,))]

    lowered = jax.jit(fn).lower(*shapes)
    assert [o.shape for o in lowered.out_info] == out_shapes
    assert lowered.compile().as_text()


def test_dropout_partitions_over_four_chips(topo):
    """A dp-sharded dropout mask under the default ``rbg`` generator. With
    a bare Python probability ``jax_enable_x64`` made it a float64 draw
    from 64-bit random bits, and the v5e compiler aborted on the
    partitioned 64-bit RngBitGenerator (the first four-chip BERT step)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu import autograd
    from mxnet_tpu import random as mx_random

    mesh = Mesh(np.asarray(topo.devices[:4]), ("dp",))
    x = jax.ShapeDtypeStruct((64, 128, 768), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype,
                               sharding=NamedSharding(mesh, P()))

    def fn(key, x):
        mx_random.push_trace_rng(key)
        try:
            with autograd.train_mode():
                return ops.dropout(NDArray(x), p=0.1)._data
        finally:
            mx_random.pop_trace_rng()

    assert "rng-bit-generator" in _compile(fn, key, x)


# -- the serving step that leaves its K/V in the pool (PR 32) -----------------
# Both serving cells' widths (chipbench/configs): mistral_7b_v01, 8 lanes of
# 32 heads over 8 KV heads, rings of 2,048; falcon_h1_34b, 32 lanes of 20
# heads over 4, rings of 512, a Mamba-2 state beside them. Pages of 128.

# mellum2_12b_a2_5b (PR 33): 16 lanes of 32 heads over 4, rings of 3,584;
# three layers bounded by a window of 1,024 (a ring of 9 pages a lane
# under a table of its own) to one full layer, 64 routed experts of
# 2304 x 896 in every block.

_PAGE = 128
_CELLS = {"mistral": dict(lanes=8, heads=32, kv=8, max_seq=2048),
          "falcon": dict(lanes=32, heads=20, kv=4, max_seq=512),
          "mellum": dict(lanes=16, heads=32, kv=4, max_seq=3584),
          "command_a": dict(lanes=8, heads=128, kv=8, max_seq=7168,
                            window=4096),
          # ax_k1 (PR 39): 8 lanes of 64 heads on ONE latent array a
          # position, 576 wide, rings of 10,880; a dense layer and a routed
          "ax_k1": dict(lanes=8, heads=64, kv=1, max_seq=10880)}
_WINDOW = 1024
# the cells whose model hands back its routed layers' load after the logits
_ROUTED = ("mellum", "command_a", "ax_k1")
_LATENT, _LATENT_V = 640, 512     # 576 stored at whole lane tiles


def _cell_model(cell, layers=1):
    from mxnet_tpu.models.falcon_h1 import FalconH1Model
    from mxnet_tpu.models.llama import LlamaModel
    from mxnet_tpu.models.mellum import MellumModel

    if cell == "ax_k1":
        from mxnet_tpu.models.ax_k1 import AxK1Model

        # one dense layer and one routed, the two kinds the cell holds
        return AxK1Model(
            vocab_size=20480, units=7168, num_layers=layers + 1,
            num_heads=64, q_rank=1536, kv_rank=_LATENT_V, nope_dim=128,
            rope_dim=64, v_dim=128, hidden_size=18432,
            expert_size=2048, num_experts=192, num_experts_per_tok=8,
            num_shared_experts=1, first_dense=1, groups=(8, 4),
            routed_scale=2.5, experts_held=(0, 8),
            rope_scaling=("yarn", 32, 4096, 32, 1, 1.0), mscale_all_dim=1)
    if cell == "command_a":
        from mxnet_tpu.models.command_a_plus import CommandAPlusModel

        return CommandAPlusModel(
            vocab_size=32768, units=4096, num_heads=128, num_kv_heads=8,
            head_dim=128, sliding_window=_CELLS[cell]["window"],
            rope_theta=50000.0, expert_size=4096, num_experts=128,
            num_experts_per_tok=8, num_shared_experts=4, experts_held=(0, 8),
            layer_types=["sliding_attention", "full_attention"][:layers + 1])
    if cell == "mellum":
        yarn = ("yarn", 16, 8192, 32, 1, 1.2772588722239782)
        return MellumModel(
            vocab_size=98304, units=2304, num_heads=32, num_kv_heads=4,
            head_dim=128, sliding_window=_WINDOW, expert_size=896,
            num_experts=64, num_experts_per_tok=8,
            layer_types=["sliding_attention", "full_attention"][:layers + 1],
            rope={"sliding_attention": (500000.0, None),
                  "full_attention": (500000.0, yarn)})
    if cell == "mistral":
        return LlamaModel(vocab_size=32000, units=4096, hidden_size=14336,
                          num_heads=32, num_kv_heads=8, num_layers=layers)
    return FalconH1Model(
        vocab_size=32640, units=5120, hidden_size=21504, num_layers=layers,
        num_heads=20, num_kv_heads=4, head_dim=128, mamba_d_ssm=_H * _P,
        mamba_d_state=_N, mamba_n_heads=_H, mamba_d_head=_P,
        mamba_n_groups=_G, mamba_d_conv=_K, theta=1e11,
        key_multiplier=0.011, mlp_multipliers=(0.5, 0.5),
        ssm_multipliers=(0.3, 0.3, 0.3, 0.3, 0.3))


@pytest.mark.parametrize("cell,int8", [
    ("mistral", False), ("mistral", True), ("falcon", False),
    ("falcon", True)], ids=["mistral-f32", "mistral-int8", "falcon-f32",
                            "falcon-int8"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, cell, int8):
    c = _CELLS[cell]
    n_pages = c["max_seq"] // _PAGE
    pool = (c["lanes"] * n_pages + 1, c["kv"], _PAGE, 128)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = sds((c["lanes"], c["heads"], 1, 128), jnp.float32)
    k = sds(pool, jnp.int8 if int8 else jnp.float32)
    scales = [sds(pool[:3], jnp.float32)] * 2 if int8 else []

    def fn(q, k, v, table, sp, *scales):
        return da._pallas_paged_decode(q, k, v, table, sp, 128 ** -0.5,
                                       *(scales or (None, None)))

    text = _compile(fn, q, k, k, sds((c["lanes"], n_pages), jnp.int32),
                    sds((c["lanes"],), jnp.int32), *scales)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cell", ["mellum", "command_a"])
def test_windowed_paged_decode_kernel_compiles_for_v5e(one_chip, cell):
    """The paged kernel over a ring of 9 pages under a window of 1,024,
    at the Mellum-2 cell's widths, and over a ring of 33 under 4,096 with
    16 query heads to a KV head, at the Command A+ cell's."""
    c = _CELLS[cell]
    window = c.get("window", _WINDOW)
    cols = window // _PAGE + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    k = sds((c["lanes"] * cols + 1, c["kv"], _PAGE, 128), jnp.float32)

    def fn(q, k, v, table, sp):
        return da._pallas_paged_decode(q, k, v, table, sp, 128 ** -0.5,
                                       None, None, window)

    text = _compile(fn, sds((c["lanes"], c["heads"], 1, 128), jnp.float32),
                    k, k, sds((c["lanes"], cols), jnp.int32),
                    sds((c["lanes"],), jnp.int32))
    assert "tpu_custom_call" in text


def test_latent_paged_decode_kernel_compiles_for_v5e(one_chip):
    """The paged kernel in its latent form at the A.X-K1 cell's widths: 64
    query heads on one array a position, 576 channels stored as 640,
    values its first 512."""
    c = _CELLS["ax_k1"]
    n_pages = c["max_seq"] // _PAGE

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, pool, table, sp):
        return da._pallas_paged_decode(q, pool, None, table, sp, 0.13, None,
                                       None, None, _LATENT_V)

    text = _compile(
        fn, sds((c["lanes"], c["heads"], 1, _LATENT), jnp.float32),
        sds((c["lanes"] * n_pages + 1, 1, _PAGE, _LATENT), jnp.float32),
        sds((c["lanes"], n_pages), jnp.int32), sds((c["lanes"],), jnp.int32))
    assert "tpu_custom_call" in text
    assert f"f32[{c['lanes']},1,{c['heads']},{_LATENT_V}]" in text


def _inplace_step(cell, rows, t_len, path, one_chip):
    """The engine's in-place step over ``cell``'s model (one layer, every
    width real, parameters never materialized), compiled for the
    described chip with its cache stores donated as ``CachedOp`` donates
    them. Returns the compiled text, the stores' shapes and for each
    store its (output, parameter) numbers."""
    from mxnet_tpu.parallel.functional import functionalize_abstract
    from mxnet_tpu.serve.generate import CacheLayout, _CacheForward

    c = _CELLS[cell]
    quant = "int8" if path == "int8" else None
    net = _cell_model(cell)
    step = _CacheForward(net, c["max_seq"], path=path, quant=quant,
                         paged=True, inplace=True)
    apply_fn, structs = functionalize_abstract(step)

    def sds(shape, dtype="float32"):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    layout = CacheLayout(net, quant)
    n_pages = c["max_seq"] // _PAGE
    cols = layout.window_columns(_PAGE) if layout.window else 0
    stores = layout.alloc(sds, c["lanes"] * n_pages + 1, _PAGE, c["lanes"],
                          window_lead=c["lanes"] * cols + 1)
    args = [sds((rows, t_len), "int32"), sds((rows,), "int32"),
            sds((rows,), "int32"), sds((rows, n_pages), "int32")]
    args += [sds((rows, cols), "int32")] * bool(cols)
    args += [sds((rows,), "int32")] * layout.has_state
    # keep, and the greedy ids the step carries: one row a lane whatever
    # the call's width, handed back and never donated
    args += [sds((rows,), "int32"), sds((c["lanes"],), "int32")]
    first = len(args)
    assert step.donate_args == tuple(range(first, first + len(stores)))
    params = {n: sds(s.shape, s.dtype) for n, s in structs.items()}
    key = jax.random.PRNGKey(0)
    fn = jax.jit(lambda p, *a: apply_fn(p, *a, rng_key=key),
                 donate_argnums=tuple(1 + i for i in step.donate_args))
    text = fn.lower(params, *args, *stores).compile().as_text()
    # the stores follow the logits, for a model that routes its load, and
    # the ids
    out0 = 2 + (cell in _ROUTED)
    pairs = {(out0 + j, len(params) + first + j) for j in range(len(stores))}
    assert not any(o == out0 - 1 for o, _ in _aliases(text)), "ids aliased"
    return text, stores, pairs


def _aliases(text):
    import re

    head = text.split("entry_computation_layout", 1)[0]
    return {(int(o), int(p)) for o, p in
            re.findall(r"\{(\d+)\}: \((\d+), \{\}", head)}


def _type(s):
    kind = {"float32": "f32", "int8": "s8", "int32": "s32"}[str(s.dtype)]
    return f"{kind}[{','.join(str(d) for d in s.shape)}]"


def _no_copy_of(stores, text):
    """No ``copy`` makes an array of a cache store's type (stores under
    4 MB aside: the conv state's 2 MB changes its tiling on the way)."""
    import re

    for s in stores:
        if s.size * s.dtype.itemsize >= 4 << 20:
            assert not re.search(
                rf"= {re.escape(_type(s))}\S* copy\(", text), _type(s)


@pytest.mark.parametrize("cell,path", [
    ("mistral", "pallas"), ("mistral", "int8"), ("falcon", "pallas"),
    ("mellum", "pallas"), ("command_a", "pallas"), ("ax_k1", "pallas")])
def test_decode_step_in_place_compiles_for_v5e(one_chip, monkeypatch, cell,
                                               path):
    """The (lanes, 1) decode step: every cache store is an input-output
    alias of the executable, the kernel is in it, nothing of a ring's
    shape is ever made, and no copy of a pool stands beside the writes
    (XLA:TPU transposes the whole operand of a scatter whose indexed
    dimensions are not its outermost: ``ops.nn.write_pages``)."""
    monkeypatch.setattr(da, "_platform_of", lambda x: "tpu")  # the described chip
    c = _CELLS[cell]
    text, stores, pairs = _inplace_step(cell, c["lanes"], 1, path, one_chip)
    assert da.last_path() == "pallas_paged"
    assert "tpu_custom_call" in text
    # the greedy ids are an output of the executable itself: no second one
    assert f"s32[{c['lanes']}]" in text.split("entry_computation_layout")[1] \
        .split("\n", 1)[0]
    assert pairs <= _aliases(text), (pairs, _aliases(text))
    for kind in ("f32", "s8"):
        assert f"{kind}[{c['lanes']},{c['kv']},{c['max_seq']}," not in text
        assert f"{kind}[{c['lanes']},{c['kv']},{c['max_seq']}]" not in text
    _no_copy_of(stores, text)
    _expert_form_of(cell, text)
    # the kernel is all of its attention: the chunk's loop is not here
    assert "attn.scores" not in text


def _expert_form_of(cell, text):
    """Which form of the expert products the decode step took
    (``ops.nn.expert_form``). Command A+'s 8 lanes can hit 0.40 of its 8
    held experts of 128: the tiles' loop is in the step, and every array
    of an expert's width has one of the types the benchmark's
    ``held_expert_roofline`` (the routed stack, one expert of it) and
    ``shared_expert_roofline`` (the shared stack) tell the branches by: a
    reshape of the stack to another type would take its products out of
    those readings. Mellum-2's 16 lanes can hit 0.88 of its 64: one
    product over the stack, no loop and no condition."""
    import re

    loops = re.findall(r" (while|conditional)\(", text)
    if cell == "ax_k1":
        # 8 lanes can hit 0.29 of its 8 held experts of 192: the tiles
        assert "while" in loops
        assert text.count("dynamic_slice_sizes={1,7168,2048}") >= 2
    elif cell == "command_a":
        assert "while" in loops
        assert set(re.findall(r"f32\[(?:\d+,)*4096,4096\]", text)) <= {
            "f32[8,4096,4096]", "f32[1,4096,4096]", "f32[4096,4096]",
            "f32[4,4096,4096]"}
        # a tile's expert, sliced out of each of the three stacks
        assert text.count("dynamic_slice_sizes={1,4096,4096}") >= 3
    else:
        assert not loops


@pytest.mark.parametrize("cell", ["mistral", "falcon", "mellum",
                                  "command_a", "ax_k1"])
def test_prefill_step_in_place_compiles_for_v5e(one_chip, monkeypatch, cell):
    """The (1, 128) prefill chunk: no Mosaic call (the benchmark tells the
    two step executables apart by it), every store aliased, no copy of a
    pool or of the state rows. Its attention is a loop over blocks of
    pages (``decode_attention._xla_blocks``, PR 38): a ``while`` under
    ``attn.scores``, and no array of the chunk's positions by the keys of
    a whole table or ring, scores or mask."""
    import re

    monkeypatch.setattr(da, "_platform_of", lambda x: "tpu")
    text, stores, pairs = _inplace_step(cell, 1, 128, "pallas", one_chip)
    assert da.last_path() == "xla_blocks"
    assert "tpu_custom_call" not in text
    assert pairs <= _aliases(text), (pairs, _aliases(text))
    _no_copy_of(stores, text)
    c = _CELLS[cell]
    # Falcon-H1's table of 512 keys is one block, which needs no loop
    assert bool(re.search(r' while\([^\n]*op_name="[^"]*attn\.scores/while"',
                          text)) == (c["max_seq"] > da._BLOCK_KEYS)
    held = {c["max_seq"]}
    if cell in ("mellum", "command_a"):   # a layer under a window
        held.add(c.get("window", _WINDOW) + _PAGE)
    for keys in held:
        if keys > da._BLOCK_KEYS:       # Falcon-H1's table is one block
            assert not re.search(rf"\[[\d,]*128,{keys}\]", text), keys
