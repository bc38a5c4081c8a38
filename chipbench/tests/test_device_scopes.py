"""The per-call split of device time by the program's scopes
(``device_scopes.py``): on events made by hand, on a protobuf made by
hand, and on small cuts of chip traces (``data/*.scopes.v5e.json.gz``, as
``python3 chipbench/device_scopes.py <workload> <file>`` cuts them)."""
import gzip
import json
import os

import pytest

import device_scopes as ds
import trace_reduce as tr
from conftest import HERE, ROOT

US = 1_000
DEC = "jit(fwd)/serve_step.decode/model/"
PRE = "jit(fwd)/serve_step.prefill/model/"
CUTS = ["mistral_chat_closed", "falcon_h1_chat_closed_c32",
        "mellum2_code_closed_c16", "command_a_plus_rag_closed_c8",
        "bert_pretrain_1chip"]


def chip_by_hand():
    """Two executables of one name on one chip, 100 us each; both call an
    instruction ``fusion.140``, a state update in one and a product in the
    other; the decode step holds a ``while`` over two body operations and
    an operation under a scope no table knows."""
    ops = [
        ("fusion.140", 0, 30 * US, DEC + "layer0/mixer/jit(f)/ssm.scan/mul"),
        ("while.1", 30 * US, 80 * US,
         DEC + "layer0/ffn/jit(f)/experts.router/experts.routed/while"),
        ("fusion.7", 35 * US, 50 * US,
         DEC + "layer0/ffn/jit(f)/experts.router/experts.routed/while/"
         "body/dot_general"),
        ("fusion.8", 55 * US, 75 * US,
         DEC + "layer0/ffn/jit(f)/experts.router/experts.routed/while/"
         "body/jit(silu)/logistic"),
        ("fusion.9", 80 * US, 90 * US, DEC + "layer0/novel.scope/add"),
        ("copy.3", 90 * US, 95 * US, ""),
        ("fusion.140", 200 * US, 260 * US,
         PRE + "layer0/attention/o_proj/jit(f)/dot_general"),
        ("fusion.2", 260 * US, 290 * US,
         PRE + "layer0/attention/jit(f)/attn.scores/kv.gather/gather"),
    ]
    modules = [("jit_fwd(11)", 0, 100 * US), ("jit_fwd(22)", 200 * US,
                                              300 * US)]
    return {"ops": ops, "modules": modules}


def test_paths_become_parts():
    part = lambda n, h="": ds.part_of(n, h)[:2]  # noqa: E731
    assert part(DEC + "layer3/attention/q_proj/jit(f)/dot_general") == \
        ("decode", "attn_proj")
    assert part(DEC + "layer3/attention/jit(f)/attn.kernel/"
                "paged_decode_attention") == ("decode", "attn_core")
    assert part(PRE + "layer3/attention/jit(write_pages)/kv.write/scatter") \
        == ("prefill", "kv_write")
    # a norm is its block's: the mixer's own, a layer's is other
    assert part(PRE + "layer3/mixer/jit(f)/norm/mul") == ("prefill", "ssm")
    assert part(PRE + "layer3/attn_norm/jit(f)/norm/mul") == \
        ("prefill", "other")
    # the sort inside the products' scope is the router's: innermost first
    assert part(DEC + "layer1/ffn/jit(f)/experts.router/experts.routed/"
                "experts.router/jit(argsort)/sort") == \
        ("decode", "experts_routed")
    assert part(DEC + "layer1/ffn/jit(f)/experts.shared/dot_general") == \
        ("decode", "experts_shared")
    assert part(DEC + "lm_head/jit(f)/dot_general") == ("decode", "head")
    assert part("jit(fwd)/serve_step.decode/jit(f)/head/argmax") == \
        ("decode", "head")
    bwd = "jit(step)/train_step.grad/transpose(jvp(model))/"
    assert part(bwd + "bert/encoder/layer0/attention/jit(f)/"
                "transpose(jvp(bhqd,bhkd->bhqk))/dot_general") == \
        ("train", "encoder")
    assert part(bwd + "bert/word_embed/jit(f)/embed/scatter-add") == \
        ("train", "other")
    assert part(bwd + "jit(f)/dot_general") == ("train", "head_loss")
    assert part("jit(step)/train_step.grad/jvp(loss)/jit(f)/reduce_sum") \
        == ("train", "head_loss")
    assert part("jit(step)/train_step.optimizer/mul") == \
        ("train", "optimizer")
    # a collective is other whatever instruction it took its name from
    assert part(bwd + "bert/encoder/layer0/ffn/ffn_1/jit(f)/dot_general",
                "all-reduce.5") == ("train", "other")
    # no step scope, no kind; a step scope alone is not a scope of the table
    assert ds.part_of("jit(add)/add") == (None, "other", False)
    assert ds.part_of("jit(fwd)/serve_step.decode/jit(add)/add") == \
        ("decode", "other", False)


def test_split_by_hand():
    split = ds.Split([chip_by_hand()], (0, 1000 * US))
    dec, pre = split.table["decode"], split.table["prefill"]
    # two executables with one name and one instruction name, kept apart
    # by the step scope of what ran inside them
    assert dec["calls"] == pre["calls"] == 1
    assert dec["parts"]["ssm"] == pytest.approx(0.030)
    assert pre["parts"]["attn_proj"] == pytest.approx(0.060)
    assert pre["parts"]["attn_core"] == pytest.approx(0.030)
    assert "ssm" not in pre["parts"]
    # a while and its body count once: 50 us, not 85
    assert dec["parts"]["experts_routed"] == pytest.approx(0.050)
    # an unknown scope and an operation with no name land in other
    assert dec["parts"]["other"] == pytest.approx(0.015)
    # the parts are the busy time, the call's own event is longer
    assert sum(dec["parts"].values()) == pytest.approx(dec["busy_ms"])
    assert dec["busy_ms"] == pytest.approx(0.095)
    assert dec["call_ms_median"] == dec["call_ms_mean"] == \
        pytest.approx(0.100)
    assert dec["scoped_share"] == pytest.approx(90 / 95)
    # what a cell does not list is reported in its other
    assert split.ms("decode", "other", ["ssm", "other"]) == \
        pytest.approx(0.065)
    assert split.ms("decode", "ffn") == 0.0
    assert split.ms("train", "encoder") is None


def test_a_call_cut_by_the_window_is_left_out():
    chip = chip_by_hand()
    split = ds.Split([chip], (10 * US, 1000 * US))
    assert "decode" not in split.table and "prefill" in split.table


def test_a_chip_mean_over_chips():
    a, b = chip_by_hand(), chip_by_hand()
    b["ops"] = [(n, s, e + (5 * US if n == "fusion.2" else 0), o)
                for n, s, e, o in b["ops"]]
    split = ds.Split([a, b], (0, 1000 * US))
    assert split.table["prefill"]["calls"] == 2
    assert split.table["prefill"]["parts"]["attn_core"] == \
        pytest.approx(0.0325)


# -- the protobuf walk -------------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _ld(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field, n):
    return _varint(field << 3) + _varint(n)


def test_hlo_op_names_from_a_file_made_by_hand(tmp_path):
    def instruction(name, op_name, uid, operands=()):
        meta = _ld(7, _ld(1, "dot") + _ld(2, op_name) + _ld(
            3, "/x/ops/nn.py") + _int(4, 300)) if op_name else b""
        return _ld(2, _ld(1, name) + _ld(2, "fusion") + meta + _int(35, uid)
                   + b"".join(_int(36, o) for o in operands))

    head = DEC + "lm_head/jit(f)/dot_general"
    # the compiler's own: a weight fetched ahead (start 1 -> done 2 -> the
    # product 3 that uses it), and a copy of the product's result (4)
    module = _ld(1, "jit_fwd") + _ld(3, _ld(1, "main")
        + instruction("slice-start.1", "", 1)
        + instruction("slice-done.1", "", 2, [1])
        + instruction("fusion.140", head, 3, [2])
        + instruction("copy.9", "", 4, [3])
        + instruction("constant.5", "", 5))
    meta = _int(1, 9) + _ld(2, "jit_fwd(9)") + _ld(
        5, _int(1, 1) + _ld(6, _ld(1, module)))
    plane = _int(1, 3) + _ld(2, "/host:metadata") + _ld(
        4, _int(1, 9) + _ld(2, meta)) + _ld(5, _int(1, 1) + _ld(
            2, _int(1, 1) + _ld(2, "Hlo Proto")))
    device = _int(1, 1) + _ld(2, "/device:TPU:0") + _ld(
        3, _int(1, 1) + _ld(2, "XLA Ops") + _varint(3 << 3 | 1)
        + (7).to_bytes(8, "little"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_ld(1, device) + _ld(1, plane) + _ld(4, "host"))
    assert ds.hlo_op_names(str(path)) == {"jit_fwd(9)": {
        "slice-start.1": head, "slice-done.1": head, "fusion.140": head,
        "copy.9": head, "constant.5": ""}}


# -- the recorded cuts ---------------------------------------------------------------

def load(cell):
    path = os.path.join(HERE, "data", cell + ".scopes.v5e.json.gz")
    with gzip.open(path, "rt") as f:
        return ds.from_cut(f.read())


@pytest.mark.parametrize("cell", CUTS)
def test_the_parts_of_a_recorded_cut_add_up(cell):
    split = load(cell)
    kinds = {"train"} if cell.startswith("bert") else {"decode", "prefill"}
    assert set(split.table) == kinds
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]
                  if cell in m.get("workloads", ())}
    for kind, row in split.table.items():
        assert row["calls"] >= 1
        # by construction: within 0.1% is asked, equality holds
        assert sum(row["parts"].values()) == pytest.approx(
            row["busy_ms"], rel=1e-9)
        mine = ds.listed_parts(cell, kind)
        assert {f"{kind}_ms_in.{p}" for p in mine} <= listed and mine
        assert sum(split.ms(kind, p, mine) for p in mine) == pytest.approx(
            row["busy_ms"], rel=1e-9)
        # the executable's own event is its operations and a little more
        assert 0.99 * row["call_ms_mean"] <= row["busy_ms"] \
            <= row["call_ms_mean"]
        assert row["scoped_share"] >= 0.97
        assert split.ms(kind, "other", mine) <= 0.1 * row["busy_ms"] \
            or (cell, kind) in OTHER_OVER_A_TENTH
        assert all(m.startswith("jit_") for m in row["modules"])


# cells whose ``other`` is over a tenth of a call, with what it is
# (PERF.md section 5 names it beside the table)
OTHER_OVER_A_TENTH = set()


def test_the_kernel_has_its_name_in_a_recorded_cut():
    path = os.path.join(HERE, "data",
                        "mistral_chat_closed.scopes.v5e.json.gz")
    with gzip.open(path, "rt") as f:
        ops = json.load(f)["chips"][0]["ops"]
    kernels = [o for o in ops if "attn.kernel" in o[3]
               and o[0].startswith("paged_decode_attention")]
    assert kernels and not any(o[0] == "f" or o[0].startswith("f.")
                               for o in ops)
    assert tr.short("%paged_decode_attention.3 = f32[8]{0} custom-call()") \
        == "paged_decode_attention.3"
