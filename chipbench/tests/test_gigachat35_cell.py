"""The GigaChat 3.5 cell at the tiny sizes of its ``rehearse`` groups (a
linear dense layer, a linear routed one and a gated latent one; 2 key
heads and 4 value heads of 8, 4 held of 16 experts, top 4): the reference,
which runs the delta rule position by position, agrees with the program's
chunked and one-step forms served from state rows and latent pages; the
bfloat16 control and every planted fault that changes the mathematics do
not; the sizes the configuration states add up; and the counts and the
readers the cell brings are held to hand-made numbers and a recorded
trace."""
import gzip
import json
import os

import numpy as np
import pytest

import device_scopes as ds
import harness
import latent_scopes
import program_spans
import trace_reduce as tr
from conftest import HERE
from test_cells import cell_of

CELL = "gigachat35_reason_closed_c32"
CONFIG = "gigachat3_5_432b_a28b.json"
SEED = 12
NEW = ["decode_ms_in.delta_rule", "prefill_ms_in.delta_rule",
       "delta_rule_roofline"]
US = 1_000
DEC = "jit(fwd)/serve_step.decode/model/"
PRE = "jit(fwd)/serve_step.prefill/model/"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_reference_agrees_and_control_and_faults_do_not(monkeypatch):
    """The rehearsal's prompts lie inside one chunk of 128, so the conv's
    inputs lost at the chunk's edges are planted every 16 positions here;
    the gate's factor of 2 is divided out by the block's post-norm and
    reads as the reference itself (``tests/test_gigachat35.py``)."""
    ref = harness.load_module("reference", "gigachat35")
    monkeypatch.setattr(ref, "CHUNK", 16)
    cell = cell_of(CELL, seed=SEED)
    assert cell.config["driver"] == "serve_closed"
    rec = harness.load_module("drivers", "serve_closed").run(
        cell, control=ref.controls("float32"))
    assert rec["compared"].correct, rec["compared"].as_dict()
    (name, gap, limit), = rec["compared"].rows
    assert name == "served_token_logit_gap_max" and gap <= limit
    assert rec["failed"] == 0 and rec["attempted"] > 0
    assert all(np.isfinite(v) and v > 0 for v in rec["end_to_end"].values())
    assert rec["counters"]["fallback_count"] == 0
    assert set(rec["control_gaps"]) == {"bfloat16"} | {
        "fault_" + f for f in ref.FAULTS}
    for name, read in rec["control_gaps"].items():
        if name == "fault_gate_factor_left_out":
            assert read <= limit, read
        else:
            assert read > limit, name


def _shapes_sum(cfg):
    ref = harness.load_module("reference", "gigachat35")
    by = {}
    for name, (shape, _) in ref.param_shapes(cfg).items():
        group = name.split(".")[0] if name.startswith("layer") else "top"
        by[group] = by.get(group, 0) + int(np.prod(shape))
    return by


def test_the_cut_adds_up():
    """The reduced_why's arithmetic, part by part and in all, against
    the count functions and the reference's leaves."""
    cfg = harness.load_json("configs", CONFIG)
    f = harness.load_module(".", "flops_gigachat35")
    assert f.linear_params(cfg) == 7168 * 24576 + 7168 * 128 + 8192 * 7168
    assert round(f.linear_params(cfg) / 1e6, 2) == 235.80
    assert f.attention_params(cfg) == 7168 * 1536 + 1536 * 64 * 192 \
        + 7168 * 576 + 512 * 64 * 256 + 8192 * 7168 + 7168 * 8192
    assert round(f.attention_params(cfg) / 1e6, 2) == 159.84
    assert f.expert_params(cfg) == 3 * 7168 * 2048
    assert f.dense_ffn_params(cfg) == 3 * 7168 * 18432
    assert f.layers(cfg) == (4, 1, 1, 4)
    by = _shapes_sum(cfg)
    mixer = f.linear_params(cfg) + 16384 * 4 + 64 + 64 + 128
    norms = 4 * 7168
    routed = 7168 * 256 + 256 + 9 * f.expert_params(cfg)
    assert by["layer0"] == mixer + f.dense_ffn_params(cfg) + norms \
        == 632_254_720
    assert by["layer1"] == by["layer2"] == by["layer3"] \
        == mixer + routed + norms == 634_089_984
    assert by["layer4"] == f.attention_params(cfg) + 1536 + 512 + routed \
        + norms == 558_070_016
    assert by["top"] == 2 * 16032 * 7168 + 7168
    total = sum(by.values())
    assert total == 3_322_436_608 and round(total * 4 / 1e9, 2) == 13.29
    assert "3,322.4 M" in cfg["reduced_why"] and "13.29 GB" in cfg[
        "reduced_why"]
    # the state and the pages beside them, at the cell's 32 lanes
    lane_layer = 64 * 128 * 128 * 4 + 3 * 16384 * 4
    assert 4 * 32 * lane_layer == 562_036_736
    pages = 32 * (cfg["serve"]["max_seq"] // 128) + 1
    assert pages * 128 * 640 * 4 == 430_243_840
    for said in ("562,036,736", "430,243,840"):
        assert said in cfg["reduced_why"]


def test_the_counts_by_hand():
    cfg = harness.load_json("configs", CONFIG)
    f = harness.load_module(".", "flops_gigachat35")
    # the recurrence: 7 K V a value head a position
    assert f.delta_rule_flops(cfg, 10) == 10 * 7 * 64 * 128 * 128
    # the state read and written an advance, the rows a position
    state = 64 * 128 * 128
    row = 2 * 32 * 128 + 2 * 64 * 128 + 2 * 64
    assert f.delta_rule_bytes(cfg, 3, 10, 4) == (2 * state * 3 + row * 10) * 4
    per_token = f.params_per_token(cfg)
    assert per_token == 4 * f.linear_params(cfg) + f.attention_params(cfg) \
        + f.dense_ffn_params(cfg) \
        + 4 * (7168 * 256 + (1 + 8 * 8 / 256) * f.expert_params(cfg))
    conv = 2 * 4 * 16384
    one = f.serve_flops(cfg, 1, 1, 1)
    assert one == pytest.approx(2 * per_token + 4 * (7 * state + conv)
                                + f.pair_flops(cfg) + 2 * 7168 * 16032)
    assert f.latent_attention_bytes(cfg, 10, 4) == 10 * 576 * 4


# the source's config.json as the catalog of public architectures has it:
# the keys the cut changes, and the widths it may not
PUBLISHED = {"num_hidden_layers": 40, "n_routed_experts": 256,
             "vocab_size": 128256,
             "full_attention_layers": list(range(3, 40, 4)),
             "num_nextn_predict_layers": 2}
WIDTHS = {"hidden_size": 7168, "intermediate_size": 18432,
          "moe_intermediate_size": 2048, "num_attention_heads": 64,
          "num_key_value_heads": 64, "kv_lora_rank": 512,
          "q_lora_rank": 1536, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128,
          "num_experts_per_tok": 8, "n_shared_experts": 1,
          "linear_num_key_heads": 32, "linear_num_value_heads": 64,
          "linear_key_head_dim": 128, "linear_value_head_dim": 128,
          "linear_conv_kernel_dim": 4, "first_k_dense_replace": 3}


def test_the_configuration_keeps_the_sources_numbers():
    """The five reduced keys alone changed, every width as published."""
    cfg = harness.load_json("configs", CONFIG)
    assert set(cfg["reduced"]) == set(PUBLISHED)
    for key, value in PUBLISHED.items():
        assert cfg["published"][key] == value and cfg[key] != value, key
    for key, value in WIDTHS.items():
        assert cfg[key] == value, key
    # the router keeps the published width; an eighth of the vocabulary,
    # 8 experts, one dense layer and a whole period after it
    assert cfg["router_experts"] == PUBLISHED["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    src_layers = cfg["source_layers"]
    assert len(src_layers) == cfg["num_hidden_layers"]
    full = PUBLISHED["full_attention_layers"]
    assert [i for i, s in enumerate(src_layers) if s in full] \
        == cfg["full_attention_layers"]
    assert sum(s < cfg["first_k_dense_replace"] for s in src_layers) == 1
    assert src_layers[1:] == list(range(4, 8))
    for said in ("deployment", "assumed", "reduced_why"):
        assert cfg[said]
    for reading in ("attention_gate", "linear_gate", "norm_gain",
                    "swiglu_limit", "router", "initialisation"):
        assert reading in cfg["assumed"]
    assert cfg["serve"]["max_seq"] % 128 == 0 and cfg["serve"]["max_seq"] \
        >= 1722 + 3444


# -- the time under the new scope ---------------------------------------------

def chip_by_hand():
    """One decode call and one chunk on one chip; each holds an operation
    under ``delta.rule`` inside the block ``mixer`` and a projection
    beside it."""
    ops = [
        ("fusion.1", 0, 30 * US,
         DEC + "layer0/mixer/jit(f)/delta.rule/multiply_reduce"),
        ("fusion.2", 30 * US, 40 * US,
         DEC + "layer0/mixer/in_proj_qkvz/dot"),
        ("fusion.3", 100 * US, 150 * US,
         PRE + "layer1/mixer/jit(f)/delta.rule/triangular_solve"),
        ("fusion.4", 150 * US, 160 * US,
         PRE + "layer1/mixer/jit(f)/ssm.conv/add"),
    ]
    return {"ops": ops, "modules": [("jit_fwd(1)", 0, 95 * US),
                                    ("jit_fwd(2)", 100 * US, 200 * US)]}


def test_the_time_under_the_rule_by_hand():
    got = latent_scopes.scope_ms([chip_by_hand()], (0, 200 * US),
                                 "delta.rule")
    assert got == {"decode": pytest.approx(0.030),
                   "prefill": pytest.approx(0.050)}
    # a part of ssm, which holds the mixer's projections and conv too
    split = ds.Split([chip_by_hand()], (0, 200 * US))
    assert split.table["decode"]["parts"]["ssm"] == pytest.approx(0.040)
    assert split.table["prefill"]["parts"]["ssm"] == pytest.approx(0.060)


def test_the_roofline_share_by_hand():
    roof = harness.load_module("layer_metrics", "delta_rule_roofline")
    cfg = harness.load_json("configs", CONFIG)
    record = {"config": cfg, "kv_itemsize": 4, "peaks": PEAKS}
    Span = program_spans.Span
    spans = [
        Span("mxnet_tpu.serve.decode", "t", 10, 20,
             {"live": 32, "state_positions": 4 * 32}),
        Span("mxnet_tpu.serve.prefill", "t", 20, 30,
             {"slot": 3, "n": 128, "state_positions": 4 * 128}),
        Span("mxnet_tpu.serve.decode", "t", 90, 120,        # past the window
             {"state_positions": 10 ** 9}),
    ]
    trace = tr.Reduced([[]], [[]], [("chipbench.window", 0, 100)])
    positions, advances = roof.need(trace, spans)
    assert positions == 4 * (32 + 128) and advances == 4 * (32 + 1)
    # bound by the state's bytes: under one operation a byte read
    flops = 7 * 64 * 128 * 128 * positions
    nbytes = roof.harness.count_fn(cfg, "delta_rule_bytes")(
        cfg, advances, positions, 4)
    assert flops / 197e12 < nbytes / 819e9
    assert roof.share(0.01, positions, advances, record) == pytest.approx(
        100 * nbytes / 819e9 / 0.01)
    assert roof.share(0.0, positions, advances, record) is None
    assert roof.share(0.01, 0, 0, record) is None


def recorded():
    path = os.path.join(HERE, "data", CELL + ".scopes.v5e.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_the_readers_on_a_recorded_cut():
    """A slice of a chip run of the cell: the rule stands inside the
    mixer's part in both executables, and the parts add up to a call."""
    d = recorded()
    chips = [{"ops": [tuple(e) for e in c["ops"]],
              "modules": [tuple(e) for e in c["modules"]]}
             for c in d["chips"]]
    window = tuple(d["window"])
    split = ds.Split(chips, window)
    assert set(split.table) == {"decode", "prefill"}
    rule = latent_scopes.scope_ms(chips, window, "delta.rule")
    parts = ["attn_proj", "attn_core", "kv_write", "ffn", "experts_routed",
             "experts_shared", "ssm", "head", "other"]
    for kind in ("decode", "prefill"):
        row = split.table[kind]
        assert 0 < rule[kind] < row["parts"]["ssm"]
        assert row["scoped_share"] >= 0.97
        assert sum(split.ms(kind, p, parts) for p in parts) \
            == pytest.approx(row["busy_ms"], rel=1e-9)
    assert any("delta.rule" in o[3] for c in chips for o in c["ops"])


@pytest.mark.parametrize("name", NEW)
def test_the_readers_read_nothing_where_there_is_nothing(name):
    """A run on no chip, a cell whose configuration names no such count,
    a trace that is not the newest file's: None, never 0; and a program
    without the scope (another cell's recorded cut) reads nothing."""
    reader = harness.load_module("layer_metrics", name)
    cfg = harness.load_json("configs", CONFIG)
    record = {"config": cfg, "kv_itemsize": 4, "peaks": PEAKS}
    trace = tr.Reduced([[("f", 0, 10)]], [[]], [("chipbench.window", 0, 10)])
    assert reader.read(trace, {}, record) is None
    assert reader.read(trace, {}, dict(record, peaks=None)) is None
    falcon = harness.load_json("configs", "falcon_h1_34b.json")
    assert reader.read(trace, {}, dict(record, config=falcon)) is None
    with gzip.open(os.path.join(HERE, "data", "falcon_h1_chat_closed_c32"
                                ".scopes.v5e.json.gz"), "rt") as f:
        other = json.load(f)
    chips = [{"ops": [tuple(e) for e in c["ops"]],
              "modules": [tuple(e) for e in c["modules"]]}
             for c in other["chips"]]
    assert latent_scopes.scope_ms(chips, tuple(other["window"]),
                                  "delta.rule") == {}


def test_the_cell_reports_what_the_benchmark_lists():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    ends = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert CELL in ends["serve_out_tok_s"]
    lists = {m["name"]: m.get("workloads", []) for m in bench["per_layer"]}
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    for name in NEW + ["step_mfu.serve", "slot_occupancy",
                       "device_idle_share.serve", "idle_in.schedule.serve",
                       "idle_in.dispatch.serve", "idle_in.sample.serve",
                       "idle_in.settle.serve", "idle_unattributed.serve"]:
        assert CELL in lists[name] and moves[name] == "serve_out_tok_s", name
    for name in NEW:
        assert lists[name] == [CELL]
    reported = {n for n, cells in ends.items() if cells is None or CELL in
                cells}
    for name, cells in lists.items():
        if CELL in cells:
            assert moves[name] in reported, name
    # their readers want what this model or this pool does not give
    for name in ("decode_attn_roofline", "mixed_attn_roofline",
                 "held_expert_roofline", "shared_expert_roofline",
                 "moe_expert_roofline", "ssm_state_roofline"):
        assert CELL not in lists[name], name
