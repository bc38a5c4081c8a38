"""The A.X-K1 cell at the tiny sizes of its ``rehearse`` groups (4 heads
on a latent of 32 beside a rope key of 8, one dense and two routed layers,
4 held of 16 experts in 4 groups of which a token keeps 2, top 4): the
non-absorbed reference agrees with the program's absorbed form served
from latent pages, the bfloat16 control and every planted fault do not,
and the counts and the readers the cell brings are held to hand-made
numbers and a recorded trace."""
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

CELL = "axk1_docqa_closed_c8"
CONFIG = "ax_k1.json"
SEED = 12
NEW = ["latent_attn_roofline", "decode_ms_in.attn_absorb",
       "prefill_ms_in.attn_absorb"]
US = 1_000
DEC = "jit(fwd)/serve_step.decode/model/"
PRE = "jit(fwd)/serve_step.prefill/model/"
PARTS = ["attn_proj", "attn_core", "kv_write", "ffn", "experts_routed",
         "experts_shared", "head", "other"]


def test_the_reference_agrees_and_control_and_faults_do_not():
    ref = harness.load_module("reference", "ax_k1")
    cell = cell_of(CELL, seed=SEED)
    assert cell.config["driver"] == "serve_closed"
    rec = harness.load_module("drivers", "serve_closed").run(
        cell, control=ref.controls("float32"))
    assert rec["compared"].correct, rec["compared"].as_dict()
    (name, gap, limit), = rec["compared"].rows
    assert name == "served_token_logit_gap_max" and gap <= limit
    assert rec["failed"] == 0 and rec["attempted"] > 0
    assert all(np.isfinite(v) and v > 0 for v in rec["end_to_end"].values())
    # the decode steps ran in the paged kernel, interpreted: no fallback
    assert rec["counters"]["fallback_count"] == 0
    assert set(rec["control_gaps"]) == {"bfloat16"} | {
        "fault_" + f for f in ref.FAULTS}
    assert len(ref.FAULTS) == 7
    for name, read in rec["control_gaps"].items():
        assert read > limit, name


def test_counts_from_the_configurations_shapes():
    cfg = harness.load_json("configs", CONFIG)
    f = harness.load_module(".", "flops_ax_k1")
    # issue 39's table: 101.12 M in attention, 44.04 M an expert, 396.4 M
    # in the dense layer's feed-forward
    assert f.attention_params(cfg) == 7168 * 1536 + 1536 * 64 * 192 \
        + 7168 * 576 + 512 * 64 * 256 + 8192 * 7168
    assert round(f.attention_params(cfg) / 1e6, 2) == 101.12
    assert f.expert_params(cfg) == 3 * 7168 * 2048
    assert f.dense_ffn_params(cfg) == 3 * 7168 * 18432
    assert f.layers(cfg) == (1, 4)
    assert f.held_share(cfg) == 8 / 192
    per_token = f.params_per_token(cfg)
    assert per_token == 5 * f.attention_params(cfg) + f.dense_ffn_params(cfg) \
        + 4 * (7168 * 192 + (1 + 8 * 8 / 192) * f.expert_params(cfg))
    # the run's own count of a token's assignments held takes the place
    # of the even share
    assert f.params_per_token(cfg, 2) - per_token == pytest.approx(
        4 * (2 - 1 / 3) * f.expert_params(cfg))
    # a pair of query and key position, a layer: 64 heads, a score over
    # 576 channels and a weighted sum over 512
    assert f.pair_flops(cfg) == 2 * 64 * (576 + 512) == 139264
    one = f.serve_flops(cfg, 1, 1, 1)
    assert one == pytest.approx(2 * per_token + 5 * 139264
                                + 2 * 7168 * 20480)
    assert f.latent_attention_flops(cfg, 10) == 1392640
    # a position's one array in one layer: 576 floats
    assert f.latent_attention_bytes(cfg, 10, 4) == 10 * 2304
    # the whole configuration's weights, as the file's reduced_why has them
    sparse = f.attention_params(cfg) + 7168 * 192 + f.expert_params(cfg) \
        + 2 * 7168 + 1536 + 512
    assert round(sparse / 1e6, 2) == 146.55
    dense = f.attention_params(cfg) + f.dense_ffn_params(cfg) + 2 * 7168 \
        + 1536 + 512
    assert round(dense / 1e6, 1) == 497.5
    total = dense + 4 * (sparse + 8 * f.expert_params(cfg)) \
        + 2 * 20480 * 7168 + 7168
    assert round(total / 1e6, 1) == 2786.6
    # the reference names exactly that many numbers
    ref = harness.load_module("reference", "ax_k1")
    assert sum(int(np.prod(s)) for s, _ in ref.param_shapes(cfg).values()) \
        == total


def test_the_configuration_keeps_the_sources_numbers():
    """Every key of the catalog's config under its own name and value,
    the three reduced keys alone changed."""
    cfg = harness.load_json("configs", CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not here")
    with open(catalog) as f:
        src = next(json.loads(line) for line in f if '"A.X-K1"' in line)
    assert cfg["source"] == src["source_url"]
    changed = {k for k, v in src["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"} == set(cfg["reduced"])
    for key in cfg["reduced"]:
        assert cfg["published"][key] == src["config"][key]
    # the router keeps the published width; an eighth of the vocabulary,
    # 8 experts, the dense layer and four of the others are the floors
    assert cfg["router_experts"] == src["config"]["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == src["config"]["vocab_size"]
    assert cfg["num_hidden_layers"] == cfg["first_k_dense_replace"] + 4
    for said in ("deployment", "assumed", "reduced_why"):
        assert cfg[said]
    assert "topk_method" in cfg["assumed"]
    assert cfg["serve"]["max_seq"] % 128 == 0 and cfg["serve"]["max_seq"] \
        >= 8809 + 538


# -- the time under the new scope ----------------------------------------------------

def chip_by_hand():
    """One decode call and one chunk on one chip; each holds an operation
    under ``attn.absorb`` inside the block ``attention``, a projection
    beside it, and the chunk a ``while`` under ``attn.scores`` whose body
    operation counts once."""
    ops = [
        ("fusion.1", 0, 10 * US,
         DEC + "layer0/attention/jit(f)/attn.absorb/dot_general"),
        ("fusion.2", 10 * US, 40 * US, DEC + "layer0/attention/q_b_proj/dot"),
        ("kernel.1", 40 * US, 90 * US,
         DEC + "layer0/attention/jit(f)/attn.kernel/pallas_call"),
        ("fusion.3", 100 * US, 130 * US,
         PRE + "layer1/attention/jit(f)/attn.absorb/dot_general"),
        ("while.1", 130 * US, 190 * US,
         PRE + "layer1/attention/jit(f)/attn.scores/while"),
        ("fusion.4", 135 * US, 185 * US,
         PRE + "layer1/attention/jit(f)/attn.scores/while/body/dot_general"),
        ("fusion.5", 190 * US, 200 * US,
         PRE + "layer1/attention/jit(f)/attn.absorb/dot_general"),
    ]
    return {"ops": ops, "modules": [("jit_fwd(1)", 0, 95 * US),
                                    ("jit_fwd(2)", 100 * US, 200 * US)]}


def test_the_time_under_a_scope_by_hand():
    got = latent_scopes.scope_ms([chip_by_hand()], (0, 200 * US),
                                 "attn.absorb")
    assert got == {"decode": pytest.approx(0.010),
                   "prefill": pytest.approx(0.040)}
    # a part of attn_proj, which holds the projection too
    split = ds.Split([chip_by_hand()], (0, 200 * US))
    assert split.table["decode"]["parts"]["attn_proj"] == pytest.approx(0.040)
    assert split.table["prefill"]["parts"]["attn_core"] == pytest.approx(0.060)
    # a scope nothing stands under, a window that holds no whole call
    assert latent_scopes.scope_ms([chip_by_hand()], (0, 200 * US),
                                  "ssm.scan") == {}
    assert latent_scopes.scope_ms([chip_by_hand()], (10 * US, 60 * US),
                                  "attn.absorb") == {}
    # a primitive called like the scope is no scope
    chip = {"ops": [("f", 0, 10 * US, DEC + "layer0/attention/attn.absorb")],
            "modules": [("jit_fwd(1)", 0, 10 * US)]}
    assert latent_scopes.scope_ms([chip], (0, 10 * US), "attn.absorb") == {}


def recorded():
    path = os.path.join(HERE, "data", CELL + ".scopes.v5e.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_the_readers_on_a_recorded_cut():
    """A slice of a chip run of the cell: the absorbed products stand
    inside the attention block's part, the latent kernel and the chunk's
    loop under the attention-core scopes, and the parts the cell lists
    add up to the busy time of a call."""
    d = recorded()
    chips = [{"ops": [tuple(e) for e in c["ops"]],
              "modules": [tuple(e) for e in c["modules"]]}
             for c in d["chips"]]
    window = tuple(d["window"])
    split = ds.Split(chips, window)
    assert set(split.table) == {"decode", "prefill"}
    absorb = latent_scopes.scope_ms(chips, window, "attn.absorb")
    for kind in ("decode", "prefill"):
        row = split.table[kind]
        assert 0 < absorb[kind] < row["parts"]["attn_proj"]
        assert row["parts"]["attn_core"] > 0 and row["scoped_share"] >= 0.97
        # of the parts, the cell lists attn_absorb alone (its rate is its
        # one end-to-end metric beside set-up); the tables' parts of this
        # model still add up to a call, and attn_absorb is none of them
        assert ds.listed_parts(CELL, kind) == ["attn_absorb"]
        assert sum(split.ms(kind, p, PARTS) for p in PARTS) \
            == pytest.approx(row["busy_ms"], rel=1e-9)
    names = {o[0].split(".")[0] for c in chips for o in c["ops"]
             if "attn.kernel" in o[3]}
    assert "paged_decode_attention" in names
    assert any("attn.scores/while" in o[3] for c in chips for o in c["ops"])
    roof = harness.load_module("layer_metrics", "latent_attn_roofline")
    assert roof.core_seconds(split) == pytest.approx(sum(
        split.table[k]["calls"] * split.table[k]["parts"]["attn_core"]
        for k in ("decode", "prefill")) / 1e3)


def test_the_roofline_share_by_hand():
    roof = harness.load_module("layer_metrics", "latent_attn_roofline")
    cfg = harness.load_json("configs", CONFIG)
    record = {"config": cfg, "kv_itemsize": 4,
              "peaks": {"bf16_flops_per_s": 197e12,
                        "hbm_bytes_per_s": 819e9}}
    Span = program_spans.Span
    spans = [
        Span("mxnet_tpu.serve.decode", "t", 10, 20,
             {"live": 8, "kv_positions_latent": 5 * 50000}),
        Span("mxnet_tpu.serve.prefill", "t", 20, 30,
             {"n": 128, "kv_keys_visited": 5 * 4096, "kv_keys_held": 1,
              "kv_pairs_latent": 5 * (128 * 3900 + 128 * 129 // 2)}),
        Span("mxnet_tpu.serve.decode", "t", 90, 120,        # past the window
             {"kv_positions_latent": 10 ** 9}),
    ]
    trace = tr.Reduced([[]], [[]], [("chipbench.window", 0, 100)])
    pairs, read = roof.need(trace, spans)
    assert pairs == 5 * (50000 + 128 * 3900 + 8256)
    assert read == 5 * (50000 + 4096)
    # bound by the matrix unit: 139,264 operations a pair at 197 TFLOP/s
    # against 2,304 B a position read at 819 GB/s
    flops_s = 139264 * pairs / 197e12
    assert flops_s > 2304 * read / 819e9
    assert roof.share(0.02, pairs, read, record) == pytest.approx(
        100 * flops_s / 0.02)
    # decode alone reads a position a pair: 60 operations a byte, under
    # the chip's 240, so the pages' bytes are the larger need
    assert roof.share(1.0, 1000, 1000, record) == pytest.approx(
        100 * 2304e3 / 819e9)
    assert roof.share(0.0, pairs, read, record) is None
    assert roof.share(0.02, 0, 0, record) is None


@pytest.mark.parametrize("name", NEW)
def test_the_readers_read_nothing_where_there_is_nothing(name):
    """A run on no chip, a cell whose configuration names no such count,
    a trace that is not the newest file's: None, never 0; and a program
    without the scope (another cell's recorded cut) reads nothing."""
    reader = harness.load_module("layer_metrics", name)
    cfg = harness.load_json("configs", CONFIG)
    record = {"config": cfg, "kv_itemsize": 4,
              "peaks": {"bf16_flops_per_s": 197e12,
                        "hbm_bytes_per_s": 819e9}}
    trace = tr.Reduced([[("f", 0, 10)]], [[]], [("chipbench.window", 0, 10)])
    assert reader.read(trace, {}, record) is None
    assert reader.read(trace, {}, dict(record, peaks=None)) is None
    mistral = harness.load_json("configs", "mistral_7b_v01.json")
    assert reader.read(trace, {}, dict(record, config=mistral)) is None
    with gzip.open(os.path.join(HERE, "data", "command_a_plus_rag_closed_c8"
                                ".scopes.v5e.json.gz"), "rt") as f:
        other = json.load(f)
    chips = [{"ops": [tuple(e) for e in c["ops"]],
              "modules": [tuple(e) for e in c["modules"]]}
             for c in other["chips"]]
    assert latent_scopes.scope_ms(chips, tuple(other["window"]),
                                  "attn.absorb") == {}


def test_the_cell_reports_what_the_benchmark_lists():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the rate and set-up alone end to end: the median of some 23
    # requests' ms a token in a 51 s window spreads by more than half its
    # bound over seeds (PERF.md section 7), so the cell leaves that metric
    # and, with it, every per-layer metric that moves it
    for m in bench["end_to_end"]:
        if "workloads" in m:
            assert (CELL in m["workloads"]) == (m["name"] == "serve_out_tok_s")
    lists = {m["name"]: m.get("workloads", []) for m in bench["per_layer"]}
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    for name in NEW + ["step_mfu.serve", "slot_occupancy",
                       "device_idle_share.serve", "idle_in.dispatch.serve"]:
        assert CELL in lists[name] and moves[name] == "serve_out_tok_s", name
    for name in NEW:
        assert lists[name] == [CELL]
    for name, cells in lists.items():
        if moves[name] == "latency_per_token_p50_ms":
            assert CELL not in cells, name
    # their readers want what this model or this pool does not give
    for name in ("decode_attn_roofline", "mixed_attn_roofline",
                 "held_expert_roofline", "shared_expert_roofline",
                 "moe_expert_roofline", "ssm_state_roofline"):
        assert CELL not in lists[name], name
