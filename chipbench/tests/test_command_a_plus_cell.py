"""The Command A+ cell at the tiny sizes of its ``rehearse`` groups (4 of
16 experts held, a token takes 4, 2 shared experts, a window of 16 over
pages of 8, 16 query heads on 2 KV heads): the reference agrees with the
program, the bfloat16 control and every planted fault do not, and the
counts and the two readers the cell brings are held to hand-made numbers
and a recorded trace."""
import gzip
import json
import os

import numpy as np
import pytest

import harness
import program_spans
import trace_reduce as tr
from conftest import HERE
from test_cells import cell_of

CELL = "command_a_plus_rag_closed_c8"
CONFIG = "command_a_plus_05_2026.json"
SEED = 12
NEW = ["held_expert_roofline", "shared_expert_roofline"]


@pytest.fixture(autouse=True)
def pages_of_eight(monkeypatch):
    """The rehearsal's window of 16 lies over pages of 8, so that a lane's
    ring of three columns is written over many times in ``max_seq`` 128;
    the engine takes its page size from the environment."""
    monkeypatch.setenv("MXNET_SERVE_KV_PAGE_SIZE", "8")


def test_the_reference_agrees_and_control_and_faults_do_not():
    ref = harness.load_module("reference", "command_a_plus")
    cell = cell_of(CELL, seed=SEED)
    assert cell.config["driver"] == "serve_closed"
    rec = harness.load_module("drivers", "serve_closed").run(
        cell, control=ref.controls("float32"))
    assert rec["compared"].correct, rec["compared"].as_dict()
    (name, gap, limit), = rec["compared"].rows
    assert name == "served_token_logit_gap_max" and gap <= limit
    assert rec["failed"] == 0 and rec["attempted"] > 0
    assert all(np.isfinite(v) and v > 0 for v in rec["end_to_end"].values())
    assert set(rec["control_gaps"]) == {"bfloat16"} | {
        "fault_" + f for f in ref.FAULTS}
    assert len(ref.FAULTS) == 6
    for name, read in rec["control_gaps"].items():
        assert read > limit, name


def test_counts_from_the_configurations_shapes():
    cfg = harness.load_json("configs", CONFIG)
    f = harness.load_module(".", "flops_command_a_plus")
    assert f.kinds(cfg) == (3, 1)
    # issue 35's table: 142.61 M in attention, 50.33 M an expert
    assert f.attention_params(cfg) == 2 * 4096 * 16384 + 2 * 4096 * 1024
    assert round(f.attention_params(cfg) / 1e6, 2) == 142.61
    assert f.expert_params(cfg) == 3 * 4096 * 4096
    assert f.held_share(cfg) == 8 / 128
    per_token = f.layer_params_per_token(cfg)
    assert per_token == f.attention_params(cfg) + 4096 * 128 \
        + (4 + 0.5) * f.expert_params(cfg)
    # the run's own count of a token's assignments held takes the place
    # of the even share
    assert f.layer_params_per_token(cfg, 2) - per_token \
        == 1.5 * f.expert_params(cfg)
    # one position that attends to one key and is sampled: a window layer
    # counted at 4096 / 7168 of a key
    one = f.serve_flops(cfg, 1, 1, 1)
    assert one == pytest.approx(
        2 * 4 * per_token + 4 * 16384 * (1 + 3 * 4096 / 7168)
        + 2 * 4096 * 32768)
    # a position's K and V in one layer: 8 heads of 128, twice, 4 bytes
    assert f.attention_bytes(cfg, 10, 30, 4) == 8192 * 40
    # 3 held experts hit in each of 4 layers: 201.3 MB an expert
    assert f.held_expert_bytes(cfg, 12, 4) == 12 * 3 * 4096 * 4096 * 4
    # one call of 4 layers reads the four shared experts of each: 3.22 GB
    assert f.shared_expert_bytes(cfg, 4, 4) == 16 * 3 * 4096 * 4096 * 4
    # the whole configuration's weights, as the file's reduced_why has them
    layer = f.attention_params(cfg) + 4096 * 128 + 4096 \
        + (4 + 8) * f.expert_params(cfg)
    assert round(layer / 1e6, 2) == 747.11
    assert round((4 * layer + 32768 * 4096 + 4096) / 1e6, 1) == 3122.7


def test_the_configuration_keeps_the_sources_numbers():
    """Every key of the catalog's config under its own name and value,
    the three reduced keys alone changed."""
    cfg = harness.load_json("configs", CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not here")
    with open(catalog) as f:
        src = next(json.loads(line) for line in f
                   if '"command-a-plus-05-2026"' in line)
    assert cfg["source"] == src["source_url"]
    changed = {k for k, v in src["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"} \
        == set(cfg["reduced"])
    for key in cfg["reduced"]:
        assert cfg["published"][key] == src["config"][key]
    # the router keeps the published width; an eighth of the vocabulary,
    # 8 experts and four layers are the floors
    assert cfg["router_experts"] == src["config"]["num_experts"]
    assert cfg["vocab_size"] * 8 == src["config"]["vocab_size"]
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]


def recorded():
    path = os.path.join(HERE, "data", CELL + ".v5e.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def spans_of(d):
    return [program_spans.Span(*s) for s in d["program_spans"]]


def test_the_two_readers_on_a_recorded_trace():
    """A slice of a chip run of the cell: the held reader finds the
    operations that read the routed stack (whole, or one expert sliced out
    of it), the shared reader those that read the shared stack, neither
    the other's, the attention kernel, the projections or the head."""
    held = harness.load_module("layer_metrics", "held_expert_roofline")
    shared = harness.load_module("layer_metrics", "shared_expert_roofline")
    operands = harness.load_module("layer_metrics",
                                   "moe_expert_roofline").operands
    cfg = harness.load_json("configs", CONFIG)
    d = recorded()
    events, (lo, hi) = [tuple(e) for e in d["events"]], d["window"]
    hx, sx = held.pattern(cfg), shared.pattern(cfg)
    h_s = held.seconds_reading(events, hx, lo, hi)
    s_s = held.seconds_reading(events, sx, lo, hi)
    assert h_s == pytest.approx(d["held_seconds"], rel=1e-9)
    assert s_s == pytest.approx(d["shared_seconds"], rel=1e-9)
    busy = tr.total(tr.union([(s, e) for _, s, e in events])) / 1e9
    assert 0 < h_s < busy and 0 < s_s < busy
    h_hits = [line for line, _, _ in events if hx.search(operands(line))]
    s_hits = [line for line, _, _ in events if sx.search(operands(line))]
    assert any("f32[8,4096,4096]" in x for x in h_hits)
    assert all("f32[4,4096,4096]" in x for x in s_hits) and s_hits
    for x in h_hits + s_hits:
        assert "tpu_custom_call" not in x
    # the attention's wide projections and the head have other types
    for other in ("f32[16384,4096]", "f32[4096,16384]", "f32[32768,4096]",
                  "f32[1024,4096]", "f32[128,4096]"):
        line = f"%f = f32[8,1,4096]{{2,1,0}} fusion({other}{{1,0}} %w)"
        assert not hx.search(operands(line)) and not sx.search(operands(line))
    # an operation that writes such an array and reads none is not counted
    assert not hx.search(operands(
        "%f = f32[8,4096,4096]{2,1,0} fusion(f32[8]{0} %x)"))
    assert hx.search(operands(
        "%f = f32[32,4096]{1,0} fusion(f32[4096,4096]{1,0} %x)"))
    trace = tr.Reduced([[]], [[]], [("chipbench.window", lo, hi)])
    hit = held.experts_hit(trace, spans_of(d))
    calls = shared.layer_calls(trace, spans_of(d), cfg)
    assert hit == d["experts_hit"] > 0 and calls == d["layer_calls"] > 0
    assert hit <= 8 * calls           # at most the 8 held a call and layer
    record = {"config": cfg, "peaks": {"hbm_bytes_per_s": 819e9},
              "kv_itemsize": 4}
    f = harness.load_module(".", "flops_command_a_plus")
    assert held.share(h_s, hit, record) == pytest.approx(
        100 * f.held_expert_bytes(cfg, hit, 4) / 819e9 / h_s)
    assert shared.share(s_s, calls, record) == pytest.approx(
        100 * f.shared_expert_bytes(cfg, calls, 4) / 819e9 / s_s)
    assert 0 < held.share(h_s, hit, record) <= 100
    assert 0 < shared.share(s_s, calls, record) <= 100
    assert held.share(0.0, hit, record) is None
    assert shared.share(s_s, 0, record) is None


@pytest.mark.parametrize("name", NEW)
def test_the_readers_read_nothing_on_another_cells_trace(name):
    """Mellum-2's recorded slice (64 experts of 2304 x 896, no shared
    expert) holds no operand of this configuration's types; a cell whose
    configuration names no such count, a run on no chip and a trace with
    no spans of the program read None, never 0."""
    reader = harness.load_module("layer_metrics", name)
    held = harness.load_module("layer_metrics", "held_expert_roofline")
    cfg = harness.load_json("configs", CONFIG)
    with gzip.open(os.path.join(HERE, "data",
                                "mellum2_code_closed_c16.v5e.json.gz"),
                   "rt") as f:
        other = json.load(f)
    events, (lo, hi) = [tuple(e) for e in other["events"]], other["window"]
    assert held.seconds_reading(events, reader.pattern(cfg), lo, hi) == 0
    record = {"config": cfg, "peaks": {"hbm_bytes_per_s": 819e9},
              "kv_itemsize": 4}
    assert reader.share(0.0, 5, record) is None
    mellum = harness.load_json("configs", "mellum2_12b_a2_5b.json")
    mistral = harness.load_json("configs", "mistral_7b_v01.json")
    trace = tr.Reduced([[("f", 0, 10)]], [[]], [("chipbench.window", 0, 10)])
    for cfg_other in (mellum, mistral):
        assert reader.read(trace, {}, dict(record, config=cfg_other)) is None
    assert reader.read(trace, {}, dict(record, peaks=None)) is None
    assert reader.read(trace, {}, record) is None   # not this trace's file
    # where an operand's type cannot tell routed from shared: nothing
    assert reader.pattern(dict(cfg, num_shared_experts=8)) is None


def test_the_cell_reports_what_the_benchmark_lists():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    lists = {m["name"]: m.get("workloads", []) for m in bench["per_layer"]}
    for name in NEW + ["mixed_attn_roofline", "step_mfu.serve",
                       "decode_step_ms", "prefill_chunk_ms"]:
        assert CELL in lists[name], name
    for name in NEW:
        assert lists[name] == [CELL]
    # their readers want what this source or this pool does not give
    for name in ("decode_attn_roofline", "moe_expert_roofline",
                 "ssm_state_roofline"):
        assert CELL not in lists[name], name
