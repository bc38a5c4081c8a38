"""The Mellum-2 cell at the tiny sizes of its ``rehearse`` groups (8
experts of which a token takes 2, a window of 16 over pages of 8): the
reference agrees with the program, the bfloat16 control and every planted
fault do not, a ring column written over a page early is caught, and the
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

CELL = "mellum2_code_closed_c16"
SEED = 12


@pytest.fixture(autouse=True)
def pages_of_eight(monkeypatch):
    """The rehearsal's window of 16 lies over pages of 8, so that a lane's
    ring of three columns is written over many times in ``max_seq`` 128;
    the engine takes its page size from the environment."""
    monkeypatch.setenv("MXNET_SERVE_KV_PAGE_SIZE", "8")


def drive(**kw):
    cell = cell_of(CELL, seed=SEED)
    assert cell.config["driver"] == "serve_closed"
    return harness.load_module("drivers", "serve_closed").run(cell, **kw)


def test_the_reference_agrees_and_control_and_faults_do_not():
    ref = harness.load_module("reference", "mellum2")
    rec = drive(control=ref.controls("float32"))
    assert rec["compared"].correct, rec["compared"].as_dict()
    (name, gap, limit), = rec["compared"].rows
    assert name == "served_token_logit_gap_max" and gap <= limit
    assert rec["failed"] == 0 and rec["attempted"] > 0
    assert all(np.isfinite(v) and v > 0 for v in rec["end_to_end"].values())
    assert set(rec["control_gaps"]) == {"bfloat16"} | {
        "fault_" + f for f in ref.FAULTS}
    for name, read in rec["control_gaps"].items():
        assert read > limit, name


def test_a_ring_column_written_over_a_page_early(monkeypatch):
    """Planted in the program: a ring one column short, so that the page
    being written takes the place of one that later queries still see;
    the cell comes out not correct."""
    from mxnet_tpu.ops.pallas import decode_attention as da
    from mxnet_tpu.serve.generate import CacheLayout

    monkeypatch.setattr(CacheLayout, "window_columns",
                        lambda self, page: self.window // page)
    real = da.paged_decode_attention

    def unchecked(q, k_pool, v_pool, page_table, start_pos, scale=None,
                  k_scale=None, v_scale=None, window=None):
        if window is None:
            return real(q, k_pool, v_pool, page_table, start_pos, scale,
                        k_scale, v_scale)
        # the kernel's own check of the ring's size stands in the way of
        # the fault: the XLA path of the same mathematics takes the ring
        return da._xla_window(q, k_pool, v_pool, page_table, start_pos,
                              scale or q.shape[-1] ** -0.5, window)

    monkeypatch.setattr(da, "paged_decode_attention", unchecked)
    monkeypatch.setattr(da, "_record_fallback", lambda *a: None)
    rec = drive()
    assert not rec["compared"].correct, rec["compared"].as_dict()


def test_counts_from_the_configurations_shapes():
    cfg = harness.load_json("configs", "mellum2_12b_a2_5b.json")
    f = harness.load_module(".", "flops_mellum2")
    assert f.kinds(cfg) == (3, 1)
    # issue 33's arithmetic: 21.23 M in attention, 6.193 M an expert
    assert f.attention_params(cfg) == 2 * 2304 * 4096 + 2 * 2304 * 512
    assert f.expert_params(cfg) == 3 * 2304 * 896
    per_token = f.layer_params_per_token(cfg)
    assert per_token == f.attention_params(cfg) + 2304 * 64 \
        + 8 * f.expert_params(cfg)
    # one position that attends to one key and is sampled: a window layer
    # counted at 1024 / 3584 of a key
    one = f.serve_flops(cfg, 1, 1, 1)
    assert one == pytest.approx(
        2 * 4 * per_token + 4 * 4096 * (1 + 3 * 1024 / 3584)
        + 2 * 2304 * 98304)
    # a position's K and V in one layer: 4 heads of 128, twice, 4 bytes
    assert f.attention_bytes(cfg, 10, 30, 4) == 4096 * 40
    # 57 experts hit in each of 4 layers: 24.8 MB an expert
    assert f.moe_expert_bytes(cfg, 57 * 4, 4) == 57 * 4 * 3 * 2304 * 896 * 4
    # the whole configuration's weights, as the file's reduced_why has them
    layer = f.attention_params(cfg) + 2304 * 64 + 64 * f.expert_params(cfg) \
        + 2 * 2304
    assert round(layer / 1e6, 2) == 417.75
    assert round((4 * layer + 2 * 98304 * 2304 + 2304) / 1e6) == 2124


def test_the_configuration_keeps_the_sources_numbers():
    """Every key of the catalog's config under its own name and value,
    ``num_hidden_layers`` alone changed."""
    cfg = harness.load_json("configs", "mellum2_12b_a2_5b.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not here")
    with open(catalog) as f:
        src = next(json.loads(line) for line in f
                   if '"Mellum2-12B-A2.5B-Instruct"' in line)
    assert cfg["source"] == src["source_url"]
    changed = {k for k, v in src["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["published"]["num_hidden_layers"] \
        == src["config"]["num_hidden_layers"]


def recorded():
    path = os.path.join(HERE, "data", CELL + ".v5e.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def spans_of(d):
    return [program_spans.Span(*s) for s in d["program_spans"]]


def test_the_expert_roofline_reader_on_a_recorded_trace():
    """A slice of a chip run of the cell: the reader finds the operations
    that read an expert weight array, in both step executables, and not
    the attention kernel, the head or the page writes."""
    reader = harness.load_module("layer_metrics", "moe_expert_roofline")
    cfg = harness.load_json("configs", "mellum2_12b_a2_5b.json")
    d = recorded()
    events, (lo, hi) = [tuple(e) for e in d["events"]], d["window"]
    seconds = reader.expert_seconds(events, cfg, lo, hi)
    assert seconds == pytest.approx(d["expert_seconds"], rel=1e-9)
    busy = tr.total(tr.union([(s, e) for _, s, e in events])) / 1e9
    assert 0 < seconds < busy
    rx = reader.pattern(cfg)
    hits = [line for line, _, _ in events
            if rx.search(reader.operands(line))]
    assert any("[64,2304,896]" in h for h in hits)
    assert any("[64,896,2304]" in h for h in hits)
    assert not any("tpu_custom_call" in h for h in hits)
    # an operation that writes such an array and reads none is not counted
    assert not rx.search(reader.operands(
        "%f = f32[64,2304,896]{2,1,0} fusion(f32[8]{0} %x)"))
    assert rx.search(reader.operands(
        "%f = f32[8]{0} fusion(f32[1,896,2304]{2,1,0} %x)"))
    trace = tr.Reduced([[]], [[]], [("chipbench.window", lo, hi)])
    hit = reader.experts_hit(trace, spans_of(d))
    assert hit == d["experts_hit"] > 0
    record = {"config": cfg, "peaks": {"hbm_bytes_per_s": 819e9},
              "kv_itemsize": 4}
    share = reader.share(seconds, hit, record)
    f = harness.load_module(".", "flops_mellum2")
    assert share == pytest.approx(
        100 * f.moe_expert_bytes(cfg, hit, 4) / 819e9 / seconds)
    assert 0 < share <= 100
    assert reader.share(0.0, hit, record) is None
    assert reader.share(seconds, 0, record) is None


def test_the_mixed_attention_reader_on_a_recorded_trace():
    """The same slice: the kernel's calls are the Mosaic calls that read a
    page pool, and the bytes are the decode spans' positions of each kind."""
    reader = harness.load_module("layer_metrics", "mixed_attn_roofline")
    cfg = harness.load_json("configs", "mellum2_12b_a2_5b.json")
    d = recorded()
    events, (lo, hi) = [tuple(e) for e in d["events"]], d["window"]
    seconds = reader.kernel_seconds(events, cfg, lo, hi)
    assert seconds == pytest.approx(d["kernel_seconds"], rel=1e-9)
    kernels = [line for line, _, _ in events if "tpu_custom_call" in line]
    assert kernels and all(reader.pattern(cfg).search(k) for k in kernels)
    # a Mosaic call that reads no page pool is another kernel
    assert reader.kernel_seconds(
        [('%g = f32[128,896]{1,0} custom-call(f32[128,2304]{1,0} %x), '
          'custom_call_target="tpu_custom_call"', lo, hi)], cfg, lo, hi) == 0
    trace = tr.Reduced([[]], [[]], [("chipbench.window", lo, hi)])
    full, window = reader.positions(trace, spans_of(d))
    assert [full, window] == d["kv_positions"] and 0 < window
    assert window <= 3 * 1024 * full     # three layers, bounded by the window
    record = {"config": cfg, "peaks": {"hbm_bytes_per_s": 819e9},
              "kv_itemsize": 4}
    share = reader.share(seconds, full, window, record)
    assert share == pytest.approx(
        100 * 4096 * (full + window) / 819e9 / seconds)
    assert 0 < share <= 100
    assert reader.share(0.0, full, window, record) is None


@pytest.mark.parametrize("name", ["moe_expert_roofline",
                                  "mixed_attn_roofline"])
def test_the_readers_read_nothing_where_there_is_none(name):
    """A cell whose model neither routes nor has a window (its
    configuration names no such count), a run on no chip, a trace with no
    spans of the program: None, never 0."""
    reader = harness.load_module("layer_metrics", name)
    cfg = harness.load_json("configs", "mellum2_12b_a2_5b.json")
    mistral = harness.load_json("configs", "mistral_7b_v01.json")
    trace = tr.Reduced([[("f", 0, 10)]], [[]], [("chipbench.window", 0, 10)])
    record = {"config": cfg, "peaks": {"hbm_bytes_per_s": 819e9},
              "kv_itemsize": 4}
    assert reader.read(trace, {}, dict(record, config=mistral)) is None
    assert reader.read(trace, {}, dict(record, peaks=None)) is None
    assert reader.read(trace, {}, record) is None   # not this trace's file
