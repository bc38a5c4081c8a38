"""The Falcon-H1 cell at the tiny sizes of its ``rehearse`` groups: the
reference agrees with the program, the bfloat16 control and the planted
faults of the recurrent state do not, and the counts and the reader the
cell brings are held to hand-made numbers and a recorded trace."""
import gzip
import json
import os

import numpy as np
import pytest

import harness
import trace_reduce as tr
from conftest import HERE
from test_cells import cell_of

CELL = "falcon_h1_chat_closed_c32"
SEED = 12   # a seed on which the tiny model's bfloat16 control flips a token


def drive(**kw):
    cell = cell_of(CELL, seed=SEED)
    return harness.load_module("drivers", cell.config["driver"]).run(cell, **kw)


def limit_of(rec):
    return {n: lim for n, _, lim in rec["compared"].rows}[
        "served_token_logit_gap_max"]


def test_the_reference_agrees_and_control_and_faults_do_not():
    ref = harness.load_module("reference", "falcon_h1")
    rec = drive(control=ref.controls("float32"))
    assert rec["compared"].correct, rec["compared"].as_dict()
    assert rec["failed"] == 0 and rec["attempted"] > 0
    assert all(np.isfinite(v) and v > 0 for v in rec["end_to_end"].values())
    gaps, limit = rec["control_gaps"], limit_of(rec)
    assert gaps["bfloat16"] > limit
    # the mixer left out, and its state lost where two calls meet
    assert gaps["fault_mixer_left_out"] > limit
    assert gaps["fault_state_lost_at_chunk_edges"] > limit
    # a lane not reset reads under the limit: the assumed weights make the
    # state forget in a few positions, and the first served token stands
    # behind a whole prompt (PERF.md section 7)
    assert "fault_lane_not_reset" in gaps


def test_state_lost_at_the_hand_over_to_decode(monkeypatch):
    """Planted in the program, where the reference cannot plant it (it is
    handed tokens and not where a prompt ends): the slot's state rows
    zeroed once its prompt is written."""
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.serve.scheduler import ContinuousEngine

    real = ContinuousEngine._prefill_chunk

    def wrap(self, i, s, n):
        real(self, i, s, n)
        if s.decoding:
            pool = self.pool
            pool.update_from_flat([
                NDArray(a._data.at[i].set(jnp.zeros_like(a._data[i])))
                if kind == "state" else a
                for a, kind in zip(pool.flat(), pool.layout.kinds)])

    monkeypatch.setattr(ContinuousEngine, "_prefill_chunk", wrap)
    rec = drive()
    assert not rec["compared"].correct, rec["compared"].as_dict()


def test_counts_from_the_configurations_shapes():
    cfg = harness.load_json("configs", "falcon_h1_34b.json")
    f = harness.load_module(".", "flops_falcon_h1")
    # issue 29's arithmetic: 430.1 M parameters a layer in matmuls
    assert f.layer_matmul_params(cfg) == 5120 * (9248 + 4096) \
        + 2 * 5120 * 2560 + 2 * 5120 * 512 + 3 * 5120 * 21504
    # a lane's state a layer: 32 x 128 x 256 and 3 x 5120 floats, in and out
    lane = 2 * 4 * (32 * 128 * 256 + 3 * 5120)
    assert f.ssm_state_bytes(cfg, 1, 4) == 5 * lane
    assert f.ssm_state_bytes(cfg, 32, 4) == 32 * 5 * lane   # 1.36 GB a step
    assert f.decode_attention_bytes(cfg, 10, 4) == 5 * 2 * 4 * 128 * 4 * 10
    assert f.decode_attention_flops(cfg, 10) == 5 * 4 * 2560 * 10
    one = f.serve_flops(cfg, 1, 1, 1)
    assert one == 5 * (2 * f.layer_matmul_params(cfg)
                       + f.scan_flops_per_position(cfg)) \
        + 5 * 4 * 2560 + 2 * 5120 * 32640


def recorded():
    path = os.path.join(HERE, "data", CELL + ".v5e.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_the_state_roofline_reader_on_a_recorded_trace():
    """120 ms of a chip run of the cell (each operation's HLO line up to
    what it writes): the reader finds the operations that write state
    rows, the update and the copies around it, and nothing else."""
    reader = harness.load_module("layer_metrics", "ssm_state_roofline")
    cfg = harness.load_json("configs", "falcon_h1_34b.json")
    d = recorded()
    events, (lo, hi) = [tuple(e) for e in d["events"]], d["window"]
    seconds = reader.state_seconds(events, cfg, lo, hi)
    assert seconds == pytest.approx(d["state_op_seconds"], rel=1e-9)
    busy = tr.total(tr.union([(s, e) for _, s, e in events])) / 1e9
    assert 0 < seconds < busy
    rx = reader.pattern(cfg)
    hits = [line for line, _, _ in events if rx.search(reader.written(line))]
    assert any("[32,32,128,256]" in h for h in hits)      # the scan's state
    assert any("[32,3,5120]" in h for h in hits)          # the conv's
    assert not any("custom_call" in h for h in hits)      # not the kernel
    # a weight of (rows, 3, 5120) read by an operation is not written by it
    assert reader.written("%f = f32[8]{0} fusion(f32[1,3,5120]{2,1,0} %x)") \
        == "f32[8]{0}"
    assert reader.written("%f = (f32[2]{0}, f32[1,3,5120]{2,1,0}) fusion(%x)") \
        == "(f32[2]{0}, f32[1,3,5120]{2,1,0})"
    record = {"traced_work": d["traced_work"], "config": cfg,
              "peaks": {"hbm_bytes_per_s": 819e9}, "kv_itemsize": 4}
    share = reader.share(seconds, record)
    steps = d["traced_work"]["decode_tokens"] \
        + d["traced_work"]["prefill_positions"] / 128
    f = harness.load_module(".", "flops_falcon_h1")
    assert share == pytest.approx(
        100 * f.ssm_state_bytes(cfg, steps, 4) / 819e9 / seconds)
    assert 0 < share <= 100
    assert reader.share(0.0, record) is None


def test_the_state_roofline_reader_reads_nothing_where_there_is_none():
    """A cell whose model keeps no recurrent state, a run with no work,
    a trace that is not the newest file's: None, never 0."""
    reader = harness.load_module("layer_metrics", "ssm_state_roofline")
    cfg = harness.load_json("configs", "falcon_h1_34b.json")
    mistral = harness.load_json("configs", "mistral_7b_v01.json")
    trace = tr.Reduced([[("f", 0, 10)]], [[]], [("chipbench.window", 0, 10)])
    record = {"traced_work": {"decode_tokens": 1, "prefill_positions": 0},
              "config": cfg, "peaks": {"hbm_bytes_per_s": 819e9},
              "kv_itemsize": 4}
    assert reader.read(trace, {}, dict(record, config=mistral)) is None
    assert reader.read(trace, {}, dict(record, traced_work=None)) is None
    assert reader.read(trace, {}, dict(record, peaks=None)) is None
    assert reader.read(trace, {}, record) is None   # not this trace's file
