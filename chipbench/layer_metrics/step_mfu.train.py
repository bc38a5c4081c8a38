"""The whole training step's share of the chips' bf16 peak: the traced
window's tokens a second, times the operations a token needs (forward and
backward, no recompute, from ``flops.py``), over chips times peak."""
import harness


def read(trace, counters, record):
    if record.get("peaks") is None or not record.get("tokens"):
        return None
    per_token = harness.count_fn(record["config"], "train_per_token")(
        record["config"], record["traffic"]["seq_length"])
    rate = record["tokens"] / record["window_s"]
    peak = record["chips"] * record["peaks"]["bf16_flops_per_s"]
    return 100.0 * rate * per_token / peak
