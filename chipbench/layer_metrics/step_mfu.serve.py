"""The serving steps' share of the chip's bf16 peak: the operations that
the prompt positions and decoded tokens of the traced window need (the
held layers, attention over each position's context, the head for every
sampled position; from ``flops.py``), over window times peak."""
import harness


def read(trace, counters, record):
    w = record.get("traced_work")
    if record.get("peaks") is None or not w or trace.window_s <= 0:
        return None
    need = harness.count_fn(record["config"], "serve_flops")(
        record["config"], w["prefill_positions"] + w["decode_tokens"],
        w["sampled"], w["prefill_context_sum"] + w["decode_context_sum"])
    if need <= 0:
        return None
    peak = record["chips"] * record["peaks"]["bf16_flops_per_s"]
    return 100.0 * need / (trace.window_s * peak)
