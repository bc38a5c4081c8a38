"""The decode-attention kernel's share of its roofline: the least time
the chip could take for the K and V bytes (and the operations) that the
decoded tokens of the window need, over the device time of the kernel's
events. Bytes and operations come from the requests' lengths and the
configuration's shapes (``flops.py``), never from the compiler."""
import harness

KERNEL = r"custom-call:tpu_custom_call"


def read(trace, counters, record):
    w = record.get("traced_work")
    if record.get("peaks") is None or not w or not w["decode_context_sum"]:
        return None
    seconds = trace.op_seconds(KERNEL)
    if not seconds:
        return None
    cfg = record["config"]
    nbytes = harness.count_fn(cfg, "decode_attention_bytes")(
        cfg, w["decode_context_sum"], record["kv_itemsize"])
    ops = harness.count_fn(cfg, "decode_attention_flops")(
        cfg, w["decode_context_sum"])
    least = max(nbytes / record["peaks"]["hbm_bytes_per_s"],
                ops / record["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / seconds
