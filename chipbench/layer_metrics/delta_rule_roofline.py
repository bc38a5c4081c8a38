"""The gated delta rule's share of its roofline, both step executables
together: the least time the chip could take for the rule over the
positions that the traced window's calls advanced, over the device time
of the operations under the op scope ``delta.rule``.

The need is the larger of two, as a roofline has it: the recurrence's
operations (``flops_gigachat35.delta_rule_flops``: 7 K V a value head a
position, 7.3 M a position a layer at 64 heads of 128 x 128) at the
matrix unit's bf16 peak, and its bytes (``delta_rule_bytes``: the 4.19 MB
state of a lane read and written once an advance, and the position's
rows) at the memory's rate. A decode step advances every lane by one
position and needs under one operation a byte: its state's bytes bind,
and the step's one-position form reads the state twice and writes it
once, so it cannot read over two thirds. A chunk's call takes the state
in and gives it back once for its 128 positions, 55 operations a byte,
still under the chip's 240: bytes bind there too. **Were the operations
to bind, stored float32 and multiplied at full precision, six bf16
passes a product, they could not be done in under six times their bf16
time: such a window could not read over 100 / 6 = 17%.**

Positions and advances are the program's own counts, never the
compiler's: ``state_positions`` of the ``serve.decode`` spans inside the
window (each lane's one position in each linear layer, an advance each)
and of the ``serve.prefill`` spans (a chunk's real positions in each
linear layer, one advance a layer: ``state_positions / n``). The time
comes through ``latent_scopes.py``'s one read of the run's file, the
scope's mean a call times the calls of ``device_scopes.py``'s split."""
import device_scopes
import harness
import latent_scopes
import program_spans

SCOPE = "delta.rule"


def need(trace, spans):
    """``(positions, advances)`` of the window's calls, summed over the
    linear layers."""
    decode = program_spans.inside(trace, spans, "serve.decode")
    chunks = [s for s in program_spans.inside(trace, spans, "serve.prefill")
              if s.stats.get("n")]
    at = sum(s.stats.get("state_positions", 0) for s in decode)
    positions = at + sum(s.stats.get("state_positions", 0) for s in chunks)
    advances = at + sum(s.stats.get("state_positions", 0) // s.stats["n"]
                        for s in chunks)
    return positions, advances


def rule_seconds(trace, split):
    """Device seconds under ``delta.rule``, every call of both serving
    executables inside the window."""
    total = 0.0
    for kind in ("decode", "prefill"):
        ms = latent_scopes.metric(trace, kind, SCOPE)
        if ms is not None and kind in split.table:
            total += split.table[kind]["calls"] * ms / 1e3
    return total


def share(seconds, positions, advances, record):
    """Per cent of the roofline; None where either side is missing."""
    if not seconds or not positions:
        return None
    cfg, peaks = record["config"], record["peaks"]
    flops = harness.count_fn(cfg, "delta_rule_flops")(cfg, positions)
    nbytes = harness.count_fn(cfg, "delta_rule_bytes")(
        cfg, advances, positions, record["kv_itemsize"])
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def read(trace, counters, record):
    cfg = record.get("config") or {}
    if record.get("peaks") is None \
            or "delta_rule_flops" not in cfg.get("flops", {}):
        return None
    spans = program_spans.spans_of(trace)
    split = device_scopes.split_of(trace)
    if spans is None or split is None:
        return None
    return share(rule_seconds(trace, split), *need(trace, spans), record)
