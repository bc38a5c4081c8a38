"""Latent attention's share of its roofline, both step executables
together: the least time the chip could take for the attention over the
latent pages that the traced window's calls needed, over the device time
their attention-core scopes took (``attn.kernel``, ``attn.scores``,
``kv.gather``: the paged kernel of the decode step, the chunk's loop over
pages).

The need is the larger of two, as a roofline has it: the operations of
the absorbed form (``flops_ax_k1.latent_attention_flops``: 2 x 64 x (576 +
512) a pair of query and key position a layer) at the matrix unit's bf16
peak, and the pages' bytes (``latent_attention_bytes``: 2,304 B a position
a layer) at the memory's rate. A decode visit alone needs 60 operations a
byte read, under the chip's 240: at the bf16 peak its pages' bytes bind. A
chunk's 128 queries share one read of their keys, 7,700 operations a
byte, and the window's operations, nearly all the chunks', are the larger
of the two sums. **Stored float32 and multiplied at full precision, six
bf16 passes a product, the operations cannot be done in under six times
their bf16 time: a window whose need is operations cannot read over 100 /
6 = 17%**; what it reads under that is the kernel's and the loop's own
loss.

Pairs and positions are the program's own counts, never the compiler's:
``kv_positions_latent`` of the ``serve.decode`` spans inside the window (a
decode visit's one query a lane sees every cached position of its lane: a
pair and a position read each), ``kv_pairs_latent`` of the
``serve.prefill`` spans (query ``j`` of a chunk sees ``start + j + 1``
keys) and their ``kv_keys_visited`` (the keys the chunk's loop reads
once for all its queries). The time comes through ``device_scopes.py``'s
one read of the run's file, by scope and not by operand types."""
import device_scopes
import harness
import program_spans

PART = "attn_core"


def need(trace, spans):
    """``(pairs, positions read)`` of the window's calls."""
    decode = program_spans.inside(trace, spans, "serve.decode")
    chunks = program_spans.inside(trace, spans, "serve.prefill")
    at = sum(s.stats.get("kv_positions_latent", 0) for s in decode)
    pairs = at + sum(s.stats.get("kv_pairs_latent", 0) for s in chunks)
    read = at + sum(s.stats.get("kv_keys_visited", 0) for s in chunks
                    if "kv_pairs_latent" in s.stats)
    return pairs, read


def core_seconds(split):
    """Device seconds under the attention-core scopes, every call of
    both serving executables inside the window."""
    return sum(row["calls"] * row["parts"].get(PART, 0.0)
               for kind, row in split.table.items()
               if kind in ("decode", "prefill")) / 1e3


def share(seconds, pairs, positions, record):
    """Per cent of the roofline; None where either side is missing."""
    if not seconds or not pairs:
        return None
    cfg, peaks = record["config"], record["peaks"]
    flops = harness.count_fn(cfg, "latent_attention_flops")(cfg, pairs)
    nbytes = harness.count_fn(cfg, "latent_attention_bytes")(
        cfg, positions, record["kv_itemsize"])
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def read(trace, counters, record):
    cfg = record.get("config") or {}
    if record.get("peaks") is None \
            or "latent_attention_flops" not in cfg.get("flops", {}):
        return None
    spans = program_spans.spans_of(trace)
    split = device_scopes.split_of(trace)
    if spans is None or split is None:
        return None
    return share(core_seconds(split), *need(trace, spans), record)
