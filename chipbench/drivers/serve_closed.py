"""Closed-loop serving cells: ``clients`` threads, each submitting its
next request to ``ContinuousEngine.submit`` when its last resolves.

The loop runs for a lead-in before the window opens, so that the window
starts on full slots. The end-to-end metrics are taken on the client's
clock alone: the engine hands back no per-token times, so the rate
spreads a request's tokens evenly from its submit to its answer and
counts those that fall inside the window (a request that crosses an edge
gives its share), and the latency is a request's whole time over its
tokens, over the requests that completed in the window. What the engine
says of itself (``ttft_ms``) feeds per-layer metrics only: the tails of
time to first token and of (client latency - ttft) / (tokens - 1).
With ``--trace 1`` the profiler runs for the first ``trace_seconds`` of
the window and the device metrics are of that part.
Once the window has closed and the engine is freed, a sample of the
finished requests is run through the plain reference, teacher-forced on
the served tokens, a layer at a time.
"""
import gc
import threading
import time

import numpy as np

import harness
import traffic
import weights


class Clients:
    """The closed loop. ``done`` and ``failed`` fill as requests resolve."""

    def __init__(self, engine, reqs, n, timeout):
        self.engine, self.reqs, self.n, self.timeout = engine, reqs, n, timeout
        self.done, self.failed = [], []
        self.halt = threading.Event()
        self.lock = threading.Lock()
        self.threads = [threading.Thread(target=self._loop, args=(c,),
                                         name=f"chipbench-client-{c}")
                        for c in range(n)]

    def _loop(self, c):
        i = c
        while not self.halt.is_set():
            if i >= len(self.reqs):
                with self.lock:
                    self.failed.append({"id": -1, "error": "the generator "
                                        "ran out of requests"})
                return
            req = self.reqs[i]
            i += self.n
            t_sub = harness.now()
            try:
                with harness.span("chipbench.submit"):
                    fut = self.engine.submit(
                        req["prompt"], max_new_tokens=req["max_new_tokens"])
                with harness.span("chipbench.wait"):
                    res = fut.result(self.timeout)
                t_done = harness.now()
                tokens = [int(t) for t in res["tokens"]]
                if len(tokens) != req["max_new_tokens"]:
                    raise AssertionError(
                        f"{len(tokens)} tokens for {req['max_new_tokens']}")
                row = {"id": req["id"], "t_sub": t_sub, "t_done": t_done,
                       "ttft_ms": float(res["ttft_ms"]), "tokens": tokens,
                       "prompt_len": len(req["prompt"])}
                with self.lock:
                    self.done.append(row)
            except Exception as e:  # a failed request is counted, not hidden
                with self.lock:
                    self.failed.append({"id": req["id"], "t_done":
                                        harness.now(), "error": repr(e)})

    def start(self):
        for t in self.threads:
            t.start()

    def finish(self, timeout):
        """No new requests; wait for those in flight."""
        self.halt.set()
        for t in self.threads:
            t.join(timeout)
        if any(t.is_alive() for t in self.threads):
            raise AssertionError("a client never got its answer")


def tokens_in_window(rows, t_open, t_close):
    """Output tokens made between two times by the client's clock alone:
    each request's tokens spread evenly from its submit to its answer."""
    total = 0
    for r in rows:
        n = len(r["tokens"])
        at = r["t_sub"] + (np.arange(n) + 1.0) / n * (r["t_done"] - r["t_sub"])
        total += int(((at >= t_open) & (at <= t_close)).sum())
    return total


def work_in_window(rows, t_open, t_close):
    """What the engine computed between two times, reckoned from the
    requests' lengths and times (it hands back no per-token times): a
    prompt's positions spread evenly from submit to first token, the
    decoded tokens evenly from first token to done. ``sampled`` counts
    the output tokens: first tokens and decoded ones."""
    w = {"prefill_positions": 0, "prefill_context_sum": 0,
         "decode_tokens": 0, "decode_context_sum": 0, "sampled": 0}
    for r in rows:
        p, n = r["prompt_len"], len(r["tokens"])
        t_first = r["t_sub"] + r["ttft_ms"] / 1e3
        at = r["t_sub"] + (np.arange(p) + 1.0) / p * (t_first - r["t_sub"])
        inside = (at >= t_open) & (at <= t_close)
        w["prefill_positions"] += int(inside.sum())
        w["prefill_context_sum"] += int((np.arange(p) + 1)[inside].sum())
        if t_open <= t_first <= t_close:
            w["sampled"] += 1
        if n > 1:
            j = np.arange(1, n)
            at = t_first + j / (n - 1.0) * (r["t_done"] - t_first)
            inside = (at >= t_open) & (at <= t_close)
            w["decode_tokens"] += int(inside.sum())
            w["decode_context_sum"] += int((p + j)[inside].sum())
            w["sampled"] += int(inside.sum())
    return w


def pick_checked(rows, count, seed):
    """A sample of the finished requests drawn from the seed, with the
    longest in it."""
    rows = sorted(rows, key=lambda r: r["id"])
    longest = max(rows, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in rows if r is not longest]
    rng = np.random.RandomState(int(seed) & 0xFFFFFFFF)
    take = rng.choice(len(rest), size=min(count, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(take)]


def reference_logits(cell, ref, maker, reqs_by_id, rows, width, numerics):
    """For each of ``numerics`` (``EXACT`` is the reference itself): logits
    over every checked request's prompt and served tokens, one full
    forward pass, a layer at a time. Returns ``{name: (rows, width,
    vocab) logits}``; ``gaps`` reduces them to what is compared."""
    import jax
    import jax.numpy as jnp

    cfg = cell.config
    toks = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        seq = reqs_by_id[r["id"]]["prompt"] + r["tokens"]
        toks[i, :len(seq)] = seq
    out = {}
    top = maker.group(-1)
    for name, num in numerics.items():
        layer = jax.jit(lambda x, p, num=num: ref.layer(x, p, cfg, num))
        x = ref.embed(jnp.asarray(toks), top["embed"])
        for i in range(cfg["num_hidden_layers"]):
            p = {k.split(".", 1)[1]: v for k, v in maker.group(i).items()}
            x = layer(x, p)
            del p
        head = jax.jit(lambda x, norm, w, num=num: ref.logits(
            x, norm, w, cfg, num))
        out[name] = head(x, top["norm"], top["head"])
    return out


def gaps(rows, ref_logits, picked_by=None):
    """The widest gap, over every served token of every checked request,
    by which the token's logit lies below the reference's best at its
    position. With ``picked_by`` (another precision's logits) the token
    judged is the one that precision puts first, not the served one."""
    ref_logits = np.asarray(ref_logits)
    widest, count = 0.0, 0
    for i, r in enumerate(rows):
        p, n = r["prompt_len"], len(r["tokens"])
        at = ref_logits[i, p - 1:p - 1 + n]              # (n, vocab)
        if picked_by is None:
            tok = np.asarray(r["tokens"])
        else:
            tok = np.asarray(picked_by)[i, p - 1:p - 1 + n].argmax(-1)
        gap = at.max(-1) - at[np.arange(n), tok]
        widest = max(widest, float(gap.max()))
        count += n
    return widest, count


def tail(values, q):
    return harness.percentile(values, q) if values else float("nan")


def run(cell, alter=None, control=None):
    """``alter`` (tests): a function applied to each finished row before
    anything reads it, to plant a fault where the answer is produced.
    ``control`` ({name: Numerics}): further precisions to read the gap
    at, each in the reference's place."""
    from mxnet_tpu import serve
    from mxnet_tpu.ops.pallas import decode_attention as da
    from mxnet_tpu.profiler import core as prof

    cfg, tr = cell.config, cell.traffic
    limits = cell.limits
    on_chip = cell.devices[0].platform == "tpu"
    if not on_chip:
        da.use_interpret(True)     # a rehearsal: the kernel is interpreted
    adapter = harness.load_module("adapters", cfg["adapter"])
    ref = harness.load_module("reference", cfg["reference"])
    maker = weights.Maker(ref.param_shapes(cfg), cell.seed,
                          cfg["initializer_range"])
    net = adapter.build(cfg, on_chip)
    harness.load_weights(net, adapter.name_map(cfg), maker)
    harness.say(phase="weights", setup_s=round(harness.now() - cell.t0, 2))

    da.reset_fallbacks()
    sv = cfg["serve"]
    engine = serve.ContinuousEngine(
        net, max_seq=sv["max_seq"], num_slots=sv["num_slots"],
        decode_path=sv["decode_path"], name="chipbench")
    warm = engine.warmup()
    engine.start()
    harness.say(phase="warm", signatures=warm["signatures"],
                prefill_chunk=engine.prefill_chunk,
                kv_pool_bytes=engine.pool.nbytes(),
                setup_s=round(harness.now() - cell.t0, 2))

    seconds = cell.seconds
    traced = min(seconds, tr["trace_seconds"])   # the profiler's part of it
    lead = tr["lead_in_seconds"]
    count = int((lead + seconds + 10) * tr.get("requests_per_second_cap", 16))
    reqs = traffic.requests(tr, cell.seed, cfg["vocab_size"],
                            count + 4 * tr["clients"])
    clients = Clients(engine, reqs, tr["clients"],
                      tr["request_timeout_seconds"])
    tracer = harness.Tracer(cell)
    clients.start()
    time.sleep(lead)
    misses0 = cell.compile_cache.stats()["disk_misses"]
    steps0 = engine.stats()["steps"]
    snap0 = engine.metrics.snapshot()
    tracer.start()
    with harness.span("chipbench.window"):
        t_open = harness.now()
        time.sleep(traced if cell.trace else seconds)
        t_traced = harness.now()
    trace_path = tracer.stop()
    time.sleep(max(0.0, seconds - (harness.now() - t_open)))
    t_close = harness.now()
    setup_s = t_open - cell.t0
    window_s = t_close - t_open
    steps1 = engine.stats()["steps"]
    snap1 = engine.metrics.snapshot()
    itl = engine.metrics.itl_samples()
    queue_ms = list(getattr(engine.metrics, "_queue_ms", ()))
    stats = cell.compile_cache.stats()
    clients.finish(tr["request_timeout_seconds"])
    engine.assert_no_recompiles()
    fallbacks = {"fallback_count": da.fallback_count(),
                 "decode_fallbacks": prof.get_counter(
                     "serve.decode_fallbacks")}
    memory_peak = harness.memory_peak(cell.devices)
    engine.close()
    if any(fallbacks.values()):
        raise AssertionError(f"the decode kernel gave way: {fallbacks}")

    rows = clients.done
    if alter is not None:
        rows = [alter(dict(r)) for r in rows]
    inside = [r for r in rows if t_open <= r["t_done"] <= t_close]
    failed = [f for f in clients.failed
              if f["id"] < 0 or t_open <= f["t_done"] <= t_close]
    done_tokens = sum(len(r["tokens"]) for r in inside)
    out_tokens = tokens_in_window(rows, t_open, t_close)
    per_token = [(r["t_done"] - r["t_sub"]) * 1e3 / len(r["tokens"])
                 for r in inside]
    ttft = [r["ttft_ms"] for r in inside]
    tpot = [((r["t_done"] - r["t_sub"]) * 1e3 - r["ttft_ms"])
            / (len(r["tokens"]) - 1) for r in inside if len(r["tokens"]) > 1]
    n_steps = steps1 - steps0
    n_req = snap1["requests"] - snap0["requests"]
    counters = {
        "compile_cache": stats,
        "compiled_in_window": stats["disk_misses"] - misses0,
        "engine_steps": n_steps,
        "itl_window": itl[-n_steps:] if n_steps > 0 else [],
        "queue_ms_window": queue_ms[-n_req:] if n_req > 0 else [],
        "num_slots": sv["num_slots"], **fallbacks,
    }
    harness.say(phase="requests", columns=["id", "prompt", "tokens",
                                           "submit_s", "ttft_ms", "done_s"],
                rows=[[r["id"], r["prompt_len"], len(r["tokens"]),
                       round(r["t_sub"] - t_open, 4), round(r["ttft_ms"], 3),
                       round(r["t_done"] - t_open, 4)] for r in rows])
    harness.say(phase="window", window_s=window_s, completed=len(inside),
                failed=len(failed), out_tokens=out_tokens,
                tokens_of_completed=done_tokens,
                latency_per_token_ms={q: tail(per_token, q) for q in (50, 90)},
                ttft_ms={q: tail(ttft, q) for q in (50, 90)},
                tpot_ms={q: tail(tpot, q) for q in (50, 90)},
                engine_steps=n_steps, memory_peak_bytes=memory_peak,
                memory=harness.memory_stats(cell.devices[0]),
                errors=[f["error"] for f in failed][:3],
                compiled_in_window=counters["compiled_in_window"])

    # the reference runs once the window has closed, the peak has been
    # read and the engine and its model are freed
    del engine, net, clients.engine
    gc.collect()
    comp = harness.Comparison()
    if inside:
        t_ref = harness.now()
        checked = pick_checked(inside, tr["checked_requests"], cell.seed)
        width = tr["prompt_tokens"]["high"] + tr["output_tokens"]["high"]
        numerics = {"reference": ref.EXACT, **(control or {})}
        logits = reference_logits(cell, ref, maker,
                                  {r["id"]: r for r in reqs}, checked,
                                  width, numerics)
        widest, compared = gaps(checked, logits["reference"])
        comp.add("served_token_logit_gap_max", widest,
                 limits["served_token_logit_gap_max"])
        extra = {name: gaps(checked, logits["reference"], logits[name])[0]
                 for name in (control or {})}
        harness.say(phase="reference", requests=len(checked),
                    tokens_compared=compared, control_gaps=extra,
                    reference_s=round(harness.now() - t_ref, 2))
    else:
        extra = {}

    record = {
        "attempted": len(inside) + len(failed), "failed": len(failed),
        "compared": comp, "memory_peak_bytes": memory_peak,
        "counters": counters, "control_gaps": extra,
        "end_to_end": {"serve_out_tok_s": out_tokens / window_s,
                       "latency_per_token_p50_ms": tail(per_token, 50),
                       "setup_s": setup_s},
        "window_s": window_s,
        "traced_work": work_in_window(rows, t_open, t_traced),
        "ttft_ms": ttft, "tpot_ms": tpot,
        "config": cfg, "traffic": tr, "chips": cell.chips,
        "peaks": cell.peaks,
        "kv_itemsize": np.dtype(cfg["precision"]).itemsize,
    }
    if cell.trace:
        import trace_reduce

        record["trace"] = trace_reduce.reduce_file(
            trace_path, cell.chips, uncovered="engine_loop_unannotated",
            ignore=("chipbench.wait",))
    return record
