#!/usr/bin/env python
"""Tier-1 serving smoke (tools/run_tier1.sh): spin up an
``InferenceSession`` behind a ``DynamicBatcher``, push 32 concurrent
client requests, and assert the serving SLO surface end to end:

* every request completes with the right answer (vs an unbatched
  reference forward),
* p99 whole-request latency stays under ``SERVE_SMOKE_P99_MS``
  (default 5000 ms — generous for CPU CI, tight enough to catch a
  recompile storm or a wedged flusher),
* zero XLA recompiles after warmup (``assert_no_recompiles``),
* the batcher shuts down cleanly (flusher thread joins, late submits
  are fast-rejected with 503).

With ``--trace-out PATH`` (the ``TIER1_TRACE=1`` pass) the same smoke
runs with request tracing + the flight recorder on, then additionally:

* injects fatal ``serve:execute`` faults until the session breaker
  opens and asserts a non-empty flight-recorder dump whose ring names
  the failing site,
* dumps the chrome trace to PATH for ``tools/trace_check.py``
  (``--expect-lane`` asserts one connected per-request lane there).

With ``--decode-path {baseline,pallas,int8,spec}`` (the
``TIER1_DECODE=1`` pass) the smoke instead exercises one decode rung of
the llama generation stack under concurrent clients:

* 8 threads drive ``generate()`` on a shared Generator (spec =
  SpeculativeGenerator over a 1-layer draft); every thread must get the
  same greedy continuation as an unthreaded reference call,
* zero recompiles across the whole run (``assert_no_recompiles``),
* 503 taxonomy: ``drain()`` makes the next generate fast-reject with
  ``ServiceUnavailable``; ``resume()`` serves again,
* 504 taxonomy: already-passed deadlines retire every row between
  decode steps and land in ``info["deadline_expired"]`` plus the
  ``deadline_expired["decode"]`` metric.

With ``--slo`` (the ``TIER1_SLO=1`` pass) the same healthy 32-client
run executes with a declarative SLO monitor attached to the session
metrics (itl/ttft p99, goodput, error-rate objectives at generous CI
targets): after the run NO objective may be burning, the monitor state
must be ``ok``, and the flight recorder must have produced zero
``slo_burn`` dumps — the guard's false-positive contract on a healthy
service.

With ``--multistep`` (the ``TIER1_MULTISTEP=1`` pass) the smoke drives
the PR-19 device-side multi-step decode loop on a ``ContinuousEngine``:

* 8 concurrent clients on an 8-step super-step engine must get greedy
  output token-identical to the classic one-visit-per-token engine,
* exactly two compiled signatures (chunked prefill + the super-step)
  and zero recompiles across every admit/retire cycle,
* a deadline that expires mid-stream settles as 504
  (``DeadlineExceeded`` with partial tokens) within a bounded wall —
  retirement latency is one super-step, not one request.

With ``--prefix`` (the ``TIER1_PREFIX=1`` pass) the smoke drives the
PR-14 "never redo prior work" stack:

* 8 clients share a 20-token system prompt on a ``ContinuousEngine``
  with the radix prefix cache on: outputs must be token-identical to
  the cache-off run, ``prefix_hit_rate > 0``, zero recompiles, and
  every non-free pool page accounted for by the trie after retirement,
* two ``--prefix-child`` subprocesses warm the same
  ``MXNET_COMPILE_CACHE_DIR``: identical stable signature keys +
  greedy tokens, and the second must replay the lattice entirely from
  disk (``disk_hits > 0, disk_misses == 0``).

Exit status 0 on pass; nonzero with a one-line reason otherwise.
"""
import os
import sys
import threading

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _device():
    """Where the smokes put their models: the chip when JAX sees one,
    else the host — observed, not a flag. Serving follows the model."""
    import mxnet_tpu as mx

    return mx.tpu() if mx.num_tpus() else mx.cpu()


def _trace_epilogue(sess, batcher_cls, runner, x, trace_out):
    """Injected-fault forensics + trace dump (the --trace-out half)."""
    import json

    from mxnet_tpu import profiler
    from mxnet_tpu.profiler import recorder
    from mxnet_tpu.resilience import faults

    faults.install_plan({"rules": [
        {"site": "serve:execute", "kind": "fatal", "times": 8}]})
    try:
        with batcher_cls(runner, max_batch_size=8, timeout_ms=2.0,
                         max_queue=64, metrics=sess.metrics,
                         name="smoke-fault") as fb:
            # sequential submits: each is its own failing batch, so the
            # session breaker sees consecutive failures and trips open
            for _ in range(5):
                try:
                    fb.submit(x).result(timeout=30)
                except Exception:  # noqa: BLE001 (the injected fault)
                    pass
    finally:
        faults.clear_plan()
    dump_path = recorder.last_dump_path()
    if not dump_path or not os.path.exists(dump_path):
        print("SERVE_SMOKE=FAIL injected serve:execute fault left no "
              "flight-recorder dump")
        return 1
    doc = json.load(open(dump_path))
    ring_names = {e.get("name") for e in doc.get("ring", [])}
    if "serve:execute" not in ring_names:
        print(f"SERVE_SMOKE=FAIL flight-recorder dump {dump_path} does "
              f"not name the failing site (ring: {sorted(ring_names)})")
        return 1
    profiler.set_state("stop")
    profiler.core.dump(trace_out)
    print(f"SERVE_SMOKE_TRACE=PASS trace={trace_out} "
          f"flightrec={dump_path} reason={doc.get('reason')}")
    return 0


def main():
    if "--prefix-child" in sys.argv:
        cache_dir = sys.argv[sys.argv.index("--prefix-child") + 1]
        return _run_prefix_child(cache_dir)
    if "--prefix" in sys.argv:
        return _run_prefix()
    if "--multistep" in sys.argv:
        return _run_multistep()
    if "--decode-path" in sys.argv:
        path = sys.argv[sys.argv.index("--decode-path") + 1]
        return _run_decode(path)
    trace_out = None
    if "--trace-out" in sys.argv:
        trace_out = sys.argv[sys.argv.index("--trace-out") + 1]
        os.environ.setdefault("MXNET_TRACE", "1")
        os.environ.setdefault("MXNET_FLIGHT_RECORDER", "1")
    if "--slo" in sys.argv:
        os.environ.setdefault("MXNET_FLIGHT_RECORDER", "1")
        return _run(trace_out, slo=True)
    return _run(trace_out)


def _run_prefix_child(cache_dir):
    """Subprocess half of --prefix: enable the persistent compile cache
    BEFORE any build, warm a ContinuousEngine over the standard tiny
    lattice, decode one request, and print a greppable JSON line with
    the disk hit/miss counters, the stable signature keys, and the
    tokens — the parent asserts process 2 compiles nothing new and both
    processes agree on keys + output."""
    import json

    import mxnet_tpu as mx
    from mxnet_tpu import cachedop, compile_cache
    from mxnet_tpu.models.llama import get_llama
    from mxnet_tpu.serve import ContinuousEngine

    compile_cache.enable(cache_dir)
    mx.random.seed(0)
    model = get_llama("llama_tiny_test")
    model.initialize(ctx=_device())
    eng = ContinuousEngine(model, max_seq=64, num_slots=4, page_size=8,
                           prefill_chunk=8, decode_path="baseline",
                           name="smoke_prefix_child")
    eng.start()
    try:
        out = eng.submit([5, 9, 2, 4], max_new_tokens=6).result(60)
    finally:
        eng.close()
    keys = sorted({k for op in list(cachedop._instances)
                   for k in op.signature_keys()})
    print("SERVE_SMOKE_PREFIX_CHILD=" + json.dumps({
        "disk_hits": compile_cache.disk_hits(),
        "disk_misses": compile_cache.disk_misses(),
        "keys": keys, "tokens": out["tokens"]}), flush=True)
    return 0


def _run_prefix():
    import json
    import subprocess
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu.models.llama import get_llama
    from mxnet_tpu.serve import ContinuousEngine

    mx.random.seed(0)
    model = get_llama("llama_tiny_test")
    model.initialize(ctx=_device())

    system = list(range(3, 23))  # 20-token shared system prompt
    prompts = [system + [30 + i, 40 + i, 50 + i] for i in range(8)]

    def run_engine(prefix_on):
        eng = ContinuousEngine(model, max_seq=64, num_slots=4, page_size=8,
                               prefill_chunk=8, decode_path="baseline",
                               prefix_cache=prefix_on,
                               name=f"smoke_prefix_{int(bool(prefix_on))}")
        eng.start()
        try:
            # first client retires (donating its prefix to the trie)
            # before the concurrent wave arrives
            first = eng.submit(prompts[0], max_new_tokens=8).result(60)
            futs = [eng.submit(p, max_new_tokens=8) for p in prompts[1:]]
            outs = [first["tokens"]] + [f.result(60)["tokens"]
                                        for f in futs]
            eng.assert_no_recompiles()
            return outs, eng.metrics.snapshot(), eng.stats()
        finally:
            eng.close()

    ref, _, _ = run_engine(False)
    got, snap, stats = run_engine(True)
    if got != ref:
        print(f"SERVE_SMOKE_PREFIX=FAIL prefix-cache-on outputs diverged "
              f"from cache-off: {got} != {ref}")
        return 1
    if not snap["prefix_hit_rate"] > 0:
        print(f"SERVE_SMOKE_PREFIX=FAIL shared system prompt produced no "
              f"trie hits (snapshot={snap})")
        return 1
    if stats["pool"]["pages_used"] != stats["prefix"]["pages_held"]:
        print(f"SERVE_SMOKE_PREFIX=FAIL retired engine leaks pages "
              f"beyond the trie: pool={stats['pool']} "
              f"prefix={stats['prefix']}")
        return 1

    # disk half: two fresh processes over one cache dir — the second
    # must warm entirely from disk (no new compiles) with identical
    # stable signature keys and identical greedy output
    child = [sys.executable, os.path.abspath(__file__)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # a cold/warm measurement over its own fresh directory: a cache placed
    # from outside would take the explicit path's say away
    # (compile_cache.enable) and hand the "cold" child a warm cache
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    docs = []
    with tempfile.TemporaryDirectory() as d:
        for i in (1, 2):
            proc = subprocess.run(
                child + ["--prefix-child", d], env=env,
                capture_output=True, text=True, timeout=600)
            line = [ln for ln in proc.stdout.splitlines()
                    if ln.startswith("SERVE_SMOKE_PREFIX_CHILD=")]
            if proc.returncode != 0 or not line:
                print(f"SERVE_SMOKE_PREFIX=FAIL child {i} rc="
                      f"{proc.returncode}\n{proc.stdout}\n{proc.stderr}")
                return 1
            docs.append(json.loads(
                line[0].split("=", 1)[1]))
    p1, p2 = docs
    if p1["keys"] != p2["keys"] or not p1["keys"]:
        print(f"SERVE_SMOKE_PREFIX=FAIL stable signature keys differ "
              f"across processes: {p1['keys']} != {p2['keys']}")
        return 1
    if p1["tokens"] != p2["tokens"]:
        print(f"SERVE_SMOKE_PREFIX=FAIL disk-warmed process output "
              f"diverged: {p2['tokens']} != {p1['tokens']}")
        return 1
    if p1["disk_misses"] == 0:
        print(f"SERVE_SMOKE_PREFIX=FAIL cold process reported no disk "
              f"misses (doc={p1})")
        return 1
    if not (p2["disk_hits"] > 0 and p2["disk_misses"] == 0):
        print(f"SERVE_SMOKE_PREFIX=FAIL warm process did not replay the "
              f"lattice from disk: hits={p2['disk_hits']} "
              f"misses={p2['disk_misses']}")
        return 1
    print(f"SERVE_SMOKE_PREFIX=PASS clients={len(prompts)} "
          f"hit_rate={snap['prefix_hit_rate']:.3f} "
          f"tokens_skipped={snap['prefix_tokens_skipped']} "
          f"signatures={len(p1['keys'])} "
          f"cold_disk_misses={p1['disk_misses']} "
          f"warm_disk_hits={p2['disk_hits']}")
    return 0


def _run_multistep():
    import time

    import mxnet_tpu as mx  # noqa: F401  (framework init)
    from mxnet_tpu.models.llama import get_llama
    from mxnet_tpu.serve import ContinuousEngine, DeadlineExceeded

    mx.random.seed(0)
    model = get_llama("llama_tiny_test")
    model.initialize(ctx=_device())
    prompts = [[5 + i, 9, 2, (3 * i) % 11 + 1] for i in range(8)]

    # reference: classic one-visit-per-token engine, sequential requests
    ref_eng = ContinuousEngine(model, max_seq=64, num_slots=4, page_size=8,
                               prefill_chunk=8, decode_path="baseline",
                               multistep=False, name="smoke_ms_ref")
    ref_eng.start()
    try:
        refs = [ref_eng.submit(p, max_new_tokens=12).result(120)["tokens"]
                for p in prompts]
    finally:
        ref_eng.close()

    eng = ContinuousEngine(model, max_seq=64, num_slots=4, page_size=8,
                           prefill_chunk=8, decode_path="baseline",
                           multistep=True, decode_steps=8, name="smoke_ms")
    eng.start()
    try:
        outs = [None] * len(prompts)
        errors = []

        def client(i):
            try:
                outs[i] = eng.submit(
                    prompts[i], max_new_tokens=12).result(120)["tokens"]
            except Exception as exc:  # noqa: BLE001
                errors.append((i, exc))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        if errors:
            i, exc = errors[0]
            print(f"SERVE_SMOKE_MULTISTEP=FAIL client {i}: "
                  f"{type(exc).__name__}: {exc}")
            return 1
        for i, o in enumerate(outs):
            if o != refs[i]:
                print(f"SERVE_SMOKE_MULTISTEP=FAIL client {i} diverged "
                      f"from the classic engine: {o} != {refs[i]}")
                return 1
        try:
            eng.assert_no_recompiles()
        except Exception as exc:  # noqa: BLE001
            print(f"SERVE_SMOKE_MULTISTEP=FAIL {exc}")
            return 1
        n_super = eng._msession.signature_count()
        if n_super != 1:
            print(f"SERVE_SMOKE_MULTISTEP=FAIL expected exactly one "
                  f"super-step signature, got {n_super}")
            return 1

        # 504: a deadline that expires mid-stream settles as
        # DeadlineExceeded with partial tokens, and retirement is
        # bounded by one super-step -- not by the request's remaining
        # budget.  Budget half of a measured 12-token wall so expiry
        # lands mid-decode on any host speed.
        t0 = time.monotonic()
        eng.submit(prompts[0], max_new_tokens=12).result(120)
        t12 = time.monotonic() - t0
        budget_ms = max(20.0, t12 * 1e3 * 0.5)
        t0 = time.monotonic()
        fut = eng.submit(prompts[1], max_new_tokens=48,
                         deadline_ms=budget_ms)
        try:
            fut.result(120)
            print("SERVE_SMOKE_MULTISTEP=FAIL mid-stream deadline did "
                  "not settle as 504")
            return 1
        except DeadlineExceeded as exc:
            settled_s = time.monotonic() - t0
            partial = list(getattr(exc, "partial", []))
        if len(partial) >= 48:
            print(f"SERVE_SMOKE_MULTISTEP=FAIL expired request ran to "
                  f"completion ({len(partial)} tokens)")
            return 1
        slack_s = budget_ms / 1e3 + max(2.0, 2.0 * t12)
        if settled_s > slack_s:
            print(f"SERVE_SMOKE_MULTISTEP=FAIL 504 settled {settled_s:.2f}s "
                  f"after submit (> {slack_s:.2f}s): retirement not "
                  f"bounded by one super-step")
            return 1
        snap = eng.metrics.snapshot()
        if not snap["deadline_expired"].get("decode"):
            print(f"SERVE_SMOKE_MULTISTEP=FAIL no decode-stage "
                  f"deadline_expired metric "
                  f"({dict(snap['deadline_expired'])})")
            return 1
        stats = eng.stats()
        print(f"SERVE_SMOKE_MULTISTEP=PASS clients={len(prompts)} "
              f"decode_steps={stats['decode_steps']} "
              f"super_signatures={n_super} "
              f"partial_504={len(partial)} "
              f"deadline_expired={dict(snap['deadline_expired'])}")
        return 0
    finally:
        eng.close()


def _run_decode(path):
    import time

    import mxnet_tpu as mx  # noqa: F401  (framework init)
    from mxnet_tpu.models.llama import get_llama
    from mxnet_tpu.serve import (Generator, ServiceUnavailable,
                                 SpeculativeGenerator)

    mx.random.seed(0)
    model = get_llama("llama_tiny_test")
    model.initialize(ctx=_device())
    if path == "spec":
        draft = get_llama("llama_tiny_test", num_layers=1)
        draft.initialize(ctx=_device())
        gen = SpeculativeGenerator(model, draft, k=2, max_seq=48,
                                   batch_buckets=(2,), prompt_buckets=(8,),
                                   name="smoke_spec")
        sess = gen.target.session
    else:
        gen = Generator(model, max_seq=48, batch_buckets=(2,),
                        prompt_buckets=(8,), name=f"smoke_{path}",
                        decode_path=path)
        sess = gen.session
    gen.warmup()
    prompts = [[5, 9, 2], [7, 3, 3, 1]]
    ref, _ = gen.generate(prompts, max_new_tokens=8)

    n_clients = 8
    outs = [None] * n_clients
    errors = []

    def client(i):
        try:
            outs[i], _ = gen.generate(prompts, max_new_tokens=8)
        except Exception as exc:  # noqa: BLE001
            errors.append((i, exc))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    if errors:
        i, exc = errors[0]
        print(f"SERVE_SMOKE_DECODE=FAIL path={path} client {i}: "
              f"{type(exc).__name__}: {exc}")
        return 1
    for i, o in enumerate(outs):
        if o != ref:
            print(f"SERVE_SMOKE_DECODE=FAIL path={path} client {i} "
                  f"diverged from the unthreaded reference: {o} != {ref}")
            return 1
    try:
        gen.assert_no_recompiles()
    except Exception as exc:  # noqa: BLE001
        print(f"SERVE_SMOKE_DECODE=FAIL path={path} {exc}")
        return 1

    # 503 taxonomy: a drained session fast-rejects, resume() reopens
    sess.drain()
    try:
        gen.generate(prompts, max_new_tokens=4)
        print(f"SERVE_SMOKE_DECODE=FAIL path={path} drained session "
              f"accepted a generate()")
        return 1
    except ServiceUnavailable:
        pass
    finally:
        sess.resume()
    again, _ = gen.generate(prompts, max_new_tokens=8)
    if again != ref:
        print(f"SERVE_SMOKE_DECODE=FAIL path={path} post-resume output "
              f"diverged: {again} != {ref}")
        return 1

    # 504 taxonomy: already-passed deadlines retire every row and count
    # as decode-stage deadline_expired
    _, info = gen.generate(prompts, max_new_tokens=8,
                           deadlines=time.monotonic() - 1.0)
    expired = info["deadline_expired"]
    snap = gen.metrics.snapshot()
    if sorted(expired) != [0, 1] or not snap["deadline_expired"].get(
            "decode"):
        print(f"SERVE_SMOKE_DECODE=FAIL path={path} past deadlines did "
              f"not expire rows (info={expired}, "
              f"metric={snap['deadline_expired']})")
        return 1
    print(f"SERVE_SMOKE_DECODE=PASS path={path} "
          f"decode_path={snap['decode_path']} clients={n_clients} "
          f"kv_cache_bytes={snap['kv_cache_bytes']} "
          f"deadline_expired={dict(snap['deadline_expired'])}")
    return 0


def _run(trace_out=None, slo=False):
    import mxnet_tpu as mx  # noqa: F401  (framework init)
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu import numpy as mnp
    from mxnet_tpu.serve import (DynamicBatcher, InferenceSession,
                                 ServiceUnavailable)

    if trace_out is not None:
        from mxnet_tpu import profiler
        profiler.set_state("run")

    # MXNET_METRICS_PORT=<p> started the /metrics endpoint at import
    # (=0 binds an ephemeral port); surface where it actually landed so
    # the harness driving this smoke can scrape it.
    from mxnet_tpu.profiler import export as _export
    mport = _export.server_port()
    if mport is not None:
        print(f"SERVE_SMOKE metrics endpoint: "
              f"http://127.0.0.1:{mport}/metrics", flush=True)

    p99_bound_ms = float(os.environ.get("SERVE_SMOKE_P99_MS", "5000"))
    n_clients = 32

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu"))
    net.add(gluon.nn.Dense(8))
    net.initialize(ctx=_device())

    sess = InferenceSession(net, batch_buckets=(1, 2, 4, 8), name="smoke")
    monitor = None
    if slo:
        from mxnet_tpu.profiler import recorder as _recorder
        from mxnet_tpu.profiler.slo import SLO, SLOMonitor
        _recorder.reset()
        monitor = SLOMonitor("smoke", [
            SLO("itl_p99_ms", 500.0),
            SLO("ttft_p99_ms", 2000.0),
            SLO("goodput", 0.95),
            SLO("error_rate", 0.05),
        ])
        monitor.attach(sess.metrics)
    sess.warmup(np.zeros((1, 16), np.float32))

    def runner(payloads):
        out = sess.predict(np.stack(payloads)).asnumpy()
        return [out[i] for i in range(len(payloads))]

    rng = np.random.RandomState(7)
    xs = [rng.randn(16).astype(np.float32) for _ in range(n_clients)]
    results = [None] * n_clients
    errors = []

    with DynamicBatcher(runner, max_batch_size=8, timeout_ms=5.0,
                        max_queue=64, metrics=sess.metrics,
                        name="smoke") as batcher:
        def client(i):
            try:
                results[i] = batcher.submit(xs[i]).result(timeout=60)
            except Exception as exc:  # noqa: BLE001
                errors.append((i, exc))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(90)
    # context exit = clean shutdown; verify the flusher actually died
    if batcher._thread.is_alive():
        print("SERVE_SMOKE=FAIL flusher thread survived close()")
        return 1
    try:
        batcher.submit(xs[0])
        print("SERVE_SMOKE=FAIL late submit after close() was accepted")
        return 1
    except ServiceUnavailable:
        pass

    if errors:
        i, exc = errors[0]
        print(f"SERVE_SMOKE=FAIL request {i}: {type(exc).__name__}: {exc}")
        return 1
    with autograd.predict_mode():
        ref = net(mnp.array(np.stack(xs))).asnumpy()
    got = np.stack(results)
    if not np.allclose(got, ref, rtol=1e-5, atol=1e-6):
        print(f"SERVE_SMOKE=FAIL wrong results "
              f"(maxdiff {np.abs(got - ref).max():.3g})")
        return 1
    try:
        sess.assert_no_recompiles()
    except Exception as exc:  # noqa: BLE001
        print(f"SERVE_SMOKE=FAIL {exc}")
        return 1
    snap = sess.metrics.snapshot()
    if snap["p99_ms"] > p99_bound_ms:
        print(f"SERVE_SMOKE=FAIL p99 {snap['p99_ms']:.1f}ms "
              f"> bound {p99_bound_ms}ms")
        return 1
    print(f"SERVE_SMOKE=PASS requests={snap['requests']} "
          f"p50={snap['p50_ms']:.1f}ms p99={snap['p99_ms']:.1f}ms "
          f"occupancy={snap['batch_occupancy']:.2f} "
          f"signatures={sess.signature_count()} "
          f"serve_hits={sess.cache_stats()['serve_hits']}")
    if monitor is not None:
        from mxnet_tpu.profiler import recorder as _recorder
        rows = monitor.evaluate()
        burning = [r["metric"] for r in rows if r["burning"]]
        health = monitor.health()
        if burning or health["state"] != "ok" or monitor.burns > 0:
            print(f"SLO_SMOKE=FAIL healthy run tripped the burn guard: "
                  f"burning={burning} health={health} rows={rows}")
            return 1
        if _recorder.dump_count() > 0:
            print(f"SLO_SMOKE=FAIL healthy run produced "
                  f"{_recorder.dump_count()} flight-recorder dump(s): "
                  f"{_recorder.last_dump_path()}")
            return 1
        print(f"SLO_SMOKE=PASS objectives={len(rows)} state="
              f"{health['state']} burns={monitor.burns} "
              f"events={[r['events_slow'] for r in rows]}")
    if trace_out is not None:
        return _trace_epilogue(sess, DynamicBatcher, runner, xs[0],
                               trace_out)
    return 0


if __name__ == "__main__":
    rc = main()
    try:
        from mxnet_tpu.resilience.lockdep import smoke_gate
    except ImportError:
        pass
    else:
        rc = smoke_gate(rc)
    sys.exit(rc)
