"""`InferenceSession`: bucketed AOT-compiled serving executor.

The serving analog of mxnet-model-server's worker atop ``Module.predict``:
a :class:`~mxnet_tpu.cachedop.CachedOpThreadSafe` wraps the block, and the
session *pads every call onto a small lattice of (batch, seq) buckets* so
steady-state traffic only ever replays already-compiled executables — the
recompile storm that per-request shapes would cause is structurally
impossible, and ``assert_no_recompiles`` turns that into a testable
invariant via ``cachedop.signature_count()``.

Resilience wiring (all existing subsystems, reused):

* cold-bucket compiles go through ``resilience.retry.call_with_retry``
  (the CachedOp build path) — a transient XLA compile failure backs off
  and retries instead of failing the request;
* a :class:`~mxnet_tpu.resilience.retry.CircuitBreaker` guards the
  session: repeated execution failures trip it open and requests
  fast-reject with a 503-style :class:`ServiceUnavailable` until a
  half-open probe heals it;
* ``MXNET_SERVE_TIMEOUT_MS`` bounds each execution with the resilience
  watchdog — a hung executable becomes a fast 503 instead of wedging the
  serving thread;
* the ``serve:execute`` fault site lets the fault-injection harness fail
  individual executions deterministically.
"""
from __future__ import annotations

import functools
import threading
import time

import numpy as _onp

from ..base import MXNetError
from ..cachedop import CachedOpThreadSafe
from ..device import current_context
from ..profiler import core as _prof
from ..profiler import export as _export
from ..profiler import trace as _trace
from ..resilience import faults as _faults
from ..resilience.retry import CircuitBreaker, CollectiveTimeoutError, \
    run_with_watchdog
from .metrics import ServeMetrics


class ServeError(MXNetError):
    """Base class for serving-path errors; carries an HTTP-style status."""

    status = 500
    #: back-off hint (ms) for overload-shaped rejects: when set, the
    #: server expects capacity to free up after roughly this long (the
    #: batcher derives it from queue depth x its drain rate), so a client
    #: or router can back off intelligently instead of hammering.
    #: ``None`` on structural failures a retry won't fix (shutdown,
    #: breaker open) — the Router uses exactly this distinction to tell
    #: "loaded replica, pass the 503 through" from "broken replica,
    #: quarantine it".
    retry_after_ms = None


class ServiceUnavailable(ServeError):
    """Fast-reject: queue full, breaker open, or execution timed out (503)."""

    status = 503


class PoolExhausted(ServiceUnavailable):
    """The paged KV block pool has no free pages for a new admission
    (503-shaped: capacity frees as in-flight requests retire and their
    pages recycle). Raised by :class:`~mxnet_tpu.serve.kv_blocks.
    PagedKVPool`; the continuous-batching scheduler catches it at the
    admission boundary and requeues the request — the pool being full is
    backpressure, never a crash."""


class DeadlineExceeded(ServeError):
    """The request's deadline passed before (or while) it was served (504).

    Distinct from :class:`ServiceUnavailable` on purpose: a 503 means the
    *server* shed or failed the request and a retry may succeed; a 504
    means the *request's own time budget* ran out — the client has already
    moved on and a silent late completion would be worse than the error.
    Raised at every stage boundary (admission, queue sweep, post-execute
    settle, between decode steps) so an expired request never burns more
    server time than the stage it is already inside.
    """

    status = 504


def block_context(block):
    """The context a block's parameters live on — where a server built
    over it puts its rings, page pools and inputs. Serving takes the
    device from the model it is given rather than from the caller's
    (thread-local) default context, which its worker threads never see."""
    try:
        return next(iter(block.collect_params().values())).list_ctx()[0]
    except (StopIteration, MXNetError):  # no parameters / not initialized
        return current_context()


def on_block_context(method):
    """Run a serving entry point under ``self.ctx`` (see
    :func:`block_context`), so every array it creates lands there."""
    @functools.wraps(method)
    def wrapped(self, *args, **kwargs):
        with self.ctx:
            return method(self, *args, **kwargs)
    return wrapped


def pick_bucket(n, buckets):
    """Smallest bucket >= n; raises :class:`ServeError` when n overflows
    the largest bucket (the request can never be served — reject it
    loudly rather than silently truncating)."""
    for b in buckets:
        if n <= b:
            return b
    raise ServeError(
        f"request size {n} exceeds the largest configured bucket "
        f"{buckets[-1]}; raise the session's bucket lattice or shard the "
        "request")


class InferenceSession:
    """Bucketed, breaker-guarded, AOT-compiled executor for one block.

    Parameters
    ----------
    block : HybridBlock
        The model (parameters must be initialized).
    batch_buckets : sequence of int
        Ascending batch-size lattice; every call's leading axis pads up to
        one of these.
    seq_buckets : sequence of int, optional
        Ascending sequence-length lattice for axis 1 of 2-D+ inputs
        (token arrays). ``None`` disables seq padding.
    pad_value : scalar
        Fill for padded sequence positions (token id 0 by default).
    """

    def __init__(self, block, batch_buckets=(1, 2, 4, 8), seq_buckets=None,
                 pad_value=0, name=None):
        from .. import config

        self.block = block
        self.ctx = block_context(block)
        self.batch_buckets = tuple(sorted(int(b) for b in batch_buckets))
        self.seq_buckets = (tuple(sorted(int(s) for s in seq_buckets))
                            if seq_buckets else None)
        self.pad_value = pad_value
        self.name = name or type(block).__name__
        self._op = CachedOpThreadSafe(block)
        self.metrics = ServeMetrics(self.name)
        self.breaker = CircuitBreaker(
            failure_threshold=config.get("MXNET_SERVE_BREAKER_THRESHOLD"),
            cooldown_calls=config.get("MXNET_SERVE_BREAKER_COOLDOWN"),
            name=f"serve:{self.name}")
        self._warm_signatures = None
        self._shapes_ready = False
        self._lock = threading.Lock()
        # drain/swap lifecycle: _quiesce guards the in-flight count;
        # drain() flips _draining and waits for it to reach zero. The
        # thread-local bypass lets swap()'s own warmup run while external
        # admission is still stopped.
        self._quiesce = threading.Condition()
        self._inflight = 0
        self._draining = False
        self._bypass = threading.local()
        # unified export surface: /healthz wraps this session's probes
        _export.register_health_provider(self)

    # -- raw protected execution -------------------------------------------
    def _timeout_s(self):
        from .. import config

        return config.get("MXNET_SERVE_TIMEOUT_MS") / 1e3

    @on_block_context
    def run(self, *args):
        """Execute one already-bucketed call under the full protection
        stack (breaker -> fault site -> watchdog -> cachedop). Raises
        :class:`ServiceUnavailable` on breaker-open or timeout; any other
        failure propagates unchanged (the batcher maps it onto the
        requests of the affected batch)."""
        from .. import autograd

        with self._quiesce:
            if self._draining and not getattr(self._bypass, "on", False):
                self.metrics.observe_reject()
                raise ServiceUnavailable(
                    f"serve session {self.name!r} is draining; no new "
                    "work admitted until swap/resume")
            self._inflight += 1
        try:
            if not self._shapes_ready:
                # complete any deferred (shape-inferred) parameter init
                # with one eager pass — CachedOp keys on param shapes,
                # which don't exist yet for in_units=0 Dense until a
                # first forward. Inside the admission gate + in-flight
                # count on purpose: this pass executes the model, and a
                # concurrent swap() must not see "quiesced" while it runs
                with self._lock:
                    if not self._shapes_ready:
                        params = self.block.collect_params().values()
                        if any(getattr(p, "_deferred_init", None)
                               is not None and p._data is None
                               for p in params):
                            with autograd.predict_mode():
                                self.block(*args)
                        self._shapes_ready = True
            if not self.breaker.allow():
                self.metrics.observe_reject()
                raise ServiceUnavailable(
                    f"serve session {self.name!r}: circuit breaker is "
                    f"{self.breaker.state} after repeated execution "
                    "failures; retry after cooldown")
            self._op.begin_serve_call()
            t0 = time.perf_counter()
            try:
                def body():
                    # fault site INSIDE the watchdog window: an injected
                    # delay models a hung execution and must trip the
                    # timeout
                    _faults.fault_point("serve:execute",
                                        {"session": self.name})
                    # the watchdog runs this on its own thread: re-enter
                    # the context for what the trace creates
                    with self.ctx, autograd.predict_mode():
                        return self._op(*args)

                # ambient-trace span: when the batcher activated a
                # request trace on this thread, the session execution
                # shows up inside that request's lane
                with _trace.span(f"serve::session_run({self.name})"):
                    out = run_with_watchdog(body, self._timeout_s(),
                                            site=f"serve:{self.name}")
            except CollectiveTimeoutError as exc:
                self.breaker.record_failure()
                raise ServiceUnavailable(
                    f"serve session {self.name!r}: execution exceeded "
                    f"MXNET_SERVE_TIMEOUT_MS ({exc})") from exc
            except Exception:
                self.breaker.record_failure()
                raise
            self.breaker.record_success()
            exec_ms = (time.perf_counter() - t0) * 1e3
            if self._op.call_was_warm():
                # warm-path call: every signature it touched was already
                # compiled — the steady-state serving invariant. Tracked
                # per-thread, so a concurrent thread's cold compile can't
                # misattribute this call
                self._op.record_serve_hit()
            if _prof.ENABLED:
                _prof.record_instant(f"serve::execute({self.name})",
                                     "serve",
                                     args={"exec_ms": round(exec_ms, 3)})
            return out
        finally:
            with self._quiesce:
                self._inflight -= 1
                if self._inflight == 0:
                    self._quiesce.notify_all()

    # -- bucketed predict ---------------------------------------------------
    def _pad_input(self, data):
        """Pad a host array onto the bucket lattice. Returns
        (padded_ndarray, real_batch, real_seq)."""
        from .. import numpy as mnp
        from ..ndarray.ndarray import NDArray

        if isinstance(data, NDArray):
            data = data.asnumpy()
        data = _onp.asarray(data)
        b = data.shape[0]
        bb = pick_bucket(b, self.batch_buckets)
        padded = data
        if bb > b:
            # batch rows pad by edge-repeat (a real row: no NaN/denormal
            # surprises in the dead lanes)
            padded = _onp.pad(padded,
                              [(0, bb - b)] + [(0, 0)] * (data.ndim - 1),
                              mode="edge")
        t = None
        if self.seq_buckets is not None and data.ndim > 1:
            t = data.shape[1]
            st = pick_bucket(t, self.seq_buckets)
            if st > t:  # seq positions pad with pad_value
                seq_w = [(0, 0), (0, st - t)] + [(0, 0)] * (data.ndim - 2)
                padded = _onp.pad(padded, seq_w, mode="constant",
                                  constant_values=self.pad_value)
        return mnp.array(padded, ctx=self.ctx), b, t

    def predict(self, data):
        """Serve one request batch: pad onto the bucket lattice, execute,
        slice the outputs back to the real request shape — the batch
        axis always, and the seq axis of any output that preserved the
        padded seq extent (positions past the real length are pad-token
        artifacts, not model output)."""
        padded, b, t = self._pad_input(data)
        st = padded.shape[1] if padded.ndim > 1 else None
        out = self.run(padded)

        def unpad(o):
            o = o[:b]
            if t is not None and t != st and o.ndim >= 2 \
                    and o.shape[1] == st:
                o = o[:, :t]
            return o

        if isinstance(out, (tuple, list)):
            return type(out)(unpad(o) for o in out)
        return unpad(out)

    def __call__(self, data):
        return self.predict(data)

    # -- warmup & recompile accounting --------------------------------------
    def warmup(self, example):
        """Compile every (batch, seq) bucket combination from one example
        input (an array shaped like a single request batch). After this,
        any request within the lattice executes with zero compiles."""
        from ..ndarray.ndarray import NDArray

        if isinstance(example, NDArray):
            example = example.asnumpy()
        example = _onp.asarray(example)
        row = example[:1]
        t0 = time.perf_counter()
        for bb in self.batch_buckets:
            tiled = _onp.repeat(row, bb, axis=0)
            if self.seq_buckets is not None and example.ndim > 1:
                for st in self.seq_buckets:
                    self.predict(_resize_seq(tiled, st, self.pad_value))
            else:
                self.predict(tiled)
        self.freeze_signatures()
        if _prof.ENABLED:
            _prof.record_instant(
                f"serve::warmup({self.name})", "serve",
                args={"signatures": self._op.signature_count(),
                      "wall_s": round(time.perf_counter() - t0, 3)})
        return self._op.signature_count()

    def freeze_signatures(self):
        """Mark the current signature set as the warm set for
        :meth:`assert_no_recompiles`."""
        self._warm_signatures = self._op.signature_count()

    def assert_no_recompiles(self):
        """Raise :class:`ServeError` if any compile happened since
        :meth:`freeze_signatures` / :meth:`warmup` — the steady-state
        serving invariant, checked from ``cachedop.signature_count()``."""
        if self._warm_signatures is None:
            raise ServeError("assert_no_recompiles called before warmup()")
        now = self._op.signature_count()
        if now != self._warm_signatures:
            raise ServeError(
                f"serve session {self.name!r} recompiled after warmup: "
                f"{self._warm_signatures} -> {now} signatures "
                f"(bucket keys: {self._op.bucket_keys()!r})")

    def signature_count(self):
        return self._op.signature_count()

    def cache_stats(self):
        return self._op.cache_stats()

    def stats(self):
        """Combined serving snapshot: metrics + executable cache + breaker
        + watchdog-orphan accounting (abandoned execution bodies that may
        still be running — see resilience.retry.watchdog_orphans)."""
        from ..resilience.retry import watchdog_orphans

        out = self.metrics.snapshot()
        out["cache"] = self.cache_stats()
        out["breaker"] = self.breaker.snapshot()
        out["watchdog_orphans"] = watchdog_orphans()
        return out

    # -- drain / hot swap / health -------------------------------------------
    def drain(self, timeout=30.0):
        """Stop admitting work and wait for every in-flight execution to
        settle. Returns True once quiesced, False on timeout (admission
        stays stopped either way — call :meth:`resume` to reopen, or
        :meth:`swap` which resumes itself). Idempotent."""
        deadline = time.monotonic() + float(timeout)
        with self._quiesce:
            self._draining = True
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._quiesce.wait(remaining)
        if _prof.ENABLED:
            _prof.record_instant(f"serve::drain({self.name})", "serve")
        return True

    def resume(self):
        """Reopen admission after :meth:`drain`."""
        with self._quiesce:
            self._draining = False
            self._quiesce.notify_all()

    def _signature_matches(self, new_block):
        """True when ``new_block``'s parameter lattice (count, shapes,
        dtypes, grad_req, in order) is identical to the serving block's —
        the condition under which the warm executables, which read param
        buffers at call time and key on param signatures, serve the new
        weights without a single recompile."""
        try:
            olds = list(self.block.collect_params().values())
            news = list(new_block.collect_params().values())
            if len(olds) != len(news):
                return False
            for po, pn in zip(olds, news):
                do, dn = po.data(), pn.data()
                if (tuple(do.shape) != tuple(dn.shape)
                        or do.dtype != dn.dtype
                        or po.grad_req != pn.grad_req):
                    return False
            return True
        except Exception:
            # uninitialized / deferred params on either side: no warm path
            return False

    def swap(self, new_block, example=None, timeout=30.0):
        """Hot-swap the served model: drain, switch executables atomically,
        resume. Returns the swap mode.

        * ``"warm"`` — ``new_block`` has the same parameter signature as
          the serving block: its weights are transplanted into the live
          parameter slots, so every already-compiled bucket executable
          (which reads param buffers per call) keeps serving —
          :meth:`assert_no_recompiles` still holds afterwards.
        * ``"cold"`` — different architecture/shapes: a fresh CachedOp
          replaces the old one; if ``example`` is given the full bucket
          lattice is re-warmed (through the internal admission bypass)
          before traffic resumes, and the new signature set is frozen.

        Raises :class:`ServiceUnavailable` if the drain times out —
        admission is resumed so the old model keeps serving."""
        from .. import autograd
        from .. import numpy as mnp

        t0 = time.perf_counter()
        if not self.drain(timeout):
            self.resume()
            raise ServiceUnavailable(
                f"serve session {self.name!r}: swap aborted — in-flight "
                f"work did not settle within {timeout}s; still serving "
                "the old model")
        try:
            if example is not None:
                # complete any deferred (shape-inferred) init on the
                # incoming block with one eager pass, so the signature
                # match sees real shapes and a same-architecture model
                # takes the warm path
                params = new_block.collect_params().values()
                if any(getattr(p, "_deferred_init", None) is not None
                       and p._data is None for p in params):
                    with autograd.predict_mode():
                        new_block(mnp.array(_onp.asarray(example),
                                            ctx=block_context(new_block)))
            if self._signature_matches(new_block):
                mode = "warm"
                olds = list(self.block.collect_params().values())
                news = list(new_block.collect_params().values())
                for po, pn in zip(olds, news):
                    po.set_data(pn.data())
            else:
                mode = "cold"
                self.block = new_block
                self.ctx = block_context(new_block)
                self._op = CachedOpThreadSafe(new_block)
                self._warm_signatures = None
                self._shapes_ready = False
                if example is not None:
                    self._bypass.on = True
                    try:
                        self.warmup(example)
                    finally:
                        self._bypass.on = False
        finally:
            self.resume()
        self.metrics.observe_swap(mode, time.perf_counter() - t0)
        return mode

    def health(self):
        """Liveness probe payload: lifecycle state, breaker, in-flight
        count, error rate over the metrics window, warm flag, watchdog
        orphans. Always answers (a wedged executor is the watchdog's
        problem, not the probe's)."""
        from ..resilience.retry import watchdog_orphans

        snap = self.metrics.snapshot()
        with self._quiesce:
            draining = self._draining
            inflight = self._inflight
        requests = snap["requests"]
        out = {
            "state": "draining" if draining else "serving",
            "ready": self.ready(),
            "warm": self._warm_signatures is not None,
            "inflight": inflight,
            "breaker": self.breaker.snapshot(),
            "error_rate": (snap["errors"] / requests) if requests else 0.0,
            "rejects": snap["rejects"],
            "sheds": snap["sheds"],
            "deadline_expired": snap["deadline_expired"],
            "watchdog_orphans": watchdog_orphans(),
        }
        # SLO burn: degraded, not dead — ready() is untouched (an SLO
        # violation is a page, not a kill switch), the probe just says so
        slo_mon = getattr(self.metrics, "slo", None)
        if slo_mon is not None:
            out["slo"] = slo_mon.health()
            if out["slo"]["state"] == "degraded":
                out["state"] = "degraded"
        return out

    def ready(self):
        """Readiness probe: warm (lattice compiled + frozen), admitting
        (not draining), and the breaker is not open. A False here is the
        load balancer's cue to route around this replica."""
        with self._quiesce:
            if self._draining:
                return False
        return (self._warm_signatures is not None
                and self.breaker.state != "open")


def _resize_seq(arr, seq, pad_value):
    """Pad or slice axis 1 of a host array to exactly ``seq``."""
    t = arr.shape[1]
    if t == seq:
        return arr
    if t > seq:
        return arr[:, :seq]
    w = [(0, 0), (0, seq - t)] + [(0, 0)] * (arr.ndim - 2)
    return _onp.pad(arr, w, mode="constant", constant_values=pad_value)
