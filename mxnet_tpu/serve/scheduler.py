"""Continuous batching: an iteration-level scheduler for the decode loop.

The PR-6/PR-10 serving stack batches at *request* granularity: the
``DynamicBatcher`` assembles a batch, ``Generator.generate`` runs it to
completion, and every request in the batch holds its slot until the
LONGEST one finishes — a 4-token interactive request admitted next to a
256-token batch job waits out all 256 steps (head-of-line blocking), and
each request's KV ring is sized ``max_seq`` whether it uses 6 positions
or all of them.

:class:`ContinuousEngine` rebatches at *iteration* granularity (Orca):
the decode loop runs forever over a fixed lattice of ``num_slots`` decode
lanes, and between any two decode steps it

* **retires** finished/expired slots — their futures settle immediately
  (an expired request keeps its partial output on the 504), their KV
  pages recycle to the free list;
* **admits** queued requests into the freed slots straight from the
  :class:`~.batcher.DynamicBatcher` queue (``start=False`` — the
  scheduler IS the consumer), interactive-first with the full PR-6
  admission surface (deadlines, shedding, idempotency keys, 503/504
  taxonomy) unchanged;
* **prefills one chunk** of one admitted prompt at a fixed ``(1, chunk)``
  signature, round-robin across prefilling slots — a long prompt streams
  through without ever stalling live decodes for more than one chunk;
* **decodes** every live slot in ONE fixed ``(num_slots, 1)`` step.

On the in-place step (the fast rungs) the engine keeps one decode visit
in flight: the step samples greedily inside itself and leaves each
lane's id on the device, so visit N+1 is enqueued before visit N's ids
are fetched and the host's part of a step runs behind the device. A
sampled lane or the strict rung makes the same loop fetch first (see
:meth:`ContinuousEngine.step`).

KV state lives in a :class:`~.kv_blocks.PagedKVPool`: per-layer page
pools plus a per-slot page table, gathered/scattered around the unchanged
model cache path (fused into the step executable on the fast rungs,
standalone exact-copy brackets around the ring executable on the strict
baseline rung — see ``kv_blocks``). A request holds
``ceil((prompt + max_new) / page_size)`` pages (reserved at admission —
it can never die mid-decode from pool pressure), not a ``max_seq`` ring;
a full pool rejects admission with :class:`~.engine.PoolExhausted` and
the request is requeued at the front, never dropped.

Trace-static by construction: occupancy changes only ever rewrite the
page-table *values* and the token/position vectors — never a shape. The
engine compiles exactly TWO signatures (one chunk prefill, one full-width
decode); :meth:`ContinuousEngine.assert_no_recompiles` holds across any
sequence of admits/retires after :meth:`warmup`. Idle slots point every
page-table entry at the null page, so one executable serves every
occupancy from empty to full.
"""
from __future__ import annotations

import threading
import time

import numpy as _onp

from ..base import MXNetError
from ..ops.pallas.decode_attention import block_range
from ..profiler import attribution as _attr
from ..profiler import core as _prof
from ..profiler import trace as _trace
from ..profiler.core import host_span
from ..resilience import faults as _faults
from .batcher import DynamicBatcher
from .engine import DeadlineExceeded, InferenceSession, PoolExhausted, \
    ServeError, ServiceUnavailable, on_block_context
from .generate import PREFIX_CACHE_NEEDS, WINDOW_PAGES_GO, _CacheForward, \
    _MultiStepForward, _STOP_WIDTH, _fresh_key_bits, _int8_weights_enabled, \
    _quantize_serving_weights, _stop_matrix, gather_rings, require_kv_only, \
    require_unbounded, resolve_decode_path, sample_tokens, scatter_rings
from .kv_blocks import PagedKVPool
from .prefix_cache import PrefixCache


def _no_runner(_batch):  # pragma: no cover - the scheduler IS the consumer
    raise ServeError("continuous-batching queue has no flusher runner")


class _Slot:
    """One decode lane's live request state (scheduler-thread private)."""

    __slots__ = ("p", "prompt", "consumed", "pos", "decoding", "pending",
                 "tokens", "max_new", "temperature", "top_k", "stop",
                 "finished", "expired", "t_admit", "admit_wait_steps",
                 "ttft_ms", "decode_steps", "seed", "token_ms", "inflight")

    def __init__(self, p, steps_now, seed=0):
        payload = p.payload
        self.p = p
        self.prompt = payload["prompt"]
        self.consumed = 0          # prompt tokens already prefilled
        self.pos = 0               # ring write position once decoding
        self.decoding = False      # prefill complete, pending token live
        self.pending = 0           # next token id to feed the decode step
        self.inflight = 0          # tokens dispatched for, not fetched yet
        self.tokens = []           # emitted output ids
        self.token_ms = []         # each kept token's ms since enqueue
        self.max_new = payload["max_new"]
        self.temperature = payload["temperature"]
        self.top_k = payload["top_k"]
        self.stop = payload["stop"]
        self.finished = False
        self.expired = False
        self.t_admit = time.monotonic()
        self.admit_wait_steps = steps_now - payload["enq_step"]
        self.ttft_ms = None
        self.decode_steps = 0
        # per-request sampling-stream id (the engine's admission counter):
        # the multistep in-trace sampler folds it into its key so two
        # requests reusing one slot never share a draw stream
        self.seed = int(seed)

    def emit(self, tid, now):
        """Account one sampled token; flips ``finished`` on stop/budget.
        ``now`` (``time.monotonic()`` of the visit that surfaced it)
        stamps a kept token on the clock and origin of ``ttft_ms``."""
        if tid in self.stop:
            self.finished = True
            return
        self.tokens.append(tid)
        self.token_ms.append((now - self.p.t_enq) * 1e3)
        if len(self.tokens) >= self.max_new:
            self.finished = True
        else:
            self.pending = tid


class _Flight:
    """One call of the step whose tokens the host has not read yet: a
    decode visit, or the last chunk of a prompt. ``riders`` are the
    ``(slot index, _Slot)`` the call made a token for; a token is given
    to its rider by identity, because the slot may have a new tenant by
    the time it arrives. The tokens are the rows of ``ids`` that the
    call wrote, or, where ``logits`` is kept, what the host's sampler
    makes of the call's logits (the strict rung, a rider that samples
    with a temperature)."""

    __slots__ = ("ids", "logits", "riders", "decode", "t_dispatch",
                 "routes")

    def __init__(self, ids, logits, riders, decode, t_dispatch, routes):
        self.ids = ids
        self.logits = logits
        self.riders = riders
        self.decode = decode
        self.t_dispatch = t_dispatch
        self.routes = routes       # expert loads of the calls up to this one


def fetch_ids(ids):
    """The blocking read of a call's greedy ids: a host (rows,) int32
    array. Its copy was started when the call was dispatched."""
    return _onp.asarray(ids.asnumpy(), _onp.int32)


def _greedy(temperature):
    return temperature is None or temperature <= 0.0


class ContinuousEngine:
    """Iteration-level scheduler + paged-KV decode loop for one model.

    Parameters
    ----------
    model : what :class:`~.generate.Generator` serves: a block with a
        ``cache=``/``start_pos=`` forward and a ``cache_spec()`` saying
        what each layer keeps between steps (K/V rows, which are paged,
        and recurrent state, which is one row a slot). With recurrent
        state the step keeps the lane contract (a dead lane's state
        comes back as it went in, a padded prefill position does not
        advance it, a request starts from zero), and ``prefix_cache``
        and ``multistep`` raise: neither can be done to a state without
        snapshots of it. A layer whose keys are bounded by a window
        (``LayerCache.window``) keeps its K/V in a ring of pages of a
        second kind, under a table of its own that the step is handed
        beside the first; ``prefix_cache``, ``multistep`` and the strict
        rung raise for such a model (a ring page is written over while
        its request lives), and the prefill chunk must lie inside one
        page. A latent layer (``LayerCache.latent``) keeps one array a
        position in a pool of its own kind under the same page table:
        the fast float32 rung (``decode_path="pallas"``) alone serves
        it, with or without ``prefix_cache`` (its pages are shared like
        any other); ``multistep``, the strict rung and int8 raise.
        Routed-expert layers have their load read back once a
        decode step (``stats()["moe"]``).
    max_seq : per-request logical ring length (prompt + generated tokens
        must fit); must be a whole number of KV pages.
    num_slots : decode lanes — the ONE compiled decode width
        (``MXNET_SERVE_SLOTS`` default).
    page_size / num_pages : pool geometry (see
        :class:`~.kv_blocks.PagedKVPool`); undersize ``num_pages`` to
        oversubscribe — admission then queues on pool pressure.
    prefill_chunk : tokens prefilled per scheduler iteration at the fixed
        ``(1, chunk)`` signature (``MXNET_SERVE_PREFILL_CHUNK``; 0 means
        one KV page).
    decode_path : serving rung ("baseline" | "pallas" | "int8", see
        :func:`~.generate.resolve_decode_path`). The baseline rung keeps
        the bitwise decode contract — paging brackets are exact copies.
    batcher_kwargs : extra :class:`~.batcher.DynamicBatcher` constructor
        overrides (``max_queue=``, ``timeout_ms=``, ...).
    """

    def __init__(self, model, max_seq=128, num_slots=None, page_size=None,
                 num_pages=None, prefill_chunk=None, pad_id=0,
                 name="llama_cb", decode_path=None, prefix_cache=None,
                 multistep=None, decode_steps=None, **batcher_kwargs):
        from .. import config

        self.model = model
        self.max_seq = int(max_seq)
        if num_slots is None:
            num_slots = int(config.get("MXNET_SERVE_SLOTS"))
        self.num_slots = int(num_slots)
        if self.num_slots < 1:
            raise ServeError(f"num_slots must be >= 1, got {num_slots}")
        self.pad_id = int(pad_id)
        if self.pad_id < 0:
            # a negative token tells the in-place step to take the lane's
            # carried id instead
            raise ServeError(f"pad_id must be >= 0, got {pad_id}")
        self.decode_path = resolve_decode_path(decode_path)
        self._quant = "int8" if self.decode_path == "int8" else None
        self._qindex, self._qflat = [], []
        if self._quant and _int8_weights_enabled():
            self._qindex, self._qflat = _quantize_serving_weights(model)
        self.pool = PagedKVPool(model, self.num_slots, self.max_seq,
                                page_size=page_size, num_pages=num_pages,
                                quant=self._quant)
        if prefill_chunk is None:
            prefill_chunk = int(config.get("MXNET_SERVE_PREFILL_CHUNK"))
        self.prefill_chunk = (int(prefill_chunk) if prefill_chunk > 0
                              else self.pool.page_size)
        if self.prefill_chunk > self.max_seq:
            self.prefill_chunk = self.max_seq
        # cross-request prefix reuse (PR-14): a radix trie over prompt
        # token ids maps matched prefixes to refcounted pool pages, so
        # _admit can skip the matched portion of chunked prefill
        if prefix_cache is None:
            prefix_cache = bool(config.get("MXNET_SERVE_PREFIX_CACHE"))
        if prefix_cache:
            require_kv_only(model, *PREFIX_CACHE_NEEDS)
            require_unbounded(model, PREFIX_CACHE_NEEDS[0], WINDOW_PAGES_GO)
        layout = self.pool.layout
        self._windowed = layout.window is not None
        if self._windowed and self.pool.page_size % self.prefill_chunk:
            raise MXNetError(
                f"prefill_chunk ({self.prefill_chunk}) must divide the KV "
                f"page ({self.pool.page_size}) for a model with a layer "
                "bounded by a window: a chunk writes inside one column of "
                "the ring of pages and reads the columns before it")
        # layers of each kind, for the decode span's kv_positions_* stats
        self._n_window_layers = sum(lay.window is not None
                                    for lay in layout.layers)
        self._n_full_layers = len(layout.layers) - self._n_window_layers
        self._n_latent_layers = sum(bool(lay.latent)
                                    for lay in layout.layers)
        # running sums of the prefill spans' kv_keys_* stats (_chunk_keys)
        self._prefill_keys = {"visited": 0, "held": 0}
        self.prefix = (PrefixCache(self.pool, name=f"{name}_prefix")
                       if prefix_cache else None)
        # fast rungs fuse the paging brackets into the step executable;
        # the strict baseline rung keeps the RING executable and runs
        # the brackets as standalone exact copies in _run_step, which is
        # what makes its decode bitwise identical to the ring path
        self._fused_paged = self.decode_path != "baseline"
        # ... and there the step keeps K/V in the pool: it consumes the
        # pool arrays (donated to the executable), writes in place and
        # reads pages through the table (_CacheForward, inplace)
        self._step_block = _CacheForward(
            model, self.max_seq, path=self.decode_path, quant=self._quant,
            qindex=self._qindex, paged=self._fused_paged,
            inplace=self._fused_paged)
        # ... and samples greedily inside itself: each lane's id stays on
        # the device in one (num_slots,) array that both executables take
        # and hand back (never donated: the host reads a call's ids after
        # later calls were handed them)
        self._carries = bool(self._step_block.donate_args)
        self._ids = None
        # calls whose ids the host has not read yet, oldest first: at most
        # one decode visit, and the last chunks of prompts around it
        self._flights = []
        self._pipeline = {"visits_ahead": 0, "visits_drained": 0,
                          "drained_by": {"sampled": 0, "strict": 0,
                                         "failure": 0},
                          "overrun_lane_steps": 0}
        self._inplace_steps = 0       # step calls that consumed the pool
        self._pool_reallocations = 0  # pools lost to a failed call
        # routed-expert layers: the load arrays of the calls not read
        # back yet, and the totals of those that were
        self._route_pending = []
        self._moe = None
        self._window_recycled = 0     # ring columns written over
        if self._windowed:
            for kind, n in self._kv_pool_bytes().items():
                _prof.set_counter(f"serve.kv_pool_bytes_{kind}", n,
                                  cat="serve")
        if self._n_latent_layers:
            _prof.set_counter("serve.kv_pool_bytes_latent",
                              self.pool.latent_nbytes(), cat="serve")
        # exactly two live signatures: (1, chunk) chunked prefill and
        # (num_slots, 1) decode — the whole point of the design
        self.session = InferenceSession(
            self._step_block,
            batch_buckets=tuple(sorted({1, self.num_slots})),
            seq_buckets=tuple(sorted({1, self.prefill_chunk})),
            pad_value=self.pad_id, name=name)
        self.ctx = self.session.ctx  # the model's device: inputs go there
        self._fresh_ids()
        self.metrics = self.session.metrics
        self.metrics.set_decode_path(self.decode_path)
        self.metrics.set_kv_cache_bytes(self.pool.nbytes(),
                                        state=self.pool.state_nbytes(),
                                        latent=self.pool.latent_nbytes())
        # the admission queue: PR-6 semantics intact, flusher OFF — the
        # scheduler consumes via take()/settle_one() between decode steps
        self._batcher = DynamicBatcher(
            _no_runner, start=False, max_batch_size=self.num_slots,
            name=f"{name}_queue", metrics=self.metrics, **batcher_kwargs)
        # decode critical-path ledger (tentpole PR 16): observations are
        # gated on _attr.ENABLED, the ledger object itself is always
        # there so tests/bench can read it without reaching into flags
        self.ledger = _attr.Ledger(name)
        self._last_emit_t = None   # previous decode step's token stamp
        self._slots = [None] * self.num_slots
        self._steps = 0            # completed scheduler iterations
        self._pf_next = 0          # round-robin cursor over prefill slots
        self._admit_wait_max = 0
        self._thread = None
        self._stop = threading.Event()
        # multi-step decode (tentpole PR 19): up to N decode iterations
        # per host visit inside one compiled loop. The super-step lives
        # in its own session; the engine still compiles exactly two
        # steady-state signatures — (1, chunk) prefill and the
        # (num_slots, N-loop) super-step (the classic (num_slots, 1)
        # decode is simply never compiled in this mode).
        if multistep is None:
            multistep = bool(config.get("MXNET_SERVE_MULTISTEP"))
        self._multistep = bool(multistep)
        if decode_steps is None:
            decode_steps = int(config.get("MXNET_SERVE_DECODE_STEPS"))
        self.decode_steps = max(1, int(decode_steps))
        self._msession = None
        self._itl_est = None   # EMA seconds per decode iteration
        self._seed_seq = 0     # admission counter -> _Slot.seed
        if self._multistep:
            self._mstep = _MultiStepForward(
                model, self.max_seq, self.decode_steps,
                path=self.decode_path, quant=self._quant,
                qindex=self._qindex, paged=True)
            self._msession = InferenceSession(
                self._mstep, batch_buckets=(self.num_slots,),
                seq_buckets=(1,), pad_value=self.pad_id,
                name=f"{name}_multi")
            # one key per engine; per-request streams come from folding
            # each slot's admission seed (and position) into it in-trace
            self._key_bits = _fresh_key_bits()

    def _fresh_ids(self):
        """The carried ids from zeros (the in-place step alone has them)."""
        from .. import numpy as mnp

        if self._carries:
            self._ids = mnp.zeros((self.num_slots,), dtype="int32",
                                  ctx=self.ctx)

    # -- admission -----------------------------------------------------------
    def submit(self, prompt, max_new_tokens=32, temperature=0.0,
               top_k=None, stop_ids=(), priority="interactive",
               deadline_ms=None, key=None):
        """Admit one generation request; returns a Future resolving to
        ``{"tokens": [...], "ttft_ms": ..., "token_ms": [...],
        "admit_wait_steps": ..., "decode_steps": ...}``. ``token_ms`` has
        one entry a token: milliseconds from the request's enqueue (the
        clock and origin of ``ttft_ms``) to the host visit that surfaced
        it, non-decreasing, ``token_ms[0] == ttft_ms``; under
        ``multistep`` the tokens of one super-step share their visit's
        stamp. The full PR-6 admission surface applies
        (priority classes, deadlines -> 504, queue caps/sheds -> 503,
        idempotency keys); a deadline that expires mid-decode settles
        with :class:`DeadlineExceeded` whose ``.partial`` carries the
        tokens generated so far."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise MXNetError("empty prompt (need >= 1 token)")
        if min(prompt) < 0:
            raise MXNetError(f"negative token id in prompt: {min(prompt)}")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        if len(prompt) + max_new > self.max_seq:
            raise MXNetError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new}) "
                f"exceeds max_seq ({self.max_seq})")
        payload = {"prompt": prompt, "max_new": max_new,
                   "temperature": temperature, "top_k": top_k,
                   "stop": frozenset(int(s) for s in stop_ids),
                   "enq_step": self._steps}
        return self._batcher.submit(payload, priority=priority,
                                    deadline_ms=deadline_ms, key=key)

    # -- scheduler iteration -------------------------------------------------
    def _live(self):
        return [s for s in self._slots if s is not None]

    def _free_idx(self):
        return [i for i, s in enumerate(self._slots) if s is None]

    def _settle_slot(self, i, error=None):
        """Retire slot ``i``: settle its future, recycle its pages. On a
        clean retirement the prefix trie adopts the prompt's full pages
        first (increfs while the slot still pins them), so the next
        request sharing this prompt prefix skips that much prefill."""
        s = self._slots[i]
        self._slots[i] = None
        s.finished = True   # a call still in flight drops this rider's row
        if self.prefix is not None and error is None and s.decoding:
            self.prefix.insert(s.prompt, self.pool.table()[i])
        self.pool.release(i)
        if error is not None:
            self._batcher.settle_one(s.p, error=error)
            return
        if s.expired:
            err = DeadlineExceeded(
                f"continuous engine {self.session.name!r}: deadline "
                f"expired after {len(s.tokens)} of {s.max_new} tokens")
            err.partial = list(s.tokens)
            self._batcher.settle_one(s.p, error=err)
            return
        n = len(s.tokens)
        self.metrics.observe_tokens(
            n, max(time.monotonic() - s.t_admit, 1e-9))
        self._batcher.settle_one(s.p, result={
            "tokens": list(s.tokens),
            "ttft_ms": s.ttft_ms,
            "token_ms": list(s.token_ms),
            "admit_wait_steps": s.admit_wait_steps,
            "decode_steps": s.decode_steps,
        })

    def _retire(self):
        now = time.monotonic()
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            if not s.finished and s.p.deadline is not None \
                    and now >= s.p.deadline:
                # the request's budget ran out between steps: stop burning
                # decode work on output nobody will read
                s.finished = s.expired = True
                self.metrics.observe_deadline("decode", s.p.priority)
            if s.finished:
                self._settle_slot(i)

    def _admit(self):
        free = self._free_idx()
        while free:
            batch, sweep = self._batcher.take(1)
            if sweep:
                self._batcher.settle_expired(sweep)
                continue
            if not batch:
                return
            p = batch[0]
            i = free[0]
            need = len(p.payload["prompt"]) + p.payload["max_new"]
            matched, pages = 0, ()
            if self.prefix is not None:
                matched, pages = self.prefix.match(p.payload["prompt"])
            try:
                self._assign_with_reclaim(i, min(need, self.max_seq),
                                          pages)
            except PoolExhausted:
                # backpressure, not failure: the request keeps its place
                # at the queue front and is re-taken as pages recycle
                self._batcher.requeue(p)
                return
            free.pop(0)
            slot = _Slot(p, self._steps, seed=self._seed_seq)
            self._seed_seq += 1
            # a prefix hit: the matched pages already hold these tokens'
            # KV, so chunked prefill starts past them (consumed counts
            # prompt tokens already written)
            slot.consumed = matched
            self._slots[i] = slot
            if self.prefix is not None:
                self.metrics.observe_prefix(matched)
            if slot.admit_wait_steps > self._admit_wait_max:
                self._admit_wait_max = slot.admit_wait_steps

    def _assign_with_reclaim(self, i, budget, pages):
        """``assign_with_prefix`` with one eviction retry: on pool
        pressure the trie reclaims LRU cached prefixes (never the pages
        just matched, never pages a live slot references) before the
        PoolExhausted surfaces as backpressure."""
        try:
            return self.pool.assign_with_prefix(i, budget, pages)
        except PoolExhausted:
            if self.prefix is None:
                raise
            shortfall = (self.pool.pages_for(budget) - len(pages)
                         - self.pool.pages_free)
            if self.prefix.reclaim(max(shortfall, 1), exclude=pages) == 0:
                raise
            return self.pool.assign_with_prefix(i, budget, pages)

    def _run_step(self, tokens, start_pos, last_idx, table, lanes, keep):
        """One call of the step executable; returns the last-position
        logits. ``lanes`` names, for each row of the call, the slot
        whose recurrent state it reads and writes, -1 for a row that is
        not live (an empty slot, a slot still prefilling while its
        neighbours decode): the step hands such a row's state back
        untouched. Only a model that keeps recurrent state is given it.
        ``keep`` names the slot whose carried id each row writes (the
        argmax of its logits) and, where its token is negative, reads;
        -1 for a row that writes none (a dead lane, a chunk that is not
        its prompt's last). The in-place step alone is given it, with
        the ids of the call before, and ``self._ids`` is rebound to what
        it hands back."""
        from .. import numpy as mnp

        stateful = self.pool.layout.has_state
        with host_span("mxnet_tpu.serve.to_device"):
            toks = mnp.array(_onp.asarray(tokens, _onp.int32))
            sp = mnp.array(_onp.asarray(start_pos, _onp.int32))
            li = mnp.array(_onp.asarray(last_idx, _onp.int32))
            tab = [mnp.array(_onp.asarray(table, _onp.int32))]
            if self._windowed:
                tab.append(mnp.array(self._window_rows(lanes)))
            ln = ([mnp.array(_onp.asarray(lanes, _onp.int32))]
                  if stateful else [])
            kp = ([mnp.array(_onp.asarray(keep, _onp.int32)), self._ids]
                  if self._carries else [])
        with host_span("mxnet_tpu.serve.dispatch"):
            if self._fused_paged:
                try:
                    out = self.session.run(toks, sp, li, *tab, *ln, *kp,
                                           *self.pool.flat(), *self._qflat)
                except Exception as exc:  # pylint: disable=broad-except
                    # a call that failed before dispatch left the pool
                    # alive and costs its own slots alone (the callers'
                    # handling); one that took the buffers with it costs
                    # every lane's cache
                    if self.pool.lost():
                        self._recover_pool(exc)
                    raise
                # before the stores come the logits, the load of a model's
                # routed-expert layers, and the in-place step's ids
                # (_CacheForward)
                flat = out[len(out) - len(self.pool.layout):]
                head = len(out) - len(flat)
                if self._carries:
                    head -= 1
                    self._ids = out[head]
                if head > 1:
                    # its copy to the host starts now and rides behind the
                    # step, so that the read after the fetch waits for none
                    out[1]._data.copy_to_host_async()
                    self._route_pending.append(out[1])
                if self._step_block.donate_args:
                    self._inplace_steps += 1
                    _prof.incr_counter("serve.pool_inplace_steps",
                                       cat="serve")
            else:
                # strict rung: paging brackets as standalone exact-copy
                # ops around the unchanged ring executable (bitwise
                # contract)
                layout = self.pool.layout
                rings = gather_rings(layout, self.pool.flat(), tab[0])
                out = self.session.run(toks, sp, li, *ln, *rings,
                                       *self._qflat)
                flat = scatter_rings(layout, self.pool.flat(), tab[0],
                                     out[1:], sp, toks.shape[1])
        with host_span("mxnet_tpu.serve.pool_update"):
            # the pages' and the state rows' swap alike
            self.pool.update_from_flat(flat)
        if stateful:
            live = int((_onp.asarray(lanes) >= 0).sum())
            _prof.incr_counter("serve.state_lane_steps", live, cat="serve")
        return out[0]

    def _kv_pool_bytes(self):
        """Bytes of the K/V page pools by kind of layer: unbounded
        (``full``) and bounded by a window (``window``)."""
        win = self.pool.window_nbytes()
        return {"full": self.pool.nbytes() - win - self.pool.state_nbytes()
                - self.pool.latent_nbytes(),
                "window": win}

    def _window_rows(self, lanes):
        """The ring table of a call: row ``r`` is slot ``lanes[r]``'s
        ring, a row that is not live (-1) all null."""
        lanes = _onp.asarray(lanes)
        live = lanes >= 0
        out = _onp.zeros((len(lanes), self.pool.window_columns), _onp.int32)
        out[live] = self.pool.window_table()[lanes[live]]
        return out

    def _note_recycled(self, first_pos, n):
        """Count the ring columns that writing positions ``first_pos ..
        first_pos + n - 1`` of one sequence starts over: a page past the
        ring's first lap takes the place of the one a lap before it."""
        page, cols = self.pool.page_size, self.pool.window_columns
        first = -(-first_pos // page)           # first page started here
        last = (first_pos + n - 1) // page
        k = max(0, last - max(first, cols) + 1)
        if k:
            self._window_recycled += k
            _prof.incr_counter("serve.window_pages_recycled", k, cat="serve")

    def _read_routes(self, pending):
        """Read back the expert loads ``pending`` of calls whose result
        has just been fetched and waited for (the arrays are results of
        calls the device has finished, never of the one in flight): one
        ``mxnet_tpu.serve.route`` span a read, with the calls' totals."""
        if not pending:
            return
        with host_span("mxnet_tpu.serve.route") as span:
            # (calls, layers, [held experts hit, most on one, assignments
            # that fell on a held expert, the live tokens' assignments,
            # experts held, held experts whose weights the call read])
            load = _onp.stack([_onp.asarray(a.asnumpy(), _onp.int64)
                               for a in pending])
            hit = int(load[..., 0].sum())
            top = int(load[..., 1].max())
            on_held = int(load[..., 2].sum())
            assignments = int(load[..., 3].sum())
            held = int(load[..., 4].sum())
            read = int(load[..., 5].sum())
            # the most loaded expert over the mean of those hit, at worst
            skew = float((load[..., 1] * load[..., 0]
                          / _onp.maximum(load[..., 2], 1)).max())
            span.set_metadata(experts_hit=hit, assignments=assignments,
                              max_load=top, calls=len(pending),
                              experts_held=held, assignments_held=on_held,
                              experts_read=read)
        m = self._moe
        if m is None:
            m = self._moe = {"reads": 0, "calls": 0, "assignments": 0,
                             "experts_hit": 0, "max_load": 0,
                             "load_max_over_mean": 0.0, "experts_held": 0,
                             "assignments_held": 0,
                             "assignments_held_share": 0.0,
                             "experts_read": 0, "picked_share": 0.0}
        m["reads"] += 1
        m["calls"] += len(pending)
        m["assignments"] += assignments
        m["experts_hit"] += hit
        m["experts_held"] += held
        m["assignments_held"] += on_held
        m["assignments_held_share"] = m["assignments_held"] \
            / max(m["assignments"], 1)
        m["experts_read"] += read
        m["picked_share"] = m["experts_hit"] / max(m["experts_read"], 1)
        m["max_load"] = max(m["max_load"], top)
        m["load_max_over_mean"] = skew
        _prof.incr_counter("serve.moe_assignments", assignments, cat="serve")
        _prof.incr_counter("serve.moe_assignments_held", on_held, cat="serve")
        _prof.incr_counter("serve.moe_experts_hit", hit, cat="serve")
        _prof.incr_counter("serve.moe_experts_read", read, cat="serve")
        _prof.set_counter("serve.moe_load_max_over_mean", skew, cat="serve")

    def _recover_pool(self, error):
        """The pool's buffers went with a failed or timed-out call (it
        had been dispatched: the step consumes its cache arguments).
        Every live lane's K/V and state is gone, so each is settled with
        ``error``; cached prefixes point at pages that no longer hold
        them, so the trie is emptied; the pool starts again from zeros
        and the engine serves the next request."""
        for i, s in enumerate(self._slots):
            if s is not None:
                self._settle_slot(i, error=error)
        # every rider of a call in flight was a live lane: settled above
        self._flights = []
        self._route_pending = []
        self._fresh_ids()
        if self.prefix is not None:
            self.prefix.clear()
        self.pool.reallocate()
        self._pool_reallocations += 1
        _prof.incr_counter("serve.pool_reallocations", cat="serve")

    def _prefill_once(self):
        """Advance ONE prefilling slot by one chunk (round-robin), at the
        fixed (1, chunk) signature. The final chunk samples the first
        token — that's the request's TTFT (stamped when the token
        reaches the host)."""
        waiting = [i for i, s in enumerate(self._slots)
                   if s is not None and not s.decoding and not s.finished]
        if not waiting:
            return
        i = min(waiting, key=lambda j: (j - self._pf_next) % self.num_slots)
        self._pf_next = (i + 1) % self.num_slots
        s = self._slots[i]
        n = min(self.prefill_chunk, len(s.prompt) - s.consumed)
        with host_span("mxnet_tpu.serve.prefill", slot=i, n=n,
                       **self._chunk_keys(s.consumed, n)):
            self._prefill_chunk(i, s, n)

    def _chunk_keys(self, start, n):
        """Keys that the attention of a chunk of ``n`` real positions
        starting at position ``start`` visits, and keys its tables hold,
        summed over the layers of each kind (a latent layer is a full
        layer whose keys are its one array a position): the blocks
        ``decode_attention._xla_blocks`` walks over float32 pages, by
        the formula its loop takes its bounds from (the host knows the
        lane's position). With latent layers also the (query, key)
        pairs their attention needs, ``kv_pairs_latent``: query ``j`` of
        the chunk sees ``start + j + 1`` keys. Nothing on the rungs that
        gather a ring."""
        if not self._fused_paged or self._quant:
            return {}
        sp, page = _onp.asarray([start]), self.pool.page_size
        visited = held = 0
        for layers, cols, window in (
                (self._n_full_layers, self.pool.pages_per_slot, None),
                (self._n_window_layers, self.pool.window_columns,
                 self.pool.layout.window)):
            if layers:
                _, turns, c = block_range(sp, self.prefill_chunk, page, cols,
                                          window)
                visited += layers * int(turns) * c * page
                held += layers * cols * page
        self._prefill_keys["visited"] += visited
        self._prefill_keys["held"] += held
        out = {"kv_keys_visited": visited, "kv_keys_held": held}
        if self._n_latent_layers:
            out["kv_pairs_latent"] = self._n_latent_layers * (
                n * start + n * (n + 1) // 2)
        return out

    def _prefill_chunk(self, i, s, n):
        """The next ``n`` prompt tokens of slot ``i`` through the step."""
        with host_span("mxnet_tpu.serve.build_inputs"):
            chunk = self.prefill_chunk
            toks = _onp.full((1, chunk), self.pad_id, _onp.int32)
            toks[0, :n] = s.prompt[s.consumed:s.consumed + n]
            table = _onp.zeros((1, self.pool.pages_per_slot), _onp.int32)
            table[0] = self.pool.table()[i]
            last = s.consumed + n >= len(s.prompt)
        try:
            pf_args = {"slot": i, "n": n}
            with _attr.phase_scope("prefill"):
                p0_ns = time.perf_counter_ns()
                try:
                    if s.consumed == 0 and self.pool.layout.has_state:
                        # the step zeroes the lane's state itself, on
                        # start_pos 0: whatever its last tenant left
                        _prof.incr_counter("serve.state_resets",
                                           cat="serve")
                    t_dispatch = time.perf_counter()
                    logits = self._run_step(toks, [s.consumed], [n - 1],
                                            table, [i], [i if last else -1])
                    if self._windowed:
                        self._note_recycled(s.consumed, n)
                except Exception as e:
                    pf_args["error"] = type(e).__name__
                    raise
                finally:
                    self._span_fanout("serve::prefill_chunk", p0_ns,
                                      time.perf_counter_ns(), pf_args,
                                      (i,))
        except Exception as exc:  # pylint: disable=broad-except
            # only THIS slot was inside the failing call (a call that
            # took the pool with it has settled every slot already)
            if self._slots[i] is not None:
                self._settle_slot(i, error=exc)
            return
        s.consumed += n
        if not last:
            return
        # prompt fully written: the first token is sampled off the last
        # real position's logits (exactly Generator._generate's step-0
        # sample)
        s.decoding = True
        s.pos = len(s.prompt)
        riders = [(i, s)]
        # ... by the step itself where the loop runs ahead: the id stays
        # on the device for the decode visit that consumes it, and the
        # host reads it behind that visit
        host_draws = self._fetch_first(riders) is not None
        self._enqueue(riders, False, t_dispatch,
                      logits if host_draws else None)
        if host_draws:
            try:
                self._land()
            except Exception:  # pylint: disable=broad-except
                pass   # a fetch that failed has settled every lane

    def _enqueue(self, riders, decode, t_dispatch, logits):
        """Put a call that has just been dispatched for ``riders`` in
        flight: each has one more token coming. ``logits`` where the
        host's sampler makes the tokens, ``None`` where the call's ids
        are the tokens; the copy of the ids to the host starts now."""
        for _, s in riders:
            s.inflight += 1
        if self._carries:
            self._ids._data.copy_to_host_async()
        # with it go the expert loads of the calls since the last one
        # in flight: its fetch will show that the device is done with them
        routes, self._route_pending = self._route_pending, []
        self._flights.append(_Flight(self._ids, logits, riders, decode,
                                     t_dispatch, routes))

    def _riders(self):
        """The slots the next decode visit carries: decoding, not ended,
        and with budget left once what is in flight for them has arrived
        (a request that ends by count is left out without waiting for
        its last token)."""
        return [(i, s) for i, s in enumerate(self._slots)
                if s is not None and s.decoding and not s.finished
                and len(s.tokens) + s.inflight < s.max_new]

    def _fetch_first(self, riders):
        """Why the next visit cannot be enqueued ahead of the fetch of
        what is in flight, or ``None``: every rider's next token has to
        be on the device, so the step must keep ids (the in-place step
        does; the strict rung's ring executable does not) and every
        rider must be greedy (a sampled token is drawn on the host, from
        ``mxnet_tpu.random``'s key stream)."""
        if not self._carries or self._multistep:
            # (the multi-step visit is handed its tokens by the host too)
            return "strict"
        if not all(_greedy(s.temperature) for _, s in riders):
            return "sampled"
        return None

    @staticmethod
    def _read(f):
        """The blocking read of the tokens of call ``f``, by slot row."""
        if f.logits is None:
            return fetch_ids(f.ids)
        if not f.decode:
            # exactly Generator._generate's step-0 sample
            (i, s), = f.riders
            return {i: int(sample_tokens(f.logits, temperature=s.temperature,
                                         top_k=s.top_k)[0])}
        if all(_greedy(s.temperature) for _, s in f.riders):
            # one greedy argmax for all rows; blocks on the device
            return sample_tokens(f.logits)
        arr = f.logits.asnumpy()   # blocking device fetch
        return {i: int(sample_tokens(arr[i:i + 1], temperature=s.temperature,
                                     top_k=s.top_k)[0]) for i, s in f.riders}

    def _land(self, leave=0):
        """Fetch, emit and settle every call in flight but the newest
        ``leave``, oldest first. Returns ``(seconds, wait ns)`` spent
        blocked in the fetches. A fetch that fails means a dispatched
        call's result never came back, and the pool's arrays descend
        from that call: every lane
        (those of the calls enqueued behind it too) is settled with the
        error, the pool starts from zeros, and the error goes on to the
        caller."""
        blocked, waited = 0.0, 0
        while len(self._flights) > leave:
            f = self._flights.pop(0)
            t0 = time.perf_counter()
            w0 = _attr.thread_wait_ns() if _attr.ENABLED else 0
            try:
                with host_span("mxnet_tpu.serve.sample"):
                    got = self._read(f)
            except Exception as exc:
                self._recover_pool(exc)
                raise
            t1 = time.perf_counter()
            blocked += t1 - t0
            if _attr.ENABLED:
                waited += _attr.thread_wait_ns() - w0
            self._read_routes(f.routes)
            self._settle_flight(f, got, t1)
        return blocked, waited

    def _settle_flight(self, f, got, t_fetched):
        """Account the tokens ``got`` (by slot row) of one fetched call.
        A rider whose request has ended since the call was dispatched is
        dropped, by the identity of the request; a decode visit's row
        lost to a stop id that was learned one visit late is counted."""
        kept = []
        for i, s in f.riders:
            s.inflight -= 1
            if not s.finished:
                kept.append((i, s))
            elif f.decode and not s.expired:
                self._count("overrun_lane_steps")
        with host_span("mxnet_tpu.serve.settle", tokens=len(kept)):
            now = time.monotonic()
            for i, s in kept:
                if f.decode:
                    s.decode_steps += 1
                else:
                    s.ttft_ms = (now - s.p.t_enq) * 1e3
                    self.metrics.observe_ttft(s.ttft_ms, s.p.priority)
                s.emit(int(got[i]), now)
            if f.decode:
                # ITL is the token-to-token gap, not just the device
                # window: in steady state it runs from the PREVIOUS
                # visit's arrival, so scheduler stalls between steps
                # (admissions, prefill chunks, an injected serve:decode
                # delay) land in the stream-stall number the SLO monitor
                # judges. First visit after idle has no waiting stream;
                # it falls back to its own dispatch.
                prev = self._last_emit_t
                self._last_emit_t = t_fetched
                start = prev if prev is not None else f.t_dispatch
                self.metrics.observe_itl((t_fetched - start) * 1e3,
                                         live=len(f.riders))

    def _count(self, what, reason=None):
        """One more of ``stats()["pipeline"][what]`` and of the counter
        ``serve.<what>``; a drained visit also by its ``reason``."""
        self._pipeline[what] += 1
        _prof.incr_counter(f"serve.{what}", cat="serve")
        if reason is not None:
            self._pipeline["drained_by"][reason] += 1
            _prof.incr_counter(f"serve.{what}.{reason}", cat="serve")

    def _decode_once(self):
        """One fixed-width decode visit over every decoding slot. Slots
        that are empty or still prefilling ride along as dead lanes:
        all-null page-table rows route their writes to the null page
        (re-zeroed in the scatter op), so they can neither corrupt live
        state nor feed garbage back to themselves. A recurrent state has
        no null page: a dead lane's ``lanes`` entry is -1 and the step
        returns its state row bit for bit (a slot in the middle of its
        prefill keeps what its chunks have built).

        The visit is enqueued BEFORE what was in flight is fetched
        wherever every rider's next token is on the device
        (:meth:`_fetch_first`), and is itself left in flight; otherwise
        the loop fetches first, and fetches the visit before it goes on
        (a drain, then a visit as it ever was)."""
        riders = self._riders()
        if riders and self._flights and self._fetch_first(riders):
            self._land()     # may end a rider: a stop id, a last token
            riders = self._riders()
        if not riders:
            # nothing to enqueue: what is in flight is all there is. An
            # idle gap, not a stall: no live token stream is waiting, so
            # the next visit's ITL restarts from its own window
            self._land()
            self._last_emit_t = None
            return
        stats = {}
        if self._windowed:
            # K/V positions the step's attention reads, by kind of layer
            # (the host knows every lane's position)
            w = self.pool.layout.window
            at = [s.pos + 1 for _, s in riders]
            stats = {"kv_positions_full": sum(at) * self._n_full_layers,
                     "kv_positions_window": sum(min(t, w) for t in at)
                     * self._n_window_layers}
        if self._n_latent_layers:
            # latent positions it reads: every one of every live lane
            stats["kv_positions_latent"] = self._n_latent_layers * sum(
                s.pos + 1 for _, s in riders)
        with host_span("mxnet_tpu.serve.decode", live=len(riders),
                       **stats):
            self._decode_step(riders)

    def _decode_step(self, riders):
        """The classic decode visit over ``riders``, (slot, request)
        pairs."""
        _faults.fault_point("serve:decode",
                            {"session": self.session.name})
        t_build = time.perf_counter()
        with host_span("mxnet_tpu.serve.build_inputs"):
            S = self.num_slots
            toks = _onp.zeros((S, 1), _onp.int32)
            pos = _onp.zeros(S, _onp.int32)
            lanes = _onp.full(S, -1, _onp.int32)
            table = _onp.zeros((S, self.pool.pages_per_slot), _onp.int32)
            live_table = self.pool.table()
            for i, s in riders:
                # a token still in flight is on the device: the step
                # takes the lane's carried id
                toks[i, 0] = -1 if s.inflight else s.pending
                pos[i] = s.pos
                lanes[i] = i
                table[i] = live_table[i]
        # the iteration's four-way attribution (host/dispatch/device/
        # wait partitions the span wall exactly; the pre-span input
        # assembly above lands in the ledger's schedule bucket): the
        # span covers dispatch, the blocking reads (the ONE sanctioned
        # device sync: the device phase is the time the host spent in
        # them, for the visit before this one where this one was
        # enqueued ahead, behind which the device is busy), and the
        # host-side emit bookkeeping
        attributing = _attr.ENABLED
        args = {"live": len(riders)}
        with _attr.phase_scope("decode"):
            t1 = time.perf_counter()
            w1 = _attr.thread_wait_ns() if attributing else 0
            s0_ns = time.perf_counter_ns()
            try:
                logits = self._run_step(toks, pos,
                                        _onp.zeros(S, _onp.int32), table,
                                        lanes, lanes)
                # positions, ring columns and budgets advance at dispatch
                for _, s in riders:
                    if self._windowed:
                        self._note_recycled(s.pos, 1)
                    s.pos += 1
                t2 = time.perf_counter()
                w2 = _attr.thread_wait_ns() if attributing else 0
                why = self._fetch_first(riders)
                if why is not None:
                    self._count("visits_drained", why)
                elif any(f.decode for f in self._flights):
                    self._count("visits_ahead")
                self._enqueue(riders, True, t1, logits if why else None)
                # enqueued ahead, the visit stays in flight and what was
                # in flight before it is read behind it; else it is read
                # with the rest
                blocked, waited = self._land(leave=int(why is None))
                if attributing:
                    t4 = time.perf_counter()
                    w4 = _attr.thread_wait_ns()
                    dispatch_ms = max(
                        0.0, (t2 - t1) * 1e3 - (w2 - w1) / 1e6)
                    device_ms = blocked * 1e3
                    wait_ms = max(0.0, (w4 - w1 - waited) / 1e6)
                    host_ms = max(
                        0.0, (t4 - t2 - blocked) * 1e3
                        - (w4 - w2 - waited) / 1e6)
                    args.update(host_ms=round(host_ms, 4),
                                dispatch_ms=round(dispatch_ms, 4),
                                device_ms=round(device_ms, 4),
                                wait_ms=round(wait_ms, 4))
                    self.ledger.observe_step(host_ms, dispatch_ms,
                                             device_ms, wait_ms,
                                             live=len(riders))
                    self.ledger.observe_schedule((t1 - t_build) * 1e3)
            except Exception as e:
                args["error"] = type(e).__name__
                raise
            finally:
                self._span_fanout("serve::decode_step", s0_ns,
                                  time.perf_counter_ns(), args,
                                  [i for i, _ in riders])

    def _run_multi(self, toks, pos, table, limit, remaining, seeds,
                   temps, top_ks, stops):
        """Dispatch one super-step over the full slot lattice; returns
        ``(block, valid, done, t_dispatch, w_dispatch)`` — the stamp pair
        is taken right after the executable call returns (dispatch done,
        device still running) so :meth:`_decode_multi` can split
        dispatch from device time like :meth:`_decode_once` does."""
        from .. import numpy as mnp

        with host_span("mxnet_tpu.serve.to_device"):
            args = [
                mnp.array(_onp.asarray(toks, _onp.int32)),
                mnp.array(_onp.asarray(pos, _onp.int32)),
                mnp.array(_onp.asarray([limit], _onp.int32)),
                mnp.array(_onp.asarray(remaining, _onp.int32)),
                mnp.array(_onp.asarray(seeds, _onp.int32)),
                mnp.array(_onp.asarray(temps, _onp.float32)),
                mnp.array(_onp.asarray(top_ks, _onp.int32)),
                mnp.array(_onp.asarray(stops, _onp.int32)),
                mnp.array(_onp.asarray(self._key_bits, _onp.uint32)),
                mnp.array(_onp.asarray(table, _onp.int32)),
            ]
        with host_span("mxnet_tpu.serve.dispatch"):
            out = self._msession.run(*args, *self.pool.flat(),
                                     *self._qflat)
        t2 = time.perf_counter()
        w2 = _attr.thread_wait_ns()
        with host_span("mxnet_tpu.serve.pool_update"):
            self.pool.update_from_flat(out[3:])
        with host_span("mxnet_tpu.serve.sample"):
            block = _onp.asarray(out[0].asnumpy(), _onp.int32)
            valid = _onp.asarray(out[1].asnumpy(), _onp.int32)
            done = _onp.asarray(out[2].asnumpy(), _onp.int32)
        return block, valid, done, t2, w2

    def _steps_limit(self, decoding):
        """The next super-step's iteration ceiling: N, degraded to 1
        when some live row's deadline could not survive a full
        N-iteration block (per-iteration EMA estimate), so 504
        retirement latency stays bounded by about one iteration —
        through the SAME executable (``steps_limit`` is traced)."""
        n = self.decode_steps
        if self._itl_est is None:
            return n
        now = time.monotonic()
        slack = min((self._slots[i].p.deadline - now for i in decoding
                     if self._slots[i].p.deadline is not None),
                    default=None)
        if slack is not None and slack < self._itl_est * n:
            return 1
        return n

    def _decode_multi(self):
        """One super-step over every decoding slot: up to
        ``decode_steps`` decode iterations inside the compiled loop,
        settled host-side in one pass by replaying :meth:`_Slot.emit`
        over each lane's valid token run. Dead/prefilling lanes ride
        along with ``remaining=0`` — device-side done from iteration 0,
        writes routed to the (re-zeroed) null page. When every lane is
        done the loop exits on-device, so an almost-finished lattice
        never burns N full iterations."""
        decoding = [i for i, s in enumerate(self._slots)
                    if s is not None and s.decoding and not s.finished]
        if not decoding:
            self._last_emit_t = None
            return
        with host_span("mxnet_tpu.serve.decode", live=len(decoding)):
            self._decode_superstep(decoding)

    def _decode_superstep(self, decoding):
        """The multi-step decode visit over the ``decoding`` slots."""
        _faults.fault_point("serve:decode",
                            {"session": self._msession.name})
        t_build = time.perf_counter()
        with host_span("mxnet_tpu.serve.build_inputs"):
            S = self.num_slots
            toks = _onp.zeros((S, 1), _onp.int32)
            pos = _onp.zeros(S, _onp.int32)
            remaining = _onp.zeros(S, _onp.int32)
            seeds = _onp.zeros(S, _onp.int32)
            temps = _onp.zeros(S, _onp.float32)
            tks = _onp.zeros(S, _onp.int32)
            table = _onp.zeros((S, self.pool.pages_per_slot), _onp.int32)
            live_table = self.pool.table()
            stop_sets = [frozenset()] * S
            for i in decoding:
                s = self._slots[i]
                toks[i, 0] = s.pending
                pos[i] = s.pos
                remaining[i] = s.max_new - len(s.tokens)
                seeds[i] = s.seed
                temps[i] = (s.temperature if s.temperature is not None
                            and s.temperature > 0.0 else 0.0)
                tks[i] = int(s.top_k) if s.top_k else 0
                table[i] = live_table[i]
                stop_sets[i] = s.stop
            stops = _stop_matrix(S, stop_sets)
            limit = self._steps_limit(decoding)
        attributing = _attr.ENABLED
        args = {"live": len(decoding), "steps": limit}
        with _attr.phase_scope("decode"):
            t1 = time.perf_counter()
            w1 = _attr.thread_wait_ns() if attributing else 0
            s0_ns = time.perf_counter_ns()
            try:
                block, valid, _done, t2, w2 = self._run_multi(
                    toks, pos, table, limit, remaining, seeds, temps,
                    tks, stops)
                t3 = time.perf_counter()
                w3 = _attr.thread_wait_ns() if attributing else 0
                # host settle: replay emit over each lane's token run —
                # the host stays the source of truth for stop/budget
                # (device done only bounds the iteration count)
                n_tok = int(sum(valid[i] for i in decoding))
                with host_span("mxnet_tpu.serve.settle", tokens=n_tok):
                    now = time.monotonic()
                    steps_run = 0
                    for i in decoding:
                        s = self._slots[i]
                        k = int(valid[i])
                        if k > steps_run:
                            steps_run = k
                        s.pos += k
                        s.decode_steps += k
                        for j in range(k):
                            s.emit(int(block[i, j]), now)
                            if s.finished:
                                break
                    if attributing:
                        t4 = time.perf_counter()
                        w4 = _attr.thread_wait_ns()
                        dispatch_ms = max(
                            0.0, (t2 - t1) * 1e3 - (w2 - w1) / 1e6)
                        device_ms = (t3 - t2) * 1e3
                        host_ms = max(
                            0.0, (t4 - t3) * 1e3 - (w4 - w3) / 1e6)
                        wait_ms = max(0.0, ((w2 - w1) + (w4 - w3)) / 1e6)
                        args.update(host_ms=round(host_ms, 4),
                                    dispatch_ms=round(dispatch_ms, 4),
                                    device_ms=round(device_ms, 4),
                                    wait_ms=round(wait_ms, 4),
                                    tokens=n_tok)
                        self.ledger.observe_step(host_ms, dispatch_ms,
                                                 device_ms, wait_ms,
                                                 live=len(decoding),
                                                 tokens=n_tok)
                        self.ledger.observe_schedule((t1 - t_build) * 1e3)
                    prev = self._last_emit_t
                    self._last_emit_t = t3
                    itl_start = prev if prev is not None else t1
                    if steps_run > 0:
                        # the visit's wall amortizes over the iterations
                        # it ran — k tokens means k consumer-visible
                        # gaps, not one giant one
                        self.metrics.observe_itl((t3 - itl_start) * 1e3,
                                                 live=len(decoding),
                                                 tokens=steps_run)
                        est = (t3 - t1) / steps_run
                        self._itl_est = (est if self._itl_est is None
                                         else 0.5 * self._itl_est
                                         + 0.5 * est)
            except Exception as e:
                args["error"] = type(e).__name__
                raise
            finally:
                self._span_fanout("serve::decode_step", s0_ns,
                                  time.perf_counter_ns(), args, decoding)

    def _span_fanout(self, name, t0_ns, t1_ns, args, slot_idx):
        """Record one span into every listed slot's request trace — an
        iteration-level step is on EACH rider's critical path, and the
        engine thread has no ambient request trace to catch ``span()``
        — plus the ambient trace when one IS active (inline ``step()``
        under an activated trace), never duplicating a target."""
        targets = []
        amb = _trace.current()
        if amb is not None:
            targets.append(amb)
        for i in slot_idx:
            s = self._slots[i]
            tr = s.p.trace if s is not None else None
            if tr is not None and tr not in targets:
                targets.append(tr)
        for tr in targets:
            tr.span_at(name, t0_ns, t1_ns, args)

    @on_block_context
    def step(self):
        """One scheduler iteration: retire -> admit (on what the host
        knows) -> dispatch one prefill chunk -> dispatch one decode
        visit -> fetch, emit and settle what was in flight BEFORE that
        visit (the visit before it, and the first token of a prompt
        whose last chunk went out in this step) -> gauges. At most one
        decode visit stays in flight. Positions, pages, ring columns
        and ``max_new`` ends advance at dispatch; token values, stop
        ids, ``ttft_ms``, ``token_ms``, the ITL sample and the route
        loads at fetch. Where a rider samples with a temperature, or on
        the strict rung, the visit is fetched before the loop goes on
        (:meth:`_fetch_first`). Execution failures (an injected
        ``serve:execute``/``serve:decode`` fault, a watchdog timeout)
        fail the requests that were inside the failing call — the
        scheduler itself keeps serving, exactly like the batcher's
        batch-failure isolation."""
        with host_span("mxnet_tpu.serve.step", engine=self.session.name,
                       step=self._steps):
            t0 = time.perf_counter()
            with host_span("mxnet_tpu.serve.retire"):
                self._retire()
            with host_span("mxnet_tpu.serve.admit"):
                self._admit()
            if _attr.ENABLED:
                # host-schedule: the admit/retire bookkeeping between
                # device calls — ROADMAP item 3's kill target
                self.ledger.observe_schedule((time.perf_counter() - t0) * 1e3)
            self._prefill_once()
            try:
                if self._multistep:
                    self._decode_multi()
                else:
                    self._decode_once()
            except Exception as exc:  # pylint: disable=broad-except
                self._fail_decoding(exc)
            self._steps += 1
            with host_span("mxnet_tpu.serve.gauges"):
                self.metrics.set_kv_pages(self.pool.pages_used,
                                          self.pool.pages_free)
                self.metrics.set_slot_occupancy(len(self._live()),
                                                self.num_slots)
                if _attr.ENABLED:
                    self.metrics.set_attribution(
                        self.ledger.host_overhead_fraction(),
                        self.ledger.device_ms_per_token())
                if self.prefix is not None:
                    self.metrics.set_prefix_gauges(self.pool.pages_shared,
                                                   self.prefix.pages_held,
                                                   self.prefix.evictions)

    def _fail_decoding(self, exc):
        """A decode visit failed. Before its dispatch: what was in flight
        before it is whole and is settled first (a request that had
        ended by count gets its last token), then the lanes that would
        have ridden are settled with ``exc``. At a fetch:
        :meth:`_recover_pool` has settled every lane, and nothing is
        left to do here. Either way the pipeline is empty after it."""
        self._count("visits_drained", "failure")
        try:
            self._land()
        except Exception:  # pylint: disable=broad-except
            return   # a fetch that failed has settled every lane
        for i, s in enumerate(self._slots):
            if s is not None and s.decoding and not s.finished:
                self._settle_slot(i, error=exc)

    def _idle(self):
        # a call in flight is work: its ids are still to be settled
        return (not self._live() and not self._flights
                and self._batcher.queue_depth() == 0)

    def _run_loop(self):
        _prof.register_thread_name()
        while not self._stop.is_set():
            if self._idle():
                with host_span("mxnet_tpu.serve.idle_wait"), \
                        self._batcher._cond:
                    if not self._batcher._queue and not self._stop.is_set():
                        self._batcher._cond.wait(0.05)
                continue
            self.step()

    # -- lifecycle -----------------------------------------------------------
    @on_block_context
    def warmup(self):
        """Compile BOTH live signatures and freeze the set: one
        (1, chunk) prefill chunk plus — classic mode — one
        (num_slots, 1) decode step, or — multistep mode — one
        (num_slots,) super-step (the classic decode signature is never
        compiled there; the super-step IS the decode executable). Every
        later admit/retire/prefill/decode replays one of these two
        executables (``assert_no_recompiles`` is the test)."""
        t0 = time.perf_counter()
        n = self.pool.pages_per_slot
        S = self.num_slots
        # warm-up rows are not live: they leave the state rows alone
        self._run_step(
            _onp.zeros((1, self.prefill_chunk), _onp.int32), [0], [0],
            _onp.zeros((1, n), _onp.int32), [-1], [-1])
        if self._multistep:
            # remaining=0: zero runtime iterations, full trace/compile
            self._run_multi(
                _onp.zeros((S, 1), _onp.int32), _onp.zeros(S, _onp.int32),
                _onp.zeros((S, n), _onp.int32), self.decode_steps,
                _onp.zeros(S, _onp.int32), _onp.zeros(S, _onp.int32),
                _onp.zeros(S, _onp.float32), _onp.zeros(S, _onp.int32),
                _onp.full((S, _STOP_WIDTH), -1, _onp.int32))
            self._msession.freeze_signatures()
        else:
            self._run_step(
                _onp.zeros((S, 1), _onp.int32),
                _onp.zeros(S, _onp.int32),
                _onp.zeros(S, _onp.int32),
                _onp.zeros((S, n), _onp.int32), _onp.full(S, -1),
                _onp.full(S, -1))
        self.session.freeze_signatures()
        sigs = self.session.signature_count()
        if self._msession is not None:
            sigs += self._msession.signature_count()
        return {"signatures": sigs,
                "wall_s": time.perf_counter() - t0}

    def start(self):
        """Warm up (if not already) and start the scheduler thread."""
        if self.session._warm_signatures is None:
            self.warmup()
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run_loop, daemon=True,
            name=f"mxtpu-serve-scheduler[{self.session.name}]")
        self._thread.start()

    def close(self, timeout=5.0):
        """Stop the scheduler thread, fail live slots and queued work
        with 503 (the batcher's close taxonomy), release every page."""
        self._stop.set()
        with self._batcher._cond:
            self._batcher._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        for i, s in enumerate(self._slots):
            if s is not None:
                self._settle_slot(i, error=ServiceUnavailable(
                    f"continuous engine {self.session.name!r} shut down "
                    f"mid-request ({len(s.tokens)} tokens generated)"))
        # every rider of a call in flight was a live slot: settled above
        self._flights = []
        self._route_pending = []
        self._batcher.close(timeout)

    def drain(self, timeout=30.0):
        """Stop admission and wait until every admitted request settles
        (queue empty AND all slots retired). :meth:`resume` reopens."""
        return self._batcher.drain(timeout)

    def resume(self):
        self._batcher.resume()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- invariants / readout ------------------------------------------------
    def assert_no_recompiles(self):
        self.session.assert_no_recompiles()
        if self._msession is not None:
            self._msession.assert_no_recompiles()

    def stats(self):
        out = self.session.stats()
        out["pool"] = self.pool.stats()
        out["state_pool_bytes"] = self.pool.state_nbytes()
        out["state_bytes_per_lane"] = (self.pool.state_nbytes()
                                       // self.num_slots)
        out["steps"] = self._steps
        # calls of the step executable that consumed the pool arrays and
        # updated them in place; pools lost to a failed call and started
        # again from zeros
        out["pool_inplace_steps"] = self._inplace_steps
        out["pool_reallocations"] = self._pool_reallocations
        # decode visits enqueued while the visit before was unfetched,
        # those that had to fetch first (and why), and lane-steps whose
        # result was dropped because the request had ended meanwhile
        out["pipeline"] = {**self._pipeline,
                           "drained_by": dict(self._pipeline["drained_by"]),
                           "in_flight": len(self._flights)}
        caches = [out["cache"]]
        if self._msession is not None:
            out["multistep"] = self._msession.stats()
            out["decode_steps"] = self.decode_steps
            caches.append(out["multistep"]["cache"])
        # warm calls of the step executables that derived nothing anew
        # from the model, and those that made no RNG key (CachedOp)
        for k in ("fast_calls", "keys_skipped"):
            out[k] = sum(c[k] for c in caches)
        if self._windowed:
            for kind, n in self._kv_pool_bytes().items():
                out[f"kv_pool_bytes_{kind}"] = n
            out["window_pages_recycled"] = self._window_recycled
        if self._n_latent_layers:
            out["kv_pool_bytes_latent"] = self.pool.latent_nbytes()
            out["latent_bytes_per_position"] = \
                self.pool.latent_bytes_per_position()
        if self._moe is not None:
            out["moe"] = dict(self._moe)
        keys = self._prefill_keys
        out["prefill_keys"] = {**keys, "visited_share":
                               keys["visited"] / max(keys["held"], 1)}
        out["slots_live"] = len(self._live())
        out["slots_total"] = self.num_slots
        out["admit_wait_steps_max"] = self._admit_wait_max
        out["queue_depth"] = self._batcher.queue_depth()
        out["duplicate_submits"] = self._batcher.duplicate_submits
        if self.prefix is not None:
            out["prefix"] = self.prefix.stats()
        return out
