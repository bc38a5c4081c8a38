"""Deterministic, seedable fault injection for the TPU runtime.

Production failure modes on a TPU pod are well known — transient XLA
compile/dispatch errors, stuck or failed ICI collectives, whole-worker
crashes — but none of them reproduce on a CPU dev box. This module makes
them reproducible: a *fault plan* names injection **sites** wired into the
dispatch layer (``ops/registry.apply``), CachedOp compile
(``cachedop._lookup_or_build``), the dist_tpu collectives
(``kvstore/dist_tpu``) and the engine wait points (``engine.wait_all``),
and each rule in the plan decides deterministically — by hit index or by a
seeded RNG — when that site throws a transient error, sleeps (a slow
collective), raises a fatal error, or simulates worker death.

Hot-path contract (same discipline as the profiler's ``_PROF`` slot): the
instrumented modules each hold a module-level ``_FAULTS = None`` slot that
:func:`install_plan` pokes and :func:`clear_plan` resets. A session that
never injects faults pays one global load + ``is None`` test per site.

Plan format (programmatic dicts or the ``MXNET_FAULT_PLAN`` env var as
JSON, or ``@/path/to/plan.json``)::

    {"seed": 7, "rules": [
        {"site": "kvstore:allreduce", "kind": "transient", "at": [0, 1]},
        {"site": "cachedop:compile",  "kind": "transient", "times": 1},
        {"site": "op:dispatch",       "kind": "transient", "prob": 0.01},
        {"site": "kvstore:allreduce", "kind": "delay", "seconds": 0.2,
         "at": [5]},
        {"site": "engine:wait",       "kind": "fatal", "at": [3]},
        {"site": "estimator:batch",   "kind": "die", "at": [12]}
    ]}

Rule matching: ``site`` must equal the instrumented site name (or ``"*"``).
Exactly one trigger per rule: ``at`` (list of 0-based hit indices for that
rule), ``times`` (fire on the first N hits), or ``prob`` (per-hit
probability from the plan-seeded RNG — deterministic for a fixed seed and
hit sequence). Kinds:

``transient``
    raises :class:`TransientFaultError` — the retry layer classifies it
    retryable, so recovery paths exercise end to end.
``fatal``
    raises :class:`InjectedFaultError` — never retried.
``delay``
    sleeps ``seconds`` (default 0.05) — a slow/stuck collective; pair with
    ``MXNET_COLLECTIVE_TIMEOUT`` to exercise the watchdog.
``die``
    raises :class:`SimulatedWorkerDeath` (a ``BaseException``) — ordinary
    ``except Exception`` recovery code cannot swallow it, so it unwinds the
    whole training loop the way a SIGKILLed worker would, without killing
    the test process.
``nan``
    does NOT raise: :meth:`FaultPlan.check` returns the string ``"nan"``
    and the *call site* corrupts its own payload (``trainer:grad`` poisons
    every parameter gradient with NaN before the optimizer update). This is
    how the numerical-guardrail paths — sentinel trip, pre-collective
    quarantine, skip-step, rewind-and-skip — are exercised deterministically
    on CPU. Sites that don't implement corruption ignore the return value,
    so a ``nan`` rule on e.g. ``engine:wait`` fires (and is counted) but
    has no effect.
``torn``
    does not raise: returns ``{"kind": "torn"}`` and the checkpoint write
    path (``ckpt:write``) lands deliberately truncated bytes at the FINAL
    checkpoint name — the on-disk state that bit rot or a partially-synced
    disk produces and that the atomic tmp+rename protocol normally rules
    out — so the CRC-quarantine + last-good rollback path is exercised
    deterministically. A ``die`` at the same site instead kills the writer
    between atomic container writes (shards present, manifest absent).
``preempt``
    does not raise: returns ``{"kind": "preempt"}`` and the preemption
    guard (``preempt:deliver`` in ``resilience.preemption``) treats the
    hit as a delivered SIGTERM — finish the step, force-save, stop — so
    graceful-drain recovery is testable without real signal delivery.

Per-replica kinds (elastic multichip training, ``resilience.elastic``) —
each takes a ``"replica"`` field naming the device-group index it targets:

``chip_loss``
    raises :class:`ChipLostError` (carries ``.replica``) — the injected
    analog of a dead chip taking its ICI ring down. Never retried; with
    ``MXNET_ELASTIC=1`` the dist_tpu collective classifies it as mesh
    loss and raises :class:`~.elastic.MeshDegraded` so an
    :class:`~.elastic.ElasticTrainingHandler` can shrink the mesh and
    resume; with elastic off it degrades to the eager fallback like any
    fatal fast-path failure (PR-2 semantics, bitwise preserved). For
    composed dp×tp(×pp) meshes the rule may instead (or additionally)
    carry a ``"device"`` field addressing the dead chip by mesh
    coordinate — either a flat device index (int) or
    ``{"axis": ..., "index": ...}`` naming a slice of a named axis — and
    :class:`ChipLostError` forwards it as ``.device`` so the elastic
    layer can drop the whole dp-group that contained the chip
    (:func:`~..parallel.mesh.rebuild_mesh`). Replica-int plans are
    unchanged: ``"replica"`` still targets a device-group index and
    ``.replica`` keeps its meaning.
``replica_delay``
    does not raise: sleeps ``seconds`` *only when the call site's current
    replica matches the rule's* (sites pass ``info={"replica": i}``;
    sites without replica info sleep unconditionally) and returns the
    marker dict ``{"kind": "replica_delay", "replica", "seconds"}`` so
    the site can report the lag to the straggler monitor.
``param_corrupt``
    does not raise: returns ``{"kind": "param_corrupt", "replica": r}``
    and the call site (``trainer:param``) perturbs replica ``r``'s
    parameter copies — the silent single-replica drift the desync audit
    exists to catch.

Replica matching: a rule with a ``"replica"`` field only *hits* when the
site's ``info`` dict carries no ``"replica"`` key or carries the same
value — so ``at`` indices count per-target-replica visits, not global
site traffic.
"""
from __future__ import annotations

import json
import threading
import time

from ..base import MXNetError
from ..profiler import core as _prof
from ..profiler import recorder as _recorder
from . import counters as _counters

# Sites wired in this PR (documented; fault_point accepts any name so new
# sites need no registry change):
KNOWN_SITES = (
    "op:dispatch",          # ops/registry.apply, before the op executes
    "cachedop:compile",     # cachedop._lookup_or_build cache miss
    "kvstore:allreduce",    # dist_tpu fast-path collective body
    "kvstore:allreduce_compile",  # dist_tpu AOT lower().compile()
    "kvstore:pushpull",     # dist_tpu.pushpull per-key loop
    "kvstore:broadcast",    # dist_tpu.broadcast per-key loop
    "engine:wait",          # engine.wait_all drain
    "estimator:batch",      # ResilientCheckpointHandler.batch_end
    "trainer:grad",         # gluon.Trainer.step, before allreduce/update
                            # (the only site implementing the 'nan' kind)
    "serve:execute",        # serve.engine.InferenceSession.run, inside
                            # the watchdog window (a 'delay' fault models
                            # a hung execution and must trip the timeout)
    "serve:queue",          # serve.batcher.DynamicBatcher.submit, before
                            # admission — the error surfaces synchronously
                            # on the submitter (a failed admission path),
                            # a 'delay' models a slow admission stall
    "serve:decode",         # serve.generate.Generator.decode_step, once
                            # per T=1 decode step — kills a generation
                            # stream mid-decode (prefill is covered by
                            # serve:execute)
    "collective:barrier",   # dist_tpu.barrier, before the psum — the one
                            # collective that could previously hang
                            # forever un-instrumented (now under the
                            # MXNET_COLLECTIVE_TIMEOUT watchdog)
    "trainer:param",        # gluon.Trainer.step, after the optimizer
                            # update — implements 'param_corrupt' (drifts
                            # one replica's parameter copies; the desync
                            # audit's injection point)
    "trainer:replica_step", # elastic.ElasticBatchProcessor, once per
                            # replica per batch with info={"replica": i}
                            # — 'replica_delay' here lags exactly one
                            # replica's forward/backward (the straggler
                            # the per-replica step clock must catch)
    "replica:dispatch",     # serve.replica.Replica.submit, before the
                            # request enters the replica's batcher, with
                            # info={"replica": i} — a 'die' here is a
                            # serving-replica death at dispatch time (the
                            # Router marks the replica dead and fails the
                            # request over to a survivor); 'transient'/
                            # 'fatal' model flaky dispatch RPCs
    "trainer:sharded_step", # parallel.functional.ShardedTrainer.step,
                            # before the compiled SPMD step dispatches —
                            # a coordinate-addressed 'chip_loss' here is
                            # the composed-mesh (dp×tp) kill the elastic
                            # rebuild-and-reshard path recovers from
    "ckpt:write",           # resilience.checkpoint write path, once per
                            # container (each shard, then the manifest)
                            # BEFORE its atomic write, with info=
                            # {"path", "shard"} — a 'die' here is a crash
                            # mid-shard-sequence (the manifest never
                            # lands, last-good stands); a 'torn' marker
                            # makes the writer land truncated bytes at
                            # the FINAL name (the bit-rot / partial-sync
                            # state os.replace normally rules out), so
                            # the CRC-quarantine rollback is testable
    "io:read",              # io.pipeline decode workers, once per record
                            # read, with info={"shard", "entry"} — a
                            # 'transient'/'fatal' or 'torn' marker makes
                            # the worker SKIP that record and bump the
                            # resilience.io_records_quarantined counter
                            # (a torn record must never crash the
                            # pipeline); a 'die' kills the worker thread
                            # mid-range (the range is requeued and the
                            # pool respawns a replacement — exactly-once
                            # delivery either way)
    "preempt:deliver",      # resilience.preemption.PreemptionHandler,
                            # once per batch with info={"batch": n} — a
                            # 'preempt' marker is an injected SIGTERM-
                            # equivalent: the training loop finishes the
                            # step, force-saves and stops exactly as if
                            # the real signal had arrived
)


class TransientFaultError(MXNetError):
    """Injected error the retry layer classifies as retryable."""


class InjectedFaultError(MXNetError):
    """Injected error classified fatal (never retried)."""


class ChipLostError(MXNetError):
    """Injected dead-chip analog: the device group ``replica`` dropped off
    the mesh mid-collective. Never retried (the chip is gone, not busy);
    ``dist_tpu`` classifies it as mesh loss when ``MXNET_ELASTIC=1``.

    ``device`` optionally addresses the dead chip by mesh coordinate — a
    flat device index (int) or ``{"axis": ..., "index": ...}`` — for
    composed dp×tp(×pp) meshes where a replica index alone cannot name
    the loss; :func:`~..parallel.mesh.rebuild_mesh` consumes either
    form."""

    def __init__(self, msg, replica=0, device=None):
        super().__init__(msg)
        self.replica = int(replica)
        self.device = device


class SimulatedWorkerDeath(BaseException):
    """Simulated whole-worker crash (SIGKILL analog, testable in-process).

    Deliberately a ``BaseException``: the framework's defensive ``except
    Exception`` blocks must not be able to 'survive' a worker death —
    only a checkpoint/resume cycle can.
    """


class FaultPlan:
    """A parsed, installed-once fault plan. Thread-safe; deterministic for
    a fixed seed and per-site hit order."""

    def __init__(self, spec):
        if isinstance(spec, FaultPlan):
            spec = spec.spec
        if isinstance(spec, str):
            spec = _parse_spec_str(spec)
        if not isinstance(spec, dict) or "rules" not in spec:
            raise MXNetError(
                "fault plan must be a dict with a 'rules' list "
                "(or JSON / @file via MXNET_FAULT_PLAN)")
        self.spec = spec
        self.seed = int(spec.get("seed", 0))
        self._lock = threading.Lock()
        self._rules = []
        import random as _random

        for i, r in enumerate(spec["rules"]):
            site = r.get("site")
            kind = r.get("kind", "transient")
            if not site:
                raise MXNetError(f"fault rule {i} missing 'site'")
            if kind not in ("transient", "fatal", "delay", "die", "nan",
                            "chip_loss", "replica_delay", "param_corrupt",
                            "torn", "preempt"):
                raise MXNetError(f"fault rule {i}: unknown kind {kind!r}")
            triggers = [t for t in ("at", "times", "prob") if t in r]
            if len(triggers) != 1:
                # a typoed trigger key would otherwise parse into a rule
                # that silently never fires — a test built on it would
                # pass while injecting nothing
                raise MXNetError(
                    f"fault rule {i} ({site}): exactly one trigger of "
                    f"'at'/'times'/'prob' required, got {triggers or r}")
            device = r.get("device")
            if device is not None:
                if kind != "chip_loss":
                    raise MXNetError(
                        f"fault rule {i} ({site}): 'device' is only valid "
                        f"on chip_loss rules, not {kind!r}")
                if isinstance(device, dict):
                    if not isinstance(device.get("axis"), str) \
                            or "index" not in device:
                        raise MXNetError(
                            f"fault rule {i} ({site}): coordinate device "
                            "must be {'axis': <name>, 'index': <int>}, "
                            f"got {device!r}")
                    device = {"axis": device["axis"],
                              "index": int(device["index"])}
                else:
                    device = int(device)
            self._rules.append({
                "site": site,
                "kind": kind,
                "at": set(r["at"]) if "at" in r else None,
                "times": int(r["times"]) if "times" in r else None,
                "prob": float(r["prob"]) if "prob" in r else None,
                "seconds": float(r.get("seconds", 0.05)),
                "replica": int(r["replica"]) if "replica" in r else None,
                "device": device,
                "message": r.get("message"),
                # per-rule RNG: independent deterministic streams, immune
                # to other rules' draw counts
                "rng": _random.Random(self.seed * 1000003 + i),
                "hits": 0,       # how often the site matched this rule
                "fired": 0,      # how often it actually injected
            })
        # lock-free pre-filter: a hot site with no rule for it costs one
        # frozenset lookup, not a lock + rule scan per dispatch
        self._sites = frozenset(r["site"] for r in self._rules)
        self._match_all = "*" in self._sites

    def stats(self):
        """Per-rule ``{site, kind, hits, fired}`` — tests assert on this."""
        with self._lock:
            return [{"site": r["site"], "kind": r["kind"],
                     "hits": r["hits"], "fired": r["fired"]}
                    for r in self._rules]

    def fired_total(self):
        with self._lock:
            return sum(r["fired"] for r in self._rules)

    def check(self, site, info=None):
        """Evaluate every matching rule for one hit of ``site``; raises or
        sleeps per the first rule that fires. Non-raising kinds return a
        marker instead: ``"nan"`` tells a corruption-capable call site to
        poison its payload (all other callers ignore the return value)."""
        if not self._match_all and site not in self._sites:
            return
        action = None
        with self._lock:
            for r in self._rules:
                if r["site"] != site and r["site"] != "*":
                    continue
                if r["replica"] is not None and isinstance(info, dict) \
                        and "replica" in info \
                        and int(info["replica"]) != r["replica"]:
                    # replica-targeted rule at a per-replica site: other
                    # replicas' visits don't hit (so `at` indices count
                    # the TARGET replica's visits, deterministically)
                    continue
                idx = r["hits"]
                r["hits"] += 1
                fire = False
                if r["at"] is not None:
                    fire = idx in r["at"]
                elif r["times"] is not None:
                    fire = r["fired"] < r["times"]
                elif r["prob"] is not None:
                    fire = r["rng"].random() < r["prob"]
                if fire and action is None:
                    r["fired"] += 1
                    action = r
        if action is None:
            return
        kind = action["kind"]
        msg = action["message"] or (
            f"injected {kind} fault at {site} "
            f"(plan seed {self.seed})")
        _counters.incr("resilience.faults_injected")
        # the failing SITE lands in the flight-recorder ring: a later
        # escalation dump (breaker-open, watchdog) names what fired here
        _recorder.note("fault", site, {"kind": kind})
        if _prof.ENABLED:
            _prof.record_instant(f"resilience::fault({site})", "resilience",
                                 args={"kind": kind})
        if kind == "delay":
            time.sleep(action["seconds"])
            return
        if kind == "nan":
            return "nan"
        if kind == "torn":
            # the checkpoint writer lands deliberately truncated bytes at
            # the final name instead of the atomic tmp+rename sequence
            return {"kind": "torn"}
        if kind == "preempt":
            # the preemption guard treats this as a delivered SIGTERM
            return {"kind": "preempt"}
        if kind == "replica_delay":
            # the replica filter above already scoped this hit to the
            # target replica (or the site carries no replica info)
            time.sleep(action["seconds"])
            return {"kind": "replica_delay",
                    "replica": action["replica"] or 0,
                    "seconds": action["seconds"]}
        if kind == "param_corrupt":
            return {"kind": "param_corrupt",
                    "replica": action["replica"] or 0}
        if kind == "chip_loss":
            where = (f"device {action['device']}"
                     if action["device"] is not None
                     else f"device group {action['replica'] or 0}")
            raise ChipLostError(
                action["message"] or
                f"injected chip loss at {site}: {where} dropped off the "
                f"mesh (plan seed {self.seed})",
                replica=action["replica"] or 0,
                device=action["device"])
        if kind == "transient":
            raise TransientFaultError(msg)
        if kind == "die":
            raise SimulatedWorkerDeath(msg)
        raise InjectedFaultError(msg)


# -- installation -----------------------------------------------------------

_active: FaultPlan | None = None
_env_checked = False
_install_lock = threading.Lock()

# instrumented modules whose _FAULTS slot mirrors the active plan
_SLOT_MODULES = (
    "mxnet_tpu.ops.registry",
    "mxnet_tpu.cachedop",
    "mxnet_tpu.engine",
    "mxnet_tpu.kvstore.dist_tpu",
    "mxnet_tpu.gluon.trainer",
)


def _parse_spec_str(s):
    s = s.strip()
    if s.startswith("@"):
        with open(s[1:]) as f:
            s = f.read()
    try:
        return json.loads(s)
    except ValueError as e:
        raise MXNetError(f"MXNET_FAULT_PLAN is not valid JSON: {e}") from None


def _poke_slots(value):
    import importlib
    import sys

    for name in _SLOT_MODULES:
        mod = sys.modules.get(name)
        if mod is None:
            # import so late installs still reach every site; these are
            # all part of the core package and cheap once jax is up
            try:
                mod = importlib.import_module(name)
            except Exception as e:
                # never silent: an unpoked slot means that site injects
                # NOTHING — a test asserting on it would pass vacuously
                import warnings

                warnings.warn(
                    f"fault plan cannot reach site module {name} "
                    f"({type(e).__name__}: {e}); faults for its sites "
                    "will not fire", RuntimeWarning, stacklevel=3)
                continue
        setattr(mod, "_FAULTS", value)


def install_plan(spec) -> FaultPlan:
    """Install ``spec`` (dict / JSON string / ``@file`` / FaultPlan) as THE
    process-wide fault plan, replacing any previous one."""
    global _active
    plan = spec if isinstance(spec, FaultPlan) else FaultPlan(spec)
    with _install_lock:
        _active = plan
        _poke_slots(plan)
    return plan


def clear_plan():
    """Remove the active fault plan (all sites return to zero-cost)."""
    global _active, _env_checked
    with _install_lock:
        _active = None
        _env_checked = True  # explicit clear also disables env re-install
        _poke_slots(None)


def get_plan() -> FaultPlan | None:
    """The active plan; installs ``MXNET_FAULT_PLAN`` from the env on the
    first call if nothing was installed programmatically."""
    global _env_checked
    if _active is None and not _env_checked:
        with _install_lock:
            _env_checked = True
        from .. import config

        raw = config.get("MXNET_FAULT_PLAN")
        if raw:
            install_plan(raw)
    return _active


def fault_point(site, info=None):
    """Module-level convenience: evaluate ``site`` against the active plan
    (used by call sites that don't keep their own slot). Forwards
    :meth:`FaultPlan.check`'s marker return (``"nan"``)."""
    plan = get_plan()
    if plan is not None:
        return plan.check(site, info)
    return None
