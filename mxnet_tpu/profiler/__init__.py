"""Runtime telemetry subsystem (reference: ``python/mxnet/profiler.py``
over ``src/profiler/``: chrome://tracing JSON, ``aggregate_stats`` tables,
``dumps()``/``get_summary()``).

Three layers:

* ``core``    — the in-process event bus: scoped ranges, counters, the
  chrome://tracing export (:func:`dump`) and the aggregate table
  (:func:`dumps`). Instrumentation hooks in ``cachedop.py`` (compile
  timing, cache hit/miss, recompile-storm warning), ``engine.py`` (wait
  stalls, async queue depth, bulk sizes), ``kvstore/dist_tpu.py``
  (allreduce timing/bytes, AOT-compile split) and ``ops/registry.py``
  (per-op call counters under ``profile_imperative``) feed it. All hooks
  are near-zero-cost while stopped: a module-level bool guard per site.
* ``metrics`` — step-level training numbers: :func:`step_marker`,
  :class:`TrainingMetrics` (samples/s, tokens/s, MFU from a FLOP
  estimate), :func:`device_memory_stats`; ``bench.py`` consumes these.
* ``xla``     — ``jax.profiler`` capture (opt-in via
  ``set_config(profile_xla=True)``) and the per-op DEVICE-time tables
  :func:`device_op_stats` / :func:`device_op_table`.

Plus three observability layers over the bus (OBSERVABILITY.md):

* ``trace``    — request-scoped tracing (``MXNET_TRACE=1``): serving
  submits and training steps become chrome async/flow lanes connected by
  trace id across threads; ``trace.summary(trace_id)`` in-process.
* ``recorder`` — the always-on flight recorder (``MXNET_FLIGHT_RECORDER``,
  default on): a bounded ring of recent faults/sheds/warnings dumped to
  JSON automatically at escalation points (DivergenceError, MeshDegraded,
  quarantine, breaker-open, watchdog timeout).
* ``export``   — one ``snapshot()`` merging every subsystem's telemetry,
  rendered as Prometheus text and optionally served over HTTP
  (``MXNET_METRICS_PORT``): /metrics, /healthz, /snapshot.

Env vars (registered in ``mx.config``): ``MXNET_PROFILER_AUTOSTART=1``
starts the bus at import, ``MXNET_PROFILER_IMPERATIVE=1`` opts into per-op
dispatch counters, ``MXNET_CACHEDOP_SIG_LIMIT`` sets the recompile-storm
threshold.
"""
from __future__ import annotations

import contextlib
import time

from ..base import MXNetError
from . import attribution, core, export, metrics, recorder, slo, trace, xla
from .core import (aggregate_stats, register_thread_name, reset,
                   snapshot_events)
from .metrics import (
    TrainingMetrics,
    chip_peak,
    device_memory_stats,
    peak_flops,
    process_peak_bytes_in_use,
    step_marker,
    training_metrics,
)
from .xla import device_op_stats, device_op_table

_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_imperative": False,
    "profile_xla": False,
    "aggregate_stats": False,
}


def set_config(filename="profile.json", profile_all=False,
               profile_symbolic=True, profile_imperative=False,
               profile_memory=True, profile_api=True,
               aggregate_stats=False, profile_xla=False,
               **kwargs):  # pylint: disable=unused-argument
    """Configure output + capture scope (reference
    ``MXSetProcessProfilerConfig``). Divergences from the reference
    defaults, both deliberate: ``profile_imperative`` defaults to False
    (per-op dispatch counters cost a dict increment per eager call) and
    ``profile_xla=True`` opts into a ``jax.profiler`` device capture
    alongside the host event bus."""
    _config["filename"] = filename
    _config["profile_all"] = profile_all
    _config["profile_imperative"] = bool(profile_imperative or profile_all)
    _config["profile_xla"] = bool(profile_xla or profile_all)
    _config["aggregate_stats"] = aggregate_stats
    if core.ENABLED:
        core.IMPERATIVE = _config["profile_imperative"]


def _install_hooks():
    """Point the hot modules' ``_PROF`` slot at the event bus. Until the
    first ``set_state('run')`` those slots are ``None`` — a session that
    never profiles pays one ``is None`` test per dispatch site."""
    from .. import engine as _engine
    from ..ops import registry as _registry

    _engine._PROF = core
    _registry._PROF = core
    # phase-tagged engine:wait events need the attribution module's
    # thread-local phase even when the ledger itself is off
    _engine._ATTR = attribution


def set_state(state="stop", profile_process="worker"):  # pylint: disable=unused-argument
    """'run' starts the event bus (+ a jax.profiler capture when
    ``profile_xla``); 'stop' halts recording."""
    if state == "run":
        if not core.ENABLED:
            _install_hooks()
            core.start()
        core.IMPERATIVE = _config["profile_imperative"]
        # started even when the bus already runs (e.g. autostart before a
        # later set_config(profile_xla=True); set_state('run'))
        if _config["profile_xla"] and not xla.is_tracing():
            try:
                xla.start_trace(_config["filename"])
            except Exception:  # device capture is best-effort
                pass
    elif state == "stop":
        core.stop()
        xla.stop_trace()
    else:
        raise MXNetError(f"invalid profiler state {state!r}")


def state():
    return "run" if core.ENABLED else "stop"


def pause(profile_process="worker"):  # pylint: disable=unused-argument
    """Suspend recording without finalizing (reference ``MXProfilePause``).
    An active jax.profiler capture is finalized too — jax has no pause, so
    the device trace is closed out (resume() starts a fresh one)."""
    core.ENABLED = False
    core.IMPERATIVE = False
    xla.stop_trace()


def resume(profile_process="worker"):  # pylint: disable=unused-argument
    _install_hooks()
    core.ENABLED = True
    core.IMPERATIVE = _config["profile_imperative"]
    if _config["profile_xla"] and not xla.is_tracing():
        try:
            xla.start_trace(_config["filename"])
        except Exception:
            pass


def dump(finished=True, profile_process="worker"):  # pylint: disable=unused-argument
    """Write the chrome://tracing JSON to the configured filename and
    return its path (reference ``MXDumpProfile``). ``finished=True`` also
    stops an active capture first."""
    if finished:
        if core.ENABLED:
            set_state("stop")
        else:
            xla.stop_trace()  # paused session: finalize the device capture
    return core.dump(_config["filename"])


def dumps(reset=False):  # pylint: disable=redefined-outer-name
    """Aggregate host-side table: ranges by total time, imperative per-op
    call counts, counter gauges (reference
    ``MXAggregateProfileStatsPrint``)."""
    return core.dumps_table(reset_after=reset)


def get_summary(reset=False):  # pylint: disable=redefined-outer-name
    """Reference ``get_summary()``: the aggregate table as a string."""
    return core.dumps_table(reset_after=reset)


@contextlib.contextmanager
def scope(name="<unk>:", cat="scope"):
    """Named range: lands in the aggregate table always, in the chrome
    trace when running, and in the XLA device trace when one is active."""
    t0 = time.perf_counter_ns()
    with core.host_span(name):
        yield
    core.record_duration(name, cat, t0)


class Task:
    """API-parity profiler objects (reference ``profiler.Task/Frame/
    Event``): named ranges you start/stop by hand."""

    def __init__(self, domain=None, name="task"):
        self.name = name
        self._t0 = None
        self._ann = None

    def start(self):
        self._t0 = time.perf_counter_ns()
        self._ann = core.host_span(self.name)
        self._ann.__enter__()

    def stop(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            core.record_duration(self.name, "task", self._t0)
            self._ann = None


Frame = Task
Event = Task


class Domain:
    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, f"{self.name}::{name}")

    def new_counter(self, name, value=0):
        return Counter(self, name, value)


class Counter:
    """Named counter; values land in the event bus as gauge events
    (reference ``profiler.Counter``)."""

    def __init__(self, domain=None, name="counter", value=0):
        self.name = name if domain is None else f"{domain.name}::{name}"
        self.value = value
        core.set_counter(self.name, value)

    def set_value(self, value):
        self.value = value
        core.set_counter(self.name, value)

    def increment(self, delta=1):
        self.set_value(self.value + delta)

    def decrement(self, delta=1):
        self.set_value(self.value - delta)


def start_server(*a, **k):  # pragma: no cover
    raise MXNetError("profiler server mode has no TPU analog; use "
                     "jax.profiler.start_server for live TensorBoard capture")


# MXNET_PROFILER_AUTOSTART: begin recording at import (the reference's
# profile_process-wide autostart env contract)
from .. import config as _cfg  # noqa: E402

if _cfg.get("MXNET_PROFILER_AUTOSTART"):
    set_config(profile_imperative=_cfg.get("MXNET_PROFILER_IMPERATIVE"))
    set_state("run")
elif _cfg.get("MXNET_PROFILER_IMPERATIVE"):
    set_config(profile_imperative=True)

# MXNET_TRACE=1: request-scoped tracing on from import (spans only land
# as chrome events while the bus records, but summaries work regardless)
if _cfg.get("MXNET_TRACE"):
    trace.enable(max_traces=_cfg.get("MXNET_TRACE_MAX"))

# MXNET_ATTRIBUTION=1: decode critical-path ledger on from import
if _cfg.get("MXNET_ATTRIBUTION"):
    attribution.enable()

# MXNET_METRICS_PORT=<p>: unified /metrics + /healthz endpoint at import
export.maybe_start_from_env()
