"""Step-level training telemetry: samples/s, tokens/s, MFU, device memory.

The role of the reference profiler's per-epoch summary rows, grown to the
numbers the BENCH trajectory actually tracks: ``TrainingMetrics`` turns
step wall-times plus a FLOP estimate into an MFU figure against the local
chip's peak (the accounting ``bench.py`` headline rows use), and
``device_memory_stats`` surfaces ``jax.local_devices()[i].memory_stats()``
per device.  ``profiler.step_marker()`` marks step boundaries on a default
``TrainingMetrics`` and emits a ``train::step`` trace range while the
profiler runs.
"""
from __future__ import annotations

import collections
import os
import statistics
import time

from . import core

# per-chip peaks by jax device_kind prefix:
# (bf16 MXU flops/s, HBM bytes/s, ICI GB/s per link-direction pair).
# Longest-prefix entries first where prefixes overlap ("TPU v5 lite"
# before "TPU v5") — chip_peak matches in declaration order.
CHIP_PEAKS = {
    "TPU v4": (275e12, 1228e9, 100e9),
    "TPU v5 lite": (197e12, 819e9, 100e9),
    "TPU v5p": (459e12, 2765e9, 200e9),
    "TPU v5e": (197e12, 819e9, 100e9),
    "TPU v5": (459e12, 2765e9, 200e9),
    "TPU v6 lite": (918e12, 1640e9, 200e9),
    "TPU v6e": (918e12, 1640e9, 200e9),
}


def chip_peak(what):
    """Peak for the local chip: what = 'flops' | 'hbm' | 'ici'.
    None when the device kind is unknown (e.g. CPU test runs)."""
    import jax

    kind = jax.devices()[0].device_kind
    for k, v in CHIP_PEAKS.items():
        if kind.startswith(k):
            return v[{"flops": 0, "hbm": 1, "ici": 2}[what]]
    return None


def peak_flops():
    """MFU denominator: MXNET_TPU_PEAK_FLOPS override, else by device_kind."""
    env = os.environ.get("MXNET_TPU_PEAK_FLOPS")
    if env:
        return float(env)
    return chip_peak("flops")


def process_peak_bytes_in_use():
    """Max allocator peak over the local devices — since PROCESS start
    (jax never resets it), so an upper bound on the current workload's
    footprint. 0 on backends that don't report (CPU)."""
    return max((m.get("peak_bytes_in_use", 0)
                for m in device_memory_stats()), default=0)


def device_memory_stats(device_index=None):
    """Per-device ``memory_stats()`` dicts (``bytes_in_use``,
    ``peak_bytes_in_use``, ... on TPU; ``{}`` on backends that don't
    report, e.g. CPU). One dict per ``jax.local_devices()`` entry, each
    tagged with its device string."""
    import jax

    out = []
    for d in jax.local_devices():
        try:
            ms = d.memory_stats() or {}
        except Exception:
            ms = {}
        out.append({"device": str(d), **ms})
    if device_index is not None:
        return out[device_index]
    return out


class TrainingMetrics:
    """Aggregates per-step wall times into throughput and MFU.

    ``flops_per_step`` is the FLOP estimate of one training step (e.g.
    XLA ``cost_analysis()['flops']`` of the compiled step — what
    ``bench.py`` feeds in); ``samples_per_step`` / ``tokens_per_step``
    are the per-step batch sizes.  Rates use the MEDIAN step time (robust
    to host-side outliers, matching bench.py's two-loop-difference
    methodology); totals are kept too for long-run accounting.
    """

    def __init__(self, flops_per_step=None, samples_per_step=None,
                 tokens_per_step=None, peak_flops=None, window=1024):
        self.flops_per_step = flops_per_step
        self.samples_per_step = samples_per_step
        self.tokens_per_step = tokens_per_step
        self.peak_flops = peak_flops
        self.steps = 0
        self.total_time_s = 0.0
        self.total_samples = 0
        self.total_tokens = 0
        self.total_flops = 0.0
        self._durations = collections.deque(maxlen=window)
        self._t_last_ns = None

    # -- recording --------------------------------------------------------
    def record_step(self, duration_s, samples=None, tokens=None, flops=None):
        """Record one completed step of ``duration_s`` seconds."""
        self.steps += 1
        self.total_time_s += duration_s
        self._durations.append(duration_s)
        s = samples if samples is not None else self.samples_per_step
        if s:
            self.total_samples += s
        t = tokens if tokens is not None else self.tokens_per_step
        if t:
            self.total_tokens += t
        f = flops if flops is not None else self.flops_per_step
        if f:
            self.total_flops += f

    def step_marker(self, samples=None, tokens=None, flops=None):
        """Mark a step boundary; the first call starts the clock, each
        subsequent call records the inter-marker duration. Returns the
        step duration in seconds (None on the first call)."""
        now = time.perf_counter_ns()
        t_last, self._t_last_ns = self._t_last_ns, now
        if t_last is None:
            return None
        self.record_step((now - t_last) / 1e9, samples, tokens, flops)
        if core.ENABLED:
            core.record_duration("train::step", "metrics", t_last, now,
                                 args={"step": self.steps})
        return (now - t_last) / 1e9

    def reset(self):
        self.steps = 0
        self.total_time_s = 0.0
        self.total_samples = 0
        self.total_tokens = 0
        self.total_flops = 0.0
        self._durations.clear()
        self._t_last_ns = None

    # -- derived numbers --------------------------------------------------
    @property
    def median_step_s(self):
        if not self._durations:
            return None
        return statistics.median(self._durations)

    def _rate(self, per_step, total):
        dt = self.median_step_s
        if per_step and dt:
            return per_step / dt
        if total and self.total_time_s > 0:
            return total / self.total_time_s
        return None

    @property
    def samples_per_sec(self):
        return self._rate(self.samples_per_step, self.total_samples)

    @property
    def tokens_per_sec(self):
        return self._rate(self.tokens_per_step, self.total_tokens)

    @property
    def mfu(self):
        """Model FLOP utilization: flops_per_step / (median step time *
        chip peak). None without a FLOP estimate or a known peak."""
        peak = self.peak_flops or peak_flops()
        dt = self.median_step_s
        f = self.flops_per_step
        if not f and self.steps:
            f = self.total_flops / self.steps
        if not (peak and dt and f):
            return None
        return f / (dt * peak)

    def memory(self):
        return device_memory_stats()

    def summary(self):
        """One JSON-able dict with every derived figure (what bench rows
        consume)."""
        dt = self.median_step_s
        peak_mem = process_peak_bytes_in_use()
        return {
            "steps": self.steps,
            "median_step_ms": round(dt * 1e3, 4) if dt else None,
            "samples_per_sec": self.samples_per_sec,
            "tokens_per_sec": self.tokens_per_sec,
            "mfu": self.mfu,
            "peak_flops": self.peak_flops or peak_flops(),
            "process_peak_bytes_in_use": peak_mem or None,
        }


_default_metrics = TrainingMetrics()


def training_metrics() -> TrainingMetrics:
    """The process-default TrainingMetrics fed by ``step_marker()``."""
    return _default_metrics


def step_marker(samples=None, tokens=None, flops=None, metrics=None):
    """Mark a training-step boundary (module-level convenience over
    :class:`TrainingMetrics`). Returns the step duration in seconds, or
    None on the first call."""
    return (metrics or _default_metrics).step_marker(
        samples=samples, tokens=tokens, flops=flops)
