"""What every driver shares: the cell's description, loading a file of
the benchmark by its name, the look for the chip, the compile cache, the
profiler, the comparison that decides ``correct`` and the result line.
"""
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(**fields):
    """An earlier line of standard output: facts of the run, for a reader."""
    print(json.dumps(fields), flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """The module ``chipbench/<kind>/<name>.py``. A name may hold dots
    (``step_mfu.train``), so it is loaded by its path."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"chipbench/{kind}/{name}.py is named by BENCHMARK.json or a "
            "configuration and is not there")
    if HERE not in sys.path:
        sys.path.insert(0, HERE)   # so that drivers import their siblings
    mod_name = f"chipbench_{kind}_{name}".replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def count_fn(config, key):
    """The function of operations or bytes that the configuration's
    ``flops`` group names under ``key`` (``flops.py`` or a module beside it)."""
    spec = config["flops"]
    return getattr(load_module(".", spec["module"]), spec[key])


def merged(base, over):
    """``base`` with ``over`` laid on it, nested groups merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic mix
    and the metrics it has to report, all read from files."""

    def __init__(self, bench, workload, seed, seconds, trace, rehearse, t0):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"chipbench: no workload {workload!r} in "
                             f"BENCHMARK.json (has {sorted(cells)})")
        self.workload = cells[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace, self.rehearse, self.t0 = bool(trace), bool(rehearse), t0
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", self.workload["traffic"] + ".json")
        self.limits = load_json("limits", workload + ".json")
        if rehearse:
            self.limits = merged(self.limits, self.limits.get("rehearse", {}))
            self.config = merged(self.config, self.config.get("rehearse", {}))
            self.traffic = merged(self.traffic,
                                  self.traffic.get("rehearse", {}))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if self._reports(m)]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if (workload in m["workloads"] if "workloads" in m
                              else m["moves"] in moved)]

    def _reports(self, metric):
        return "workloads" not in metric or self.name in metric["workloads"]


def find_devices(cell):
    """The chips of this run, or None where the machine has not got them:
    the benchmark never falls back to the host."""
    import jax

    devices = jax.devices()
    if not cell.rehearse and devices[0].platform != "tpu":
        print(f"chipbench: no TPU here (found {devices[0].platform}); the "
              "benchmark does not fall back to the host", file=sys.stderr)
        return None
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chip(s), found "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices[:cell.chips]


def peaks_for(device):
    table = load_json("peaks.json")
    if device.device_kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind "
                         f"{device.device_kind!r} in chipbench/peaks.json")
    return table[device.device_kind]


def enable_compile_cache():
    """Where ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``:
    a fixed path, because the path is part of the cache's key."""
    from mxnet_tpu import compile_cache

    compile_cache.enable(os.path.join(ROOT, ".jax_cache"))
    return compile_cache


def memory_peak(devices):
    """Peak bytes held on the fullest chip, as the backend reports them:
    what live arrays took at their peak, and what the runtime reserved
    beside them (on a TPU the compiled programs' temporaries are kept in
    a reserved region that ``peak_bytes_in_use`` leaves out)."""
    def held(d):
        s = d.memory_stats() or {}
        return s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)

    return int(max(held(d) for d in devices))


def memory_stats(device):
    return {k: int(v) for k, v in (device.memory_stats() or {}).items()
            if "bytes" in k}


def load_weights(net, name_map, maker):
    """Give every parameter of the program's ``net`` the benchmark's
    weights, a group (a layer) at a time so that a deep model never holds
    two copies."""
    from mxnet_tpu.ndarray.ndarray import NDArray

    params = net.collect_params()
    by_ref = {ref: params[prog] for prog, ref in name_map.items()}
    for gid in sorted(maker.groups):
        for ref_name, arr in maker.group(gid).items():
            by_ref.pop(ref_name).set_data(NDArray(arr))
    if by_ref:
        raise AssertionError(f"parameters left without weights: "
                             f"{sorted(by_ref)}")


class Tracer:
    """JAX's profiler around the traced window, written under
    ``chipbench/.trace/<workload>/`` (a fixed path, emptied first)."""

    def __init__(self, cell):
        self.dir = os.path.join(HERE, ".trace", cell.name)
        self.on = cell.trace

    def start(self):
        if not self.on:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        jax.profiler.start_trace(self.dir)

    def stop(self):
        """Stop and return the path of the ``.xplane.pb`` written."""
        if not self.on:
            return None
        import glob

        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not found:
            raise AssertionError(f"the profiler wrote no trace to {self.dir}")
        return found[0]


def span(name):
    """A host span in the profiler's own trace (no cost when it is off)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Comparison:
    """The numbers compared, each beside its limit. ``correct`` is that
    every one lies at or under its limit."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit):
        self.rows.append((name, float(value), float(limit)))

    @property
    def correct(self):
        return bool(self.rows) and all(
            v == v and v <= lim for _, v, lim in self.rows)

    def as_dict(self):
        return {n: {"value": v, "limit": lim} for n, v, lim in self.rows}

    def print_tail(self):
        for n, v, lim in self.rows:
            flag = "ok" if v == v and v <= lim else "OVER"
            print(f"chipbench compared {n} = {v:.6g} limit {lim:.6g} {flag}",
                  file=sys.stderr)
        sys.stderr.flush()


def percentile(values, q):
    """The q-th percentile by linear interpolation (numpy's default)."""
    import numpy as np

    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def now():
    return time.perf_counter()
