#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

reads the cell from ``BENCHMARK.json`` and finds by name its
configuration, traffic mix, driver, reference and per-layer metric
readers (see README.md). The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, with ``--trace 1``, ``breakdown``; ``compared`` comes last. Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result. ``--rehearse`` runs the same code at the tiny sizes of
the files' ``rehearse`` groups on whatever platform is there, and says so.
"""
import time

T0 = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402


def layer_metrics(cell, trace, counters, record):
    """Every per-layer metric of the cell whose reader found something."""
    out = {}
    for m in cell.per_layer:
        reader = harness.load_module("layer_metrics", m["name"])
        value = reader.read(trace, counters, record)
        if value is None:
            continue
        value = float(value)
        name = m["name"]
        if m["unit"] == "%" and ("roofline" in name or "mfu" in name) \
                and value > 100.0:
            raise AssertionError(
                f"{name} reads {value:.2f}%: over 100% of a peak, so its "
                "operations or bytes are counted too high or its time "
                "leaves out part of the work")
        out[name] = {"value": value, "unit": m["unit"]}
    return out


def run(argv=None, t0=T0):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform: no device number "
                         "from such a run means anything")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.Cell(bench, args.workload, args.seed, args.seconds,
                        args.trace, args.rehearse, t0)

    import mxnet_tpu  # noqa: F401  (fails in a directory without the program)

    devices = harness.find_devices(cell)
    if devices is None:
        return 2
    cell.devices = devices
    cell.peaks = None if cell.rehearse and devices[0].platform != "tpu" \
        else harness.peaks_for(devices[0])
    cell.compile_cache = harness.enable_compile_cache()
    harness.say(phase="start", workload=cell.name, seed=cell.seed,
                seconds=cell.seconds, trace=cell.trace,
                rehearse=cell.rehearse, platform=devices[0].platform,
                import_s=round(time.perf_counter() - t0, 2))

    driver = harness.load_module("drivers", cell.config["driver"])
    record = driver.run(cell)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    result = {"correct": record["compared"].correct,
              "attempted": record["attempted"], "failed": record["failed"],
              "device": device}
    if record["failed"]:
        result["correct"] = False
    if cell.trace:
        trace = record["trace"]
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["metrics"] = layer_metrics(cell, trace, record["counters"],
                                          record)
        result["breakdown"] = trace.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {
            n: {"value": float(record["end_to_end"][n]), "unit": u}
            for n, u in units.items()}
    result["compared"] = record["compared"].as_dict()
    sys.stdout.flush()
    record["compared"].print_tail()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
