#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; no benchmark run
calls this.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 [--seconds 12] [--faults 1]

For each seed, in one process: the program's numbers against the plain
reference (the lower readings), the control's (the reference put in the
program's place at the nearest precision below the configuration's) and,
for a training cell with ``--faults 1``, the faults planted in the
reference (half of the batch left out; on several chips the exchange left
out, which leaves one chip's rows). One JSON line a seed on standard
output, and ``chiprun_out/control_<workload>.jsonl`` beside it.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402


class ReferenceOnly:
    """What ``train.reference_numbers`` needs of a program, without the
    program: the control and the faults are planted in the reference, so
    a four-chip cell's can be read on one chip."""

    def __init__(self, cell):
        import traffic
        import weights

        cfg = cell.config
        self.ref = harness.load_module("reference", cfg["reference"])
        self.maker = weights.Maker(self.ref.param_shapes(cfg), cell.seed,
                                   cfg["initializer_range"])
        self.pool = traffic.batches(cell.traffic, cell.seed,
                                    cfg["vocab_size"], cell.chips)


def train_readings(cell, faults, reference_only=False, controls=True):
    train = harness.load_module("drivers", "train")
    steps = cell.traffic["checked_steps"]
    if reference_only:
        prog, got = ReferenceOnly(cell), None
    else:
        prog = train.Program(cell)
        got = prog.first_steps(steps)
        prog.free()
    ref = train.reference_numbers(cell, prog, steps)

    def read(numbers):
        where = train.compare(harness.Comparison(), {}, numbers, ref,
                              prog.ref.diff_norms)
        return {**where.pop("read"), **where}

    out = {"still_leaves": sorted(train.still_leaves(ref["grad_norms"])),
           "reference_losses": ref["losses"]}
    if got is not None:
        out.update(program=read(got), losses=got["losses"])
    for name, num in prog.ref.controls(cell.config["precision"]).items() \
            if controls else ():
        out["control_" + name] = read(
            train.reference_numbers(cell, prog, steps, num=num))
    if faults:
        rows = prog.pool[0][0].shape[0]
        out["fault_half_batch"] = read(train.reference_numbers(
            cell, prog, steps, take_rows=slice(0, rows // 2)))
        out["fault_state_unchanged"] = read(train.reference_numbers(
            cell, prog, steps, learning_rate=0.0))
        if cell.chips > 1:
            out["fault_no_exchange"] = read(train.reference_numbers(
                cell, prog, steps, take_rows=slice(0, rows // cell.chips)))
    return out


def serve_readings(cell, faults, reference_only=False, controls=True):
    serve = harness.load_module("drivers", "serve_closed")
    ref = harness.load_module("reference", cell.config["reference"])
    rec = serve.run(cell, control=ref.controls(cell.config["precision"])
                    if controls else None)
    out = {"program": {n: v for n, v, _ in rec["compared"].rows},
           "failed": rec["failed"], "attempted": rec["attempted"],
           "end_to_end": rec["end_to_end"]}
    for name, gap in rec["control_gaps"].items():
        out["control_" + name] = {"served_token_logit_gap_max": gap}
    return out


READ = {"train": train_readings, "serve_closed": serve_readings}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--reference-only", type=int, default=0,
                    help="training: no program, so any cell on one chip")
    ap.add_argument("--controls", type=int, default=1,
                    help="0: the program against the reference alone (the "
                         "lower readings), where the control is already read")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    import mxnet_tpu  # noqa: F401

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"control_{args.workload}.jsonl")
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(bench, args.workload, seed, args.seconds, False,
                            args.rehearse, T0)
        chips = cell.chips
        if args.reference_only:
            cell.chips = 1      # the look for chips only; rows stay the cell's
        cell.devices = harness.find_devices(cell)
        cell.chips = chips
        if cell.devices is None:
            return 2
        cell.peaks = None
        cell.compile_cache = harness.enable_compile_cache()
        t = time.perf_counter()
        row = {"workload": args.workload, "seed": seed,
               **READ[cell.config["driver"]](cell, args.faults,
                                             args.reference_only,
                                             bool(args.controls))}
        row["seconds"] = round(time.perf_counter() - t, 1)
        line = json.dumps(row)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
