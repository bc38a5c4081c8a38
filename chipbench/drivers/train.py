"""Training cells: ``ShardedTrainer.step`` over a ``dp`` mesh of the
cell's chips, one ``jax.device_put`` and one ``trainer.step`` a step.

Set-up builds one trainer, drives it through its first steps on rows that
all differ (these are the steps the plain reference follows), and hands
that same trainer to the window. The window keeps at most ``AHEAD``
steps in flight and ends in a fetch of the last loss.
"""
import gc
import statistics

import numpy as np

import harness
import traffic
import weights

AHEAD = 2   # steps dispatched before the oldest loss is fetched


def gaps(program, reference, skip=()):
    """The worst leaf's gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Returns (gap, leaf)."""
    floor = statistics.median(reference.values())
    worst, at = 0.0, None
    for n, r in reference.items():
        if n in skip:
            continue
        g = abs(program[n] - r) / max(r, floor)
        if not g <= worst:      # a NaN is the worst there is
            worst, at = g, n
    return worst, at


def still_leaves(grad_norms):
    """Leaves whose gradient is nought to rounding in the reference (a
    key's bias under softmax): under a thousandth of the median leaf's.
    Under Adam they move by round-off alone, so their change is not
    compared."""
    floor = 1e-3 * statistics.median(grad_norms.values())
    return {n for n, g in grad_norms.items() if g < floor}


def compare(comp, limits, got, ref, diff_norms):
    """Read the training numbers: each checked step's loss, the first
    gradient's norm and the parameters' change by the worst leaf, and the
    first gradient's departure from the reference's, element by element
    (``diff_norms``, the reference module's), by the median leaf and by
    the worst. A number with a limit in the cell's file goes into
    ``comp``; the others (those no control or fault separates from a
    sound run, PERF.md section 2) are returned with the leaves at which
    the worst was read."""
    read = {}
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"]), 1):
        read[f"loss_step{i}_rel_gap"] = abs(a - b) / abs(b)
    read["grad_norm_worst_leaf_gap"], at = gaps(got["grad_norms"],
                                                ref["grad_norms"])
    read["change_norm_worst_leaf_gap"], at_c = gaps(
        got["change_norms"], ref["change_norms"],
        skip=still_leaves(ref["grad_norms"]))
    floor = statistics.median(ref["grad_norms"].values())
    off = {n: d / max(ref["grad_norms"][n], floor) for n, d in diff_norms(
        {n: np.asarray(a) for n, a in got["first_grads"].items()},
        ref["first_grads"]).items()}
    at_d = max(off, key=off.get)
    read["grad_diff_median_leaf"] = statistics.median(off.values())
    read["grad_diff_worst_leaf"] = off[at_d]
    for name, value in read.items():
        if name in limits:
            comp.add(name, value, limits[name])
    return {"read": read, "grad_leaf": at, "change_leaf": at_c,
            "diff_leaf": at_d}


class Program:
    """The trainer with its feed: what set-up checks and the window times."""

    def __init__(self, cell):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from mxnet_tpu.parallel import ShardedTrainer, ShardingRules, \
            make_mesh

        cfg, tr = cell.config, cell.traffic
        self.adapter = harness.load_module("adapters", cfg["adapter"])
        self.ref = harness.load_module("reference", cfg["reference"])
        self.names = self.adapter.name_map(cfg)
        self.maker = weights.Maker(self.ref.param_shapes(cfg), cell.seed,
                                   cfg["initializer_range"])
        net, loss_fn = self.adapter.build(
            cfg, cell.devices[0].platform == "tpu")
        harness.load_weights(net, self.names, self.maker)
        mesh = make_mesh({"dp": cell.chips}, devices=list(cell.devices))
        t = cfg["train"]
        if t["optimizer"] != "adam" or t["weight_decay"]:
            raise ValueError("the train driver runs plain Adam")
        self.trainer = ShardedTrainer(
            net, loss_fn, "adam",
            {"learning_rate": t["learning_rate"], "beta1": t["beta1"],
             "beta2": t["beta2"], "epsilon": t["epsilon"]},
            mesh=mesh, rules=ShardingRules(default_axis=None),
            dtype=cfg["precision"])
        self.sharding = NamedSharding(mesh, P("dp"))
        self.pool = traffic.batches(tr, cell.seed, cfg["vocab_size"],
                                    cell.chips)
        self.tokens_per_step = self.pool[0][0].size
        self._put = jax.device_put

    def step(self, i):
        """The window's own call and feed: batch ``i`` of the pool goes to
        the chips and through one ``trainer.step``; returns the loss, not
        yet fetched."""
        tokens, labels = self.pool[i % len(self.pool)]
        with harness.span("chipbench.device_put"):
            data = self._put(tokens, self.sharding)
            labels = tuple(self._put(a, self.sharding) for a in labels)
        with harness.span("chipbench.step"):
            return self.trainer.step(data, labels)

    def fetch(self, loss):
        with harness.span("chipbench.fetch"):
            return float(loss.asnumpy().reshape(-1)[0])

    def params_by_leaf(self):
        return {self.names[n]: a for n, a in self.trainer.params.items()}

    def first_steps(self, steps):
        """Drive the first ``steps`` steps and read what the reference is
        compared with: each loss, every leaf's norm of the first gradient
        as Adam got it (its first moment after one step is (1 - beta1)
        times that gradient), every leaf's norm of its change."""
        import jax
        import jax.numpy as jnp

        start = {n: jnp.copy(a) for n, a in self.params_by_leaf().items()}
        beta1 = self.trainer.optimizer.beta1
        losses, grad_norms, first = [], None, None
        for i in range(steps):
            losses.append(self.fetch(self.step(i)))
            if i == 0:
                moments = self.trainer.export_state()["opt_states"]
                first = {self.names[n]: np.asarray(st[0]) / (1 - beta1)
                         for n, st in moments.items()}
                grad_norms = {n: float(np.linalg.norm(
                    g.astype(np.float64).ravel())) for n, g in first.items()}
                del moments
        norms = jax.jit(lambda a, b: {
            n: jnp.sqrt(jnp.sum(jnp.square(a[n] - b[n]))) for n in a})
        change = norms(self.params_by_leaf(), start)
        return {"losses": losses, "grad_norms": grad_norms,
                "first_grads": first,
                "change_norms": {n: float(x) for n, x in change.items()}}

    def window(self, cell, tracer, first_step):
        """Steps for ``seconds`` (the traced window is shorter), at most
        ``AHEAD`` in flight, ending in a fetch of the last loss. Returns
        (steps, window seconds, t_open, last loss, trace file)."""
        seconds = min(cell.seconds, cell.traffic["trace_seconds"]) \
            if cell.trace else cell.seconds
        pending = []
        tracer.start()
        with harness.span("chipbench.window"):
            t_open = harness.now()
            n = 0
            while harness.now() - t_open < seconds:
                pending.append(self.step(first_step + n))
                n += 1
                if len(pending) > AHEAD:
                    self.fetch(pending.pop(0))
            last = [self.fetch(x) for x in pending][-1]
            t_close = harness.now()
        path = tracer.stop()
        return n, t_close - t_open, t_open, last, path

    def free(self):
        self.trainer = None
        gc.collect()


def reference_numbers(cell, prog, steps, num=None, take_rows=None,
                      learning_rate=None):
    """The plain reference's (or, with ``num``, the control's) first
    steps, from the benchmark's own weights and the same batches."""
    ref = prog.ref
    return ref.train_steps(
        prog.maker.all(), prog.pool[:steps], cell.config,
        cell.config["train"],
        rows_block=cell.traffic.get("reference_rows_block", 32),
        num=num or ref.EXACT, take_rows=take_rows,
        learning_rate=learning_rate)


def run(cell):
    tr = cell.traffic
    limits = cell.limits
    steps = tr["checked_steps"]
    prog = Program(cell)
    got = prog.first_steps(steps)
    misses_before = cell.compile_cache.stats()["disk_misses"]
    harness.say(phase="first_steps", losses=got["losses"],
                setup_s=round(harness.now() - cell.t0, 2))

    tracer = harness.Tracer(cell)
    n, window_s, t_open, last_loss, trace_path = prog.window(
        cell, tracer, steps)
    setup_s = t_open - cell.t0
    tokens = n * prog.tokens_per_step
    stats = cell.compile_cache.stats()
    counters = {"compile_cache": stats,
                "compiled_in_window": stats["disk_misses"] - misses_before}
    memory_peak = harness.memory_peak(cell.devices)
    harness.say(phase="window", steps=n, window_s=window_s, tokens=tokens,
                last_loss=last_loss, memory_peak_bytes=memory_peak,
                step_flops_compiler=prog.trainer.step_flops,
                memory=harness.memory_stats(cell.devices[0]), **counters)
    if not np.isfinite(last_loss):
        raise AssertionError(f"the window's last loss is {last_loss}")

    # the reference runs once the window has closed and the program's
    # state is freed, in blocks of rows, on one chip
    prog.free()
    t_ref = harness.now()
    ref = reference_numbers(cell, prog, steps)
    comp = harness.Comparison()
    where = compare(comp, limits, got, ref, prog.ref.diff_norms)
    harness.say(phase="reference", losses=ref["losses"], **where,
                reference_s=round(harness.now() - t_ref, 2))

    record = {
        "attempted": n, "failed": 0, "compared": comp,
        "memory_peak_bytes": memory_peak, "counters": counters,
        "end_to_end": {"train_tok_s": tokens / window_s, "setup_s": setup_s},
        "window_s": window_s, "tokens": tokens, "steps": n,
        "config": cell.config, "traffic": tr, "chips": cell.chips,
        "peaks": cell.peaks,
    }
    if cell.trace:
        import trace_reduce

        record["trace"] = trace_reduce.reduce_file(trace_path, cell.chips)
    return record
