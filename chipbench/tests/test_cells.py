"""Each cell, driven past the harness's look for a chip at the tiny sizes
of the files' ``rehearse`` groups: the reference agrees with the program,
the control does not, and every fault a cell can have is caught."""
import json
import os

import numpy as np
import pytest

import harness
from conftest import ROOT


def cell_of(workload, seed=11, seconds=1.0, trace=False):
    import jax

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.Cell(bench, workload, seed, seconds, trace, True,
                        harness.now())
    cell.devices = jax.devices()[:cell.chips]
    cell.peaks = None
    cell.compile_cache = harness.enable_compile_cache()
    return cell


def drive(workload, **kw):
    cell = cell_of(workload)
    return harness.load_module("drivers", cell.config["driver"]).run(cell, **kw)


def worst(record):
    return {n: v / lim for n, v, lim in record["compared"].rows}


# -- the reference against the program, tiny, on the CPU ----------------------

@pytest.mark.parametrize("workload", ["bert_pretrain_1chip",
                                      "bert_pretrain_dp4",
                                      "mistral_chat_closed"])
def test_the_reference_agrees_with_the_program(workload):
    rec = drive(workload)
    assert rec["compared"].correct, rec["compared"].as_dict()
    assert rec["failed"] == 0 and rec["attempted"] > 0
    assert all(np.isfinite(v) and v > 0 for v in rec["end_to_end"].values())


def test_the_whole_command_prints_the_contracts_line(capsys):
    import run

    assert run.run(["--workload", "bert_pretrain_1chip", "--seed",
                    "3000000021", "--seconds", "1", "--trace", "1",
                    "--rehearse"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    line = json.loads(last)
    assert list(line)[-1] == "compared" and line["correct"] is True
    assert {"attempted", "failed", "metrics", "device", "breakdown"} <= set(line)
    assert line["device"]["platform"] == "cpu"   # never a device's name
    assert "window_s" in line["device"]


def test_without_a_chip_there_is_no_result(capsys):
    import run

    rc = run.run(["--workload", "bert_pretrain_1chip", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert "correct" not in capsys.readouterr().out


# -- the control: the reference in the program's place, one precision down ----

def test_training_control_in_fp8_is_not_correct():
    train = harness.load_module("drivers", "train")
    cell = cell_of("bert_pretrain_1chip")
    limits = harness.load_json("limits", cell.name + ".json")["rehearse"]
    prog = train.Program(cell)
    steps = cell.traffic["checked_steps"]
    ref = train.reference_numbers(cell, prog, steps)
    num = prog.ref.controls(cell.config["precision"])["float8_e4m3fn"]
    low = train.reference_numbers(cell, prog, steps, num=num)
    comp = harness.Comparison()
    train.compare(comp, limits, low, ref, prog.ref.diff_norms)
    assert not comp.correct, comp.as_dict()


def test_serving_control_in_bf16_is_not_correct():
    ref = harness.load_module("reference", "decoder")
    rec = drive("mistral_chat_closed", control=ref.controls("float32"))
    limit = dict((n, lim) for n, _, lim in rec["compared"].rows)[
        "served_token_logit_gap_max"]
    assert rec["compared"].correct
    assert rec["control_gaps"]["bfloat16"] > limit


# -- faults planted under the timed path --------------------------------------

def break_step(monkeypatch, wrap):
    train = harness.load_module("drivers", "train")
    real = train.Program.step
    monkeypatch.setattr(train.Program, "step",
                        lambda self, i: wrap(self, i, real))


def test_a_step_that_returns_its_state_unchanged(monkeypatch):
    def wrap(self, i, real):
        import jax.numpy as jnp

        tr = self.trainer
        keep = {n: jnp.copy(a) for n, a in tr.params.items()}
        loss = real(self, i)
        tr.params.update(keep)
        return loss

    break_step(monkeypatch, wrap)
    rec = drive("bert_pretrain_1chip")
    assert not rec["compared"].correct
    assert worst(rec)["change_norm_worst_leaf_gap"] > 1


def test_half_of_the_batch_left_out(monkeypatch):
    def wrap(self, i, real):
        pool = self.pool
        tokens, (mlm, nsp) = pool[i % len(pool)]
        h = len(tokens) // 2
        twice = lambda a: np.concatenate([a[:h], a[:h]])   # noqa: E731
        self.pool = [(twice(tokens), (twice(mlm), twice(nsp)))]
        try:
            return real(self, 0)
        finally:
            self.pool = pool

    break_step(monkeypatch, wrap)
    rec = drive("bert_pretrain_1chip")
    assert not rec["compared"].correct, worst(rec)


def test_the_exchange_between_chips_left_out(monkeypatch):
    """Without the all-reduce every chip steps on its own rows' gradient:
    chip 0's rows, fed to all four, give what chip 0 would then hold."""
    def wrap(self, i, real):
        pool = self.pool
        tokens, (mlm, nsp) = pool[i % len(pool)]
        q = len(tokens) // 4
        own = lambda a: np.concatenate([a[:q]] * 4)   # noqa: E731
        self.pool = [(own(tokens), (own(mlm), own(nsp)))]
        try:
            return real(self, 0)
        finally:
            self.pool = pool

    break_step(monkeypatch, wrap)
    rec = drive("bert_pretrain_dp4")
    assert not rec["compared"].correct, worst(rec)


def test_a_token_altered_where_it_is_produced():
    def alter(row):
        row["tokens"] = list(row["tokens"])
        row["tokens"][-1] = (row["tokens"][-1] + 1) % 500 + 1
        return row

    rec = drive("mistral_chat_closed", alter=alter)
    assert not rec["compared"].correct
