"""The reduction from a trace to device times, on intervals made by hand
and on a small trace recorded on the chip (``data/``)."""
import gzip
import os

import pytest

import trace_reduce as tr
from conftest import HERE

MS = 1_000_000


def test_union_counts_overlap_and_nesting_once():
    merged = tr.union([(0, 10), (5, 20), (30, 40), (32, 35), (40, 41)])
    assert merged == [(0, 20), (30, 41)]
    assert tr.total(merged) == 31
    assert tr.intersect(merged, [(15, 33)]) == [(15, 20), (30, 33)]
    assert tr.complement(merged, -5, 50) == [(-5, 0), (20, 30), (41, 50)]


def test_self_time_takes_the_body_out_of_the_loop():
    ev = [("while", 0, 100), ("body.a", 10, 40), ("body.b", 50, 90),
          ("after", 100, 120)]
    assert dict(tr.self_times(ev)) == {"while": 30, "body.a": 30,
                                       "body.b": 40, "after": 20}


def made_by_hand():
    # one chip, a window of 100 ms: busy 0-30, 40-70 (collective 60-70
    # alone, collective 50-60 under a fusion), idle 30-40 and 70-100
    ops = [[("fusion.1", 0, 30 * MS), ("fusion.2", 40 * MS, 60 * MS),
            ("all-reduce-start.1", 50 * MS, 52 * MS),
            ("all-reduce-done.1", 60 * MS, 70 * MS),
            ("all-reduce.9", 52 * MS, 60 * MS)]]
    modules = [[("jit_step(1)", 0, 70 * MS)]]
    spans = [("chipbench.window", 0, 100 * MS),
             ("chipbench.device_put", 29 * MS, 39 * MS),
             ("chipbench.fetch", 72 * MS, 95 * MS)]
    return tr.Reduced(ops, modules, spans)


def test_busy_idle_exposed_and_gaps():
    r = made_by_hand()
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.06)
    assert r.idle_share() == pytest.approx(0.4)
    # 50-70 is collective time; 50-60 runs under fusion.2, 60-70 alone
    assert r.exposed_collective_s() == pytest.approx(0.010)
    gaps = dict((n, s) for n, s in r.idle_gaps())
    assert gaps == {"chipbench.fetch": pytest.approx(0.030),
                    "chipbench.device_put": pytest.approx(0.010)}
    assert r.op_seconds(r"^fusion") == pytest.approx(0.05)
    assert r.op_seconds(r"no_such_kernel") is None
    with_k, without = r.module_ms_by_kernel(r"all-reduce")
    assert with_k == {"jit_step(1)": [pytest.approx(70.0)]} and not without


def test_a_gap_under_no_span_is_named_uncovered():
    r = tr.Reduced([[("f", 0, 10), ("g", 50, 60)]], [[]],
                   [("chipbench.window", 0, 60)],
                   uncovered="engine_loop_unannotated")
    assert r.idle_gaps() == [["engine_loop_unannotated", pytest.approx(40e-9)]]


def test_nothing_to_read_reads_nothing():
    r = tr.Reduced([], [], [("chipbench.window", 0, 10)])
    assert r.idle_share() is None and r.exposed_collective_s() is None
    assert r.busy_s == 0.0 and r.breakdown() == {"device_ops": [],
                                                 "idle_gaps": []}


def test_short_names():
    line = "%fusion.5 = bf16[30522,768]{1,0:T(8,128)(2,1)} fusion(bf16[3] %x)"
    assert tr.short(line) == "fusion.5"
    assert tr.short("all-reduce-start.3") == "all-reduce-start.3"
    assert tr.label(line) == "fusion.5 bf16[30522,768]"
    assert tr.label("%f.1 = (f32[8]{0}, f32[2]{0}) fusion(...)") == "f.1 f32[8]"


def recorded(name):
    path = os.path.join(HERE, "data", name)
    if not os.path.isfile(path):
        pytest.skip(f"{name} is not recorded")
    with gzip.open(path, "rt") as f:
        return tr.Reduced.from_json(f.read())


def test_recorded_one_chip_trace():
    """The first 120 ms of a ``bert_pretrain_1chip`` window on a v5e
    (PR 26): one ``jit_step`` call of about 80 ms is whole inside it."""
    r = recorded("bert_pretrain_1chip.v5e.json.gz")
    assert len(r.ops) == 1 and r.exposed_collective_s() is None
    assert 0.5 < r.busy_s / r.window_s <= 1.0
    steps = [e - s for n, s, e in r.module_calls() if n.startswith("jit_step")]
    assert steps and all(60 * MS < d < 110 * MS for d in steps)
    assert sum(s for _, s in r.top_ops()) <= r.busy_s * 1.0001
    assert all(n.startswith("chipbench.") or n == "unannotated"
               for n, _ in r.idle_gaps())


def test_recorded_four_chip_trace():
    """The first 120 ms of a ``bert_pretrain_dp4`` window: four chips,
    and the gradient all-reduce shows as collective time."""
    r = recorded("bert_pretrain_dp4.v5e.json.gz")
    assert len(r.ops) == 4
    exposed = r.exposed_collective_s()
    assert exposed is not None and 0 < exposed < r.window_s
    assert 0 <= r.idle_share() < 1
