"""The program's host spans against the chip's idle time: the split on
intervals made by hand, on small traces cut on the chip (``data/``), and
both drivers rehearsed with the profiler on (a CPU trace has no device
plane, so there the idle shares are left out, but the spans are found)."""
import gzip
import json
import os

import pytest

import harness
import program_spans as ps
import trace_reduce as tr
from conftest import HERE, ROOT

MS = 1_000_000
SERVE = ["idle_in.schedule.serve", "idle_in.dispatch.serve",
         "idle_in.sample.serve", "idle_in.settle.serve",
         "idle_unattributed.serve"]
TRAIN = ["idle_in.prepare.train", "idle_in.call.train",
         "idle_in.commit.train", "idle_unattributed.train"]


def span(name, start, end, thread="engine#1", **stats):
    return ps.Span(ps.PREFIX + name, thread, start * MS, end * MS, stats)


def reduced(busy, window=(0, 100), chips=1):
    """A trace whose chips are busy over ``busy`` (milliseconds)."""
    ops = [[(f"fusion.{k}", s * MS, e * MS) for k, (s, e) in enumerate(busy)]
           for _ in range(chips)]
    return tr.Reduced(ops, [[] for _ in ops],
                      [(tr.WINDOW_SPAN, window[0] * MS, window[1] * MS)])


def split_ms(trace, spans):
    return {n.replace(ps.PREFIX, ""): ns / MS
            for n, ns in ps.idle_by_span(trace, spans).items()}


# -- intervals made by hand ----------------------------------------------------

def test_a_gap_is_cut_across_three_spans():
    # busy 0-10 and 70-100: one gap of 60 ms under three spans and a hole
    trace = reduced([(0, 10), (70, 100)])
    spans = [span("serve.sample", 5, 25), span("serve.settle", 25, 30),
             span("serve.dispatch", 40, 80)]
    assert split_ms(trace, spans) == {
        "serve.sample": 15, "serve.settle": 5, "serve.dispatch": 30,
        "unattributed": 10}
    assert sum(split_ms(trace, spans).values()) == pytest.approx(
        100 * trace.idle_share())


def test_a_piece_goes_to_the_innermost_span():
    # idle 20-90; step covers all of it, decode 30-80 inside it, dispatch
    # 40-50 inside that: each keeps what its children leave
    trace = reduced([(0, 20), (90, 100)])
    spans = [span("serve.step", 10, 95), span("serve.decode", 30, 80),
             span("serve.dispatch", 40, 50)]
    assert split_ms(trace, spans) == {
        "serve.step": 20, "serve.decode": 40, "serve.dispatch": 10}


def test_a_span_on_another_thread_takes_what_started_later():
    # the engine's step covers 0-100; a client's span on its own thread
    # opens at 50: from there it is the covering span that started last
    trace = reduced([(0, 40), (80, 100)])
    spans = [span("serve.step", 0, 100),
             span("trainer.step", 50, 60, thread="client#7")]
    assert split_ms(trace, spans) == {"serve.step": 30, "trainer.step": 10}


def test_two_spans_of_one_name_side_by_side_and_a_gap_beside_none():
    trace = reduced([(0, 10), (20, 30), (60, 100)])
    spans = [span("serve.step", 10, 20), span("serve.step", 30, 45)]
    assert split_ms(trace, spans) == {"serve.step": 25, "unattributed": 15}


def test_the_chip_that_idles_most_is_the_one_split():
    ops = [[("f", 0, 90 * MS)], [("f", 0, 40 * MS)]]
    trace = tr.Reduced(ops, [[], []], [(tr.WINDOW_SPAN, 0, 100 * MS)])
    assert split_ms(trace, [span("trainer.call", 30, 100)]) == {
        "trainer.call": 60}
    assert trace.idle_share() == pytest.approx(0.6)


def test_no_device_event_reads_nothing():
    trace = tr.Reduced([], [], [(tr.WINDOW_SPAN, 0, 100 * MS)])
    assert ps.idle_by_span(trace, [span("serve.step", 0, 100)]) is None


def reader(name):
    return harness.load_module("layer_metrics", name)


def test_readers_turn_the_split_into_shares_of_the_window():
    trace = reduced([(0, 10), (70, 100)])
    trace.program_spans = [
        span("serve.step", 0, 100), span("serve.retire", 10, 12),
        span("serve.decode", 12, 90), span("serve.build_inputs", 12, 14),
        span("serve.to_device", 14, 20), span("serve.dispatch", 20, 40),
        span("serve.sample", 40, 64), span("serve.settle", 64, 66, tokens=8),
        span("serve.idle_wait", 66, 68)]
    got = {n: reader(n).read(trace, {}, {}) for n in SERVE}
    assert got == {
        "idle_in.schedule.serve": pytest.approx(2 + 2),   # retire, decode
        "idle_in.dispatch.serve": pytest.approx(2 + 6 + 20),
        "idle_in.sample.serve": pytest.approx(24),
        "idle_in.settle.serve": pytest.approx(2),
        "idle_unattributed.serve": pytest.approx(2)}      # the idle wait
    assert sum(got.values()) == pytest.approx(
        reader("device_idle_share.serve").read(trace, {}, {}))
    assert reader("host_visits_per_token").read(trace, {}, {}) == 1 / 8


def test_a_program_without_spans_reads_none_not_zero(monkeypatch):
    monkeypatch.setattr(ps, "newest_trace", lambda: None)
    trace = reduced([(0, 10), (70, 100)])
    for n in SERVE + TRAIN + ["host_visits_per_token"]:
        assert reader(n).read(trace, {}, {}) is None, n


# -- small traces cut on the chip -----------------------------------------------

@pytest.mark.parametrize("cell, names, whole", [
    ("mistral_chat_closed", SERVE, "device_idle_share.serve"),
    ("bert_pretrain_1chip", TRAIN, "device_idle_share.train")])
def test_shares_add_up_to_the_idle_share_on_a_chip_trace(cell, names, whole):
    path = os.path.join(HERE, "data", cell + ".spans.v5e.json.gz")
    with gzip.open(path, "rt") as f:
        trace, spans = ps.from_cut(f.read())
    assert spans and any(trace.busy)
    trace.program_spans = spans
    got = {n: reader(n).read(trace, {}, {}) for n in names}
    assert all(v is not None and v >= 0 for v in got.values()), got
    idle = reader(whole).read(trace, {}, {})
    assert 0 < idle < 100
    assert sum(got.values()) == pytest.approx(idle, abs=1e-6)
    # the program's spans cover the chip's idle time but for a sliver
    assert got[names[-1]] < 0.05 * idle, got
    split = ps.idle_by_span(trace, spans)
    assert sum(split.values()) == sum(
        e - s for s, e in ps.idle_intervals(trace))


# -- both drivers rehearsed with the profiler on ---------------------------------

SERVE_SPANS = {"serve." + n for n in (
    "step", "retire", "admit", "prefill", "decode", "build_inputs",
    "to_device", "dispatch", "pool_update", "sample", "settle", "gauges")}
# trainer.compile runs on a signature's first call, which set-up makes
TRAIN_SPANS = {"trainer." + n for n in (
    "step", "unwrap", "optimizer_scalars", "rng_split", "gather_args",
    "call", "commit")}


@pytest.mark.parametrize("cell, want, reported", [
    ("mistral_chat_closed", SERVE_SPANS, ["host_visits_per_token"]),
    ("bert_pretrain_1chip", TRAIN_SPANS, [])])
def test_a_rehearsed_traced_run_holds_every_span(cell, want, reported,
                                                 capsys):
    import run

    assert run.run(["--workload", cell, "--seed", "3000000023", "--seconds",
                    "1", "--trace", "1", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        new = [m["name"] for m in json.load(f)["per_layer"]
               if m["source"] == "program_span" and cell in m["workloads"]]
    # no device plane on the CPU: the idle shares are left out, not 0
    assert [n for n in new if n in line["metrics"]] == reported
    for n in reported:
        assert line["metrics"][n]["value"] > 0

    window, spans = ps.read_file(ps.newest_trace())
    inside = {s.name[len(ps.PREFIX):] for s in spans
              if s.start >= window[0] and s.end <= window[1]}
    assert want <= inside, want - inside
    # a trace of another window is not this run's: nothing is read
    other = tr.Reduced([], [], [(tr.WINDOW_SPAN, window[0], window[1] + 1)])
    assert ps.spans_of(other) is None
    same = tr.Reduced([], [], [(tr.WINDOW_SPAN, *window)])
    assert len(ps.spans_of(same)) == len(spans)
