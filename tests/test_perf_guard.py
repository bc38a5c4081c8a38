"""Perf-regression gate (PR 16 tentpole, layer 3): row recovery from
truncated driver tails, the spread-aware noise model, unit-derived
direction, weather widening, and the CLI verdicts (self-check green on
the checked-in history, red on a doctored candidate)."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "tools"))
import perf_regression as pg  # noqa: E402

REPO = os.path.join(os.path.dirname(__file__), os.pardir)


def _row(metric="m", value=100.0, unit="img/s", **kw):
    return dict(metric=metric, value=value, unit=unit, **kw)


# -- row recovery ------------------------------------------------------------


def test_extract_rows_tolerates_noise_and_truncation():
    text = ("warmup chatter\n"
            '{"metric": "a", "value": 1.5, "unit": "ms"} trailing\n'
            'not json {"metric": 7} {"metric": "skipme"}\n'
            '{"metric": "b", "value": 2, "unit": "img/s", '
            '"spread": [1.9, 2.1]}\n'
            '{"metric": "c", "val')   # truncated mid-object: dropped
    rows = pg.extract_rows(text)
    assert [r["metric"] for r in rows] == ["a", "b"]
    assert rows[1]["spread"] == [1.9, 2.1]


def test_load_history_real_repo_rounds():
    history = pg.load_history(REPO)
    assert len(history) >= 3            # r03..r05 BENCH files have rows
    labels = [label for label, _ in history]
    assert labels == sorted(labels, key=pg._round_key)
    for _, rows in history:
        metrics = [r["metric"] for r in rows]
        assert len(metrics) == len(set(metrics))   # per-round dedupe


# -- noise model -------------------------------------------------------------


def test_direction_from_unit():
    assert pg._higher_is_better("img/s")
    assert pg._higher_is_better("tok/s")
    assert pg._higher_is_better(None)
    for u in ("ms", "us", "s", "ms/token", "ms/step", "s/iter"):
        assert not pg._higher_is_better(u)


def test_inside_spread_is_not_a_regression():
    base = _row(value=2707.31, spread=[2609.86, 2780.03])
    hist = [("r04", [base])]
    # the real r05 dip: below the point value but inside r04's spread
    regs, checked = pg.compare(hist, [_row(value=2633.3)])
    assert checked == 1 and regs == []


def test_out_of_band_throughput_drop_fails():
    hist = [("r04", [_row(value=2707.31, spread=[2609.86, 2780.03])])]
    (reg,), _ = pg.compare(hist, [_row(value=1500.0)])
    assert reg["metric"] == "m" and reg["direction"] == "higher"
    assert reg["band"][0] > 1500.0
    assert reg["reference_round"] == "r04"


def test_lower_better_latency_direction():
    hist = [("r03", [_row(unit="ms", value=10.0)])]
    regs, _ = pg.compare(hist, [_row(unit="ms", value=9.0)])
    assert regs == []                       # faster is fine
    (reg,), _ = pg.compare(hist, [_row(unit="ms", value=20.0)])
    assert reg["direction"] == "lower"


def test_candidate_spread_edge_gets_benefit_of_doubt():
    hist = [("r02", [_row(value=100.0)])]
    # point value regressed, but the candidate's own spread reaches back
    # into the band: noisy-but-overlapping is not a regression
    regs, _ = pg.compare(hist, [_row(value=80.0, spread=[78.0, 95.0])])
    assert regs == []
    regs, _ = pg.compare(hist, [_row(value=80.0, spread=[78.0, 82.0])])
    assert len(regs) == 1


def test_weather_dominated_widens_slack():
    hist = [("r05", [_row(value=100.0, weather_dominated=True)])]
    # 25% drop: outside the plain 10% slack, inside the 3x-widened 30%
    regs, _ = pg.compare(hist, [_row(value=75.0)])
    assert regs == []
    regs, _ = pg.compare(hist, [_row(value=75.0)], weather_factor=1.0)
    assert len(regs) == 1
    # the CANDIDATE being weather-marked widens too
    hist = [("r05", [_row(value=100.0)])]
    regs, _ = pg.compare(hist, [_row(value=75.0,
                                     weather_dominated=True)])
    assert regs == []


def test_new_metric_has_nothing_to_regress_against():
    regs, checked = pg.compare([("r01", [_row("old", 5.0)])],
                               [_row("brand_new", 1.0)])
    assert regs == [] and checked == 0


# -- CLI verdicts ------------------------------------------------------------


def test_self_check_green_on_checked_in_history(capsys):
    assert pg.main(["--history-dir", REPO]) == 0
    assert "PERFGUARD PASS" in capsys.readouterr().out


def test_doctored_regression_fails(tmp_path, capsys):
    history = pg.load_history(REPO)
    # doctor the newest round's first throughput row down to rubble
    target = None
    for _, rows in reversed(history):
        for r in rows:
            if pg._higher_is_better(r.get("unit")):
                target = dict(r)
                break
        if target is not None:
            break
    assert target is not None
    target["value"] = target["value"] * 0.1
    target.pop("spread", None)
    target.pop("weather_dominated", None)
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps([target]))
    rc = pg.main(["--history-dir", REPO, "--fresh", str(fresh)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "PERF_REGRESSION" in out and target["metric"] in out


def test_empty_history_and_fresh_skip(tmp_path, capsys):
    assert pg.main(["--history-dir", str(tmp_path)]) == 0
    assert "PERFGUARD SKIP" in capsys.readouterr().out
    empty = tmp_path / "empty.txt"
    empty.write_text("no rows here\n")
    assert pg.main(["--history-dir", REPO, "--fresh", str(empty)]) == 0
    assert "no metric rows" in capsys.readouterr().out
