"""``BENCHMARK.json`` names only what the contract allows and what the
directory holds."""
import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units(bench):
    names = [c["name"] for c in bench["configs"]] \
        + [w["name"] for w in bench["workloads"]] \
        + [w["traffic"] for w in bench["workloads"]] \
        + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]] \
        + [k for c in bench["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for group in ("configs", "workloads"):
        group_names = [e["name"] for e in bench[group]]
        assert len(set(group_names)) == len(group_names)
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_a_pair_of_config_and_traffic_stands_once(bench):
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs), pairs
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_every_file_named_is_there(bench):
    for c in bench["configs"]:
        path = os.path.join(ROOT, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert key in cfg["published"], key
        for kind in ("drivers", "adapters", "reference"):
            key = {"drivers": "driver", "adapters": "adapter",
                   "reference": "reference"}[kind]
            assert os.path.isfile(os.path.join(BENCH, kind, cfg[key] + ".py"))
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(BENCH, "limits",
                                           w["name"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py")), m["name"]


def test_metrics_hang_together(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in cells
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or w in moved["workloads"]
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert 1 <= bench["run_seconds"] <= 51


def test_no_width_is_reduced(bench):
    for c in bench["configs"]:
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size|"
                                 r"head_dim|num_experts_per_tok)$", key), key


def test_keys_lines_and_what_each_cell_reports(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    lines = list(bench["command"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16 and c["file"].startswith("chipbench/")
        lines.append(c["source"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        lines.append(m["layer"])
    assert 1 <= len(bench["command"]) <= 32
    for s in lines:
        assert 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s, s
    for w in bench["workloads"]:
        e2e = [m["name"] for m in bench["end_to_end"]
               if "workloads" not in m or w["name"] in m["workloads"]]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert any(w["name"] in m["workloads"] if "workloads" in m
                   else m["moves"] in e2e for m in bench["per_layer"])
    # all of a full check's runs at 24 cells fit the driver's 43,200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
