"""BENCHMARK.json keeps to its contract, and every configuration, traffic
mix, limit file and per-layer metric it names is found by name and loads."""
import json
import os
import re

import pytest

import cell as cells

ROOT = os.path.dirname(cells.BENCH)
SPEC = cells.load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRIC_KEYS = {"name", "unit", "better", "source"}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    files = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        body = cells.load_json(ROOT, c["file"])
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not k.endswith(("_dim", "_rank"))
        assert os.path.isfile(os.path.join(cells.BENCH, "data", body["data"]["generator"] + ".py"))
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


def test_workloads():
    pairs = set()
    assert 1 <= len(SPEC["workloads"]) <= 24
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_metrics():
    e2e, layer = SPEC["end_to_end"], SPEC["per_layer"]
    names = [m["name"] for m in e2e + layer]
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25 for m in e2e)
    for m in e2e:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"bound"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in layer:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"layer", "moves"}
        assert _line(m["layer"]) and m["moves"] in {e["name"] for e in e2e}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_piece_of_a_cell_loads(workload):
    cell = cells.find_cell(ROOT, workload)
    unit = cells.load_module(os.path.join(cells.BENCH, "units", cell.traffic["unit"] + ".py"),
                             "t_unit_" + cell.traffic["unit"])
    assert callable(unit.Unit)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    for name in reported - {"setup_s"}:
        assert cells.by_prefix(name, {cell.traffic["metric"]}), name
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in reported
        assert callable(cells.reader(m["name"]).read)
    assert cell.limits and all(v > 0 for v in cell.limits.values())


def test_mixes_are_data():
    for w in {w["traffic"] for w in SPEC["workloads"]}:
        with open(os.path.join(cells.BENCH, "traffic", w + ".json")) as f:
            assert isinstance(json.load(f), dict)
