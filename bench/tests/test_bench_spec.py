"""BENCHMARK.json against the rules the harness relies on: every name it
gives is found as a file, and every field has the shape the benchmark's
format requires."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = {w["name"] for w in SPEC["workloads"]}


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_configs_are_files_with_their_reference(cfg):
    assert NAME.match(cfg["name"]) and cfg["reduced"] == []
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"] and data["reduced"] == []
    assert (ROOT / "bench/configs" / f"{data['reference']}.py").is_file()
    assert (ROOT / "bench/systems" / f"{data['system']}.py").is_file()
    assert set(data["check"]) == {"score_gap", "score_err", "bad_answers"}
    assert data["matmul_precision"] in ("default", "high", "highest")
    assert data["check"]["bad_answers"] == 0


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cells_name_files_that_exist(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] == 1 and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    traffic = json.loads((ROOT / "bench/traffic"
                          / f"{cell['traffic']}.json").read_text())
    assert (ROOT / "bench/kinds" / f"{traffic['kind']}.py").is_file()
    reported = [m for m in SPEC["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    assert {m["name"] for m in reported} > {"setup_s"}
    assert any(cell["name"] in m.get("workloads", CELLS)
               for m in SPEC["per_layer"])


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metrics(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert set(metric.get("workloads", CELLS)) <= CELLS
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
        assert (ROOT / "bench/metrics" / f"{metric['name']}.py").is_file()
        for cell in metric["workloads"]:
            e2e = next(m for m in SPEC["end_to_end"]
                       if m["name"] == metric["moves"])
            assert cell in e2e.get("workloads", CELLS)
