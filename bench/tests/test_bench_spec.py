"""BENCHMARK.json against the rules the harness relies on (`bench/spec.py`):
every name it gives is found as a file, and every field has the shape the
benchmark's format requires."""
import json
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_limits():
    assert spec.top_level_problems(SPEC, ROOT) == []


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_configs_are_files_with_their_reference(cfg):
    assert spec.config_problems(SPEC, cfg, ROOT) == []


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cells_name_files_that_exist(cell):
    assert spec.cell_problems(SPEC, cell, ROOT) == []


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metrics(metric):
    assert spec.metric_problems(SPEC, metric, ROOT) == []
