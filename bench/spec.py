"""The rules ``BENCHMARK.json`` keeps, as one checker.

``problems(spec, root)`` lists, in plain words, each way in which the
benchmark's spec breaks a rule that the harness relies on: every name it
gives is found as a file, and every field has the shape the benchmark's
format requires.  An empty list means the spec is sound.

A configuration may be cut to one chip's share of a deployment.  Its
``reduced`` then names each key changed from the source (dotted for a key
inside a group, such as ``pq.m``), the same list in ``BENCHMARK.json`` and
in the configuration's file; the file's ``published`` gives the source's
value of each of those keys, and its ``deployment`` says over how many
chips each layer is divided, and how.  No width is ever cut.  A cell runs
on 1 or 4 chips, and at most half of the cells, or one, take 4.
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Any, Dict, List, Optional

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_LEVEL = {"command", "paths", "run_seconds", "configs", "workloads",
             "end_to_end", "per_layer"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
CHECKS = {"score_gap", "score_err", "bad_answers"}

# Widths: the sizes a cut never changes, by this benchmark's names and by
# those of a published config.json.  Any key ending in `_dim` or `_rank`
# is one too.
WIDTHS = ("d_model", "d_ff", "n_heads", "n_kv_heads", "head_dim", "d_expert",
          "experts_per_token", "max_seq_len", "pq.m", "pq.b",
          "hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "num_experts_per_tok")
WIDTH_SUFFIXES = ("_dim", "_rank")
MAX_REDUCED = 16
MISSING = object()


def is_width(key: str) -> bool:
    """A dotted key names a width by its whole name or by its last part."""
    return (key in WIDTHS or key.rsplit(".", 1)[-1] in WIDTHS
            or key.endswith(WIDTH_SUFFIXES))


def cells_of(spec: Dict[str, Any]) -> set:
    return {w["name"] for w in spec["workloads"]}


def top_level_problems(spec: Dict[str, Any], root: Path) -> List[str]:
    """Keys, command, paths, run length, size, and the four-chip cap."""
    out = []
    if set(spec) != TOP_LEVEL:
        out.append(f"top-level keys are {sorted(spec)}, not {sorted(TOP_LEVEL)}")
        return out
    if spec["paths"] != ["bench"] or spec["command"][1:2] != ["bench/run.py"]:
        out.append("paths is not ['bench'] or the command does not run "
                   "bench/run.py")
    if not 1 <= spec["run_seconds"] <= 51:
        out.append(f"run_seconds {spec['run_seconds']} is not in 1..51")
    spec_file = root / "BENCHMARK.json"
    if spec_file.is_file() and len(spec_file.read_bytes()) > 64 * 1024:
        out.append("BENCHMARK.json is over 64 KiB")
    four = [w["name"] for w in spec["workloads"] if w.get("chips") == 4]
    cap = max(1, len(spec["workloads"]) // 2)
    if len(four) > cap:
        out.append(f"{len(four)} cells ask for 4 chips ({', '.join(four)}); "
                   f"at most {cap} of {len(spec['workloads'])} may")
    return out


def _at(data: Any, key: str) -> Any:
    """The value of a dotted key, or MISSING."""
    for part in key.split("."):
        if not isinstance(data, dict) or part not in data:
            return MISSING
        data = data[part]
    return data


def _widths_inside(group: Any, prefix: str):
    """Dotted keys of the widths inside a group, ``prefix`` its own key."""
    for k, v in (group.items() if isinstance(group, dict) else ()):
        key = f"{prefix}.{k}"
        if isinstance(v, dict):
            yield from _widths_inside(v, key)
        elif is_width(key):
            yield key


def cut_problems(name: str, reduced: List[str],
                 data: Dict[str, Any]) -> List[str]:
    """A configuration's cut as its file writes it down: each key of
    ``reduced`` is in the file and in ``published`` with another value,
    ``published`` has no other key, ``deployment`` says how the chips
    share each layer, and no width changes."""
    where = f"configs[{name}]"
    out = []
    if len(reduced) > MAX_REDUCED:
        out.append(f"{where}: reduced has {len(reduced)} keys, over "
                   f"{MAX_REDUCED}")
    published = data.get("published", {})
    if not isinstance(published, dict):
        return out + [f"{where}: published is not a mapping of key to value"]
    extra = sorted(set(published) - set(reduced))
    if extra:
        out.append(f"{where}: published names keys not in reduced: {extra}")
    if not reduced:
        return out
    deployment = data.get("deployment")
    if not isinstance(deployment, str) or not deployment.strip():
        out.append(f"{where}: no deployment says over how many chips each "
                   "layer is divided, and how")
    for key in reduced:
        if not NAME.match(key):
            out.append(f"{where}: reduced key {key!r} is not a name")
        if is_width(key):
            out.append(f"{where}: reduced names a width, {key}")
        value = _at(data, key)
        if value is MISSING:
            out.append(f"{where}: reduced key {key} is not a key of the file")
            continue
        if key not in published:
            out.append(f"{where}: reduced key {key} is missing from "
                       "published")
            continue
        if published[key] == value:
            out.append(f"{where}: published {key} equals the file's value "
                       f"{value!r}, so it was not cut")
        source = published[key]  # nested under the key, as in the file
        for part in reversed(key.split(".")):
            source = {part: source}
        for width in _widths_inside(value, key):
            if _at(source, width) != _at(data, width):
                out.append(f"{where}: reduced names a width, {width}, "
                           f"inside the group {key}")
    return out


def config_problems(spec: Dict[str, Any], cfg: Dict[str, Any], root: Path,
                    data: Optional[Dict[str, Any]] = None) -> List[str]:
    """One ``configs`` entry and its file (``data``, read from ``root``
    where not given): names, reference, adapter, check, and the cut."""
    name = cfg["name"]
    where = f"configs[{name}]"
    out = []
    if not NAME.match(name):
        out.append(f"{where}: not a name")
    if data is None:
        path = root / cfg["file"]
        if not path.is_file():
            return out + [f"{where}: no file {cfg['file']}"]
        data = json.loads(path.read_text())
    if data.get("name") != name:
        out.append(f"{where}: the file's name is {data.get('name')!r}")
    if data.get("reduced") != cfg["reduced"]:
        out.append(f"{where}: reduced differs between BENCHMARK.json "
                   f"({cfg['reduced']}) and the file ({data.get('reduced')})")
    if not (root / "bench/configs" / f"{data.get('reference')}.py").is_file():
        out.append(f"{where}: no plain reference "
                   f"bench/configs/{data.get('reference')}.py")
    if not (root / "bench/systems" / f"{data.get('system')}.py").is_file():
        out.append(f"{where}: no system adapter "
                   f"bench/systems/{data.get('system')}.py")
    check = data.get("check", {})
    if set(check) != CHECKS:
        out.append(f"{where}: check compares {sorted(check)}, not "
                   f"{sorted(CHECKS)}")
    elif check["bad_answers"] != 0:
        out.append(f"{where}: bad_answers limit is {check['bad_answers']}, "
                   "not 0")
    if data.get("matmul_precision") not in ("default", "high", "highest"):
        out.append(f"{where}: matmul_precision "
                   f"{data.get('matmul_precision')!r} is not one of "
                   "default, high, highest")
    return out + cut_problems(name, data.get("reduced") or [], data)


def cell_problems(spec: Dict[str, Any], cell: Dict[str, Any], root: Path,
                  traffic: Optional[Dict[str, Any]] = None) -> List[str]:
    """One ``workloads`` entry: keys, names, chips, its configuration and
    traffic files, and that it reports ``setup_s``, another end-to-end
    metric and a per-layer metric."""
    where = f"workloads[{cell.get('name')}]"
    if set(cell) != CELL_KEYS:
        return [f"{where}: keys are {sorted(cell)}, not {sorted(CELL_KEYS)}"]
    out = []
    if not (NAME.match(cell["name"]) and NAME.match(cell["traffic"])):
        out.append(f"{where}: name or traffic is not a name")
    if cell["chips"] not in (1, 4):
        out.append(f"{where}: chips is {cell['chips']}, not 1 or 4")
    if not 1 <= len(cell["why"]) <= 200:
        out.append(f"{where}: why has {len(cell['why'])} characters, "
                   "not 1..200")
    if cell["config"] not in {c["name"] for c in spec["configs"]}:
        out.append(f"{where}: no configuration {cell['config']}")
    if traffic is None:
        path = root / "bench/traffic" / f"{cell['traffic']}.json"
        if not path.is_file():
            return out + [f"{where}: no traffic file {path.name}"]
        traffic = json.loads(path.read_text())
    if not (root / "bench/kinds" / f"{traffic.get('kind')}.py").is_file():
        out.append(f"{where}: no traffic kind "
                   f"bench/kinds/{traffic.get('kind')}.py")
    cells = cells_of(spec)
    reported = {m["name"] for m in spec["end_to_end"]
                if cell["name"] in m.get("workloads", cells)}
    if not reported > {"setup_s"}:
        out.append(f"{where}: reports {sorted(reported)}, not setup_s and "
                   "another end-to-end metric")
    if not any(cell["name"] in m.get("workloads", cells)
               for m in spec["per_layer"]):
        out.append(f"{where}: reports no per-layer metric")
    return out


def metric_problems(spec: Dict[str, Any], metric: Dict[str, Any],
                    root: Path) -> List[str]:
    """One metric: name, unit, direction, cells; a bound and its source
    for an end-to-end metric, a reader and what it moves for a per-layer
    one."""
    where = f"metrics[{metric['name']}]"
    out = []
    if not (NAME.match(metric["name"]) and UNIT.match(metric["unit"])):
        out.append(f"{where}: name or unit is malformed")
    if metric["better"] not in ("lower", "higher"):
        out.append(f"{where}: better is {metric['better']!r}")
    cells = cells_of(spec)
    if not set(metric.get("workloads", cells)) <= cells:
        out.append(f"{where}: names cells that do not exist")
    if "bound" in metric:
        if metric["source"] not in ("host_clock", "device_trace"):
            out.append(f"{where}: an end-to-end metric from "
                       f"{metric['source']}")
        if not 0.01 <= metric["bound"] <= 0.25:
            out.append(f"{where}: bound {metric['bound']} not in 0.01..0.25")
        return out
    if metric["source"] not in ("device_trace", "program_span",
                                "program_counter", "host_clock"):
        out.append(f"{where}: source {metric['source']!r}")
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    if metric["moves"] not in e2e:
        return out + [f"{where}: moves {metric['moves']}, not an end-to-end "
                      "metric"]
    if not (root / "bench/metrics" / f"{metric['name']}.py").is_file():
        out.append(f"{where}: no reader bench/metrics/{metric['name']}.py")
    for cell in metric.get("workloads", []):
        if cell not in e2e[metric["moves"]].get("workloads", cells):
            out.append(f"{where}: cell {cell} does not report "
                       f"{metric['moves']}")
    return out


def problems(spec: Dict[str, Any], root: Path,
             files: Optional[Dict[str, Any]] = None) -> List[str]:
    """Every problem of the spec.  ``files`` maps a path under ``root`` to
    that JSON file's contents, read in its place (a doctored copy)."""
    files = files or {}
    out = top_level_problems(spec, root)
    if set(spec) != TOP_LEVEL:
        return out
    for cfg in spec["configs"]:
        out += config_problems(spec, cfg, root, files.get(cfg["file"]))
    for cell in spec["workloads"]:
        traffic = files.get(f"bench/traffic/{cell.get('traffic')}.json")
        out += cell_problems(spec, cell, root, traffic)
    for metric in spec["end_to_end"] + spec["per_layer"]:
        out += metric_problems(spec, metric, root)
    return out
