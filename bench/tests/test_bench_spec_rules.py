"""The spec checker (`bench/spec.py`) on doctored copies of BENCHMARK.json
and of a configuration file, held in memory: a configuration cut to one
chip's share and four-chip cells are taken where the cut is written down
and the cap kept, and each way of breaking those rules is reported."""
import copy
import json
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CANDGEN = "sasrec-gowalla.candgen"

# LFM2-8B-A1B (huggingface.co/LiquidAI/LFM2-8B-A1B config.json) as a RecJPQ
# user encoder, cut in depth to one period of its layer pattern: 8 of 24
# blocks, every width and all 32 experts as published.  It borrows the
# existing reference and adapter, so that only the cut is under test.
LAYER_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv", "conv", "full_attention",
               "conv", "conv", "conv", "full_attention", "conv", "conv",
               "conv", "full_attention", "conv", "conv", "full_attention",
               "conv", "conv"]
LFM2_FILE = "bench/configs/lfm2-8b-a1b-recjpq-gowalla.json"
LFM2 = {
    "name": "lfm2-8b-a1b-recjpq-gowalla",
    "source": "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/"
              "config.json",
    "system": "seqrec_engine", "reference": "seqrec_reference",
    "backbone": "lfm2", "n_items": 1271638, "max_seq_len": 200,
    "hidden_size": 2048, "intermediate_size": 7168,
    "moe_intermediate_size": 1792, "num_attention_heads": 32,
    "num_key_value_heads": 8, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "conv_L_cache": 3,
    "num_hidden_layers": 8,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv"],
    "pq": {"m": 8, "b": 512, "code_dtype": "uint16"},
    "dtype": "bfloat16", "matmul_precision": "default",
    "reduced": ["num_hidden_layers", "layer_types"],
    "published": {"num_hidden_layers": 24, "layer_types": LAYER_TYPES},
    "deployment": "1 chip per layer: each block whole on one chip, all 32 "
                  "experts with it; the 16 blocks left out lie on 2 further "
                  "chips as pipeline stages",
    "check": {"score_gap": 0.001, "score_err": 0.001, "bad_answers": 0},
}


def add_cell(doc, name, chips=1, config="sasrec-recjpq-gowalla"):
    """A cell like candgen, reported wherever candgen is."""
    like = next(w for w in doc["workloads"] if w["name"] == CANDGEN)
    doc["workloads"].append({**like, "name": name, "config": config,
                             "chips": chips})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CANDGEN in m.get("workloads", []):
            m["workloads"].append(name)


def with_lfm2():
    """The real spec with the cut configuration and one cell of it."""
    doc = copy.deepcopy(SPEC)
    doc["configs"].append({"name": LFM2["name"], "source": LFM2["source"],
                           "file": LFM2_FILE,
                           "reduced": list(LFM2["reduced"]),
                           "why": "gated short convolutions, GQA and 32 "
                                  "experts as the user encoder"})
    add_cell(doc, "lfm2-gowalla.candgen", config=LFM2["name"])
    return doc, {LFM2_FILE: copy.deepcopy(LFM2)}


def test_the_real_spec_is_sound():
    assert spec.problems(SPEC, ROOT) == []


def test_a_written_down_cut_is_taken():
    doc, files = with_lfm2()
    assert spec.problems(doc, ROOT, files) == []


@pytest.mark.parametrize("n_cells,n_four", [(2, 1), (3, 1), (5, 2)])
def test_four_chip_cells_up_to_the_cap_are_taken(n_cells, n_four):
    doc = copy.deepcopy(SPEC)
    for i in range(n_cells - len(doc["workloads"])):
        add_cell(doc, f"extra-{i}")
    for cell in doc["workloads"][:n_four]:
        cell["chips"] = 4
    assert spec.problems(doc, ROOT) == []


def _cfg(doc, files):
    return doc["configs"][-1], files[LFM2_FILE]


def _reduced_differs(doc, files):
    _cfg(doc, files)[0]["reduced"] = ["num_hidden_layers"]


def _missing_from_published(doc, files):
    del _cfg(doc, files)[1]["published"]["layer_types"]


def _published_equals_file(doc, files):
    _cfg(doc, files)[1]["published"]["num_hidden_layers"] = 8


def _no_deployment(doc, files):
    del _cfg(doc, files)[1]["deployment"]


def _width_in_reduced(doc, files):
    entry, data = _cfg(doc, files)
    for where in (entry, data):
        where["reduced"].append("moe_intermediate_size")
    data["moe_intermediate_size"] = 896
    data["published"]["moe_intermediate_size"] = 1792


def _width_inside_a_group(doc, files):
    entry, data = _cfg(doc, files)
    for where in (entry, data):
        where["reduced"].append("pq")
    data["published"]["pq"] = {**data["pq"], "b": 256}


def _not_a_key_of_the_file(doc, files):
    entry, data = _cfg(doc, files)
    for where in (entry, data):
        where["reduced"].append("moe.n_experts")
    data["published"]["moe.n_experts"] = 32


def _published_beyond_reduced(doc, files):
    _cfg(doc, files)[1]["published"]["num_experts"] = 64


def _chips_2(doc, files):
    doc["workloads"][0]["chips"] = 2


def _two_four_chip_cells_among_two(doc, files):
    doc.update(copy.deepcopy(SPEC))
    for cell in doc["workloads"]:
        cell["chips"] = 4


def _third_four_chip_cell_among_five(doc, files):
    add_cell(doc, "extra-0")
    add_cell(doc, "extra-1")
    for cell in doc["workloads"][:3]:
        cell["chips"] = 4


BROKEN = [
    (_reduced_differs, "reduced differs between BENCHMARK.json"),
    (_missing_from_published, "layer_types is missing from published"),
    (_published_equals_file, "published num_hidden_layers equals the file's"),
    (_no_deployment, "no deployment"),
    (_width_in_reduced, "reduced names a width, moe_intermediate_size"),
    (_width_inside_a_group, "reduced names a width, pq.b, inside the group"),
    (_not_a_key_of_the_file, "moe.n_experts is not a key of the file"),
    (_published_beyond_reduced, "published names keys not in reduced"),
    (_chips_2, "chips is 2, not 1 or 4"),
    (_two_four_chip_cells_among_two, "2 cells ask for 4 chips"),
    (_third_four_chip_cell_among_five, "3 cells ask for 4 chips"),
]


@pytest.mark.parametrize("doctor,reported", BROKEN,
                         ids=[d.__name__.strip("_") for d, _ in BROKEN])
def test_a_broken_rule_is_reported(doctor, reported):
    doc, files = with_lfm2()
    doctor(doc, files)
    found = spec.problems(doc, ROOT, files)
    assert len(found) == 1 and reported in found[0], found
