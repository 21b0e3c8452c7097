"""The benchmark's yardsticks on the CPU: peaks table, work counted from
shapes, and the traffic generator."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import costs, peaks, traffic_gen

ROOT = Path(__file__).resolve().parents[2]
SASREC = {"backbone": "sasrec", "n_items": 1000, "d_model": 8, "n_blocks": 2,
          "n_heads": 2, "d_ff": 16, "max_seq_len": 5,
          "pq": {"m": 4, "b": 16, "code_dtype": "uint8"}}
BERT = {**SASREC, "backbone": "bert4rec"}


def test_peaks_of_v5e_and_unknown_device_is_an_error():
    p = peaks.peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["int8_ops_per_s"],
            p["hbm_bytes_per_s"]) == (197e12, 393e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("cfg,length,positions", [
    (SASREC, 1, 1), (SASREC, 3, 3), (SASREC, 5, 5), (SASREC, 50, 5),
    (BERT, 1, 2), (BERT, 4, 5), (BERT, 5, 5), (BERT, 50, 5)])
def test_real_positions(cfg, length, positions):
    assert costs.real_positions(cfg, length) == positions


def test_backbone_and_head_flops_by_hand():
    # SASRec, 3 positions: projections 2*3*8*32, causal pairs 6 ->
    # 2*2*6*8, MLP 2*3*2*8*16; two blocks.
    per_block = 2 * 3 * 8 * 32 + 2 * 2 * 6 * 8 + 2 * 3 * 2 * 8 * 16
    assert costs.backbone_flops(SASREC, 3) == 2 * per_block
    # BERT4Rec attends over all 9 pairs.
    per_block = 2 * 3 * 8 * 32 + 2 * 2 * 9 * 8 + 2 * 3 * 2 * 8 * 16
    assert costs.backbone_flops(BERT, 3) == 2 * per_block
    assert costs.head_flops(SASREC) == 2 * 16 * 8 + 1000 * 4
    assert costs.request_flops(SASREC, 2) == (costs.backbone_flops(SASREC, 2)
                                              + costs.head_flops(SASREC))


@pytest.mark.parametrize("count", [costs.backbone_flops,
                                   costs.real_positions])
def test_unknown_backbone_has_no_count(count):
    with pytest.raises(ValueError, match="lfm2"):
        count({**SASREC, "backbone": "lfm2"}, 3)


def test_padding_positions_are_not_useful_work():
    assert costs.request_flops(SASREC, 2) < costs.request_flops(SASREC, 5)
    assert costs.request_flops(SASREC, 5) == costs.request_flops(SASREC, 500)


def test_pqtopk_cost_from_shapes():
    c = costs.pqtopk_cost(batch=64, n_items=1_271_808, m=8, b=512, k=16,
                          code_bytes=2)
    assert c["ops"] == 64 * 1_271_808 * 8
    assert c["bytes"] == 1_271_808 * 8 * 2 + 64 * 8 * 512 * 4 + 64 * 16 * 8


def test_roofline_share_names_its_bound():
    p = peaks.peaks("TPU v5 lite")
    mem = costs.roofline_share(ops=1e6, nbytes=819e6, seconds=0.01, peaks=p)
    assert mem["bound"] == "memory" and mem["percent"] == pytest.approx(10.0)
    comp = costs.roofline_share(ops=197e10, nbytes=1.0, seconds=0.1, peaks=p)
    assert comp["bound"] == "compute" and comp["percent"] == pytest.approx(10.0)


def test_same_work_in_another_order_for_every_seed():
    spec = {"dist": "log_uniform", "min": 1, "max": 200}
    a = traffic_gen.history_lengths(spec, 500, traffic_gen.rng_for(1, "l"))
    b = traffic_gen.history_lengths(spec, 500,
                                    traffic_gen.rng_for(2 ** 31 + 9, "l"))
    assert sorted(a) == sorted(b) and list(a) != list(b)
    assert a.min() >= 1 and a.max() <= 200
    ga = traffic_gen.exponential_gaps(400, 100.0, traffic_gen.rng_for(1, "g"))
    gb = traffic_gen.exponential_gaps(400, 100.0, traffic_gen.rng_for(3, "g"))
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert ga.sum() == pytest.approx(4.0, rel=0.02)


def test_zipf_items_favour_low_ids_and_stay_in_the_catalogue():
    s = traffic_gen.ItemSampler({"dist": "zipf", "exponent": 1.0}, 1000)
    ids = s.draw(np.random.default_rng(0), 20000)
    assert ids.min() >= 1 and ids.max() <= 1000
    assert (ids == 1).mean() > 5 * (ids == 10).mean() > 0


def test_histories_are_reproducible_from_the_seed():
    traffic = {"history": {"dist": "log_uniform", "min": 1, "max": 20},
               "items": {"dist": "zipf", "exponent": 1.0}}
    cfg = {"n_items": 50}
    a = traffic_gen.histories(traffic, cfg, 30, 7)
    b = traffic_gen.histories(traffic, cfg, 30, 7)
    c = traffic_gen.histories(traffic, cfg, 30, 8)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", sorted(
    p.stem for p in (ROOT / "bench/traffic").glob("*.json")))
def test_history_lengths_keep_the_datasets_mean(mix):
    """Each mix's lengths have the mean history length of the dataset it
    stands for, as its source states it, within 1%."""
    spec = json.loads((ROOT / "bench/traffic" / f"{mix}.json").read_text())
    lengths = traffic_gen.history_lengths(
        spec["history"], 100_000, traffic_gen.rng_for(1, "lengths"))
    target = spec["history"]["mean_of_source"]
    assert abs(lengths.mean() / target - 1) < 0.01, lengths.mean()
