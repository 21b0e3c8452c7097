"""The correctness check on the CPU: its pieces, the control that must fail
it, and whole runs with the timed path broken underneath that must come
out not correct."""
import io
import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import check, control, faults, harness, traffic_gen

ROOT = Path(__file__).resolve().parents[2]
GOWALLA = json.loads((ROOT / "bench/configs/sasrec-recjpq-gowalla.json")
                     .read_text())


def _cfg(**over):
    """Gowalla's widths and limits over a catalogue a CPU can score."""
    return {**GOWALLA, "n_items": 4095, **over}


def test_sample_holds_the_longest_history_and_follows_the_seed():
    reqs = {i: traffic_gen.Work(i, np.ones(1 + i % 7, np.int32), 10)
            for i in range(100)}
    reqs[42] = traffic_gen.Work(42, np.ones(50, np.int32), 10)
    a = check.sample(reqs, list(reqs), 8, seed=2 ** 31 + 3)
    assert len(a) == 8 and 42 in a
    assert a == check.sample(reqs, list(reqs), 8, seed=2 ** 31 + 3)
    assert a != check.sample(reqs, list(reqs), 8, seed=5)
    assert check.sample(reqs, [1, 2], 8, seed=1) == [1, 2]


@pytest.mark.parametrize("items,bad", [
    ([3, 1, 2], False), ([3, 1], True), ([3, 3, 2], True),
    ([3, 1, 10], True), ([-1, 1, 2], True)])
def test_malformed_answers(items, bad):
    assert check.malformed(np.array(items), np.zeros(len(items)), 3,
                           n_rows=10) is bad


def test_judge_holds_every_reading_to_its_limit():
    limits = {"score_gap": 0.1, "score_err": 0.1, "bad_answers": 0}
    ok, table = check.judge({"score_gap": 0.05, "score_err": 0.1,
                             "bad_answers": 0.0}, limits)
    assert ok and table["score_gap"] == {"value": 0.05, "limit": 0.1}
    assert not check.judge({"score_gap": 0.05, "score_err": 0.2,
                            "bad_answers": 0.0}, limits)[0]
    assert not check.judge({"score_gap": 0.0, "score_err": 0.0,
                            "bad_answers": 1.0}, limits)[0]


def _program_answers(cfg, weights, hists, k):
    from bench.systems import seqrec_engine
    from repro.serving.engine import Request
    eng = seqrec_engine.build(cfg, {"k": k, "max_batch": len(hists)},
                              weights)
    _, prep = eng.prepare([Request(i, h, k=k) for i, h in enumerate(hists)])
    res = eng.complete(eng.launch(prep))
    return (np.stack([r.items for r in res]),
            np.stack([r.scores for r in res]))


def test_control_in_bfloat16_fails_where_the_program_passes():
    """At Gowalla's widths over a small catalogue: the float32 program
    (exact on the CPU) passes the configuration's limits, the bfloat16
    control does not."""
    from bench.configs import seqrec_reference as ref
    cfg = _cfg()
    traffic = json.loads((ROOT / "bench/traffic/open-poisson-k10.gowalla.r2080.json")
                         .read_text())
    hists = traffic_gen.histories(traffic, cfg, 16, seed=2 ** 31 + 11)
    reqs = {i: traffic_gen.Work(i, h, 10) for i, h in enumerate(hists)}
    w = ref.make_weights(cfg, 2 ** 31 + 11)
    limits = {k: v for k, v in cfg["check"].items() if k != "bad_answers"}
    ids, scores = _program_answers(cfg, w, hists, 10)
    prog = check.readings(ref, cfg, w, 1, reqs,
                          {i: (ids[i], scores[i]) for i in reqs},
                          list(reqs), list(reqs))
    assert check.judge(prog, limits)[0], prog
    ids, scores = control.answers(ref, cfg, w, hists, 10)
    ctl = check.readings(ref, cfg, w, 1, reqs,
                         {i: (ids[i], scores[i]) for i in reqs},
                         list(reqs), list(reqs))
    assert not check.judge(ctl, limits)[0], ctl


OPEN = {"kind": "open_poisson", "rate_per_s": 30, "k": 10, "max_batch": 4,
        "history": {"dist": "log_uniform", "min": 1, "max": 30},
        "items": {"dist": "zipf", "exponent": 1.0}, "check_requests": 8,
        "check_scores": 64}
CLOSED = {"kind": "closed_batch", "k": 20, "max_batch": 8, "pool": 40,
          "history": {"dist": "log_uniform", "min": 1, "max": 30},
          "items": {"dist": "zipf", "exponent": 1.0},
          "check_requests": 4, "check_scores": 64}


@pytest.mark.parametrize("fault,traffic", [
    (None, OPEN), ("answer_altered", OPEN),
    (None, CLOSED), ("half_batch", CLOSED)],
    ids=["open-sound", "open-answer_altered", "closed-sound",
         "closed-half_batch"])
def test_whole_run_with_the_timed_path_broken(fault, traffic):
    """The harness's look for a chip skipped, a whole run on a small model
    at Gowalla's limits: sound, it is correct; with the timed path broken
    underneath, it is not."""
    cfg = _cfg(d_model=64, n_heads=4, d_ff=64, max_seq_len=24,
               pq={"m": 8, "b": 32, "code_dtype": "uint16"}, n_items=3000)
    metric = "p50_ms" if traffic is OPEN else "users_per_s"
    cell = harness.Cell("cpu-test", cfg, traffic, 1,
                        [{"name": "setup_s", "unit": "s"},
                         {"name": metric, "unit": "x"}])
    out, err = io.StringIO(), io.StringIO()
    with faults.planted(fault):
        result = harness.run(cell, seed=2 ** 31 + 7, seconds=1.0,
                             trace=False, t_start=time.monotonic(), out=out,
                             err=err, devices_fn=lambda n: jax.devices()[:n])
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", metric}
    assert err.getvalue().splitlines()[-3].startswith("check score_gap")


def test_no_accelerator_no_result(tmp_path):
    """Without a chip, and in a checkout without the program, the run
    exits non-zero and prints no result."""
    cmd = [sys.executable, "bench/run.py", "--workload",
           "sasrec-gowalla.steady", "--seed", "1", "--seconds", "1",
           "--trace", "0"]
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path)}
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == "", p.stdout
    bare = tmp_path / "bare"
    bare.mkdir()
    (bare / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    subprocess.run(["cp", "-r", str(ROOT / "bench"), str(bare / "bench")],
                   check=True)
    p = subprocess.run(cmd, cwd=bare, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == "", p.stdout
