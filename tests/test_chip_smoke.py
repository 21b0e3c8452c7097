"""``chip_smoke.py`` cannot rot: its phases run here on the reduced
SASRec-RecJPQ config (CPU, kernels in interpret mode), and its entry point
refuses a host without a TPU, or without the rest of the repository."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs.base import get_reduced
from repro.models import seqrec

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(smoke):
    cfg = get_reduced(smoke.ARCH).model
    params = seqrec.init_seqrec(jax.random.PRNGKey(0), cfg)
    return params, cfg, smoke.make_histories(cfg, smoke.N_REQUESTS, 0)


def _passed(rep):
    assert rep["ok"], rep
    assert rep["mismatches"] == 0 and rep["shed"] == 0 \
        and rep["timeouts"] == 0
    assert rep["served"] == 2 * 64 and rep["compiles_while_serving"] == 0


@pytest.mark.parametrize("method", ["pqtopk_fused", "pqtopk_pruned"])
def test_route_phase_matches_xla_reference(smoke, model, method):
    params, cfg, hists = model
    assert min(map(len, hists)) >= 2
    assert max(map(len, hists)) <= cfg.max_seq_len
    rep = smoke.route_phase(params, cfg, method, hists, expect_kernel=False)
    _passed(rep)
    assert rep["n_compiles"] == len(smoke.BUCKETS)
    assert ("ladder" in rep) == (method == "pqtopk_pruned")
    if method == "pqtopk_pruned":
        assert rep["sentinel_slots"] > 0
        assert rep["sentinel_table_mismatches"] == 0


def test_sharded_phase_matches_one_device_route(smoke, model):
    params, cfg, hists = model
    devices = jax.devices()[:1]
    rep = smoke.sharded_phase(params, cfg, hists, devices,
                              expect_kernel=False)
    _passed(rep)
    assert sum(rep["rows_per_device"].values()) == rep["codes_rows"]


def test_mismatch_is_counted(smoke, model):
    """The comparison is not vacuous: a wrong id or a drifted score
    counts as a mismatch."""
    import numpy as np
    from repro.serving.engine import Result
    ids = np.arange(10, dtype=np.int32)
    sc = np.linspace(1, 0, 10, dtype=np.float32)
    ok = Result(0, ids, sc, 1.0)
    swapped = Result(0, ids[::-1].copy(), sc, 1.0)
    drifted = Result(0, ids, sc + 1e-3, 1.0)
    assert smoke.count_mismatches([ok], [ids], [sc]) == 0
    assert smoke.count_mismatches([swapped, drifted], [ids, ids],
                                  [sc, sc]) == 2


def test_main_refuses_a_host_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_script_alone_refuses(tmp_path):
    """In a directory holding only the script, it exits non-zero and
    prints no result (it never gets as far as JAX)."""
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, SCRIPT.name], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
