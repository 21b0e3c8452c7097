"""The trace reduction and the per-layer readers, on a short trace of
``sasrec-gowalla.steady`` recorded on a TPU v5e by ``bench/run.py
--trace 1`` (CPU only: the trace is read, not made)."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import layers, peaks, trace
from bench.systems import seqrec_engine as system

TRACE = Path(__file__).resolve().parents[1] / "testdata"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(str(TRACE)), 1, system.STEP_MODULE,
                        system.KERNEL)


def _ctx(reduced, n_batches=None):
    batches = [SimpleNamespace(rids=[i], lengths=[38], host_s=0.001)
               for i in range(len(reduced.steps) if n_batches is None
                              else n_batches)]
    cfg = {"backbone": "sasrec", "n_items": 1271638, "d_model": 512,
           "n_blocks": 2, "d_ff": 512, "max_seq_len": 200,
           "pq": {"m": 8, "b": 512}}
    return {"cfg": cfg, "loop": SimpleNamespace(batches=batches),
            "trace": reduced, "kernel": system.KERNEL,
            "kernel_shapes": system.kernel_shapes,
            "peaks": peaks.peaks("TPU v5 lite")}


def test_window_busy_steps_and_kernel_calls(reduced):
    assert 0.1 < reduced.window_s < 2
    assert 0 < reduced.busy_s <= reduced.window_s
    assert len(reduced.steps) >= 3
    assert len(reduced.kernels) == len(reduced.steps)
    assert reduced.n_devices == 1
    b = reduced.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "%pq_topk.1 custom-call"


def test_kernel_shapes_come_from_the_call(reduced):
    shapes = system.kernel_shapes(reduced.kernels[0])
    assert shapes["n_items"] == 1271808 and shapes["m"] == 8
    assert shapes["b"] == 512 and shapes["k"] == 16
    assert shapes["code_bytes"] == 2 and shapes["batch"] in (8, 16, 32, 64)


def test_readers_on_the_recorded_trace(reduced):
    ctx = _ctx(reduced)
    step, kern = layers.step_device_ms(ctx), layers.kernel_device_ms(ctx)
    assert 0 < kern < step < 100
    assert 0 < layers.kernel_roofline(ctx) < 100
    assert 0 < layers.step_mfu(ctx) < 100
    assert layers.host_ms(ctx) == pytest.approx(1.0)


def test_a_step_without_the_kernel_fails_loudly(reduced):
    ctx = _ctx(reduced)
    ctx["trace"] = trace.Reduced(reduced.window_s, reduced.busy_s,
                                 reduced.steps, [], {}, {}, 1)
    with pytest.raises(RuntimeError, match="ran no event of the kernel"):
        layers.kernel_device_ms(ctx)
    with pytest.raises(RuntimeError, match="ran no event of the kernel"):
        layers.kernel_roofline(ctx)


def test_nothing_to_read_is_no_number(reduced):
    ctx = _ctx(reduced)
    ctx["trace"] = trace.Reduced(1.0, 0.5, [], [], {}, {}, 1)
    assert layers.step_device_ms(ctx) is None
    assert layers.kernel_device_ms(ctx) is None
    assert layers.kernel_roofline(ctx) is None
    assert layers.step_mfu(ctx) is None


def test_mfu_needs_a_step_per_batch(reduced):
    with pytest.raises(RuntimeError, match="serve steps for"):
        layers.step_mfu(_ctx(reduced, n_batches=len(reduced.steps) + 1))


def test_hlo_text_parsing():
    text = ("%pq_topk.1 = (f32[621,64,16]{2,1,0:T(8,128)S(1)}, "
            "s32[621,64,16]{2,1,0:T(8,128)S(1)}) custom-call(s32[621]"
            "{0:T(1024)S(1)} %copy-done.10, u16[1271808,8]{1,0:T(8,128)(2,1)}"
            " %pad.2, f32[64,8,512]{2,1,0:T(8,128)S(1)} %f), "
            "custom_call_target=\"tpu_custom_call\"")
    assert trace.shapes_of(text)[3] == ("u16", (1271808, 8))
    assert trace.op_label(text) == "%pq_topk.1 custom-call"
    assert trace.op_label("%pad.2 = u16[8]{0} pad(u16[7]{0} %c)") == \
        "%pad.2 pad"
    ev = trace.Event(text, 0, 10)
    assert system.kernel_shapes(ev) == {"batch": 64, "n_items": 1271808,
                                        "m": 8, "b": 512, "k": 16,
                                        "code_bytes": 2}


def test_idle_gaps_are_split_by_host_span():
    spans = [trace.Event("device.wait", 0, 50),
             trace.Event("engine.prepare", 60, 80)]
    out = trace._attribute([(40, 100)], spans)
    assert out["device.wait"] == pytest.approx(10e-9)
    assert out["engine.prepare"] == pytest.approx(20e-9)
    assert out["loop.other"] == pytest.approx(30e-9)
