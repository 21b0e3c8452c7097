"""``bench/engine_spans.py`` and the readers of the engine's own metrics,
on a fake run: the window of records, its faults, and each metric."""
from types import SimpleNamespace

import pytest

from bench import engine_spans, harness
from bench.engine_loop import Batch
from repro.serving.engine import BatchSpan

READERS = ["engine.queue_ms.serve", "engine.flight_ms.serve",
           "engine.fill.serve", "engine.self_ms.serve", "engine.self_ms.batch"]


def _span(i, rows, bucket=4):
    """Batch ``i``: prepare 1 ms, launch 2 ms, in flight 10 ms, complete
    4 ms of which the device wait is 3 ms; each row waited 5 ms."""
    t = 10.0 * i
    return BatchSpan(i, rows, bucket, 8, 0.005 * rows, t_prepare=t,
                     t_prepared=t + 0.001, t_launch=t + 0.001,
                     t_launched=t + 0.003, t_complete=t + 0.010,
                     t_ready=t + 0.013, t_fetched=t + 0.0135,
                     t_done=t + 0.014)


def _ctx(spans, rows):
    engine = SimpleNamespace(batch_spans=spans)
    batches = [Batch(list(range(n)), [1] * n) for n in rows]
    return {"loop": SimpleNamespace(engine=engine, batches=batches)}


def test_window_is_the_last_records_when_the_engine_holds_more():
    spans = [_span(0, 4), _span(1, 1), _span(2, 3), _span(3, 2)]
    got = engine_spans.window(_ctx(spans, [3, 2]))
    assert [s.batch_index for s in got] == [2, 3]


def test_window_raises_when_the_engine_holds_fewer():
    with pytest.raises(RuntimeError, match="holds 1 batch records for 2"):
        engine_spans.window(_ctx([_span(0, 3)], [3, 3]))


def test_window_raises_when_the_rows_do_not_line_up():
    with pytest.raises(RuntimeError, match="do not line up"):
        engine_spans.window(_ctx([_span(0, 3), _span(1, 2)], [2, 3]))


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_no_number(name):
    """A program without the records, and a run without batches."""
    read = harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read
    ctx = _ctx([], [])
    assert read(ctx) is None
    ctx["loop"].engine = SimpleNamespace()
    ctx["loop"].batches = [Batch([0], [1])]
    assert read(ctx) is None


@pytest.mark.parametrize("name,value", [
    ("engine.queue_ms.serve", 5.0),
    ("engine.flight_ms.serve", 10.0),
    ("engine.fill.serve", 100.0 * 5 / 8),
    ("engine.self_ms.serve", 1.0 + 2.0 + (4.0 - 3.0)),
    ("engine.self_ms.batch", 1.0 + 2.0 + (4.0 - 3.0)),
])
def test_readers(name, value):
    read = harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read
    spans = [_span(0, 4), _span(1, 3), _span(2, 2)]
    assert read(_ctx(spans, [3, 2])) == pytest.approx(value)
