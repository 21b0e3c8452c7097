"""The serving engine's own accounting: one ``BatchSpan`` per completed
batch, the ``stats()`` counters read from them, the bounded latency
window, the ``serve.*`` profiler spans, and the named scopes on the
compiled serve step."""
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.serving import engine as engine_lib
from repro.serving.engine import BatchSpan, Request, RetrievalEngine
from repro.training.fault_tolerance import ServeFaultInjector

SPANS = {"serve.prepare", "serve.launch", "serve.complete", "serve.wait",
         "serve.fetch"}
#: Host span prefixes the benchmark's idle-gap attribution owns.
BENCH_PREFIXES = ("engine.", "device.", "loop.")
SCOPES = ("backbone", "head.table", "head.codes_pad", "head.kernel",
          "head.merge")


@pytest.fixture(scope="module")
def fused():
    """A reduced SASRec-RecJPQ engine on the fused PQTopK route, buckets
    of 1 and 4 compiled."""
    cfg = get_reduced("sasrec-recjpq").model
    from repro.models import seqrec
    params = seqrec.init_seqrec(jax.random.PRNGKey(0), cfg)
    eng = RetrievalEngine.for_seqrec(params, cfg, k=5, max_batch=4,
                                     method="pqtopk_fused")
    eng.warmup([1, 4])
    return eng, cfg


@pytest.fixture
def engine(fused):
    eng, cfg = fused
    eng.batch_spans.clear()
    return eng, cfg


def _reqs(cfg, n, start=0, **kw):
    rng = np.random.default_rng(start)
    return [Request(start + i, rng.integers(1, cfg.n_items + 1, 6), k=5,
                    **kw) for i in range(n)]


def _serve(eng, reqs):
    shed, prep = eng.prepare(reqs)
    assert prep is not None
    return shed + eng.complete(eng.launch(prep))


def _cheap_engine(**kw):
    def serve_fn(seqs, k):
        s = jnp.sum(seqs, axis=1, keepdims=True).astype(jnp.float32) \
            + jnp.arange(16, dtype=jnp.float32)[None, :]
        v, i = jax.lax.top_k(s, k)
        return i.astype(jnp.int32), v
    return RetrievalEngine(serve_fn, seq_len=4, k=4, max_batch=4, **kw)


def test_one_record_per_batch_through_the_split_calls_and_run_once(engine):
    eng, cfg = engine
    batches0 = eng.stats()["batches"]
    _serve(eng, _reqs(cfg, 4))
    for r in _reqs(cfg, 3, start=10):
        eng.submit(r)
    assert len(eng.run_once()) == 3
    spans = list(eng.batch_spans)
    assert [type(s) for s in spans] == [BatchSpan, BatchSpan]
    assert spans[1].batch_index == spans[0].batch_index + 1
    assert [s.rows for s in spans] == [4, 3]
    assert eng.stats()["batches"] == batches0 + 2
    for s in spans:
        assert (s.t_prepare <= s.t_prepared <= s.t_launch <= s.t_launched
                <= s.t_complete <= s.t_ready <= s.t_fetched <= s.t_done)


def test_a_batch_shed_whole_writes_no_record(engine):
    eng, cfg = engine
    old = time.monotonic() - 1.0
    res, prep = eng.prepare(_reqs(cfg, 2, arrival=old, deadline_ms=1.0))
    assert prep is None and all(r.shed for r in res)
    for r in _reqs(cfg, 2, arrival=old, deadline_ms=1.0):
        eng.submit(r)
    assert all(r.shed for r in eng.run_once())
    assert not eng.batch_spans


def test_rows_bucket_and_fill_of_three_in_a_bucket_of_four(engine):
    eng, cfg = engine
    _serve(eng, _reqs(cfg, 3))
    (span,) = eng.batch_spans
    assert (span.rows, span.bucket, span.kk) == (3, 4, 8)
    assert eng.stats()["fill"] == pytest.approx(0.75)


def test_queue_wait_counts_from_arrival(engine):
    eng, cfg = engine
    now = time.monotonic()
    late, fresh = _reqs(cfg, 2)
    late.arrival = now - 0.05
    _serve(eng, [late, fresh])
    (span,) = eng.batch_spans
    assert span.queue_s >= 0.05
    assert eng.stats()["queue_ms"] >= 25.0          # mean of two rows


def test_a_request_shed_after_the_compile_leaves_the_queue_wait():
    """The second shed pass (after a slow cold compile) takes the shed
    row's wait back out of ``queue_s``: it counts live rows only."""
    def slow_compile(seqs, k):
        time.sleep(0.3)                      # trace-time cost of the compile
        s = jnp.sum(seqs, axis=1, keepdims=True).astype(jnp.float32) \
            + jnp.arange(16, dtype=jnp.float32)[None, :]
        v, i = jax.lax.top_k(s, k)
        return i.astype(jnp.int32), v

    eng = RetrievalEngine(slow_compile, seq_len=4, k=4, max_batch=4)
    now = time.monotonic()
    doomed = Request(0, np.arange(1, 5), k=4, arrival=now - 0.05,
                     deadline_ms=100.0)
    res, prep = eng.prepare([doomed, Request(1, np.arange(1, 5), k=4)])
    assert [r.request_id for r in res] == [0] and res[0].shed
    span = prep.span
    assert span.rows == 1 and span.bucket == 2
    assert 0.0 <= span.queue_s < 0.05


def test_spans_add_up_to_each_request_latency(engine):
    """Back to back: queue + prepare + launch + flight + complete's own
    time is each request's latency, within a millisecond."""
    eng, cfg = engine
    reqs = _reqs(cfg, 4)
    for i, r in enumerate(reqs):
        r.arrival = time.monotonic() - 0.01 * i
    results = _serve(eng, reqs)
    (s,) = eng.batch_spans
    for req, res in zip(reqs, results):
        parts = ((s.t_prepare - req.arrival) + (s.t_prepared - s.t_prepare)
                 + (s.t_launched - s.t_launch) + (s.t_ready - s.t_launched)
                 + (s.t_done - s.t_ready))
        assert abs(1e3 * parts - res.latency_ms) < 1.0
    st = eng.stats()
    assert st["flight_ms"] == pytest.approx(1e3 * (s.t_ready - s.t_launched))


def test_retries_keep_the_last_attempts_launch():
    eng = _cheap_engine(faults=ServeFaultInjector(fail_at_batches=[0]),
                        max_retries=2, retry_backoff_ms=30.0)
    eng.submit(Request(0, np.arange(1, 5), k=4))
    (res,) = eng.run_once()
    assert not res.shed and eng.retried == 1
    (s,) = eng.batch_spans
    assert s.t_launch - s.t_prepared >= 0.03


def test_records_and_latencies_stop_at_their_window(monkeypatch):
    monkeypatch.setattr(engine_lib, "SPAN_WINDOW", 3)
    eng = _cheap_engine()
    for i in range(5):
        eng.submit(Request(i, np.arange(1, 5), k=4))
        eng.run_once()
    assert [s.batch_index for s in eng.batch_spans] == [2, 3, 4]
    assert len(eng.latencies_ms) == 3
    st = eng.stats()
    assert st["count"] == 5 and st["batches"] == 5


def test_the_window_is_two_to_the_sixteen(fused):
    eng, _ = fused
    assert eng.batch_spans.maxlen == eng.latencies_ms.maxlen == 2 ** 16


def test_clearing_latencies_after_warm_up_restarts_the_percentiles():
    """What ``launch/serve.py`` does after its warm-up: ``mRT_ms`` and
    ``p99_ms`` then read only later requests; ``count`` stays the total."""
    eng = _cheap_engine()
    eng.submit(Request(0, np.arange(1, 5), k=4,
                       arrival=time.monotonic() - 5.0))
    eng.run_once()
    assert eng.stats()["mRT_ms"] >= 5_000.0
    eng.latencies_ms.clear()
    eng.submit(Request(1, np.arange(1, 5), k=4))
    eng.run_once()
    st = eng.stats()
    assert st["mRT_ms"] < 1_000.0 and st["count"] == 2


def test_counters_with_no_traffic():
    st = _cheap_engine().stats()
    assert st["queue_ms"] is None and st["flight_ms"] is None
    assert st["fill"] is None and st["batches"] == 0


def _host_event_names(trace_dir: Path):
    from jax.profiler import ProfileData
    (path,) = trace_dir.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return {e.name for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events}


def test_profiler_trace_holds_the_serve_spans(engine, tmp_path):
    eng, cfg = engine
    with jax.profiler.trace(str(tmp_path)):
        _serve(eng, _reqs(cfg, 3))
    names = _host_event_names(tmp_path)
    assert SPANS <= names
    assert not [n for n in names if n.startswith(BENCH_PREFIXES)]


def test_compiled_step_keeps_its_module_name_and_scopes(fused):
    """The benchmark finds the serve step by its module name and reads the
    parts of the step by these scopes."""
    eng, _ = fused
    (text,) = [t for (bucket, *_), t in eng.compiled_hlo().items()
               if bucket == 4]
    assert re.match(r"HloModule jit_serve_fn\b", text)
    op_names = " ".join(re.findall(r'op_name="([^"]*)"', text))
    for scope in SCOPES:
        assert f"/{scope}/" in op_names, scope
