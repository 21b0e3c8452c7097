"""Open loop: independent users arrive as a Poisson process at a fixed rate.

Traffic keys: ``rate_per_s``, ``k``, ``max_batch``, ``history`` and
``items`` (see ``traffic_gen``).  Every request due in the window is sent
on schedule by a generator thread whether or not earlier ones have
finished, and is timed from its due time to the moment the loop holds its
result.  The window's length is the schedule's; the loop then serves what
is still queued, and that wait counts in the latencies.

A lead-in comes first and is not measured: two full batches at once, then
``LEAD_IN_S`` of arrivals at the mix's rate.  Under load the serving
loop settles with two batches in flight; starting from an empty queue it
can instead keep one in flight until a burst or a host stall tips it over,
which would make a run's median depend on when that happened.  So the
lead-in pins the loop's two-in-flight operating point; users send no
such burst.  Which point the loop should hold is the program's policy
(``engine_loop``).

End-to-end metrics: ``p50_ms`` and ``p90_ms`` (nearest rank) over every
request due in the window.  A shed request counts as missing any limit:
it enters the percentiles as ``MISSED_MS``.  The 99th percentile and the
number of host stalls (runs of requests sent ``STALL_MS`` or more late)
are on the run's ``window`` line: a stall of the whole process, which the
chip's host shows every few seconds, sets the 99th percentile.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Any, Dict, List

import numpy as np

from bench import traffic_gen
from bench.engine_loop import Loop

LEAD_S = 0.005     # the lead-in starts this long after drive() is called
LEAD_IN_S = 0.5    # seconds of lead-in arrivals before the window
MISSED_MS = 1e9    # the latency a shed request counts as
STALL_MS = 50.0


def warm_batches(traffic: Dict[str, Any]) -> List[int]:
    """Every power-of-two bucket up to max_batch: arrivals fill any."""
    out, b = [], 1
    while b < traffic["max_batch"]:
        out.append(b)
        b *= 2
    return out + [traffic["max_batch"]]


def _arrivals(n: int, rate: float, seed: int, stream: str) -> np.ndarray:
    gaps = traffic_gen.exponential_gaps(n, rate,
                                        traffic_gen.rng_for(seed, stream))
    return np.cumsum(gaps) - gaps[0]


def make_work(traffic: Dict[str, Any], cfg: Dict[str, Any], seed: int,
              seconds: float) -> List[traffic_gen.Work]:
    """The lead-in's requests (negative ids, due before 0), then the
    window's (ids from 0, due in [0, seconds))."""
    rate, k = traffic["rate_per_s"], traffic["k"]
    n = max(1, int(round(rate * seconds)))
    lead_due = np.concatenate([
        np.zeros(2 * traffic["max_batch"]),
        _arrivals(int(round(rate * LEAD_IN_S)), rate, seed, "lead")])
    hists = traffic_gen.histories(traffic, cfg, n + len(lead_due), seed)
    lead = [traffic_gen.Work(-1 - i, h, k, float(t) - LEAD_IN_S)
            for i, (h, t) in enumerate(zip(hists[n:], lead_due))]
    window = [traffic_gen.Work(i, h, k, float(t)) for i, (h, t) in
              enumerate(zip(hists, _arrivals(n, rate, seed, "gaps")))]
    return lead + window


def drive(loop: Loop, work: List[traffic_gen.Work], seconds: float,
          ) -> Dict[str, Any]:
    """Send ``work`` on schedule and serve it. -> the run's record."""
    from repro.serving.engine import Request
    engine = loop.engine
    lock = threading.Lock()
    lateness: Dict[int, float] = {}
    queue_len: List[tuple] = []
    t0 = time.monotonic() + LEAD_S + LEAD_IN_S

    def generate():
        for w in work:
            due = t0 + w.due
            while True:
                now = time.monotonic()
                if now >= due:
                    break
                time.sleep(min(due - now, 0.002))
            with lock:
                engine.submit(Request(w.rid, w.history, k=w.k, arrival=due))
            lateness[w.rid] = time.monotonic() - due

    # The generator gets the interpreter back within half a millisecond
    # of its due time, not Python's default 5 ms.
    switch = sys.getswitchinterval()
    sys.setswitchinterval(0.0005)
    gen = threading.Thread(target=generate, name="bench-generator")
    gen.start()
    answered = 0
    while answered < len(work):
        with lock:
            batch = None
            if loop.has_room and engine.batcher.ready():
                queue_len.append((time.monotonic() - t0,
                                  len(engine.batcher.queue)))
                batch = engine.batcher.next_batch()
        if batch:
            answered += loop.launch(batch)
        elif loop.inflight:
            answered += loop.finish_oldest()
        else:
            loop.idle(0.0002)
    gen.join()
    sys.setswitchinterval(switch)
    window = [w for w in work if w.rid >= 0]
    return {"t0": t0, "work": window, "seconds": seconds,
            "lateness_s": np.array([lateness[w.rid] for w in window]),
            "queue_len": queue_len}


def requests(record: Dict[str, Any]) -> Dict[int, traffic_gen.Work]:
    """Every request due in the window, by id."""
    return {w.rid: w for w in record["work"]}


def latencies_ms(loop: Loop, record: Dict[str, Any]) -> np.ndarray:
    t0 = record["t0"]
    out = np.full(len(record["work"]), MISSED_MS)
    for w in record["work"]:
        a = loop.answers.get(w.rid)
        if a is not None and not a.shed:
            out[w.rid] = (a.t_done - (t0 + w.due)) * 1e3
    return out


def _pct(lat: np.ndarray, q: float) -> float:
    return float(np.percentile(lat, q, method="inverted_cdf"))


def end_to_end(loop: Loop, record: Dict[str, Any]) -> Dict[str, float]:
    lat = latencies_ms(loop, record)
    return {"p50_ms": _pct(lat, 50), "p90_ms": _pct(lat, 90)}


def validity(loop: Loop, record: Dict[str, Any]) -> Dict[str, Any]:
    """Signals that say whether the run measured what it claims."""
    late = record["lateness_s"] * 1e3
    stalled = late >= STALL_MS
    q = record["queue_len"]
    half = record["seconds"] / 2
    first = [x for t, x in q if 0 <= t < half]
    second = [x for t, x in q if half <= t < record["seconds"]]
    return {
        "generator_lateness_ms": {"median": float(np.median(late)),
                                  "max": float(np.max(late))},
        "host_stalls": int(stalled[:1].sum()
                           + (stalled[1:] & ~stalled[:-1]).sum()),
        "p99_ms": _pct(latencies_ms(loop, record), 99),
        "queue_at_launch": {
            "first_half_mean": float(np.mean(first)) if first else None,
            "second_half_mean": float(np.mean(second)) if second else None},
    }
