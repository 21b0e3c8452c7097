"""Closed loop: a caller keeps two full batches of ``max_batch`` users on
the device (the serving loop's depth) and sends the next batch as soon as
one comes back.

Traffic keys: ``k``, ``max_batch``, ``pool`` (users generated up front
and cycled through), ``history`` and ``items`` (see ``traffic_gen``).

End-to-end metric: ``users_per_s``, the users whose top-k came back
inside the window over the window, which runs from the first launch to
the last completion inside ``seconds``.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

from bench import traffic_gen
from bench.engine_loop import Loop


def warm_batches(traffic: Dict[str, Any]) -> List[int]:
    return [traffic["max_batch"]]


def make_work(traffic: Dict[str, Any], cfg: Dict[str, Any], seed: int,
              seconds: float) -> List[traffic_gen.Work]:
    hists = traffic_gen.histories(traffic, cfg, traffic["pool"], seed)
    return [traffic_gen.Work(i, h, traffic["k"]) for i, h in enumerate(hists)]


def drive(loop: Loop, work: List[traffic_gen.Work], seconds: float,
          ) -> Dict[str, Any]:
    from repro.serving.engine import Request
    engine = loop.engine
    size = loop.engine.batcher.max_batch
    sent = 0

    def send():
        nonlocal sent
        for _ in range(size):
            w = work[sent % len(work)]
            engine.submit(Request(sent, w.history, k=w.k))
            sent += 1
        loop.launch(engine.batcher.next_batch())

    t0 = time.monotonic()
    deadline = t0 + seconds
    while loop.has_room:
        send()
    users, t_last = 0, t0
    while loop.inflight:
        batch = loop.inflight[0][1]
        loop.finish_oldest()
        if batch.t_done <= deadline:
            users += len(batch.rids)
            t_last = batch.t_done
        if time.monotonic() < deadline:
            send()
    return {"t0": t0, "t_last": t_last, "users": users, "sent": sent,
            "work": work, "seconds": seconds}


def requests(record: Dict[str, Any]) -> Dict[int, traffic_gen.Work]:
    """Every request sent, by id (ids count on past the pool)."""
    work = record["work"]
    return {rid: work[rid % len(work)] for rid in range(record["sent"])}


def end_to_end(loop: Loop, record: Dict[str, Any]) -> Dict[str, float]:
    span = record["t_last"] - record["t0"]
    return {"users_per_s": record["users"] / span if span > 0 else 0.0}


def validity(loop: Loop, record: Dict[str, Any]) -> Dict[str, Any]:
    return {"users_in_window": record["users"],
            "window_s": record["t_last"] - record["t0"]}
