"""The serving loop the timed window drives, and the host spans around it.

It drives ``RetrievalEngine``'s split-phase API the way the replicated
router's worker does: a batch the engine's batcher calls ready is
prepared (padded into its power-of-two bucket) and launched while at most
one other batch is still on the device; otherwise the loop blocks on the
oldest batch in flight and completes it.  The device wait
(``jax.block_until_ready``) is its own span, so ``engine.prepare``,
``engine.launch`` and ``engine.complete`` are host time only.

The depth is a serving policy, and it belongs to the program: the
router's worker keeps two batches in flight, but the engine offers no
loop of its own to call, so this one copies the worker's.  Once the
program exposes its dispatch loop, the benchmark should call that.
"""
from __future__ import annotations

import collections
import contextlib
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax

DEPTH = 2          # batches in flight, as the router's worker keeps


@dataclass
class Answer:
    """What came back for one request: its top-k, when the loop had it
    (host monotonic clock), and whether the engine shed it."""
    rid: int
    items: Any
    scores: Any
    t_done: float
    shed: bool = False


@dataclass
class Batch:
    """One dispatched batch: its requests, their history lengths, and its
    host seconds in prepare, launch and complete."""
    rids: List[int]
    lengths: List[int]
    host_s: float = 0.0
    t_done: Optional[float] = None


class Loop:
    """``annotate`` makes each host span a profiler TraceAnnotation, so a
    device trace can tell what the host did in each idle gap."""

    def __init__(self, engine, annotate: bool = False):
        self.engine = engine
        self.annotate = annotate
        self.inflight: collections.deque = collections.deque()
        self.batches: List[Batch] = []
        self.answers: Dict[int, Answer] = {}

    def _span(self, name: str):
        return (jax.profiler.TraceAnnotation(name) if self.annotate
                else contextlib.nullcontext())

    @property
    def has_room(self) -> bool:
        return len(self.inflight) < DEPTH

    def launch(self, reqs) -> int:
        """Prepare and launch ``reqs`` as one batch. -> requests answered
        at once (shed by the engine before dispatch)."""
        eng = self.engine
        t0 = time.monotonic()
        with self._span("engine.prepare"):
            shed, prep = eng.prepare(reqs)
        now = time.monotonic()
        for r in shed:
            self.answers[r.request_id] = Answer(r.request_id, r.items,
                                                r.scores, now, shed=True)
        if prep is None:
            return len(shed)
        with self._span("engine.launch"):
            inflight = eng.launch(prep)
        batch = Batch([r.request_id for r in prep.requests],
                      [len(r.payload) for r in prep.requests],
                      host_s=time.monotonic() - t0)
        self.inflight.append((inflight, batch))
        self.batches.append(batch)
        return len(shed)

    def finish_oldest(self) -> int:
        """Block on the oldest batch in flight and complete it. -> requests
        answered."""
        inflight, batch = self.inflight.popleft()
        with self._span("device.wait"):
            jax.block_until_ready(inflight.out)
        t0 = time.monotonic()
        with self._span("engine.complete"):
            results = self.engine.complete(inflight)
        now = time.monotonic()
        batch.host_s += now - t0
        batch.t_done = now
        for r in results:
            self.answers[r.request_id] = Answer(r.request_id, r.items,
                                                r.scores, now, shed=r.shed)
        return len(results)

    def idle(self, seconds: float) -> None:
        with self._span("loop.idle"):
            time.sleep(seconds)
