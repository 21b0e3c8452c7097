"""Batched serving engine.

Two engines share the queue/batcher machinery:

* ``RetrievalEngine`` — the paper's serving mode: request = user history,
  response = top-K items.  Backbone -> phi -> PQTopK -> TopK, batched.
* ``DecodeEngine``    — LM decode with slot-based continuous batching: a
  fixed pool of KV-cache slots; requests claim a slot, every ``step()``
  decodes one token for all active slots through the PQ vocab head.

Both apply deadline-based request timeouts (serving-side straggler
mitigation, same policy knob as training's StragglerMonitor).

``RetrievalEngine`` keeps one :class:`BatchSpan` per completed batch and
marks its three calls as profiler spans (``serve.prepare``,
``serve.launch``, ``serve.complete`` with ``serve.wait`` and
``serve.fetch`` inside), so a ``jax.profiler`` trace shows what the
engine's host was doing on the device ops' clock (docs/SERVING.md).
"""
from __future__ import annotations

import collections
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import auto_axes
from repro.training.fault_tolerance import (SimulatedFailure,
                                            StragglerMonitor)


@dataclass
class Request:
    request_id: int
    payload: Any                      # user seq (np.ndarray) / prompt ids
    k: int = 10
    arrival: float = field(default_factory=time.monotonic)
    # Requests past their deadline are SHED before dispatch (run_once)
    # and count as timeouts.  The default is deliberately lenient — a
    # cold engine's first dispatch compiles, which on a loaded host can
    # take seconds, and a request with no explicit latency contract
    # should be served late rather than dropped.  Pass a tight
    # deadline_ms to opt into real shedding.
    deadline_ms: float = 60_000.0


@dataclass
class Result:
    request_id: int
    items: np.ndarray
    scores: np.ndarray
    latency_ms: float
    timed_out: bool = False
    # A shed request was never scored: either it was already past its
    # deadline before dispatch (load shedding — items/scores empty), or
    # its batch exhausted the retry budget after injected/real failures,
    # or the router's degradation ladder dropped it under overload.
    shed: bool = False
    # Exactness contract (docs/SERVING.md): every step of the router's
    # load-degradation ladder that can change what the client receives is
    # tagged here ("k_cap", "k_cap+rung_pin", "load_shed", ...).  An empty
    # tag on a non-shed result asserts the full exact serving path ran —
    # the chaos harness holds those results bit-identical to the
    # single-engine oracle.
    degraded: str = ""
    # Which replica served this result (-1: single-engine / shed before
    # dispatch) and whether it was raced against a hedge re-issue.
    replica: int = -1
    hedged: bool = False
    # Catalogue version watermark (durable-mutation routing, ISSUE 10):
    # the serving replica's applied LSN at dispatch time, or -1 when the
    # fabric serves an immutable catalogue.  A result whose replica
    # lagged the committed LSN past the router's staleness budget also
    # carries degraded="stale_catalogue".
    lsn: int = -1


class MicroBatcher:
    """Greedy size/timeout batcher with power-of-two padding buckets so jit
    recompiles stay bounded.

    ``max_wait_ms`` is the partial-batch dispatch deadline: a batch is
    ``ready`` once it is full OR its oldest enqueued request has waited
    longer than ``max_wait_ms`` — the pipelined router loop polls
    :meth:`ready` so a trickle of requests dispatches after the wait
    expires instead of blocking on a full bucket (the synchronous
    ``drain`` path always flushes, so it never waits)."""

    def __init__(self, max_batch: int = 64, max_wait_ms: float = 2.0):
        self.max_batch = max_batch
        self.max_wait_ms = max_wait_ms
        self.queue: collections.deque[Request] = collections.deque()
        self._enq_t: collections.deque[float] = collections.deque()

    def submit(self, req: Request):
        self.queue.append(req)
        self._enq_t.append(time.monotonic())

    def oldest_wait_ms(self, now: Optional[float] = None) -> float:
        """How long the head-of-queue request has been waiting (0.0 when
        the queue is empty)."""
        if not self._enq_t:
            return 0.0
        return ((time.monotonic() if now is None else now)
                - self._enq_t[0]) * 1e3

    def ready(self, now: Optional[float] = None) -> bool:
        """True when a batch should dispatch: full bucket, or the oldest
        request has out-waited ``max_wait_ms``."""
        if len(self.queue) >= self.max_batch:
            return True
        return bool(self.queue) and self.oldest_wait_ms(now) >= self.max_wait_ms

    def next_batch(self) -> List[Request]:
        out = []
        while self.queue and len(out) < self.max_batch:
            out.append(self.queue.popleft())
            if self._enq_t:
                self._enq_t.popleft()
        return out

    @staticmethod
    def bucket(n: int, max_batch: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return min(b, max_batch)


#: How many of the latest completed batches ``RetrievalEngine`` keeps a
#: ``BatchSpan`` of, and how many request latencies it keeps for
#: ``stats()``: about half a minute of traffic at 2,000 requests/s.
SPAN_WINDOW = 2 ** 16


@dataclass(slots=True)
class BatchSpan:
    """One completed batch at the engine's boundaries, on the host's
    monotonic clock (seconds).  ``prepare`` runs from ``t_prepare`` to
    ``t_prepared``, ``launch`` from ``t_launch`` to ``t_launched`` (the
    last attempt's, under retries) and ``complete`` from ``t_complete`` to
    ``t_done``: it waits for the device until ``t_ready`` (the batch's
    request latencies are timed to it) and has the results on the host at
    ``t_fetched``.  ``queue_s`` sums ``t_prepare - Request.arrival`` over
    the batch's ``rows`` live requests; ``bucket`` is the padded rows sent
    to the device and ``kk`` the batch's compiled k."""
    batch_index: int
    rows: int
    bucket: int
    kk: int
    queue_s: float
    t_prepare: float
    t_prepared: float = 0.0
    t_launch: float = 0.0
    t_launched: float = 0.0
    t_complete: float = 0.0
    t_ready: float = 0.0
    t_fetched: float = 0.0
    t_done: float = 0.0


@dataclass
class PreparedBatch:
    """Host-side work of one dispatch, done: deadline-shed applied (twice
    — once on entry and once more after the variant compile, so a request
    whose deadline expired *during* a cold-start AOT compile is shed, not
    served late), requests padded into their pow2 bucket, the compiled
    variant resolved.  ``launch`` turns this into device work."""
    requests: List[Request]           # alive, in batch-row order
    seqs: Any                         # (bucket, seq_len) jnp.int32
    fn: Callable                      # compiled variant (takes seqs)
    kk: int                           # trace-static batch k
    batch_index: int
    span: BatchSpan                   # filled in by launch and complete
    degraded: str = ""


@dataclass
class InFlightBatch:
    """One asynchronously dispatched batch: the device owns ``out`` until
    :meth:`RetrievalEngine.complete` blocks on it.  The pipelined router
    loop keeps up to ``dispatch_depth`` of these per replica in flight
    while the host pads/dispatches the next batch."""
    prep: PreparedBatch
    out: Any
    straggler: bool = False           # set by complete()


class RetrievalEngine:
    """Paper-mode serving: top-K item retrieval for user sequences."""

    def __init__(self, serve_fn: Callable[[jax.Array, int], Tuple[jax.Array, jax.Array]],
                 *, seq_len: int, k: int = 10, max_k: Optional[int] = None,
                 max_batch: int = 64, method: Optional[str] = None,
                 jit_serve: bool = True, ladder: Optional[Tuple[int, ...]] = None,
                 head_state: Optional[Any] = None,
                 params: Optional[Any] = None,
                 faults: Optional[Any] = None, max_retries: int = 2,
                 retry_backoff_ms: float = 1.0,
                 straggler_factor: float = 3.0,
                 serve_fn_pinned: Optional[Callable] = None):
        """``serve_fn(item_seq (B,S) int32, k)`` -> (ids (B,k), scores).

        ``method`` is informational here (the scoring route is baked into
        ``serve_fn``); use :meth:`for_seqrec` to have the engine build the
        serve function for a named route itself.  Every built-in route —
        including the single-dispatch ``pqtopk_pruned`` cascade — is a pure
        traced function, so ``jit_serve=True`` is the norm; pass ``False``
        only for externally supplied serve functions that manage their own
        dispatch.

        Compiled serve variants are memoised per ``(batch_bucket, k_bucket,
        method)`` (AOT ``lower().compile()`` for jitted routes), and the
        variant count is surfaced as ``stats()["n_compiles"]`` so recompile
        behaviour is observable and regression-testable.

        ``max_k`` caps client-supplied ``Request.k`` — oversized k must not
        reach ``serve_fn`` (the fused kernel rejects k > tile, and any
        route fails at k > N), where it would abort every request in the
        batch.  Callers raising it above ``k`` are asserting that
        ``serve_fn`` can serve up to ``max_k`` winners (i.e. max_k <=
        min(N, kernel tile) for the baked-in route — :meth:`for_seqrec`
        derives this bound itself); the default is ``k``, which is always
        safe because ``serve_fn`` must support the engine's own k.

        ``ladder`` records the calibrated slot-budget ladder baked into a
        pruned ``serve_fn`` (informational; :meth:`for_seqrec` calibrates
        and sets it).  A ladder-enabled serve fn returns a third output —
        the rung taken — which the engine tallies into ``rung_counts`` so
        ``stats()["rung_hit_fraction"]`` reports how often serving stayed
        on a non-exhaustive rung.

        ``head_state`` makes the engine **hot-swappable**: ``serve_fn``
        then takes a third argument — a pytree of head arrays (codes,
        pruned metadata, tombstone mask) — which the engine threads as
        *data* into every dispatch and :meth:`swap_head_state` replaces
        between batches.  Compiled variants close over ``self`` and read
        the head late, so a swap with identical structure/shapes/dtypes
        costs ZERO recompiles — that invariant is what makes streaming
        catalogue mutation servable (docs/PRUNING.md §Catalogue
        mutation).

        ``params`` is a pytree of arrays ``serve_fn`` takes as its third
        argument in the same way, but fixed for the engine's life (no
        swap).  Passed as data, model weights and catalogue are not
        compiled into the executables as constants, and they keep the
        placement they were given: a catalogue split over a mesh stays
        split, each device holding its own rows.

        ``faults`` (a ``ServeFaultInjector``) plus ``max_retries`` /
        ``retry_backoff_ms`` give :meth:`run_once` graceful degradation:
        a failed dispatch retries with exponential backoff, exhausted
        retries shed the batch (``Result.shed``) instead of crashing, and
        already-expired requests are shed before padding/dispatch.  A
        ``StragglerMonitor`` (``straggler_factor`` x rolling median)
        flags slow batches into ``stats()["stragglers"]``.

        ``serve_fn_pinned`` is the optional *degraded* serve route the
        router's load ladder steps down to (``rung_pin``): same signature
        as ``serve_fn``, typically the pruned cascade pinned to its
        cheapest calibrated rung (bounded cost, possibly inexact — every
        result served through it is tagged ``Result.degraded``).
        Compiled variants are memoised separately per (bucket, k, method,
        pinned) key.
        """
        self._serve_fn = serve_fn
        self._jit_serve = jit_serve
        self._fn = (jax.jit(serve_fn, static_argnums=(1,)) if jit_serve
                    else serve_fn)
        self._serve_fn_pinned = serve_fn_pinned
        self._fn_pinned = None
        if serve_fn_pinned is not None:
            self._fn_pinned = (jax.jit(serve_fn_pinned, static_argnums=(1,))
                               if jit_serve else serve_fn_pinned)
        self._compiled: Dict[Tuple[int, int, Optional[str]], Callable] = {}
        self._executables: Dict[Tuple[int, int, Optional[str], bool],
                                Any] = {}
        self.seq_len = seq_len
        self.k = k
        self.max_k = k if max_k is None else max(max_k, k)
        self.method = method
        self.ladder = None if ladder is None else tuple(ladder)
        self.rung_counts: collections.Counter = collections.Counter()
        self.batcher = MicroBatcher(max_batch=max_batch)
        # The latest SPAN_WINDOW request latencies and batch records;
        # n_timed and n_batches count every one since the engine started.
        self.latencies_ms: collections.deque[float] = collections.deque(
            maxlen=SPAN_WINDOW)
        self.batch_spans: collections.deque[BatchSpan] = collections.deque(
            maxlen=SPAN_WINDOW)
        self.n_timed = 0
        self.n_batches = 0
        self.timeouts = 0
        if head_state is not None and params is not None:
            raise ValueError("pass head_state (swappable) or params "
                             "(fixed), not both")
        # Arrays threaded into every dispatch as serve_fn's third arg.
        self._data = head_state if head_state is not None else params
        self._swappable = head_state is not None
        self._head_treedef = None
        self._head_sds = None
        if self._data is not None:
            self._head_treedef = jax.tree_util.tree_structure(self._data)
            self._head_sds = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=getattr(a, "sharding", None)),
                self._data)
        self.faults = faults
        self.max_retries = max_retries
        self.retry_backoff_ms = retry_backoff_ms
        self.straggler_monitor = StragglerMonitor(factor=straggler_factor)
        self.retried = 0
        self.shed = 0
        self.n_swaps = 0
        self._batch_index = 0

    @classmethod
    def for_seqrec(cls, params, cfg, *, k: int = 10, max_batch: int = 64,
                   method: Optional[str] = None, sharded_mesh=None,
                   calibrate: Optional[bool] = None,
                   survival_stats: Optional[Sequence[int]] = None,
                   ladder: Optional[Tuple[int, ...]] = None,
                   faults: Optional[Any] = None,
                   max_retries: int = 2,
                   retry_backoff_ms: float = 1.0,
                   ) -> "RetrievalEngine":
        """Stand up an engine on a seqrec model with an explicit scoring
        route.  ``method=None`` falls back to ``cfg.serve_method`` — the
        production configs default to ``"pqtopk_fused"`` (the Pallas fused
        score+top-k kernel).  ``method="pqtopk_pruned"`` is the
        single-dispatch in-graph cascade: backbone, bounds, theta seeding,
        survivor compaction and compacted scoring all trace into ONE jitted
        serve function — no host sync anywhere on the serve path.

        For the pruned route the engine also installs a **calibrated
        slot-budget ladder**: a one-shot calibration pass at build time
        (``calibrate``, default on; or recorded ``survival_stats`` — a
        sequence of surviving-tile counts from production traffic) feeds
        ``pruning.calibrate_ladder``, and the resulting 2-3 rung ladder of
        power-of-two budgets is baked into the serve fn.  The common case
        then scores a small compacted buffer, overflow escalates rung by
        rung inside the same dispatch, and the final rung is always
        exhaustive — exactness at any skew.  An explicit ``ladder`` skips
        calibration entirely; ``calibrate=False`` disables the ladder.

        When ``cfg.pq.query_grouping`` is on, calibration is
        **group-aware**: the observable is the MAX per-group survivor
        count (``pruning.survival_count_grouped``) rather than the
        batch-any union count, because that is what the grouped ladder
        escalates on — union-count rungs would be needlessly tall and
        forfeit most of the per-group win.
        """
        from repro.core import pruning, retrieval_head
        from repro.kernels.pqtopk import kernel as pqtopk_kernel
        from repro.models import seqrec as seqrec_lib
        method = method or getattr(cfg, "serve_method", "pqtopk")
        if sharded_mesh is not None:
            sharded_mesh = auto_axes(sharded_mesh)
        # Largest k any route built here can serve: bounded by the
        # catalogue, and for the fused-kernel-backed routes also by the
        # kernel's item tile (pq_topk / pq_topk_tiles reject k > tile).
        max_k = cfg.n_items
        if method in ("pqtopk_fused", "pqtopk_pruned"):
            max_k = min(max_k, pqtopk_kernel.DEFAULT_TILE)

        if method == "pqtopk_pruned" and sharded_mesh is not None:
            # Align the pruning-tile layout to the mesh ONCE at engine
            # build, so the sharded cascade never rebuilds metadata.
            params = {**params, "item_emb":
                      retrieval_head.ensure_sharded_pruned_state(
                          params["item_emb"], sharded_mesh, k_hint=max_k)}

        if method == "pqtopk_pruned" and ladder is None \
                and calibrate is not False:
            state = params["item_emb"].get("pruned") \
                if retrieval_head.is_pq(params["item_emb"]) else None
            if isinstance(state, pruning.PrunedHeadState):
                counts = (list(survival_stats)
                          if survival_stats is not None else
                          cls._observe_survival(params, cfg, k=k,
                                                max_batch=max_batch))
                # Sharded states tile per shard: calibrate rungs against
                # the per-shard tile count the sharded cascade compacts.
                t = (state.tiles_per_shard if state.shards > 1
                     else state.n_tiles)
                counts = [c if state.shards <= 1 else -(-c // state.shards)
                          for c in counts]
                ladder = pruning.calibrate_ladder(counts, t, k, state.tile)

        with_rung = method == "pqtopk_pruned" and ladder is not None
        if sharded_mesh is not None:
            # Weights on every device of the mesh; on the pruned route
            # each device holds only its own slice of the catalogue.
            split = method == "pqtopk_pruned"
            placed = jax.device_put(
                {k: v for k, v in params.items()
                 if not (split and k == "item_emb")},
                NamedSharding(sharded_mesh, P()))
            if split:
                placed["item_emb"] = retrieval_head.place_sharded(
                    params["item_emb"], sharded_mesh)
            params = placed

        # Jitted, this closure is the module ``jit_serve_fn``: the name a
        # device trace (and the benchmark) finds the serve step by.
        def serve_fn(seqs, kk, p):
            return seqrec_lib.serve_topk(p, seqs, cfg, k=kk,
                                         method=method,
                                         sharded_mesh=sharded_mesh,
                                         ladder=ladder,
                                         return_rung=with_rung)

        # Degraded route for the router's load ladder: the same cascade
        # pinned to its cheapest calibrated rung (no exhaustive
        # escalation — bounded cost, possibly inexact, so every result
        # served through it is tagged).  Only built when the ladder has a
        # genuinely non-exhaustive rung: a one-tile catalogue's pinned
        # rung IS the exhaustive rung and would degrade nothing.
        serve_fn_pinned = None
        if method == "pqtopk_pruned" and ladder is not None \
                and sharded_mesh is None:
            state = params["item_emb"].get("pruned") \
                if retrieval_head.is_pq(params["item_emb"]) else None
            n_tiles = getattr(state, "n_tiles", None)
            if n_tiles is None or min(ladder) < n_tiles:
                def serve_fn_pinned(seqs, kk, p):
                    return seqrec_lib.serve_topk(p, seqs, cfg, k=kk,
                                                 method=method,
                                                 ladder=ladder,
                                                 pin_rung=True)

        return cls(serve_fn, seq_len=cfg.max_seq_len, k=k, max_k=max_k,
                   max_batch=max_batch, method=method, ladder=ladder,
                   params=params, faults=faults, max_retries=max_retries,
                   retry_backoff_ms=retry_backoff_ms,
                   serve_fn_pinned=serve_fn_pinned)

    @classmethod
    def for_seqrec_mutable(cls, params, cfg, mstate, *, k: int = 10,
                           max_batch: int = 64,
                           calibrate: Optional[bool] = None,
                           survival_stats: Optional[Sequence[int]] = None,
                           ladder: Optional[Tuple[int, ...]] = None,
                           faults: Optional[Any] = None,
                           max_retries: int = 2,
                           retry_backoff_ms: float = 1.0,
                           ) -> "RetrievalEngine":
        """Engine over a **mutable catalogue**: serve the single-dispatch
        pruned cascade against a ``mutation.MutableHeadState`` whose
        codes / bounds / tombstone mask are threaded through every
        dispatch as data and hot-swapped between batches with
        :meth:`swap_head_state` — zero recompiles per mutation because
        the pow2-padded capacity keeps every shape static.

        The serve fn merges the swapped head arrays over ``params``'s
        item head: ``codes`` (capacity rows), the incrementally
        maintained ``pruned`` state (bounds may be stale after deletes —
        still dominating, hence still exact), and ``live`` (the
        tombstone mask the cascade's theta seeding and kernel both
        honour, so delisted items can never surface).  Calibration runs
        against the initial head with the mask threaded through
        ``pruning.survival_count``.
        """
        from repro.core import pruning
        from repro.kernels.pqtopk import kernel as pqtopk_kernel
        from repro.models import seqrec as seqrec_lib
        head0 = mstate.head_arrays() if hasattr(mstate, "head_arrays") \
            else dict(mstate)
        max_k = min(cfg.n_items, pqtopk_kernel.DEFAULT_TILE)

        def merged(head):
            return {**params, "item_emb": {**params["item_emb"],
                                           "codes": head["codes"],
                                           "pruned": head["pruned"],
                                           "live": head["live"]}}

        if ladder is None and calibrate is not False:
            counts = (list(survival_stats)
                      if survival_stats is not None else
                      cls._observe_survival(merged(head0), cfg, k=k,
                                            max_batch=max_batch))
            state = head0["pruned"]
            ladder = pruning.calibrate_ladder(counts, state.n_tiles, k,
                                              state.tile)
        with_rung = ladder is not None

        def serve_fn(seqs, kk, head):
            return seqrec_lib.serve_topk(merged(head), seqs, cfg, k=kk,
                                         method="pqtopk_pruned",
                                         ladder=ladder,
                                         return_rung=with_rung)

        return cls(serve_fn, seq_len=cfg.max_seq_len, k=k, max_k=max_k,
                   max_batch=max_batch, method="pqtopk_pruned",
                   ladder=ladder, head_state=head0, faults=faults,
                   max_retries=max_retries,
                   retry_backoff_ms=retry_backoff_ms)

    @staticmethod
    def _observe_survival(params, cfg, *, k: int, max_batch: int,
                          n_batches: int = 3, seed: int = 0) -> List[int]:
        """One-shot build-time calibration pass: surviving-tile counts of
        the pruned cascade's bounds+theta prefix (no scoring) over a few
        synthetic request batches at representative batch sizes.  Survival
        uses the batch-any rule, so small and full batches bracket the
        counts serving will see.  Production deployments can skip this by
        recording real counts and passing ``survival_stats``."""
        from repro.core import pruning, retrieval_head, scoring
        from repro.models import seqrec as seqrec_lib
        head = params["item_emb"]
        state = head["pruned"]
        seed_kw = retrieval_head._seed_kwargs(getattr(cfg, "pq", None))

        pq = getattr(cfg, "pq", None)
        grouped = pq is not None and pq.query_grouping and pq.n_groups > 1

        st = state
        if state.shards > 1:
            # Flat counts from a per-shard layout would misread tile
            # boundaries; bound each shard's tile block independently
            # (same layout the sharded cascade sees) and sum.
            st = pruning.build_pruned_state(
                head["codes"], state.b, state.tile, backend=state.backend)

        def count_fn(params, st, seqs):
            head = params["item_emb"]
            phi = seqrec_lib.sequence_embedding(params, seqs, cfg)
            s = scoring.subid_scores(head["sub_emb"].astype(jnp.float32),
                                     phi.astype(jnp.float32))
            live = head.get("live")
            if grouped:
                # Group-aware observable: the grouped ladder escalates on
                # the max per-group count, so calibrate against that.
                return pruning.survival_count_grouped(
                    head["codes"], s, k, st, n_groups=pq.n_groups,
                    live=live, **seed_kw)
            return pruning.survival_count(head["codes"], s, k, st,
                                          live=live, **seed_kw)

        fn = jax.jit(count_fn)
        rng = np.random.default_rng(seed)
        counts = []
        for bsz in dict.fromkeys((1, min(8, max_batch), max_batch)):
            for _ in range(n_batches):
                seqs = rng.integers(
                    1, cfg.n_items + 1,
                    (bsz, cfg.max_seq_len)).astype(np.int32)
                counts.append(int(fn(params, st, jnp.asarray(seqs))))
        return counts

    def submit(self, req: Request):
        self.batcher.submit(req)

    def batch_k(self, ks: Sequence[int]) -> int:
        """The trace-static k this engine compiles for a batch whose client
        ks are ``ks``: each clamped into [1, max_k] (an unvalidated
        oversized k would abort the whole batch inside serve_fn), floored
        at the engine's own k, then bucketed to a power of two so distinct
        client values cannot drive unbounded jit recompiles — same policy
        as the batch-size padding buckets.  Factored out of
        :meth:`run_once` so the recompile-hazard analysis pass
        (``repro.analysis.passes.recompile``) probes the real mapping that
        keys compiled variants, not a re-implementation of it."""
        kk = max(max(min(int(k), self.max_k) for k in ks), self.k, 1)
        return MicroBatcher.bucket(kk, self.max_k)

    def _variant(self, bucket: int, kk: int, pinned: bool = False) -> Callable:
        """Memoised serve variant for one (batch_bucket, k_bucket, method,
        pinned) key.

        Jitted routes are AOT-lowered and compiled once per key, so
        ``stats()["n_compiles"]`` counts real compilations — the padding
        buckets guarantee the key space is O(log(max_batch) * log(max_k)).
        Returned callables take the (bucketed) sequence batch only.
        ``pinned=True`` resolves against the degraded rung-pinned serve
        route (``serve_fn_pinned``); callers must fall back to
        ``pinned=False`` when :attr:`has_pinned` is unset.
        """
        if pinned and self._fn_pinned is None:
            raise ValueError("no pinned (degraded) serve fn on this engine")
        key = (bucket, kk, self.method, pinned)
        fn = self._compiled.get(key)
        if fn is None:
            jfn = self._fn_pinned if pinned else self._fn
            sfn = self._serve_fn_pinned if pinned else self._serve_fn
            if self._jit_serve:
                sds = jax.ShapeDtypeStruct((bucket, self.seq_len), jnp.int32)
                try:
                    if self._data is not None:
                        # Head arrays are DATA: lower against their
                        # shapes/dtypes/placement once, read
                        # ``self._data`` late at every call so
                        # swap_head_state takes effect with zero
                        # recompiles.
                        exe = jfn.lower(sds, kk, self._head_sds).compile()
                        fn = lambda seqs, _e=exe: _e(seqs, self._data)
                    else:
                        exe = jfn.lower(sds, kk).compile()
                        fn = lambda seqs, _e=exe: _e(seqs)
                    self._executables[key] = exe
                except (jax.errors.TracerArrayConversionError,
                        jax.errors.TracerBoolConversionError,
                        jax.errors.ConcretizationTypeError):
                    # Unlowerable serve fn (caller-supplied closure doing
                    # host work): fall back to jit's dispatch cache — the
                    # key still counts one logical compile per variant.
                    # Genuine compile failures (OOM, lowering bugs) are NOT
                    # swallowed: they raise here, before any request of the
                    # batch is half-served, and never inflate n_compiles.
                    if self._data is not None:
                        fn = lambda seqs, _k=kk, _f=jfn: _f(
                            seqs, _k, self._data)
                    else:
                        fn = lambda seqs, _k=kk, _f=jfn: _f(seqs, _k)
            elif self._data is not None:
                fn = lambda seqs, _k=kk, _f=sfn: _f(
                    seqs, _k, self._data)
            else:
                fn = lambda seqs, _k=kk, _f=sfn: _f(seqs, _k)
            self._compiled[key] = fn
        return fn

    def warmup(self, buckets: Sequence[int], ks: Sequence[int] = (),
               pinned: bool = False) -> None:
        """AOT-compile the serve variant of every padding bucket that
        batch sizes ``buckets`` fall in, at the engine's k and any client
        ``ks``, before traffic arrives: a cold compile inside the serving
        loop would be charged to the first requests' latency."""
        kks = {self.batch_k([k]) for k in set(ks) | {self.k}}
        for b in buckets:
            for kk in kks:
                self._variant(MicroBatcher.bucket(b, self.batcher.max_batch),
                              kk, pinned=pinned)

    @property
    def data(self) -> Optional[Any]:
        """The arrays threaded into every dispatch (``params`` or the
        swappable ``head_state``), as placed on the device(s)."""
        return self._data

    def compiled_hlo(self) -> Dict[Tuple[int, int, Optional[str], bool],
                                   str]:
        """Optimised HLO text of every AOT-compiled serve variant, by
        variant key: what the device runs.  A Pallas kernel shows up as a
        ``tpu_custom_call`` when compiled for a TPU."""
        return {key: exe.as_text()
                for key, exe in self._executables.items()}

    @property
    def has_pinned(self) -> bool:
        """Whether this engine carries a degraded rung-pinned serve route
        (the router's ladder step 2 falls back to step 1 without one)."""
        return self._fn_pinned is not None

    def swap_head_state(self, head) -> None:
        """Replace the served head arrays between batches — zero recompiles.

        Accepts either the pytree ``head_arrays()`` returns or any object
        exposing that method (e.g. ``mutation.MutableHeadState``).  The
        swap is validated structurally: the pytree treedef (which carries
        the pruned state's static metadata — tile, capacity, backend) and
        every leaf's shape/dtype must match what the engine compiled
        against, because those are baked into the AOT executables.  The
        pow2-capacity design in ``core.mutation`` exists precisely so
        live churn never trips this check; a capacity *growth* must build
        a new engine (a new compile is then honest and expected)."""
        if not self._swappable:
            raise ValueError(
                "engine was not built with a swappable head; use "
                "for_seqrec_mutable (or pass head_state=) to enable "
                "hot swapping")
        if hasattr(head, "head_arrays"):
            head = head.head_arrays()
        leaves, treedef = jax.tree_util.tree_flatten(head)
        if treedef != self._head_treedef:
            raise ValueError(
                f"swapped head structure {treedef} differs from the "
                f"compiled structure {self._head_treedef}; hot swap "
                "requires identical static metadata")
        for old, new in zip(jax.tree_util.tree_leaves(self._head_sds),
                            leaves):
            if old.shape != new.shape or old.dtype != new.dtype:
                raise ValueError(
                    f"hot swap would change a head leaf from "
                    f"{old.shape}/{old.dtype} to {new.shape}/{new.dtype}; "
                    "capacity and dtypes are compile-static — rebuild the "
                    "engine to grow the catalogue")
        self._data = jax.tree_util.tree_unflatten(treedef, leaves)
        self.n_swaps += 1

    def _shed_result(self, r: Request, now: float,
                     degraded: str = "") -> Result:
        lat = (now - r.arrival) * 1e3
        timed_out = lat > r.deadline_ms
        self.shed += 1
        self.timeouts += int(timed_out)
        self.latencies_ms.append(lat)
        self.n_timed += 1
        return Result(r.request_id, np.empty(0, np.int32),
                      np.empty(0, np.float32), lat, timed_out=timed_out,
                      shed=True, degraded=degraded)

    @functools.partial(jax.profiler.annotate_function, name="serve.prepare")
    def prepare(self, reqs: List[Request], *, k_cap: Optional[int] = None,
                rung_pin: bool = False,
                ) -> Tuple[List[Result], Optional[PreparedBatch]]:
        """Host side of one dispatch: shed expired requests, pad the rest
        into their pow2 bucket, resolve (and if cold, compile) the serve
        variant.  Returns (shed results, prepared batch or None).

        ``k_cap``/``rung_pin`` are the router's degradation-ladder knobs:
        cap the batch k below the clients' asks, and/or route through the
        rung-pinned serve fn.  Both are recorded in
        ``PreparedBatch.degraded`` so every result carries its tag.

        Deadline shedding runs TWICE: once on entry, and once more after
        the variant lookup — a cold engine's first lookup AOT-compiles,
        which can take seconds, and a tight-deadline request that expired
        *during* that compile must come back ``timed_out`` instead of
        being served late.  The second pass keeps the already-compiled
        bucket (expired rows just become padding), so the compile is not
        wasted and later identical requests serve normally.
        """
        batch_index = self._batch_index
        self._batch_index += 1
        # Load shedding BEFORE padding/dispatch: a request already past
        # its deadline would burn a batch slot producing an answer nobody
        # is waiting for — and worse, widen the padding bucket for the
        # requests that are still alive.
        t_prepare = now = time.monotonic()
        results: List[Result] = []
        alive: List[Request] = []
        queue_s = 0.0
        for r in reqs:
            waited = now - r.arrival
            if waited * 1e3 > r.deadline_ms:
                results.append(self._shed_result(r, now))
            else:
                alive.append(r)
                queue_s += waited
        if not alive:
            return results, None
        bucket = MicroBatcher.bucket(len(alive), self.batcher.max_batch)
        # Requests in one batch may disagree on k: score once at the batch
        # max and slice each request's prefix — top-k prefixes nest, so
        # every request sees exactly its own top-k.  batch_k clamps and
        # buckets so client values cannot drive unbounded recompiles.
        kk = self.batch_k([r.k for r in alive])
        tags = []
        if k_cap is not None:
            capped = MicroBatcher.bucket(max(1, min(k_cap, self.max_k)),
                                         self.max_k)
            if capped < kk:
                kk = capped
                tags.append("k_cap")
        pinned = rung_pin and self.has_pinned
        if pinned:
            tags.append("rung_pin")
        fn = self._variant(bucket, kk, pinned=pinned)
        # Post-compile re-shed (same bucket — expired rows become padding).
        now = time.monotonic()
        survivors: List[Request] = []
        for r in alive:
            if (now - r.arrival) * 1e3 > r.deadline_ms:
                results.append(self._shed_result(r, now))
                queue_s -= t_prepare - r.arrival
            else:
                survivors.append(r)
        if not survivors:
            return results, None
        seqs = np.zeros((bucket, self.seq_len), np.int32)
        for i, r in enumerate(survivors):
            s = np.asarray(r.payload)[-self.seq_len:]
            seqs[i, -len(s):] = s
        span = BatchSpan(batch_index, len(survivors), bucket, kk, queue_s,
                         t_prepare)
        prep = PreparedBatch(survivors, jnp.asarray(seqs), fn, kk,
                             batch_index, span, degraded="+".join(tags))
        span.t_prepared = time.monotonic()
        return results, prep

    @functools.partial(jax.profiler.annotate_function, name="serve.launch")
    def launch(self, prep: PreparedBatch) -> InFlightBatch:
        """Dispatch a prepared batch asynchronously.  The returned handle's
        ``out`` is an in-flight device computation — the caller overlaps
        host work (padding the NEXT batch) with it and calls
        :meth:`complete` to block.  Injected faults
        (``ServeFaultInjector.check``) raise here, before dispatch, so
        the caller's retry loop sees them."""
        span = prep.span
        span.t_launch = time.monotonic()
        if self.faults is not None:
            self.faults.check(prep.batch_index)
        inflight = InFlightBatch(prep, prep.fn(prep.seqs))
        span.t_launched = time.monotonic()
        return inflight

    @functools.partial(jax.profiler.annotate_function, name="serve.complete")
    def complete(self, inflight: InFlightBatch) -> List[Result]:
        """Block until the dispatched batch has actually finished, then
        timestamp and slice per-request results.

        ``jax.block_until_ready`` comes FIRST: JAX dispatch is async even
        on CPU, so timestamping after the ``fn(seqs)`` call alone would
        measure enqueue cost, not completion — latency accounting and the
        straggler monitor would both read near-zero for a slow kernel."""
        prep = inflight.prep
        span = prep.span
        span.t_complete = time.monotonic()
        with jax.profiler.TraceAnnotation("serve.wait"):
            out = jax.block_until_ready(inflight.out)
        if self.faults is not None:
            delay = self.faults.delay_s(prep.batch_index)
            if delay:
                time.sleep(delay)  # synthetic straggler, lands in elapsed
        span.t_ready = now = time.monotonic()
        inflight.straggler = self.straggler_monitor.record(
            prep.batch_index, now - span.t_launch)
        with jax.profiler.TraceAnnotation("serve.fetch"):
            if len(out) == 3:
                # Ladder-enabled pruned route: third output is the rung
                # taken (an i32 scalar riding the same dispatch) — tally
                # it so stats() can report rung_hit_fraction.
                ids, scores, rung = out
                self.rung_counts[int(rung)] += 1
            else:
                ids, scores = out
            ids, scores = np.asarray(ids), np.asarray(scores)
        span.t_fetched = time.monotonic()
        results: List[Result] = []
        for i, r in enumerate(prep.requests):
            lat = (now - r.arrival) * 1e3
            timed_out = lat > r.deadline_ms
            self.timeouts += int(timed_out)
            self.latencies_ms.append(lat)
            rk = max(1, min(r.k, prep.kk))
            results.append(Result(r.request_id, ids[i, :rk],
                                  scores[i, :rk], lat, timed_out,
                                  degraded=prep.degraded))
        self.n_timed += len(prep.requests)
        self.n_batches += 1
        span.t_done = time.monotonic()
        self.batch_spans.append(span)
        return results

    def run_once(self, *, k_cap: Optional[int] = None,
                 rung_pin: bool = False) -> List[Result]:
        """Synchronous serve of one batch: prepare -> launch (with bounded
        retry) -> complete.  The pipelined router loop uses the pieces
        directly to keep multiple batches in flight."""
        reqs = self.batcher.next_batch()
        if not reqs:
            return []
        results, prep = self.prepare(reqs, k_cap=k_cap, rung_pin=rung_pin)
        if prep is None:
            return results
        # Bounded retry with exponential backoff: only *injected/declared*
        # failures (SimulatedFailure) are retried — they model transient
        # node faults.  Genuine serve bugs still raise.  Exhausted retries
        # shed the batch instead of crashing the serving loop.
        inflight = None
        for attempt in range(self.max_retries + 1):
            try:
                inflight = self.launch(prep)
                break
            except SimulatedFailure:
                if attempt >= self.max_retries:
                    break
                self.retried += 1
                time.sleep(self.retry_backoff_ms * (2 ** attempt) / 1e3)
        if inflight is None:
            # Retries exhausted: the batch never dispatched, so the
            # injector's straggler delay must NOT fire — sleeping here
            # would only inflate the shed requests' recorded latency.
            now = time.monotonic()
            results.extend(self._shed_result(r, now) for r in prep.requests)
            return results
        results.extend(self.complete(inflight))
        return results

    def drain(self) -> List[Result]:
        out = []
        while self.batcher.queue:
            out.extend(self.run_once())
        return out

    def stats(self) -> Dict[str, Any]:
        # No traffic yet -> None, NOT 0.0: a placeholder zero is a real
        # latency to any aggregator averaging across replicas and would
        # drag fleet percentiles toward zero.
        # mRT/p99 and the batch means cover the latest SPAN_WINDOW
        # requests and batches; count and batches are totals.
        lat = (np.asarray(self.latencies_ms) if self.latencies_ms
               else None)
        spans = self.batch_spans
        rows = sum(s.rows for s in spans)
        out: Dict[str, Any] = {
            "count": float(self.n_timed),
            "mRT_ms": float(np.median(lat)) if lat is not None else None,
            "p99_ms": (float(np.percentile(lat, 99))
                       if lat is not None else None),
            "timeouts": float(self.timeouts),
            "n_compiles": float(len(self._compiled)),
            "retried": float(self.retried),
            "shed": float(self.shed),
            "stragglers": float(len(self.straggler_monitor.flagged)),
            # Mean wait of a request from arrival to its batch's prepare.
            "queue_ms": (1e3 * sum(s.queue_s for s in spans) / rows
                         if spans else None),
            # Mean time a batch was in flight: from its launch returning
            # to complete's wait ending (its device run, behind whatever
            # was on the device before it).
            "flight_ms": (1e3 * sum(s.t_ready - s.t_launched for s in spans)
                          / len(spans) if spans else None),
            # Live rows over the padded rows sent to the device.
            "fill": rows / sum(s.bucket for s in spans) if spans else None,
            "batches": float(self.n_batches),
        }
        if self._swappable:
            out["n_swaps"] = float(self.n_swaps)
        if self.ladder is not None:
            # Fraction of served batches that stayed on a non-exhaustive
            # rung (the last rung of the normalised ladder scores every
            # tile); per-rung batch counts for the curious.
            total = sum(self.rung_counts.values())
            non_exhaustive = sum(c for r, c in self.rung_counts.items()
                                 if r < len(self.ladder) - 1)
            out["ladder"] = self.ladder
            out["rung_hit_fraction"] = (non_exhaustive / total if total
                                        else 0.0)
            out["rung_counts"] = dict(sorted(self.rung_counts.items()))
        return out


class DecodeEngine:
    """Slot-based continuous batching for LM decode."""

    def __init__(self, decode_fn, init_caches_fn, *, n_slots: int,
                 max_len: int, k: int = 8):
        """``decode_fn(tokens (B,), pos (B,), caches)`` ->
        (next_tokens (B,), caches); caches batched over slots."""
        self._decode = jax.jit(decode_fn)
        self.caches = init_caches_fn(n_slots)
        self.n_slots = n_slots
        self.max_len = max_len
        self.k = k
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.slot_pos = np.zeros(n_slots, np.int32)
        self.slot_token = np.zeros(n_slots, np.int32)
        self.slot_out: List[List[int]] = [[] for _ in range(n_slots)]
        self.waiting: collections.deque[Request] = collections.deque()
        self.finished: List[Tuple[Request, List[int]]] = []

    def submit(self, req: Request):
        self.waiting.append(req)

    def _admit(self):
        for s in range(self.n_slots):
            if self.slot_req[s] is None and self.waiting:
                req = self.waiting.popleft()
                self.slot_req[s] = req
                self.slot_pos[s] = 0
                self.slot_token[s] = int(np.asarray(req.payload).reshape(-1)[0])
                self.slot_out[s] = []

    def step(self, max_new: int = 16):
        """One engine iteration: admit, decode one token for all slots,
        retire finished requests."""
        self._admit()
        active = [s for s in range(self.n_slots) if self.slot_req[s]]
        if not active:
            return
        tokens = jnp.asarray(self.slot_token)
        pos = jnp.asarray(self.slot_pos)
        nxt, self.caches = self._decode(tokens, pos, self.caches)
        nxt = np.asarray(nxt)
        for s in active:
            self.slot_out[s].append(int(nxt[s]))
            self.slot_token[s] = int(nxt[s])
            self.slot_pos[s] += 1
            if self.slot_pos[s] >= min(max_new, self.max_len - 1):
                self.finished.append((self.slot_req[s], self.slot_out[s]))
                self.slot_req[s] = None

    def run(self, max_new: int = 16):
        while self.waiting or any(self.slot_req):
            self.step(max_new)
        return self.finished
