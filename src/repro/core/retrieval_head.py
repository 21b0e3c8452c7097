"""RetrievalHead: "score a huge id space against a query vector, top-K".

The paper's technique packaged as the first-class head used by every arch
that retrieves from a large id space (seqrec items, LM vocab at decode,
recsys candidate catalogues).  Holds either

* a PQ representation  — ``{"codes": (N, m), "sub_emb": (m, b, d/m)}``, or
* a dense table        — ``{"table": (N, d)}`` (Transformer-Default baseline)

and exposes scoring via any of the paper's three algorithms plus the Pallas
kernel path and the item-sharded distributed path.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import PQConfig
from repro.core import pq as pq_lib
from repro.core import pruning, scoring, topk as topk_lib
from repro.distributed.sharding import manual_axis_map

Params = Dict[str, Any]

#: Methods accepted by ``top_items``/``serve_topk`` — the paper's three
#: algorithms plus the Pallas routes (scores-only kernel, fused
#: score+top-k kernel), the cascaded pruned route, and the approximate
#: block-max route.  Every method — including ``pqtopk_pruned``, whose
#: cascade is a single in-graph dispatch since PR 3 — is a pure traced
#: function of (params, phi): jittable, decode-loop and shard_map safe.
TOP_ITEMS_METHODS = ("dense", "recjpq", "pqtopk", "pqtopk_onehot",
                     "pqtopk_kernel", "pqtopk_fused", "pqtopk_pruned",
                     "pqtopk_approx")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(key: jax.Array, n_items: int, d_model: int,
         pq: Optional[PQConfig] = None, codes=None, centroids=None,
         dtype: Any = jnp.float32) -> Params:
    if pq is None:
        table = jax.random.normal(key, (n_items, d_model), jnp.float32) * 0.02
        return {"table": table.astype(dtype)}
    params = pq_lib.init_pq_embedding(key, pq, n_items, d_model, codes,
                                      centroids, dtype)
    # Query-independent pruning metadata (bit-packed code presence or
    # min/max code ranges, per pq.bound_backend), built once here and
    # carried in the param tree so the in-graph pruned cascade never
    # rebuilds it — not even inside a decode loop.  A frozen integer
    # buffer to the optimizer, like "codes".
    params["pruned"] = pruning.build_pruned_state(
        params["codes"], pq.b, DEFAULT_PRUNE_TILE,
        backend=pq.bound_backend)
    if pq.super_factor > 1:
        # Hierarchical super-tile level (docs/PRUNING.md §Hierarchical
        # bounds): built once here by reduction over the child metadata;
        # the cascade auto-detects it and inserts the super pass-0.
        params["pruned"] = pruning.with_super(params["pruned"],
                                              pq.super_factor)
    return params


def abstract(n_items: int, d_model: int, pq: Optional[PQConfig] = None,
             dtype: Any = jnp.float32) -> Params:
    if pq is None:
        return {"table": jax.ShapeDtypeStruct((n_items, d_model), dtype)}
    params = pq_lib.abstract_pq_embedding(pq, n_items, d_model, dtype)
    params["pruned"] = pruning.abstract_pruned_state(
        n_items, pq.m, pq.b, DEFAULT_PRUNE_TILE,
        backend=pq.bound_backend, super_factor=pq.super_factor)
    return params


def is_pq(params: Params) -> bool:
    return "codes" in params


def n_items(params: Params) -> int:
    return (params["codes"] if is_pq(params) else params["table"]).shape[0]


def embed(params: Params, ids: jax.Array) -> jax.Array:
    """Input-embedding lookup (shared with the head, as in RecJPQ)."""
    if is_pq(params):
        return pq_lib.reconstruct(params, ids)
    return jnp.take(params["table"], ids, axis=0)


# ---------------------------------------------------------------------------
# scoring
# ---------------------------------------------------------------------------

def score_all(params: Params, phi: jax.Array, method: str = "pqtopk",
              ) -> jax.Array:
    """All item scores (B, N) via the selected algorithm."""
    if method == "dense":
        w = (pq_lib.reconstruct_all(params) if is_pq(params)
             else params["table"])
        return scoring.score_dense(w.astype(phi.dtype), phi)
    if not is_pq(params):
        raise ValueError(f"method {method!r} requires a PQ head")
    s = scoring.subid_scores(params["sub_emb"].astype(jnp.float32),
                             phi.astype(jnp.float32))
    if method == "recjpq":
        return scoring.score_recjpq(params["codes"], s)
    if method == "pqtopk":
        return scoring.score_pqtopk(params["codes"], s)
    if method == "pqtopk_onehot":
        return scoring.score_pqtopk_onehot(params["codes"], s)
    if method == "pqtopk_kernel":
        from repro.kernels.pqtopk import ops as kernel_ops
        return kernel_ops.pq_scores(params["codes"], s)
    raise ValueError(f"unknown scoring method {method!r}")


def score_candidates(params: Params, phi: jax.Array, item_ids: jax.Array,
                     method: str = "pqtopk") -> jax.Array:
    """Scores for a candidate subset V (Algorithm 1's optional V)."""
    if method == "dense":
        w = embed(params, item_ids)
        return scoring.score_dense(w.astype(phi.dtype), phi)
    s = scoring.subid_scores(params["sub_emb"].astype(jnp.float32),
                             phi.astype(jnp.float32))
    if method in ("pqtopk_kernel", "pqtopk_fused"):
        # Fused-path subset scoring: gather V's codes, run the one-hot MXU
        # kernel over just those rows (no per-tile top-k — V is small).
        from repro.kernels.pqtopk import ops as kernel_ops
        return kernel_ops.pq_scores(params["codes"][item_ids], s)
    return scoring.score_pqtopk(params["codes"][item_ids], s)


def top_items(params: Params, phi: jax.Array, k: int,
              method: str = "pqtopk", tile: int = 8192,
              pq_cfg: Optional[PQConfig] = None,
              ladder=None, pin_rung: bool = False,
              return_rung: bool = False,
              ) -> Tuple[jax.Array, jax.Array]:
    """TopK(score, K) — returns (values (B,k), item ids (B,k)).

    ``method="pqtopk_fused"`` routes through the fused Pallas kernel: scores
    and per-tile winners stay in VMEM and only (B, n_tiles, k) candidates
    reach HBM — O(B*K*N/TN) output traffic instead of the O(B*N) score
    matrix that every score_all + tiled_topk route materialises.

    ``method="pqtopk_pruned"`` runs the single-dispatch in-graph cascade
    (bounds -> theta -> compaction -> compacted fused scoring, all in one
    traced computation; ``pq_cfg`` supplies the theta-seeding policy knobs,
    ``ladder`` the calibrated slot budgets, and ``return_rung=True`` makes
    the route additionally return the ladder rung taken — still one
    dispatch).
    """
    if params.get("live") is not None and method != "pqtopk_pruned":
        raise ValueError(
            f"params carry a tombstone mask ('live') but method {method!r} "
            f"would ignore it and could return delisted items; mutable "
            f"catalogues serve via 'pqtopk_pruned'")
    if pin_rung and method != "pqtopk_pruned":
        raise ValueError("pin_rung (the load-degraded cascade) is only "
                         "meaningful for method='pqtopk_pruned'")
    if method == "pqtopk_fused":
        if not is_pq(params):
            raise ValueError("method 'pqtopk_fused' requires a PQ head")
        with jax.named_scope("head.table"):
            s = scoring.subid_scores(params["sub_emb"].astype(jnp.float32),
                                     phi.astype(jnp.float32))
        from repro.kernels.pqtopk import ops as kernel_ops
        return kernel_ops.pq_topk(params["codes"], s, k)
    if method == "pqtopk_pruned":
        if not is_pq(params):
            raise ValueError("method 'pqtopk_pruned' requires a PQ head")
        return _top_items_pruned_ingraph(params, phi, k, pq_cfg=pq_cfg,
                                         ladder=ladder, pin_rung=pin_rung,
                                         return_rung=return_rung)
    if method == "pqtopk_approx":
        if not is_pq(params):
            raise ValueError("method 'pqtopk_approx' requires a PQ head")
        r = score_all(params, phi, "pqtopk")
        return topk_lib.approx_topk_maxblock(r, k)
    r = score_all(params, phi, method)
    return topk_lib.tiled_topk(r, k, tile)


# ---------------------------------------------------------------------------
# cascaded pruned retrieval (upper-bound tile skipping, docs/PRUNING.md)
# ---------------------------------------------------------------------------

DEFAULT_PRUNE_TILE = pruning.DEFAULT_PRUNE_TILE
DEFAULT_SEED_TILES = pruning.DEFAULT_SEED_TILES


_subid_scores_jit = jax.jit(
    lambda sub_emb, phi: scoring.subid_scores(sub_emb.astype(jnp.float32),
                                              phi.astype(jnp.float32)))


def _seed_kwargs(pq_cfg: Optional[PQConfig]) -> Dict[str, Any]:
    """theta-seeding knobs for the in-graph cascade, from PQConfig."""
    if pq_cfg is None:
        return {}
    return {"seed_policy": pq_cfg.seed_policy,
            "seed_tiles": pq_cfg.seed_tiles,
            "seed_max_tiles": pq_cfg.seed_max_tiles,
            "seed_stab_tol": pq_cfg.seed_stab_tol}


def _grouping_kwargs(pq_cfg: Optional[PQConfig]) -> Dict[str, Any]:
    """Per-query grouping knobs for the pruned cascade, from PQConfig."""
    if pq_cfg is None:
        return {}
    return {"query_grouping": pq_cfg.query_grouping,
            "n_groups": pq_cfg.n_groups}


def _pruned_state(params: Params) -> Optional[pruning.PrunedHeadState]:
    st = params.get("pruned")
    return st if isinstance(st, pruning.PrunedHeadState) else None


def _top_items_pruned_ingraph(params, phi, k, *,
                              pq_cfg: Optional[PQConfig] = None,
                              slot_budget: Optional[int] = None,
                              ladder=None, pin_rung: bool = False,
                              return_rung: bool = False):
    """The single-dispatch pruned route: one traced computation.

    Reads the :class:`pruning.PrunedHeadState` threaded through the param
    tree (bit-packed presence or code ranges, per its bound backend;
    rebuilding it in-graph only for legacy param dicts that predate the
    state) and runs ``pruning.cascade_topk_ingraph`` — bounds, theta
    seeding, cumsum-scatter compaction into ``-1``-padded slot buffers
    (one per ladder rung), and the compacted fused scoring, with no
    device->host sync.  Bit-identical to the exhaustive oracle; jit /
    decode-loop safe.  ``return_rung=True`` appends the ladder rung taken
    (i32) to the outputs — same single dispatch.

    A ``"live"`` entry in params (mutable catalogues, core/mutation.py)
    is the tombstone mask: threaded into the cascade as traced data, so
    churn never recompiles and dead items never reach the top-k.
    """
    codes, sub_emb = params["codes"], params["sub_emb"]
    live = params.get("live")
    s = scoring.subid_scores(sub_emb.astype(jnp.float32),
                             phi.astype(jnp.float32))
    state = _pruned_state(params)
    if state is not None and state.shards != 1:
        # A shard-aligned state (installed by ensure_sharded_pruned_state)
        # tiles the catalogue per shard; the flat route needs the shards=1
        # layout, so rebuild in-graph rather than misread the tiles.
        state = None
    if state is None:
        # Legacy param dicts / sharded-state fallback: rebuild in-graph,
        # honouring the config's bound backend and super-tile factor.
        state = pruning.build_pruned_state(
            codes, int(sub_emb.shape[1]), DEFAULT_PRUNE_TILE,
            backend=pq_cfg.bound_backend if pq_cfg is not None
            else "bitmask")
        if pq_cfg is not None and pq_cfg.super_factor > 1:
            state = pruning.with_super(state, pq_cfg.super_factor)
    out = pruning.cascade_topk_ingraph(codes, s, k, state,
                                       tile=DEFAULT_PRUNE_TILE,
                                       slot_budget=slot_budget,
                                       ladder=ladder, pin_rung=pin_rung,
                                       live=live,
                                       return_stats=return_rung,
                                       **_seed_kwargs(pq_cfg),
                                       **_grouping_kwargs(pq_cfg))
    if return_rung:
        vals, ids, stats = out
        return vals, ids, stats["rung_hit"]
    return out


def top_items_pruned(params: Params, phi: jax.Array, k: int, *,
                     tile: int = DEFAULT_PRUNE_TILE,
                     seed_tiles: int = DEFAULT_SEED_TILES,
                     use_kernel: Optional[bool] = None,
                     interpret: Optional[bool] = None,
                     return_stats: bool = False):
    """Two-pass cascaded retrieval, host mode (PR 2 reference path).

    Pass 1 (jitted): per-tile upper bounds from cached code-presence
    metadata, theta from a greedy exact pass over the ``seed_tiles`` most
    promising tiles, survival mask.  Host sync: compact surviving tile
    indices (power-of-two slot bucket, sentinel-padded).  Pass 2 (jitted
    per bucket size): fused scoring + top-k over surviving tiles only.

    The serving path no longer uses this — ``method="pqtopk_pruned"``
    through :func:`top_items` is the single-dispatch in-graph cascade.
    Kept as the host-orchestrated reference the in-graph route is
    parity-tested against (and for interactive use where a per-call
    device->host sync is acceptable).

    Exact: every skipped tile's bound is below theta, and at least k items
    score >= theta, so the top-k (values AND ids, ties included) matches
    the exhaustive oracle bit-for-bit.  With ``return_stats`` also returns
    {"n_tiles", "n_survived", "n_scored", "survival_fraction"}.
    """
    if not is_pq(params):
        raise ValueError("top_items_pruned requires a PQ head")
    s = _subid_scores_jit(params["sub_emb"], phi)
    return pruning.cascade_topk(params["codes"], s, k, tile=tile,
                                seed_tiles=seed_tiles, use_kernel=use_kernel,
                                interpret=interpret,
                                return_stats=return_stats)


def ensure_sharded_pruned_state(params: Params, mesh, axis: str = "model", *,
                                k_hint: int = 64,
                                tile: int = DEFAULT_PRUNE_TILE,
                                backend: Optional[str] = None,
                                super_factor: Optional[int] = None
                                ) -> Params:
    """Return ``params`` with a :class:`pruning.PrunedHeadState` whose tile
    layout is aligned to ``mesh``'s ``axis`` (tiles never straddle shard
    boundaries, so the metadata arrays split evenly over the mesh).

    A no-op when the threaded state is already compatible (same shard
    layout AND same bound backend); otherwise builds the shard-aligned
    state ONCE (engine/head build time) so the sharded serve path never
    rebuilds metadata per call.  ``k_hint`` is the largest k the route
    will serve — the tile must hold the per-shard oversampled top-(k +
    pad) winners.  ``backend=None`` preserves the threaded state's
    backend (default ``"bitmask"``); ``super_factor=None`` likewise
    preserves the threaded state's super-tile factor (the rebuilt sharded
    state regroups supers PER SHARD, so the hierarchical pass-0 and the
    shard-skip both stay shard-local).
    """
    if not is_pq(params):
        return params
    codes = params["codes"]
    n = codes.shape[0]
    n_shards = mesh.shape[axis]
    pad = (-n) % n_shards
    n_local = (n + pad) // n_shards
    k_local = min(k_hint + pad, n_local)
    st = _pruned_state(params)
    if backend is None:
        backend = st.backend if st is not None else "bitmask"
    if super_factor is None:
        super_factor = st.super_factor if st is not None else 0
    super_factor = 0 if super_factor <= 1 else int(super_factor)
    if (st is not None and st.shards == n_shards and st.tile >= k_local
            and st.backend == backend and st.super_factor == super_factor):
        return params
    b = params["sub_emb"].shape[1]
    need = _shard_tile(tile, k_local, n_local)
    new = pruning.build_pruned_state(codes, b, need, shards=n_shards,
                                     backend=backend)
    if super_factor:
        new = pruning.with_super(new, super_factor)
    return {**params, "pruned": new}


def _shard_tile(tile: int, k_local: int, n_local: int) -> int:
    """Pruning tile of the item-sharded route: ``tile``, grown to hold the
    per-shard top-``k_local`` when that is larger — and then to a multiple
    of 128, the row count the TPU kernel's codes block needs (k=2048 on 4
    shards asks for 2049) — and never past the shard's ``n_local`` rows."""
    if k_local > tile:
        tile = -(-k_local // 128) * 128
    return min(tile, n_local)


def _true_rows(params: Params, n_shards: int) -> int:
    """Catalogue size of an item head: the codes' rows, unless
    :func:`place_sharded` padded them to its shard-aligned state's
    ``shards * n_local`` rows, in which case the state holds the size."""
    rows = params["codes"].shape[0]
    st = _pruned_state(params)
    if (st is not None and st.shards == n_shards
            and rows == st.shards * st.n_local):
        return st.n_items
    return rows


def place_sharded(params: Params, mesh, axis: str = "model") -> Params:
    """Place an item head on ``mesh`` as the item-sharded cascade reads it:
    the codes padded to the shard-aligned state's ``shards * n_local``
    rows and split over ``axis`` (each device holds its own N/shards
    rows), the state's tile-major metadata split along its tile axis, and
    every other leaf replicated.  The padded rows are never served: the
    route takes the catalogue size from the state (:func:`_true_rows`).
    Call after :func:`ensure_sharded_pruned_state`."""
    st = _pruned_state(params)
    if st is None or st.shards != mesh.shape[axis]:
        raise ValueError("place_sharded needs the mesh-aligned pruned state "
                         "of ensure_sharded_pruned_state")
    codes = params["codes"]
    codes = jnp.pad(codes, ((0, st.shards * st.n_local - codes.shape[0]),
                            (0, 0)))
    split = NamedSharding(mesh, P(axis))
    out = jax.device_put({k: v for k, v in params.items()
                          if k not in ("codes", "pruned")},
                         NamedSharding(mesh, P()))
    out["codes"] = jax.device_put(codes, split)
    out["pruned"] = jax.device_put(st, split)
    return out


def top_items_pruned_sharded(params: Params, phi: jax.Array, k: int, mesh,
                             axis: str = "model", *,
                             tile: int = DEFAULT_PRUNE_TILE,
                             seed_tiles: Optional[int] = None,
                             pq_cfg: Optional[PQConfig] = None,
                             ladder=None,
                             super_ladder=None,
                             use_kernel: Optional[bool] = None,
                             interpret: Optional[bool] = None,
                             return_stats: bool = False):
    """Item-sharded cascade in ONE ``shard_map`` — single device dispatch.

    Each shard: bounds its local tiles from its slice of the bit-packed
    presence state, seeds a local theta from its own most promising tiles,
    shares ``theta = pmax(theta_local)`` (each local theta certifies >= k
    items somewhere, so the max is still certified and is the tightest such
    bound), compacts its local survivors with the in-graph cumsum scatter
    into a ``-1``-padded slot buffer (full per-shard length — SPMD uniform
    by construction, no cross-shard max needed), scores them through the
    compacted fused kernel, and contributes k candidates to the same
    O(k * shards) all-gather merge as every other sharded route.  The PR 2
    version needed two shard_maps with a host compaction between them;
    theta sharing and compaction now both live inside the single Manual
    region, so the route is jit- and decode-loop safe.

    Uses the shard-aligned state threaded through ``params`` when present
    (see :func:`ensure_sharded_pruned_state`); otherwise builds one
    in-graph — still a single dispatch, just with per-call rebuild cost.

    With ``pq_cfg.query_grouping`` the per-query route runs inside the
    same Manual region: each shard seeds per-query local thetas over its
    own tiles, the certified threshold is the per-query
    ``pmax(theta_local)`` over shards, and each shard then buckets queries
    by ITS local survivor sets, compacts a 2D (group, slot) table, scores
    it, and un-permutes its winners back to request order before the
    all-gather merge — shards may group differently (survivor overlap is
    a local property), which is safe because every cross-shard op runs in
    request order.

    With a hierarchical state (``with_super``; super-tiles grouped PER
    SHARD) each shard seeds theta from its SUPER-tile bounds, shares the
    ``pmax`` theta, and then runs the two-stage tail behind a shard-local
    ``lax.cond``: when NONE of the shard's super-tiles survive the shared
    theta, the shard skips the child-bound gather and the scoring kernel
    entirely and contributes ``-inf`` candidates pointing at the global
    sentinel id — super-tile bounds decide which shards a query batch
    touches at all.  Every collective (theta ``pmax``, the all-gather
    merge, the stats reductions) stays OUTSIDE the cond: the predicate is
    shard-divergent, and a collective inside a divergent branch would
    deadlock the mesh.
    """
    if not is_pq(params):
        raise ValueError("top_items_pruned_sharded requires a PQ head")
    from repro.kernels.pqtopk import ops as kernel_ops
    codes, sub_emb = params["codes"], params["sub_emb"]
    live = params.get("live")
    n_shards = mesh.shape[axis]
    n = _true_rows(params, n_shards)
    pad = (-n) % n_shards
    n_local = (n + pad) // n_shards
    # The local pass oversamples the top-(k + pad) so shard-padding rows
    # can be masked out afterwards; the tile must hold that many winners
    # (k <= tile is required everywhere, k + pad only here).
    k_local = min(k + pad, n_local)
    b = sub_emb.shape[1]
    state = _pruned_state(params)
    want_backend = (state.backend if state is not None else
                    (pq_cfg.bound_backend if pq_cfg is not None
                     else "bitmask"))
    want_super = (state.super_factor if state is not None else
                  (pq_cfg.super_factor if pq_cfg is not None else 0))
    if (state is None or state.shards != n_shards or state.tile < k_local
            or state.backend != want_backend):
        state = pruning.build_pruned_state(
            codes[:n], b, _shard_tile(tile, k_local, n_local),
            shards=n_shards,
            backend=want_backend)
        if want_super > 1:
            state = pruning.with_super(state, want_super)
    hier = state.has_super
    tile = state.tile
    t_local = state.tiles_per_shard
    codes_p = jnp.pad(codes, ((0, n + pad - codes.shape[0]), (0, 0)))
    if use_kernel is None:
        from repro import compat
        use_kernel = compat.on_tpu()
    if interpret is None:
        from repro import compat
        interpret = not compat.on_tpu()
    # Precedence: explicit seed_tiles argument > PQConfig knobs > defaults.
    seed_kw = _seed_kwargs(pq_cfg)
    if seed_tiles is not None:
        seed_kw["seed_tiles"] = seed_tiles
        seed_kw["seed_max_tiles"] = max(
            seed_tiles, seed_kw.get("seed_max_tiles",
                                    pruning.DEFAULT_SEED_MAX_TILES))
    # Per-shard ladder: budgets apply to the shard's local tile count.
    # Each shard escalates on its own survivor count (lax.cond branches
    # hold no collectives, so divergent rungs across shards are fine); the
    # final rung is always the full local buffer — exhaustive per shard.
    rungs = pruning.normalize_ladder(ladder, t_local, k_local, tile)
    # The backend's metadata arrays all carry the tile axis first, so one
    # P(axis, ...) spec per array shards them alongside the codes.  A
    # hierarchical state's super arrays ride the same axis (supers are
    # grouped per shard), appended after the child arrays.
    n_child_parts = len(state.meta_arrays())
    meta_parts = state.meta_arrays()
    if hier:
        factor = state.super_factor
        s_per_shard = state.supers_per_shard
        sup_rungs = pruning.normalize_ladder(
            pruning.default_super_ladder(s_per_shard)
            if super_ladder is None else super_ladder,
            s_per_shard, k_local, factor * tile)
        meta_parts = meta_parts + state.super_meta_arrays()
    meta_specs = tuple(P(axis, *([None] * (a.ndim - 1)))
                       for a in meta_parts)
    grp_kw = _grouping_kwargs(pq_cfg)
    grouped = grp_kw.get("query_grouping", False) and \
        grp_kw.get("n_groups", 1) > 1
    if hier and grouped:
        raise ValueError(
            "query_grouping and hierarchical super-tiles are mutually "
            "exclusive on the sharded route too; strip the super level "
            "or disable grouping")
    n_groups = grp_kw.get("n_groups", pruning.DEFAULT_N_GROUPS)
    bq = phi.shape[0]
    bt = (kernel_ops.group_batch_tile(bq, n_groups) if grouped
          else kernel_ops.effective_batch_tile(bq))
    b_pad = -(-bq // bt) * bt

    def shard_body(codes_local, meta_local, sub_emb_, phi_,
                   live_local=None):
        s = scoring.subid_scores(sub_emb_.astype(jnp.float32),
                                 phi_.astype(jnp.float32))
        child_local = meta_local[:n_child_parts]
        offset = jax.lax.axis_index(axis) * n_local
        if hier:
            sup_local = meta_local[n_child_parts:]
            sup_bounds = pruning.bounds_from_parts(state.backend,
                                                   sup_local, s)
            theta_local, n_seed_used, _sf = pruning.theta_seed_ingraph(
                codes_local, s, sup_bounds, k, tile=factor * tile,
                n_items=n, id_offset=offset,
                degenerate=pruning.degenerate_from_parts(
                    state.backend, sup_local, state.b),
                live=live_local, **seed_kw)
            theta = jax.lax.pmax(theta_local, axis)
            sup_mask = pruning.survival_mask(sup_bounds, theta)
            sup_slots, sup_count = pruning.compact_mask(sup_mask)

            def hier_tail(r_sup, i_sup):
                sup_ids = sup_slots[:r_sup]
                gid_t = (sup_ids[:, None] * factor
                         + jnp.arange(factor, dtype=jnp.int32)[None, :]
                         ).reshape(-1)
                valid = (gid_t >= 0) & (gid_t < t_local)
                safe = jnp.clip(gid_t, 0, t_local - 1)
                parts_sel = tuple(p[safe] for p in child_local)
                cb = pruning.bounds_from_parts(state.backend, parts_sel, s)
                cmask = pruning.survival_mask(cb, theta) & valid
                child_slots, child_count = pruning.compact_values(cmask,
                                                                  gid_t)
                crungs = pruning.normalize_ladder(ladder, r_sup * factor,
                                                  k_local, tile)
                slot_lists = tuple(child_slots[:r] for r in crungs)
                lv, li, crung = kernel_ops._pq_topk_tiles_ladder(
                    codes_local, s, k_local, slot_lists, child_count,
                    tile=tile, batch_tile=bt, live=live_local,
                    use_kernel=use_kernel, interpret=interpret)
                overflow = (child_count > crungs[-2] if len(crungs) > 1
                            else jnp.bool_(False))
                return (lv, li, child_count,
                        jnp.asarray(crungs, jnp.int32)[crung], crung,
                        jnp.int32(len(crungs)), jnp.asarray(overflow),
                        jnp.int32(s_per_shard + r_sup * factor),
                        jnp.int32(i_sup))

            def sup_rung_fn(i):
                def run():
                    return hier_tail(sup_rungs[i], i)
                if i == len(sup_rungs) - 1:
                    return run
                nxt = sup_rung_fn(i + 1)
                return lambda: jax.lax.cond(sup_count <= sup_rungs[i],
                                            run, nxt)

            def skip_tail():
                # Shard-skip: none of this shard's supers survive the
                # shared theta for ANY query — no child bound is gathered
                # and no kernel runs; the shard contributes -inf
                # candidates pointing at the global sentinel id n (the
                # gid map below adds offset back).
                lv = jnp.full((bq, k_local), -jnp.inf, jnp.float32)
                li = jnp.full((bq, k_local), n, jnp.int32) - offset
                return (lv, li, jnp.int32(0), jnp.int32(0), jnp.int32(0),
                        jnp.int32(1), jnp.bool_(False),
                        jnp.int32(s_per_shard), jnp.int32(0))

            # The skip cond's predicate is shard-local (divergent across
            # the mesh); every collective stays outside it.
            (lv, li, count, n_scored_loc, rung, n_rungs_loc, overflow_loc,
             bounds_loc, sup_rung) = jax.lax.cond(
                sup_count == jnp.int32(0), skip_tail, sup_rung_fn(0))
            max_group = count
            pairs = count * jnp.int32(b_pad)
        elif grouped:
            bounds = pruning.bounds_from_parts(state.backend, child_local,
                                               s)
            degenerate = pruning.degenerate_from_parts(
                state.backend, child_local, state.b)
            theta_local, n_seed_used, _sf = pruning.theta_seed_perquery(
                codes_local, s, bounds, k, tile=tile, n_items=n,
                id_offset=offset, degenerate=degenerate, live=live_local,
                **seed_kw)
            # Per-query certified threshold: each shard's theta_q
            # certifies >= k items somewhere score >= theta_q, so the
            # per-query max over shards is still certified — and the
            # tightest any shard proves.
            theta = jax.lax.pmax(theta_local, axis)
            pq_mask = pruning.survival_mask_perquery(bounds, theta)
            perm, inv_p, slots2d, counts = pruning.group_and_compact(
                pq_mask, n_groups=n_groups, batch_tile=bt)
            slot_lists = tuple(slots2d[:, :r] for r in rungs)
            lv, li, rung = kernel_ops._pq_topk_tiles_ladder(
                codes_local, jnp.take(s, perm, axis=0), k_local, slot_lists,
                counts, tile=tile, batch_tile=bt, live=live_local,
                use_kernel=use_kernel, interpret=interpret)
            # Back to request order before anything cross-shard.
            lv = jnp.take(lv, inv_p, axis=0)
            li = jnp.take(li, inv_p, axis=0)
            count = pq_mask.any(axis=0).sum(dtype=jnp.int32)
            max_group = counts.max()
            pairs = (counts * jnp.int32(bt)).sum()
            n_scored_loc = jnp.asarray(rungs, jnp.int32)[rung]
        else:
            bounds = pruning.bounds_from_parts(state.backend, child_local,
                                               s)
            degenerate = pruning.degenerate_from_parts(
                state.backend, child_local, state.b)
            theta_local, n_seed_used, _sf = pruning.theta_seed_ingraph(
                codes_local, s, bounds, k, tile=tile, n_items=n,
                id_offset=offset, degenerate=degenerate, live=live_local,
                **seed_kw)
            theta = jax.lax.pmax(theta_local, axis)
            mask = pruning.survival_mask(bounds, theta)
            # One compaction; rung buffers are prefixes of the full buffer.
            slots_full, count = pruning.compact_mask(mask)
            slot_lists = tuple(slots_full[:r] for r in rungs)
            lv, li, rung = kernel_ops._pq_topk_tiles_ladder(
                codes_local, s, k_local, slot_lists, count, tile=tile,
                batch_tile=bt, live=live_local, use_kernel=use_kernel,
                interpret=interpret)
            max_group = count
            pairs = count * jnp.int32(b_pad)
            n_scored_loc = jnp.asarray(rungs, jnp.int32)[rung]
        gid = li.astype(jnp.int32) + offset.astype(jnp.int32)
        lv = jnp.where(gid < n, lv, -jnp.inf)
        if live_local is not None:
            # Dead winners already carry the LOCAL sentinel id (n_local);
            # re-point every -inf candidate at the GLOBAL sentinel n so
            # the cross-shard merge sees one uniform "no item" id.
            gid = jnp.where(lv == -jnp.inf, jnp.int32(n), gid)
        if k_local > k:
            lv, sel = jax.lax.top_k(lv, k)
            gid = jnp.take_along_axis(gid, sel, axis=1)
        vals, ids = topk_lib.merge_local_topk(lv, gid, k, axis)
        base = (vals, ids, jax.lax.psum(count, axis),
                jax.lax.pmax(n_seed_used, axis),
                jax.lax.pmax(rung, axis),
                jax.lax.psum(n_scored_loc, axis),
                jax.lax.pmax(max_group, axis),
                jax.lax.psum(pairs, axis),
                jax.lax.psum(count * jnp.int32(b_pad), axis))
        if hier:
            return base + (jax.lax.psum(sup_count, axis),
                           jax.lax.pmax(sup_rung, axis),
                           jax.lax.psum(bounds_loc, axis),
                           jax.lax.pmax(n_rungs_loc, axis),
                           jax.lax.pmax(overflow_loc.astype(jnp.int32),
                                        axis))
        return base

    n_out = 14 if hier else 9
    if live is None:
        fn = manual_axis_map(
            shard_body, mesh,
            in_specs=(P(axis, None), meta_specs, P(), P()),
            out_specs=(P(),) * n_out)
        outs = fn(codes_p, meta_parts, sub_emb, phi)
    else:
        # Tombstone mask rides the mesh axis alongside the codes (shard
        # padding rows are dead); everything else is the same ONE
        # shard_map — churn is pure data, so zero recompiles per swap.
        live_p = jnp.pad(live, (0, n + pad - live.shape[0]))

        def body_live(codes_local, meta_local, live_local, sub_emb_, phi_):
            return shard_body(codes_local, meta_local, sub_emb_, phi_,
                              live_local=live_local)

        fn = manual_axis_map(
            body_live, mesh,
            in_specs=(P(axis, None), meta_specs, P(axis), P(), P()),
            out_specs=(P(),) * n_out)
        outs = fn(codes_p, meta_parts, live_p, sub_emb, phi)
    (vals, ids, survived, n_seed_used, rung, n_scored, max_group,
     pairs_scored, pairs_union) = outs[:9]
    if not return_stats:
        return vals, ids
    total = n_shards * t_local
    if hier:
        sup_survived, sup_rung, bounds_comp, n_rungs_t, overflow_t = outs[9:]
        n_rungs_stat = n_rungs_t
        overflow_stat = overflow_t != 0
        sup_stats = {"n_super": state.n_super,
                     "n_super_survived": sup_survived,
                     "super_rung_hit": sup_rung,
                     "bounds_computed": bounds_comp}
    else:
        n_rungs_stat = len(rungs)
        # Overflow is per-shard (survivor skew can force one shard to
        # its exhaustive rung while the global total still fits), so
        # derive it from the pmax'd rung, not the psum'd count.
        overflow_stat = (rung == len(rungs) - 1
                         if len(rungs) > 1 else jnp.bool_(False))
        sup_stats = {"n_super": 0, "n_super_survived": 0,
                     "super_rung_hit": 0, "bounds_computed": total}
    stats = {"n_tiles": total, "n_survived": survived,
             "n_scored": n_scored,
             "survival_fraction": survived / jnp.float32(max(total, 1)),
             "n_seed_used": n_seed_used,
             "seed_survival_est": survived / jnp.float32(max(total, 1)),
             "rung_hit": rung, "n_rungs": n_rungs_stat,
             "slot_overflow": overflow_stat,
             "bound_backend": state.backend,
             # Kernel group rows actually built (the 8-row sublane floor
             # can collapse small batches below the requested n_groups).
             "n_groups": b_pad // bt if grouped else 1,
             "max_group_survived": max_group,
             "pairs_scored": pairs_scored, "pairs_union": pairs_union,
             **sup_stats}
    return vals, ids, stats


# ---------------------------------------------------------------------------
# distributed: items sharded over a mesh axis, O(k * shards) merge
# ---------------------------------------------------------------------------

def top_items_sharded(params: Params, phi: jax.Array, k: int, mesh,
                      axis: str = "model", method: str = "pqtopk",
                      pq_cfg: Optional[PQConfig] = None,
                      ladder=None,
                      ) -> Tuple[jax.Array, jax.Array]:
    """Item-sharded retrieval: codes sharded over ``axis``; each shard runs
    PQTopK locally and contributes k candidates to an all-gather merge.

    Per-shard collective volume: k * (4 + 4) bytes * n_shards — independent
    of N (DESIGN.md §5).
    """
    if not is_pq(params):
        return _dense_top_items_sharded(params, phi, k, mesh, axis)
    if method == "pqtopk_pruned":
        return top_items_pruned_sharded(params, phi, k, mesh, axis,
                                        pq_cfg=pq_cfg, ladder=ladder)
    if params.get("live") is not None:
        raise ValueError(
            f"params carry a tombstone mask ('live') but method {method!r} "
            "would ignore it and could return delisted items; mutable "
            "catalogues serve via 'pqtopk_pruned'")
    n = params["codes"].shape[0]
    n_shards = mesh.shape[axis]
    pad = (-n) % n_shards
    codes = params["codes"]
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    n_local = (n + pad) // n_shards

    if method == "pqtopk_fused":
        shard_fn = _fused_shard_fn(k, n, n_local, pad, axis)
    else:
        scorer = {"pqtopk": scoring.score_pqtopk,
                  "pqtopk_onehot": scoring.score_pqtopk_onehot,
                  "pqtopk_kernel": scoring.score_pqtopk,
                  "recjpq": scoring.score_recjpq}[method]

        def shard_fn(codes_local, sub_emb, phi_):
            s = scoring.subid_scores(sub_emb.astype(jnp.float32),
                                     phi_.astype(jnp.float32))
            r_local = scorer(codes_local, s)
            offset = jax.lax.axis_index(axis) * n_local
            # Mask padding rows (global id >= n) out of the top-k.
            gid = offset + jnp.arange(n_local)
            r_local = jnp.where(gid[None, :] < n, r_local, -jnp.inf)
            return topk_lib.local_then_merge_topk(r_local, k, axis, offset)

    fn = manual_axis_map(
        shard_fn, mesh,
        in_specs=(P(axis, None), P(), P()),
        out_specs=(P(), P()),   # outputs are replicated post-all_gather
    )
    return fn(codes, params["sub_emb"], phi)


def _fused_shard_fn(k: int, n: int, n_local: int, pad: int, axis: str):
    """Shard body for the fused route: the Pallas kernel produces this
    shard's top-k directly (per-tile winners merged in the wrapper — the
    (B, N_local) score matrix never exists), then the cross-shard merge is
    the same O(k * shards) all-gather as every other method.

    Shard-level padding rows (zero codes, only on the last shard) are real
    rows to the kernel, so we oversample the local top-(k + pad): at most
    ``pad`` winners can be padding, which we mask to -inf after mapping to
    global ids — the surviving candidates still contain the true local
    top-k, keeping the route exact.
    """
    from repro.kernels.pqtopk import ops as kernel_ops
    k_local = min(k + pad, n_local)

    def shard_fn(codes_local, sub_emb, phi_):
        s = scoring.subid_scores(sub_emb.astype(jnp.float32),
                                 phi_.astype(jnp.float32))
        lv, li = kernel_ops.pq_topk(codes_local, s, k_local)
        offset = jax.lax.axis_index(axis) * n_local
        gid = li.astype(jnp.int32) + offset.astype(jnp.int32)
        lv = jnp.where(gid < n, lv, -jnp.inf)
        if pad:
            # Re-rank after masking so each shard contributes its best k.
            lv, sel = jax.lax.top_k(lv, min(k, k_local))
            gid = jnp.take_along_axis(gid, sel, axis=1)
        return topk_lib.merge_local_topk(lv, gid, k, axis)

    return shard_fn


def _dense_top_items_sharded(params: Params, phi: jax.Array, k: int, mesh,
                             axis: str) -> Tuple[jax.Array, jax.Array]:
    n = params["table"].shape[0]
    n_local = n // mesh.shape[axis]

    def shard_fn(table_local, phi_):
        r_local = scoring.score_dense(table_local.astype(phi_.dtype), phi_)
        offset = jax.lax.axis_index(axis) * n_local
        return topk_lib.local_then_merge_topk(
            r_local.astype(jnp.float32), k, axis, offset)

    fn = manual_axis_map(
        shard_fn, mesh,
        in_specs=(P(axis, None), P()),
        out_specs=(P(), P()),
    )
    return fn(params["table"], phi)
