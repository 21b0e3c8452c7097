"""Entrypoint registry for the serve-path static analysis.

An :class:`Entrypoint` names one serving computation worth guarding and
knows how to build it into a :class:`BuiltEntry`: a callable plus abstract
(or concrete) arguments that :func:`jax.make_jaxpr` can trace, together
with the pass-facing contracts — trace-static argument specs for the
recompile-hazard pass, the VMEM budget and expected kernel count for the
Pallas contract pass, and (for engine entries) a runtime dispatch counter.

The registry covers every serving route the repo ships (ISSUE 6 / the
check_single_dispatch lineage):

* ``flat_fused``          — serve_topk via the fused Pallas score+top-k
                            kernel (``pqtopk_fused``)
* ``flat_pruned``         — the single-dispatch in-graph pruned cascade
                            with a slot-budget ladder (nested ``lax.cond``)
* ``grouped_perquery``    — the per-query grouped cascade (bucketing scan,
                            argsort permutation, 2D compaction)
* ``sharded_pruned``      — the item-sharded cascade under ``shard_map``
* ``lm_decode_step``      — the PQ-head pruned cascade inside one LM
                            decode step (stacked-cache scan backbone)
* ``pruned_tiles_kernel`` — the scalar-prefetch Pallas kernel on a 1D
                            ``-1``-padded compacted tile list (interpret
                            mode, so the contract pass sees the real
                            ``pallas_call`` params on CPU CI)
* ``grouped_tiles_kernel``— same kernel with the grouped 2D (batch-tile,
                            slot) table
* ``engine_aot``          — a calibrated RetrievalEngine on the pruned
                            route (AOT-compiled variants, runtime dispatch
                            counting)
* ``engine_aot_grouped``  — the engine on the grouped route
* ``flat_tombstone``      — the pruned cascade over a mutated catalogue:
                            tombstone mask + stale-but-dominating bounds
                            threaded as data through ONE dispatch
* ``tombstone_tiles_kernel`` — the compacted-tile kernel with the live
                            block riding the same clamped sentinel index
                            map as the codes
* ``engine_mutable``      — the hot-swap engine: mutate + swap_head_state
                            between batches, then prove the served batch
                            is still ONE dispatch with ZERO new compiles
* ``router_replicated``   — the replicated fabric: K health-checked
                            replicas behind one submit/drain — a
                            healthy-path batch is ONE compiled dispatch
                            on exactly one replica, and replica id never
                            keys a compile

Builds are cached (`build()`), and the heavyweight shared fixtures
(catalogue params) are built once and reused across entries.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

# NOTE: jax and the repro model stack are imported lazily inside builders
# so `import repro.analysis` stays cheap (and so the AST lint below can
# hold this module to its own no-module-level-jnp-constant rule).

DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024   # bytes; ~half a TPU core's VMEM,
                                        # leaving headroom for scratch and
                                        # the compiler's own buffers


@dataclass(frozen=True)
class StaticArgSpec:
    """One trace-static argument feeding a jit/AOT boundary.

    ``sample`` is a representative set of raw client-side values;
    ``mapper`` is the *real* production mapping from client value to the
    trace-static key (e.g. ``RetrievalEngine.batch_k``).  The recompile
    pass asserts ``{mapper(v) for v in sample}`` stays within ``allowed``
    (when given) and under ``max_variants`` — so unbounded client values
    can never key unbounded compiles.
    """

    name: str
    sample: Tuple[Any, ...]
    mapper: Callable[[Any], Any]
    max_variants: int
    allowed: Optional[frozenset] = None
    note: str = ""


@dataclass
class BuiltEntry:
    """A materialised entrypoint, ready for the passes."""

    fn: Callable                      # traced by jax.make_jaxpr(fn)(*args)
    args: Tuple[Any, ...]             # ShapeDtypeStructs or arrays
    static_specs: Tuple[StaticArgSpec, ...] = ()
    vmem_budget: int = DEFAULT_VMEM_BUDGET
    expect_pallas: int = 0            # min pallas_call count in the trace
    dispatch_counter: Optional[Callable[[], int]] = None
    notes: str = ""


@dataclass(frozen=True)
class Entrypoint:
    name: str
    description: str
    build: Callable[[], BuiltEntry]
    tags: Tuple[str, ...] = ()


REGISTRY: Dict[str, Entrypoint] = {}


def register(name: str, description: str, tags: Tuple[str, ...] = ()):
    def deco(fn):
        REGISTRY[name] = Entrypoint(name, description, fn, tags)
        return fn
    return deco


@functools.lru_cache(maxsize=None)
def build(name: str) -> BuiltEntry:
    return REGISTRY[name].build()


# ---------------------------------------------------------------------------
# shared fixtures
# ---------------------------------------------------------------------------

SEQREC_N_ITEMS = 16384      # several pruning tiles at DEFAULT_PRUNE_TILE
STATIC_LADDER = (2, 4)      # multi-rung (normalised ladder appends the
                            # exhaustive rung) without calibration cost


@functools.lru_cache(maxsize=None)
def _seqrec_setup():
    """Reduced sasrec-recjpq scaled to a multi-tile catalogue with
    position-clustered codes — the same fixture the dispatch guard script
    has always used: clustering gives tiles genuinely distinct bounds, so
    pruning (and ladder calibration, for the engine entries) is real."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dataclasses import replace
    from repro.configs import get_reduced
    from repro.models import seqrec as seqrec_lib

    cfg = replace(get_reduced("sasrec-recjpq").model, n_items=SEQREC_N_ITEMS)
    rng0 = np.random.default_rng(7)
    centers = (np.arange(cfg.n_items + 1) / (cfg.n_items + 1)
               * cfg.pq.b).astype(np.int64)
    codes = jnp.asarray(
        (centers[:, None] + rng0.integers(-1, 2, (cfg.n_items + 1,
                                                  cfg.pq.m))) % cfg.pq.b,
        jnp.int32)
    params = seqrec_lib.init_seqrec(jax.random.PRNGKey(0), cfg, codes=codes)
    return params, cfg


def _seq_sds(cfg, batch: int = 4):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((batch, cfg.max_seq_len), jnp.int32)


def _serve_entry(method: str, *, grouped: bool = False, mesh=None,
                 ladder=None, return_rung: bool = False,
                 expect_pallas: int = 0, k: int = 5) -> BuiltEntry:
    from dataclasses import replace
    from repro.models import seqrec as seqrec_lib

    params, cfg = _seqrec_setup()
    if grouped:
        cfg = replace(cfg, pq=replace(cfg.pq, query_grouping=True,
                                      n_groups=4))

    def fn(seqs):
        return seqrec_lib.serve_topk(params, seqs, cfg, k=k, method=method,
                                     sharded_mesh=mesh, ladder=ladder,
                                     return_rung=return_rung)

    return BuiltEntry(fn, (_seq_sds(cfg),), expect_pallas=expect_pallas,
                      notes=f"serve_topk method={method!r} "
                            f"n_items={cfg.n_items} grouped={grouped} "
                            f"sharded={mesh is not None}")


# ---------------------------------------------------------------------------
# serve_topk routes
# ---------------------------------------------------------------------------

@register("flat_fused",
          "serve_topk through the fused Pallas score+top-k kernel "
          "(method='pqtopk_fused') — backbone, subid scores and the "
          "batch-tiled kernel grid in one trace",
          tags=("serve", "kernel"))
def _build_flat_fused() -> BuiltEntry:
    return _serve_entry("pqtopk_fused", expect_pallas=1)


@register("flat_pruned",
          "the single-dispatch in-graph pruned cascade with a multi-rung "
          "slot-budget ladder (nested lax.cond chain) and rung telemetry",
          tags=("serve", "pruned"))
def _build_flat_pruned() -> BuiltEntry:
    return _serve_entry("pqtopk_pruned", ladder=STATIC_LADDER,
                        return_rung=True)


@register("grouped_perquery",
          "the per-query grouped cascade: theta per query, overlap-"
          "bucketing scan, stable-argsort permutation and the 2D "
          "(group, slot) compaction, all in one trace",
          tags=("serve", "pruned", "grouped"))
def _build_grouped_perquery() -> BuiltEntry:
    return _serve_entry("pqtopk_pruned", grouped=True, ladder=STATIC_LADDER,
                        return_rung=True)


@register("sharded_pruned",
          "the item-sharded pruned cascade under shard_map (shard-local "
          "cascade + O(k x shards) merge)",
          tags=("serve", "pruned", "sharded"))
def _build_sharded_pruned() -> BuiltEntry:
    from repro.core import retrieval_head
    from repro.launch.mesh import make_mesh

    params, cfg = _seqrec_setup()
    mesh = make_mesh((1,), ("model",))
    params = {**params, "item_emb":
              retrieval_head.ensure_sharded_pruned_state(
                  params["item_emb"], mesh, k_hint=5)}
    from repro.models import seqrec as seqrec_lib

    def fn(seqs):
        return seqrec_lib.serve_topk(params, seqs, cfg, k=5,
                                     method="pqtopk_pruned",
                                     sharded_mesh=mesh)

    return BuiltEntry(fn, (_seq_sds(cfg),),
                      notes="sharded serve_topk, 1-device 'model' mesh")


@register("flat_hier",
          "the hierarchical two-stage cascade: super-tile pass-0 pruning "
          "+ theta seeded from super bounds + two-stage compaction, still "
          "ONE dispatch (nested super-rung/child-rung lax.cond chains)",
          tags=("serve", "pruned", "hier"))
def _build_flat_hier() -> BuiltEntry:
    from repro.core import pruning
    from repro.models import seqrec as seqrec_lib

    params, cfg = _seqrec_setup()
    head = dict(params["item_emb"])
    head["pruned"] = pruning.with_super(head["pruned"], 4)
    params = {**params, "item_emb": head}

    def fn(seqs):
        return seqrec_lib.serve_topk(params, seqs, cfg, k=5,
                                     method="pqtopk_pruned",
                                     ladder=STATIC_LADDER)

    return BuiltEntry(fn, (_seq_sds(cfg),),
                      notes=f"hierarchical serve_topk, super_factor=4, "
                            f"n_super={head['pruned'].n_super}")


@register("sharded_hier",
          "the item-sharded hierarchical cascade: per-shard super-tile "
          "pass-0 behind the shard-local skip cond (collectives outside), "
          "ONE shard_map",
          tags=("serve", "pruned", "sharded", "hier"))
def _build_sharded_hier() -> BuiltEntry:
    from repro.core import retrieval_head
    from repro.launch.mesh import make_mesh
    from repro.models import seqrec as seqrec_lib

    params, cfg = _seqrec_setup()
    mesh = make_mesh((1,), ("model",))
    params = {**params, "item_emb":
              retrieval_head.ensure_sharded_pruned_state(
                  params["item_emb"], mesh, k_hint=5, super_factor=4)}

    def fn(seqs):
        return seqrec_lib.serve_topk(params, seqs, cfg, k=5,
                                     method="pqtopk_pruned",
                                     sharded_mesh=mesh)

    return BuiltEntry(fn, (_seq_sds(cfg),),
                      notes="sharded hierarchical serve_topk, "
                            "super_factor=4, 1-device 'model' mesh")


@register("lm_decode_step",
          "one LM decode step (stacked-cache layer scan) with the pruned "
          "PQ vocabulary head — the cascade inside the decode loop",
          tags=("decode", "pruned"))
def _build_lm_decode() -> BuiltEntry:
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_reduced
    from repro.models import transformer as T

    cfg = get_reduced("qwen2.5-14b").model
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    caches = T.init_caches(cfg, 2, 16, abstract=True)
    tok = jax.ShapeDtypeStruct((2,), jnp.int32)

    def fn(token, c):
        return T.lm_decode_step(params, token, jnp.int32(0), c, cfg, k=8,
                                head_method="pqtopk_pruned")

    return BuiltEntry(fn, (tok, caches),
                      notes=f"qwen2.5-14b reduced, vocab={cfg.vocab}, "
                            f"head_method='pqtopk_pruned'")


# ---------------------------------------------------------------------------
# direct Pallas kernel routes (interpret mode: the kernel grid, block
# specs and scalar-prefetch index maps are in the trace on CPU CI too)
# ---------------------------------------------------------------------------

def _kernel_fixture(n: int = 1024, m: int = 8, b: int = 16, bq: int = 16):
    import jax
    import jax.numpy as jnp
    codes = jax.ShapeDtypeStruct((n, m), jnp.int8)
    s = jax.ShapeDtypeStruct((bq, m, b), jnp.float32)
    return codes, s


@register("pruned_tiles_kernel",
          "pq_topk_tiles forced onto the scalar-prefetch Pallas kernel "
          "(interpret mode) with a 1D -1-padded compacted tile list — "
          "the sentinel index-map clamp contract surface",
          tags=("kernel",))
def _build_pruned_tiles_kernel() -> BuiltEntry:
    import jax.numpy as jnp
    from repro.kernels.pqtopk import ops

    codes, s = _kernel_fixture()
    tile_idx = jnp.asarray([0, -1], jnp.int32)   # one live slot + sentinel

    def fn(c, sc):
        return ops.pq_topk_tiles(c, sc, 8, tile_idx, tile=512,
                                 use_kernel=True, interpret=True)

    return BuiltEntry(fn, (codes, s), expect_pallas=1,
                      notes="1D compacted slots, int8 codes, tile=512")


@register("grouped_tiles_kernel",
          "the grouped kernel grid: 2D (batch-tile, slot) table, each "
          "kernel batch tile scoring its own -1-padded slot row",
          tags=("kernel", "grouped"))
def _build_grouped_tiles_kernel() -> BuiltEntry:
    import jax.numpy as jnp
    from repro.kernels.pqtopk import ops

    codes, s = _kernel_fixture()
    tile_idx = jnp.asarray([[0, 1], [1, -1]], jnp.int32)

    def fn(c, sc):
        return ops.pq_topk_tiles(c, sc, 8, tile_idx, tile=512,
                                 batch_tile=8, use_kernel=True,
                                 interpret=True)

    return BuiltEntry(fn, (codes, s), expect_pallas=1,
                      notes="2D grouped slots, batch_tile=8")


# ---------------------------------------------------------------------------
# engine AOT variants (runtime dispatch counting + recompile-key specs)
# ---------------------------------------------------------------------------

def _pow2_buckets(limit: int) -> frozenset:
    out, b = set(), 1
    while b < limit:
        out.add(b)
        b *= 2
    out.add(limit)
    return frozenset(out)


def _count_engine_dispatches(eng, cfg, k: int, base_id: int) -> int:
    """Warm the engine's compile cache, then wrap every memoised compiled
    variant in a counter and serve one guarded batch: the number of
    entries that fire is the per-batch dispatch count.  Runs under
    ``jax.transfer_guard("disallow")`` (additionally catches implicit D2H
    syncs on accelerator backends; on CPU D2H is zero-copy and unguarded,
    so the trace check is the load-bearing one there)."""
    import jax
    import numpy as np
    from repro.serving.engine import Request

    rng = np.random.default_rng(base_id)
    for i in range(4):
        eng.submit(Request(base_id + i,
                           rng.integers(1, cfg.n_items + 1, 8), k=k))
    eng.drain()                                   # warm outside the guard
    calls = []
    for key, f in list(eng._compiled.items()):
        eng._compiled[key] = (
            lambda seqs, _f=f, _key=key: (calls.append(_key), _f(seqs))[1])
    for i in range(4):
        eng.submit(Request(base_id + 10 + i,
                           rng.integers(1, cfg.n_items + 1, 8), k=k))
    with jax.transfer_guard("disallow"):
        results = eng.run_once()
    assert len(results) == 4, f"served {len(results)}/4"
    return len(calls)


def _engine_entry(*, grouped: bool, base_id: int) -> BuiltEntry:
    from dataclasses import replace
    from repro.serving.engine import MicroBatcher, RetrievalEngine

    params, cfg = _seqrec_setup()
    if grouped:
        cfg = replace(cfg, pq=replace(cfg.pq, query_grouping=True,
                                      n_groups=4))
    k, max_batch = 5, 8
    eng = RetrievalEngine.for_seqrec(params, cfg, k=k, max_batch=max_batch,
                                     method="pqtopk_pruned")
    assert eng._jit_serve, "pruned route must be a jitted serve fn"
    # The calibrated ladder must be active: the single-dispatch guarantee
    # has to hold WITH the nested lax.cond rung chain in the trace.
    assert eng.ladder is not None and len(eng.ladder) >= 2, (
        f"expected a calibrated multi-rung ladder, got {eng.ladder!r}")

    import jax
    import jax.numpy as jnp
    sds = jax.ShapeDtypeStruct((4, cfg.max_seq_len), jnp.int32)

    # Every trace-static value that keys a compiled variant, probed
    # through the REAL production mappings (engine.batch_k / bucket):
    specs = (
        StaticArgSpec(
            "batch_bucket",
            sample=tuple(range(1, max_batch + 1)),
            mapper=lambda n, _mb=max_batch: MicroBatcher.bucket(n, _mb),
            allowed=_pow2_buckets(max_batch),
            max_variants=max_batch.bit_length() + 1,
            note="pow2 padding buckets for the request batch size"),
        StaticArgSpec(
            "k_bucket",
            sample=tuple(range(1, 64)) + (200, 1000, 10 ** 9),
            mapper=lambda kv, _e=eng: _e.batch_k([kv]),
            allowed=_pow2_buckets(eng.max_k),
            max_variants=eng.max_k.bit_length() + 1,
            note="client k clamped into [1, max_k] then pow2-bucketed"),
        StaticArgSpec(
            "ladder_rung",
            sample=tuple(eng.ladder),
            mapper=lambda r: r,
            allowed=frozenset(eng.ladder),
            max_variants=4,
            note="calibrated slot budgets baked into ONE serve fn (rungs "
                 "are cond branches, never separate compiles)"),
    )
    if grouped:
        specs += (StaticArgSpec(
            "n_groups", sample=(cfg.pq.n_groups,), mapper=lambda g: g,
            allowed=frozenset({cfg.pq.n_groups}), max_variants=1,
            note="config-static group count"),)

    return BuiltEntry(
        fn=lambda seqs: eng._serve_fn(seqs, k, eng._data),
        args=(sds,),
        static_specs=specs,
        dispatch_counter=lambda: _count_engine_dispatches(eng, cfg, k,
                                                          base_id),
        notes=f"RetrievalEngine.for_seqrec pqtopk_pruned, calibrated "
              f"ladder={eng.ladder}, grouped={grouped}")


@register("engine_aot",
          "a calibrated RetrievalEngine on the pruned route: AOT variant "
          "keys, client-k bucketing, runtime single-dispatch counting",
          tags=("serve", "engine", "pruned"))
def _build_engine_aot() -> BuiltEntry:
    return _engine_entry(grouped=False, base_id=0)


@register("engine_aot_grouped",
          "the engine on the grouped per-query route: same AOT/bucketing "
          "contracts with the grouped cascade in the trace",
          tags=("serve", "engine", "pruned", "grouped"))
def _build_engine_aot_grouped() -> BuiltEntry:
    return _engine_entry(grouped=True, base_id=100)


# ---------------------------------------------------------------------------
# mutable-catalogue routes (ISSUE 7: tombstones, hot swap)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _mutable_setup():
    """A MutableHeadState over the shared catalogue with a few dozen
    deletions applied — stale (loosened) bounds plus a real tombstone
    mask, i.e. the exact serve-path shape streaming mutation produces."""
    import numpy as np
    from repro.core.mutation import MutableHeadState

    params, cfg = _seqrec_setup()
    mstate = MutableHeadState.build(params["item_emb"]["codes"], cfg.pq.b)
    rng = np.random.default_rng(11)
    for iid in rng.choice(np.arange(1, cfg.n_items + 1), 64, replace=False):
        mstate.delete(int(iid))
    return params, cfg, mstate


@register("flat_tombstone",
          "serve_topk on a mutated catalogue: capacity-padded codes, "
          "stale-but-dominating bounds and the tombstone mask all enter "
          "as DATA — the whole degraded cascade must still be one trace",
          tags=("serve", "pruned", "mutable"))
def _build_flat_tombstone() -> BuiltEntry:
    from repro.models import seqrec as seqrec_lib

    params, cfg, mstate = _mutable_setup()
    p = {**params, "item_emb": {**params["item_emb"],
                                **mstate.head_arrays()}}

    def fn(seqs):
        return seqrec_lib.serve_topk(p, seqs, cfg, k=5,
                                     method="pqtopk_pruned",
                                     ladder=STATIC_LADDER,
                                     return_rung=True)

    return BuiltEntry(fn, (_seq_sds(cfg),),
                      notes=f"mutable head, capacity={mstate.cap}, "
                            f"n_live={mstate.n_live}, ladder rungs in "
                            "trace, tombstones as data")


@register("tombstone_tiles_kernel",
          "the compacted-tile kernel with a live (tombstone) block: the "
          "(1, tile) int8 mask rides the same clamped sentinel index map "
          "as the codes blocks — the mutable kernel contract surface",
          tags=("kernel", "mutable"))
def _build_tombstone_tiles_kernel() -> BuiltEntry:
    import jax
    import jax.numpy as jnp
    from repro.kernels.pqtopk import ops

    codes, s = _kernel_fixture()
    tile_idx = jnp.asarray([0, -1], jnp.int32)   # one live slot + sentinel
    live = jax.ShapeDtypeStruct((codes.shape[0],), jnp.bool_)

    def fn(c, sc, lv):
        return ops.pq_topk_tiles(c, sc, 8, tile_idx, tile=512, live=lv,
                                 use_kernel=True, interpret=True)

    return BuiltEntry(fn, (codes, s, live), expect_pallas=1,
                      notes="1D compacted slots + tombstone mask block, "
                            "tile=512")


@register("engine_mutable",
          "the hot-swap engine: serve, mutate the catalogue, "
          "swap_head_state, serve again — the swapped batch must be ONE "
          "dispatch through the SAME compiled variants (zero recompiles)",
          tags=("serve", "engine", "pruned", "mutable"))
def _build_engine_mutable() -> BuiltEntry:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serving.engine import (MicroBatcher, Request,
                                      RetrievalEngine)

    params, cfg, mstate = _mutable_setup()
    k, max_batch = 5, 8
    eng = RetrievalEngine.for_seqrec_mutable(params, cfg, mstate, k=k,
                                             max_batch=max_batch)
    assert eng._swappable, "mutable engine must be swappable"

    sds = jax.ShapeDtypeStruct((4, cfg.max_seq_len), jnp.int32)

    def count() -> int:
        rng = np.random.default_rng(200)
        for i in range(4):
            eng.submit(Request(200 + i,
                               rng.integers(1, cfg.n_items + 1, 8), k=k))
        eng.drain()                               # warm outside the guard
        n_variants = len(eng._compiled)
        # Mutate the catalogue and hot-swap it in — the whole point is
        # that the swapped batch below reuses the SAME compiled variant.
        for iid in rng.choice(np.arange(1, cfg.n_items + 1), 16,
                              replace=False):
            if bool(np.asarray(mstate.live)[int(iid)]):
                mstate.delete(int(iid))
        eng.swap_head_state(mstate)
        calls = []
        for key, f in list(eng._compiled.items()):
            eng._compiled[key] = (
                lambda seqs, _f=f, _key=key:
                (calls.append(_key), _f(seqs))[1])
        for i in range(4):
            eng.submit(Request(210 + i,
                               rng.integers(1, cfg.n_items + 1, 8), k=k))
        with jax.transfer_guard("disallow"):
            results = eng.run_once()
        assert len(results) == 4, f"served {len(results)}/4"
        assert len(eng._compiled) == n_variants, (
            f"hot swap minted {len(eng._compiled) - n_variants} new "
            "compiled variant(s)")
        return len(calls)

    specs = (
        StaticArgSpec(
            "batch_bucket",
            sample=tuple(range(1, max_batch + 1)),
            mapper=lambda n, _mb=max_batch: MicroBatcher.bucket(n, _mb),
            allowed=_pow2_buckets(max_batch),
            max_variants=max_batch.bit_length() + 1,
            note="pow2 padding buckets for the request batch size"),
        StaticArgSpec(
            "k_bucket",
            sample=tuple(range(1, 64)) + (200, 1000, 10 ** 9),
            mapper=lambda kv, _e=eng: _e.batch_k([kv]),
            allowed=_pow2_buckets(eng.max_k),
            max_variants=eng.max_k.bit_length() + 1,
            note="client k clamped into [1, max_k] then pow2-bucketed"),
        StaticArgSpec(
            "head_swap",
            sample=(0, 1, 2),
            mapper=lambda _swap: "head-as-data",
            allowed=frozenset({"head-as-data"}),
            max_variants=1,
            note="catalogue mutations are pure data: every swap maps to "
                 "the one compiled head structure"),
    )

    return BuiltEntry(
        fn=lambda seqs: eng._serve_fn(seqs, k, eng._data),
        args=(sds,),
        static_specs=specs,
        dispatch_counter=count,
        notes=f"for_seqrec_mutable, capacity={mstate.cap}, "
              f"ladder={eng.ladder}, swap-then-serve under "
              "transfer_guard")


# ---------------------------------------------------------------------------
# replicated fabric (ISSUE 8: router)
# ---------------------------------------------------------------------------

@register("router_replicated",
          "the replicated serving fabric: health-checked replicas behind "
          "one submit/drain — a healthy-path batch is ONE compiled "
          "dispatch on exactly one replica (no fan-out, no duplicated "
          "work) and replica id never keys a compile",
          tags=("serve", "engine", "pruned", "router"))
def _build_router_replicated() -> BuiltEntry:
    import jax
    import jax.numpy as jnp
    from repro.serving.engine import MicroBatcher
    from repro.serving.router import ReplicaRouter

    params, cfg = _seqrec_setup()
    k, max_batch = 5, 8
    router = ReplicaRouter.for_seqrec(params, cfg, n_replicas=2, k=k,
                                      max_batch=max_batch,
                                      method="pqtopk_pruned", hedge=False)
    router.warmup()
    eng = router.engines[0]
    assert eng.ladder is not None and len(eng.ladder) >= 2, (
        f"expected a calibrated multi-rung ladder, got {eng.ladder!r}")
    assert all(e.ladder == eng.ladder for e in router.engines), (
        "replicas must share the lead engine's calibrated ladder")

    sds = jax.ShapeDtypeStruct((4, cfg.max_seq_len), jnp.int32)

    def count() -> int:
        import numpy as np
        from repro.serving.engine import Request

        rng = np.random.default_rng(300)

        def feed(base: int, n: int):
            for i in range(n):
                router.submit(Request(
                    base + i, rng.integers(1, cfg.n_items + 1, 8), k=k))

        # Warm through real router traffic: two full buckets form in one
        # scheduling pass and land on the two least-loaded replicas, so
        # every replica serves before we start counting.
        feed(300, 2 * max_batch)
        router.drain()
        assert all(rs.completed >= 1 for rs in router.replicas), (
            "warm traffic did not reach every replica")
        calls: list = []
        for eng_i in router.engines:
            for key, f in list(eng_i._compiled.items()):
                eng_i._compiled[key] = (
                    lambda seqs, _f=f, _key=key:
                    (calls.append(_key), _f(seqs))[1])
        feed(340, max_batch)                 # exactly ONE full-bucket job
        # The transfer-guard context manager is thread-local and the
        # router launches/completes batches on its worker threads, so
        # the guard has to go through the global config for this serve.
        prev = getattr(jax.config, "jax_transfer_guard", None) or "allow"
        jax.config.update("jax_transfer_guard", "disallow")
        try:
            results = router.drain()
        finally:
            jax.config.update("jax_transfer_guard", prev)
        assert len(results) == max_batch, (
            f"served {len(results)}/{max_batch}")
        assert not any(r.shed for r in results)
        assert not any(r.degraded for r in results), (
            "healthy-path batch must not carry degradation tags")
        return len(calls)

    specs = (
        StaticArgSpec(
            "batch_bucket",
            sample=tuple(range(1, max_batch + 1)),
            mapper=lambda n, _mb=max_batch: MicroBatcher.bucket(n, _mb),
            allowed=_pow2_buckets(max_batch),
            max_variants=max_batch.bit_length() + 1,
            note="pow2 padding buckets for the request batch size"),
        StaticArgSpec(
            "k_bucket",
            sample=tuple(range(1, 64)) + (200, 1000, 10 ** 9),
            mapper=lambda kv, _e=eng: _e.batch_k([kv]),
            allowed=_pow2_buckets(eng.max_k),
            max_variants=eng.max_k.bit_length() + 1,
            note="client k clamped into [1, max_k] then pow2-bucketed"),
        StaticArgSpec(
            "ladder_rung",
            sample=tuple(eng.ladder),
            mapper=lambda r: r,
            allowed=frozenset(eng.ladder),
            max_variants=4,
            note="one shared calibrated ladder across the fleet (rungs "
                 "are cond branches, never separate compiles)"),
        StaticArgSpec(
            "replica",
            sample=tuple(range(router.n_replicas)),
            mapper=lambda _rid: "shared-trace",
            allowed=frozenset({"shared-trace"}),
            max_variants=1,
            note="replica id is pure routing state: every replica "
                 "compiles the one identical serve structure"),
    )

    return BuiltEntry(
        fn=lambda seqs: eng._serve_fn(seqs, k, eng._data),
        args=(sds,),
        static_specs=specs,
        dispatch_counter=count,
        notes=f"ReplicaRouter.for_seqrec x{router.n_replicas} replicas, "
              f"shared ladder={eng.ladder}, hedging off, global "
              "transfer_guard over the worker threads")


# ---------------------------------------------------------------------------
# durable mutation fabric (ISSUE 10: WAL, LSN watermarks)
# ---------------------------------------------------------------------------

@register("router_durable",
          "the durable mutation fabric: WAL-append + LSN-fenced fan-out + "
          "hot swap on every replica — a post-mutation batch is ONE "
          "compiled dispatch, and neither the LSN watermark nor the "
          "replica id ever keys a compile",
          tags=("serve", "engine", "pruned", "router", "mutable"))
def _build_router_durable() -> BuiltEntry:
    import tempfile
    import time as time_lib

    import jax
    import jax.numpy as jnp
    from repro.core.mutation import MutableHeadState
    from repro.serving.catalogue_log import CatalogueLog
    from repro.serving.engine import MicroBatcher
    from repro.serving.router import ReplicaRouter

    params, cfg = _seqrec_setup()
    # A FRESH state (not the lru-cached _mutable_setup one, which other
    # entrypoints mutate): the log's meta pins the catalogue layout.
    mstate = MutableHeadState.build(params["item_emb"]["codes"], cfg.pq.b)
    log = CatalogueLog(tempfile.mkdtemp(prefix="repro_wal_"),
                       fsync_every=16)
    k, max_batch = 5, 8
    router = ReplicaRouter.for_seqrec_mutable(
        params, cfg, mstate, n_replicas=2, k=k, max_batch=max_batch,
        log=log, hedge=False)
    router.warmup()
    eng = router.engines[0]
    assert all(e.ladder == eng.ladder for e in router.engines), (
        "replicas must share the lead engine's calibrated ladder")
    assert eng._swappable, "fleet must be hot-swappable"

    sds = jax.ShapeDtypeStruct((4, cfg.max_seq_len), jnp.int32)

    def count() -> int:
        import numpy as np
        from repro.serving.engine import Request

        rng = np.random.default_rng(400)

        def feed(base: int, n: int):
            for i in range(n):
                router.submit(Request(
                    base + i, rng.integers(1, cfg.n_items + 1, 8), k=k))

        feed(400, 2 * max_batch)               # warm every replica
        router.drain()
        assert all(rs.completed >= 1 for rs in router.replicas), (
            "warm traffic did not reach every replica")
        n_variants = [len(e._compiled) for e in router.engines]

        # Commit a mutation batch through the WAL and wait for every
        # replica's worker to replay it (hot swap, between dispatches).
        ops = [("delete", int(i)) for i in
               rng.choice(np.arange(1, cfg.n_items + 1), 8, replace=False)]
        committed = router.apply_mutations(ops)
        deadline = time_lib.monotonic() + 30.0
        while any(rep["lag"] != 0
                  for rep in router.stats()["replicas"].values()):
            assert time_lib.monotonic() < deadline, "catch-up stalled"
            time_lib.sleep(0.01)
        assert [len(e._compiled) for e in router.engines] == n_variants, (
            "mutation propagation minted new compiled variant(s)")

        calls: list = []
        for eng_i in router.engines:
            for key, f in list(eng_i._compiled.items()):
                eng_i._compiled[key] = (
                    lambda seqs, _f=f, _key=key:
                    (calls.append(_key), _f(seqs))[1])
        feed(440, max_batch)                   # ONE full-bucket job
        # Global guard: launches/completions happen on worker threads
        # (the thread-local context manager would not reach them).
        prev = getattr(jax.config, "jax_transfer_guard", None) or "allow"
        jax.config.update("jax_transfer_guard", "disallow")
        try:
            results = router.drain()
        finally:
            jax.config.update("jax_transfer_guard", prev)
        assert len(results) == max_batch, (
            f"served {len(results)}/{max_batch}")
        assert not any(r.shed or r.degraded for r in results), (
            "healthy-path post-mutation batch must be untagged")
        assert all(r.lsn == committed for r in results), (
            "every Result must carry the committed-LSN watermark")
        return len(calls)

    specs = (
        StaticArgSpec(
            "batch_bucket",
            sample=tuple(range(1, max_batch + 1)),
            mapper=lambda n, _mb=max_batch: MicroBatcher.bucket(n, _mb),
            allowed=_pow2_buckets(max_batch),
            max_variants=max_batch.bit_length() + 1,
            note="pow2 padding buckets for the request batch size"),
        StaticArgSpec(
            "k_bucket",
            sample=tuple(range(1, 64)) + (200, 1000, 10 ** 9),
            mapper=lambda kv, _e=eng: _e.batch_k([kv]),
            allowed=_pow2_buckets(eng.max_k),
            max_variants=eng.max_k.bit_length() + 1,
            note="client k clamped into [1, max_k] then pow2-bucketed"),
        StaticArgSpec(
            "lsn",
            sample=(0, 1, 8, 123, 10 ** 6),
            mapper=lambda _lsn: "head-as-data",
            allowed=frozenset({"head-as-data"}),
            max_variants=1,
            note="the catalogue version is pure data: every committed "
                 "LSN serves through the one compiled head structure"),
        StaticArgSpec(
            "replica",
            sample=tuple(range(router.n_replicas)),
            mapper=lambda _rid: "shared-trace",
            allowed=frozenset({"shared-trace"}),
            max_variants=1,
            note="replica id is pure routing state: every replica "
                 "compiles the one identical serve structure"),
    )

    return BuiltEntry(
        fn=lambda seqs: eng._serve_fn(seqs, k, eng._data),
        args=(sds,),
        static_specs=specs,
        dispatch_counter=count,
        notes=f"ReplicaRouter.for_seqrec_mutable x{router.n_replicas} + "
              f"CatalogueLog WAL, shared ladder={eng.ladder}, "
              "mutate-swap-serve under global transfer_guard")
