"""Sharding rules: parameter specs by tree-path pattern + an activation
sharding plan (contextvar) the model code consults via ``constrain``.

Conventions (DESIGN.md §5):
  mesh axes    ``(pod, data, model)`` multi-pod / ``(data, model)`` single-pod
  batch        ("pod", "data")  — flattened onto the leading batch dim
  seq (SP)     "model"          — long sequences / KV caches
  vocab / items / experts / table-rows  "model"
  FSDP param dim                "data"
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro import compat

# ---------------------------------------------------------------------------
# Activation plan: name -> PartitionSpec, plus the active mesh.
# ---------------------------------------------------------------------------

_PLAN: contextvars.ContextVar[Optional["ShardingPlan"]] = \
    contextvars.ContextVar("activation_plan", default=None)


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto`` (the compiler propagates
    shardings; ``with_sharding_constraint`` hints them).  ``jax.make_mesh``
    defaults to ``Explicit`` axes since jax 0.9, which reject such hints
    and make every ambiguous gather a type error."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


class ShardingPlan:
    """Named activation specs bound to a mesh.

    The specs are hints to the compiler's sharding propagation, so the
    plan keeps an all-``Auto`` view of the mesh it is given."""

    def __init__(self, mesh: Mesh, specs: Dict[str, P]):
        self.mesh = auto_axes(mesh)
        self.specs = dict(specs)

    def sharding(self, name: str) -> Optional[NamedSharding]:
        spec = self.specs.get(name)
        if spec is None:
            return None
        return NamedSharding(self.mesh, spec)


@contextlib.contextmanager
def activation_plan(plan: Optional[ShardingPlan]):
    tok = _PLAN.set(plan)
    try:
        yield plan
    finally:
        _PLAN.reset(tok)


def strip_axis(plan: "ShardingPlan", axis: str) -> "ShardingPlan":
    """Plan view with ``axis`` removed from every spec — used inside
    shard_map regions that are Manual over that axis (e.g. PowerSGD's
    manual-pod gradient exchange)."""
    def fix(spec: P) -> P:
        out = []
        for entry in spec:
            if entry == axis:
                out.append(None)
            elif isinstance(entry, tuple):
                kept = tuple(a for a in entry if a != axis)
                out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
            else:
                out.append(entry)
        return P(*out)
    return ShardingPlan(plan.mesh, {k: fix(v) for k, v in plan.specs.items()})


def constrain(x: jax.Array, name: str) -> jax.Array:
    """Apply the named activation constraint if a plan is active; no-op
    otherwise (single-device tests/smoke runs)."""
    plan = _PLAN.get()
    if plan is None:
        return x
    sh = plan.sharding(name)
    if sh is None or len(sh.spec) > x.ndim:
        return x
    return jax.lax.with_sharding_constraint(x, sh)


def current_plan() -> Optional[ShardingPlan]:
    return _PLAN.get()


def manual_axis_map(fn, mesh: Mesh, in_specs, out_specs, *,
                    axis_names: Optional[set] = None):
    """The repo's standard manual-collective region: ``shard_map`` with
    replication checking off (our regions end in all-gathers whose outputs
    are replicated by construction, which the checker cannot prove).

    Goes through :mod:`repro.compat` so every shard_mapped path — item-
    sharded retrieval, PowerSGD gradient exchange — picks up the right
    ``shard_map``/keyword spelling for the installed JAX.
    """
    return compat.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False,
                            axis_names=axis_names)


# ---------------------------------------------------------------------------
# Standard activation plans
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def lm_activation_plan(mesh: Mesh, *, shard_seq: bool = True,
                       tp_internal: bool = False,
                       vocab_tp: bool = False) -> ShardingPlan:
    """``tp_internal`` = Megatron-style sequence-parallel TP: the residual
    stream stays seq-sharded over 'model', but inside each layer the d_ff
    intermediate and the query heads are model-sharded, so per-layer
    collectives are d_model-sized AG/RS at the layer boundary instead of
    d_ff-sized gathers (the §Perf nemotron iteration)."""
    b = batch_axes(mesh)
    seq = "model" if shard_seq else None
    # Logits: when the sequence is model-sharded keep it sharded through the
    # head (vocab unsharded per device) — avoids all-gathering hidden; when
    # seq is unsharded, shard the vocab dim instead (classic TP head).
    logits = P(b, seq, None) if (shard_seq and not vocab_tp) \
        else P(b, None, "model")
    extra = {}
    if tp_internal:
        extra = {
            "mlp_hidden": P(b, None, "model"),
            "attn_q_heads": P(b, None, "model", None),
        }
    return ShardingPlan(mesh, {
        "tokens": P(b, None),
        "hidden": P(b, seq, None),
        "logits": logits,
        **extra,
        "phi": P(b, None),                    # (B, d) decode hidden
        "kv_cache": P(b, "model", None, None),
        "kv_cache_batch1": P(None, ("data", "model"), None, None),
        "moe_group": P(b, seq, None, None),
        "scores": P(b, "model"),              # (B, N) item scores
    })


def recsys_activation_plan(mesh: Mesh) -> ShardingPlan:
    b = batch_axes(mesh)
    return ShardingPlan(mesh, {
        "batch": P(b),
        "dense_feats": P(b, None),
        "sparse_ids": P(b, None),
        "hidden": P(b, None),
        "seq_hidden": P(b, None, None),
        "scores": P(b, "model"),
    })


def gnn_activation_plan(mesh: Mesh) -> ShardingPlan:
    all_axes = tuple(mesh.axis_names)
    return ShardingPlan(mesh, {
        "edges": P(all_axes),                 # edge lists over all devices
        "edge_feats": P(all_axes, None),
        "node_feats": P(None, None),          # replicated (DESIGN.md §5)
        "batch_nodes": P(batch_axes(mesh)),
    })


# ---------------------------------------------------------------------------
# Parameter sharding rules (path-pattern -> PartitionSpec)
# ---------------------------------------------------------------------------

def _match(rules, path: str, ndim: int) -> P:
    for pat, spec in rules:
        if re.search(pat, path):
            if len(spec) > ndim:
                raise ValueError(f"spec {spec} too long for {path} ndim={ndim}")
            return spec
    return P()


def path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):         # GetAttrKey (registered dataclasses
            parts.append(str(p.name))    # like pruning.PrunedHeadState)
        else:
            parts.append(str(p))
    return "/".join(parts)


def lm_param_rules(scan_layers: bool = True):
    """Stacked layer params have a leading L dim (unsharded).

    2-D weight matrices: FSDP dim over 'data', TP dim over 'model'.
    Experts over 'model' (EP); embedding/vocab over 'model'.
    """
    l = (None,) if scan_layers else ()
    return [
        # MoE experts: (L, E, d, f) — E over model, d over data.
        (r"layers/.*moe/(up|gate)$", P(*l, "model", "data", None)),
        (r"layers/.*moe/down$",      P(*l, "model", None, "data")),
        (r"layers/.*moe/router/w$",  P(*l, None, "model")),
        (r"layers/.*moe/shared/.*/w$", P(*l, "data", "model")),
        # Attention + dense MLP 2-D mats: (L, d_in, d_out).
        (r"layers/.*(wq|wk|wv|up|gate)/w$", P(*l, "data", "model")),
        (r"layers/.*(wo|down)/w$",          P(*l, "model", "data")),
        (r"layers/.*/b$", P(*l, "model")),
        (r"layers/.*(scale|bias)$", P(*l, None)),
        # Embedding + unembedding: vocab over model, d over data.
        (r"(embed|head)/table$", P("model", "data")),
        (r"head/w$", P("data", "model")),
        # PQ head: codes over model (items), sub-embeddings replicated.
        (r"pq_head/codes$", P("model", None)),
        (r"pq_head/sub_emb$", P()),
        (r".*", P()),
    ]


def seqrec_param_rules():
    return [
        (r"item_emb/codes$", P("model", None)),
        (r"item_emb/sub_emb$", P()),
        (r"item_emb/table$", P("model", None)),
        (r".*/(wq|wk|wv|up|gate)/w$", P(None, "model")),
        (r".*/(wo|down)/w$", P("model", None)),
        (r".*", P()),
    ]


def recsys_param_rules():
    return [
        (r"tables/.*", P("model", None)),      # embedding rows over model
        (r"item_emb/codes$", P("model", None)),
        (r"item_emb/(sub_emb|table)$", P()),
        (r"mlp/.*w$", P(None, "model")),
        (r".*", P()),
    ]


def gnn_param_rules():
    return [(r".*", P())]        # GraphSAGE params are tiny: replicate


def param_shardings(mesh: Mesh, params: Any, rules) -> Any:
    """Map a params pytree (of arrays OR ShapeDtypeStructs) to NamedShardings."""

    def leaf(path, x):
        spec = _match(rules, path_str(path), len(x.shape))
        # Drop axes that don't divide evenly — replicate those dims instead.
        fixed = []
        for dim, ax in enumerate(spec):
            if ax is None:
                fixed.append(None)
                continue
            size = mesh.shape[ax] if isinstance(ax, str) else 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                size = mesh.shape[a] if not isinstance(ax, tuple) else size
            if isinstance(ax, tuple):
                size = 1
                for a in ax:
                    size *= mesh.shape[a]
            fixed.append(ax if x.shape[dim] % size == 0 else None)
        return NamedSharding(mesh, P(*fixed))

    return jax.tree_util.tree_map_with_path(leaf, params)


def replicated(mesh: Mesh, tree: Any) -> Any:
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
