"""SASRec / gBERT4Rec backbones with the RecJPQ item layer — the paper's
own models (Table 3).

Item id 0 is padding; real items are 1..n_items.  The PQ embedding is
*shared* between the input layer and the scoring head (as in RecJPQ).
Training uses gBCE with uniform negative sampling [gSASRec, RecSys'23] so
large catalogues are trainable; serving scores the full catalogue through
any of the paper's scoring algorithms.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SeqRecConfig
from repro.core import retrieval_head
from repro.distributed.sharding import constrain
from repro.models import attention as attn_lib, layers

Params = Dict[str, Any]


def init_seqrec(key: jax.Array, cfg: SeqRecConfig, codes=None,
                centroids=None) -> Params:
    ks = jax.random.split(key, cfg.n_blocks + 3)
    dtype = jnp.dtype(cfg.param_dtype)
    head_dim = cfg.d_model // cfg.n_heads
    from repro.configs.base import AttentionConfig
    acfg = AttentionConfig(n_heads=cfg.n_heads, n_kv_heads=cfg.n_heads,
                           head_dim=head_dim)
    blocks = []
    for i in range(cfg.n_blocks):
        bks = jax.random.split(ks[i], 2)
        blocks.append({
            "attn": attn_lib.attention_init(bks[0], acfg, cfg.d_model, dtype),
            "ln1": layers.norm_init(cfg.d_model, "layernorm", dtype),
            "ln2": layers.norm_init(cfg.d_model, "layernorm", dtype),
            "mlp": layers.mlp_init(bks[1], cfg.d_model, cfg.d_ff,
                                   gated=False, dtype=dtype),
        })
    p: Params = {
        # +1 row for padding id 0.
        "item_emb": retrieval_head.init(ks[-3], cfg.n_items + 1, cfg.d_model,
                                        cfg.pq, codes=codes,
                                        centroids=centroids, dtype=dtype),
        "pos_emb": layers.embedding_init(ks[-2], cfg.max_seq_len, cfg.d_model,
                                         dtype),
        "final_norm": layers.norm_init(cfg.d_model, "layernorm", dtype),
        "blocks": blocks,
    }
    if cfg.backbone == "bert4rec":
        p["mask_emb"] = (jax.random.normal(ks[-1], (cfg.d_model,), jnp.float32)
                         * 0.02).astype(dtype)
    return p


def abstract_seqrec(cfg: SeqRecConfig) -> Params:
    return jax.eval_shape(functools.partial(init_seqrec, cfg=cfg),
                          jax.random.PRNGKey(0))


def _attn_cfg(cfg: SeqRecConfig):
    from repro.configs.base import AttentionConfig
    return AttentionConfig(n_heads=cfg.n_heads, n_kv_heads=cfg.n_heads,
                           head_dim=cfg.d_model // cfg.n_heads)


def seqrec_hidden(params: Params, item_seq: jax.Array, cfg: SeqRecConfig,
                  ) -> jax.Array:
    """item_seq (B, S) int32 (0 = pad) -> hidden (B, S, d)."""
    b, s = item_seq.shape
    x = retrieval_head.embed(params["item_emb"], item_seq)
    x = x * (item_seq != 0)[..., None].astype(x.dtype)
    x = x + params["pos_emb"]["table"][None, :s].astype(x.dtype)
    x = constrain(x, "seq_hidden")
    acfg = _attn_cfg(cfg)
    causal = cfg.backbone == "sasrec"
    for blk in params["blocks"]:
        h = layers.apply_norm(blk["ln1"], x, "layernorm")
        h = attn_lib.full_attention(blk["attn"], acfg, h, causal=causal)
        x = x + h
        h = layers.apply_norm(blk["ln2"], x, "layernorm")
        x = x + layers.mlp(blk["mlp"], h, "gelu")
    return layers.apply_norm(params["final_norm"], x, "layernorm")


# ---------------------------------------------------------------------------
# training: gBCE with uniform negatives
# ---------------------------------------------------------------------------

def gbce_loss(pos_scores: jax.Array, neg_scores: jax.Array, mask: jax.Array,
              n_items: int, n_negatives: int, t: float) -> jax.Array:
    """Generalised BCE [gSASRec].  beta = alpha*(t*(1-1/alpha)+1/alpha),
    sigma^beta(s+) applied via logits: log(sigma^beta(s)) = beta*logsigmoid(s)."""
    alpha = n_negatives / max(n_items - 1, 1)
    beta = alpha * (t * (1.0 - 1.0 / alpha) + 1.0 / alpha)
    pos = beta * jax.nn.log_sigmoid(pos_scores)                   # (B, S)
    neg = jax.nn.log_sigmoid(-neg_scores).sum(-1)                 # (B, S)
    per_pos = -(pos + neg)
    denom = jnp.maximum(mask.sum(), 1.0)
    return (per_pos * mask).sum() / denom


def seqrec_loss(params: Params, batch: Dict[str, jax.Array],
                cfg: SeqRecConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """batch: input_seq (B,S), targets (B,S), negatives (B,S,n_neg) — all
    item ids (0 pad).  SASRec: next-item at every position; BERT4Rec: the
    data pipeline pre-masks inputs and sets targets only at masked slots."""
    hidden = seqrec_hidden(params, batch["input_seq"], cfg)       # (B,S,d)
    emb = params["item_emb"]
    pos_emb = retrieval_head.embed(emb, batch["targets"])         # (B,S,d)
    neg_emb = retrieval_head.embed(emb, batch["negatives"])       # (B,S,n,d)
    h32 = hidden.astype(jnp.float32)
    pos_scores = jnp.einsum("bsd,bsd->bs", h32, pos_emb.astype(jnp.float32))
    neg_scores = jnp.einsum("bsd,bsnd->bsn", h32, neg_emb.astype(jnp.float32))
    mask = (batch["targets"] != 0).astype(jnp.float32)
    loss = gbce_loss(pos_scores, neg_scores, mask, cfg.n_items,
                     cfg.n_negatives, cfg.gbce_t)
    return loss, {"nll": loss}


# ---------------------------------------------------------------------------
# serving: sequence embedding phi + catalogue scoring (the paper's pipeline)
# ---------------------------------------------------------------------------

def sequence_embedding(params: Params, item_seq: jax.Array, cfg: SeqRecConfig,
                       ) -> jax.Array:
    """phi for each user: last position (SASRec) / mask slot appended at the
    end (BERT4Rec single-step next-item inference, as served in the paper)."""
    if cfg.backbone == "bert4rec":
        b = item_seq.shape[0]
        # Shift left, append the [MASK] position.
        seq = jnp.concatenate(
            [item_seq[:, 1:], jnp.zeros((b, 1), item_seq.dtype)], axis=1)
        x = retrieval_head.embed(params["item_emb"], seq)
        x = x * (seq != 0)[..., None].astype(x.dtype)
        x = x.at[:, -1, :].set(params["mask_emb"].astype(x.dtype))
        x = x + params["pos_emb"]["table"][None, :seq.shape[1]].astype(x.dtype)
        acfg = _attn_cfg(cfg)
        for blk in params["blocks"]:
            h = layers.apply_norm(blk["ln1"], x, "layernorm")
            h = attn_lib.full_attention(blk["attn"], acfg, h, causal=False)
            x = x + h
            h = layers.apply_norm(blk["ln2"], x, "layernorm")
            x = x + layers.mlp(blk["mlp"], h, "gelu")
        x = layers.apply_norm(params["final_norm"], x, "layernorm")
        return x[:, -1, :].astype(jnp.float32)
    hidden = seqrec_hidden(params, item_seq, cfg)
    return hidden[:, -1, :].astype(jnp.float32)


def serve_topk(params: Params, item_seq: jax.Array, cfg: SeqRecConfig, *,
               k: int = 10, method: str = "pqtopk", sharded_mesh=None,
               ladder=None, pin_rung: bool = False,
               return_rung: bool = False):
    """Full serving path: backbone -> phi -> scoring -> TopK (Table 3).

    ``sharded_mesh``: item-sharded distributed retrieval (shard-local
    PQTopK + O(k x shards) merge instead of an O(B x N) score gather).

    ``ladder``/``pin_rung``/``return_rung`` apply to
    ``method="pqtopk_pruned"`` only: the calibrated slot-budget ladder for
    the cascade, whether to pin it to its cheapest rung (the router's
    load-degraded mode — bounded cost, possibly inexact, every result
    served through it must be tagged), and whether to additionally return
    the rung taken (i32 scalar — still one dispatch; the serving engine
    uses it to track ``rung_hit_fraction``)."""
    with jax.named_scope("backbone"):
        phi = constrain(sequence_embedding(params, item_seq, cfg), "phi")
    if method != "pqtopk_pruned" and return_rung:
        raise ValueError("return_rung is only meaningful for the pruned "
                         "cascade (method='pqtopk_pruned')")
    if pin_rung and sharded_mesh is not None:
        raise ValueError("pin_rung is not threaded through the sharded "
                         "cascade; degrade the flat replicas instead")
    if sharded_mesh is not None:
        if method == "pqtopk_pruned" and return_rung:
            vals, ids, stats = retrieval_head.top_items_pruned_sharded(
                params["item_emb"], phi, k, sharded_mesh, pq_cfg=cfg.pq,
                ladder=ladder, return_stats=True)
            return ids, vals, stats["rung_hit"]
        vals, ids = retrieval_head.top_items_sharded(
            params["item_emb"], phi, k, sharded_mesh, method=method,
            pq_cfg=cfg.pq, ladder=ladder)
    else:
        out = retrieval_head.top_items(params["item_emb"], phi, k,
                                       method=method, pq_cfg=cfg.pq,
                                       ladder=ladder, pin_rung=pin_rung,
                                       return_rung=return_rung)
        if return_rung:
            vals, ids, rung = out
            return ids, vals, rung
        vals, ids = out
    return ids, vals
