"""Plain reference of the SASRec / gBERT4Rec RecJPQ serving path, and the
seeded weights that the system under test and this reference both use.

Nothing here imports the program.  The weights are made from the seed by
:func:`make_weights`, in one jitted call, in the layout the program's
``serve_topk`` takes; the reference makes them again from the same seed
after the timed window instead of reading the program's copy.

The forward pass follows the program's equations, which depart from the
published SASRec / BERT4Rec in these ways (noted in PERF.md):

* pre-norm blocks (LayerNorm, eps 1e-6, before attention and MLP) and a
  final LayerNorm;
* rotary position encoding (theta 1e4) on queries and keys, on top of the
  learned absolute position table;
* histories are right-aligned in the 200 slots; padding slots (id 0) get a
  zero item embedding but keep their position embedding and are not masked
  as keys;
* GELU in its tanh form; no dropout;
* BERT4Rec serves one step: the history shifted left by one slot, the
  ``[MASK]`` embedding in the last slot, phi read there;
* attention normalises after the product with the values:
  ``softmax(QK)V = (exp(QK - max) V) / sum(exp(QK - max))``.

Activations are float32 and every matmul runs at the configuration's
``matmul_precision``.  At ``default`` the TPU rounds each matmul's inputs
to bfloat16 and sums in float32, so where an input is rounded matters:
the unnormalised attention weights are rounded, not the normalised ones,
and the sub-item score table is a matmul like the rest.

phi is the last slot's output.  An item's score is the sum over the m
splits of ``S[split, code]`` with ``S = phi_split . sub_emb``; the
catalogue is every row of ``codes`` (row 0 included, as the program
serves it).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = {"default": jax.lax.Precision.DEFAULT,
              "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}
LN_EPS = 1e-6
ROPE_THETA = 10_000.0


class Sizes(NamedTuple):
    backbone: str          # "sasrec" | "bert4rec"
    n_items: int
    d_model: int
    n_blocks: int
    n_heads: int
    d_ff: int
    max_seq_len: int
    m: int
    b: int
    code_dtype: str
    precision: str         # matmul precision, a key of PRECISIONS


def sizes(cfg: Dict[str, Any]) -> Sizes:
    pq = cfg["pq"]
    return Sizes(cfg["backbone"], cfg["n_items"], cfg["d_model"],
                 cfg["n_blocks"], cfg["n_heads"], cfg["d_ff"],
                 cfg["max_seq_len"], pq["m"], pq["b"], pq["code_dtype"],
                 cfg["matmul_precision"])


def jax_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (jax keys keep 32 bits, so
    large seeds are hashed down rather than truncated)."""
    word = np.random.default_rng([int(seed), 0x5EED]).integers(
        0, 2 ** 32, dtype=np.uint64)
    return jax.random.PRNGKey(int(word))


def _init(key, z: Sizes):
    d, ff = z.d_model, z.d_ff
    ks = iter(jax.random.split(key, 8 + 12 * z.n_blocks))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    def norm():
        return {"scale": 1.0 + normal((d,), 0.1), "bias": normal((d,), 0.1)}

    blocks = []
    for _ in range(z.n_blocks):
        blocks.append({
            "attn": {n: {"w": normal((d, d), d ** -0.5)}
                     for n in ("wq", "wk", "wv", "wo")},
            "ln1": norm(), "ln2": norm(),
            "mlp": {"up": {"w": normal((d, ff), d ** -0.5)},
                    "down": {"w": normal((ff, d), ff ** -0.5)}},
        })
    codes = jax.random.randint(next(ks), (z.n_items + 1, z.m), 0, z.b,
                               jnp.int32).astype(jnp.dtype(z.code_dtype))
    w = {
        "item_emb": {"codes": codes,
                     "sub_emb": normal((z.m, z.b, d // z.m), 0.02)},
        "pos_emb": {"table": normal((z.max_seq_len, d), 0.02)},
        "final_norm": norm(),
        "blocks": blocks,
    }
    if z.backbone == "bert4rec":
        w["mask_emb"] = normal((d,), 0.02)
    return w


@functools.lru_cache(maxsize=None)
def _init_fn(z: Sizes):
    return jax.jit(functools.partial(_init, z=z))


def make_weights(cfg: Dict[str, Any], seed: int):
    """All weights and the catalogue's codes, on the default device, in
    one jitted call from ``seed``."""
    return _init_fn(sizes(cfg))(jax_key(seed))


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------

def _layernorm(p, x, dtype):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + jnp.asarray(LN_EPS, dtype))
    return y * p["scale"].astype(dtype) + p["bias"].astype(dtype)


def _rope(x, dtype):
    """x (B, S, H, D): rotate the first and second halves of D by
    position * theta^(-2i/D)."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = ROPE_THETA ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs      # (S, D/2)
    cos = jnp.cos(ang)[None, :, None, :].astype(dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _embed(item_emb, ids, dtype):
    codes = item_emb["codes"][ids].astype(jnp.int32)          # (B, S, m)
    sub = item_emb["sub_emb"].astype(dtype)                    # (m, b, d/m)
    return jnp.concatenate([sub[k][codes[..., k]]
                            for k in range(sub.shape[0])], -1)


def _mm(eq, a, b, z: Sizes, dtype):
    return jnp.einsum(eq, a, b, precision=PRECISIONS[z.precision],
                      preferred_element_type=jnp.float32).astype(dtype)


def attention(a, h, z: Sizes, *, dtype=jnp.float32):
    """Self-attention of one block over h (B, S, d): causal for SASRec,
    bidirectional for BERT4Rec; every key is attended, padding included."""
    bsz, s, _ = h.shape
    hd = z.d_model // z.n_heads
    q, k, v = (_mm("bsd,de->bse", h, a[n]["w"].astype(dtype), z, dtype
                   ).reshape(bsz, s, z.n_heads, hd)
               for n in ("wq", "wk", "wv"))
    q, k = _rope(q, dtype), _rope(k, dtype)
    logits = _mm("bqhd,bkhd->bhqk", q * jnp.asarray(hd ** -0.5, dtype), k, z,
                 dtype)
    if z.backbone == "sasrec":
        logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits,
                           jnp.asarray(-jnp.inf, dtype))
    p = jnp.exp(logits - jnp.max(logits, -1, keepdims=True))
    total = jnp.swapaxes(jnp.sum(p, -1), 1, 2)[..., None]       # (B,S,H,1)
    o = (_mm("bhqk,bkhd->bqhd", p, v, z, dtype) / total).reshape(
        bsz, s, z.d_model)
    return _mm("bsd,de->bse", o, a["wo"]["w"].astype(dtype), z, dtype)


def mlp(p, h, z: Sizes, *, dtype=jnp.float32):
    u = jax.nn.gelu(_mm("bsd,df->bsf", h, p["up"]["w"].astype(dtype), z,
                        dtype), approximate=True)
    return _mm("bsf,fd->bsd", u, p["down"]["w"].astype(dtype), z, dtype)


def phi(w, seqs, z: Sizes, *, dtype=jnp.float32):
    """seqs (B, S) int32, right-aligned, 0 = padding -> phi (B, d) in
    ``dtype``: activations in ``dtype``, matmuls at the configuration's
    precision, summed in float32."""
    bsz, s = seqs.shape
    if z.backbone == "bert4rec":
        seqs = jnp.concatenate([seqs[:, 1:], jnp.zeros((bsz, 1), seqs.dtype)],
                               axis=1)
    x = _embed(w["item_emb"], seqs, dtype) * (seqs != 0)[..., None].astype(dtype)
    if z.backbone == "bert4rec":
        x = x.at[:, -1, :].set(w["mask_emb"].astype(dtype))
    x = x + w["pos_emb"]["table"][None, :s].astype(dtype)
    for blk in w["blocks"]:
        x = x + attention(blk["attn"], _layernorm(blk["ln1"], x, dtype), z,
                          dtype=dtype)
        x = x + mlp(blk["mlp"], _layernorm(blk["ln2"], x, dtype), z,
                    dtype=dtype)
    x = _layernorm(w["final_norm"], x, dtype)
    return x[:, -1, :]


def subid_table(w, ph, z: Sizes):
    """S (B, m, b) in float32: each split of phi against that split's
    sub-item embeddings, at the configuration's precision."""
    sub = w["item_emb"]["sub_emb"].astype(jnp.float32)
    return jnp.einsum("bms,mjs->bmj",
                      ph.astype(jnp.float32).reshape(ph.shape[0], z.m, -1),
                      sub, precision=PRECISIONS[z.precision],
                      preferred_element_type=jnp.float32)


def scores_at(w, ph, z: Sizes, rows):
    """Scores of catalogue rows: ``rows`` (R,) shared by every request ->
    (B, R); ``rows`` (B, K), one list per request -> (B, K).  A score is
    the float32 sum over the splits of S at the row's code, added in split
    order."""
    s = subid_table(w, ph, z)
    codes = w["item_emb"]["codes"][rows].astype(jnp.int32)    # (..., m)
    if rows.ndim == 1:
        parts = [s[:, k, :][:, codes[:, k]] for k in range(z.m)]
    else:
        parts = [jnp.take_along_axis(s[:, k, :], codes[..., k], axis=1)
                 for k in range(z.m)]
    r = parts[0]
    for p in parts[1:]:
        r = r + p
    return r


def all_scores(w, ph, z: Sizes):
    """phi (B, d) -> scores of every catalogue row (B, n_items + 1)."""
    return scores_at(w, ph, z, jnp.arange(z.n_items + 1))
