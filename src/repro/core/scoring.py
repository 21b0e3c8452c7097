"""Scoring algorithms — the heart of the paper.

Given a batch of sequence embeddings ``phi (B, d)`` and an item space
described either densely (``W (N, d)``) or by PQ codes (``codes (N, m)`` +
sub-embeddings ``Psi (m, b, d/m)``), compute all item scores ``r (B, N)``.

* ``score_dense``          — Transformer-Default baseline: r = phi @ W.T.
* ``subid_scores``         — S matrix (Eq. 4): S[q,k,j] = psi_{k,j} . phi_{q,k}.
* ``score_recjpq``         — Algorithm 2 (RecJPQ original): *sequential*
                             fori_loop over splits carrying a (B, N)
                             accumulator — faithfully reproduces the
                             non-parallelisable structure of the TF original.
* ``score_pqtopk``         — Algorithm 1 (PQTopK): one vectorised
                             gather-and-sum, parallel over items.
* ``score_pqtopk_onehot``  — TPU-native restatement: per-split one-hot
                             matmul on the MXU (DESIGN.md §3); identical
                             output, different roofline.

All functions take S pre-computed where applicable so benchmarks can isolate
"scoring" exactly like the paper does.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def tree_sum(parts):
    """Balanced-tree reduction of a list of arrays.

    This is THE accumulation order for per-split partial scores: Algorithm 1
    (:func:`score_pqtopk`), the jnp kernel oracle (``kernels/pqtopk/ref.py``)
    and the Pallas tile kernel (``kernels/pqtopk/kernel.py``) all reduce
    through it, so their f32 rounding is bit-identical — parity tests
    compare them at atol=0.  Also avoids materialising a (B, m, N) stack
    and keeps the adds parallelisable (no loop-carried accumulator).
    """
    parts = list(parts)
    return tree_sum_lazy(parts.__getitem__, len(parts))


def tree_sum_lazy(make, n: int):
    """``tree_sum([make(i) for i in range(n)])``, each part made only when
    its subtree is reduced: the same association — pairs, then pairs of
    pairs, the odd part carried up a level — so the same rounding, with
    at most log2(n) + 1 partial sums alive at once (what a Pallas kernel's
    VMEM holds).  The first ``p`` parts, ``p`` the largest power of two
    below ``n``, form one complete subtree; the rest form the other."""
    if n == 1:
        return make(0)
    p = 1 << ((n - 1).bit_length() - 1)
    left = tree_sum_lazy(make, p)
    return left + tree_sum_lazy(lambda i: make(p + i), n - p)


def score_dense(w: jax.Array, phi: jax.Array) -> jax.Array:
    """Default matmul scoring r = W phi. w: (N, d), phi: (B, d) -> (B, N)."""
    return jnp.einsum("bd,nd->bn", phi, w, preferred_element_type=jnp.float32)


def subid_scores(sub_emb: jax.Array, phi: jax.Array) -> jax.Array:
    """Eq. 4. sub_emb: (m, b, d/m), phi: (B, d) -> S: (B, m, b).

    Cost O(B * b * d): independent of the catalogue size N.
    """
    B, d = phi.shape
    m, b, sub = sub_emb.shape
    assert d == m * sub, f"phi dim {d} != m*sub {m * sub}"
    phi_split = phi.reshape(B, m, sub)
    return jnp.einsum("bms,mjs->bmj", phi_split, sub_emb,
                      preferred_element_type=jnp.float32)


def score_pqtopk(codes: jax.Array, s: jax.Array) -> jax.Array:
    """Algorithm 1 (PQTopK): r_i = sum_k S[k, G[i,k]], parallel over items.

    codes: (N, m) int, s: (B, m, b) -> (B, N) f32.

    The m per-split gathers are *independent* (no loop-carried accumulator —
    the paper's point vs Alg. 2) and are reduced as a balanced tree, so no
    (B, m, N) intermediate is materialised.
    """
    m = codes.shape[1]
    parts = [jnp.take(s[:, k, :].astype(jnp.float32),
                      codes[:, k].astype(jnp.int32), axis=1)
             for k in range(m)]                        # m x (B, N)
    return tree_sum(parts)


def score_recjpq(codes: jax.Array, s: jax.Array) -> jax.Array:
    """Algorithm 2 (RecJPQ original): sequential accumulation over splits.

    The outer loop over k is a ``lax.fori_loop`` carrying the full (B, N)
    accumulator — the loop-carried dependency prevents parallelisation over
    splits *and* forces N-sized accumulator traffic per split, exactly the
    structure the paper identifies as the bottleneck.
    """
    n, m = codes.shape
    bq = s.shape[0]

    def body(k, acc):
        # Gather split k's codes for every item, then that split's scores.
        ck = jax.lax.dynamic_slice_in_dim(codes, k, 1, axis=1)[:, 0]  # (N,)
        sk = jax.lax.dynamic_slice_in_dim(s, k, 1, axis=1)[:, 0]      # (B, b)
        return acc + jnp.take(sk, ck.astype(jnp.int32), axis=1)

    return jax.lax.fori_loop(0, m, body, jnp.zeros((bq, n), jnp.float32))


def score_pqtopk_onehot(codes: jax.Array, s: jax.Array) -> jax.Array:
    """MXU restatement of Algorithm 1: scores = sum_k onehot(G_k) @ S_k^T.

    One-hots are built on the fly via iota comparison (never stored in HBM).
    This trades 2*m*b FLOPs/item/query for m bytes/item of HBM traffic —
    the TPU-native adaptation (DESIGN.md §3); output identical to
    ``score_pqtopk``.
    """
    n, m = codes.shape
    b = s.shape[-1]
    iota = jax.lax.broadcasted_iota(codes.dtype, (1, b), 1)  # (1, b)
    acc = None
    for k in range(m):
        onehot = (codes[:, k:k + 1] == iota).astype(s.dtype)  # (N, b)
        part = jnp.einsum("nb,qb->qn", onehot, s[:, k, :],
                          preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    return acc


def score_items_pqtopk(codes: jax.Array, s: jax.Array,
                       item_ids: jax.Array) -> jax.Array:
    """PQTopK over a candidate subset V ⊆ I (Algorithm 1's optional V)."""
    return score_pqtopk(codes[item_ids], s)


SCORERS = {
    "dense": None,  # needs W, dispatched in retrieval_head
    "recjpq": score_recjpq,
    "pqtopk": score_pqtopk,
    "pqtopk_onehot": score_pqtopk_onehot,
}
