"""The control of the correctness check: the plain reference put in the
program's place one precision step below the configuration's float32, in
bfloat16: weights and activations in bfloat16, every matmul and the
scores' sums in float32, the scores served in float32.  It is what a
bfloat16 backbone with a float32-accumulating head would serve; a check
whose limits let it pass could not tell that from the configuration's
float32.  The benchmark's own runs never compute it; ``limits.py`` reads
it beside the program, and a test keeps it failing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.check import GAP_BLOCK, pad_histories


def _bf16(a):
    return a.astype(jnp.bfloat16) if jnp.issubdtype(a.dtype, jnp.floating) \
        else a


@functools.partial(jax.jit, static_argnames=("ref", "z", "k"))
def _topk(w, seqs, *, ref, z, k):
    w = jax.tree_util.tree_map(_bf16, w)
    ph = ref.phi(w, seqs, z, dtype=jnp.bfloat16)
    vals, ids = jax.lax.top_k(ref.all_scores(w, ph, z), k)
    return ids, vals


def answers(ref, cfg, weights, hists, k: int):
    """The control's (ids (n, k), scores (n, k)) for ``hists``."""
    z = ref.sizes(cfg)
    seqs = pad_histories(hists, cfg["max_seq_len"])
    ids, scores = [], []
    for i in range(0, len(seqs), GAP_BLOCK):
        block = seqs[i:i + GAP_BLOCK]
        n = len(block)
        if n < GAP_BLOCK:
            block = np.concatenate([block, np.repeat(block[-1:],
                                                     GAP_BLOCK - n, 0)])
        a, b = _topk(weights, jnp.asarray(block), ref=ref, z=z, k=k)
        ids.append(np.asarray(a)[:n])
        scores.append(np.asarray(b)[:n])
    return np.concatenate(ids), np.concatenate(scores)
