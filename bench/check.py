"""The comparison that decides ``correct``.

After the window closes and the program's state is freed, answered
requests are scored again by the configuration's plain reference, at the
precision the configuration states (float32 activations, matmuls at its
``matmul_precision``): phi, and the float32 sum of the sub-item score
table at each item's codes.  Scores are measured in units of the user's
spread, the standard deviation of the user's reference scores over
``SIGMA_ROWS`` catalogue rows drawn from the seed.  Three numbers are
compared, each against the limit the configuration file states
(``check``):

* ``score_err``: over ``check_scores`` answered requests drawn from the
  seed, the widest distance between a served score and the reference
  score of the same item.  A wrong item, a wrong phi or a wrong sum shows
  here.
* ``score_gap``: over ``check_requests`` of them (the longest history
  always among them), scored against the whole catalogue, the widest gap
  by which a served item's reference score lies below the reference's
  score at the same rank.  An item missed or reordered shows here.
* ``bad_answers``: requests due that got no answer (shed) or a malformed
  one (wrong length, an id outside the catalogue, an id twice), among all
  requests.  Its limit is 0.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic_gen

GAP_BLOCK = 16     # requests scored against the whole catalogue at once
ERR_BLOCK = 256    # requests scored at their served items at once
SIGMA_ROWS = 16384
NOTHING = 1e9      # the reading when no answer could be compared


def sample(requests: Dict[int, traffic_gen.Work], answered: List[int],
           n: int, seed: int, stream: str = "check") -> List[int]:
    """``n`` answered request ids drawn from the seed, the one with the
    longest history (lowest id on a tie) always among them."""
    answered = sorted(answered)
    if len(answered) <= n:
        return answered
    longest = max(answered, key=lambda r: (len(requests[r].history), -r))
    rest = [r for r in answered if r != longest]
    picked = traffic_gen.rng_for(seed, stream).choice(
        len(rest), n - 1, replace=False)
    return sorted([longest] + [rest[i] for i in picked])


def sigma_rows(cfg: Dict[str, Any], seed: int) -> np.ndarray:
    n = cfg["n_items"] + 1
    return np.sort(traffic_gen.rng_for(seed, "sigma").choice(
        n, min(SIGMA_ROWS, n), replace=False)).astype(np.int32)


def pad_histories(hists, seq_len: int) -> np.ndarray:
    out = np.zeros((len(hists), seq_len), np.int32)
    for i, h in enumerate(hists):
        h = np.asarray(h)[-seq_len:]
        out[i, seq_len - len(h):] = h
    return out


def malformed(items, scores, k: int, n_rows: int) -> bool:
    items = np.asarray(items)
    return bool(len(items) != k or len(np.asarray(scores)) != k
                or items.min(initial=0) < 0 or items.max(initial=0) >= n_rows
                or len(np.unique(items)) != k)


@functools.partial(jax.jit, static_argnames=("ref", "z"))
def _err(w, seqs, ids, scores, rows, *, ref, z):
    """Per request: the widest |served - reference| score of its served
    items, and its spread."""
    ph = ref.phi(w, seqs, z)
    sigma = jnp.std(ref.scores_at(w, ph, z, rows), axis=1)
    at = ref.scores_at(w, ph, z, ids)
    return jnp.max(jnp.abs(scores - at), axis=1) / sigma


@functools.partial(jax.jit, static_argnames=("ref", "z", "k"))
def _gap(w, seqs, ids, rows, *, ref, z, k):
    """Per request: the widest gap of a served item below the reference's
    item of the same rank."""
    ph = ref.phi(w, seqs, z)
    sigma = jnp.std(ref.scores_at(w, ph, z, rows), axis=1)
    top, _ = jax.lax.top_k(ref.all_scores(w, ph, z), k)
    at = ref.scores_at(w, ph, z, ids)
    return jnp.max(top - at, axis=1) / sigma


def _blocks(fn, block: int, seqs: np.ndarray, *arrays) -> np.ndarray:
    """``fn`` over rows in blocks of ``block`` (the last padded with
    copies of its last row). -> per-row results."""
    out = []
    for i in range(0, len(seqs), block):
        n = len(seqs[i:i + block])

        def cut(a):
            a = a[i:i + block]
            return (np.concatenate([a, np.repeat(a[-1:], block - n, 0)])
                    if n < block else a)
        out.append(np.asarray(fn(jnp.asarray(cut(seqs)),
                                 *(jnp.asarray(cut(a)) for a in arrays)))[:n])
    return np.concatenate(out)


def readings(ref, cfg: Dict[str, Any], weights, seed: int,
             requests: Dict[int, traffic_gen.Work],
             served: Dict[int, Tuple[Any, Any]], err_ids: List[int],
             gap_ids: List[int]) -> Dict[str, float]:
    """``score_err`` over ``err_ids``, ``score_gap``
    over ``gap_ids``, for the served (ids, scores) ``served[rid]``."""
    z = ref.sizes(cfg)
    rows = jnp.asarray(sigma_rows(cfg, seed))
    out = {"score_err": 0.0, "score_gap": 0.0}
    for name, rids, block in (("score_err", err_ids, ERR_BLOCK),
                              ("score_gap", gap_ids, GAP_BLOCK)):
        by_k: Dict[int, List[int]] = {}
        for rid in rids:
            by_k.setdefault(len(served[rid][0]), []).append(rid)
        for k, group in sorted(by_k.items()):
            seqs = pad_histories([requests[r].history for r in group],
                                 cfg["max_seq_len"])
            ids = np.stack([np.asarray(served[r][0], np.int32)
                            for r in group])
            if name == "score_err":
                scores = np.stack([np.asarray(served[r][1], np.float32)
                                   for r in group])
                vals = _blocks(lambda s, i, c: _err(weights, s, i, c, rows,
                                                    ref=ref, z=z),
                               block, seqs, ids, scores)
            else:
                vals = _blocks(lambda s, i: _gap(weights, s, i, rows,
                                                 ref=ref, z=z, k=k),
                               block, seqs, ids)
            out[name] = max(out[name], float(vals.max()))
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """-> (every reading within its limit, {name: {value, limit}})."""
    table = {name: {"value": values[name], "limit": limits[name]}
             for name in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table


def run_check(ref, cfg: Dict[str, Any], seed: int,
              requests: Dict[int, traffic_gen.Work], answers: Dict[int, Any],
              traffic: Dict[str, Any], weights
              ) -> Tuple[Dict[str, float], List[int]]:
    """Readings of the three numbers for one run, and the requests sampled
    for the gap."""
    n_rows = cfg["n_items"] + 1
    bad = 0
    good = []
    for rid, w in requests.items():
        a = answers.get(rid)
        k = min(w.k, n_rows)
        if a is None or a.shed or malformed(a.items, a.scores, k, n_rows):
            bad += 1
        else:
            good.append(rid)
    gap_ids = sample(requests, good, traffic["check_requests"], seed)
    err_ids = sorted(set(gap_ids) | set(
        sample(requests, good, traffic["check_scores"], seed, "scores")))
    values = {"bad_answers": float(bad)}
    if not gap_ids:
        values.update(score_gap=NOTHING, score_err=NOTHING)
        return values, gap_ids
    values.update(readings(ref, cfg, weights, seed, requests,
                           {r: (answers[r].items, answers[r].scores)
                            for r in err_ids}, err_ids, gap_ids))
    return values, gap_ids
