"""Work counted from shapes: the yardstick that utilization and roofline
shares divide by.  Both counts are of what the algorithm needs, not of
what an implementation happens to compute, so a later change to how the
program computes them cannot move the count.
"""
from __future__ import annotations

from typing import Any, Dict


def real_positions(cfg: Dict[str, Any], history_len: int) -> int:
    """Positions of a request that carry its history: the last
    ``max_seq_len`` items, and for BERT4Rec the [MASK] slot that replaces
    the oldest one.  Another backbone brings its own count."""
    s = cfg["max_seq_len"]
    if cfg["backbone"] == "bert4rec":
        return min(history_len, s - 1) + 1
    if cfg["backbone"] == "sasrec":
        return min(history_len, s)
    raise ValueError(f"no count of real positions for {cfg['backbone']!r}")


def backbone_flops(cfg: Dict[str, Any], positions: int) -> float:
    """Forward FLOPs of the Transformer over ``positions`` real positions,
    as if each of them is computed (padding is not useful work): per block
    the q, k, v and output projections, the attention scores and their
    weighted sum (causal pairs for SASRec, all pairs for BERT4Rec), and the
    two MLP matrices.  Norms, softmax and activations are left out.
    Another backbone brings its own count."""
    d, ff, n = cfg["d_model"], cfg["d_ff"], positions
    if cfg["backbone"] == "sasrec":
        pairs = n * (n + 1) / 2
    elif cfg["backbone"] == "bert4rec":
        pairs = n * n
    else:
        raise ValueError(f"no FLOP count for backbone {cfg['backbone']!r}")
    per_block = 2 * n * d * 4 * d + 2 * 2 * pairs * d + 2 * n * 2 * d * ff
    return float(cfg["n_blocks"] * per_block)


def head_flops(cfg: Dict[str, Any]) -> float:
    """The PQ head for one request: the sub-item score table S
    (2 * m * b * d/m) and one addition per split per catalogue item."""
    pq = cfg["pq"]
    return float(2 * pq["b"] * cfg["d_model"] + cfg["n_items"] * pq["m"])


def request_flops(cfg: Dict[str, Any], history_len: int) -> float:
    """Useful FLOPs of serving one request."""
    return (backbone_flops(cfg, real_positions(cfg, history_len))
            + head_flops(cfg))


def pqtopk_cost(batch: int, n_items: int, m: int, b: int, k: int,
                code_bytes: int) -> Dict[str, float]:
    """What one PQTopK call over ``n_items`` items for ``batch`` queries
    needs: ``batch * n_items * m`` score additions; the codes read once
    (n_items * m * code_bytes), the f32 score table S (batch * m * b * 4)
    and the top-k values and ids written (batch * k * 8)."""
    return {
        "ops": float(batch * n_items * m),
        "bytes": float(n_items * m * code_bytes + batch * m * b * 4
                       + batch * k * 8),
    }


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peaks: Dict[str, float]) -> Dict[str, Any]:
    """Least time the chip could take (the larger of ops at the bf16 peak
    and bytes at the HBM bandwidth) over the time taken, in %, and which
    of the two bounds it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"percent": 100.0 * max(t_ops, t_bytes) / seconds,
            "bound": "memory" if t_bytes >= t_ops else "compute"}
