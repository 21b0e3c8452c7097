"""The system under test for the seqrec configurations: the program's
``RetrievalEngine.for_seqrec`` on its configured scoring route, over the
benchmark's weights."""
from __future__ import annotations

from typing import Any, Dict


def build(cfg: Dict[str, Any], traffic: Dict[str, Any], weights):
    from repro.configs.base import PQConfig, SeqRecConfig
    from repro.serving.engine import RetrievalEngine
    pq = cfg["pq"]
    model = SeqRecConfig(
        name=cfg["name"], backbone=cfg["backbone"], n_items=cfg["n_items"],
        d_model=cfg["d_model"], n_blocks=cfg["n_blocks"],
        n_heads=cfg["n_heads"], d_ff=cfg["d_ff"],
        max_seq_len=cfg["max_seq_len"],
        pq=PQConfig(m=pq["m"], b=pq["b"], code_dtype=pq["code_dtype"]),
        dtype=cfg["dtype"], param_dtype=cfg["dtype"],
        serve_method=cfg["serve_method"])
    return RetrievalEngine.for_seqrec(weights, model, k=traffic["k"],
                                      max_batch=traffic["max_batch"],
                                      method=cfg["serve_method"])


#: The serve program's jitted module, as the device trace names it.
STEP_MODULE = "jit_serve_fn"
#: The HLO instruction name of the fused PQTopK kernel's custom call.
KERNEL = "pq_topk"


def kernel_shapes(event) -> Dict[str, Any]:
    """B, N, m, b, k and the code width of one kernel call, read from the
    shapes in its HLO instruction: results ``(n_slots, B, k)`` twice, then
    operands tile index, codes ``(N, m)`` and the score table
    ``(B, m, b)``."""
    from bench.trace import shapes_of
    (_, (_, _, k)), _, _, (code_t, (n, m)), (_, (batch, _, b)) = \
        shapes_of(event.name.split(", custom_call_target")[0])[:5]
    return {"batch": batch, "n_items": n, "m": m, "b": b, "k": k,
            "code_bytes": int(code_t[1:]) // 8}
