"""Smoke test of the serving path on a TPU.

Serves SASRec-RecJPQ at the published Gowalla width of
``configs/sasrec_recjpq.py`` (d=512, 2 blocks, 200 positions,
N=1,271,638 items, m=8 splits of b=512 sub-ids, uint16 codes) through
``RetrievalEngine.for_seqrec``, with random weights from ``--seed``, and
checks every answer against the plain-XLA PQTopK route (Algorithm 1)
computed on the same chip.

    python chip_smoke.py                # one chip: fused + pruned routes
    python chip_smoke.py --four-chips   # item-sharded route on 4 chips

Phases (one chip):
  fused   the config's serve route (``pqtopk_fused``, the Pallas
          score + top-k kernel over every item tile); also prints the
          kernel's extraction share at the benchmark cells' shapes.
  pruned  ``pqtopk_pruned``: bounds, theta, compaction into a -1-padded
          tile table and the same kernel over the surviving tiles, under
          the calibrated slot-budget ladder.
Phase (``--four-chips``, and nothing else):
  sharded ``pqtopk_pruned`` with the catalogue split over a ("model",)
          mesh of 4 devices, against the one-device route in the same
          process.

Each phase warms the padding buckets 1 and 64, serves 64 seeded requests
with history lengths from 2 to 200, and fails on any mismatch, shed or
timed-out request, compile inside the serving window, or compiled serve
function without a ``tpu_custom_call`` (the Pallas kernel, not the
interpreter or an XLA fallback).  One line per phase is printed; the last
line, only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script exits non-zero, printing no result, when JAX finds no TPU.
Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "sasrec-recjpq"
N_REQUESTS = 64
BUCKETS = (1, 64)
K = 10
#: (users, k bucket) of the benchmark's steady and candgen cells.
SHARE_SHAPES = ((64, 16), (256, 128))


def make_histories(cfg, n: int, seed: int):
    """``n`` user histories, lengths uniform in [2, max_seq_len]."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.n_items + 1,
                         int(rng.integers(2, cfg.max_seq_len + 1)))
            for _ in range(n)]


def pad_histories(cfg, hists):
    """The (n, max_seq_len) int32 batch the engine builds: each history
    right-aligned, zero (the padding id) in front."""
    import numpy as np
    seqs = np.zeros((len(hists), cfg.max_seq_len), np.int32)
    for i, h in enumerate(hists):
        seqs[i, -len(h):] = h
    return seqs


def xla_reference(params, cfg, hists, k: int):
    """Top-k by the plain-XLA ``pqtopk`` route (Alg. 1: per-split score
    gathers, no Pallas) from the backbone output phi of the same
    histories. -> (ids (n, k), scores (n, k)) on the host."""
    import jax
    import numpy as np
    from repro.core import retrieval_head
    from repro.models import seqrec

    @jax.jit
    def ref(p, seqs):
        phi = seqrec.sequence_embedding(p, seqs, cfg)
        return retrieval_head.top_items(p["item_emb"], phi, k,
                                        method="pqtopk")

    vals, ids = ref(params, pad_histories(cfg, hists))
    return np.asarray(ids), np.asarray(vals)


def sentinel_table_check(params, cfg, hists, k: int):
    """The pruned route's kernel input shape, forced: a compacted table of
    every other codes tile, padded with ``-1`` sentinel slots, scored by
    the kernel and by the XLA gather path from the same S.  (A calibrated
    ladder on random weights may keep every tile, and then no sentinel
    slot reaches the kernel.) -> (slots, sentinel slots, mismatches)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import scoring
    from repro.kernels.pqtopk import kernel as pq_kernel, ops
    from repro.models import seqrec
    head = params["item_emb"]
    phi = jax.jit(seqrec.sequence_embedding, static_argnums=2)(
        params, pad_histories(cfg, hists), cfg)
    s = scoring.subid_scores(head["sub_emb"], phi)
    n = head["codes"].shape[0]
    n_tiles = ops.n_tiles(n, min(pq_kernel.DEFAULT_TILE, -(-n // 128) * 128))
    kept = np.arange(0, n_tiles, 2, dtype=np.int32)
    table = np.full(len(kept) + max(8, len(kept) // 4), -1, np.int32)
    table[:len(kept)] = kept
    got_v, got_i = ops.pq_topk_tiles(head["codes"], s, k, jnp.asarray(table),
                                     use_kernel=True)
    ref_v, ref_i = ops.pq_topk_tiles(head["codes"], s, k, jnp.asarray(table),
                                     use_kernel=False)
    bad = int(np.sum(~(np.all(np.asarray(got_i) == np.asarray(ref_i), 1)
                       & np.all(np.isclose(np.asarray(got_v),
                                           np.asarray(ref_v),
                                           rtol=1e-5, atol=1e-6), 1))))
    return len(table), len(table) - len(kept), bad


def extraction_shares(params, cfg, seed: int):
    """The fused kernel's extraction share (max-extract passes run over
    slots x batch tiles x K, ``ops.extraction_share``) at the benchmark
    cells' shapes: 64 users at k bucket 16 (online top-10) and 256 users
    at k bucket 128 (top-100 candidate generation). -> {shape: share}."""
    import jax
    from repro.core import scoring
    from repro.kernels.pqtopk import ops
    from repro.models import seqrec
    head = params["item_emb"]
    phi_fn = jax.jit(seqrec.sequence_embedding, static_argnums=2)
    shares = {}
    for bq, k in SHARE_SHAPES:
        phi = phi_fn(params, pad_histories(
            cfg, make_histories(cfg, bq, seed + bq)), cfg)
        s = scoring.subid_scores(head["sub_emb"], phi)
        shares[f"b{bq}_k{k}"] = float(ops.extraction_share(head["codes"], s,
                                                           k))
    return shares


def count_mismatches(results, ref_ids, ref_scores) -> int:
    """Requests whose ids differ from the reference or whose scores are
    not within f32 tolerance of it."""
    import numpy as np
    bad = 0
    for r, ids, sc in zip(results, ref_ids, ref_scores):
        if not (np.array_equal(r.items, ids)
                and np.allclose(r.scores, sc, rtol=1e-5, atol=1e-6)):
            bad += 1
    return bad


def serve(engine, hists, k: int):
    """Serve ``hists`` as one batch; -> (results in request order, wall
    ms from submit to the last result)."""
    from repro.serving.engine import Request
    t0 = time.perf_counter()
    for i, h in enumerate(hists):
        engine.submit(Request(i, h, k=k))
    results = sorted(engine.drain(), key=lambda r: r.request_id)
    return results, (time.perf_counter() - t0) * 1e3


def peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def check_engine(engine, hists, ref_ids, ref_scores, *, k: int,
                 expect_kernel: bool):
    """Warm, serve twice and check one engine. -> report dict."""
    t0 = time.perf_counter()
    engine.warmup(BUCKETS)
    compile_s = time.perf_counter() - t0
    n_compiles = int(engine.stats()["n_compiles"])
    results, first_ms = serve(engine, hists, k)
    again, warm_ms = serve(engine, hists, k)
    st = engine.stats()
    hlo = engine.compiled_hlo()
    rep = {
        "method": engine.method,
        "compile_s": compile_s,
        "first_batch_ms": first_ms,
        "warm_batch_ms": warm_ms,
        "n_compiles": int(st["n_compiles"]),
        "compiles_while_serving": int(st["n_compiles"]) - n_compiles,
        "served": len(results) + len(again),
        "mismatches": (count_mismatches(results, ref_ids, ref_scores)
                       + count_mismatches(again, ref_ids, ref_scores)),
        "shed": int(st["shed"]),
        "timeouts": int(st["timeouts"]),
        "kernel_in_every_variant": bool(hlo) and all(
            "tpu_custom_call" in t for t in hlo.values()),
    }
    if engine.ladder is not None:
        rep["ladder"] = list(engine.ladder)
        rep["rung_counts"] = {str(r): c for r, c in st["rung_counts"].items()}
    rep["ok"] = (rep["served"] == 2 * len(hists) and rep["mismatches"] == 0
                 and rep["shed"] == 0 and rep["timeouts"] == 0
                 and rep["compiles_while_serving"] == 0
                 and (rep["kernel_in_every_variant"] or not expect_kernel))
    return rep


def route_phase(params, cfg, method: str, hists, *, k: int = K,
                expect_kernel: bool = True, seed: int = 0):
    """One-chip phase: the engine on ``method`` against the XLA route."""
    import jax
    from repro.serving.engine import RetrievalEngine
    t0 = time.perf_counter()
    engine = RetrievalEngine.for_seqrec(params, cfg, k=k,
                                        max_batch=max(BUCKETS), method=method)
    build_s = time.perf_counter() - t0
    ref_ids, ref_scores = xla_reference(params, cfg, hists, k)
    rep = {"phase": method, "build_s": build_s,
           **check_engine(engine, hists, ref_ids, ref_scores, k=k,
                          expect_kernel=expect_kernel)}
    if method == "pqtopk_fused":
        rep["extraction_share"] = extraction_shares(params, cfg, seed)
    if method == "pqtopk_pruned":
        slots, sentinels, bad = sentinel_table_check(params, cfg, hists, k)
        rep.update(table_slots=slots, sentinel_slots=sentinels,
                   sentinel_table_mismatches=bad)
        rep["ok"] = rep["ok"] and bad == 0
    rep["peak_bytes_in_use"] = peak_bytes(jax.devices()[0])
    return rep


def sharded_phase(params, cfg, hists, devices, *, k: int = K,
                  expect_kernel: bool = True):
    """Item-sharded pruned route over a ("model",) mesh of ``devices``,
    against the one-device pruned route: the codes must sit as N/len
    rows on each device, and the ids must equal the one-device ids."""
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.serving.engine import RetrievalEngine
    mesh = make_mesh((len(devices),), ("model",), devices=devices)
    t0 = time.perf_counter()
    engine = RetrievalEngine.for_seqrec(
        params, cfg, k=k, max_batch=max(BUCKETS), method="pqtopk_pruned",
        sharded_mesh=mesh)
    build_s = time.perf_counter() - t0
    single = RetrievalEngine.for_seqrec(params, cfg, k=k,
                                        max_batch=max(BUCKETS),
                                        method="pqtopk_pruned")
    single.warmup((len(hists),))
    ref, _ = serve(single, hists, k)
    ref_ids = np.stack([r.items for r in ref])
    ref_scores = np.stack([r.scores for r in ref])
    codes = engine.data["item_emb"]["codes"]
    rows = {str(s.device): int(s.data.shape[0])
            for s in codes.addressable_shards}
    rep = {"phase": "sharded", "n_devices": len(devices),
           "build_s": build_s, "codes_rows": codes.shape[0],
           "rows_per_device": rows,
           **check_engine(engine, hists, ref_ids, ref_scores, k=k,
                          expect_kernel=expect_kernel)}
    rep["peak_bytes_in_use"] = [peak_bytes(d) for d in devices]
    rep["ok"] = rep["ok"] and (
        sorted(rows) == sorted(str(d) for d in devices)
        and set(rows.values()) == {codes.shape[0] // len(devices)})
    return rep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the item-sharded phase, on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.compile_cache import place_compile_cache
    except ImportError:
        print("chip_smoke: the repro package is not beside this script",
              file=sys.stderr)
        return 2
    import jax
    from repro.configs.base import get_config
    from repro.models import seqrec

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    n_chips = 4 if args.four_chips else 1
    if len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(json.dumps({"phase": "setup", "compile_cache":
                      place_compile_cache()}), flush=True)

    cfg = get_config(ARCH).model
    t0 = time.perf_counter()
    params = seqrec.init_seqrec(jax.random.PRNGKey(args.seed), cfg)
    jax.block_until_ready(params)
    print(json.dumps({"phase": "init", "arch": ARCH,
                      "n_items": cfg.n_items, "d_model": cfg.d_model,
                      "seconds": time.perf_counter() - t0}), flush=True)
    hists = make_histories(cfg, N_REQUESTS, args.seed)
    if args.four_chips:
        phases = [lambda: sharded_phase(params, cfg, hists, devices[:4])]
    else:
        phases = [lambda m=m: route_phase(params, cfg, m, hists,
                                          seed=args.seed)
                  for m in (cfg.serve_method, "pqtopk_pruned")]
    ok = True
    for phase in phases:
        try:
            rep = phase()
        except Exception:                       # noqa: BLE001 — reported
            traceback.print_exc()
            rep = {"phase": "error", "ok": False}
        ok = ok and rep["ok"]
        print(json.dumps(rep), flush=True)
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
