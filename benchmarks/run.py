"""Benchmark orchestrator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  table3/*    — scoring + total mRT per (dataset, backbone, method) [Table 3]
  figure2/*   — scoring latency vs catalogue size, m in {8, 64}   [Fig. 2]
  kernel/*    — PQ scoring algorithm micro-bench (XLA paths) + the
                pruned-vs-exhaustive retrieval sweep on skewed data
  serving/*   — latency under load through the replicated fabric
                (ReplicaRouter, K in {1, 2, 4}, with/without a chaos
                plan): per-request p50/p99 + req/s end to end
  recovery/*  — durable catalogue log (WAL): append latency vs the
                fsync batching knob, recover() wall time vs replay-tail
                length, and the snapshot-cadence trade-off
  roofline/*  — dry-run roofline terms, if artifacts exist        [§Roofline]

and also writes a machine-readable ``BENCH_pr10.json`` (``--json PATH``) so
the perf trajectory is tracked across PRs: every row carries its section,
method tag, median us/call, items/s where defined, and extra tags (survival
fraction + seed size + bound backend + ladder / rung-hit fraction for the
pruned route, interpret-mode markers, ...).  Timed rows additionally carry
``q25_us``/``q75_us``/``iqr_us``/``n_reps`` so trend tooling can require
IQR separation before calling a regression (noise-robust comparisons).
The ``churn`` section measures the mutable catalogue: interleaved
update+query streaming at N=2^20 through the incrementally maintained
``MutableHeadState`` (stale-but-dominating bounds, tombstone mask), with
per-sample exactness checks against the exhaustive masked oracle.  The document also carries an
environment ``fingerprint`` (python/jax/jaxlib versions, backend, thread
pinning) so ``scripts/bench_compare.py`` can refuse joins of numbers
measured on different software stacks (``--allow-mixed`` overrides).
Rows measured through the Pallas interpreter (``"interpret": true``) time
the emulator, not the kernel — their ``items_per_s`` is null so they can
never enter throughput trend comparisons (see README §Benchmarks).

Full-scale sweeps (10^7+ items) are behind ``--full`` (CI keeps <= 10^6).
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.compile_cache import place_compile_cache


def environment_fingerprint() -> dict:
    """What was measured *on*: the software stack and thread pinning that
    make two benchmark numbers comparable.  Persisted into every BENCH
    json; ``scripts/bench_compare.py`` joins across PRs only when the
    fingerprints agree (identical dicts) or are absent (legacy files)."""
    import os
    import platform

    import jax as _jax
    import jaxlib as _jaxlib

    threads = {var: os.environ[var]
               for var in ("OMP_NUM_THREADS", "MKL_NUM_THREADS",
                           "OPENBLAS_NUM_THREADS", "XLA_FLAGS")
               if os.environ.get(var)}
    return {
        "python": platform.python_version(),
        "jax": _jax.__version__,
        "jaxlib": _jaxlib.__version__,
        "backend": _jax.default_backend(),
        "machine": platform.machine(),
        "cpu_count": os.cpu_count(),
        # The cores this process may actually run on (taskset pinning in
        # ci.sh shows up here): a 1-core and an 8-core affinity mask are
        # different machines as far as latency numbers are concerned.
        "cpu_affinity": (sorted(os.sched_getaffinity(0))
                         if hasattr(os, "sched_getaffinity") else None),
        # Unpinned thread counts are themselves provenance: two runs with
        # different pinning must not be joined silently.
        "threads": threads or "unpinned",
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip", action="append", default=[],
                    choices=["table3", "figure2", "kernel", "churn",
                             "serving", "recovery", "roofline", "hier"])
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--json", default="BENCH_pr10.json",
                    help="machine-readable output path ('' disables)")
    args = ap.parse_args(argv)
    place_compile_cache()

    rows = []

    def _emit(section: str, name: str, us: float | None, derived: str = "",
              *, method: str = "", items_per_s: float | None = None,
              tags: dict | None = None, timing: dict | None = None):
        us_s = f"{us:.1f}" if us is not None else "nan"
        print(f"{name},{us_s},{derived}")
        row = {"section": section, "name": name, "method": method,
               "median_us": us, "items_per_s": items_per_s,
               "tags": tags or {}}
        if timing is not None:
            # Variance alongside the median: trend tooling treats two
            # rows as distinguishable only when their IQR intervals
            # separate (scripts/bench_compare.py).
            row["q25_us"] = timing["q25_s"] * 1e6
            row["q75_us"] = timing["q75_s"] * 1e6
            row["iqr_us"] = timing["iqr_s"] * 1e6
            row["n_reps"] = timing["n_reps"]
        rows.append(row)

    print("name,us_per_call,derived")

    if "table3" not in args.skip:
        from benchmarks import table3
        datasets = ("booking", "gowalla") if args.full else ("booking",)
        # CI default keeps the 1.27M-item Gowalla build out (slow dense
        # reconstruction on host); --full reproduces the whole table.
        t3 = table3.run(repeats=args.repeats, datasets=datasets)
        for r in t3:
            _emit("table3",
                  f"table3/{r['dataset']}/{r['backbone']}/{r['method']}/scoring",
                  r["scoring_ms"] * 1e3,
                  f"total_ms={r['total_ms']:.2f};backbone_ms={r['backbone_ms']:.2f}",
                  method=r["method"],
                  tags={"total_ms": r["total_ms"],
                        "backbone_ms": r["backbone_ms"]},
                  timing=r.get("timing"))

    if "figure2" not in args.skip:
        from benchmarks import figure2
        f2 = figure2.run(full=args.full, repeats=args.repeats)
        for r in f2:
            us = None if r["scoring_ms"] is None else r["scoring_ms"] * 1e3
            tags = {"n_items": r["n_items"], "m": r["m"]}
            derived = ""
            if us is None:
                derived = ("interp-guard" if r["method"] == "pqtopk_fused"
                           else "mem-wall")
                tags["guard"] = derived
            if "survival_fraction" in r:
                tags["survival_fraction"] = r["survival_fraction"]
                derived = f"survival={r['survival_fraction']:.3f}"
            if "n_seed_used" in r:
                tags["n_seed_used"] = r["n_seed_used"]
            # Pruned rows are self-describing: backend + ladder + rung-hit
            # fraction travel with every row (None = no ladder in play).
            for tag in ("bound_backend", "ladder", "rung_hit_fraction"):
                if tag in r:
                    tags[tag] = r[tag]
            # Interpret-mode rows time the Pallas emulator, not the kernel
            # (the PR 2 figure2/m8/n10000/pqtopk_fused "anomaly" — 108 ms vs
            # 0.57 ms plain pqtopk, a 200x artefact of interpretation):
            # tag them and null items/s so trend tooling can never compare
            # them against compiled rows.
            interp = bool(r.get("interpret", False))
            if interp:
                tags["interpret"] = True
                derived = (derived + ";" if derived else "") + "interpret-mode"
            _emit("figure2", f"figure2/m{r['m']}/n{r['n_items']}/{r['method']}",
                  us, derived, method=r["method"],
                  items_per_s=(None if us is None or interp
                               else r["n_items"] / us * 1e6),
                  tags=tags, timing=r.get("timing"))

    if "kernel" not in args.skip:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from benchmarks.timing import time_fn
        from repro.core import pruning, scoring, topk as topk_lib
        rng = np.random.default_rng(0)
        n, m, b = 262_144, 8, 256
        codes = jnp.asarray(rng.integers(0, b, (n, m)), jnp.int32)
        s = jax.random.normal(jax.random.PRNGKey(0), (1, m, b))
        for name, alg in [("pqtopk", scoring.score_pqtopk),
                          ("recjpq", scoring.score_recjpq),
                          ("onehot", scoring.score_pqtopk_onehot)]:
            fn = jax.jit(alg)
            t = time_fn(lambda: fn(codes, s), repeats=args.repeats)
            _emit("kernel", f"kernel/pq_scoring_262k/{name}",
                  t["median_s"] * 1e6, f"items_per_s={n / t['median_s']:.3e}",
                  method=name, items_per_s=n / t["median_s"],
                  tags={"n_items": n}, timing=t)
        # Retrieval (scoring + top-k) comparison: XLA two-stage vs the fused
        # Pallas kernel, whose HBM output is O(B*K*N/TN) not O(B*N).
        from repro import compat
        from repro.kernels.pqtopk import ops as pq_ops
        k = 10
        fn = jax.jit(lambda c_, s_: topk_lib.tiled_topk(
            scoring.score_pqtopk(c_, s_), k))
        t = time_fn(lambda: fn(codes, s), repeats=args.repeats)
        _emit("kernel", "kernel/pq_retrieval_262k/pqtopk",
              t["median_s"] * 1e6, f"items_per_s={n / t['median_s']:.3e}",
              method="pqtopk", items_per_s=n / t["median_s"],
              tags={"n_items": n}, timing=t)
        t = time_fn(lambda: pq_ops.pq_topk(codes, s, k),
                    repeats=args.repeats)
        # Off TPU the fused kernel runs in interpret mode — the number times
        # the emulator, not the kernel; tag it and null items/s so it can't
        # enter throughput comparisons (README §Benchmarks).
        interp = not compat.on_tpu()
        tag = ";interpret-mode" if interp else ""
        _emit("kernel", "kernel/pq_retrieval_262k/pqtopk_fused",
              t["median_s"] * 1e6, f"items_per_s={n / t['median_s']:.3e}{tag}",
              method="pqtopk_fused",
              items_per_s=None if interp else n / t["median_s"],
              tags={"n_items": n, "interpret": interp}, timing=t)
        # Pruned-vs-exhaustive retrieval on skewed-score synthetic data
        # (N = 2^20): codes clustered by catalogue position (as after a
        # popularity-ordered RecJPQ assignment) + heavy-tailed sub-id
        # scores, the regime arXiv:2505.00560 targets.  Three exact
        # competitors: the exhaustive XLA route, the exhaustive fused route
        # (Pallas on TPU / its XLA lowering off TPU — compiled either way,
        # never the interpreter), and the single-dispatch in-graph cascade.
        # The PR 2 host two-pass cascade is kept as a fourth row so the
        # dispatch-fusion win is visible in the same file.
        n_sk, tile_sk = 1 << 20, 1024
        centers = (np.arange(n_sk) / n_sk * b).astype(np.int64)
        codes_sk = jnp.asarray(
            (centers[:, None] + rng.integers(-1, 2, (n_sk, m))) % b,
            jnp.int32)
        g = rng.standard_normal((1, m, b))
        s_sk = jnp.asarray(np.sign(g) * np.abs(g) ** 3, jnp.float32)
        fn_ex = jax.jit(lambda c_, s_: topk_lib.tiled_topk(
            scoring.score_pqtopk(c_, s_), k))
        t = time_fn(lambda: fn_ex(codes_sk, s_sk), repeats=args.repeats)
        _emit("kernel", "kernel/pq_retrieval_1m_skewed/pqtopk",
              t["median_s"] * 1e6, f"items_per_s={n_sk / t['median_s']:.3e}",
              method="pqtopk", items_per_s=n_sk / t["median_s"],
              tags={"n_items": n_sk, "skewed": True}, timing=t)
        # Exhaustive fused: identity tile list through pq_topk_tiles — the
        # same compacted-scoring entry the cascade uses, with zero pruning.
        ident = jnp.arange(pq_ops.n_tiles(n_sk, tile_sk), dtype=jnp.int32)
        fn_fx = jax.jit(lambda c_, s_: pq_ops.pq_topk_tiles(
            c_, s_, k, ident, tile=tile_sk))
        t = time_fn(lambda: fn_fx(codes_sk, s_sk), repeats=args.repeats)
        _emit("kernel", "kernel/pq_retrieval_1m_skewed/pqtopk_fused",
              t["median_s"] * 1e6, f"items_per_s={n_sk / t['median_s']:.3e}",
              method="pqtopk_fused", items_per_s=n_sk / t["median_s"],
              tags={"n_items": n_sk, "skewed": True, "tile": tile_sk,
                    "lowering": "pallas" if compat.on_tpu() else "xla"},
              timing=t)
        # Bound-backend comparison sweep: the single-dispatch in-graph
        # cascade (adaptive theta seeding, CALIBRATED slot-budget ladder)
        # for both metadata backends at N=2^20 skewed, on two code
        # layouts: "wrap" (the legacy `% b` synthetic — its handful of
        # full-span wrap tiles are the range backend's worst case: the
        # convex hull of {0, .., b-1} is everything, bounds go loose and
        # theta seeding wastes its budget there) and "clip" (clipped
        # clustered codes — RecJPQ's popularity-ordered assignment never
        # wraps, the regime the range backend targets).  Per (layout,
        # backend): metadata bytes, bound tightness (survival fraction),
        # items/s through the ladder, and the rung-hit fraction over a
        # stream of fresh query batches with a per-batch exactness check
        # against the exhaustive oracle (zero correctness loss, counted).
        def fresh_s(i):
            gg = np.random.default_rng(1000 + i).standard_normal((1, m, b))
            return jnp.asarray(np.sign(gg) * np.abs(gg) ** 3, jnp.float32)

        codes_clip = jnp.asarray(
            np.clip(centers[:, None] + rng.integers(-1, 2, (n_sk, m)),
                    0, b - 1), jnp.int32)
        n_cal, n_stream = 5, 12
        for layout, codes_l in (("wrap", codes_sk), ("clip", codes_clip)):
            fn_ex_l = jax.jit(lambda c_, s_: topk_lib.tiled_topk(
                scoring.score_pqtopk(c_, s_), k))
            backend_rows = {}
            suffix = "" if layout == "wrap" else "_clip"
            for backend in pruning.BOUND_BACKENDS:
                state = pruning.build_pruned_state(codes_l, b, tile_sk,
                                                   backend=backend)
                count_fn = jax.jit(
                    lambda s_, c_=codes_l, st_=state: pruning.survival_count(
                        c_, s_, k, st_, seed_policy="adaptive"))
                counts = [int(count_fn(fresh_s(i))) for i in range(n_cal)]
                ladder = pruning.calibrate_ladder(counts, state.n_tiles, k,
                                                  state.tile)

                # One jitted variant returning the rung alongside the
                # winners (same trick as the serve path) — the stream
                # below scores each batch exactly once.
                def _pr(c_, s_, st_=state, ld_=ladder):
                    v_, i_, stats_ = pruning.cascade_topk_ingraph(
                        c_, s_, k, st_, seed_policy="adaptive",
                        ladder=ld_, return_stats=True)
                    return v_, i_, stats_["rung_hit"]

                fn_pr = jax.jit(_pr)
                n_rungs = len(ladder)       # calibrate_ladder output is
                hits = mismatches = 0       # already normalized
                for i in range(n_stream):
                    s_i = fresh_s(n_cal + i)
                    v_pr, i_pr, rung_i = fn_pr(codes_l, s_i)
                    hits += int(int(rung_i) < n_rungs - 1)
                    v_ex, i_ex = fn_ex_l(codes_l, s_i)
                    mismatches += int(
                        not (np.array_equal(np.asarray(v_pr),
                                            np.asarray(v_ex))
                             and np.array_equal(np.asarray(i_pr),
                                                np.asarray(i_ex))))
                _, _, stats = pruning.cascade_topk_ingraph(
                    codes_l, s_sk, k, state, seed_policy="adaptive",
                    ladder=ladder, return_stats=True)
                stats = {kk: vv.item() if hasattr(vv, "item") else vv
                         for kk, vv in stats.items()}
                t = time_fn(lambda: fn_pr(codes_l, s_sk),
                            repeats=args.repeats)
                backend_rows[backend] = (stats, state)
                _emit("kernel",
                      f"kernel/pq_retrieval_1m_skewed/"
                      f"pqtopk_pruned_{backend}{suffix}",
                      t["median_s"] * 1e6,
                      f"items_per_s={n_sk / t['median_s']:.3e};"
                      f"survival={stats['survival_fraction']:.4f};"
                      f"meta_bytes={state.nbytes};ladder={ladder};"
                      f"rung_hit={hits}/{n_stream};"
                      f"mismatches={mismatches}",
                      method="pqtopk_pruned",
                      items_per_s=n_sk / t["median_s"],
                      tags={"n_items": n_sk, "skewed": True,
                            "tile": tile_sk, "code_layout": layout,
                            "bound_backend": backend,
                            "survival_fraction":
                                stats["survival_fraction"],
                            "n_survived": stats["n_survived"],
                            "n_tiles": stats["n_tiles"],
                            "n_seed_used": stats["n_seed_used"],
                            "seed_policy": "adaptive",
                            "ladder": list(ladder),
                            "rung_hit_fraction": hits / n_stream,
                            "exactness_mismatches": mismatches,
                            "stream_batches": n_stream,
                            "dispatches_per_query": 1,
                            "meta_bytes": state.nbytes,
                            "meta_bytes_bool_pr2": state.bool_nbytes},
                      timing=t)
            # Headline deltas per layout: metadata footprint ratio and
            # bound-tightness loss (range survival - bitmask survival).
            st_bm, meta_bm = backend_rows["bitmask"]
            st_rg, meta_rg = backend_rows["range"]
            _emit("kernel",
                  f"kernel/pq_retrieval_1m_skewed/backend_delta{suffix}",
                  None,
                  f"meta_ratio={meta_rg.nbytes / meta_bm.nbytes:.3f};"
                  f"survival_delta="
                  f"{st_rg['survival_fraction'] - st_bm['survival_fraction']:.4f}",
                  method="backend_delta",
                  tags={"n_items": n_sk, "skewed": True,
                        "code_layout": layout,
                        "meta_bytes_bitmask": meta_bm.nbytes,
                        "meta_bytes_range": meta_rg.nbytes,
                        "meta_ratio_range_over_bitmask":
                            meta_rg.nbytes / meta_bm.nbytes,
                        "survival_fraction_bitmask":
                            st_bm["survival_fraction"],
                        "survival_fraction_range":
                            st_rg["survival_fraction"],
                        "survival_fraction_delta":
                            st_rg["survival_fraction"]
                            - st_bm["survival_fraction"]})
        t = time_fn(lambda: pruning.cascade_topk(codes_sk, s_sk, k,
                                                 tile=tile_sk),
                    repeats=args.repeats)
        _emit("kernel", "kernel/pq_retrieval_1m_skewed/pqtopk_pruned_host",
              t["median_s"] * 1e6,
              f"items_per_s={n_sk / t['median_s']:.3e};host-two-pass",
              method="pqtopk_pruned_host", items_per_s=n_sk / t["median_s"],
              tags={"n_items": n_sk, "skewed": True, "tile": tile_sk,
                    "bound_backend": "bitmask", "ladder": None,
                    "rung_hit_fraction": None,
                    "dispatches_per_query": 2}, timing=t)
        # ---------------------------------------------------------------
        # Mixed-batch per-query sweep (PR 5 headline): N=2^20 clipped
        # clustered codes, B in {8, 64, 256} queries whose score skew
        # concentrates on DIFFERENT code windows — so each query's
        # survivor set is a different catalogue region and the batch-any
        # union degrades toward exhaustive as B grows (the regime the
        # per-query grouped route exists for).  Per B, two single-
        # dispatch pruned routes are measured with their own calibrated
        # ladders: the batch-any union route and the query-grouped route
        # (G=8, per-query thetas, 2D (group, slot) table).  Reported:
        # items/s, scored slot·query pairs (grouped sum_g B_g*S_g vs the
        # union B*|union| — the headline ratio), per-group vs union
        # survival, and a per-batch exactness check against the chunked
        # exhaustive oracle for EVERY pruned row (zero tolerance).
        state_mx = pruning.build_pruned_state(codes_clip, b, tile_sk)
        n_groups_mx = 8

        def mixed_s(bq, i):
            rr = np.random.default_rng(5000 + 131 * bq + i)
            gg = rr.standard_normal((bq, m, b))
            gg = np.sign(gg) * np.abs(gg) ** 3
            for q in range(bq):
                w = (q * b) // bq
                gg[q, :, max(0, w - 1):w + 3] += 6.0
            return jnp.asarray(gg, jnp.float32)

        def oracle_chunked(s_b, chunk=16):
            outs = []
            for lo in range(0, s_b.shape[0], chunk):
                outs.append(fn_ex(codes_clip, s_b[lo:lo + chunk]))
            return (jnp.concatenate([o[0] for o in outs]),
                    jnp.concatenate([o[1] for o in outs]))

        fn_ex = jax.jit(lambda c_, s_: topk_lib.tiled_topk(
            scoring.score_pqtopk(c_, s_), k))
        n_cal_mx, n_stream_mx = 2, 2
        for bq_mx in (8, 64, 256):
            route_rows = {}
            # The stream batches (and hence their exhaustive oracles) are
            # identical for both routes — compute each oracle once, not
            # once per route (it is the most expensive part of the sweep).
            stream = [mixed_s(bq_mx, n_cal_mx + i)
                      for i in range(n_stream_mx)]
            oracles = [oracle_chunked(s_i) for s_i in stream]
            for grouping in (False, True):
                tag = "grouped" if grouping else "batchany"
                if grouping:
                    count_fn = jax.jit(lambda s_: pruning.survival_count_grouped(
                        codes_clip, s_, k, state_mx, n_groups=n_groups_mx,
                        seed_tiles=4))
                else:
                    count_fn = jax.jit(lambda s_: pruning.survival_count(
                        codes_clip, s_, k, state_mx, seed_tiles=4))
                counts = [int(count_fn(mixed_s(bq_mx, i)))
                          for i in range(n_cal_mx)]
                ladder = pruning.calibrate_ladder(counts, state_mx.n_tiles,
                                                  k, state_mx.tile)

                def _pr(s_, grouping=grouping, ladder=ladder):
                    v_, i_, st_ = pruning.cascade_topk_ingraph(
                        codes_clip, s_, k, state_mx, seed_tiles=4,
                        query_grouping=grouping, n_groups=n_groups_mx,
                        ladder=ladder, return_stats=True)
                    # jit outputs must be arrays: keep the numeric stats.
                    num = {kk: st_[kk] for kk in
                           ("pairs_scored", "pairs_union", "n_survived",
                            "max_group_survived", "survival_fraction",
                            "rung_hit", "n_groups")}
                    return v_, i_, num

                fn_pr = jax.jit(_pr)
                mismatches = 0
                pairs_scored = pairs_union = 0
                for i in range(n_stream_mx):
                    s_i = stream[i]
                    v_pr, i_pr, st_i = fn_pr(s_i)
                    v_ex, i_ex = oracles[i]
                    mismatches += int(
                        not (np.array_equal(np.asarray(v_pr),
                                            np.asarray(v_ex))
                             and np.array_equal(np.asarray(i_pr),
                                                np.asarray(i_ex))))
                    pairs_scored += int(st_i["pairs_scored"])
                    pairs_union += int(st_i["pairs_union"])
                # Time a HELD-OUT batch (neither calibration nor stream):
                # timing a batch the ladder was calibrated on would
                # guarantee a fitted rung and overstate throughput.
                s_t = mixed_s(bq_mx, n_cal_mx + n_stream_mx)
                v_, i_, st = fn_pr(s_t)
                st = {kk: vv.item() if hasattr(vv, "item") else vv
                      for kk, vv in st.items()}
                t = time_fn(lambda: fn_pr(s_t), repeats=args.repeats)
                ips = bq_mx * n_sk / t["median_s"]
                route_rows[tag] = (st, pairs_scored, pairs_union)
                _emit("kernel",
                      f"kernel/pq_retrieval_1m_mixed/B{bq_mx}/"
                      f"pqtopk_pruned_{tag}",
                      t["median_s"] * 1e6,
                      f"items_per_s={ips:.3e};"
                      f"pairs={pairs_scored}/{pairs_union};"
                      f"union_survival={st['survival_fraction']:.4f};"
                      f"max_group={st['max_group_survived']};"
                      f"ladder={ladder};mismatches={mismatches}",
                      method="pqtopk_pruned",
                      items_per_s=ips,
                      tags={"n_items": n_sk, "B": bq_mx, "mixed": True,
                            "tile": tile_sk, "grouping": tag,
                            "n_groups": st["n_groups"],
                            "bound_backend": "bitmask",
                            "survival_fraction": st["survival_fraction"],
                            "n_survived": st["n_survived"],
                            "max_group_survived": st["max_group_survived"],
                            "pairs_scored": pairs_scored,
                            "pairs_union": pairs_union,
                            "ladder": list(ladder),
                            "exactness_mismatches": mismatches,
                            "stream_batches": n_stream_mx,
                            "dispatches_per_query": 1}, timing=t)
            st_g, pg, pu = route_rows["grouped"]
            st_a, pa, _ = route_rows["batchany"]
            _emit("kernel",
                  f"kernel/pq_retrieval_1m_mixed/B{bq_mx}/grouping_delta",
                  None,
                  f"pairs_grouped={pg};pairs_batchany={pa};"
                  f"pair_ratio={pg / max(pa, 1):.3f};"
                  f"max_group={st_g['max_group_survived']}"
                  f"/union={st_a['n_survived']}",
                  method="grouping_delta",
                  tags={"n_items": n_sk, "B": bq_mx, "mixed": True,
                        "pairs_grouped": pg, "pairs_batchany": pa,
                        "pair_ratio_grouped_over_batchany":
                            pg / max(pa, 1),
                        "union_survived": st_a["n_survived"],
                        "max_group_survived":
                            st_g["max_group_survived"]})

    if "churn" not in args.skip:
        # -------------------------------------------------------------
        # Streaming catalogue mutation at N=2^20 (ISSUE 7 headline):
        # interleaved update+query through the incrementally maintained
        # MutableHeadState — queries run against STALE (loosened) bounds
        # plus the tombstone mask and must stay bit-exact vs the
        # exhaustive masked oracle; the section reports the mutation
        # cost, the stale-vs-fresh query latency gap (the price of
        # degradation), and the retighten cost that closes it.
        import jax
        import jax.numpy as jnp
        import numpy as np
        from benchmarks.timing import time_fn
        from repro.core import pruning, scoring, topk as topk_lib
        from repro.core.mutation import MutableHeadState

        rng_ch = np.random.default_rng(42)
        n_ch, m_ch, b_ch, tile_ch, k_ch = 1 << 20, 8, 256, 1024, 10
        centers_ch = (np.arange(n_ch) / n_ch * b_ch).astype(np.int64)
        codes_ch = jnp.asarray(
            np.clip(centers_ch[:, None]
                    + rng_ch.integers(-1, 2, (n_ch, m_ch)), 0, b_ch - 1),
            jnp.int32)
        g_ch = rng_ch.standard_normal((1, m_ch, b_ch))
        s_ch = jnp.asarray(np.sign(g_ch) * np.abs(g_ch) ** 3, jnp.float32)

        oracle_ch = jax.jit(lambda c_, lv_, s_: topk_lib.tiled_topk(
            jnp.where(lv_[None, :], scoring.score_pqtopk(c_, s_),
                      -jnp.inf), k_ch))

        def fresh_row():
            return jnp.asarray(rng_ch.integers(0, b_ch, m_ch), jnp.int32)

        for backend in pruning.BOUND_BACKENDS:
            mstate = MutableHeadState.build(codes_ch, b_ch, tile_ch,
                                            backend=backend)
            # Head arrays enter as traced ARGUMENTS — the same data-not-
            # constants contract the hot-swap engine compiles against.
            cascade_ch = jax.jit(
                lambda c_, lv_, st_, s_: pruning.cascade_topk_ingraph(
                    c_, s_, k_ch, st_, live=lv_)[:2])

            # Mutation cost (update = tombstone-free absorb + staleness).
            victims = rng_ch.integers(1, n_ch, 64)
            vi = iter(np.tile(victims, 100))
            t_mut = time_fn(
                lambda: mstate.update(int(next(vi)), fresh_row()),
                repeats=max(args.repeats * 4, 16), warmup=4)
            _emit("churn", f"churn/1m/update_{backend}",
                  t_mut["median_s"] * 1e6,
                  f"mutations_per_s={1 / t_mut['median_s']:.3e}",
                  method="mutation_update",
                  tags={"n_items": n_ch, "capacity": mstate.cap,
                        "tile": tile_ch, "bound_backend": backend},
                  timing=t_mut)

            # Interleaved stream: update -> query, exactness-checked.
            n_pairs, mismatches = 8, 0
            for i in range(n_pairs):
                if i % 4 == 3:
                    mstate.delete(int(rng_ch.integers(9, n_ch)))
                else:
                    mstate.update(1 + i, fresh_row())
                gg = np.random.default_rng(7000 + i).standard_normal(
                    (1, m_ch, b_ch))
                s_i = jnp.asarray(np.sign(gg) * np.abs(gg) ** 3,
                                  jnp.float32)
                ha = mstate.head_arrays()
                v_pr, i_pr = cascade_ch(ha["codes"], ha["live"],
                                        ha["pruned"], s_i)
                v_ex, i_ex = oracle_ch(ha["codes"], ha["live"], s_i)
                mismatches += int(
                    not (np.array_equal(np.asarray(v_pr),
                                        np.asarray(v_ex))
                         and np.array_equal(np.asarray(i_pr),
                                            np.asarray(i_ex))))

            # Query latency on the now-stale state vs after retighten.
            ha = mstate.head_arrays()
            stats_stale = mstate.stats()
            t_stale = time_fn(lambda: cascade_ch(ha["codes"], ha["live"],
                                                 ha["pruned"], s_ch),
                              repeats=args.repeats)
            _emit("churn", f"churn/1m/query_stale_{backend}",
                  t_stale["median_s"] * 1e6,
                  f"items_per_s={n_ch / t_stale['median_s']:.3e};"
                  f"stale_tiles={int(stats_stale['stale_tiles'])};"
                  f"mismatches={mismatches}",
                  method="pqtopk_pruned",
                  items_per_s=n_ch / t_stale["median_s"],
                  tags={"n_items": n_ch, "bound_backend": backend,
                        "tile": tile_ch, "churned": True,
                        "stale_tiles": stats_stale["stale_tiles"],
                        "n_mutations": stats_stale["n_mutations"],
                        "exactness_mismatches": mismatches,
                        "stream_pairs": n_pairs,
                        "dispatches_per_query": 1},
                  timing=t_stale)

            t_ret = time_fn(lambda: mstate.retighten() or None,
                            repeats=1, warmup=0)
            ha = mstate.head_arrays()
            t_fresh = time_fn(lambda: cascade_ch(ha["codes"], ha["live"],
                                                 ha["pruned"], s_ch),
                              repeats=args.repeats)
            _emit("churn", f"churn/1m/query_fresh_{backend}",
                  t_fresh["median_s"] * 1e6,
                  f"items_per_s={n_ch / t_fresh['median_s']:.3e};"
                  f"retighten_us={t_ret['median_s'] * 1e6:.1f}",
                  method="pqtopk_pruned",
                  items_per_s=n_ch / t_fresh["median_s"],
                  tags={"n_items": n_ch, "bound_backend": backend,
                        "tile": tile_ch, "churned": False,
                        "retighten_us": t_ret["median_s"] * 1e6,
                        "dispatches_per_query": 1},
                  timing=t_fresh)

    if "serving" not in args.skip:
        # -------------------------------------------------------------
        # Latency under load through the replicated fabric (ISSUE 8):
        # the same request stream through ReplicaRouter with K replicas,
        # healthy and under a deterministic chaos plan.  Per config:
        # per-request latency quartiles (the row's timing dict), req/s,
        # and the fabric counters (hedges, re-dispatches, sheds) so the
        # robustness cost is visible next to the latency it buys.
        import time as time_lib
        from dataclasses import replace as _replace

        import jax
        import numpy as np
        from repro.configs.base import get_reduced
        from repro.models import seqrec as seqrec_lib
        from repro.serving.engine import Request
        from repro.serving.router import ReplicaRouter
        from repro.training.fault_tolerance import ReplicaFaultPlan

        arch_srv = get_reduced("sasrec-recjpq")
        cfg_srv = _replace(arch_srv.model, n_items=8192)
        params_srv = seqrec_lib.init_seqrec(jax.random.PRNGKey(0), cfg_srv)
        n_req, mb_srv, k_srv = 192, 8, 10
        ladder_srv = None
        for n_rep in (1, 2, 4):
            for chaos in (False, True):
                if chaos and n_rep == 1:
                    continue            # replica-level chaos needs spares
                plans = ({1: ReplicaFaultPlan(crash_windows=((2, 5),))}
                         if chaos else None)
                router = ReplicaRouter.for_seqrec(
                    params_srv, cfg_srv, n_replicas=n_rep, k=k_srv,
                    max_batch=mb_srv, method="pqtopk_pruned",
                    ladder=ladder_srv, calibrate=ladder_srv is None,
                    fault_plans=plans, hedge=n_rep > 1)
                ladder_srv = router.engines[0].ladder
                rng_srv = np.random.default_rng(0)
                with router:
                    # Warm every pow2 padding bucket the trickle can form:
                    # a lazy compile inside the timed stream would read as
                    # a multi-second straggler and poison the p99.
                    router.warmup(buckets=tuple(
                        2 ** j for j in range(mb_srv.bit_length())))
                    t0 = time_lib.monotonic()
                    for i in range(n_req):
                        seq = rng_srv.integers(1, cfg_srv.n_items + 1, 16)
                        router.submit(Request(i, seq, k=k_srv))
                        router.pump()
                    res = router.drain()
                    wall = time_lib.monotonic() - t0
                    st_r = router.stats()
                assert len(res) == n_req, f"lost {n_req - len(res)} requests"
                lat_s = np.sort(np.asarray([r.latency_ms for r in res])) / 1e3
                q25, med, q75 = np.quantile(lat_s, (0.25, 0.5, 0.75))
                timing = {"median_s": med, "q25_s": q25, "q75_s": q75,
                          "iqr_s": q75 - q25, "n_reps": len(res)}
                n_shed = sum(1 for r in res if r.shed)
                ej = sum(r_["ejections"] for r_ in st_r["replicas"].values())
                re_ad = sum(r_["readmissions"]
                            for r_ in st_r["replicas"].values())
                suffix = "_chaos" if chaos else ""
                _emit("serving",
                      f"serving/load_K{n_rep}{suffix}/pqtopk_pruned",
                      med * 1e6,
                      f"req_per_s={n_req / wall:.1f};"
                      f"p99_ms={st_r['p99_ms']:.2f};"
                      f"hedges={int(st_r['hedges'])};"
                      f"redispatched={int(st_r['redispatched'])};"
                      f"shed={n_shed}",
                      method="pqtopk_pruned",
                      items_per_s=cfg_srv.n_items * (n_req - n_shed) / wall,
                      tags={"n_items": cfg_srv.n_items,
                            "n_replicas": n_rep, "chaos": chaos,
                            "n_requests": n_req, "max_batch": mb_srv,
                            "req_per_s": n_req / wall,
                            "p50_ms": st_r["p50_ms"],
                            "p99_ms": st_r["p99_ms"],
                            "hedges": int(st_r["hedges"]),
                            "hedge_wins": int(st_r["hedge_wins"]),
                            "redispatched": int(st_r["redispatched"]),
                            "duplicates_suppressed":
                                int(st_r["duplicates_suppressed"]),
                            "shed": n_shed,
                            "degrade_events": int(st_r["degrade_events"]),
                            "ejections": ej, "readmissions": re_ad,
                            "ladder": (list(ladder_srv)
                                       if ladder_srv else None)},
                      timing=timing)

    if "recovery" not in args.skip:
        # -------------------------------------------------------------
        # Durable catalogue log (ISSUE 10): what durability costs and
        # what recovery costs.  Three knobs, each a row family:
        #   * append latency vs fsync_every — the fsync amortization
        #     curve (fsync_every=1 is the durability ceiling, larger
        #     groups trade a bounded loss window for throughput);
        #   * recover() wall time vs tail length — snapshot restore +
        #     LSN-ordered replay, the crash-restart cost a stale replica
        #     or a restarted router actually pays;
        #   * snapshot cadence — the cost of cutting an LSN-keyed
        #     snapshot and the recover-time reduction it buys.
        import shutil
        import tempfile

        import numpy as np
        from benchmarks.timing import time_fn
        from repro.core.mutation import MutableHeadState, apply_op
        from repro.serving.catalogue_log import CatalogueLog

        rng_rc = np.random.default_rng(11)
        n_rc, m_rc, b_rc, tile_rc = 4096, 8, 256, 64
        codes_rc = rng_rc.integers(0, b_rc, (n_rc, m_rc)).astype(np.int32)

        def _mk_rc():
            return MutableHeadState.build(codes_rc, b_rc, tile_rc)

        def _ops_rc(mstate, n):
            ops = []
            for _ in range(n):
                live = np.where(np.asarray(mstate.live))[0]
                live = live[live > 0]
                row = rng_rc.integers(0, b_rc, m_rc).astype(np.int32)
                r = rng_rc.random()
                if (r < 0.3 and (mstate.free or mstate.n_rows < mstate.cap)) \
                        or live.size <= 1:
                    op = ("insert", row)
                elif r < 0.65:
                    op = ("delete", int(rng_rc.choice(live)))
                else:
                    op = ("update", int(rng_rc.choice(live)), row)
                apply_op(mstate, op)
                ops.append(op)
            return ops

        base_rc = _mk_rc()
        ops_pool = _ops_rc(base_rc.clone(), 1024)

        for fsync_every in (1, 8, 64):
            d = tempfile.mkdtemp(prefix="bench_wal_")
            log = CatalogueLog(d, fsync_every=fsync_every)
            it = iter(ops_pool * 8)
            t = time_fn(lambda: log.append(next(it)),
                        repeats=max(args.repeats * 64, 256), warmup=8)
            st_log = log.stats()
            log.close()
            shutil.rmtree(d)
            _emit("recovery", f"recovery/append/fsync{fsync_every}",
                  t["median_s"] * 1e6,
                  f"appends_per_s={1 / t['median_s']:.3e};"
                  f"log_bytes={int(st_log['log_bytes'])}",
                  method="wal_append",
                  items_per_s=1 / t["median_s"],
                  tags={"fsync_every": fsync_every,
                        "n_items": n_rc,
                        "log_bytes": int(st_log["log_bytes"]),
                        "n_fsyncs": int(st_log["n_fsyncs"])},
                  timing=t)

        # recover() = newest snapshot + tail replay: sweep the tail.
        for tail_len in (0, 256, 1024):
            d = tempfile.mkdtemp(prefix="bench_wal_")
            mstate = _mk_rc()
            with CatalogueLog(d, fsync_every=64) as log:
                log.snapshot(mstate)            # genesis at lsn 0
                for op in ops_pool[:tail_len]:
                    log.append(op)
                log.sync()
                t = time_fn(lambda: log.recover(), repeats=args.repeats,
                            warmup=1)
                _, lsn = log.recover()
                assert lsn == tail_len
            shutil.rmtree(d)
            _emit("recovery", f"recovery/recover/tail{tail_len}",
                  t["median_s"] * 1e6,
                  f"ops_replayed={tail_len};"
                  f"ops_per_s={tail_len / t['median_s']:.3e}"
                  if tail_len else "snapshot-only",
                  method="wal_recover",
                  items_per_s=(tail_len / t["median_s"]
                               if tail_len else None),
                  tags={"tail_len": tail_len, "n_items": n_rc,
                        "capacity": mstate.cap}, timing=t)

        # Snapshot cadence: amortized snapshot cost vs the recover-time
        # reduction it buys (0 = genesis-only, the full-replay extreme).
        for snap_every in (0, 128, 512):
            d = tempfile.mkdtemp(prefix="bench_wal_")
            mstate = _mk_rc()
            with CatalogueLog(d, fsync_every=64,
                              snapshot_every=snap_every) as log:
                log.snapshot(mstate)
                t_snap = None
                for op in ops_pool:
                    log.append(op)
                    apply_op(mstate, op)
                    if log.maybe_snapshot(mstate) is not None \
                            and t_snap is None:
                        # time one representative snapshot cut
                        t_snap = time_fn(lambda: log.snapshot(mstate),
                                         repeats=max(args.repeats, 3),
                                         warmup=0)
                log.sync()
                n_snaps = int(log.stats()["n_snapshots"])
                t_rec = time_fn(lambda: log.recover(),
                                repeats=args.repeats, warmup=1)
            shutil.rmtree(d)
            _emit("recovery", f"recovery/cadence/snap{snap_every}",
                  t_rec["median_s"] * 1e6,
                  f"n_snapshots={n_snaps};"
                  + (f"snapshot_us={t_snap['median_s'] * 1e6:.0f}"
                     if t_snap else "genesis-only"),
                  method="wal_cadence",
                  items_per_s=len(ops_pool) / t_rec["median_s"],
                  tags={"snapshot_every": snap_every,
                        "n_snapshots": n_snaps, "n_items": n_rc,
                        "stream_len": len(ops_pool),
                        "snapshot_us": (t_snap["median_s"] * 1e6
                                        if t_snap else None)},
                  timing=t_rec)

    if "hier" not in args.skip:
        # -------------------------------------------------------------
        # Hierarchical super-tile cascade at very large N (ISSUE 9
        # tentpole): flat vs hierarchical pruned cascade on a
        # popularity-sorted tile-coherent catalogue, bit-checked against
        # the streaming one-shot oracle.  Reports the pass-1 bound-work
        # reduction (the acceptance bar is >= 10x at N=2^24 with zero
        # mismatches) and the peak-RSS ceiling of the run.  N=2^27 only
        # under --full (1 GB of codes).
        import importlib.util as _ilu
        _spec = _ilu.spec_from_file_location("billion_item_sim",
                                             "examples/billion_item_sim.py")
        _sim = _ilu.module_from_spec(_spec)
        _spec.loader.exec_module(_sim)
        hier_ns = [1 << 24] + ([1 << 27] if args.full else [])
        for n_h in hier_ns:
            for backend_h in ("bitmask", "range"):
                r = _sim.run_hier_compare(n_h, repeats=args.repeats,
                                          backend=backend_h)
                tags_h = {"n_items": r["n_items"], "m": r["m"],
                          "bound_backend": backend_h, "hier": True,
                          "super_tile": r["super_factor"],
                          "n_tiles": r["n_tiles"],
                          "n_super": r["n_super"],
                          "flat_bounds": r["flat_bounds"],
                          "hier_bounds": r["hier_bounds"],
                          "bound_reduction": r["bound_reduction"],
                          "mismatches": r["mismatches"],
                          "peak_rss_mb": r["peak_rss_mb"]}
                _emit("hier",
                      f"hier/n{r['n_items']}/{backend_h}/super",
                      r["hier_s"] * 1e6,
                      f"flat_us={r['flat_s'] * 1e6:.0f};"
                      f"bound_reduction={r['bound_reduction']:.1f}x;"
                      f"mismatches={r['mismatches']};"
                      f"peak_rss_mb={r['peak_rss_mb']:.0f}",
                      method="pruned_hier",
                      items_per_s=r["n_items"] / max(r["hier_s"], 1e-9),
                      tags=tags_h)
                _emit("hier",
                      f"hier/n{r['n_items']}/{backend_h}/flat",
                      r["flat_s"] * 1e6,
                      f"bounds={r['flat_bounds']}",
                      method="pruned_flat",
                      items_per_s=r["n_items"] / max(r["flat_s"], 1e-9),
                      tags={"n_items": r["n_items"], "m": r["m"],
                            "bound_backend": backend_h, "hier": False,
                            "n_tiles": r["n_tiles"]})

    if "roofline" not in args.skip:
        import os
        from benchmarks import roofline
        art = "benchmarks/artifacts/dryrun"
        if os.path.isdir(art):
            for r in roofline.table(art):
                if "error" in r:
                    _emit("roofline", f"roofline/{r['arch']}/{r['shape']}",
                          None, f"error={r['error'][:50]}")
                    continue
                rf = r.get("roofline_frac")
                _emit("roofline", f"roofline/{r['arch']}/{r['shape']}",
                      r["bound_s"] * 1e6,
                      f"dominant={r['dominant']};"
                      f"roofline_frac={rf:.3f}" if rf else
                      f"dominant={r['dominant']}",
                      tags={"dominant": r["dominant"]})

    if args.json:
        import platform

        import jax as _jax
        doc = {
            "pr": 10,
            "backend": _jax.default_backend(),
            "platform": platform.platform(),
            "repeats": args.repeats,
            "fingerprint": environment_fingerprint(),
            "rows": rows,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"# wrote {args.json} ({len(rows)} rows)", file=sys.stderr)


if __name__ == "__main__":
    main()
