"""Serving launcher: paper-mode top-K retrieval over a request stream.

  PYTHONPATH=src python -m repro.launch.serve --arch sasrec-recjpq --reduced \
      --requests 256 --method pqtopk
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import place_compile_cache
from repro.configs.base import get_config, get_reduced
from repro.serving.engine import Request, RetrievalEngine


def _ms(v) -> str:
    """Latency field for humans; None (no traffic) is 'n/a', never 0.00."""
    return "n/a" if v is None else f"{v:.2f}ms"


def _churn_ops(shadow, rng, n_steps, b):
    """Draw a batch of valid mutation ops, applying them to ``shadow`` as
    drawn (validity of op i+1 can depend on op i — e.g. no double
    delete).  The caller routes the SAME ops through the durable path;
    ``shadow`` is its local mirror of the committed catalogue."""
    from repro.core.mutation import apply_op

    ops = []
    for _ in range(n_steps):
        r = rng.random()
        row = rng.integers(0, b, shadow.m)
        live = np.where(np.asarray(shadow.live))[0]
        live = live[live > 0]                # row 0 is the padding id
        if (r < 0.2 and (shadow.free or shadow.n_rows < shadow.cap)) \
                or live.size <= 1:
            op = ("insert", row)
        elif r < 0.5:
            op = ("delete", int(rng.choice(live)))
        else:
            op = ("update", int(rng.choice(live)), row)
        apply_op(shadow, op)
        ops.append(op)
    return ops


def _print_durable_stats(stats):
    log_st = stats.get("log")
    print(f"durable: committed_lsn={int(stats['committed_lsn'])} "
          f"mutations={int(stats['mutations_applied'])} "
          f"stale_served={int(stats['stale_served'])} "
          f"catchup_events={int(stats['catchup_events'])} "
          f"staleness_budget={int(stats['staleness_budget'])}")
    if log_st is not None:
        print(f"log: lsn={int(log_st['lsn'])} "
              f"bytes={int(log_st['log_bytes'])} "
              f"fsyncs={int(log_st['n_fsyncs'])} "
              f"snapshots={int(log_st['n_snapshots'])} "
              f"latest_snapshot_lsn={int(log_st['latest_snapshot_lsn'])} "
              f"torn_bytes_dropped={int(log_st['torn_bytes_dropped'])}")


def _serve_replicated_mutable(args, params, cfg):
    """K replicas over ONE durable mutable catalogue: mutation batches
    commit through the WAL between request batches, replicas catch up by
    LSN-fenced replay, and the chaos flags exercise replica crash
    (recover-from-log + gated re-admission) and writer crash (torn
    record; the fabric is rebuilt from ``CatalogueLog.recover``)."""
    from repro.core.mutation import MutableHeadState
    from repro.serving.catalogue_log import CatalogueLog
    from repro.serving.router import ReplicaRouter
    from repro.training.fault_tolerance import SimulatedFailure

    log = None
    if args.log_dir:
        log = CatalogueLog(args.log_dir, snapshot_every=args.snapshot_every)
    if args.recover:
        mstate, lsn0 = log.recover()
        print(f"recovered catalogue from {args.log_dir} at lsn {lsn0} "
              f"(torn bytes dropped: {log.torn_bytes_dropped})")
    else:
        mstate = MutableHeadState.build(
            params["item_emb"]["codes"], cfg.pq.b,
            backend=cfg.pq.bound_backend,
            super_factor=cfg.pq.super_factor)
    shadow = mstate.clone()               # the launcher's committed mirror
    crash_plan = []                       # [(lsn, rid)], ascending
    for spec in args.crash_replica_at or []:
        rid, _, lsn = spec.partition(":")
        crash_plan.append((int(lsn), int(rid)))
    crash_plan.sort()

    def mk_router(state, the_log):
        return ReplicaRouter.for_seqrec_mutable(
            params, cfg, state, n_replicas=args.replicas, k=args.k,
            max_batch=args.max_batch, calibrate=not args.no_calibrate,
            log=the_log, hedge=not args.no_hedge,
            staleness_budget=args.staleness_budget)

    router = mk_router(mstate, log)
    if args.crash_writer_at is not None:
        log.fail_at_lsn = args.crash_writer_at
    rng = np.random.default_rng(0)
    mrng = np.random.default_rng(1)
    results = []
    t0 = time.monotonic()
    i = 0
    with router:
        router.warmup()
        while i < args.requests:
            hist_len = int(rng.integers(2, cfg.max_seq_len))
            seq = rng.integers(1, cfg.n_items + 1, hist_len)
            router.submit(Request(i, seq, k=args.k))
            i += 1
            if args.churn_steps and i % args.max_batch == 0:
                ops = _churn_ops(shadow, mrng, args.churn_steps, cfg.pq.b)
                try:
                    committed = router.apply_mutations(ops)
                except SimulatedFailure as exc:
                    print(f"chaos: {exc}")
                    break
                while crash_plan and committed >= crash_plan[0][0]:
                    _, rid = crash_plan.pop(0)
                    print(f"chaos: crashing replica {rid} at "
                          f"lsn {committed}")
                    router.crash_replica(rid)
                router.pump()
        results += router.drain()
        if log is not None and not getattr(log, "_crashed", False):
            log.sync()                    # clean shutdown: nothing buffered
        stats = router.stats()
    if i < args.requests:
        # Writer died mid-append: stand a NEW fabric up from the durable
        # log (torn-tail truncation + snapshot + replay) and finish the
        # stream — the kill-and-recover path, end to end.
        print("rebuilding the fabric from the durable log ...")
        log = CatalogueLog(args.log_dir,
                           snapshot_every=args.snapshot_every)
        state, lsn = log.recover()
        print(f"recovered at lsn {lsn} "
              f"(torn bytes dropped: {log.torn_bytes_dropped})")
        shadow = state.clone()
        with mk_router(state, log) as router:
            router.warmup()
            while i < args.requests:
                hist_len = int(rng.integers(2, cfg.max_seq_len))
                seq = rng.integers(1, cfg.n_items + 1, hist_len)
                router.submit(Request(i, seq, k=args.k))
                i += 1
                if args.churn_steps and i % args.max_batch == 0:
                    router.apply_mutations(
                        _churn_ops(shadow, mrng, args.churn_steps,
                                   cfg.pq.b))
                    router.pump()
            results += router.drain()
            log.sync()
            stats = router.stats()
    wall = time.monotonic() - t0
    eng = router.engines[0]
    print(f"served {len(results)} requests in {wall:.2f}s "
          f"({len(results) / wall:.1f} req/s) replicas={args.replicas} "
          f"mutable=True durable={args.log_dir is not None}")
    print(f"p50={_ms(stats['p50_ms'])} p99={_ms(stats['p99_ms'])} "
          f"dup_suppressed={stats['duplicates_suppressed']} "
          f"redispatched={stats['redispatched']} "
          f"degraded={dict(stats['degraded_results'])}")
    _print_durable_stats(stats)
    for rid, rs in stats["replicas"].items():
        print(f"  replica[{rid}] state={rs['state']} "
              f"completed={rs['completed']} "
              f"ejections={rs['ejections']} "
              f"readmissions={rs['readmissions']} "
              f"applied_lsn={rs['applied_lsn']} lag={rs['lag']} "
              f"n_compiles={rs['n_compiles']}")
    if eng.ladder is not None:
        print(f"ladder={eng.ladder} (shared across replicas)")
    return results


def _serve_replicated(args, params, cfg):
    """Drive the ReplicaRouter fabric: K engine replicas behind one
    submit/pump/drain loop, optionally under a deterministic chaos plan."""
    from repro.serving.router import ReplicaRouter

    fault_plans = None
    if args.chaos:
        from repro.training.fault_tolerance import ReplicaFaultPlan
        # Replica 1 dies for a few dispatches (ejection + re-dispatch +
        # half-open re-admission); replica 2, when present, straggles
        # (hedging + straggler strikes).  Indices are per-replica dispatch
        # counters, so the schedule is reproducible under any interleaving.
        fault_plans = {1: ReplicaFaultPlan(crash_windows=((1, 4),))}
        if args.replicas > 2:
            fault_plans[2] = ReplicaFaultPlan(slow_windows=((0, 3),),
                                              slow_ms=250.0)
    router = ReplicaRouter.for_seqrec(
        params, cfg, n_replicas=args.replicas, k=args.k,
        max_batch=args.max_batch, method=args.method,
        calibrate=not args.no_calibrate,
        fault_plans=fault_plans, hedge=not args.no_hedge)
    rng = np.random.default_rng(0)
    with router:
        router.warmup()
        t0 = time.monotonic()
        for i in range(args.requests):
            hist_len = int(rng.integers(2, cfg.max_seq_len))
            seq = rng.integers(1, cfg.n_items + 1, hist_len)
            router.submit(Request(i, seq, k=args.k))
            router.pump()
        results = router.drain()
        wall = time.monotonic() - t0
        stats = router.stats()
    eng = router.engines[0]
    print(f"served {len(results)} requests in {wall:.2f}s "
          f"({len(results) / wall:.1f} req/s) replicas={args.replicas} "
          f"method={eng.method} chaos={args.chaos}")
    print(f"p50={_ms(stats['p50_ms'])} p99={_ms(stats['p99_ms'])} "
          f"hedges={stats['hedges']} hedge_wins={stats['hedge_wins']} "
          f"dup_suppressed={stats['duplicates_suppressed']} "
          f"redispatched={stats['redispatched']}")
    print(f"degrade_level={stats['degrade_level']} "
          f"degrade_events={stats['degrade_events']} "
          f"recover_events={stats['recover_events']} "
          f"shed_load={stats['shed_load']} "
          f"degraded={dict(stats['degraded_results'])}")
    for rid, rs in stats["replicas"].items():
        print(f"  replica[{rid}] state={rs['state']} "
              f"dispatched={rs['dispatched']} completed={rs['completed']} "
              f"failures={rs['failures']} stragglers={rs['stragglers']} "
              f"ejections={rs['ejections']} "
              f"readmissions={rs['readmissions']} "
              f"n_compiles={rs['n_compiles']}")
    if eng.ladder is not None:
        print(f"ladder={eng.ladder} (shared across replicas)")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="sasrec-recjpq")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--method", default=None,
                    choices=["dense", "recjpq", "pqtopk", "pqtopk_onehot",
                             "pqtopk_kernel", "pqtopk_fused",
                             "pqtopk_pruned", "pqtopk_approx"],
                    help="scoring route; default: the arch config's "
                         "serve_method.  pqtopk_pruned = the two-pass "
                         "cascade (upper-bound tile skipping); "
                         "pqtopk_approx = block-max approximate top-k")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--seed-policy", default=None,
                    choices=["greedy", "adaptive"],
                    help="theta-seeding policy for the pruned cascade "
                         "(overrides the arch config's PQConfig)")
    ap.add_argument("--bound-backend", default=None,
                    choices=["bitmask", "range"],
                    help="pruned-cascade bound backend (overrides the arch "
                         "config's PQConfig): bitmask = uint32 code-"
                         "presence sets; range = int16 min/max code ranges "
                         "(1/8 the metadata, looser bounds)")
    ap.add_argument("--super-factor", type=int, default=None,
                    help="hierarchical super-tile factor for the pruned "
                         "cascade (overrides the arch config's PQConfig): "
                         "groups of this many child tiles get OR-ed/"
                         "hulled pass-0 metadata; 0 disables the level "
                         "(mutually exclusive with --query-grouping)")
    ap.add_argument("--no-calibrate", action="store_true",
                    help="disable the build-time slot-budget ladder "
                         "calibration for the pruned cascade (serve the "
                         "full-length compacted buffer instead)")
    ap.add_argument("--query-grouping", action="store_true",
                    help="per-query pruned survival (pqtopk_pruned only): "
                         "seed theta per query, bucket queries by "
                         "survivor-set overlap, and score each group's "
                         "compacted tile list — sum_g B_g*S_g work "
                         "instead of the batch-any B*|union|")
    ap.add_argument("--n-groups", type=int, default=None,
                    help="query-group count for --query-grouping "
                         "(default: the arch config's PQConfig.n_groups; "
                         "1 recovers the batch-any route)")
    ap.add_argument("--mutable", action="store_true",
                    help="serve through a MutableHeadState (pow2-padded "
                         "capacity + tombstone mask): the catalogue "
                         "mutates between batches and the engine "
                         "hot-swaps the head arrays with zero recompiles "
                         "(forces the pqtopk_pruned route)")
    ap.add_argument("--churn-steps", type=int, default=0,
                    help="with --mutable: catalogue mutations "
                         "(update/delete/insert mix) applied + hot-"
                         "swapped between every served batch")
    ap.add_argument("--fail-at", type=int, action="append", default=None,
                    help="batch indices whose dispatch raises a "
                         "SimulatedFailure (repeatable flag); the engine "
                         "retries with exponential backoff and sheds "
                         "after --max-retries instead of crashing")
    ap.add_argument("--fail-repeats", type=int, default=1,
                    help="consecutive failing attempts per --fail-at "
                         "batch (> --max-retries exercises shedding)")
    ap.add_argument("--slow-at", type=int, action="append", default=None,
                    help="batch indices delayed by --slow-ms (synthetic "
                         "stragglers; flagged in stats)")
    ap.add_argument("--slow-ms", type=float, default=50.0)
    ap.add_argument("--max-retries", type=int, default=2)
    ap.add_argument("--replicas", type=int, default=1,
                    help="> 1 serves through the ReplicaRouter fabric: "
                         "pipelined dispatch over health-checked engine "
                         "replicas with hedging and the load-adaptive "
                         "degradation ladder")
    ap.add_argument("--chaos", action="store_true",
                    help="with --replicas: install a deterministic "
                         "ReplicaFaultPlan (a crash window on replica 1, "
                         "a straggle window on replica 2 when present) so "
                         "ejection, re-dispatch, hedging and re-admission "
                         "are all visible in the printed stats")
    ap.add_argument("--no-hedge", action="store_true",
                    help="with --replicas: disable hedged dispatch")
    ap.add_argument("--log-dir", default=None,
                    help="with --mutable: durable catalogue state — every "
                         "mutation commits to a checksummed WAL in this "
                         "directory (LSN-keyed snapshots alongside) before "
                         "any engine applies it")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="with --log-dir: cut an LSN-keyed snapshot every "
                         "N committed mutations (0 = only the genesis "
                         "snapshot; recovery then replays the whole log)")
    ap.add_argument("--recover", action="store_true",
                    help="with --log-dir: recover the catalogue from the "
                         "newest valid snapshot + log-tail replay instead "
                         "of building it fresh (the post-crash restart "
                         "path; torn log tails are truncated)")
    ap.add_argument("--staleness-budget", type=int, default=0,
                    help="with --mutable --replicas: max LSNs a replica "
                         "may lag the committed catalogue before its "
                         "results are tagged stale_catalogue and it is "
                         "deprioritised (and re-admission is gated)")
    ap.add_argument("--crash-writer-at", type=int, default=None,
                    metavar="LSN",
                    help="chaos, with --log-dir: the append of this LSN "
                         "writes a torn half-record and dies; the "
                         "launcher then rebuilds the fabric from "
                         "CatalogueLog.recover() and finishes the stream")
    ap.add_argument("--crash-replica-at", action="append", default=None,
                    metavar="RID:LSN",
                    help="chaos, with --mutable --replicas --log-dir: "
                         "crash replica RID (drop its in-memory "
                         "catalogue) once the committed LSN reaches LSN; "
                         "it must recover from the log before the health "
                         "FSM re-admits it (repeatable)")
    args = ap.parse_args(argv)
    place_compile_cache()

    arch = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    assert arch.family == "seqrec", "serve.py drives the seqrec archs"
    cfg = arch.model
    pq_overrides = {}
    if args.seed_policy is not None:
        pq_overrides["seed_policy"] = args.seed_policy
    if args.bound_backend is not None:
        pq_overrides["bound_backend"] = args.bound_backend
    if args.query_grouping:
        pq_overrides["query_grouping"] = True
    if args.n_groups is not None:
        pq_overrides["n_groups"] = args.n_groups
    if args.super_factor is not None:
        pq_overrides["super_factor"] = args.super_factor
    if pq_overrides:
        if getattr(cfg, "pq", None) is None:
            raise SystemExit(f"arch {args.arch!r} has no PQ head (dense "
                             "item embedding); --seed-policy/--bound-"
                             "backend/--query-grouping only apply to the "
                             "pruned PQ cascade")
        from dataclasses import replace
        cfg = replace(cfg, pq=replace(cfg.pq, **pq_overrides))
    from repro.models import seqrec as m
    params = m.init_seqrec(jax.random.PRNGKey(0), cfg)

    faults = None
    if args.fail_at or args.slow_at:
        from repro.training.fault_tolerance import ServeFaultInjector
        faults = ServeFaultInjector(fail_at_batches=tuple(args.fail_at or ()),
                                    fail_repeats=args.fail_repeats,
                                    slow_at_batches=tuple(args.slow_at or ()),
                                    slow_ms=args.slow_ms)

    if args.log_dir and not args.mutable:
        raise SystemExit("--log-dir logs catalogue mutations; it needs "
                         "--mutable")
    if args.recover and not args.log_dir:
        raise SystemExit("--recover replays a durable log; it needs "
                         "--log-dir")
    if args.snapshot_every and not args.log_dir:
        raise SystemExit("--snapshot-every needs --log-dir")
    if args.crash_writer_at is not None and not args.log_dir:
        raise SystemExit("--crash-writer-at tears a WAL record; it needs "
                         "--log-dir")
    if args.crash_replica_at and not (args.mutable and args.replicas > 1
                                      and args.log_dir):
        raise SystemExit("--crash-replica-at needs --mutable, --replicas "
                         "> 1 and --log-dir (recovery replays the log)")

    if args.replicas > 1:
        if args.fail_at or args.slow_at:
            raise SystemExit("--fail-at/--slow-at inject inside ONE engine; "
                             "replica-level chaos is --chaos")
        if args.mutable or args.churn_steps:
            if args.chaos:
                raise SystemExit("--chaos drives the immutable fabric; "
                                 "durable chaos is --crash-replica-at / "
                                 "--crash-writer-at")
            if args.method not in (None, "pqtopk_pruned"):
                raise SystemExit("--mutable serves the tombstone-masked "
                                 f"pruned cascade; --method {args.method} "
                                 "has no live-mask route")
            return _serve_replicated_mutable(args, params, cfg)
        return _serve_replicated(args, params, cfg)
    if args.chaos:
        raise SystemExit("--chaos needs --replicas > 1")

    mstate = None
    log = None
    if args.mutable:
        if args.method not in (None, "pqtopk_pruned"):
            raise SystemExit("--mutable serves the tombstone-masked pruned "
                             f"cascade; --method {args.method} has no live-"
                             "mask route")
        if getattr(cfg, "pq", None) is None:
            raise SystemExit(f"arch {args.arch!r} has no PQ head; --mutable "
                             "needs sub-item codes to mutate")
        from repro.core.mutation import MutableHeadState
        if args.log_dir:
            from repro.serving.catalogue_log import CatalogueLog
            log = CatalogueLog(args.log_dir,
                               snapshot_every=args.snapshot_every)
            if args.crash_writer_at is not None:
                log.fail_at_lsn = args.crash_writer_at
        if args.recover:
            mstate, lsn0 = log.recover()
            print(f"recovered catalogue from {args.log_dir} at lsn {lsn0} "
                  f"(torn bytes dropped: {log.torn_bytes_dropped})")
        else:
            mstate = MutableHeadState.build(
                params["item_emb"]["codes"], cfg.pq.b,
                backend=cfg.pq.bound_backend,
                super_factor=cfg.pq.super_factor)
        if log is not None and log.latest_snapshot_lsn() is None:
            log.snapshot(mstate)          # genesis: recovery needs a base
        engine = RetrievalEngine.for_seqrec_mutable(
            params, cfg, mstate, k=args.k, max_batch=args.max_batch,
            calibrate=not args.no_calibrate, faults=faults,
            max_retries=args.max_retries)
    else:
        if args.churn_steps:
            raise SystemExit("--churn-steps requires --mutable")
        engine = RetrievalEngine.for_seqrec(params, cfg, k=args.k,
                                            max_batch=args.max_batch,
                                            method=args.method,
                                            calibrate=not args.no_calibrate,
                                            faults=faults,
                                            max_retries=args.max_retries)
    rng = np.random.default_rng(0)
    # Warm the jit caches (per padding bucket) before the timed stream.
    for b in (1, args.max_batch):
        for i in range(b):
            engine.submit(Request(-1 - i, rng.integers(1, cfg.n_items + 1, 4),
                                  k=args.k))
        engine.drain()
    engine.latencies_ms.clear()
    engine.timeouts = 0
    def churn(step_rng):
        # Update-heavy mix with occasional deletes/inserts, mirroring a
        # live catalogue feed; every mutation only loosens bounds (or is
        # exact, for inserts) so the swapped head stays serve-correct.
        # With --log-dir the same ops commit to the WAL (and snapshots
        # cut on the --snapshot-every cadence) before the hot swap.
        ops = _churn_ops(mstate, step_rng, args.churn_steps, cfg.pq.b)
        if log is not None:
            from repro.training.fault_tolerance import SimulatedFailure
            try:
                log.append_many(ops)
                log.maybe_snapshot(mstate)
            except SimulatedFailure as exc:
                # Torn record on disk; keep serving the in-memory state
                # and demonstrate recovery on the next run (--recover).
                print(f"chaos: {exc}")
                args.churn_steps = 0
        engine.swap_head_state(mstate)

    t0 = time.monotonic()
    results = []
    for i in range(args.requests):
        hist_len = int(rng.integers(2, cfg.max_seq_len))
        seq = rng.integers(1, cfg.n_items + 1, hist_len)
        engine.submit(Request(i, seq, k=args.k))
        if len(engine.batcher.queue) >= args.max_batch:
            results += engine.drain()
            if mstate is not None and args.churn_steps:
                churn(rng)
    results += engine.drain()
    wall = time.monotonic() - t0
    stats = engine.stats()
    print(f"served {len(results)} requests in {wall:.2f}s "
          f"({len(results) / wall:.1f} req/s) method={engine.method}")
    print(f"mRT={_ms(stats['mRT_ms'])} p99={_ms(stats['p99_ms'])} "
          f"timeouts={int(stats['timeouts'])} "
          f"n_compiles={int(stats['n_compiles'])} "
          f"retried={int(stats['retried'])} shed={int(stats['shed'])} "
          f"stragglers={int(stats['stragglers'])}")
    if mstate is not None:
        ms = mstate.stats()
        print(f"catalogue: capacity={int(ms['capacity'])} "
              f"n_live={int(ms['n_live'])} "
              f"n_mutations={int(ms['n_mutations'])} "
              f"stale_tiles={int(ms['stale_tiles'])} "
              f"n_swaps={int(stats['n_swaps'])}")
    if log is not None:
        log.close()
        ls = log.stats()
        print(f"log: lsn={int(ls['lsn'])} bytes={int(ls['log_bytes'])} "
              f"fsyncs={int(ls['n_fsyncs'])} "
              f"snapshots={int(ls['n_snapshots'])} "
              f"latest_snapshot_lsn={int(ls['latest_snapshot_lsn'])}")
    if engine.ladder is not None:
        print(f"ladder={engine.ladder} "
              f"rung_hit_fraction={stats['rung_hit_fraction']:.2f} "
              f"rung_counts={stats['rung_counts']}")
    # A shed or late request is a failed run, unless --fail-at injected
    # the failures that shed it.
    if not args.fail_at and (stats["shed"] or stats["timeouts"]):
        raise SystemExit(f"FAIL: {int(stats['shed'])} shed and "
                         f"{int(stats['timeouts'])} timed-out requests")
    return results


if __name__ == "__main__":
    main()
