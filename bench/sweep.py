"""Find the knee of an open-loop cell once, by a sweep on the chip.

    python3 bench/sweep.py --config bench/configs/<c>.json \
        --traffic <mix> --rates 1000,1500,2000 --seconds 6 --seed 1

Builds the system once, warms it, then serves the mix at each rate in
turn (the mix's own ``rate_per_s`` is replaced) and prints one line per
rate: p50, p90 and p99 latency, requests shed, generator lateness, batch
sizes, and the mean queue length at launch in the first and second half
of the window.  The knee is the highest rate whose second half's queue
is no more than ``GROWTH`` times its first half's (a margin for
noise) and that sheds nothing.  The benchmark's cells
run at a fixed rate taken from this sweep; the sweep is not part of a
run.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
GROWTH = 1.02


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    # The compile cache lives in this checkout, at a fixed path, whatever
    # the environment names: two checkouts measured side by side share no
    # cache, and a later run of this one finds every program.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np
    from bench import engine_loop, harness
    cell = harness.Cell(
        "sweep", harness.load_json(ROOT / args.config),
        harness.load_json(ROOT / "bench" / "traffic" / f"{args.traffic}.json"))
    harness.place_compile_cache()
    devices = harness.check_devices(1)
    kind, cfg, traffic = cell.kind, cell.cfg, dict(cell.traffic)
    weights = cell.reference.make_weights(cfg, args.seed)
    engine = cell.system.build(cfg, traffic, weights)
    harness.warm(engine, kind, traffic,
                 kind.make_work(traffic, cfg, args.seed, 1.0))
    harness.say(sys.stdout, phase="setup", device=devices[0].device_kind,
                setup_s=time.monotonic() - T_START)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic["rate_per_s"] = rate
        work = kind.make_work(traffic, cfg, args.seed, args.seconds)
        loop = engine_loop.Loop(engine)
        with harness.no_gc():
            record = kind.drive(loop, work, args.seconds)
        e2e = kind.end_to_end(loop, record)
        v = kind.validity(loop, record)
        q = v["queue_at_launch"]
        shed = sum(1 for a in loop.answers.values() if a.shed)
        sizes = [len(b.rids) for b in loop.batches]
        harness.say(sys.stdout, rate_per_s=rate, **e2e, shed=shed,
                    requests=len(work), batches=len(sizes),
                    mean_batch=float(np.mean(sizes)), **v,
                    sustained=bool(shed == 0 and q["second_half_mean"]
                                   is not None and q["second_half_mean"]
                                   <= GROWTH * q["first_half_mean"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
