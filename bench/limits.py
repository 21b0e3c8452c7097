"""Readings that the correctness limits are set from, on the chip.

    python3 bench/limits.py --workload <name> --seeds 1,2,3 --seconds 2 \
        [--fault answer_altered|half_batch]

Runs the cell as ``run.py`` does, once per seed in one process (a short
window at the cell's own load), and after each run's check also reads the
control (the reference in bfloat16 in the program's place) on the same
sampled requests.  With ``--fault`` the timed path carries that fault
(``faults.py``).  Prints each run's ``check`` and ``control`` lines and
its result.  The benchmark's runs never compute the control.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    # The compile cache lives in this checkout, at a fixed path, whatever
    # the environment names: two checkouts measured side by side share no
    # cache, and a later run of this one finds every program.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import faults, harness
    cell = harness.Cell.load(harness.load_json(ROOT / "BENCHMARK.json"),
                             args.workload)
    harness.place_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        with faults.planted(args.fault):
            result = harness.run(cell, seed=seed, seconds=args.seconds,
                                 trace=False, t_start=time.monotonic(),
                                 control=args.fault is None)
        print(json.dumps({"seed": seed, "fault": args.fault, **result}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
