"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program (``src/``).  The last line of standard output is the
result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number the correctness check compared, with its limit (also the last
lines of standard error).  Earlier lines report set-up, the window's
validity signals and the trace summary.  Exits non-zero, printing no
result, when JAX finds no accelerator or fewer chips than the cell needs.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw profiler trace into this directory")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    # The compile cache lives in this checkout, at a fixed path, whatever
    # the environment names: two checkouts measured side by side share no
    # cache, and a later run of this one finds every program.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro  # noqa: F401  (the program under test)
        from bench import harness
    except ImportError as e:
        print(f"bench: cannot import the benchmark or the program: {e}",
              file=sys.stderr)
        return 2
    try:
        cell = harness.Cell.load(harness.load_json(ROOT / "BENCHMARK.json"),
                                 args.workload)
        harness.place_compile_cache()
        result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), t_start=T_START,
                             keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
