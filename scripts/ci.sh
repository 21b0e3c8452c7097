#!/usr/bin/env bash
# Tier-1 CI entrypoint: install dev deps (best-effort — offline containers
# rely on the importorskip guards), then run the suite in two tiers (fast
# first, the marked matrix second), guard against silent test deletion,
# prove the single-dispatch property, and smoke the benchmarks.
set -euo pipefail

cd "$(dirname "$0")/.."

if ! python -c "import hypothesis" 2>/dev/null; then
    pip install -r requirements-dev.txt 2>/dev/null \
        || echo "WARN: could not install dev deps; property tests fall back to deterministic grids / skip"
fi

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Lint gate (crash-level rules only — see ruff.toml).  Best-effort like
# the hypothesis install: offline containers without ruff warn and skip;
# the repro.analysis ast-lint pass below covers the codebase-specific
# hazards regardless.
if python -m ruff --version >/dev/null 2>&1; then
    python -m ruff check .
else
    echo "WARN: ruff not installed; skipping lint gate (pip install -r requirements-dev.txt)"
fi

# Bytecode must never be committed: .gitignore covers __pycache__/*.pyc,
# and this guard fails CI if a tracked .pyc ever reappears (it happened
# once — a PR 4 follow-up commit shipped tests/__pycache__).
if git ls-files | grep -q '\.pyc$'; then
    echo "FAIL: tracked .pyc files in the repo:" >&2
    git ls-files | grep '\.pyc$' >&2
    exit 1
fi

# Anti-test-deletion guard: the collected count must never drop below the
# previous tier-1 baseline (bump this when a PR adds tests; a drop means a
# test file stopped importing or someone deleted coverage).  pytest also
# exits non-zero on collection errors, so a broken import fails CI rather
# than silently shrinking the suite.
TIER1_BASELINE=472
collected=$(python -m pytest --collect-only -q 2>/dev/null | tail -1 \
            | grep -o '[0-9]\+ tests collected' | grep -o '^[0-9]\+' || echo 0)
if [ "${collected}" -lt "${TIER1_BASELINE}" ]; then
    echo "FAIL: collected ${collected} tests < tier-1 baseline ${TIER1_BASELINE}" >&2
    exit 1
fi
echo "collected ${collected} tests (baseline ${TIER1_BASELINE})"

# Fast tier first (quick signal), then the full marked matrix (slow /
# sharded / hypothesis) — together they cover the whole suite exactly once.
python -m pytest -x -q -m "not slow and not sharded and not hypothesis" "$@"
python -m pytest -x -q -m "slow or sharded or hypothesis" "$@"

# Serve-path static analysis (docs/ANALYSIS.md): every registered
# entrypoint (flat fused/pruned, grouped per-query, sharded, lm decode,
# compacted-tile kernels, engine AOT routes) under every pass
# (dispatch-count, host-transfer, recompile-hazard, kernel-contract,
# ast-lint).  Exits non-zero on ANY finding; the JSON report is a CI
# artifact, not tracked.
python -m repro.analysis --json ANALYSIS_REPORT.json

# The pruned serve route must be ONE device dispatch per query batch —
# since ISSUE 6 a thin wrapper over repro.analysis adding the *runtime*
# dispatch counter (trace-level checks alone can't see host replay) and
# the host-cascade negative control that proves the framework
# discriminates.
python scripts/check_single_dispatch.py

# Billion-item simulator smoke (ISSUE 9): the streaming scorer at a
# CI-sized N with a ragged final chunk (exactly-one-compile + padded
# tail), then the flat-vs-hierarchical cascade comparison, which exits
# non-zero on any exactness mismatch.  Small N keeps it seconds-fast;
# the real N in {2^24, 2^27} runs live in the `hier` BENCH section.
python examples/billion_item_sim.py --items 2e5 --chunk 65536 --repeats 1
python examples/billion_item_sim.py --mode hier --items 262144 \
    --tile 256 --factor 16 --repeats 1

# Crash-recovery smoke (ISSUE 10): churn a mutable catalogue through the
# checksummed WAL, tear the writer mid-append, recover in a "new
# process" and verify the recovered catalogue AND everything served from
# it are bit-identical to an oracle replay of the durable prefix.  The
# example exits non-zero on any parity mismatch or if the tear never
# fires.
python examples/serve_catalogue.py --kill-and-recover --items 2000 \
    --d-model 64 --requests 16 --crash-at 11

# Fast benchmark smoke: exercises the kernel paths (fused interpret-mode,
# single-dispatch pruned cascade, bound-backend comparison sweep, the
# per-query mixed-batch sweep, the catalogue-churn section with its
# sampled exactness checks, the replicated-fabric latency-under-load
# section, the durable-log recovery section, figure2) end to end so
# kernel-path breakage surfaces in CI, not just in unit tests, and
# refreshes the machine-readable BENCH_pr10.json.  table3/roofline/hier
# stay out (slow dataset builds / artifact-dependent).  --repeats 3
# (up from 1): quartiles over one
# sample are degenerate, and the IQR-separation rule needs real spread
# to be meaningful.
#
# Thread pinning (PR 8): single-threaded BLAS/Eigen and a one-core
# affinity mask where taskset exists.  Unpinned thread pools made every
# latency number hostage to scheduler noise; the pinning lands in the
# environment fingerprint, so pinned and unpinned files can never be
# silently joined into one trajectory.
export OMP_NUM_THREADS=1 MKL_NUM_THREADS=1 OPENBLAS_NUM_THREADS=1
export XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_cpu_multi_thread_eigen=false"
PIN=""
if command -v taskset >/dev/null 2>&1; then
    PIN="taskset -c 0"
fi
${PIN} python -m benchmarks.run --skip table3 --skip roofline \
    --skip hier --repeats 3 --json BENCH_pr10.json > /dev/null

# Cross-PR perf trajectory, two views.  Informational: the whole history
# joined across the pinning seam (--allow-mixed; trend only, never
# gated).  Gate: --split-environments partitions files by environment
# fingerprint and --strict fails CI on an IQR-separated regression
# WITHIN the current (pinned) partition — the first trajectory stable
# enough to gate on; historical unpinned regressions report but cannot
# fail a run that did not produce them.
python scripts/bench_compare.py --allow-mixed
python scripts/bench_compare.py --strict --split-environments
