"""Faults planted in the timed path, to show that the correctness check
catches them (``limits.py --fault``, ``tests/test_bench_check.py``).  Each
rewrites the serve program's output of a batch right where it is made,
before the engine slices it into answers."""
from __future__ import annotations

import contextlib

import numpy as np


def answer_altered(out):
    """The first request of every batch gets another item at rank 1."""
    ids, vals = out[0], out[1]
    return (ids.at[0, 0].set((ids[0, 0] + 1) % 100), vals) + tuple(out[2:])


def half_batch(out):
    """Only the first half of every batch computed: the other rows copy
    it."""
    ids, vals = out[0], out[1]
    half = max(1, ids.shape[0] // 2)
    rows = np.arange(ids.shape[0]) % half
    return (ids[rows], vals[rows]) + tuple(out[2:])


FAULTS = {"answer_altered": answer_altered, "half_batch": half_batch}


@contextlib.contextmanager
def planted(name):
    """Within the block, every batch the engine launches carries the
    fault ``name`` (None: no fault)."""
    if name is None:
        yield
        return
    from repro.serving import engine as engine_mod
    fault = FAULTS[name]
    launch = engine_mod.RetrievalEngine.launch

    def broken(self, prep):
        inflight = launch(self, prep)
        inflight.out = fault(inflight.out)
        return inflight
    engine_mod.RetrievalEngine.launch = broken
    try:
        yield
    finally:
        engine_mod.RetrievalEngine.launch = launch
