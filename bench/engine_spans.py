"""The serving engine's own record of the window's batches, and the
per-layer metrics the engine measures itself.

``RetrievalEngine`` keeps a ``BatchSpan`` of each batch it completed
(``engine.batch_spans``), the harness's warm-up batches among them.  The
loop completes every batch it launches, so the window's batches are the
engine's last ``len(loop.batches)`` records.  A program that keeps no
such records gives None, and the metrics are left out of the result.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional


def window(ctx: Dict[str, Any]) -> Optional[List[Any]]:
    """The engine's records of the loop's batches, in order; None where
    the engine keeps none or the loop dispatched nothing.  Raises where
    the engine holds fewer records than the loop dispatched batches, or
    their rows differ from the batches' requests."""
    loop = ctx["loop"]
    spans = getattr(loop.engine, "batch_spans", None)
    if spans is None or not loop.batches:
        return None
    n = len(loop.batches)
    if len(spans) < n:
        raise RuntimeError(f"the engine holds {len(spans)} batch records "
                           f"for {n} dispatched batches")
    out = list(spans)[-n:]
    rows = [s.rows for s in out]
    if rows != [len(b.rids) for b in loop.batches]:
        raise RuntimeError("the engine's batch records do not line up with "
                           "the loop's dispatched batches")
    return out


def queue_ms(ctx: Dict[str, Any]) -> Optional[float]:
    """Mean milliseconds a request waited from its arrival to its batch's
    ``prepare``."""
    spans = window(ctx)
    if not spans:
        return None
    return 1e3 * sum(s.queue_s for s in spans) / sum(s.rows for s in spans)


def flight_ms(ctx: Dict[str, Any]) -> Optional[float]:
    """Mean milliseconds per batch from ``launch`` returning to the end of
    ``complete``'s device wait: its device run, plus its wait behind the
    batch ahead of it."""
    spans = window(ctx)
    if not spans:
        return None
    return 1e3 * sum(s.t_ready - s.t_launched for s in spans) / len(spans)


def fill(ctx: Dict[str, Any]) -> Optional[float]:
    """Live rows over the padded rows sent to the device (%)."""
    spans = window(ctx)
    if not spans:
        return None
    return 100.0 * sum(s.rows for s in spans) / sum(s.bucket for s in spans)


def self_ms(ctx: Dict[str, Any]) -> Optional[float]:
    """Mean host milliseconds per batch inside ``prepare``, ``launch`` and
    ``complete``, less ``complete``'s device wait."""
    spans = window(ctx)
    if not spans:
        return None
    total = sum((s.t_prepared - s.t_prepare) + (s.t_launched - s.t_launch)
                + (s.t_done - s.t_ready) for s in spans)
    return 1e3 * total / len(spans)
