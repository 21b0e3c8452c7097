"""What the per-layer readers compute, from the run's record and its trace.

Each reader in ``bench/metrics/`` is one of these applied to a context
holding the configuration (``cfg``), the serving loop (``loop``: its
dispatched batches), the reduced trace (``trace``), the kernel's name and
the function that reads a call's shapes (``kernel``, ``kernel_shapes``)
and the chip's peaks (``peaks``).  A reader
that finds nothing to read returns None, and the metric is left out of
the result; a traced step without the kernel's events is an error.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from bench import costs


def host_ms(ctx: Dict[str, Any]) -> Optional[float]:
    """Mean host milliseconds per dispatched batch in ``prepare``,
    ``launch`` and ``complete`` (the device wait excluded)."""
    batches = ctx["loop"].batches
    if not batches:
        return None
    return 1e3 * sum(b.host_s for b in batches) / len(batches)


def step_device_ms(ctx: Dict[str, Any]) -> Optional[float]:
    """Mean device milliseconds per execution of the serve program."""
    steps = ctx["trace"].steps
    if not steps:
        return None
    return 1e3 * sum(s.seconds for s in steps) / len(steps)


def _kernels(ctx: Dict[str, Any]):
    tr = ctx["trace"]
    if tr.steps and not tr.kernels:
        raise RuntimeError(
            f"{len(tr.steps)} traced serve steps ran no event of the kernel "
            f"{ctx['kernel']!r}")
    return tr.kernels


def kernel_device_ms(ctx: Dict[str, Any]) -> Optional[float]:
    """Device milliseconds of the fused PQTopK kernel per serve step."""
    kernels = _kernels(ctx)
    if not kernels or not ctx["trace"].steps:
        return None
    return 1e3 * sum(k.seconds for k in kernels) / len(ctx["trace"].steps)


def kernel_roofline(ctx: Dict[str, Any]) -> Optional[float]:
    """Share (%) of the roofline the kernel reached: the PQTopK work of
    each call, counted from the call's own shapes, over the summed kernel
    time."""
    kernels = _kernels(ctx)
    if not kernels:
        return None
    ops = nbytes = seconds = 0.0
    for k in kernels:
        c = costs.pqtopk_cost(**ctx["kernel_shapes"](k))
        ops += c["ops"]
        nbytes += c["bytes"]
        seconds += k.seconds
    return costs.roofline_share(ops, nbytes, seconds, ctx["peaks"])["percent"]


def step_mfu(ctx: Dict[str, Any]) -> Optional[float]:
    """Useful model FLOPs of the requests served in the traced window over
    the device time of the serve steps that served them, as a share (%) of
    the chip's bf16 peak."""
    steps, batches = ctx["trace"].steps, ctx["loop"].batches
    if not steps or not batches:
        return None
    if len(steps) != len(batches):
        raise RuntimeError(f"trace holds {len(steps)} serve steps for "
                           f"{len(batches)} dispatched batches")
    flops = sum(costs.request_flops(ctx["cfg"], n)
                for b in batches for n in b.lengths)
    seconds = sum(s.seconds for s in steps)
    return 100.0 * flops / seconds / ctx["peaks"]["bf16_flops_per_s"]

