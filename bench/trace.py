"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Only the window is read: the host span ``bench.window`` that the harness
puts around the timed loop gives its start and end on the trace's clock.
Inside it:

* device busy time: the union of the intervals in which an operation ran
  on the device (the ``XLA Ops`` line of each ``/device:<N>`` plane),
  averaged over the chips;
* steps: executions of the serve program, events of the ``XLA Modules``
  line whose name starts with the program's jitted module name;
* kernel calls: events of the ``XLA Ops`` line for the kernel's custom
  call (the event's name is the HLO instruction, ``%<kernel>.<n> = ...
  custom-call(...)``, which also gives the call's shapes);
* idle gaps: the time between busy intervals, split by the benchmark's
  host span (``engine.prepare``, ``device.wait``, ...) that covers it.
"""
from __future__ import annotations

import gzip
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir: str):
    """The ProfileData of the newest ``.xplane.pb`` (or ``.xplane.pb.gz``)
    under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb*"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    if files[-1].suffix == ".gz":
        return ProfileData.from_serialized_xspace(
            gzip.decompress(files[-1].read_bytes()))
    return ProfileData.from_file(str(files[-1]))


@dataclass
class Event:
    name: str
    start: int          # ns, trace clock
    end: int

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


def _events(line) -> List[Event]:
    return [Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


_SHAPE = re.compile(r"([a-z]+[0-9]+)\[([0-9,]*)\]")
_OPCODE = re.compile(r"[\]\}\)] ([a-z][a-z0-9\-]*)\(")


def op_label(text: str) -> str:
    """``%fusion.12 = f32[..]{..} fusion(...)`` -> ``%fusion.12 fusion``:
    the HLO instruction's name and opcode, without its shapes."""
    name = text.split(" = ", 1)[0]
    m = _OPCODE.search(text)
    return f"{name} {m.group(1)}" if m else name


def shapes_of(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """The (dtype, dims) of every array shape written in an HLO text, in
    order: for a custom call, its results first, then its operands."""
    return [(t, tuple(int(x) for x in d.split(",") if x))
            for t, d in _SHAPE.findall(text)]


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    steps: List[Event]
    kernels: List[Event]
    op_seconds: Dict[str, float]
    idle_by_host: Dict[str, float]
    n_devices: int

    def summary(self) -> Dict[str, Any]:
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "idle_share": (1 - self.busy_s / self.window_s
                               if self.window_s else None),
                "steps": len(self.steps), "kernel_calls": len(self.kernels),
                "devices": self.n_devices}

    def breakdown(self) -> Dict[str, List[List[Any]]]:
        ops = sorted(self.op_seconds.items(), key=lambda x: -x[1])[:10]
        gaps = sorted(self.idle_by_host.items(), key=lambda x: -x[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:")
            and not p.name.startswith("/device:CUSTOM")]


def _window(pd) -> Tuple[int, int]:
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    return int(e.start_ns), int(e.start_ns + e.duration_ns)
    raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")


def _host_spans(pd, lo: int, hi: int) -> List[Event]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in _events(line):
                if (e.name.startswith(("engine.", "device.", "loop."))
                        and e.end > lo and e.start < hi):
                    out.append(e)
    return out


def _attribute(gaps, spans: List[Event]) -> Dict[str, float]:
    """Idle seconds by the host span that covers them; what no span
    covers is the loop's own Python."""
    spans = sorted(spans, key=lambda e: e.start)
    out: Dict[str, float] = {}
    i = 0
    for gs, ge in gaps:
        covered = 0
        while i < len(spans) and spans[i].end <= gs:
            i += 1
        j = i
        while j < len(spans) and spans[j].start < ge:
            ov = min(ge, spans[j].end) - max(gs, spans[j].start)
            if ov > 0:
                out[spans[j].name] = out.get(spans[j].name, 0.0) + ov * 1e-9
                covered += ov
            j += 1
        rest = (ge - gs) - covered
        if rest > 0:
            out["loop.other"] = out.get("loop.other", 0.0) + rest * 1e-9
    return out


def reduce(pd, n_devices: int, step_module: str, kernel: str) -> Reduced:
    lo, hi = _window(pd)
    planes = _device_planes(pd)[:n_devices]
    if not planes:
        raise ValueError("trace holds no device plane")
    busy_total = 0.0
    steps: List[Event] = []
    kernels: List[Event] = []
    op_seconds: Dict[str, float] = {}
    gaps0 = []
    for idx, plane in enumerate(planes):
        lines = {ln.name: ln for ln in plane.lines}
        ops = [e for e in _events(lines[OPS_LINE])
               if e.end > lo and e.start < hi] if OPS_LINE in lines else []
        busy = _clip(_union([(e.start, e.end) for e in ops]), lo, hi)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        if idx == 0:
            for e in ops:
                label = op_label(e.name)
                op_seconds[label] = op_seconds.get(label, 0.0) + e.seconds
            is_kernel = re.compile(rf"^%{re.escape(kernel)}(\.\d+)? = .*"
                                   r"custom-call\(").match
            kernels = [e for e in ops if is_kernel(e.name)]
            if MODULES_LINE in lines:
                steps = [e for e in _events(lines[MODULES_LINE])
                         if e.name.split("(")[0] == step_module
                         and lo <= e.start < hi]
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps0 = [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    idle = _attribute(gaps0, _host_spans(pd, lo, hi))
    return Reduced((hi - lo) * 1e-9, busy_total / len(planes), steps,
                   kernels, op_seconds, idle, len(planes))
