"""One run of one cell: set up, measure for ``seconds``, check, report.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<file>.json`` — sizes of a configuration; its
  ``reference`` key names the plain reference beside it
  (``bench/configs/<reference>.py``) and its ``system`` key the adapter
  that builds the system under test (``bench/systems/<system>.py``);
* ``bench/traffic/<traffic>.json`` — a traffic mix; its ``kind`` key names
  the code that makes and sends it (``bench/kinds/<kind>.py``);
* ``bench/metrics/<metric>.py`` — the reader of one per-layer metric.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    name = "bench_dyn_" + "_".join(path.relative_to(BENCH).with_suffix("")
                                   .parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    return json.loads(path.read_text())


class Cell:
    """A workload of ``BENCHMARK.json`` with everything it names loaded."""

    def __init__(self, name: str, cfg: Dict[str, Any],
                 traffic: Dict[str, Any], chips: int = 1,
                 end_to_end: Optional[List[Dict[str, Any]]] = None,
                 per_layer: Optional[List[Dict[str, Any]]] = None):
        self.name, self.cfg, self.traffic, self.chips = (name, cfg, traffic,
                                                          chips)
        self.end_to_end = end_to_end or []
        self.per_layer = per_layer or []

    @classmethod
    def load(cls, spec: Dict[str, Any], name: str):
        by_name = {w["name"]: w for w in spec["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
        w = by_name[name]
        conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
        return cls(name, load_json(ROOT / conf["file"]),
                   load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                   w["chips"],
                   [m for m in spec["end_to_end"]
                    if name in m.get("workloads", [name])],
                   [m for m in spec["per_layer"]
                    if name in m.get("workloads", [name])])

    @property
    def kind(self):
        return load_module(BENCH / "kinds" / f"{self.traffic['kind']}.py")

    @property
    def reference(self):
        return load_module(BENCH / "configs" / f"{self.cfg['reference']}.py")

    @property
    def system(self):
        return load_module(BENCH / "systems" / f"{self.cfg['system']}.py")

    def reader(self, metric: str):
        return load_module(BENCH / "metrics" / f"{metric}.py")


def place_compile_cache() -> str:
    """JAX's persistent compilation cache where the program places it
    (the entry points point ``JAX_COMPILATION_CACHE_DIR`` at the
    checkout's ``.jax_cache/`` first), caching every program, however
    small or quick to compile, so that only a cell's first run compiles."""
    import jax
    from repro.compile_cache import place_compile_cache as place
    path = place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_devices(chips: int) -> List[Any]:
    """The chips to run on; NoChip where JAX found no accelerator or too
    few of them.  Never falls back to the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no device: {e}") from None
    if devices[0].platform == "cpu":
        raise NoChip("JAX found no accelerator (platform cpu)")
    if len(devices) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts JAX traces and backend compiles while ``active``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.active = False
        self.counts = {e.rsplit("/", 1)[1]: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.counts[event.rsplit("/", 1)[1]] += 1


def warm(engine, kind, traffic, work) -> None:
    """Compile and run once every padding bucket the cell's traffic uses,
    at its k, so that nothing compiles or loads inside the window."""
    import jax
    from repro.serving.engine import Request
    buckets = kind.warm_batches(traffic)
    engine.warmup(buckets, ks=[traffic["k"]])
    for b in buckets:
        reqs = [Request(-1 - i, work[i % len(work)].history, k=traffic["k"])
                for i in range(b)]
        _, prep = engine.prepare(reqs)
        inflight = engine.launch(prep)
        jax.block_until_ready(inflight.out)
        engine.complete(inflight)


@contextlib.contextmanager
def no_gc():
    """No garbage collection inside the window, where a collection of the
    run's many request objects would pause the loop at a random time."""
    gc.collect()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def cpu_seconds() -> float:
    """CPU seconds this process has used (host load beside the chip)."""
    import resource
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def say(stream, **kw) -> None:
    print(json.dumps(kw), file=stream, flush=True)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, out=sys.stdout, err=sys.stderr,
        devices_fn: Callable[[int], List[Any]] = check_devices,
        keep_trace: Optional[str] = None,
        control: bool = False) -> Dict[str, Any]:
    """One run; prints validity lines to ``out`` and returns the result
    line (the caller prints it last).  ``control`` also reads the
    control's numbers on the same sample (``limits.py``; never in a
    benchmark run)."""
    import jax
    from bench import engine_loop
    from bench import check as check_lib
    from bench import peaks as peaks_lib

    devices = devices_fn(cell.chips)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(out, phase="device", **device,
        compile_cache=jax.config.jax_compilation_cache_dir)
    kind, ref = cell.kind, cell.reference
    cfg, traffic = cell.cfg, cell.traffic
    counter = CompileCounter()

    t = time.monotonic()
    weights = ref.make_weights(cfg, seed)
    jax.block_until_ready(weights)
    t_weights = time.monotonic() - t
    engine = cell.system.build(cfg, traffic, weights)
    work = kind.make_work(traffic, cfg, seed, seconds)
    t = time.monotonic()
    warm(engine, kind, traffic, work)
    t_warm = time.monotonic() - t
    setup_s = time.monotonic() - t_start
    say(out, phase="setup", setup_s=setup_s, weights_s=t_weights,
        warm_s=t_warm, warmed_buckets=kind.warm_batches(traffic),
        requests_prepared=len(work))

    loop = engine_loop.Loop(engine, annotate=trace)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        # Host annotations and device activity; no Python function tracer,
        # which would slow the loop and swell the trace.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    counter.active = True
    cpu0 = cpu_seconds()
    try:
        with no_gc(), (jax.profiler.TraceAnnotation("bench.window") if trace
                       else contextlib.nullcontext()):
            record = kind.drive(loop, work, seconds)
    finally:
        counter.active = False
        cpu_s = cpu_seconds() - cpu0
        if trace:
            jax.profiler.stop_trace()
    metrics_e2e = kind.end_to_end(loop, record)
    memory_peak = peak_bytes(devices)
    requests = kind.requests(record)
    answers = loop.answers
    failed = sum(1 for rid in requests
                 if rid not in answers or answers[rid].shed)
    sizes = {}
    for b in loop.batches:
        sizes[len(b.rids)] = sizes.get(len(b.rids), 0) + 1
    say(out, phase="window", attempted=len(requests), completed=sum(
        1 for rid in requests if rid in answers and not answers[rid].shed),
        shed=sum(1 for a in answers.values() if a.shed),
        timed_out=int(engine.stats()["timeouts"]), failed=failed,
        batches=len(loop.batches),
        batch_size_histogram={str(k): v for k, v in sorted(sizes.items())},
        compiles_in_window=counter.counts, cpu_s=cpu_s,
        **kind.validity(loop, record))

    metrics: Dict[str, Any] = {}
    if trace:
        from bench import trace as trace_lib
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        system = cell.system
        reduced = trace_lib.reduce(trace_lib.load(trace_dir), len(devices),
                                   system.STEP_MODULE, system.KERNEL)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"cfg": cfg, "loop": loop, "trace": reduced,
               "kernel": system.KERNEL, "kernel_shapes": system.kernel_shapes,
               "peaks": peaks_lib.peaks(device["kind"])}
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        say(out, phase="trace", **reduced.summary())
    else:
        values = {"setup_s": setup_s, **metrics_e2e}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    device["memory_peak_bytes"] = memory_peak

    # The program's state goes before the reference runs.
    del engine, loop.engine, weights
    gc.collect()
    t = time.monotonic()
    ref_weights = ref.make_weights(cfg, seed)
    values, picked = check_lib.run_check(ref, cfg, seed, requests, answers,
                                         traffic, ref_weights)
    ok, table = check_lib.judge(values, cfg["check"])
    say(out, phase="check", seconds=time.monotonic() - t,
        sampled=len(picked))
    if control and picked:
        from bench import control as control_lib
        k = len(answers[picked[0]].items)
        ids, scores = control_lib.answers(
            ref, cfg, ref_weights, [requests[r].history for r in picked], k)
        ctl = check_lib.readings(
            ref, cfg, ref_weights, seed, requests,
            {r: (ids[i], scores[i]) for i, r in enumerate(picked)},
            picked, picked)
        say(out, phase="control", precision="bfloat16",
            correct=check_lib.judge({**values, **ctl}, cfg["check"])[0],
            **ctl)
    result = {"correct": bool(ok), "attempted": len(requests),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = reduced.breakdown()
    result["checks"] = table
    for name, v in table.items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=err, flush=True)
    return result
