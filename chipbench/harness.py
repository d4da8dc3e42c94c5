"""Drive one benchmark cell: set-up, the measured window, the check
against the plain reference, and the result line.

Everything is found by name:

* ``BENCHMARK.json`` names the cell's configuration and traffic mix;
* ``chipbench/configs/<file>`` (the configuration's ``file``) holds the
  deployment, and its ``generator`` key names ``chipbench/gen/<gen>.py``;
* ``chipbench/traffic/<mix>.json`` holds the mix, and its ``loop`` key names
  ``chipbench/loops/<loop>.py``, which sets up, runs the window and checks;
* ``chipbench/metrics/<metric>.py`` reads one metric from the run.

So a later change adds a configuration, a mix, a cell or a metric as new
files and entries, and edits none of these.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def load_metric(name: str):
    """The reader module of one metric (file names may hold dots)."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end":
            out.append(m)
        else:
            e2e = {x["name"]: x for x in bench["end_to_end"]}[m["moves"]]
            if "workloads" not in e2e or cell in e2e["workloads"]:
                out.append(m)
    return out


class CompileCounter:
    """Counts the programs JAX compiles or loads from its persistent cache
    (one ``backend_compile`` event each) and the persistent-cache hits.
    JAX keeps its listeners for the life of the process, so there is one
    counter per process (`compile_counter`)."""

    def __init__(self):
        import jax

        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, int, float]:
        return self.programs, self.cache_hits, self.seconds


class GcClock:
    """Counts the interpreter's garbage collections per generation, and
    the seconds they stopped the process, while installed."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._t = 0.0

    def _callback(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            g = info["generation"]
            self.count[g] += 1
            self.seconds[g] += time.perf_counter() - self._t

    def __enter__(self):
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)
        return False


_COUNTER: list[CompileCounter] = []


def compile_counter() -> CompileCounter:
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit: the run is
    correct only where every value is at most its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Run:
    """Everything one run knows; loops fill ``state`` and ``window``,
    metric readers read them."""

    cell: str
    seed: int
    seconds: float
    tracing: bool
    config: dict
    traffic: dict
    gen: object
    state: dict = dataclasses.field(default_factory=dict)
    window: dict = dataclasses.field(default_factory=dict)
    setup_s: float = 0.0
    trace: dict | None = None       # trace_reduce.reduce of the window
    spans: list = dataclasses.field(default_factory=list)
    device_kind: str = ""

    def annotate(self, name: str):
        """A host annotation in the profiler's trace, in the traced run;
        ``trace_reduce`` splits the device's idle time by it."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        from .trace_reduce import HOST_PREFIX

        return jax.profiler.TraceAnnotation(HOST_PREFIX + name)

    @staticmethod
    def note(line: str) -> None:
        """An earlier line of the run's output (not the result)."""
        print(line, flush=True)


def build_run(cell: str, seed: int, seconds: float, tracing: bool,
              bench: dict | None = None) -> Run:
    bench = load_benchmark() if bench is None else bench
    entry = workload_entry(bench, cell)
    config = load_config(bench, entry["config"])
    traffic = load_traffic(entry["traffic"])
    gen = importlib.import_module(f"chipbench.gen.{config['generator']}")
    return Run(cell, int(seed), float(seconds), bool(tracing), config,
               traffic, gen)


def _start_profiler():
    import jax

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    return log_dir


def run_cell(run: Run, bench: dict | None = None,
             t_start: float | None = None) -> dict:
    """Set up, measure and check one run; return the result object.  The
    caller has turned JAX's persistent compilation cache on where it wants
    one (``run.py`` does)."""
    import jax

    from repro import flags, obs
    from repro.core.setcover import engine_counters

    from . import trace_reduce

    bench = load_benchmark() if bench is None else bench
    t_start = time.perf_counter() if t_start is None else t_start
    dev = jax.devices()[0]
    run.device_kind = dev.device_kind
    counter = compile_counter()
    flags.reset()
    loop = importlib.import_module(f"chipbench.loops.{run.traffic['loop']}")

    loop.setup(run)
    run.setup_s = time.perf_counter() - t_start
    c0 = counter.snapshot()
    run.note(f"setup: {run.setup_s:.3f} s, {c0[0]} programs compiled or "
             f"loaded ({c0[1]} persistent-cache hits, {c0[2]:.3f} s)")

    log_dir = None
    if run.tracing:
        flags.FLAGS["obs_level"] = "trace"
        obs.reset()
        log_dir = _start_profiler()
    e0 = engine_counters()
    try:
        with GcClock() as gcc, run.annotate("window"):
            loop.window(run)
    finally:
        if run.tracing:
            jax.profiler.stop_trace()
    e1 = engine_counters()
    c1 = counter.snapshot()
    if run.tracing:
        run.spans = list(obs.tracer().events)
        flags.FLAGS["obs_level"] = "off"
        obs.reset()
        run.trace = trace_reduce.reduce(
            trace_reduce.load_xplane(trace_reduce.find_xplane(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
        totals: dict[str, list] = {}
        for e in run.spans:
            if e.get("ph") == "X":
                t = totals.setdefault(e["name"], [0, 0.0])
                t[0] += 1
                t[1] += e["dur"] * 1e-6
        run.note("window: program spans (count, total s) "
                 f"{ {k: (n, round(s, 6)) for k, (n, s) in sorted(totals.items())} }")
    run.window["engine"] = {k: e1[k] - e0[k] for k in e0 if e1[k] != e0[k]}
    run.note(f"window: {c1[0] - c0[0]} programs compiled or loaded inside "
             f"the window (want 0)")
    run.note(f"window: engine counter deltas {run.window['engine']}")
    run.note(f"window: garbage collections per generation {gcc.count}, "
             f"{[round(x, 6) for x in gcc.seconds]} s")
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    run.note(f"device: peak_bytes_in_use {peak}")

    checks = loop.check(run)
    correct = all(c.ok for c in checks)

    kind = "per_layer" if run.tracing else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, run.cell, kind):
        value = load_metric(m["name"]).read(run)
        if value is None:
            if kind == "end_to_end":
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   f"nothing in {run.cell}")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {
        "correct": bool(correct),
        "attempted": int(run.window["attempted"]),
        "failed": int(run.window.get("failed", 0)),
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace_reduce.top(run.trace["ops"]),
            "idle_gaps": trace_reduce.top(run.trace["idle"]),
        }
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def emit(result: dict) -> None:
    """Checks as the last lines of stderr; the result as the last line of
    stdout."""
    print(f"correct: {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
