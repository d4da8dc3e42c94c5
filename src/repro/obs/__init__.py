"""repro.obs — unified observability: metrics registry + structured tracer.

Level selection is flag-driven and re-read on every accessor call, so code
never caches the wrong object across a ``flags.set_variant``:

* ``flags.FLAGS["obs_level"] == "off"``      -> ``registry()`` is
  ``NULL_REGISTRY``, ``tracer()`` is ``NULL_TRACER`` (both no-op
  singletons; zero allocations on hot paths).
* ``"counters"``                              -> real ``Registry``, null
  tracer.
* ``"trace"``                                 -> real ``Registry`` + real
  ``Tracer``.

The contract (gated by tests/test_obs.py and benchmarks/bench_obs.py): no
observability level may change placement or serving RESULTS — hooks only
read state — and ``"off"`` must be timing-neutral on the serving loop.

``timed(name, **args)`` is the repo-wide timing idiom replacing scattered
``time.perf_counter()`` pairs: it always measures (``.seconds`` is valid
at every obs level, so ``fit_seconds``-style stats keep their values) and
additionally records a trace span when ``obs_level == "trace"``.

At ``"trace"`` the tracer also records one ``jit.compile`` complete event
per program XLA compiles or loads from the persistent cache (JAX's
``backend_compile`` duration event; args ``program``), parented to the
span open at the time.  The listener is registered with
``jax.monitoring`` once, the first time ``tracer()`` returns the live
tracer, and checks the level on every event.
"""

from __future__ import annotations

import time

from .. import flags as _flags
from .registry import (Registry, NullRegistry, NULL_REGISTRY,
                       DEFAULT_BUCKETS, parse_prom_text)
from .trace import Tracer, NullTracer, NULL_TRACER, NULL_SPAN
from .timeseries import SeriesRing, TimeSeriesStore
from .health import SLORule, Alert, HealthMonitor
from .analyze import (load_events, build_span_tree, aggregate_spans,
                      critical_path, top_slowest, render_report)

__all__ = [
    "Registry", "NullRegistry", "NULL_REGISTRY", "DEFAULT_BUCKETS",
    "parse_prom_text", "Tracer", "NullTracer", "NULL_TRACER", "NULL_SPAN",
    "SeriesRing", "TimeSeriesStore", "SLORule", "Alert", "HealthMonitor",
    "load_events", "build_span_tree", "aggregate_spans", "critical_path",
    "top_slowest", "render_report",
    "level", "registry", "tracer", "reset", "timed",
]

LEVELS = ("off", "counters", "trace")

_REGISTRY = Registry()
_TRACER = Tracer()


def level() -> str:
    """Current ``obs_level`` flag value (validated)."""
    lv = _flags.FLAGS.get("obs_level", "off")
    if lv not in LEVELS:
        raise ValueError(f"unknown obs_level {lv!r}; expected one of {LEVELS}")
    return lv


def registry():
    """The live ``Registry`` at "counters"/"trace", else ``NULL_REGISTRY``."""
    return NULL_REGISTRY if _flags.FLAGS.get("obs_level", "off") == "off" \
        else _REGISTRY


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_listener: list = []


def _on_duration(event: str, duration: float, **kwargs):
    if event != _COMPILE_EVENT:
        return
    tr = tracer()
    if tr.active:
        t1 = time.perf_counter()
        tr.complete("jit.compile", t1 - duration, t1,
                    program=str(kwargs.get("fun_name", "")))


def tracer():
    """The live ``Tracer`` at "trace", else ``NULL_TRACER``."""
    if _flags.FLAGS.get("obs_level", "off") != "trace":
        return NULL_TRACER
    if not _compile_listener:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _compile_listener.append(_on_duration)
    return _TRACER


def reset():
    """Drop all recorded metrics and trace events (flags are untouched)."""
    _REGISTRY.clear()
    _TRACER.clear()


class timed:
    """Always-on timing context manager; trace span when tracing.

    ``with obs.timed("fit.place", algorithm="lmbr") as t: ...`` then read
    ``t.seconds``.  Replaces bare ``time.perf_counter()`` pairs so stats
    like ``fit_seconds`` keep identical values at every obs level while
    the same region shows up in the Chrome trace when enabled.
    """

    __slots__ = ("_span", "t0", "seconds")

    def __init__(self, name: str, **args):
        self._span = tracer().span(name, **args)
        self.t0 = 0.0
        self.seconds = 0.0

    def __enter__(self):
        self._span.begin()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.seconds = time.perf_counter() - self.t0
        self._span.end()
        return False
