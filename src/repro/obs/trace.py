"""Structured trace recorder producing Chrome-trace JSON and JSONL.

``Tracer`` records flat event dicts in the Chrome trace-event format
(https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU):

* ``span("fit.hpa", k=8)`` — a context manager emitting one complete
  ("ph": "X") event on exit, with microsecond ``ts``/``dur`` relative to
  the tracer's epoch.  ``span(...).begin()`` / ``.end(**args)`` is the
  same span for a site that cannot indent a block, and ``set(**args)``
  adds args known only inside the span.
* ``event("drift.fire")`` — an instant ("i") event.
* ``counter("online", served=..., inflight=...)`` — a counter ("C")
  event; Perfetto renders these as stacked time series.
* ``complete(name, t0, t1, **args)`` — an explicit complete event from
  two ``time.perf_counter()`` stamps, for work that does not nest as a
  ``with`` block (e.g. a migration transfer that starts in one
  ``advance()`` call and lands in a later one).

The tracer keeps a stack of open spans.  Every complete and instant event
carries three args besides its own: ``id`` (unique within the tracer),
``parent`` (the id of the enclosing open span, or null) and ``fit`` (the
sequence number, from 1, of the enclosing top-level ``service.*`` request
span, or null).  A ``complete`` event's parent is the innermost open span
that began before its ``t0``.  Counter events carry only their series.

Each span also opens a ``jax.profiler.TraceAnnotation`` of the same name,
so it lands on the host plane of any profiler trace being captured, on
the clock of the device ops.  JAX is imported on the first span.

``to_chrome_trace()`` serialises to the JSON object format that
chrome://tracing and https://ui.perfetto.dev load directly;
``to_jsonl()`` emits one event per line for streaming consumers.

``NULL_TRACER`` implements the same surface as no-ops (``span`` returns a
shared no-op span), so hot paths pay one attribute check when
``flags.obs_level != "trace"``.
"""

from __future__ import annotations

import json
import time

__all__ = ["Tracer", "NullTracer", "NULL_TRACER", "NULL_SPAN"]

REQUEST_PREFIX = "service."

_ANNOTATION = None


def _annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation`` named ``name``."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    ann = _ANNOTATION(name)
    ann.__enter__()
    return ann


class _Span:
    """One open span of a ``Tracer``: a context manager, or ``begin()`` /
    ``end()``; emits one complete event when it ends."""

    __slots__ = ("_tracer", "name", "args", "t0", "id", "parent", "fit",
                 "_ann")

    def __init__(self, tracer, name, args):
        self._tracer = tracer
        self.name = name
        self.args = args

    def begin(self) -> "_Span":
        tr = self._tracer
        top = tr._stack[-1] if tr._stack else None
        tr._next_id += 1
        self.id = tr._next_id
        self.parent = top.id if top is not None else None
        self.fit = top.fit if top is not None else None
        if self.fit is None and self.name.startswith(REQUEST_PREFIX):
            tr._fits += 1
            self.fit = tr._fits
        self._ann = _annotation(self.name)
        self.t0 = time.perf_counter()
        tr._stack.append(self)
        return self

    def set(self, **args) -> None:
        """Add args to the event this span will emit."""
        self.args.update(args)

    def end(self, **args) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self.args.update(args)
        stack = self._tracer._stack
        # spans a raised exception left open above this one end with it
        while stack and stack.pop() is not self:
            pass
        self._tracer._record(self.name, self.t0, t1, self.id, self.parent,
                             self.fit, self.args)

    def __enter__(self):
        return self.begin()

    def __exit__(self, exc_type, exc, tb):
        self.end()
        return False


class Tracer:
    active = True

    def __init__(self, pid: int = 0):
        self.pid = pid
        self.events: list = []
        self.epoch = time.perf_counter()
        self._stack: list[_Span] = []
        self._next_id = 0
        self._fits = 0

    def _us(self, t_pc: float) -> float:
        return (t_pc - self.epoch) * 1e6

    def _record(self, name, t0, t1, sid, parent, fit, args):
        self.events.append({
            "name": name, "ph": "X", "ts": self._us(t0),
            "dur": (t1 - t0) * 1e6, "pid": self.pid, "tid": 0,
            "args": {**args, "id": sid, "parent": parent, "fit": fit},
        })

    # -- recording -------------------------------------------------------
    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args)

    def complete(self, name: str, t0: float, t1: float, **args):
        """Complete event from two ``time.perf_counter()`` stamps; its
        parent is the innermost open span that began before ``t0``."""
        encl = next((s for s in reversed(self._stack) if s.t0 <= t0), None)
        self._next_id += 1
        self._record(name, t0, t1, self._next_id,
                     encl.id if encl is not None else None,
                     encl.fit if encl is not None else None, args)

    def event(self, name: str, **args):
        top = self._stack[-1] if self._stack else None
        self._next_id += 1
        self.events.append({
            "name": name, "ph": "i", "s": "t",
            "ts": self._us(time.perf_counter()), "pid": self.pid, "tid": 0,
            "args": {**args, "id": self._next_id,
                     "parent": top.id if top is not None else None,
                     "fit": top.fit if top is not None else None},
        })

    def counter(self, name: str, **values):
        self.events.append({
            "name": name, "ph": "C",
            "ts": self._us(time.perf_counter()), "pid": self.pid, "tid": 0,
            "args": values,
        })

    # -- export ----------------------------------------------------------
    def to_chrome_trace(self) -> str:
        return json.dumps(
            {"traceEvents": self.events, "displayTimeUnit": "ms"}
        )

    def to_jsonl(self) -> str:
        return "\n".join(json.dumps(e) for e in self.events)

    def spans(self, name: str | None = None) -> list:
        """Complete ("X") events, optionally filtered by exact name."""
        return [e for e in self.events
                if e["ph"] == "X" and (name is None or e["name"] == name)]

    def clear(self):
        self.events.clear()
        self.epoch = time.perf_counter()
        self._stack.clear()
        self._next_id = 0
        self._fits = 0


class _NullSpan:
    __slots__ = ()

    def begin(self):
        return self

    def set(self, **args):
        pass

    def end(self, **args):
        pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NULL_SPAN = _NullSpan()


class NullTracer:
    """No-op stand-in for ``Tracer`` when tracing is disabled."""

    active = False
    events = ()

    def span(self, name: str, **args):
        return NULL_SPAN

    def complete(self, name: str, t0: float, t1: float, **args):
        pass

    def event(self, name: str, **args):
        pass

    def counter(self, name: str, **values):
        pass

    def to_chrome_trace(self) -> str:
        return '{"traceEvents": []}'

    def to_jsonl(self) -> str:
        return ""

    def spans(self, name: str | None = None) -> list:
        return []

    def clear(self):
        pass


NULL_TRACER = NullTracer()
