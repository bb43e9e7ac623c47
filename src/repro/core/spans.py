"""Spans and compile counters inside the planner.

A *span* marks one layer boundary of the planning path (the names are
listed in ``NAMES``); a *compile* is one XLA backend compile that JAX ran
while a span was open.  Recording is off by default, and then ``span()``
costs one global check and hands back a shared null context::

    from repro.core import spans

    with spans.recording() as rec:
        driver.feed(job)
    for s, own in zip(rec.spans, rec.self_times()):
        print(s.name, s.request, s.seconds, own)
    for c in rec.compiles:             # c.span: index of the innermost span
        print(c.fun_name, c.seconds, c.span)

While a recording is on, spans are kept in memory (times on the
``time.perf_counter`` clock, each with the index of its enclosing span and
the request id inherited from the nearest span that set one) and a
``jax.monitoring`` listener books every backend compile that was not a
persistent-cache read against the innermost open span, as a child interval
of it: a span's *self time* is its duration less its child spans' and its
compiles'.  ``recording(annotate=True)`` also opens a
``jax.profiler.TraceAnnotation("repro.<name>")`` per span, so a profile
taken meanwhile shows the spans on its host plane, on the device planes'
clock.  Nothing is written to disk.

Spans sit at Python call boundaries only: never inside a jitted or Pallas
function (there they would run once, at trace time), and never inside a
per-flow, per-BNA-step or per-cache-lookup loop.  Recording changes no
plan: spans read no planner state.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

__all__ = ["NAMES", "Span", "Compile", "Recorder", "span", "annotate",
           "recording", "start", "stop"]

# the stable span names, one per layer boundary (PERF.md, "Layers")
NAMES = ("stream.feed", "session.replan", "session.repair", "session.plan",
         "plan.order", "plan.group", "plan.group_block", "plan.decompose",
         "plan.merge_fix", "plan.nested_decompose", "plan.coflow_intervals",
         "plan.backfill")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclass
class Span:
    name: str
    start: float
    end: float | None              # None while the span is open
    parent: int | None             # index of the enclosing span
    request: object = None
    attrs: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class Compile:
    fun_name: str
    seconds: float
    end: float                     # time.perf_counter() when it finished
    span: int | None               # innermost open span, None outside all


@dataclass
class Recorder:
    """What one recording collected."""

    annotate: bool = False
    spans: list[Span] = field(default_factory=list)
    compiles: list[Compile] = field(default_factory=list)
    _stack: list[int] = field(default_factory=list, repr=False)
    _hit_pending: bool = field(default=False, repr=False)

    def self_times(self) -> list[float]:
        """Per span: its duration less its child spans' and the compiles
        booked directly against it (seconds)."""
        out = [s.seconds for s in self.spans]
        for s in self.spans:
            if s.parent is not None:
                out[s.parent] -= s.seconds
        for c in self.compiles:
            if c.span is not None:
                out[c.span] -= c.seconds
        return out

    def outermost(self, i: int) -> bool:
        """No enclosing span has the same name (a nested merge-and-fix
        under DMA-RT is inside another)."""
        name, p = self.spans[i].name, self.spans[i].parent
        while p is not None:
            if self.spans[p].name == name:
                return False
            p = self.spans[p].parent
        return True

    def within(self, i: int, name: str) -> bool:
        """Span ``i`` is, or lies inside, a span called ``name``."""
        p = i
        while p is not None:
            if self.spans[p].name == name:
                return True
            p = self.spans[p].parent
        return False

    # jax.monitoring: a persistent-cache read also reports a backend
    # compile duration, right after its cache-hit event; only the others
    # are compiles
    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self._hit_pending = True

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event != COMPILE_EVENT:
            return
        if self._hit_pending:
            self._hit_pending = False
            return
        self.compiles.append(Compile(
            str(kw.get("fun_name", "")), float(duration), time.perf_counter(),
            self._stack[-1] if self._stack else None))


class _Null:
    """What ``span()`` returns while nothing records."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


_NULL = _Null()
_rec: Recorder | None = None


class _Open:
    __slots__ = ("rec", "name", "request", "attrs", "index", "ann")

    def __init__(self, rec: Recorder, name: str, request, attrs: dict):
        self.rec, self.name = rec, name
        self.request, self.attrs = request, attrs

    def __enter__(self):
        rec = self.rec
        parent = rec._stack[-1] if rec._stack else None
        request = self.request
        if request is None and parent is not None:
            request = rec.spans[parent].request
        self.index = len(rec.spans)
        self.ann = None
        if rec.annotate:
            import jax

            self.ann = jax.profiler.TraceAnnotation(f"repro.{self.name}")
            self.ann.__enter__()
        rec.spans.append(Span(self.name, time.perf_counter(), None, parent,
                              request, self.attrs))
        rec._stack.append(self.index)
        return self

    def __exit__(self, *exc) -> bool:
        rec = self.rec
        rec.spans[self.index].end = time.perf_counter()
        rec._stack.pop()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False

    def set(self, **attrs) -> None:
        """Attributes known only once the span's work is done."""
        self.rec.spans[self.index].attrs.update(attrs)


def span(name: str, request=None, **attrs):
    """A context manager marking ``name`` (one of ``NAMES``); ``request``
    tags it and every span inside it.  Off: the shared null context."""
    if _rec is None:
        return _NULL
    return _Open(_rec, name, request, attrs)


def annotate(**attrs) -> None:
    """Attributes for the innermost open span, from code below the layer
    that opened it; nothing while nothing records."""
    if _rec is not None and _rec._stack:
        _rec.spans[_rec._stack[-1]].attrs.update(attrs)


def start(annotate: bool = False) -> Recorder:
    """Turn recording on: spans from here on, and the compile listener."""
    global _rec
    if _rec is not None:
        raise RuntimeError("a recording is already on")
    import jax.monitoring as monitoring

    rec = Recorder(annotate=annotate)
    monitoring.register_event_listener(rec._on_event)
    monitoring.register_event_duration_secs_listener(rec._on_duration)
    _rec = rec
    return rec


def stop() -> Recorder:
    """Turn recording off and hand over what it collected."""
    global _rec
    rec = _rec
    if rec is None:
        raise RuntimeError("no recording is on")
    import jax.monitoring as monitoring

    _rec = None
    monitoring.unregister_event_listener(rec._on_event)
    monitoring.unregister_event_duration_listener(rec._on_duration)
    return rec


@contextlib.contextmanager
def recording(annotate: bool = False):
    """``start()`` ... ``stop()`` around a block; yields the Recorder."""
    rec = start(annotate)
    try:
        yield rec
    finally:
        stop()
