"""What every run shares: the cell's files, the clock, spans, compile
counting, the profiler, and the record a driver hands back.

A driver (``bench/drivers/<name>.py``) gets a ``Context`` and returns a
``Run``.  It marks the window with ``ctx.open_window()`` and
``ctx.close_window()``, wraps its calls into the program in
``ctx.span(name)``, and records each answered request with
``ctx.answered(due, done)``.  The metric readers
(``bench/metrics/<name>.py``) read only the ``Run``.
"""
from __future__ import annotations

import contextlib
import json
import re
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``; the name is checked before it is used
    as a path."""
    if not NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    return json.loads((BENCH / kind / f"{name}.json").read_text())


@dataclass
class Run:
    """What a driver measured.  Times are ``time.perf_counter()`` seconds."""

    setup_s: float = 0.0
    window: tuple[float, float] = (0.0, 0.0)    # start, last counted answer
    requests: list[tuple[float, float]] = field(default_factory=list)
    attempted: int = 0
    spans: list[tuple[str, float, float]] = field(default_factory=list)
    compiles: list[tuple[float, float]] = field(default_factory=list)
    counters: dict = field(default_factory=dict)  # name -> (start, end)
    checks: list[tuple[str, float, float]] = field(default_factory=list)
    failed: int = 0
    memory_peak_bytes: int | None = None
    notes: dict = field(default_factory=dict)
    trace: object = None                          # lib.trace.Reduced

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim for _, v, lim in self.checks)

    def in_window(self, t0: float, t1: float) -> bool:
        return self.window[0] <= t0 and t1 <= self.window[1]


class Context:
    """One run's inputs and instruments."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, trace_dir: Path | None, t_process: float):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = seed, seconds
        self.trace_dir = trace_dir
        self.run = Run()
        self._t_process = t_process
        self._hit_pending = False
        self._deadline = None
        import jax.monitoring as monitoring

        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    # jax.monitoring: a persistent-cache read also reports a backend
    # compile duration, right after its cache-hit event; only the others
    # are compiles
    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self._hit_pending = True

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event != COMPILE_EVENT:
            return
        if self._hit_pending:
            self._hit_pending = False
            return
        self.run.compiles.append((time.perf_counter(), duration))

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span, also written into the profile when tracing."""
        if self.trace_dir is not None:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.run.spans.append((name, t0, time.perf_counter()))

    def open_window(self) -> None:
        """Set-up ends here; in a traced run the profiler starts."""
        self._window_ann = None
        if self.trace_dir is not None:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1      # the benchmark's spans, not XLA's
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._window_ann = jax.profiler.TraceAnnotation("bench.window")
        now = time.perf_counter()
        self.run.setup_s = time.time() - self._t_process
        self.run.window = (now, now)
        self._deadline = now + self.seconds
        if self._window_ann is not None:
            self.run.notes["window_anchor"] = time.perf_counter()
            self._window_ann.__enter__()

    def window_open(self) -> bool:
        return time.perf_counter() < self._deadline

    def answered(self, due: float, done: float) -> bool:
        """Record one request; False for the one in flight at the close,
        which is left out.  A traced window closes after the traffic's
        ``trace_requests`` answers: the profile of a whole window would
        outgrow the run's time limit."""
        if done > self._deadline:
            return False
        self.run.requests.append((due, done))
        self.run.window = (self.run.window[0], done)
        if self.trace_dir is not None and \
                len(self.run.requests) >= self.traffic["trace_requests"]:
            self._deadline = done
        return True

    def close_window(self) -> None:
        import jax
        import jax.monitoring as monitoring

        if self.trace_dir is not None:
            self._window_ann.__exit__(None, None, None)
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.run.notes["trace_collect_s"] = round(
                time.perf_counter() - t0, 1)
        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_event_duration_listener(self._on_duration)
        if not self.run.requests:
            raise RuntimeError("the window answered no request")

