"""Reduce a profiler trace (``.xplane.pb``) to the traced window's numbers.

Planes and lines read (see PERF.md, "Layers"):

- device: every plane named ``/device:TPU:<n>``.  Its ``XLA Modules`` line
  holds one event per program run, named ``<program>(<id>)``
  (``jit_decompose(…)``, ``jit_coflow_merge_padded(…)``, and one per eager
  op such as ``jit_add(…)``); its ``XLA Ops`` line one event per operation
  inside them.
- host: the ``bench.*`` events that the benchmark's own
  ``TraceAnnotation`` spans write on the host plane (``/host:CPU``).

Busy time is the union of the ``XLA Ops`` intervals clipped to the window,
averaged over the chips; the idle share is 1 - busy / window.  Device
time by program sums the clipped ``XLA Modules`` durations under the
stable name, the event name without its trailing ``(<id>)``.  (Op events
nest, a ``while`` holding its body's ops, so their durations do not add
up; only their union is read.)  Each idle gap is named by the benchmark
span around its midpoint, with ``/compile`` added when one of the run's
XLA compiles (``jax.monitoring``, host clock) covers the midpoint too.

Trace time and host time are tied at the start of the ``bench.window``
span: ``ns = start_ns(bench.window) + (perf - anchor_perf) * 1e9``.
"""
from __future__ import annotations

import re
from collections import defaultdict
from itertools import chain
from dataclasses import dataclass, field

import numpy as np

_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")
WINDOW_SPAN = "bench.window"


def stable_name(name: str) -> str:
    """``jit_decompose(123)`` -> ``jit_decompose``."""
    return _SUFFIX.sub("", name)


@dataclass
class Reduced:
    window_s: float
    busy_s: float                                         # mean over chips
    chips: int
    modules: dict = field(default_factory=dict)   # name -> [runs, seconds]
    gaps: dict = field(default_factory=dict)      # span -> seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union(starts: np.ndarray, ends: np.ndarray):
    """Disjoint (starts, ends) covering the same points as the input."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, dtype=bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    return s[first], np.maximum.reduceat(e, first)


def _line(plane, name: str):
    return next((ln for ln in plane.lines if ln.name == name), None)


def _table(names, seconds) -> dict:
    out: dict = defaultdict(lambda: [0, 0.0])
    for n, s in zip(names, seconds):
        out[n][0] += 1
        out[n][1] += float(s)
    return dict(out)


def reduce(profile, anchor_perf: float, window_perf: tuple[float, float],
           compiles_perf=()) -> Reduced:
    """``profile`` is a ``jax.profiler.ProfileData``; ``window_perf`` the
    window on the ``time.perf_counter`` clock, ``anchor_perf`` the moment
    the ``bench.window`` span opened on that clock, ``compiles_perf`` the
    run's compiles as (end, seconds) on that clock."""
    spans, anchor_ns = [], None
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    a = float(e.start_ns)
                    anchor_ns = a if anchor_ns is None else min(anchor_ns, a)
                elif e.name.startswith("bench."):
                    spans.append((float(e.start_ns),
                                  float(e.start_ns + e.duration_ns),
                                  e.name[len("bench."):]))
    if anchor_ns is None:
        raise ValueError(f"the profile has no {WINDOW_SPAN!r} span")

    def ns(perf: float) -> float:
        return anchor_ns + (perf - anchor_perf) * 1e9

    lo, hi = ns(window_perf[0]), ns(window_perf[1])
    devices = [p for p in profile.planes
               if re.fullmatch(r"/device:TPU:\d+", p.name)]
    if not devices:
        raise ValueError("the profile has no TPU device plane")
    out = Reduced(window_s=(hi - lo) / 1e9, busy_s=0.0, chips=len(devices))
    all_s, all_e = [], []
    names, secs = [], []
    for plane in devices:
        for e in _line(plane, "XLA Modules").events:
            a = min(max(e.start_ns, lo), hi)
            b = min(max(e.start_ns + e.duration_ns, lo), hi)
            if b > a:
                names.append(stable_name(e.name))
                secs.append((b - a) / 1e9)
        # millions of op events: one pass, straight into an array
        ops = np.fromiter(chain.from_iterable(
            (e.start_ns, e.duration_ns)
            for e in _line(plane, "XLA Ops").events),
            dtype=np.float64).reshape(-1, 2)
        o_start, o_end = ops[:, 0], ops[:, 0] + ops[:, 1]
        c_start, c_end = np.clip(o_start, lo, hi), np.clip(o_end, lo, hi)
        keep = c_end > c_start
        s, e = union(c_start[keep], c_end[keep])
        out.busy_s += float((e - s).sum()) / 1e9
        all_s.append(s)
        all_e.append(e)
    out.busy_s /= len(devices)
    out.modules = _table(names, secs)

    # idle gaps: the complement of the busy union inside the window
    s, e = union(np.concatenate(all_s), np.concatenate(all_e))
    g_start = np.concatenate([[lo], e])
    g_end = np.concatenate([s, [hi]])
    gap = g_end - g_start > 1.0     # under a nanosecond is clock rounding
    g_start, g_end = g_start[gap], g_end[gap]
    mid = (g_start + g_end) / 2
    spans.sort()
    sp_start = np.array([x[0] for x in spans], dtype=np.float64)
    sp_end = np.array([x[1] for x in spans], dtype=np.float64)
    k = np.searchsorted(sp_start, mid, side="right") - 1
    held = (k >= 0) & (mid <= sp_end[np.maximum(k, 0)]) if spans else \
        np.zeros(mid.size, dtype=bool)
    c_s = np.array([ns(end - dur) for end, dur in compiles_perf], np.float64)
    c_e = np.array([ns(end) for end, _ in compiles_perf], np.float64)
    c_s, c_e = union(c_s, c_e)
    j = np.searchsorted(c_s, mid, side="right") - 1
    compiling = (j >= 0) & (mid <= c_e[np.maximum(j, 0)]) if c_s.size else \
        np.zeros(mid.size, dtype=bool)
    labels = sorted({x[2] for x in spans}) + ["outside_spans"]
    code = np.array([labels.index(x[2]) for x in spans] or [0], dtype=np.int64)
    which = np.where(held, code[np.maximum(k, 0)], len(labels) - 1)
    key = which * 2 + compiling.astype(np.int64)
    secs = np.bincount(key, weights=(g_end - g_start) / 1e9,
                       minlength=2 * len(labels))
    for i, v in enumerate(secs):
        if v > 0:
            out.gaps[labels[i // 2] + ("/compile" if i % 2 else "")] = float(v)
    return out


def top(table: dict, n: int = 10) -> list[list]:
    """The n largest entries of a name -> seconds (or [count, seconds])
    table, as [name, seconds] pairs."""
    items = [(k, v[1] if isinstance(v, list) else v) for k, v in table.items()]
    return [[k, v] for k, v in sorted(items, key=lambda kv: -kv[1])[:n]]
