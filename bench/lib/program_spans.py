"""Read the program's own spans (``repro.core.spans``) beside a traced run.

The program marks its layer boundaries with spans and books each XLA
compile to the innermost open one.  A recording started with
``annotate=True`` once the profiler is on also writes every span as a
``repro.<name>`` event on the profile's host plane, on the device planes'
clock.  This module reads both:

- from the profile (``reduce``): the window's idle gaps under longer
  labels, the benchmark span, then the innermost program span, then
  ``/compile`` when a compile covers the gap (``feed/compile`` becomes,
  say, ``feed/plan.merge_fix/compile``); the device time of the programs
  that start inside each program span; and the device time of the
  programs that start inside a ``repro.plan.merge_fix`` event, the
  eager scatters, pads and slices of merge-and-fix as well as its kernel.
  A profile with no ``repro.*`` event gives exactly the labels of
  ``bench.lib.trace.reduce``.
- from the recorder (``METRICS``, ``notes``): the per-layer numbers, each
  per answered request in the window, and the compiles by span and
  ``fun_name``.

The gaps are recomputed here the way ``bench.lib.trace.reduce`` computes
them.  ``bench/tests/spans_at_size.py`` runs a cell with the recording
on and prints these; ``bench/run.py`` does not call this module.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from itertools import chain

import numpy as np

from bench.lib import trace

PREFIX = "repro."


@dataclass
class ProgramTrace:
    gaps: dict = field(default_factory=dict)        # label -> seconds
    device_by_span: dict = field(default_factory=dict)  # span -> seconds
    merge_fix_device_s: float = 0.0
    events: int = 0                 # repro.* host events in the window


def _host_events(profile):
    """(anchor_ns, bench spans, program spans), each span (start, end,
    name), the prefix taken off."""
    bench, program, anchor = [], [], None
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
                if e.name == trace.WINDOW_SPAN:
                    anchor = a if anchor is None else min(anchor, a)
                elif e.name.startswith("bench."):
                    bench.append((a, b, e.name[len("bench."):]))
                elif e.name.startswith(PREFIX):
                    program.append((a, b, e.name[len(PREFIX):]))
    if anchor is None:
        raise ValueError(f"the profile has no {trace.WINDOW_SPAN!r} span")
    return anchor, bench, program


def innermost(points: np.ndarray, spans: list) -> np.ndarray:
    """Per sorted point, the index into ``spans`` of the innermost span
    holding it, -1 for none.  Spans nest (one thread's stack), so the one
    that started last among those holding a point is the innermost: each
    span, in order of start, overwrites its own range."""
    out = np.full(points.size, -1, dtype=np.int64)
    for k in sorted(range(len(spans)), key=lambda k: (spans[k][0],
                                                       -spans[k][1])):
        a, b = spans[k][0], spans[k][1]
        out[np.searchsorted(points, a, side="left"):
            np.searchsorted(points, b, side="right")] = k
    return out


def reduce(profile, anchor_perf: float, window_perf: tuple[float, float],
           compiles_perf=()) -> ProgramTrace:
    """The arguments are those of ``bench.lib.trace.reduce``."""
    anchor_ns, bench, program = _host_events(profile)

    def ns(perf: float) -> float:
        return anchor_ns + (perf - anchor_perf) * 1e9

    lo, hi = ns(window_perf[0]), ns(window_perf[1])
    devices = [p for p in profile.planes
               if re.fullmatch(r"/device:TPU:\d+", p.name)]
    if not devices:
        raise ValueError("the profile has no TPU device plane")
    out = ProgramTrace(events=sum(1 for a, b, _ in program
                                  if lo <= a and b <= hi))
    all_s, all_e, mod_start, mod_s = [], [], [], []
    for plane in devices:
        for e in trace._line(plane, "XLA Modules").events:
            a = min(max(e.start_ns, lo), hi)
            b = min(max(e.start_ns + e.duration_ns, lo), hi)
            if b > a:
                mod_start.append(float(e.start_ns))
                mod_s.append((b - a) / 1e9)
        ops = np.fromiter(chain.from_iterable(
            (e.start_ns, e.duration_ns)
            for e in trace._line(plane, "XLA Ops").events),
            dtype=np.float64).reshape(-1, 2)
        c_start = np.clip(ops[:, 0], lo, hi)
        c_end = np.clip(ops[:, 0] + ops[:, 1], lo, hi)
        keep = c_end > c_start
        s, e = trace.union(c_start[keep], c_end[keep])
        all_s.append(s)
        all_e.append(e)

    # device time by the innermost program span a program started in
    order = np.argsort(mod_start, kind="stable")
    starts = np.asarray(mod_start, dtype=np.float64)[order]
    secs = np.asarray(mod_s, dtype=np.float64)[order]
    where = innermost(starts, program)
    table: dict = defaultdict(float)
    for k, v in zip(where, secs):
        if k >= 0:
            table[program[k][2]] += float(v)
    out.device_by_span = dict(table)
    m_s, m_e = trace.union(
        np.array([a for a, _, n in program if n == "plan.merge_fix"]),
        np.array([b for _, b, n in program if n == "plan.merge_fix"]))
    j = np.searchsorted(m_s, starts, side="right") - 1
    inside = (j >= 0) & (starts <= m_e[np.maximum(j, 0)]) if m_s.size else \
        np.zeros(starts.size, dtype=bool)
    out.merge_fix_device_s = float(secs[inside].sum())

    # idle gaps: the complement of the busy union inside the window
    s, e = trace.union(np.concatenate(all_s), np.concatenate(all_e))
    g_start = np.concatenate([[lo], e])
    g_end = np.concatenate([s, [hi]])
    gap = g_end - g_start > 1.0     # under a nanosecond is clock rounding
    g_start, g_end = g_start[gap], g_end[gap]
    mid = (g_start + g_end) / 2
    c_s, c_e = trace.union(
        np.array([ns(end - dur) for end, dur in compiles_perf], np.float64),
        np.array([ns(end) for end, _ in compiles_perf], np.float64))
    j = np.searchsorted(c_s, mid, side="right") - 1
    compiling = (j >= 0) & (mid <= c_e[np.maximum(j, 0)]) if c_s.size else \
        np.zeros(mid.size, dtype=bool)
    in_bench = innermost(mid, bench)
    in_program = innermost(mid, program)
    gaps: dict = defaultdict(float)
    for kb, kp, c, d in zip(in_bench, in_program, compiling,
                            (g_end - g_start) / 1e9):
        label = bench[kb][2] if kb >= 0 else "outside_spans"
        if kp >= 0:
            label += "/" + program[kp][2]
        gaps[label + ("/compile" if c else "")] += float(d)
    out.gaps = dict(gaps)
    return out


# --- the per-layer numbers, per answered request in the window; None -------
# --- without a recording ----------------------------------------------------

def _in_window(run, rec) -> list[int]:
    w0, w1 = run.window
    return [i for i, s in enumerate(rec.spans)
            if s.end is not None and w0 <= s.start and s.end <= w1]


def _per_request(run, seconds: float) -> float:
    return seconds / len(run.requests) * 1e3


def _self_ms(run, rec, names) -> float | None:
    if rec is None:
        return None
    own = rec.self_times()
    return _per_request(run, sum(own[i] for i in _in_window(run, rec)
                                 if rec.spans[i].name in names))


def _outer_ms(run, rec, name) -> float | None:
    if rec is None:
        return None
    return _per_request(run, sum(
        rec.spans[i].seconds for i in _in_window(run, rec)
        if rec.spans[i].name == name and rec.outermost(i)))


def _window_compiles(run, rec) -> list:
    w0, w1 = run.window
    return [c for c in rec.compiles if w0 <= c.end <= w1]


def replan_self_ms(run, rec, ptrace):
    """Self time of the session's spans: sub-instance, repair
    certification, epoch building (ms)."""
    return _self_ms(run, rec, ("session.replan", "session.repair",
                               "session.plan"))


def order_ms(run, rec, ptrace):
    """Self time of Algorithm 5 (loads included) and grouping (ms)."""
    return _self_ms(run, rec, ("plan.order", "plan.group"))


def decompose_ms(run, rec, ptrace):
    """Wall of the outermost batched decompositions: host, device wait
    and compiles (ms)."""
    return _outer_ms(run, rec, "plan.decompose")


def merge_fix_ms(run, rec, ptrace):
    """Wall of the outermost merge-and-fix spans (ms)."""
    return _outer_ms(run, rec, "plan.merge_fix")


def merge_fix_compile_ms(run, rec, ptrace):
    """Compile seconds booked inside merge-and-fix spans (ms)."""
    if rec is None:
        return None
    return _per_request(run, sum(
        c.seconds for c in _window_compiles(run, rec)
        if c.span is not None and rec.within(c.span, "plan.merge_fix")))


def merge_fix_device_ms(run, rec, ptrace):
    """Device time of the programs that start inside a
    ``repro.plan.merge_fix`` host event (ms)."""
    if rec is None or ptrace is None:
        return None
    return _per_request(run, ptrace.merge_fix_device_s)


METRICS = {f.__name__: f for f in (replan_self_ms, order_ms, decompose_ms,
                                   merge_fix_ms, merge_fix_compile_ms,
                                   merge_fix_device_ms)}


def read(run, rec, ptrace=None) -> dict:
    """name -> value of every metric that has something to read."""
    values = {name: f(run, rec, ptrace) for name, f in METRICS.items()}
    return {k: v for k, v in values.items() if v is not None}


def notes(run, rec, ptrace=None) -> dict:
    """``compiles_by_span``: the top 10 (innermost span, ``fun_name``) with
    count and seconds; ``program_spans_per_request``;
    ``program_compiles_in_window``; ``time_by_span``: per span name its
    count, self seconds, compile seconds and (traced) device seconds."""
    inside = _in_window(run, rec)
    compiles = _window_compiles(run, rec)
    by: dict = defaultdict(lambda: [0, 0.0])
    for c in compiles:
        key = (rec.spans[c.span].name if c.span is not None else "outside",
               c.fun_name)
        by[key][0] += 1
        by[key][1] += c.seconds
    top = sorted(by.items(), key=lambda kv: -kv[1][1])[:10]
    own = rec.self_times()
    table: dict = {}
    for i in inside:
        row = table.setdefault(rec.spans[i].name,
                               {"n": 0, "self_s": 0.0, "compile_s": 0.0})
        row["n"] += 1
        row["self_s"] += own[i]
    for c in compiles:
        if c.span is not None and rec.spans[c.span].name in table:
            table[rec.spans[c.span].name]["compile_s"] += c.seconds
    if ptrace is not None:
        for name, row in table.items():
            row["device_s"] = ptrace.device_by_span.get(name, 0.0)
    return {"compiles_by_span": [[s, f, n, round(t, 4)]
                                 for (s, f), (n, t) in top],
            "program_spans_per_request": len(inside) / len(run.requests),
            "program_compiles_in_window": len(compiles),
            "time_by_span": {k: {kk: round(v, 4) for kk, v in row.items()}
                             for k, row in table.items()}}


def below_replan_share(gaps: dict) -> float | None:
    """Share of the ``feed/…/compile`` idle seconds whose innermost program
    span lies below ``session.replan``."""
    total = below = 0.0
    for label, s in gaps.items():
        parts = label.split("/")
        if parts[0] != "feed" or parts[-1] != "compile":
            continue
        total += s
        if len(parts) == 3 and parts[1] not in ("stream.feed",
                                                "session.replan"):
            below += s
    return below / total if total else None
