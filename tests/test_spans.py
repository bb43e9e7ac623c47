"""In-program spans and compile counters (core/spans.py).

Pins: off, ``span()`` is one shared null object and nothing is recorded
or registered; nesting, parent indices, request inheritance and self-time
arithmetic on a hand-timed tree; an eager op's compile booked against the
innermost span with its ``fun_name``; the layer spans a ``StreamDriver``
records, one request id per fed job; completions equal with recording on
and off; and every span sitting at its layer boundary, in host code that
no jit stages.
"""
import ast
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import (StreamDriver, clear_caches, run_stream, spans,
                        stream_jobs)

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


def _listeners():
    from jax._src import monitoring   # the public module cannot list them

    return (len(monitoring.get_event_listeners()),
            len(monitoring.get_event_duration_listeners()))


class _Clock:
    """A perf_counter that reads the times it is given, in order."""

    def __init__(self, *ticks):
        self.ticks = list(ticks)

    def perf_counter(self):
        return self.ticks.pop(0)


# --- off --------------------------------------------------------------------

def test_off_is_one_null_object_and_registers_nothing():
    before = _listeners()
    a = spans.span("plan.order")
    b = spans.span("stream.feed", request=3, extra=1)
    assert a is b is spans._NULL
    with a as sp:
        sp.set(hit=True)
        jax.numpy.asarray(np.arange(7919, dtype=np.int32)).sum()
    assert spans._rec is None
    assert _listeners() == before


def test_recording_registers_only_while_on():
    before = _listeners()
    with spans.recording() as rec:
        assert _listeners() == (before[0] + 1, before[1] + 1)
        with pytest.raises(RuntimeError, match="already on"):
            spans.start()
    assert _listeners() == before
    assert spans._rec is None
    assert spans.span("plan.group") is spans._NULL
    with pytest.raises(RuntimeError, match="no recording"):
        spans.stop()
    assert rec.spans == [] and rec.compiles == []


# --- a hand-timed tree ------------------------------------------------------

def test_nesting_requests_and_self_time(monkeypatch):
    # feed [0, 10) > replan [1, 9) > (repair [2, 3), plan [4, 8) > order
    # [5, 6)), a compile of 1.5 s ending at 7 inside plan; then a root
    # without a request, and one that sets its own
    monkeypatch.setattr(spans, "time", _Clock(
        0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 20, 21, 30, 31, 32, 33))
    with spans.recording() as rec:
        with spans.span("stream.feed", request=7):
            with spans.span("session.replan") as sp:
                with spans.span("session.repair"):
                    pass
                with spans.span("session.plan"):
                    with spans.span("plan.order", hit=False):
                        pass
                    rec._on_duration(spans.COMPILE_EVENT, 1.5,
                                     fun_name="jit(add)")
                    rec._on_event(spans.CACHE_HIT_EVENT)
                    rec._on_duration(spans.COMPILE_EVENT, 0.9,
                                     fun_name="jit(cached)")   # a cache read
                sp.set(outcome="full")
        with spans.span("plan.merge_fix"):
            pass
        with spans.span("stream.feed", request=8):
            with spans.span("plan.merge_fix"):
                pass
    names = [s.name for s in rec.spans]
    assert names == ["stream.feed", "session.replan", "session.repair",
                     "session.plan", "plan.order", "plan.merge_fix",
                     "stream.feed", "plan.merge_fix"]
    assert [s.parent for s in rec.spans] == [None, 0, 1, 1, 3, None, None, 6]
    assert [s.request for s in rec.spans] == [7] * 5 + [None, 8, 8]
    assert rec.spans[1].attrs == {"outcome": "full"}
    assert rec.spans[4].attrs == {"hit": False}
    assert [(s.start, s.end) for s in rec.spans] == [
        (0, 10), (1, 9), (2, 3), (4, 8), (5, 6), (20, 21), (30, 33),
        (31, 32)]
    assert len(rec.compiles) == 1
    c = rec.compiles[0]
    assert (c.fun_name, c.seconds, c.end, c.span) == ("jit(add)", 1.5, 7, 3)
    assert rec.self_times() == [2, 3, 1, 4 - 1 - 1.5, 1, 1, 2, 1]
    assert [rec.outermost(i) for i in range(8)] == [True] * 8
    assert rec.within(4, "session.replan") and not rec.within(5, "stream.feed")


def test_nested_span_of_the_same_name_is_not_outermost():
    with spans.recording() as rec:
        with spans.span("plan.merge_fix"):
            with spans.span("plan.group_block"):
                with spans.span("plan.merge_fix"):
                    pass
    assert [rec.outermost(i) for i in range(3)] == [True, True, False]
    assert rec.within(2, "plan.group_block")


# --- compiles ---------------------------------------------------------------

def test_eager_op_on_a_fresh_shape_is_one_compile_in_its_span():
    x = np.arange(2 * 4973, dtype=np.int32).reshape(2, -1)
    with spans.recording() as rec:
        with spans.span("session.plan"):
            with spans.span("plan.merge_fix"):
                (jax.numpy.asarray(x) * 3).block_until_ready()
    assert len(rec.compiles) == 1
    c = rec.compiles[0]
    assert "multiply" in c.fun_name
    assert rec.spans[c.span].name == "plan.merge_fix"
    assert c.seconds > 0
    assert rec.spans[1].start <= c.end - c.seconds and \
        c.end <= rec.spans[1].end
    assert rec.self_times()[1] == pytest.approx(
        rec.spans[1].seconds - c.seconds)


# --- the planning path ------------------------------------------------------

def _stream(n=12):
    return stream_jobs(12, n, seed=5, process="poisson", load=0.9, mu=3)


def test_stream_driver_records_the_layers_per_request():
    jobs = _stream()
    clear_caches()
    drv = StreamDriver(12, "gdm", delays="spread", seed=0)
    with spans.recording() as rec:
        for j in jobs:
            drv.feed(j)
    roots = [i for i, s in enumerate(rec.spans) if s.parent is None]
    assert [rec.spans[i].name for i in roots] == ["stream.feed"] * len(jobs)
    assert [rec.spans[i].request for i in roots] == [j.jid for j in jobs]
    root_of = {}
    for i, s in enumerate(rec.spans):
        assert s.name in spans.NAMES
        assert s.end is not None and s.start <= s.end
        if s.parent is not None:
            p = rec.spans[s.parent]
            assert p.start <= s.start and s.end <= p.end
            root_of[i] = root_of[s.parent]
        else:
            root_of[i] = i
        assert s.request == rec.spans[root_of[i]].request
    replans = [s for s in rec.spans if s.name == "session.replan"]
    assert len(replans) == len(jobs)
    assert all(rec.spans[s.parent].name == "stream.feed" for s in replans)
    assert {s.attrs["outcome"] for s in replans} <= {"repair", "full"}
    inside = {s.name for s in rec.spans
              if s.parent is not None
              and rec.spans[s.parent].name == "session.replan"}
    assert inside == {"session.repair", "session.plan"}
    names = {s.name for s in rec.spans}
    assert {"plan.order", "plan.group", "plan.group_block",
            "plan.decompose", "plan.merge_fix"} <= names
    assert all(rec.within(i, "session.replan")
               for i, s in enumerate(rec.spans) if s.name.startswith("plan."))
    assert len(rec.spans) < 40 * len(jobs)
    assert all(t > -1e-9 for t in rec.self_times())


@pytest.mark.parametrize("scheduler", ["gdm", "gdm_rt"])
def test_completions_equal_with_recording_on_and_off(scheduler):
    jobs = _stream(10)
    clear_caches()
    off = run_stream(jobs, 12, scheduler, delays="spread", seed=0)
    clear_caches()
    with spans.recording(annotate=True) as rec:
        on = run_stream(jobs, 12, scheduler, delays="spread", seed=0)
    assert rec.spans
    assert on.online.job_completions == off.online.job_completions
    assert on.online.twct() == off.online.twct()


# --- where the spans sit ----------------------------------------------------

# (module, function, span) for every span the program opens
WHERE = {
    ("core/stream.py", "feed", "stream.feed"),
    ("core/session.py", "_ensure_plan", "session.replan"),
    ("core/session.py", "_ensure_plan", "session.repair"),
    ("core/session.py", "_ensure_plan", "session.plan"),
    ("core/ordering.py", "cached_job_order", "plan.order"),
    ("core/gdm.py", "group_jobs", "plan.group"),
    ("core/backend.py", "group_block", "plan.group_block"),
    ("core/backend.py", "bna_pieces_many", "plan.decompose"),
    ("core/pipeline.py", "_plan_decompositions", "plan.decompose"),
    ("core/timeline.py", "merge_and_fix", "plan.merge_fix"),
    ("core/timeline.py", "_decompose", "plan.nested_decompose"),
}


def _span_calls(tree):
    """(innermost enclosing function, span name) of every ``span(...)``."""
    out = []

    def walk(node, fn):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.Lambda)):
                walk(child, child)
                continue
            if isinstance(child, ast.Call) and \
                    isinstance(child.func, ast.Name) and \
                    child.func.id == "span":
                out.append((fn, child.args[0].value))
            walk(child, fn)

    walk(tree, None)
    return out


def test_every_span_sits_at_its_layer_boundary_outside_jit():
    from repro.analysis.rules._util import import_aliases
    from repro.analysis.rules.purity import _jitted_functions

    found = set()
    for path in sorted(SRC.rglob("*.py")):
        rel = path.relative_to(SRC).as_posix()
        if rel == "core/spans.py":
            continue
        tree = ast.parse(path.read_text())
        calls = _span_calls(tree)
        if not calls:
            continue
        jitted = {id(fn) for fn, _ in
                  _jitted_functions(tree, import_aliases(tree))}
        top = {id(n) for n in tree.body if isinstance(n, ast.FunctionDef)}
        top |= {id(n) for c in tree.body if isinstance(c, ast.ClassDef)
                for n in c.body if isinstance(n, ast.FunctionDef)}
        for fn, name in calls:
            assert fn is not None and id(fn) in top, (rel, name)
            assert id(fn) not in jitted, (rel, fn.name)
            found.add((rel, fn.name, name))
    assert found == WHERE
    assert {name for _, _, name in WHERE} == set(spans.NAMES)


def test_annotate_sets_the_innermost_open_span():
    """``annotate`` hands attributes from code below a layer to the span
    that layer opened, the innermost one open; off, it does nothing."""
    spans.annotate(k_pad=8)                  # off: no recording to touch
    with spans.recording() as rec:
        with spans.span("plan.merge_fix", K=3):
            spans.annotate(k_pad=16)
            with spans.span("plan.decompose"):
                spans.annotate(coflows=2)
        spans.annotate(k_pad=32)             # no span open
    assert rec.spans[0].attrs == {"K": 3, "k_pad": 16}
    assert rec.spans[1].attrs == {"coflows": 2}
