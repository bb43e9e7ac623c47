"""The program's spans beside a traced run (``bench.lib.program_spans``):
the longer gap labels and the merge-and-fix device time on a profile
built by hand, checked by brute force; the recorded profile, which has no
``repro.*`` event, keeps the labels of ``bench.lib.trace.reduce``; each
per-layer reader on a hand-built run; and a whole run at a small size on
the CPU with the recording on."""
import gzip
import json

import jax
import pytest
from jax.profiler import ProfileData

import repro.core as core
from bench.lib import program_spans as ps
from bench.lib import trace
from bench.lib.harness import ROOT, Run, load_json
from bench.tests import spans_at_size
from bench.tests.test_trace import DATA, _Ev, _Line, _Plane, _Profile
from repro.core.spans import Compile, Recorder, Span

# the window span opens at perf 10.0 = trace 1000 ns; the window is
# [1000, 10000)
HOST = [
    _Ev("bench.window", 1000, 9000),
    _Ev("bench.advance", 1000, 1000),
    _Ev("bench.feed", 2000, 7000),
    _Ev("repro.stream.feed", 2100, 6800),
    _Ev("repro.session.replan", 2200, 6600),
    _Ev("repro.plan.decompose", 2300, 2500),
    _Ev("repro.plan.merge_fix", 5000, 3000),
    _Ev("repro.plan.nested_decompose", 7000, 800),
]
MODULES = [("jit_decompose(7)", 2500, 2000), ("jit_scatter-add(3)", 5200, 300),
           ("jit_coflow_merge_padded(4)", 7100, 200), ("jit_add(5)", 8200, 100)]
WINDOW = (10.0, 10.0 + 9000e-9)
COMPILES = [(10.0 + 5600e-9, 1000e-9)]            # [5600, 6600)


def _profile(host=HOST):
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev(n, a, d) for n, a, d in MODULES]),
        _Line("XLA Ops", [_Ev(f"%op.{i}", a, d)
                          for i, (_, a, d) in enumerate(MODULES)])])
    return _Profile([_Plane("/host:CPU", [_Line("python3", list(host))]), dev])


def _brute_gaps(lo, hi):
    """Label every idle nanosecond's gap by scanning every span."""
    busy = sorted((a, a + d) for _, a, d in MODULES)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    gaps.append((t, hi))
    out = {}
    for a, b in gaps:
        mid = (a + b) / 2

        def holding(prefix):
            hold = [e for e in HOST if e.name.startswith(prefix)
                    and e.name != "bench.window"
                    and e.start_ns <= mid <= e.start_ns + e.duration_ns]
            return min(hold, key=lambda e: e.duration_ns, default=None)

        b_ev, p_ev = holding("bench."), holding("repro.")
        label = b_ev.name[len("bench."):] if b_ev else "outside_spans"
        if p_ev:
            label += "/" + p_ev.name[len("repro."):]
        if any(1000 + (end - 10.0) * 1e9 - dur * 1e9 <= mid
               <= 1000 + (end - 10.0) * 1e9 for end, dur in COMPILES):
            label += "/compile"
        out[label] = out.get(label, 0.0) + (b - a) / 1e9
    return out


def test_gap_labels_by_hand_and_by_brute_force():
    red = ps.reduce(_profile(), 10.0, WINDOW, COMPILES)
    want = {"advance": 1500e-9,                      # [1000, 2500)
            "feed/session.replan": 700e-9,           # [4500, 5200)
            "feed/plan.merge_fix/compile": 1600e-9,  # [5500, 7100)
            "feed/plan.nested_decompose": 900e-9,    # [7300, 8200)
            "outside_spans": 1700e-9}                # [8300, 10000)
    assert red.gaps == pytest.approx(want)
    assert red.gaps == pytest.approx(_brute_gaps(1000, 10000))
    assert red.events == 5
    assert ps.below_replan_share(red.gaps) == 1.0
    assert ps.below_replan_share({"feed/compile": 1.0,
                                  "feed/plan.order/compile": 3.0,
                                  "feed/session.replan/compile": 1.0,
                                  "feed/plan.order": 9.0}) == 0.6
    assert ps.below_replan_share({"advance": 1.0}) is None


def test_merge_fix_device_time_by_brute_force():
    red = ps.reduce(_profile(), 10.0, WINDOW, COMPILES)
    spans = [e for e in HOST if e.name == "repro.plan.merge_fix"]
    want = sum(d for _, a, d in MODULES
               if any(s.start_ns <= a <= s.start_ns + s.duration_ns
                      for s in spans)) / 1e9
    assert red.merge_fix_device_s == pytest.approx(want)
    assert red.merge_fix_device_s == pytest.approx(500e-9)
    assert red.device_by_span == pytest.approx({
        "plan.decompose": 2000e-9, "plan.merge_fix": 300e-9,
        "plan.nested_decompose": 200e-9, "session.replan": 100e-9})


def test_without_program_events_the_labels_are_the_harness_own():
    host = [e for e in HOST if not e.name.startswith("repro.")]
    red = ps.reduce(_profile(host), 10.0, WINDOW, COMPILES)
    assert red.gaps == pytest.approx(
        trace.reduce(_profile(host), 10.0, WINDOW, COMPILES).gaps)
    assert red.events == 0 and red.merge_fix_device_s == 0.0


def test_recorded_profile_keeps_its_labels():
    meta = json.loads((DATA / "small.meta.json").read_text())
    profile = ProfileData.from_serialized_xspace(
        gzip.decompress((DATA / "small.xplane.pb.gz").read_bytes()))
    args = (profile, meta["anchor_perf"], tuple(meta["window_perf"]),
            [tuple(c) for c in meta["compiles_perf"]])
    want = trace.reduce(*args).gaps
    got = ps.reduce(*args)
    assert set(got.gaps) == set(want)
    assert got.gaps == pytest.approx(want, rel=1e-12)
    assert got.events == 0 and got.device_by_span == {}


# --- the per-layer readers ----------------------------------------------------

def _recorded_run():
    """Two requests in the window [0, 10) and what a recording held."""
    S = Span
    spans = [
        S("stream.feed", 0.0, 5.0, None, 1),
        S("session.replan", 0.5, 4.5, 0, 1, {"outcome": "full"}),
        S("session.repair", 0.6, 0.8, 1, 1),
        S("session.plan", 1.0, 4.0, 1, 1),
        S("plan.order", 1.1, 1.3, 3, 1, {"hit": False}),
        S("plan.group", 1.3, 1.4, 3, 1),
        S("plan.decompose", 1.5, 2.0, 3, 1, {"coflows": 4}),
        S("plan.group_block", 2.0, 3.5, 3, 1, {"hit": False}),
        S("plan.merge_fix", 2.1, 3.4, 7, 1),
        S("plan.merge_fix", 2.5, 3.0, 8, 1),
        S("stream.feed", 5.0, 9.0, None, 2),
        S("session.replan", 5.5, 8.5, 10, 2, {"outcome": "repair"}),
        S("session.repair", 5.6, 8.4, 11, 2),
        S("stream.feed", 10.5, 11.0, None, 3),     # after the window
    ]
    compiles = [Compile("jit(scatter-add)", 0.4, 2.9, 9),
                Compile("jit(pad)", 0.2, 2.3, 8),
                Compile("jit_decompose", 0.3, 1.9, 6),
                Compile("jit(add)", 0.1, 10.9, 13)]
    run = Run(window=(0.0, 10.0), requests=[(0.0, 5.0), (5.0, 10.0)])
    return run, Recorder(spans=spans, compiles=compiles)


@pytest.mark.parametrize("metric,want", [
    # self: replans 0.8 + 0.2, repairs 0.2 + 2.8, plan 3.0 - 2.3 = 0.7
    ("replan_self_ms", 4.7 / 2 * 1e3),
    ("order_ms", 0.3 / 2 * 1e3),
    ("decompose_ms", 0.5 / 2 * 1e3),
    ("merge_fix_ms", 1.3 / 2 * 1e3),                # the outermost only
    ("merge_fix_compile_ms", 0.6 / 2 * 1e3),
    ("merge_fix_device_ms", 0.05 / 2 * 1e3),
])
def test_each_reader_by_hand(metric, want):
    run, rec = _recorded_run()
    ptrace = ps.ProgramTrace(merge_fix_device_s=0.05)
    reader = ps.METRICS[metric]
    assert reader(run, None, None) is None          # not traced
    assert reader(run, rec, ptrace) == pytest.approx(want)
    assert ps.read(run, None) == {}
    assert ps.read(run, rec, ptrace)[metric] == pytest.approx(want)


def test_notes_by_hand():
    run, rec = _recorded_run()
    notes = ps.notes(run, rec)
    assert notes["compiles_by_span"] == [
        ["plan.merge_fix", "jit(scatter-add)", 1, 0.4],
        ["plan.decompose", "jit_decompose", 1, 0.3],
        ["plan.merge_fix", "jit(pad)", 1, 0.2]]
    assert notes["program_spans_per_request"] == 6.5
    assert notes["program_compiles_in_window"] == 3
    # (1.3 - 0.5 - 0.2) + (0.5 - 0.4)
    assert notes["time_by_span"]["plan.merge_fix"] == {
        "n": 2, "self_s": pytest.approx(0.7), "compile_s": pytest.approx(0.6)}


# --- a whole run with the recording on ----------------------------------------

@pytest.fixture
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    core.clear_caches()
    yield
    core.clear_caches()


def test_whole_run_with_the_recording_on(_cache):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]
    config = load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    config["ports"] = 12
    config["coflows"]["max_flows"] = 50
    traffic["calibration_jobs"] = 30
    traffic["warmup_arrivals"] = 3
    out, run, ptrace = spans_at_size.measure(
        bench, cell, 2**31 + 5, 2.0, False, jax.devices()[:1], config,
        traffic)
    assert out["correct"] is True and ptrace is None
    rec = run.program
    roots = [s for s in rec.spans if s.parent is None]
    assert roots and {s.name for s in roots} == {"stream.feed"}
    assert len(roots) == run.attempted
    harness_count = sum(1 for t, _ in run.compiles
                        if run.window[0] <= t <= run.window[1])
    assert run.notes["program_compiles_in_window"] == harness_count
    assert run.notes["program_spans_per_request"] > 3
    assert ps.read(run, rec)["merge_fix_ms"] > 0
    assert core.spans._rec is None                  # stopped at the close
