"""The trace reduction, against a profile built by hand and against a
short window recorded on one TPU v5e (``data/``: the profile of a traced
run of the harness and the host-clock facts of that run).  The busy
union, the idle gaps and the device time by program are recomputed here
by brute force and must agree with ``bench.lib.trace.reduce``."""
import gzip
import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

from bench.lib import trace


@dataclass
class _Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclass
class _Line:
    name: str
    events: list


@dataclass
class _Plane:
    name: str
    lines: list


@dataclass
class _Profile:
    planes: list


def test_reduce_by_hand():
    # host clock: the window span opens at perf 10.0 = trace 1000 ns
    host = _Plane("/host:CPU", [_Line("python3", [
        _Ev("bench.window", 1000, 9000),
        _Ev("bench.advance", 1000, 1000),      # [1000, 2000)
        _Ev("bench.feed", 2000, 7000),         # [2000, 9000)
    ])])
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit_decompose(7)", 2500, 2000),
                              _Ev("jit_add(3)", 6000, 500)]),
        _Line("XLA Ops", [
            _Ev("%while.3 = s32[] while(...)", 2500, 1500),
            _Ev("%fusion.1 = s32[4] fusion(...)", 3500, 1000),  # overlaps
            _Ev("%add.0 = s32[] add(...)", 6000, 500),
            _Ev("%copy.9 = s32[] copy(...)", 9500, 1000),       # clipped
        ])])
    window = (10.0, 10.0 + 9500e-9)                           # [1000, 10500)
    compiles = [(10.0 + 4500e-9, 1000e-9)]                    # [4500, 5500)
    red = trace.reduce(_Profile([host, dev]), 10.0, window, compiles)
    assert red.window_s == pytest.approx(9500e-9)
    busy = 2000 + 500 + 1000                 # [2500,4500) [6000,6500) [9500,10500)
    assert red.busy_s == pytest.approx(busy * 1e-9)
    assert red.modules == {"jit_decompose": [1, pytest.approx(2000e-9)],
                           "jit_add": [1, pytest.approx(500e-9)]}
    # gaps: [1000,2500) mid 1750 advance; [4500,6000) mid 5250 feed and
    # compiling; [6500,9500) mid 8000 feed
    assert red.gaps == {"advance": pytest.approx(1500e-9),
                        "feed/compile": pytest.approx(1500e-9),
                        "feed": pytest.approx(3000e-9)}
    assert sum(red.gaps.values()) + red.busy_s == pytest.approx(red.window_s)


DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def recorded():
    """Two requests of the harness at 16 ports on one TPU v5e, profiled
    (``small.xplane.pb.gz``), with that run's host-clock window, anchor,
    compiles and result line (``small.meta.json``)."""
    meta = json.loads((DATA / "small.meta.json").read_text())
    profile = ProfileData.from_serialized_xspace(
        gzip.decompress((DATA / "small.xplane.pb.gz").read_bytes()))
    red = trace.reduce(profile, meta["anchor_perf"], tuple(meta["window_perf"]),
                       [tuple(c) for c in meta["compiles_perf"]])
    return profile, meta, red


def _window_ns(profile, meta):
    anchor = min(e.start_ns for p in profile.planes
                 if p.name.startswith("/host:") for ln in p.lines
                 for e in ln.events if e.name == trace.WINDOW_SPAN)
    w0, w1 = meta["window_perf"]
    return (anchor + (w0 - meta["anchor_perf"]) * 1e9,
            anchor + (w1 - meta["anchor_perf"]) * 1e9)


def test_busy_union_matches_a_grid(recorded):
    profile, meta, red = recorded
    lo, hi = _window_ns(profile, meta)
    grid = np.zeros(int((hi - lo) // 1e6) + 1, dtype=bool)   # 1 ms cells
    for p in profile.planes:
        if p.name != "/device:TPU:0":
            continue
        for ln in p.lines:
            if ln.name != "XLA Ops":
                continue
            for e in ln.events:
                a = max(e.start_ns, lo)
                b = min(e.start_ns + e.duration_ns, hi)
                if b > a:
                    grid[int((a - lo) // 1e6):int(np.ceil((b - lo) / 1e6))] \
                        = True
    assert red.chips == 1
    assert abs(red.window_s - (hi - lo) / 1e9) < 1e-9
    # a 1 ms grid rounds every interval outwards by less than 1 ms
    assert red.busy_s <= grid.sum() / 1e3 + 1e-9
    n_ops = sum(1 for p in profile.planes if p.name == "/device:TPU:0"
                for ln in p.lines if ln.name == "XLA Ops" for _ in ln.events)
    assert red.busy_s >= grid.sum() / 1e3 - 2e-3 * n_ops
    assert 0.0 < red.idle_share < 1.0


def test_gaps_and_busy_fill_the_window(recorded):
    _, _, red = recorded
    assert sum(red.gaps.values()) + red.busy_s == pytest.approx(
        red.window_s, rel=1e-6)
    assert set(red.gaps) <= {"advance", "feed", "bookkeeping",
                             "advance/compile", "feed/compile",
                             "bookkeeping/compile", "outside_spans",
                             "outside_spans/compile"}


def test_programs_by_stable_name(recorded):
    profile, meta, red = recorded
    lo, hi = _window_ns(profile, meta)
    want = 0.0
    for p in profile.planes:
        if p.name != "/device:TPU:0":
            continue
        for ln in p.lines:
            if ln.name != "XLA Modules":
                continue
            for e in ln.events:
                if e.name.startswith("jit_decompose("):
                    a = max(e.start_ns, lo)
                    b = min(e.start_ns + e.duration_ns, hi)
                    want += max(b - a, 0) / 1e9
    assert red.modules["jit_decompose"][1] == pytest.approx(want, rel=1e-9)
    assert "jit_coflow_merge_padded" in red.modules
    assert trace.stable_name("jit_decompose(123)") == "jit_decompose"
    assert trace.stable_name("jit_add") == "jit_add"


def test_matches_the_run_that_recorded_it(recorded):
    _, meta, red = recorded
    dev = meta["result"]["device"]
    assert red.busy_s == pytest.approx(dev["busy_s"], rel=1e-9)
    assert red.window_s == pytest.approx(dev["window_s"], rel=1e-9)
    per_request = red.modules["jit_decompose"][1] / len(meta["requests"])
    assert per_request * 1e3 == pytest.approx(
        meta["result"]["metrics"]["decompose_device_ms"]["value"], rel=1e-9)


def test_top_orders_by_seconds():
    assert trace.top({"a": [3, 1.0], "b": [1, 2.0], "c": [9, 0.5]}, 2) == \
        [["b", 2.0], ["a", 1.0]]


@pytest.mark.parametrize("metric", ["decompose_device_ms",
                                    "coflow_merge_device_ms"])
def test_program_time_reads_zero_when_the_program_never_ran(metric):
    import importlib

    from bench.lib.harness import Run

    reader = importlib.import_module(f"bench.metrics.{metric}")
    run = Run(requests=[(0.0, 1.0), (1.0, 2.0)])
    assert reader.read(run) is None                    # not traced
    run.trace = trace.Reduced(window_s=2.0, busy_s=0.5, chips=1,
                              modules={"jit_loads": [1, 0.5]})
    assert reader.read(run) == 0.0
    run.trace.modules = {"jit_decompose": [2, 0.25],
                         "jit_coflow_merge_padded": [2, 0.25]}
    assert reader.read(run) == pytest.approx(125.0)
