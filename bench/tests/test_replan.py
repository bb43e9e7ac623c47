"""The offline replan cells: the plain G-DM-with-backfilling reference
plans what the program's ``gdm_bf`` plans, job for job; a whole
``offline_replan`` run of each cell is correct on the CPU at a small
size; a program without the backfill span ends a backfilled run at once;
and the readers of the backfill and decomposition spans on hand-built
runs."""
import json

import jax
import pytest

import repro.core as core
from bench import run as bench_run
from bench.lib.harness import ROOT, Run, load_json
from bench.lib.workload import JobStream
from bench.metrics import backfill_ms, coflow_intervals_ms, decompose_ms
from bench.reference import backfill as ref_backfill
from bench.reference.model import Coflow, Instance, Job
from bench.reference.plan import Pieces
from repro.core.spans import Recorder, Span

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = ["fb150_gdm.replan", "fb150_gdm_bf.replan"]


def _specs(m, max_flows, n, seed):
    config = {"ports": m,
              "jobs": {"mu_bar": 4, "dag": "general", "edge_prob": 0.5},
              "coflows": {"min_flows": 10, "max_flows": max_flows,
                          "flow_size_lognormal": [3.0, 1.6], "min_flow": 1,
                          "max_flow": 2472}}
    stream = JobStream(config, {"load": 0.7, "calibration_jobs": 30}, seed)
    return [stream.job(k) for k in range(n)]


@pytest.mark.parametrize("m,max_flows,n,seed,released", [
    (12, 50, 6, 1, True), (12, 50, 8, 2**31 + 3, True),
    (16, 80, 6, 5, False), (14, 60, 7, 2**31 + 11, False),
])
def test_reference_backfill_equals_gdm_bf(m, max_flows, n, seed, released):
    specs = _specs(m, max_flows, n, seed)
    rel = {s.jid: (s.release if released else 0) for s in specs}
    prog_jobs = [core.Job(s.jid, [core.Coflow(s.jid, i, d)
                                  for i, d in enumerate(s.demands)],
                          list(s.edges), weight=s.weight, release=rel[s.jid])
                 for s in specs]
    ref_jobs = [Job(s.jid, [Coflow(s.jid, i, d)
                            for i, d in enumerate(s.demands)],
                    list(s.edges), weight=s.weight, release=rel[s.jid])
                for s in specs]
    core.clear_caches()
    prog = core.plan(core.Instance(m, prog_jobs), "gdm_bf", delays="spread",
                     seed=0, exec="packet")
    ref: dict = {}
    for e in ref_backfill.gdm_bf(Instance(m, ref_jobs), Pieces()):
        ref[e.jid] = max(ref.get(e.jid, 0.0), float(e.t1))
    assert prog.job_completions() == ref
    assert prog.twct() == sum(s.weight * ref[s.jid] for s in specs)
    # backfilling moved something: the reference is not the plain plan
    plain = core.plan(core.Instance(m, prog_jobs), "gdm", delays="spread",
                      seed=0)
    assert prog.twct() < plain.twct()


@pytest.fixture
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    core.clear_caches()
    yield
    core.clear_caches()


@pytest.mark.parametrize("cell", CELLS)
def test_whole_replan_run_is_correct(cell, _cache):
    (w,) = [w for w in BENCH["workloads"] if w["name"] == cell]
    config = load_json("configs", w["config"])
    traffic = load_json("traffic", w["traffic"])
    config["ports"] = 12
    config["coflows"]["max_flows"] = 50
    traffic["calibration_jobs"] = 30
    out, run = bench_run.measure(BENCH, w, 2**31 + 7, 2.0, False,
                                 jax.devices()[:1], config, traffic)
    json.dumps(out)
    assert out["correct"] is True and out["failed"] == 0
    assert all(c["value"] == 0.0 for c in out["checks"].values())
    assert set(out["checks"]) == {"residual_mismatch", "jobs_unanswered",
                                  "completion_gap_max", "twct_rel_gap"}
    assert run.notes["requests_in_window"] == len(run.requests) > 1
    assert run.notes["jobs_fed"] == traffic["backlog_jobs"]
    backlog = run.notes["backlog"]
    assert backlog["jobs"] + backlog["finished"] <= traffic["backlog_jobs"]
    assert backlog["jobs"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_session_execution_fault_is_caught(cell, _cache, monkeypatch):
    """A session that sends less of its plans than they say, while being
    fed, leaves a residual that the reference's own replay does not: the
    run is not correct, and ``residual_mismatch`` says why."""
    from repro.core import session as core_session

    execute = core_session.execute_transcript

    def short(transcript, horizon, *args):
        execute(transcript, horizon * 0.9, *args)

    monkeypatch.setattr(core_session, "execute_transcript", short)
    (w,) = [w for w in BENCH["workloads"] if w["name"] == cell]
    config = load_json("configs", w["config"])
    traffic = load_json("traffic", w["traffic"])
    config["ports"] = 12
    config["coflows"]["max_flows"] = 50
    traffic["calibration_jobs"] = 30
    out, _ = bench_run.measure(BENCH, w, 2**31 + 7, 2.0, False,
                               jax.devices()[:1], config, traffic)
    assert out["correct"] is False
    assert out["checks"]["residual_mismatch"]["value"] > 0


@pytest.mark.parametrize("cell,ends", [("fb150_gdm.replan", False),
                                        ("fb150_gdm_bf.replan", True)])
def test_program_without_backfill_span_ends_a_backfilled_run(
        cell, ends, _cache, monkeypatch):
    """A program that has no ``plan.backfill`` span exits a backfilled
    cell before it feeds anything; the plain G-DM cell runs as before."""
    from bench.drivers import offline_replan

    monkeypatch.setattr(core.spans, "NAMES", tuple(
        n for n in core.spans.NAMES if n != "plan.backfill"))
    fed, job = [], offline_replan.JobStream.job

    def counted(self, k):
        fed.append(k)
        return job(self, k)

    monkeypatch.setattr(offline_replan.JobStream, "job", counted)
    (w,) = [w for w in BENCH["workloads"] if w["name"] == cell]
    config = load_json("configs", w["config"])
    traffic = load_json("traffic", w["traffic"])
    config["ports"] = 12
    config["coflows"]["max_flows"] = 50
    traffic["calibration_jobs"] = 30
    if ends:
        with pytest.raises(SystemExit, match="plan.backfill"):
            bench_run.measure(BENCH, w, 2**31 + 7, 2.0, False,
                              jax.devices()[:1], config, traffic)
        assert fed == []
    else:
        out, _ = bench_run.measure(BENCH, w, 2**31 + 7, 2.0, False,
                                   jax.devices()[:1], config, traffic)
        assert out["correct"] is True
        assert len(fed) == traffic["backlog_jobs"]


def _spans():
    S = Span
    return [
        S("plan.backfill", 0.5, 4.5, None, None),
        S("plan.coflow_intervals", 0.6, 2.6, 0, None),
        S("plan.nested_decompose", 0.7, 2.5, 1, None),
        S("plan.coflow_intervals", 2.6, 3.1, 0, None),
        S("plan.backfill", 5.0, 6.0, None, None),
        S("plan.coflow_intervals", 5.0, 5.5, 4, None),
        S("plan.backfill", 10.5, 11.5, None, None),    # after the window
        S("plan.coflow_intervals", 10.6, 11.0, 6, None),
    ]


@pytest.mark.parametrize("reader,want_s", [
    (backfill_ms, 4.0 + 1.0),
    (coflow_intervals_ms, 2.0 + 0.5 + 0.5),
])
def test_readers_by_hand(reader, want_s, monkeypatch):
    run = Run(window=(0.0, 10.0), requests=[(0.0, 5.0), (5.0, 10.0)])
    assert reader.read(run) is None                 # no recording
    run.program = Recorder(spans=_spans())
    assert reader.read(run) == pytest.approx(want_s / 2 * 1e3)
    run.program = Recorder(spans=[Span("stream.feed", 0.0, 1.0, None, 1)])
    assert reader.read(run) == 0.0                  # nothing backfilled
    # a program without the span: nothing to read, and no error
    monkeypatch.setattr(core.spans, "NAMES", ("stream.feed",))
    run.program = Recorder(spans=_spans())
    assert reader.read(run) is None


def test_decompose_ms_reads_the_outermost_coflow_decompositions():
    S = Span
    spans = [
        S("plan.decompose", 0.5, 2.0, None, None),
        S("plan.nested_decompose", 2.0, 3.0, None, None),   # no coflow BNA
        S("plan.decompose", 5.0, 5.5, None, None),
        S("plan.decompose", 10.5, 11.0, None, None),         # after the window
    ]
    run = Run(window=(0.0, 10.0), requests=[(0.0, 5.0), (5.0, 10.0)])
    assert decompose_ms.read(run) is None                    # no recording
    run.program = Recorder(spans=spans)
    assert decompose_ms.read(run) == pytest.approx((1.5 + 0.5) / 2 * 1e3)
    run.program = Recorder(spans=[Span("stream.feed", 0.0, 1.0, None, 1)])
    assert decompose_ms.read(run) == 0.0
    # both replan cells list the reader
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == "decompose_ms"]
    assert set(CELLS) <= set(m["workloads"])
