"""Drive whole runs of the harness on the CPU (the look for a TPU is
skipped) at a small size: a sound run is correct, and the run reads not
correct when the timed path is broken underneath it, once per fault:

- an answer altered where it is produced (every Step 2 delay one unit
  later, so plans move);
- half of the requests left out (the session drops every odd arrival);
- the control: the program's own admission path switched on, which
  defers arrivals and so breaks the pure-mode guarantee.
"""
import importlib
import json

import jax
import pytest

import repro.core as core
from bench import run as bench_run
from bench.lib.harness import ROOT, load_json

# the package re-exports functions under these modules' names
dma = importlib.import_module("repro.core.dma")
dma_srt = importlib.import_module("repro.core.dma_srt")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def _measure(cell_name: str, seed: int = 2**31 + 5):
    config_name, traffic_name = cell_name.split(".")
    cell = {"name": cell_name, "config": config_name,
            "traffic": traffic_name, "chips": 1}
    config = load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    config["ports"] = 12
    config["coflows"]["max_flows"] = 50
    traffic["calibration_jobs"] = 30
    traffic["warmup_arrivals"] = 3
    out, _ = bench_run.measure(BENCH, cell, seed, 2.0, False,
                               jax.devices()[:1], config, traffic)
    json.dumps(out)        # the result line must serialise
    return out


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    core.clear_caches()
    yield
    core.clear_caches()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = _measure(cell)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in BENCH["end_to_end"]
                                   if cell in m.get("workloads", [cell])}
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0.0 for c in out["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_altered_plans_are_caught(cell, monkeypatch):
    real = dma.draw_delays

    def late(uids, delta, beta, rng):
        return {u: d + 1 for u, d in real(uids, delta, beta, rng).items()}

    monkeypatch.setattr(dma, "draw_delays", late)
    monkeypatch.setattr(dma_srt, "draw_delays", late)
    out = _measure(cell)
    assert out["correct"] is False
    assert out["checks"]["completion_gap_max"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_dropped_requests_are_caught(cell, monkeypatch):
    real = core.SchedulerSession.submit

    def drop_odd(self, job):
        if job.jid % 2 == 0:
            real(self, job)

    monkeypatch.setattr(core.SchedulerSession, "submit", drop_odd)
    out = _measure(cell)
    assert out["correct"] is False
    assert out["checks"]["jobs_unanswered"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_admission_is_caught(cell, monkeypatch):
    real = core.StreamDriver

    def with_admission(*args, **kw):
        return real(*args, admission=core.AdmissionPolicy(
            max_pending=64, replan_budget=0.0, window=4), **kw)

    monkeypatch.setattr(core, "StreamDriver", with_admission)
    out = _measure(cell)
    assert out["correct"] is False
