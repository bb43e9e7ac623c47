"""The plain reference (bench/reference) plans what the program's own
batch loop plans on its host backends, job for job, on small streams;
its memo on DMA-RT's interval decompositions changes no completion."""
import pytest

import repro.core as core
from bench.lib.workload import JobStream
from bench.reference import timeline
from bench.reference.bna import bna
from bench.reference.online import ROOTED, simulate


def _config(dag: str) -> dict:
    return {"ports": 12,
            "jobs": {"mu_bar": 4, "dag": dag, "edge_prob": 0.5},
            "coflows": {"min_flows": 10, "max_flows": 50,
                        "flow_size_lognormal": [3.0, 1.6], "min_flow": 1,
                        "max_flow": 2472}}


@pytest.mark.parametrize("scheduler,dag", [("gdm", "general"),
                                           ("gdm_rt", "fan_in_tree")])
@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_reference_matches_program_batch_loop(scheduler, dag, seed):
    stream = JobStream(_config(dag), {"load": 0.7, "calibration_jobs": 30},
                       seed)
    specs = [stream.job(k) for k in range(14)]
    jobs = [core.Job(s.jid, [core.Coflow(s.jid, i, d)
                             for i, d in enumerate(s.demands)],
                     list(s.edges), weight=s.weight, release=s.release)
            for s in specs]
    with core.use_plan_backend("python"), core.use_alpha_backend("numpy"), \
            core.use_bna_backend("numpy"):
        core.clear_caches()
        prog = core.simulate_online(core.Instance(12, jobs), scheduler,
                                    driver="batch", delays="spread", seed=0)
    assert simulate(specs, 12, ROOTED[scheduler]) == prog.job_completions


@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_nested_memo_changes_no_completion(seed, monkeypatch):
    """DMA-RT's interval decompositions go through the run's memo: the
    same completions, job for job, as a BNA on every interval, and fewer
    distinct interval matrices than nested calls."""
    stream = JobStream(_config("fan_in_tree"),
                       {"load": 0.7, "calibration_jobs": 30}, seed)
    specs = [stream.job(k) for k in range(14)]
    memoized = simulate(specs, 12, True)

    calls, distinct = [], set()
    real = timeline._decompose

    def plain(events, edges, al, exp, m, pieces):
        def every_time(dm):
            calls.append(1)
            distinct.add(dm.tobytes())
            return bna(dm)
        return real(events, edges, al, exp, m, every_time)

    monkeypatch.setattr(timeline, "_decompose", plain)
    assert simulate(specs, 12, True) == memoized
    assert 0 < len(distinct) < len(calls)
