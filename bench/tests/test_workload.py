"""The job stream depends on the seed alone: two consumptions of different
length agree on their common prefix, whatever was drawn before; every
seed serves the same instance with its ports relabelled, so seeds bring
the same work; the draws keep the published marginals."""
import numpy as np
import pytest

from bench.lib.harness import load_json
from bench.lib.workload import JobStream

SMALL = {"ports": 16, "jobs": {"mu_bar": 5, "dag": "general",
                               "edge_prob": 0.5},
         "coflows": {"min_flows": 10, "max_flows": 60,
                     "flow_size_lognormal": [3.0, 1.6], "min_flow": 1,
                     "max_flow": 2472}}
POISSON = {"load": 0.7, "calibration_jobs": 40}


def _same(a, b) -> bool:
    return (a.jid == b.jid and a.release == b.release and a.edges == b.edges
            and a.weight == b.weight and len(a.demands) == len(b.demands)
            and all(np.array_equal(x, y)
                    for x, y in zip(a.demands, b.demands)))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_prefix_depends_on_seed_alone(seed):
    short = JobStream(SMALL, POISSON, seed)
    long_ = JobStream(SMALL, POISSON, seed)
    a = [short.job(k) for k in range(12)]
    b = [long_.job(k) for k in range(40)]
    assert all(_same(x, y) for x, y in zip(a, b))
    # random access after a longer walk gives the same job again
    assert _same(long_.job(5), a[5])
    releases = [j.release for j in b]
    assert releases == sorted(releases)


def test_seeds_relabel_one_instance():
    a = JobStream(SMALL, POISSON, 1)
    b = JobStream(SMALL, POISSON, 2**31 + 9)
    assert a.rate == b.rate
    assert not np.array_equal(a.ports, b.ports)
    # port x of seed b is port y of seed a
    to_a = np.argsort(a.ports)[b.ports]
    for k in range(16):
        ja, jb = a.job(k), b.job(k)
        assert (ja.release, ja.edges) == (jb.release, jb.edges)
        assert len(ja.demands) == len(jb.demands)
        for x, y in zip(ja.demands, jb.demands):
            assert np.array_equal(y, x[np.ix_(to_a, to_a)])
            assert np.all(np.diag(y) == 0)
        assert any(not np.array_equal(x, y)
                   for x, y in zip(ja.demands, jb.demands))


def test_draws_keep_the_marginals():
    s = JobStream(SMALL, POISSON, 11)
    mu_bar = SMALL["jobs"]["mu_bar"]
    counts = np.array([len(s._content(k)[0]) for k in range(1500)])
    hist = np.bincount(counts, minlength=2 * mu_bar)[1:]
    assert counts.min() >= 1 and counts.max() <= 2 * mu_bar - 1
    assert abs(counts.mean() - mu_bar) < 0.2
    assert hist.min() > 0.8 * counts.size / (2 * mu_bar - 1)
    gaps = np.diff([0] + [s._release(k) for k in range(3200)])
    assert abs(gaps.mean() * s.rate - 1.0) < 0.05


@pytest.mark.parametrize("config", ["fb150_gdm", "fb150_gdm_rt"])
def test_cell_configs_keep_the_published_shape(config):
    cfg = load_json("configs", config)
    assert cfg["ports"] == 150
    s = JobStream(cfg, load_json("traffic", "arrivals"), 3)
    job = s.job(0)
    for d in job.demands:
        assert d.shape == (150, 150) and d.dtype == np.int64
        assert d.min() >= 0 and d.max() <= 2472 * cfg["coflows"]["max_flows"]
        assert np.all(np.diag(d) == 0)
    if cfg["jobs"]["dag"] == "fan_in_tree":
        assert len(job.edges) == len(job.demands) - 1
        assert all(a < b for a, b in job.edges)
