"""Jitted planning pipeline (core/pipeline.py + the REPRO_PLAN_BACKEND
dispatch in core/backend.py):

  * plan identity — the 9-scenario x 6-scheduler matrix planned under
    ``jit`` must be results-identical (twct, per-job and per-coflow
    completions) to the ``python`` path, including with the pallas
    alpha/BNA backends layered on top (reduced grid);
  * decomposition bit-identity at the pipeline level — pieces equal the
    scalar ``bna`` and relative edge intervals equal the python RLE on the
    padding/width-bucket edge cases: zero-demand coflows, 1x1 singletons,
    widths straddling the power-of-two bucket cuts;
  * structural edge cases at the instance level — singleton levels (a
    chain of one-coflow levels), forest residuals (a job whose Starts-After
    DAG is a multi-root forest), zero-demand coflows inside a job;
  * session repair-path equivalence — the event-driven driver under jit
    replays the online protocol bit-identically (repair on and off);
  * backend knob + cache plumbing — validation, context-manager restore,
    prefetch warming the edge cache, ``cache_stats()['plan']`` exposure.

Compile cost discipline: tests never clear the compile cache (executables
are data-independent), so the suite pays each (B_pad, w, T_cap) signature
once.
"""
import functools

import numpy as np
import pytest

from repro import scenarios
from repro.core import (available_schedulers, backend, bna, cache_stats,
                        clear_caches, plan, prefetch_plan, simulate_online,
                        use_plan_backend)
from repro.core import pipeline
from repro.core.backend import config, resolve_plan_backend, set_plan_backend
from repro.core.timeline import bna_pieces_to_edge_intervals
from repro.core.types import Coflow, Instance, Job

SCHEDULERS = sorted(available_schedulers())
# tiny sizes so the full matrix stays CI-cheap (mirrors tests/test_matching)
TINY = {
    "fb_like": dict(m=6, scale=0.03),
    "fb_like_rt": dict(m=6, scale=0.03),
    "alibaba_sparse": dict(m=6, scale=0.15),
    "incast": dict(m=6, scale=0.1),
    "shuffle_heavy": dict(m=6, scale=0.2),
    "wide_shallow": dict(m=6, scale=0.2),
    "online_poisson": dict(m=6, scale=0.03),
    "deep_chain": dict(m=6, scale=0.25),
    "dist_collectives": dict(m=8, scale=0.5),
}


@functools.lru_cache(maxsize=None)
def _tiny(name):
    return scenarios.build(name, seed=0, **TINY[name])


def _fingerprint(p):
    """twct + per-job completions + the full transcript, canonicalized
    (flows within an entry sorted) so edge emission order is immaterial."""
    entries = tuple(sorted(
        (e.jid, e.cid, round(float(e.t0), 9), round(float(e.t1), 9),
         tuple(sorted(zip(np.asarray(e.srcs).tolist(),
                          np.asarray(e.dsts).tolist(),
                          np.round(np.asarray(e.units, dtype=float), 9)
                          .tolist()))))
        for e in p.transcript().entries))
    return (p.twct(), p.makespan, tuple(sorted(p.job_completions().items())),
            entries)


@functools.lru_cache(maxsize=None)
def _ref_plan(scen, sched):
    """Python-path reference, caches cold."""
    built = _tiny(scen)
    opts = scenarios.scheduler_opts(sched, built.meta)
    with use_plan_backend("python"):
        clear_caches()
        p = plan(built.instance, sched, seed=0, **opts)
    return _fingerprint(p)


# --------------------------------------------------------------------------
# plan identity: 9 scenarios x 6 schedulers, jit vs python
# --------------------------------------------------------------------------

@pytest.mark.parametrize("sched", SCHEDULERS)
@pytest.mark.parametrize("scen", sorted(TINY))
def test_plan_identity_jit(scen, sched):
    built = _tiny(scen)
    opts = scenarios.scheduler_opts(sched, built.meta)
    ref = _ref_plan(scen, sched)
    with use_plan_backend("jit"):
        clear_caches()
        p = plan(built.instance, sched, seed=0, **opts)
    assert _fingerprint(p) == ref, f"{scen}/{sched}: jit plan diverged"


@pytest.mark.parametrize("sched", ("gdm", "om_alg_bf"))
@pytest.mark.parametrize("scen", ("wide_shallow", "incast", "deep_chain"))
def test_plan_identity_jit_pallas_stack(scen, sched):
    """jit plan backend with the pallas alpha AND BNA backends layered on
    top (the fused merge_fix path engages where it applies)."""
    built = _tiny(scen)
    opts = scenarios.scheduler_opts(sched, built.meta)
    ref = _ref_plan(scen, sched)
    with use_plan_backend("jit"), backend.use_alpha_backend("pallas"), \
            backend.use_bna_backend("pallas"):
        clear_caches()
        p = plan(built.instance, sched, seed=0, **opts)
    assert _fingerprint(p) == ref, f"{scen}/{sched}: pallas stack diverged"


def test_fused_merge_fix_reports_its_bucket():
    """Under the jit + pallas stack each merge_and_fix with edges runs the
    fused step: its ``plan.merge_fix`` span carries the power-of-two K_pad
    bucket the kernel ran on, and ``cache_stats()["merge_fix"]`` counts
    one call per such span."""
    from repro.core import spans

    built = _tiny("incast")
    with use_plan_backend("jit"), backend.use_alpha_backend("pallas"), \
            backend.use_bna_backend("pallas"):
        clear_caches()
        before = cache_stats()["merge_fix"]
        with spans.recording() as rec:
            plan(built.instance, "gdm", seed=0)
        after = cache_stats()["merge_fix"]
    fused = [s.attrs for s in rec.spans
             if s.name == "plan.merge_fix" and "k_pad" in s.attrs]
    assert fused and after["calls"] - before["calls"] == len(fused)
    assert after["misses"] - before["misses"] <= len(fused)
    for a in fused:
        assert a["k_pad"] >= 8 and not a["k_pad"] & (a["k_pad"] - 1)


# --------------------------------------------------------------------------
# decomposition bit-identity: padding / width-bucket edge cases
# --------------------------------------------------------------------------

def _edge_set(t0, t1, s, r):
    return sorted(zip(np.asarray(t0).tolist(), np.asarray(t1).tolist(),
                      np.asarray(s).tolist(), np.asarray(r).tolist()))


def _assert_decomp_matches(demands):
    pieces_list, edges_list = pipeline._plan_decompositions(demands)
    for i, (dem, pieces, rel) in enumerate(zip(demands, pieces_list,
                                               edges_list)):
        ref = bna(np.asarray(dem, np.int64))
        assert len(pieces) == len(ref), f"demand {i}: piece count"
        for (t1, p1), (t2, p2) in zip(pieces, ref):
            assert t1 == t2 and np.array_equal(p1, p2), \
                f"demand {i}: pieces diverged"
        ei = bna_pieces_to_edge_intervals(ref, 0)
        assert _edge_set(*rel) == _edge_set(ei.t0, ei.t1, ei.s, ei.r), \
            f"demand {i}: edge intervals diverged"


def test_decompose_width_bucket_edges():
    rng = np.random.default_rng(7)
    demands = [np.zeros((4, 4), np.int64),            # zero-demand coflow
               np.array([[5]], np.int64),             # 1x1 singleton
               np.zeros((1, 1), np.int64)]            # 1x1 zero
    for m in (2, 3, 7, 8, 9, 16, 17):                 # bucket cuts 8|9, 16|17
        d = rng.integers(0, 25, size=(m, m))
        d[rng.random((m, m)) > 0.5] = 0
        demands.append(d)
    demands.append(np.diag(rng.integers(1, 9, 6)))    # permutation support
    demands.append(np.eye(5, dtype=np.int64) * 3)     # another diagonal
    _assert_decomp_matches(demands)


def test_decompose_sparse_support_padding():
    # support restriction: dense rows scattered through a mostly-zero
    # matrix, so the packed sub-matrix is much smaller than m
    rng = np.random.default_rng(11)
    demands = []
    for m, k in ((12, 2), (16, 3), (20, 5)):
        d = np.zeros((m, m), np.int64)
        rows = rng.choice(m, size=k, replace=False)
        cols = rng.choice(m, size=k, replace=False)
        for a in rows:
            for b in cols:
                if rng.random() < 0.7:
                    d[a, b] = int(rng.integers(1, 30))
        demands.append(d)
    _assert_decomp_matches(demands)


def test_decompose_bucket_in_lane_chunks(monkeypatch):
    """A bucket wider than one chunk runs as several compiled programs
    (here two lanes each, so chunks of 2, 2 and 1) and still decomposes
    every demand exactly like the scalar bna."""
    rng = np.random.default_rng(13)
    demands = [_rand_demand(rng, 6, density=0.6) for _ in range(5)]
    T_cap = pipeline._pow2(max(int((d > 0).sum()) for d in demands)
                           + 6 * 8 + 8)
    monkeypatch.setattr(pipeline, "_PIECES_BYTES", 2 * T_cap * 8 * 4)
    assert pipeline._chunk_lanes(8, T_cap) == 2
    before = cache_stats()["launches"]["decompose"]
    _assert_decomp_matches(demands)
    assert cache_stats()["launches"]["decompose"] == before + 3


def test_chunk_lanes_bound_piece_stack():
    for w, T_cap in ((8, 64), (128, 4096), (256, 32768), (256, 1 << 22)):
        lanes = pipeline._chunk_lanes(w, T_cap)
        assert lanes >= 1 and lanes & (lanes - 1) == 0
        assert lanes == 1 or lanes * T_cap * w * 4 <= pipeline._PIECES_BYTES
        assert 2 * lanes * T_cap * w * 4 > pipeline._PIECES_BYTES


# --------------------------------------------------------------------------
# structural instance-level edge cases
# --------------------------------------------------------------------------

def _plan_both(inst, sched="gdm", **opts):
    with use_plan_backend("python"):
        clear_caches()
        ref = _fingerprint(plan(inst, sched, seed=0, **opts))
    with use_plan_backend("jit"):
        clear_caches()
        got = _fingerprint(plan(inst, sched, seed=0, **opts))
    assert got == ref


def _rand_demand(rng, m, density=0.5, hi=15):
    d = rng.integers(0, hi, size=(m, m))
    d[rng.random((m, m)) > density] = 0
    return d


@pytest.mark.parametrize("sched", ("gdm", "om_alg"))
def test_singleton_levels_chain(sched):
    # one coflow per level: the degenerate DAG shape where every group is
    # a singleton
    rng = np.random.default_rng(0)
    m, depth = 5, 6
    cofs = [Coflow(0, k, _rand_demand(rng, m)) for k in range(depth)]
    edges = [(k, k + 1) for k in range(depth - 1)]
    inst = Instance(m, [Job(0, cofs, edges, weight=1.0, release=0)])
    _plan_both(inst, sched)


@pytest.mark.parametrize("sched", ("gdm", "om_alg"))
def test_forest_residual_dag(sched):
    # multi-root forest inside one job plus an isolated coflow — the
    # residual shapes geometric grouping leaves behind
    rng = np.random.default_rng(1)
    m = 6
    cofs = [Coflow(0, k, _rand_demand(rng, m)) for k in range(5)]
    edges = [(0, 1), (2, 3)]  # two trees + coflow 4 isolated
    jobs = [Job(0, cofs, edges, weight=2.0, release=0),
            Job(1, [Coflow(1, 0, _rand_demand(rng, m))], [], weight=0.5,
                release=3)]
    inst = Instance(m, jobs)
    _plan_both(inst, sched)


def test_zero_demand_coflow_in_job():
    rng = np.random.default_rng(2)
    m = 4
    cofs = [Coflow(0, 0, _rand_demand(rng, m)),
            Coflow(0, 1, np.zeros((m, m), np.int64)),
            Coflow(0, 2, _rand_demand(rng, m))]
    inst = Instance(m, [Job(0, cofs, [(0, 1), (1, 2)], weight=1.0,
                            release=0)])
    _plan_both(inst, "gdm")


# --------------------------------------------------------------------------
# session repair-path equivalence under jit
# --------------------------------------------------------------------------

@pytest.mark.parametrize("repair", (True, False))
def test_session_equivalence_jit(repair):
    built = _tiny("online_poisson")
    with use_plan_backend("python"):
        clear_caches()
        ref = simulate_online(built.instance, "gdm", driver="session",
                              seed=0, repair=repair)
    with use_plan_backend("jit"):
        clear_caches()
        got = simulate_online(built.instance, "gdm", driver="session",
                              seed=0, repair=repair)
    assert got.job_completions == ref.job_completions
    assert got.twct() == ref.twct()
    assert got.reschedules == ref.reschedules


# --------------------------------------------------------------------------
# backend knob + cache plumbing
# --------------------------------------------------------------------------

def test_plan_backend_knob_validation():
    with pytest.raises(ValueError):
        set_plan_backend("bogus")
    prev = config.plan_backend
    with use_plan_backend("jit"):
        assert config.plan_backend == "jit"
        assert resolve_plan_backend() == "jit"
    assert config.plan_backend == prev
    assert resolve_plan_backend("python") == "python"
    assert resolve_plan_backend("auto") in ("python", "jit")


def test_prefetch_warms_edge_cache_and_stats():
    rng = np.random.default_rng(5)
    demands = [_rand_demand(rng, 5) for _ in range(6)]
    with use_plan_backend("jit"):
        pipeline.clear_pipeline_caches()
        prefetch_plan(demands)
        st = cache_stats()["plan"]
        assert st["edges"]["size"] > 0
        assert st["compile"]["batches"] >= 1
        before = st["edges"]["hits"]
        for d in demands:  # every per-coflow lookup must now hit
            assert pipeline.coflow_edges_rel(d) is not None
        st = cache_stats()["plan"]
        assert st["edges"]["hits"] >= before + len(demands)


def test_prefetch_python_backend_untouched():
    rng = np.random.default_rng(6)
    demands = [_rand_demand(rng, 4) for _ in range(3)]
    with use_plan_backend("python"):
        pipeline.clear_pipeline_caches()
        prefetch_plan(demands)  # routes to prefetch_bna, not the pipeline
        assert cache_stats()["plan"]["edges"]["size"] == 0
        assert backend.plan_edges(demands[0]) is None


class _DeviceDown(RuntimeError):
    pass


def _raise_device_down(*_a, **_k):
    raise _DeviceDown("device path failed")


@pytest.mark.parametrize("site,target,plan_backend", [
    ("pipeline", "repro.core.pipeline.prefetch_demands", "auto"),
    ("merge_fix", "repro.kernels.merge_fix.ops.merge_fix_step", "auto"),
    ("coflow_merge", "repro.kernels.coflow_merge.ops.edge_interval_alphas",
     "python"),
    ("bna_step", "repro.kernels.bna_step.ops.bna_step_batch", "python"),
])
def test_auto_device_failure_raises(monkeypatch, site, target, plan_backend):
    """With ``auto`` resolving to the device backends (as on a TPU), a
    failing kernel or pipeline stage raises out of ``plan``: no backend
    quietly hands the work to the host and warns."""
    import importlib
    import warnings

    module, name = target.rsplit(".", 1)
    monkeypatch.setattr(importlib.import_module(module), name,
                        _raise_device_down)
    monkeypatch.setattr(backend, "_resolve_auto", lambda: "pallas")
    built = _tiny("incast")
    with use_plan_backend(plan_backend), \
            backend.use_alpha_backend("auto"), \
            backend.use_bna_backend("auto"), warnings.catch_warnings():
        warnings.simplefilter("error")
        clear_caches()
        with pytest.raises(_DeviceDown):
            plan(built.instance, "gdm", seed=0)
    clear_caches()


@pytest.mark.parametrize("path", ("plan", "bna_many"))
def test_int32_guard_stays_exact_under_auto(monkeypatch, path):
    """A demand whose row sum passes 2^31 - 1 is past every int32 device
    program.  With ``auto`` resolving to the device backends (as on a
    TPU), the range guards keep its bucket on the numpy step: the plan
    and the pieces stay exact, the pallas step is never launched, and the
    jit pipeline counts one bucket fallback."""
    import warnings

    from repro.core.matching import bna_many

    big = np.array([[2**30, 2**30, 0],
                    [0, 3, 2**30],
                    [5, 0, 7]], np.int64)
    assert big.sum(axis=1).max() >= 2**31 - 1
    inst = Instance(3, [Job(0, [Coflow(0, 0, big)], [], weight=1.0,
                            release=0)])
    with use_plan_backend("python"), backend.use_alpha_backend("numpy"), \
            backend.use_bna_backend("numpy"):
        clear_caches()
        ref = _fingerprint(plan(inst, "gdm", seed=0))
    monkeypatch.setattr(backend, "_resolve_auto", lambda: "pallas")
    with use_plan_backend("auto"), backend.use_alpha_backend("auto"), \
            backend.use_bna_backend("auto"), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        clear_caches()
        if path == "plan":
            assert _fingerprint(plan(inst, "gdm", seed=0)) == ref
            assert cache_stats()["plan"]["compile"]["bucket_fallbacks"] == 1
        else:
            got = bna_many([big])[0]
            want = bna(big)
            assert len(got) == len(want)
            assert all(ta == tb and np.array_equal(ma, mb)
                       for (ta, ma), (tb, mb) in zip(got, want))
        assert cache_stats()["launches"]["bna_step"] == 0
    clear_caches()
