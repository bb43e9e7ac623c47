"""The merged-interval decomposition in batches (``timeline._decompose``).

Every alpha_I > 1 interval's matrix goes through
``backend.bna_pieces_many`` in chunks; the scalar path decomposed one
interval at a time with ``bna.bna``.  Pins, on seeded instances at 12-16
ports: the pieces, the per-unit completions and the per-coflow edge
intervals are the scalar path's, bit for bit, across alpha = 1 and
alpha > 1 intervals, repeated matrices and a chunk boundary, for a G-DM
``coflow_intervals()`` and a DMA-RT nested plan; a ``gdm_bf`` session
under arrivals completes every job when it does; and the compiled buckets
of the jit plan backend give the scalar pieces too.
"""
import numpy as np
import pytest

import repro.core as core
from repro.core import backend, spans, timeline
from repro.core.bna import bna
from repro.core.timeline import (EdgeIntervals, UnitSchedule, merge_and_fix,
                                 unit_from_coflow_plan)

PLAN_OPTS = {"delays": "spread", "seed": 0}


def _scalar(demands, keys=None):
    return [bna(d) for d in demands]


def _fields(out):
    pieces, completion, segs = out
    return ([(p.t0, p.dur, p.srcs.tolist(), p.dsts.tolist(), p.mult.tolist())
             for p in pieces],
            completion,
            [getattr(segs, f).tolist()
             for f in ("t0", "t1", "s", "r", "owner", "jid", "cid")])


def _instance(m, seed, rooted=False):
    return core.paper_workload(m=m, mu_bar=4, seed=seed, scale=0.05,
                               rooted=rooted)


def _merged(m, seed, repeat=1):
    """A merge of random coflow units with overlapping starts, each unit
    laid out ``repeat`` times back to back, so the same interval
    matrices come up again."""
    rng = np.random.default_rng(seed)
    units = []
    for u in range(4):
        d = np.zeros((m, m), dtype=np.int64)
        for _ in range(3 * m):
            s, r = rng.integers(0, m, 2)
            d[s, r] += int(rng.integers(1, 6))
        unit = unit_from_coflow_plan(u, 0, d, bna(d), int(rng.integers(0, 6)))
        span_len = int(unit.edges.t1.max()) + 20
        edges = EdgeIntervals.concat([unit.edges.shifted(i * span_len)
                                      for i in range(repeat)])
        units.append(UnitSchedule(u, edges.with_owner(u), unit.ledger))
    return merge_and_fix(units, m, origin=3)


def _decompose(sched):
    return timeline._decompose(sched.events, sched.merged, sched.alphas,
                               sched.exp, sched.m)


@pytest.mark.parametrize("m,seed,repeat,chunk", [
    (12, 0, 1, 256), (12, 1, 3, 256), (16, 2, 3, 2), (16, 3, 1, 1),
])
def test_batched_walk_is_the_scalar_walk(m, seed, repeat, chunk, monkeypatch):
    sched = _merged(m, seed, repeat)
    assert (sched.alphas > 1).any() and (sched.alphas == 1).any()
    core.clear_caches()
    monkeypatch.setattr(timeline, "_DECOMPOSE_CHUNK", chunk)
    with spans.recording() as rec:
        batched = _fields(_decompose(sched))
    with monkeypatch.context() as mp:
        mp.setattr(backend, "bna_pieces_many", _scalar)
        scalar = _fields(_decompose(sched))
    assert batched == scalar
    (sp,) = [s for s in rec.spans if s.name == "plan.nested_decompose"]
    assert sp.attrs["intervals"] == sched.alphas.size
    assert sp.attrs["batched"] == int((sched.alphas > 1).sum()) > 0
    if repeat > 1:
        assert sp.attrs["distinct"] < sp.attrs["batched"]
    else:
        assert sp.attrs["distinct"] <= sp.attrs["batched"]


def test_chunk_boundary_changes_nothing(monkeypatch):
    sched = _merged(14, 7, repeat=2)
    n = int((sched.alphas > 1).sum())
    outs = []
    for chunk in (n, n - 1, 3, 256):
        core.clear_caches()
        monkeypatch.setattr(timeline, "_DECOMPOSE_CHUNK", chunk)
        outs.append(_fields(_decompose(sched)))
    assert all(o == outs[0] for o in outs[1:])


@pytest.mark.parametrize("m,seed", [(12, 4), (16, 5)])
def test_gdm_coflow_intervals_equal_the_scalar_path(m, seed, monkeypatch):
    inst = _instance(m, seed)

    def intervals():
        core.clear_caches()
        parts = core.plan(inst, "gdm", **PLAN_OPTS).schedule.parts
        return [[getattr(p.coflow_intervals(), f).tolist()
                 for f in ("t0", "t1", "s", "r", "owner", "jid", "cid")]
                for p in parts]

    with spans.recording() as rec:
        batched = intervals()
    assert sum(s.attrs["batched"] for s in rec.spans
               if s.name == "plan.nested_decompose") > 0
    monkeypatch.setattr(backend, "bna_pieces_many", _scalar)
    assert intervals() == batched


@pytest.mark.parametrize("m,seed", [(12, 6), (16, 8)])
def test_dma_rt_nested_plan_equals_the_scalar_path(m, seed, monkeypatch):
    inst = _instance(m, seed, rooted=True)

    def nested():
        core.clear_caches()
        sched = core.plan(inst, "gdm_rt", **PLAN_OPTS).schedule
        return (sched.job_completions(),
                [[(e.jid, e.cid, e.e0, e.e1) for e in p.ledger]
                 for p in sched.parts],
                [[getattr(p.merged, f).tolist()
                  for f in ("t0", "t1", "s", "r", "owner", "jid", "cid")]
                 for p in sched.parts])

    with spans.recording() as rec:
        batched = nested()
    assert any(s.attrs.get("batched", 0) > 0 for s in rec.spans
               if s.name == "plan.nested_decompose")
    monkeypatch.setattr(backend, "bna_pieces_many", _scalar)
    assert nested() == batched


@pytest.mark.parametrize("seed", [9, 10])
def test_gdm_bf_session_under_arrivals_unchanged(seed, monkeypatch):
    jobs = core.stream_jobs(12, 8, seed=seed, load=0.9, mu=3)

    def run():
        core.clear_caches()
        r = core.run_stream(jobs, 12, "gdm_bf", exec="packet", **PLAN_OPTS)
        return r.online.job_completions, r.online.twct()

    batched = run()
    assert len(batched[0]) == len(jobs)
    monkeypatch.setattr(backend, "bna_pieces_many", _scalar)
    assert run() == batched


def test_jit_buckets_give_the_scalar_pieces():
    """The jit plan backend decomposes the interval matrices on programs
    of their own (``jit_decompose_intervals``), counted apart from the
    coflow decomposition, and opens no ``plan.decompose`` span for them:
    their time stays in ``plan.nested_decompose``."""
    sched = _merged(12, 11, repeat=2)
    core.clear_caches()
    before = core.cache_stats()["launches"]
    with backend.use_plan_backend("jit"):
        with spans.recording() as rec:
            jit = _fields(_decompose(sched))
    after = core.cache_stats()["launches"]
    core.clear_caches()
    assert jit == _fields(_decompose(sched))
    assert after["decompose_intervals"] > before["decompose_intervals"]
    assert after["decompose"] == before["decompose"]
    assert [s.name for s in rec.spans] == ["plan.nested_decompose"]
    assert rec.spans[0].attrs["batched"] > 0


def test_interval_batches_of_any_size_share_one_program():
    """The interval path pads every batch of a (w, T_cap) bucket to the
    same lane count and every support to at least width 8, so batches of
    4 and of 6 matrices run one compiled program per width, and the pieces
    are still ``bna.bna``'s."""
    from repro.core import pipeline

    rng = np.random.default_rng(12)
    mats = []
    for _ in range(8):
        d = np.zeros((12, 12), dtype=np.int64)
        d[:10, :10] = rng.integers(0, 4, (10, 10)) * (rng.random((10, 10))
                                                      < 0.4)
        d[np.arange(10), np.arange(10)] += 1
        mats.append(d)
    tiny = []
    for k in (2, 3):                   # supports narrower than any bucket
        d = np.zeros((12, 12), dtype=np.int64)
        d[np.arange(k), (np.arange(k) + 1) % k] = np.arange(1, k + 1) * 5
        d[0, 0] = 3
        tiny.append(d)
    before = set(pipeline.compile_cache._od)
    with backend.use_plan_backend("jit"):
        got = pipeline.decompose_pieces(mats[:3] + tiny[:1]) + \
            pipeline.decompose_pieces(mats[3:] + tiny[1:])
    want = [bna(d) for d in mats[:3] + tiny[:1] + mats[3:] + tiny[1:]]
    assert [[(t, m.tolist()) for t, m in p] for p in got] == \
        [[(t, m.tolist()) for t, m in p] for p in want]
    fixed = {("bna_intervals", pipeline._fixed_lanes(16), 16, 256),
             ("bna_intervals", pipeline._fixed_lanes(8), 8, 64)}
    assert fixed <= set(pipeline.compile_cache._od)
    assert set(pipeline.compile_cache._od) - before <= fixed
