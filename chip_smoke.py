#!/usr/bin/env python3
"""Chip smoke test: run the coflow planner's device path once on one TPU
and check every answer against the host reference.

    python chip_smoke.py [--scale S] [--seed N] [--full-trace]

The workload is the paper's Facebook-trace fabric (``paper_workload``:
m = 150 ports, flow sizes 1-2472, ~5 coflows per job).  ``--scale`` cuts
the number of coflows (and the widest coflow) proportionally in phases A
to C, whose host reference must finish in the same process; phase D
always takes the whole trace.  The port count is never cut.  Phases A to
C run by default, phase D alone with ``--full-trace``, all in one
process:

* A, offline: ``plan(..., "gdm")`` and ``plan(..., "gdm_rt")`` on the
  device path (``auto`` backends on a TPU: jit plan pipeline, pallas
  kernels) against the same plans on the reference path (python plan
  backend, numpy alpha and BNA backends), compared transcript for
  transcript.
* B, online: the same instance with Poisson releases fed one arrival at a
  time through ``run_stream`` on the device path, against
  ``simulate_online(..., driver="batch")`` on the reference path: job
  completions and TWCT must be equal.
* C, kernel: the batched BNA (``bna_many``) with the pallas ``bna_step``
  kernel against the scalar ``bna`` reference on a few of the instance's
  coflows.  The served path does not reach this kernel (the jit pipeline
  decomposes in XLA), so it is checked on its own.
* D, full trace: the decomposition stage of the served path
  (``prefetch_plan``) on all of the trace's coflows, never cut.  Its
  widest bucket needs more than one lane chunk, so this is where the
  chunked decomposition runs at the size it was built for.  No reference
  can finish at this size, so every decomposition is checked on its own:
  each must be a valid BNA schedule (``verify_bna_schedule``: matchings
  that serve the demand exactly, in the effective size).  It takes about
  14 minutes on one TPU v5e, too long to run beside A to C by default.

Each phase prints one JSON line: wall seconds, the compile counters of
``cache_stats()["plan"]``, the device program launches of
``cache_stats()["launches"]``, and the device's peak memory.  Every cache is
cleared before each run, so the reference never reads a device result.  The
last line is ``{"ok": true, "device": {...}}``.  Without a TPU, or on any
mismatch or exception, the script exits nonzero before that line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from contextlib import contextmanager
from pathlib import Path

import numpy as np

M_PORTS = 150   # the paper's fabric (PAPER_STATS["m"]); never cut
KERNEL_COFLOWS = 8


def emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: {msg}")


@contextmanager
def reference_backends(core):
    with core.use_plan_backend("python"), core.use_alpha_backend("numpy"), \
            core.use_bna_backend("numpy"):
        yield


def fingerprint(p) -> tuple:
    """TWCT, makespan, per-job completions and the whole transcript; flows
    inside an entry are sorted, since the jit pipeline emits a coflow's
    edges in (sender, start) order and the python path does not."""
    entries = sorted(
        (e.jid, e.cid, float(e.t0), float(e.t1),
         tuple(sorted(zip(np.asarray(e.srcs).tolist(),
                          np.asarray(e.dsts).tolist(),
                          np.asarray(e.units, dtype=float).tolist()))))
        for e in p.transcript().entries)
    return (p.twct(), p.makespan, sorted(p.job_completions().items()),
            entries)


def device_counters(core) -> dict:
    st = core.cache_stats()
    comp = st.get("plan", {}).get("compile", {})
    return {"launches": st["launches"],
            "compiles": comp.get("compiles", 0),
            "compile_s": comp.get("compile_s", 0.0),
            "bucket_fallbacks": comp.get("bucket_fallbacks", 0)}


def peak_bytes(jax) -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_device(core, fn):
    """fn() on the device path with every result cache cold; returns
    (result, wall seconds, counters of this run alone)."""
    core.clear_caches()
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    counters = device_counters(core)
    if counters["bucket_fallbacks"]:
        fail(f"{counters['bucket_fallbacks']} bucket(s) left the device "
             "through the int32 range guard")
    return out, wall, counters


def run_reference(core, fn):
    core.clear_caches()
    t0 = time.perf_counter()
    with reference_backends(core):
        out = fn()
    return out, time.perf_counter() - t0


def require_launches(phase: str, counters: dict, names) -> None:
    missing = [n for n in names if not counters["launches"].get(n)]
    if missing:
        fail(f"phase {phase}: {missing} never launched on the device")


def phase_offline(core, jax, scale: float, seed: int) -> None:
    for sched, rooted in (("gdm", False), ("gdm_rt", True)):
        inst = core.paper_workload(m=M_PORTS, mu_bar=5, seed=seed,
                                   scale=scale, rooted=rooted)
        dev, wall, counters = run_device(
            core, lambda: core.plan(inst, sched, seed=seed))
        require_launches(f"A/{sched}", counters,
                         ("decompose", "loads", "coflow_merge"))
        ref, ref_wall = run_reference(
            core, lambda: core.plan(inst, sched, seed=seed))
        equal = fingerprint(dev) == fingerprint(ref)
        emit({"phase": f"A_offline_{sched}", "jobs": inst.n,
              "coflows": sum(j.mu for j in inst.jobs),
              "twct": dev.twct(), "equal_to_reference": equal,
              "wall_s": wall, "reference_wall_s": ref_wall,
              "peak_bytes_in_use": peak_bytes(jax), **counters})
        if not equal:
            fail(f"phase A/{sched}: device plan differs from the reference "
                 f"(twct {dev.twct()} vs {ref.twct()})")


def phase_online(core, jax, scale: float, seed: int) -> None:
    base = core.paper_workload(m=M_PORTS, mu_bar=5, seed=seed, scale=scale)
    inst = core.poisson_releases(base, theta=3 * core.theta0(base),
                                 seed=seed)
    opts = dict(delays="spread", seed=seed)
    dev, wall, counters = run_device(
        core, lambda: core.run_stream(inst.jobs, inst.m, "gdm", **opts))
    require_launches("B", counters, ("decompose", "loads", "coflow_merge"))
    ref, ref_wall = run_reference(
        core, lambda: core.simulate_online(inst, "gdm", driver="batch",
                                           **opts))
    equal = (dev.online.job_completions == ref.job_completions
             and dev.online.twct() == ref.twct())
    emit({"phase": "B_online_gdm_spread", "jobs": inst.n,
          "twct": dev.online.twct(), "equal_to_reference": equal,
          "arrivals": int(dev.latencies_s.size),
          "p50_ms": dev.p50_ms, "p99_ms": dev.p99_ms,
          "wall_s": wall, "reference_wall_s": ref_wall,
          "peak_bytes_in_use": peak_bytes(jax), **counters})
    if not equal:
        fail(f"phase B: streamed completions differ from the batch "
             f"reference (twct {dev.online.twct()} vs {ref.twct()})")


def phase_kernel(core, jax, scale: float, seed: int) -> None:
    inst = core.paper_workload(m=M_PORTS, mu_bar=5, seed=seed, scale=scale)
    demands = [c.demand for j in inst.jobs for c in j.coflows]
    demands = demands[:KERNEL_COFLOWS]
    dev, wall, counters = run_device(core, lambda: core.bna_many(demands))
    require_launches("C", counters, ("bna_step",))
    t0 = time.perf_counter()
    ref = [core.bna(d) for d in demands]
    ref_wall = time.perf_counter() - t0
    equal = all(
        len(a) == len(b) and all(ta == tb and np.array_equal(ma, mb)
                                 for (ta, ma), (tb, mb) in zip(a, b))
        for a, b in zip(dev, ref))
    emit({"phase": "C_kernel_bna_step", "coflows": len(demands),
          "steps": sum(len(p) for p in dev), "equal_to_reference": equal,
          "wall_s": wall, "reference_wall_s": ref_wall,
          "peak_bytes_in_use": peak_bytes(jax), **counters})
    if not equal:
        fail("phase C: bna_step pieces differ from the scalar bna reference")


def phase_full_trace(core, jax, seed: int) -> None:
    from repro.core.bna import support_restrict, verify_bna_schedule
    from repro.core.matching import bucket_width

    inst = core.paper_workload(m=M_PORTS, mu_bar=5, seed=seed, scale=1.0)
    demands = [c.demand for j in inst.jobs for c in j.coflows]
    _, wall, counters = run_device(core, lambda: core.prefetch_plan(demands))
    widths = set()
    for d in demands:
        sub = support_restrict(np.asarray(d, np.int64))[0]
        if sub is not None:
            widths.add(bucket_width(sub.shape[0]))
    launches = counters["launches"]["decompose"]
    if launches <= len(widths):
        fail(f"phase D: {launches} decompose launches for {len(widths)} "
             "width buckets; no bucket ran in more than one lane chunk")
    misses = core.cache_stats()["bna"]["misses"]
    t0 = time.perf_counter()
    for d in demands:
        verify_bna_schedule(np.asarray(d, np.int64),
                            core.backend.bna_pieces(d))
    verify_s = time.perf_counter() - t0
    if core.cache_stats()["bna"]["misses"] != misses:
        fail("phase D: a decomposition was recomputed on the host")
    emit({"phase": "D_full_trace_decompose", "coflows": len(demands),
          "width_buckets": len(widths), "valid_bna_schedules": True,
          "wall_s": wall, "verify_wall_s": verify_s,
          "peak_bytes_in_use": peak_bytes(jax), **counters})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, default=0.15,
                    help="fraction of the trace's 267 coflows (and of its "
                         "widest coflow) that phases A-C plan; ports stay "
                         "at 150")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full-trace", action="store_true",
                    help="run phase D alone: decompose all 267 coflows of "
                         "the trace on the device")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{devices[0].platform!r}); the device path cannot run and "
              "this script does not fall back to the CPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import repro.core as core
    from repro.kernels import default_interpret

    cache_dir = core.configure_compile_cache()
    warnings.simplefilter("error", RuntimeWarning)
    backends = {"alpha": core.backend.resolve_alpha_backend(),
                "bna": core.backend.resolve_bna_backend(),
                "plan": core.backend.resolve_plan_backend()}
    if backends != {"alpha": "pallas", "bna": "pallas", "plan": "jit"}:
        fail(f"auto backends resolved to {backends} on a TPU")
    if default_interpret():
        fail("Pallas kernels would run in interpret mode on a TPU")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    emit({"device": device, "backends": backends, "ports": M_PORTS,
          "scale": args.scale, "seed": args.seed,
          "reduced": ({"scale": args.scale, "phases": "A-C"}
                      if args.scale < 1.0 and not args.full_trace else {}),
          "compile_cache": cache_dir})

    if args.full_trace:
        phase_full_trace(core, jax, args.seed)
    else:
        phase_offline(core, jax, args.scale, args.seed)
        phase_online(core, jax, args.scale, args.seed)
        phase_kernel(core, jax, args.scale, args.seed)
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
