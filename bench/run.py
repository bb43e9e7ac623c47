#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``.  Its configuration
(``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``) are data; the traffic file names the
driver (``bench/drivers/<driver>.py``) that builds the inputs from the
seed, warms up, measures for ``--seconds``, and checks what the timed
path produced against the plain reference (``bench/reference``).  Each
metric of the cell is computed by its own reader,
``bench/metrics/<metric>.py``: the end-to-end metrics with ``--trace 0``,
the per-layer metrics with ``--trace 1`` (profiler on, and the window closed
after the traffic's ``trace_requests`` answers).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``, each compared number with its limit.  The same
numbers end standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime logs to a fixed directory under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.lib.harness import NAME, Context, load_json  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    if not trace:
        return [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])]


def measure(bench: dict, cell: dict, seed: int, seconds: float,
            trace: bool, devices: list, config: dict | None = None,
            traffic: dict | None = None) -> tuple[dict, object]:
    """One run of ``cell`` on ``devices``: (the result object, the Run).
    ``config`` and ``traffic`` default to the cell's files."""
    config = config or load_json("configs", cell["config"])
    traffic = traffic or load_json("traffic", cell["traffic"])
    if not NAME.match(traffic["driver"]):
        raise ValueError(f"bad driver name {traffic['driver']!r}")
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    trace_dir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = TRACE_DIR
    ctx = Context(config, traffic, seed, seconds, trace_dir, T_PROCESS)
    run = driver.run(ctx)
    # on standard error in every run, traced or not: what the window spent
    # compiling, the first suspect when runs of the same work drift apart
    inside = [d for t, d in run.compiles
              if run.window[0] <= t <= run.window[1]]
    run.notes["window_compiles"] = len(inside)
    run.notes["window_compile_s"] = round(sum(inside), 3)

    device ={"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": {}, "device": device}
    if trace:
        from jax.profiler import ProfileData

        from bench.lib import trace as tr

        t0 = time.perf_counter()
        pb = sorted(TRACE_DIR.glob("**/*.xplane.pb"))[-1]
        run.notes["trace_bytes"] = pb.stat().st_size
        run.trace = tr.reduce(ProfileData.from_file(str(pb)),
                              run.notes.pop("window_anchor"), run.window,
                              run.compiles)
        run.notes["trace_reduce_s"] = round(time.perf_counter() - t0, 1)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": tr.top(run.trace.modules),
                            "idle_gaps": tr.top(run.trace.gaps)}
    for m in metrics_for(bench, cell["name"], trace):
        if not NAME.match(m["name"]):
            raise ValueError(f"bad metric name {m['name']!r}")
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(run)
        if value is not None:
            out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in run.checks}
    return out, run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform!r} device(s). "
              "The benchmark does not fall back to another platform.",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    out, run = measure(bench, cell, args.seed, args.seconds,
                       bool(args.trace), devices[:cell["chips"]])
    for k, v in run.notes.items():
        print(f"{k}: {v}", file=sys.stderr)
    for name, v, lim in run.checks:
        print(f"{name} {v!r} limit {lim!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
