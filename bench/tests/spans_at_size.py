"""Run a cell with the program's spans recording, on the chip.

    python3 bench/tests/spans_at_size.py --workload <cell> --seconds <s> \\
        --trace <0|1> SEED...

The run is ``bench/run.py``'s, with ``repro.core.spans`` recording from
the window's open to its close (``annotate=True`` in a traced run, so the
spans land in the profile).  Each seed prints the result line of the
benchmark, with the per-layer numbers of ``bench.lib.program_spans`` added
to ``metrics`` when traced, and its notes on standard error:
``compiles_by_span``, ``program_spans_per_request``,
``program_compiles_in_window``, ``time_by_span``, the idle gaps by
program span (``program_gaps``) and ``feed_compile_below_replan``.
Untraced, it measures what the recording costs: compare its
``plans_per_s`` with ``bench/run.py``'s.  All seeds run in one process.
The benchmark's own runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run as bench_run  # noqa: E402
from bench.lib import harness, program_spans  # noqa: E402
from bench.lib import trace as tr  # noqa: E402


class RecordingContext(harness.Context):
    """The harness's context, with the program's recording on in the
    window."""

    def open_window(self) -> None:
        from repro.core import spans

        super().open_window()
        spans.start(annotate=self.trace_dir is not None)

    def close_window(self) -> None:
        from repro.core import spans

        self.run.program = spans.stop()
        super().close_window()


def measure(bench: dict, cell: dict, seed: int, seconds: float,
            trace: bool, devices: list, config=None, traffic=None):
    """``bench.run.measure`` with the recording on: (result, Run, the
    ProgramTrace or None)."""
    reduce, got = tr.reduce, []

    def both(profile, anchor_perf, window_perf, compiles_perf=()):
        got.append(program_spans.reduce(profile, anchor_perf, window_perf,
                                        compiles_perf))
        return reduce(profile, anchor_perf, window_perf, compiles_perf)

    bench_run.Context, tr.reduce = RecordingContext, both
    try:
        out, run = bench_run.measure(bench, cell, seed, seconds, trace,
                                     devices, config, traffic)
    finally:
        bench_run.Context, tr.reduce = harness.Context, reduce
    ptrace = got[0] if got else None
    if trace:
        for name, v in program_spans.read(run, run.program, ptrace).items():
            out["metrics"][name] = {"value": v, "unit": "ms"}
    run.notes.update(program_spans.notes(run, run.program, ptrace))
    if ptrace is not None:
        run.notes["program_gaps"] = tr.top(ptrace.gaps, 20)
        run.notes["feed_compile_below_replan"] = \
            program_spans.below_replan_share(ptrace.gaps)
    return out, run, ptrace


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args()

    import jax

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("spans_at_size.py: no TPU", file=sys.stderr)
        return 2
    for seed in args.seeds:
        out, run, _ = measure(bench, cell, seed, args.seconds,
                              bool(args.trace), devices[:cell["chips"]])
        for k, v in run.notes.items():
            print(f"{k}: {json.dumps(v)}", file=sys.stderr)
        print(json.dumps({"spans": "recording", "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
