"""Run the control of a cell at the cell's own size, on the chip.

    python3 bench/tests/control_at_size.py --workload <cell> --seconds <s> SEED...

The control is the program's own admission path switched on
(``AdmissionPolicy(max_pending=64, replan_budget=0.0, window=4)``), as in
``test_faults.test_control_admission_is_caught``: it defers arrivals and
so breaks the pure-mode guarantee that the configuration states.  Each
seed prints one JSON line with ``correct`` (it has to read false) and
the compared numbers.  All seeds run in one process, so set-up compiles
once.  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run as bench_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args()

    import jax

    import repro.core as core

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control_at_size.py: no TPU", file=sys.stderr)
        return 2
    real = core.StreamDriver

    def with_admission(*a, **kw):
        return real(*a, admission=core.AdmissionPolicy(
            max_pending=64, replan_budget=0.0, window=4), **kw)

    core.StreamDriver = with_admission
    for seed in args.seeds:
        core.clear_caches()
        out, run = bench_run.measure(bench, cell, seed, args.seconds, False,
                                     devices[:cell["chips"]])
        print(json.dumps({"control": "admission", "seed": seed,
                          "correct": out["correct"], "failed": out["failed"],
                          "checks": out["checks"], "notes": run.notes}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
