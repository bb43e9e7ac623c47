"""End-to-end training driver: a small LM trained for a few hundred steps
with the full production substrate — deterministic data pipeline, AdamW,
checkpointing + auto-resume, straggler monitoring.

  PYTHONPATH=src python examples/train_lm.py               # ~25M params, CPU
  PYTHONPATH=src python examples/train_lm.py --steps 300   # longer run

(The ~100M+ assigned architectures train with the same TrainRunner via
`python -m repro.launch.train --arch <id>`; on this CPU container use
--smoke there. The dry-run proves the full configs lower + fit on the
production mesh.)"""
import argparse
import tempfile

from repro.configs import get_config
from repro.core import configure_compile_cache
from repro.data.pipeline import DataConfig
from repro.ft import FTConfig, TrainRunner
from repro.models.common import LayerSpec
from repro.train.optim import OptConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config("tinyllama-1.1b").replace(
        name="example-lm",
        d_model=args.d_model, n_heads=4, n_kv_heads=2, d_head=args.d_model // 4,
        d_ff=4 * args.d_model, vocab=8192,
        period=(LayerSpec("attn", "dense"),), n_periods=args.layers,
        param_dtype="float32", compute_dtype="float32", remat="none")

    with tempfile.TemporaryDirectory() as ckpt:
        runner = TrainRunner(
            cfg,
            OptConfig(lr=3e-4, warmup_steps=max(args.steps // 10, 1),
                      total_steps=args.steps),
            DataConfig(seq_len=args.seq_len, global_batch=args.batch, seed=0),
            FTConfig(ckpt_dir=ckpt, ckpt_every=max(args.steps // 4, 1)),
        )
        runner.run(args.steps)
        log = runner.metrics_log
        print(f"steps: {len(log)}  loss {log[0]['loss']:.3f} -> {log[-1]['loss']:.3f}"
              f"  stragglers flagged: {len(runner.monitor.flagged)}")
        assert log[-1]["loss"] < log[0]["loss"], "training must reduce loss"


if __name__ == "__main__":
    main()
