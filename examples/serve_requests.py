"""Serving example: batched requests through the continuous-batching engine
with the paper's coflow-ordered admission vs FIFO.

  PYTHONPATH=src python examples/serve_requests.py
"""
import jax
import numpy as np

from repro.configs import get_config
from repro.core import configure_compile_cache
from repro.serve import Request, ServeConfig, ServingEngine
from repro.train.step import init_params


def main() -> None:
    configure_compile_cache()
    cfg = get_config("qwen3-1.7b").smoke()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def make_requests():
        return [Request(rid=i,
                        tokens=rng.integers(1, cfg.vocab,
                                            size=int(rng.integers(4, 20))),
                        max_new=8,
                        weight=float(rng.uniform(0.5, 3.0)),
                        arrival=float(i // 3))
                for i in range(9)]

    for admission in ("coflow", "fifo"):
        eng = ServingEngine(cfg, params,
                            ServeConfig(slots=3, capacity=64,
                                        admission=admission))
        stats = eng.run(make_requests())
        print(f"{admission:6s}: completed={stats['completed']} "
              f"decode_steps={stats['steps']} "
              f"weighted_finish={stats['weighted_finish']:.1f}")


if __name__ == "__main__":
    main()
