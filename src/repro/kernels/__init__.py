"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships three files:
  <name>/<name>.py — pl.pallas_call with explicit BlockSpec VMEM tiling
  <name>/ops.py    — jit'd public wrapper (padding, layout, interpret switch)
  <name>/ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels:
  flash_attention — blocked online-softmax GQA attention (train/prefill)
  ssd_scan        — Mamba2 state-space-duality chunked scan
  coflow_merge    — the paper's DMA merge hot loop: per-interval per-port
                    packet counts and alpha_t via running prefix sums
  bna_step        — the batched matching hot loop: one lock-step iteration
                    of the multi-coflow BNA decomposition (step lengths,
                    transmissions, matched-edge invalidation) over a
                    (B, w, w) demand stack; bit-identical to its numpy ref

On a TPU backend every kernel is compiled by Mosaic; on any other backend
`default_interpret()` is True and the kernels run in Pallas interpret mode,
which is how the CPU test suite validates them against the refs.  Whether
the scheduler kernels compile for a v5e is checked without a chip by
``tests/test_chip_compile.py``; ``chip_smoke.py`` runs them on one.

Every launch of a scheduler device program is counted here, at the one
place that launches it (``count_launch``): the Pallas kernels after their
int32 range guards, and the jitted planning pipeline's ``decompose``,
``decompose_intervals`` and ``loads`` programs.  ``repro.core.cache_stats()["launches"]`` reports the
counts, so a run shows which device paths did its work.
"""

_launches = {"coflow_merge": 0, "bna_step": 0, "decompose": 0,
             "decompose_intervals": 0, "loads": 0}


def count_launch(name: str) -> None:
    _launches[name] += 1


def launch_counts() -> dict:
    return dict(_launches)


def reset_launch_counts() -> None:
    for k in _launches:
        _launches[k] = 0


def default_interpret() -> bool:
    import jax

    return jax.default_backend() != "tpu"
