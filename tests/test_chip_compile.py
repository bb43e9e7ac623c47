"""Compile rehearsals for a described TPU v5e: the scheduler's device
programs go through the chip's own compiler (Mosaic for the Pallas
kernels, XLA:TPU for the jitted pipeline) at the shapes the 150-port
fabric produces, without a chip attached.

Interpret mode on the CPU cannot see what only the chip's compiler
refuses (primitives Mosaic does not lower, layouts it cannot cast, more
memory than the chip has), so these tests do.  Nothing runs: results are
checked by the interpret-mode parity tests in ``test_kernels.py`` and on
the chip by ``chip_smoke.py``.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

V5E_HBM_BYTES = 16 * 10**9
PORTS_150 = 2 * 150          # sender + receiver columns of the delta array


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU compiler logs to a fixed directory outside the checkout
    # unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with JAX's persistent cache off: entries
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _compile(fn, *avals):
    compiled = jax.jit(fn).lower(*avals).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, f"{total} bytes do not fit one v5e"
    return compiled, total


@pytest.mark.parametrize("K", [8, 4096, 65536])
def test_coflow_merge_compiles_for_v5e(one_chip, K):
    """The merge_and_fix alpha kernel as ``interval_alphas`` calls it
    (the fused ``merge_fix`` step reaches the same ``padded_alphas``):
    (K, 2m) deltas padded to a (K_pad, 384) tile, up to
    ``coflow_merge_padded`` at (65536, 384)."""
    from repro.kernels.coflow_merge.ops import kernel_alphas

    compiled, _ = _compile(
        lambda d: kernel_alphas(d, block_k=1024, interpret=False),
        _i32((K, PORTS_150), one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_merge_fix_programs_fit_one_v5e(one_chip):
    """The fused merge_fix step's one device program at the largest bucket
    the 150-port cell reaches (K_pad 16384): ``padded_alphas`` on the
    host-built (K_pad, 384) delta tile, no slice back to an exact K."""
    from repro.kernels.coflow_merge.ops import padded_alphas

    compiled, _ = _compile(
        lambda d: padded_alphas(d, block_k=1024, interpret=False),
        _i32((16384, 384), one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,w", [(128, 128), (64, 256)])
def test_bna_step_compiles_for_v5e(one_chip, B, w):
    from repro.kernels.bna_step.bna_step import bna_step_padded

    compiled, _ = _compile(
        lambda *a: bna_step_padded(*a, interpret=False),
        _i32((B, w, w), one_chip), _i32((B, w), one_chip),
        _i32((B, w), one_chip), _i32((B, 1), one_chip),
        _i32((B, w), one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_pipeline_decompose_fits_one_v5e(one_chip):
    """The jitted BNA decomposition at the chip-size w = 256 signature: a
    coflow of the full trace has up to ~22 500 flows, so T_cap = 32768,
    and the bucket runs in chunks of ``_chunk_lanes`` lanes."""
    from repro.core import pipeline

    w, T_cap = 256, 32768
    lanes = pipeline._chunk_lanes(w, T_cap)
    assert lanes * T_cap * w * 4 <= pipeline._PIECES_BYTES
    _, total = _compile(pipeline._build_decompose(w, T_cap),
                        _i32((lanes, w, w), one_chip), _i32((lanes,), one_chip))
    assert total < 2 * pipeline._PIECES_BYTES


def test_pipeline_loads_compiles_for_v5e(one_chip):
    from repro.core import pipeline

    _compile(pipeline._build_loads(150, 64),
             _i32((512, 150, 150), one_chip), _i32((512,), one_chip))
