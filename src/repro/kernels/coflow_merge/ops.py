"""Public wrapper for coflow_merge: scatter the edge activations into the
delta array, pad to kernel tiles, dispatch (interpret on CPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import count_launch, default_interpret
from .coflow_merge import coflow_merge_padded
from .ref import alphas_ref, build_delta

_I32_MAX = int(np.iinfo(np.int32).max)


def interval_alphas(
    si: np.ndarray,   # (E,) start interval index per edge activation
    ei: np.ndarray,   # (E,) end interval index (exclusive)
    s: np.ndarray,    # (E,) sender port
    r: np.ndarray,    # (E,) receiver port
    K: int,
    m: int,
    *,
    block_k: int = 1024,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> np.ndarray:
    """alpha_t per merged interval (DMA Steps 3-4)."""
    if K <= 0:
        return np.zeros(0, dtype=np.int64)
    if interpret is None:
        interpret = default_interpret()
    if int(np.asarray(si).size) >= _I32_MAX:
        # per-port activation counts are bounded by E, and the delta
        # accumulators are int32 — past this nothing (kernel or ref) is exact
        raise ValueError(
            f"coflow_merge: {np.asarray(si).size} edge activations overflow "
            "the int32 delta accumulators")
    delta = build_delta(jnp.asarray(si), jnp.asarray(ei), jnp.asarray(s),
                        jnp.asarray(r), K, m)
    if not use_kernel:
        return np.asarray(alphas_ref(delta), dtype=np.int64)
    return np.asarray(kernel_alphas(delta, block_k=block_k,
                                    interpret=interpret), dtype=np.int64)


def kernel_alphas(delta: jax.Array, *, block_k: int,
                  interpret: bool) -> jax.Array:
    """(K,) int32 alphas of a (K, ports) delta array through the kernel:
    K is padded to a power-of-two block (at most `block_k`, at least 8)
    and ports to the 128-lane multiple.  Shared by `interval_alphas` and
    the fused merge_fix step."""
    K, ports = delta.shape
    bk = min(block_k, max(8, 1 << (K - 1).bit_length()))
    k_pad = (-K) % bk
    p_pad = (-ports) % 128
    # Pallas indexes the padded delta with int32 arithmetic; past that the
    # jnp reference (64-bit indexing) is the only correct path.
    if (K + k_pad) * (ports + p_pad) >= _I32_MAX:
        return alphas_ref(delta)
    count_launch("coflow_merge")
    dpad = jnp.pad(delta, ((0, k_pad), (0, p_pad)))
    return coflow_merge_padded(dpad, block_k=bk, interpret=interpret)[:K, 0]


def edge_interval_alphas(
    events: np.ndarray,  # (K+1,) sorted unique interval boundaries
    t0: np.ndarray,      # (E,) edge activation start times
    t1: np.ndarray,      # (E,) edge activation end times (exclusive)
    s: np.ndarray,
    r: np.ndarray,
    m: int,
    **kw,
) -> np.ndarray:
    """interval_alphas from raw edge-interval times: the merge_and_fix entry
    point used by the engine's backend dispatch (core/backend.py).  Bins the
    activation times into interval indices, then runs the kernel."""
    si = np.searchsorted(events, t0)
    ei = np.searchsorted(events, t1)
    return interval_alphas(si, ei, np.asarray(s), np.asarray(r),
                           int(events.size) - 1, m, **kw)
