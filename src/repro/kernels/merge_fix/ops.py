"""merge_fix — the fused merge_and_fix tail reusing the coflow_merge kernel.

One call takes the raw merged edge activations and produces both the
per-interval alphas AND the expanded interval durations
``len_i * max(alpha_i, 1)`` (Lemma 6), keeping the binning, the delta
scatter, the prefix-sum/max (the coflow_merge Pallas kernel), and the
duration product in a single device round-trip instead of the
searchsorted → kernel → host → numpy product chain the classic path runs.

Exactness: everything is integer arithmetic.  The duration product runs
in-graph in int32 only when ``max(len) * E`` provably fits (activation
counts bound every alpha by E); otherwise it falls back to a host-side
int64 product — never an error, always bit-identical to
``ref.merge_fix_ref``.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import default_interpret
from ..coflow_merge.ops import kernel_alphas
from ..coflow_merge.ref import alphas_ref, build_delta

_INT32_MAX = np.int64(2**31 - 1)


def merge_fix_step(
    events: np.ndarray,  # (K+1,) sorted unique interval boundaries
    t0: np.ndarray,      # (E,) edge activation start times
    t1: np.ndarray,      # (E,) edge activation end times (exclusive)
    s: np.ndarray,       # (E,) sender port
    r: np.ndarray,       # (E,) receiver port
    m: int,
    *,
    block_k: int = 1024,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[np.ndarray, np.ndarray]:
    """Returns (alphas (K,) int64, deltas (K,) int64); deltas cumsum to
    merge_and_fix's ``exp`` (before the origin shift)."""
    K = int(events.size) - 1
    if K < 1:
        return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64)
    if interpret is None:
        interpret = default_interpret()
    E = int(np.asarray(t0).size)
    if E >= int(_INT32_MAX):
        # delta entries and alphas are activation counts bounded by E, and
        # the kernel accumulates them in int32
        raise ValueError("too many edge activations for the int32 "
                         f"coflow_merge accumulator ({E} >= 2^31-1)")
    si = np.searchsorted(events, t0)
    ei = np.searchsorted(events, t1)
    delta = build_delta(jnp.asarray(si), jnp.asarray(ei), jnp.asarray(s),
                        jnp.asarray(r), K, m)
    if use_kernel:
        al = kernel_alphas(delta, block_k=block_k, interpret=interpret)
    else:
        al = alphas_ref(delta)
    lens = np.asarray(events[1:] - events[:-1], dtype=np.int64)
    max_len = int(lens.max(initial=0))
    if max_len * max(E, 1) < int(_INT32_MAX):
        # alphas <= E (each activation contributes at most one count per
        # port), so every product fits int32: fuse it in-graph
        deltas = np.asarray(
            jnp.asarray(lens, dtype=jnp.int32)
            * jnp.maximum(al.astype(jnp.int32), 1),
            dtype=np.int64)
        return np.asarray(al, dtype=np.int64), deltas
    alphas = np.asarray(al, dtype=np.int64)
    return alphas, lens * np.maximum(alphas, 1)
