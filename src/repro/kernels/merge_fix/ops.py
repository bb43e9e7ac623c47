"""merge_fix — the fused merge_and_fix tail reusing the coflow_merge kernel.

One call takes the raw merged edge activations and produces both the
per-interval alphas AND the expanded interval durations
``len_i * max(alpha_i, 1)`` (Lemma 6).  The binning and the delta scatter
run on the host, the prefix-sum/max on the device (the coflow_merge Pallas
kernel, one transfer each way), and the duration product on the host.

Shapes: the device sees only the bucket K_pad, the number of merged
intervals K rounded up to a power of two of at least 8 (a multiple of the
kernel's power-of-two block), never an exact K or the number of edge
activations.  Rows past K hold at most the ends of activations on the
last event, and nothing reads them back.  So the kernel compiles once per
(K_pad, ports) bucket in a process, not once per call;
``merge_fix_stats()`` counts the calls and the buckets seen, and the
innermost open span gets ``k_pad``.

Exactness: everything is integer arithmetic.  The delta counts and the
alphas are bounded by the activation count, which must fit the kernel's
int32 accumulators; the duration product is int64, bit-identical to
``ref.merge_fix_ref``.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.spans import annotate

from .. import default_interpret
from ..coflow_merge.ops import padded_alphas

_INT32_MAX = np.int64(2**31 - 1)

_calls = [0]
_buckets: set = set()


def merge_fix_stats() -> dict:
    """Calls of the fused step, and the distinct (K_pad, ports) buckets it
    has seen in this process (its misses: each builds one kernel program
    on its first call)."""
    return {"calls": _calls[0], "misses": len(_buckets)}


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
    k_pad = max(8, 1 << (K - 1).bit_length())
    lanes = 2 * m + (-2 * m) % 128
    _calls[0] += 1
    _buckets.add((k_pad, lanes))
    annotate(k_pad=k_pad)
    # (k_pad + 1, lanes) deltas, flat: +1 where an activation starts on its
    # sender and receiver lanes, -1 where it ends; row k_pad (an end on the
    # last event when K == k_pad) is dropped, as build_delta drops row K
    si = np.searchsorted(events, t0) * lanes
    ei = np.searchsorted(events, t1) * lanes
    s = np.asarray(s)
    r = np.asarray(r) + m
    delta = np.zeros((k_pad + 1) * lanes, dtype=np.int32)
    np.add.at(delta, np.concatenate([si + s, si + r]), 1)
    np.subtract.at(delta, np.concatenate([ei + s, ei + r]), 1)
    delta = delta[:k_pad * lanes].reshape(k_pad, lanes)
    if use_kernel:
        al = np.asarray(padded_alphas(jnp.asarray(delta),
                                      block_k=min(block_k, k_pad),
                                      interpret=interpret))[:K, 0]
    else:
        al = np.cumsum(delta[:K], axis=0).max(axis=1)
    alphas = al.astype(np.int64)
    lens = np.asarray(events[1:] - events[:-1], dtype=np.int64)
    return alphas, lens * np.maximum(alphas, 1)
