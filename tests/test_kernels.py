"""Pallas kernel validation: shape/dtype sweeps, interpret mode vs the
pure-jnp ref oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.coflow_merge import interval_alphas
from repro.kernels.coflow_merge.ref import alphas_ref, build_delta
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_decode_step, ssd_ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("shape", [
    (1, 2, 2, 16, 16, 32),    # MHA square
    (2, 4, 2, 33, 33, 24),    # GQA, ragged seq
    (1, 8, 2, 64, 128, 48),   # cross-length (prefill-with-prefix)
    (1, 4, 1, 1, 96, 64),     # decode shape (q_len = 1)
    (1, 4, 4, 48, 48, 128),   # MXU-aligned head dim
])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(shape, dtype, tol, causal):
    B, Hq, Hkv, Sq, Sk, d = shape
    q = jnp.asarray(RNG.normal(size=(B, Hq, Sq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, Sk, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, Sk, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = attention_ref(q, k, v, causal=causal)
    err = float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max())
    assert err < tol, err


@pytest.mark.parametrize("shape,chunk", [
    ((1, 16, 2, 1, 8, 16), 8),
    ((2, 33, 4, 2, 16, 32), 16),    # ragged + state groups
    ((1, 64, 2, 2, 32, 64), 32),
    ((1, 40, 8, 1, 16, 8), 64),     # chunk > seq
])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4)])
def test_ssd_scan_sweep(shape, chunk, dtype, tol):
    B, S, H, G, N, P = shape
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), dtype)
    a = jnp.asarray(RNG.uniform(0.55, 1.0, size=(B, S, H)), dtype)
    b = jnp.asarray(RNG.normal(size=(B, S, G, N)), dtype) * 0.3
    c = jnp.asarray(RNG.normal(size=(B, S, G, N)), dtype) * 0.3
    out = ssd_scan(x, a, b, c, chunk=chunk)
    ref = ssd_ref(x, a, b, c)
    rel = float(jnp.abs(out - ref).max() / (jnp.abs(ref).max() + 1e-9))
    assert rel < tol, rel


def test_ssd_decode_step_matches_scan_tail():
    B, S, H, G, N, P = 1, 12, 2, 1, 8, 16
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), jnp.float32)
    a = jnp.asarray(RNG.uniform(0.6, 1.0, size=(B, S, H)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(B, S, G, N)), jnp.float32)
    c = jnp.asarray(RNG.normal(size=(B, S, G, N)), jnp.float32)
    full = ssd_ref(x, a, b, c)
    h = jnp.zeros((B, H, N, P), jnp.float32)
    rep = H // G
    for t in range(S):
        h, y = ssd_decode_step(h, x[:, t], a[:, t], b[:, t], c[:, t])
        assert float(jnp.abs(y - full[:, t]).max()) < 1e-4


@pytest.mark.parametrize("seed", range(6))
def test_coflow_merge_sweep(seed):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(2, 40))
    E = int(rng.integers(1, 500))
    t0 = rng.integers(0, 300, E)
    t1 = t0 + rng.integers(1, 60, E)
    events = np.unique(np.concatenate([t0, t1]))
    si = np.searchsorted(events, t0)
    ei = np.searchsorted(events, t1)
    s = rng.integers(0, m, E)
    r = rng.integers(0, m, E)
    K = events.size - 1
    got = interval_alphas(si, ei, s, r, K, m, block_k=64)
    ref = np.asarray(alphas_ref(build_delta(
        jnp.asarray(si), jnp.asarray(ei), jnp.asarray(s), jnp.asarray(r), K, m)))
    assert (got == ref).all()


def test_coflow_merge_empty():
    assert interval_alphas(np.zeros(0, int), np.zeros(0, int),
                           np.zeros(0, int), np.zeros(0, int), 0, 4).size == 0


def _random_bna_state(rng, B, w):
    """A batch of BNA-step states: demands with consistent row/col/D and a
    partial matching (the kernel's arithmetic contract doesn't require the
    matching to be perfect — parity must hold on any state, including the
    drained all-zero matrices the batch loop leaves in place)."""
    d = rng.integers(0, 40, size=(B, w, w))
    d[rng.random((B, w, w)) > 0.6] = 0
    d[0] = 0                                      # a drained matrix
    row = d.sum(axis=2)
    col = d.sum(axis=1)
    D = np.maximum(row.max(axis=1), col.max(axis=1))
    match = np.full((B, w), -1, dtype=np.int64)
    for i in range(B):
        perm = rng.permutation(w)
        keep = rng.random(w) < 0.8
        match[i, keep] = perm[keep]
    match[0] = -1
    return (d.astype(np.int64), row.astype(np.int64), col.astype(np.int64),
            D.astype(np.int64), match)


@pytest.mark.parametrize("B,w", [(1, 1), (3, 2), (8, 8), (17, 13), (40, 32)])
@pytest.mark.parametrize("seed", [0, 1])
def test_bna_step_kernel_bit_identical(B, w, seed):
    from repro.kernels.bna_step import bna_step_batch
    from repro.kernels.bna_step.ref import bna_step_ref

    rng = np.random.default_rng(seed)
    state = _random_bna_state(rng, B, w)
    got = bna_step_batch(*state)
    want = bna_step_ref(*state)
    names = ("t", "piece", "d", "row", "col", "D", "invalid")
    for name, g, r in zip(names, got, want):
        assert np.array_equal(np.asarray(g, dtype=np.int64),
                              np.asarray(r, dtype=np.int64)), \
            f"bna_step {name} diverged (B={B}, w={w})"


def test_bna_step_int32_guard():
    from repro.kernels.bna_step.ops import bna_step_batch

    d = np.zeros((1, 2, 2), np.int64)
    d[0, 0, 0] = 2**40
    row = d.sum(axis=2)
    col = d.sum(axis=1)
    D = row.max(axis=1)
    match = np.full((1, 2), -1, np.int64)
    with pytest.raises(ValueError, match="int32"):
        bna_step_batch(d, row, col, D, match)


@pytest.mark.parametrize("seed", range(4))
def test_merge_fix_step_matches_ref(seed):
    from repro.kernels.merge_fix import merge_fix_step
    from repro.kernels.merge_fix.ref import merge_fix_ref

    rng = np.random.default_rng(seed)
    m = int(rng.integers(2, 30))
    E = int(rng.integers(1, 400))
    t0 = rng.integers(0, 250, E)
    t1 = t0 + rng.integers(1, 50, E)
    s = rng.integers(0, m, E)
    r = rng.integers(0, m, E)
    events = np.unique(np.concatenate([t0, t1]))
    for use_kernel in (True, False):
        al, de = merge_fix_step(events, t0, t1, s, r, m,
                                use_kernel=use_kernel, block_k=64)
        ral, rde = merge_fix_ref(events, t0, t1, s, r, m)
        assert np.array_equal(al, ral) and np.array_equal(de, rde), \
            f"merge_fix diverged (m={m}, E={E}, kernel={use_kernel})"


def test_merge_fix_step_empty_and_int64_lens():
    from repro.kernels.merge_fix import merge_fix_step
    from repro.kernels.merge_fix.ref import merge_fix_ref

    z = np.zeros(0, np.int64)
    al, de = merge_fix_step(np.array([0], np.int64), z, z, z, z, 4)
    assert al.size == 0 and de.size == 0
    # interval lengths too big for the in-graph int32 product: the host
    # int64 fallback must still match the oracle exactly
    t0 = np.array([0, 0], np.int64)
    t1 = np.array([2**33, 2**32], np.int64)
    s = np.array([0, 1], np.int64)
    r = np.array([1, 0], np.int64)
    events = np.unique(np.concatenate([t0, t1]))
    al, de = merge_fix_step(events, t0, t1, s, r, 2)
    ral, rde = merge_fix_ref(events, t0, t1, s, r, 2)
    assert np.array_equal(al, ral) and np.array_equal(de, rde)
    assert de.dtype == np.int64 and de.max() > 2**31


def _bucket_edge_case(E, K, rng):
    """E activations over exactly K intervals on 150 ports, one of them
    ending on the last event (``ei == K``) and one starting on the first."""
    events = np.cumsum(rng.integers(1, 40, K + 1)) - 1
    i0 = rng.integers(0, K, E)
    i1 = np.minimum(i0 + rng.integers(1, 6, E), K)
    i0[0], i1[0] = 0, K
    i1[-1] = K
    m = 150
    return (events.astype(np.int64), events[i0], events[i1],
            rng.integers(0, m, E), rng.integers(0, m, E), m)


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("K", [7, 8, 9, 1023, 1024, 1025])
@pytest.mark.parametrize("E", [127, 128, 129, 255, 256, 257])
def test_merge_fix_step_exact_across_buckets(E, K, use_kernel):
    """Exact E and K on either side of a power of two land in different
    padded buckets; the padded activations and intervals must change no
    bit of what is read back."""
    from repro.kernels.merge_fix import merge_fix_step
    from repro.kernels.merge_fix.ref import merge_fix_ref

    events, t0, t1, s, r, m = _bucket_edge_case(
        E, K, np.random.default_rng(E * 10007 + K))
    assert np.searchsorted(events, t1).max() == K
    al, de = merge_fix_step(events, t0, t1, s, r, m, use_kernel=use_kernel)
    ral, rde = merge_fix_ref(events, t0, t1, s, r, m)
    assert al.dtype == de.dtype == np.int64
    assert np.array_equal(al, ral) and np.array_equal(de, rde)


def test_merge_fix_step_compiles_once_per_bucket():
    """Two calls with different exact (E, K) inside one K_pad bucket build
    one program: the second compiles nothing.  A call in a new bucket
    builds exactly one more."""
    from repro.core import cache_stats, spans
    from repro.kernels.merge_fix import merge_fix_step
    from repro.kernels.merge_fix.ref import merge_fix_ref

    rng = np.random.default_rng(5)
    m = 450                # 1024 lanes: a kernel width no other test uses
    cases = [(140, 20), (500, 30), (300, 40)]     # K_pad 32, 32, 64
    runs = []
    for E, K in cases:
        events, t0, t1, s, r, _ = _bucket_edge_case(E, K, rng)
        before = cache_stats()["merge_fix"]
        with spans.recording() as rec, spans.span("plan.merge_fix"):
            got = merge_fix_step(events, t0, t1, s, r, m)
        after = cache_stats()["merge_fix"]
        assert all(np.array_equal(a, b) for a, b in
                   zip(got, merge_fix_ref(events, t0, t1, s, r, m)))
        runs.append((after["calls"] - before["calls"],
                     after["misses"] - before["misses"], len(rec.compiles),
                     rec.spans[0].attrs["k_pad"]))
    assert [c for c, _, _, _ in runs] == [1, 1, 1]
    assert [b for _, b, _, _ in runs] == [1, 0, 1]
    assert runs[0][2] > 0 and runs[1][2] == 0 and runs[2][2] > 0
    assert [k for _, _, _, k in runs] == [32, 32, 64]
