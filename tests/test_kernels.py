"""Per-kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret mode executes the Pallas kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import (flash_attention, ranl_update, region_aggregate,
                           rwkv_wkv)
from repro.kernels import ref

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------
# region_aggregate / ranl_update
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d", [(1, 128), (4, 500), (16, 1024), (32, 777)])
def test_region_aggregate_matches_oracle(n, d, dtype):
    ks = jax.random.split(KEY, 3)
    g = jax.random.normal(ks[0], (n, d)).astype(dtype)
    m = jax.random.uniform(ks[1], (n, d)) < 0.5
    c = jax.random.normal(ks[2], (n, d)).astype(dtype)
    g1, c1 = region_aggregate(g, m, c, block_d=256)
    g2, c2 = ref.region_aggregate_ref(g, m, c)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(g1, np.float32),
                               np.asarray(g2, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 12), st.integers(1, 300), st.integers(0, 10_000),
       st.floats(0.0, 1.0))
def test_region_aggregate_property(n, d, seed, p):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    g = jax.random.normal(ks[0], (n, d))
    m = jax.random.uniform(ks[1], (n, d)) < p
    c = jax.random.normal(ks[2], (n, d))
    g1, c1 = region_aggregate(g, m, c)
    g2, c2 = ref.region_aggregate_ref(g, m, c)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(c1, c2)


@pytest.mark.parametrize("n,d,mu,lr", [(4, 256, 1e-3, 1.0),
                                       (8, 1000, 0.5, 0.3)])
def test_ranl_update_matches_oracle(n, d, mu, lr):
    ks = jax.random.split(KEY, 5)
    g = jax.random.normal(ks[0], (n, d))
    m = jax.random.uniform(ks[1], (n, d)) < 0.4
    c = jax.random.normal(ks[2], (n, d))
    x = jax.random.normal(ks[3], (d,))
    h = jnp.abs(jax.random.normal(ks[4], (d,)))
    x1, c1 = ranl_update(x, h, g, m, c, mu=mu, lr=lr, block_d=256)
    x2, c2 = ref.ranl_update_ref(x, h, g, m, c, mu=mu, lr=lr)
    np.testing.assert_allclose(x1, x2, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(c1, c2)


@pytest.mark.parametrize("n,d", [(1, 1), (1, 7), (3, 129), (5, 1),
                                 (2, 511), (7, 513)])
def test_region_aggregate_odd_padded_shapes(n, d):
    """Odd / sub-block / just-past-block D exercises the padding path."""
    ks = jax.random.split(KEY, 3)
    g = jax.random.normal(ks[0], (n, d))
    m = jax.random.uniform(ks[1], (n, d)) < 0.5
    c = jax.random.normal(ks[2], (n, d))
    g1, c1 = region_aggregate(g, m, c)
    g2, c2 = ref.region_aggregate_ref(g, m, c)
    np.testing.assert_allclose(g1, g2, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_region_aggregate_all_uncovered():
    """No region covered anywhere: output is the memory mean, memory kept."""
    n, d = 4, 300
    ks = jax.random.split(KEY, 2)
    g = jax.random.normal(ks[0], (n, d))
    m = jnp.zeros((n, d), bool)
    c = jax.random.normal(ks[1], (n, d))
    g1, c1 = region_aggregate(g * 0.0, m, c)
    np.testing.assert_allclose(g1, c.mean(axis=0), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c))


def test_ranl_update_single_worker():
    """N=1: covered coordinates take the worker's gradient verbatim."""
    d, mu, lr = 200, 1e-2, 0.7
    ks = jax.random.split(KEY, 4)
    g = jax.random.normal(ks[0], (1, d))
    m = jax.random.uniform(ks[1], (1, d)) < 0.5
    c = jax.random.normal(ks[2], (1, d))
    x = jax.random.normal(ks[3], (d,))
    h = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 9), (d,)))
    x1, c1 = ranl_update(x, h, g * m, m, c, mu=mu, lr=lr)
    x2, c2 = ref.ranl_update_ref(x, h, g * m, m, c, mu=mu, lr=lr)
    np.testing.assert_allclose(x1, x2, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


@pytest.mark.parametrize("n,d", [(1, 7), (3, 129), (6, 1000)])
def test_ranl_update_all_uncovered(n, d):
    """All-uncovered fused update steps along the memory mean only."""
    ks = jax.random.split(KEY, 4)
    g = jax.random.normal(ks[0], (n, d))
    m = jnp.zeros((n, d), bool)
    c = jax.random.normal(ks[1], (n, d))
    x = jax.random.normal(ks[2], (d,))
    h = jnp.abs(jax.random.normal(ks[3], (d,))) + 0.5
    x1, c1 = ranl_update(x, h, g * 0.0, m, c, mu=1e-3, lr=1.0)
    expect = x - c.mean(axis=0) / jnp.maximum(h, 1e-3)
    np.testing.assert_allclose(x1, expect, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c))


def test_kernel_consistent_with_core_aggregation():
    """Kernel == repro.core.aggregation.server_aggregate on region masks."""
    from repro.core import contiguous_regions, expand_mask, server_aggregate
    n, d, q = 6, 512, 8
    ids = contiguous_regions(d, q)
    ks = jax.random.split(KEY, 3)
    rm = jax.random.uniform(ks[0], (n, q)) < 0.5
    masks = expand_mask(rm, ids)
    g = jax.random.normal(ks[1], (n, d)) * masks
    c = jax.random.normal(ks[2], (n, d))
    g1, c1 = region_aggregate(g, masks, c)
    g2, c2 = server_aggregate(g, masks, c)
    np.testing.assert_allclose(g1, g2, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(c1, c2)
    # server_aggregate's kernel dispatch flag routes to the same kernel
    g3, c3 = server_aggregate(g, masks, c, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g3))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c3))


# --------------------------------------------------------------------------
# logistic_grads: one pass over each worker's X
# --------------------------------------------------------------------------

_LOGISTIC_CASES = {
    # name: (workers, rows, width, pruned, grad_noise, seeds); the kernel
    # tiles rows by its default 512
    "rows_a_tile_multiple": (3, 2048, 128, False, 0.0, 0),
    "ragged_rows": (3, 1500, 128, False, 0.0, 0),
    "rows_within_one_tile": (3, 300, 128, False, 0.0, 0),
    "width_not_lane_multiple": (2, 1500, 250, False, 0.0, 0),
    "width_of_the_convex_cell": (2, 600, 2000, False, 0.0, 0),
    "pruned_zero_regions": (4, 1100, 250, True, 0.0, 0),
    "grad_noise": (4, 300, 40, True, 0.3, 0),
    "vmapped_over_seeds": (3, 1100, 40, True, 0.1, 2),
}


@pytest.mark.parametrize("case", sorted(_LOGISTIC_CASES))
def test_logistic_grads_match_vmapped_worker_grad(case, monkeypatch):
    """The fused kernel (interpret mode) equals
    ``vmap(Logistic.worker_grad)`` at the pruned iterates, noise included,
    also under a leading vmap axis as the batch engine runs it."""
    from repro.core import Logistic, contiguous_regions, expand_mask
    from repro.kernels import logistic_grad
    N, n, d, pruned, noise, seeds = _LOGISTIC_CASES[case]
    ks = jax.random.split(jax.random.fold_in(KEY, 1), 5)
    X = jax.random.normal(ks[3], (N, n, d)) / np.sqrt(d) \
        + 0.5 * jax.random.normal(ks[4], (N, 1, d))      # non-IID shifts
    y = jnp.where(jax.random.uniform(ks[4], (N, n)) < 0.5, 1.0, -1.0)
    prob = Logistic(X=X, y=y, lam=0.05, grad_noise=noise, hess_noise=0.0,
                    x_star=jnp.zeros(d), mu=0.05, L_g=1.0)
    x = jax.random.normal(ks[0], (max(seeds, 1), N, d))
    if pruned:                       # whole regions of each worker zeroed
        keep = jax.random.uniform(ks[1], (max(seeds, 1), N, 5)) < 0.5
        x = jnp.where(expand_mask(keep, contiguous_regions(d, 5)), x, 0.0)
    keys = jax.random.split(ks[2], N)
    # the CPU lays X out row-major; answer as the TPU does for rows
    monkeypatch.setattr(logistic_grad, "rows_minor_layout",
                        lambda *a, **kw: True)
    assert prob.grad_path(True, True) == "fused"

    def fused(xp):
        return prob.pruned_grads(xp, keys, use_kernel=True, interpret=True)

    def oracle(xp):
        return jax.vmap(prob.worker_grad)(jnp.arange(N), xp, keys)

    got = jax.vmap(fused)(x) if seeds else fused(x[0])
    want = jax.vmap(oracle)(x) if seeds else oracle(x[0])
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * scale)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,hd,win", [
    (1, 128, 2, 2, 64, 0),       # MHA
    (2, 256, 4, 2, 64, 0),       # GQA
    (1, 256, 4, 1, 128, 0),      # MQA
    (2, 256, 4, 2, 64, 100),     # sliding window
    (1, 256, 2, 2, 32, 64),      # narrow window
])
def test_flash_attention_matches_oracle(b, s, h, kv, hd, win, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd)).astype(dtype)
    o1 = flash_attention(q, k, v, causal=True, window=win,
                         block_q=64, block_k=64)
    o2 = ref.flash_attention_ref(q, k, v, causal=True, window=win)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_matches_model_blocked_attention():
    """Kernel agrees with the model zoo's pure-jnp blocked attention."""
    from repro.models.attention import blocked_attention
    b, s, h, hd = 1, 128, 4, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, h, hd))
    v = jax.random.normal(ks[2], (b, s, h, hd))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    o_model = blocked_attention(q, k, v, pos, pos, q_chunk=64, kv_chunk=64,
                                static_positions=True)
    o_kernel = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(o_model, o_kernel, rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# rwkv wkv
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,hd,bt", [
    (1, 64, 2, 16, 32), (2, 128, 4, 64, 128), (1, 256, 1, 32, 64),
])
def test_rwkv_wkv_matches_oracle(b, s, h, hd, bt):
    r, k, v = (jax.random.normal(jax.random.fold_in(KEY, i), (b, s, h, hd))
               for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(
        jax.random.fold_in(KEY, 9), (b, s, h, hd))) * 0.5 + 0.45
    u = jax.random.normal(jax.random.fold_in(KEY, 4), (h, hd)) * 0.3
    s0 = jax.random.normal(jax.random.fold_in(KEY, 5), (b, h, hd, hd)) * 0.1
    y1, sf1 = rwkv_wkv(r, k, v, w, u, s0, block_t=bt)
    y2, sf2 = ref.rwkv_wkv_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(sf1, sf2, rtol=2e-4, atol=2e-4)


def test_rwkv_wkv_matches_model_scan():
    """Kernel agrees with the model zoo's lax.scan recurrence."""
    from repro.models.rwkv import _wkv_scan
    b, s, h, hd = 1, 64, 2, 16
    r, k, v = (jax.random.normal(jax.random.fold_in(KEY, i), (b, s, h, hd))
               for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(
        jax.random.fold_in(KEY, 7), (b, s, h, hd))) * 0.5 + 0.45
    u = jax.random.normal(jax.random.fold_in(KEY, 8), (h, hd)) * 0.3
    s0 = jnp.zeros((b, h, hd, hd))
    y_model, s_model = _wkv_scan(r, k, v, w, u, s0)
    y_kern, s_kern = rwkv_wkv(r, k, v, w, u, s0, block_t=32)
    np.testing.assert_allclose(y_model, y_kern, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s_model, s_kern, rtol=2e-4, atol=2e-4)
