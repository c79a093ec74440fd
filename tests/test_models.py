"""Per-architecture smoke tests (reduced configs) + decode consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config, smoke_variant
from repro.data import make_batch
from repro.models import forward, init_decode_cache, init_model, lm_loss

KEY = jax.random.PRNGKey(0)


def _smoke(arch):
    return smoke_variant(get_config(arch))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_smoke_forward_and_train_step(arch):
    """Required per-arch smoke: reduced variant (2 layers, d_model<=512,
    <=4 experts), one forward + one train step, shape + finite checks."""
    cfg = _smoke(arch)
    assert cfg.num_layers == 2 and cfg.d_model <= 512
    assert cfg.num_experts <= 4
    params = init_model(cfg, KEY)
    batch = make_batch(cfg, KEY, batch=2, seq=32, kind="train")

    logits, _, aux = forward(params, batch, cfg, q_chunk=16, kv_chunk=16)
    exp = ((2, 32, cfg.num_codebooks, cfg.vocab_size)
           if cfg.modality == "audio" else (2, 32, cfg.vocab_size))
    assert logits.shape == exp
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(p, batch, cfg, q_chunk=16, kv_chunk=16))(params)
    assert bool(jnp.isfinite(loss))
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", [a for a in ALL_ARCHS
                                  if not get_config(a).uses_mla])
def test_arch_decode_step(arch):
    cfg = _smoke(arch)
    params = init_model(cfg, KEY)
    cache = init_decode_cache(cfg, 2, 16, jnp.float32)
    tok = (jnp.zeros((2, 1, cfg.num_codebooks), jnp.int32)
           if cfg.modality == "audio" else jnp.zeros((2, 1), jnp.int32))
    logits, new_cache, _ = forward(params, {"tokens": tok, "pos": jnp.int32(0)},
                                   cfg, mode="decode", cache=cache,
                                   kv_chunk=16)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert (jax.tree_util.tree_structure(new_cache)
            == jax.tree_util.tree_structure(cache))


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "rwkv6-3b",
                                  "hymba-1.5b", "musicgen-medium",
                                  "phi3.5-moe-42b-a6.6b"])
def test_prefill_decode_matches_full_forward(arch):
    """Teacher-forced prefill+decode must reproduce the train-mode logits
    (the serving path is a faithful incremental evaluation)."""
    cfg = _smoke(arch)
    params = init_model(cfg, KEY)
    T, Tp = 12, 8
    batch = make_batch(cfg, KEY, batch=2, seq=T, kind="train")
    toks = batch["tokens"]

    full_logits, _, _ = forward(params, {"tokens": toks}, cfg,
                                mode="train", q_chunk=16, kv_chunk=16,
                                remat=False)

    from repro.launch.serve import pad_cache
    prefix = {"tokens": toks[:, :Tp]}
    pre_logits, cache, _ = forward(params, prefix, cfg, mode="prefill",
                                   q_chunk=16, kv_chunk=16)
    if not cfg.attn_free:
        cache = pad_cache(cache, T)
    np.testing.assert_allclose(
        np.asarray(pre_logits[:, -1], np.float32),
        np.asarray(full_logits[:, Tp - 1], np.float32),
        rtol=2e-3, atol=2e-3)

    for t in range(Tp, T):
        step_batch = {"tokens": toks[:, t:t + 1], "pos": jnp.int32(t)}
        logits, cache, _ = forward(params, step_batch, cfg, mode="decode",
                                   cache=cache, kv_chunk=16)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(full_logits[:, t], np.float32),
            rtol=3e-3, atol=3e-3)


def test_sliding_window_decode_matches_windowed_forward():
    cfg = dataclasses.replace(_smoke("mistral-nemo-12b"), sliding_window=8)
    params = init_model(cfg, KEY)
    T, W = 16, 8
    toks = make_batch(cfg, KEY, batch=1, seq=T)["tokens"]
    full_logits, _, _ = forward(params, {"tokens": toks}, cfg, mode="train",
                                q_chunk=16, kv_chunk=16, remat=False,
                                window=W)
    cache = init_decode_cache(cfg, 1, W, jnp.float32)
    for t in range(T):
        logits, cache, _ = forward(
            params, {"tokens": toks[:, t:t + 1], "pos": jnp.int32(t)},
            cfg, mode="decode", cache=cache, window=W, kv_chunk=16)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(full_logits[:, t], np.float32),
            rtol=3e-3, atol=3e-3)


def test_moe_dispatch_matches_dense_oracle():
    """Sorted dropless dispatch == explicit per-token expert compute."""
    from repro.models.moe import apply_moe, init_moe
    cfg = _smoke("phi3.5-moe-42b-a6.6b")
    p = init_moe(cfg, KEY)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 16, cfg.d_model))
    out, aux, stats = apply_moe(p, x, cfg)
    assert int(stats["dropped_rows"]) == 0

    xf = x.reshape(-1, cfg.d_model)
    logits = xf @ p["router"]
    top_w, top_e = jax.lax.top_k(logits, cfg.experts_per_token)
    top_w = jax.nn.softmax(top_w, axis=-1)
    want = jnp.zeros_like(xf)
    for t in range(xf.shape[0]):
        acc = jnp.zeros((cfg.d_model,))
        for j in range(cfg.experts_per_token):
            e = int(top_e[t, j])
            h = jax.nn.silu(xf[t] @ p["gate"][e]) * (xf[t] @ p["up"][e])
            acc = acc + top_w[t, j] * (h @ p["down"][e])
        want = want.at[t].set(acc)
    np.testing.assert_allclose(out.reshape(-1, cfg.d_model), want,
                               rtol=2e-3, atol=2e-3)
    assert float(aux) > 0


def test_vlm_patch_fusion_changes_prefix_only():
    cfg = _smoke("llava-next-mistral-7b")
    params = init_model(cfg, KEY)
    batch = make_batch(cfg, KEY, batch=1, seq=16)
    l1, _, _ = forward(params, batch, cfg, q_chunk=16, kv_chunk=16,
                       remat=False)
    batch2 = dict(batch)
    batch2["patch_embeds"] = batch["patch_embeds"] * 2.0
    l2, _, _ = forward(params, batch2, cfg, q_chunk=16, kv_chunk=16,
                       remat=False)
    assert not np.allclose(np.asarray(l1, np.float32),
                           np.asarray(l2, np.float32))


def test_param_count_analytic_close_to_actual():
    for arch in ("phi4-mini-3.8b", "rwkv6-3b", "hymba-1.5b"):
        cfg = _smoke(arch)
        params = init_model(cfg, KEY)
        actual = sum(x.size for x in jax.tree.leaves(params))
        assert abs(actual - cfg.param_count()) / actual < 0.15
