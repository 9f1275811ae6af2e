"""Serving-path correctness: decode == forward, prefill priming, SWA ring."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import smoke_config
from repro.models import lm

FAMS = ["yi-34b", "h2o-danube-1.8b", "mamba2-780m", "hymba-1.5b",
        "musicgen-medium", "arctic-480b", "internvl2-1b",
        "granite-4.0-h-micro"]


def f32(name):
    return smoke_config(name).replace(compute_dtype="float32",
                                      param_dtype="float32")


def tokens_for(cfg, key, B, S):
    shape = (B, S, cfg.num_codebooks) if cfg.frontend == "audio" else (B, S)
    return jax.random.randint(key, shape, 0, cfg.vocab_size)


@pytest.mark.parametrize("arch", FAMS)
def test_decode_matches_forward(arch):
    cfg = f32(arch)
    key = jax.random.PRNGKey(1)
    params = lm.init_params(cfg, key)
    B, S = 2, 20
    toks = tokens_for(cfg, key, B, S)
    batch = {"tokens": toks}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jax.random.normal(
            key, (B, cfg.num_patches, cfg.vit_dim), jnp.float32)
        full, _ = lm.forward(cfg, params, batch, remat="none")
        return  # token-by-token vlm decode needs image prefill; covered below
    full, _ = lm.forward(cfg, params, batch, remat="none")
    cache = lm.init_cache(cfg, B, S)
    step = jax.jit(lambda p, t, c: lm.decode_step(cfg, p, t, c))
    outs = []
    for t in range(S):
        lg, cache = step(params, toks[:, t:t + 1], cache)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    scale = float(jnp.max(jnp.abs(full)))
    assert float(jnp.max(jnp.abs(full - dec))) / scale < 2e-3


@pytest.mark.parametrize("arch", FAMS)
def test_prefill_then_decode_matches_forward(arch):
    cfg = f32(arch)
    key = jax.random.PRNGKey(2)
    params = lm.init_params(cfg, key)
    B, S = 2, 24
    toks = tokens_for(cfg, key, B, S)
    batch = {"tokens": toks}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jax.random.normal(
            key, (B, cfg.num_patches, cfg.vit_dim), jnp.float32)
    lg_pre, cache = lm.prefill(cfg, params, batch,
                               max_len=S + cfg.num_patches + 8)
    nxt = tokens_for(cfg, jax.random.PRNGKey(9), B, 1)
    lg_dec, cache = lm.decode_step(cfg, params, nxt, cache)
    batch2 = dict(batch)
    batch2["tokens"] = jnp.concatenate([toks, nxt], axis=1)
    full2, _ = lm.forward(cfg, params, batch2, remat="none")
    scale = float(jnp.max(jnp.abs(full2)))
    assert float(jnp.max(jnp.abs(full2[:, -1] - lg_dec[:, 0]))) / scale < 2e-3


def test_swa_ring_buffer_wraps():
    """Decode far past the window: cache stays window-sized and correct."""
    cfg = f32("h2o-danube-1.8b")  # smoke window = 16
    key = jax.random.PRNGKey(3)
    params = lm.init_params(cfg, key)
    B, S = 1, 40  # > 2x window
    toks = tokens_for(cfg, key, B, S)
    full, _ = lm.forward(cfg, params, {"tokens": toks}, remat="none")
    cache = lm.init_cache(cfg, B, S)
    assert cache["kv"]["k"].shape[2] == cfg.sliding_window  # ring size
    step = jax.jit(lambda p, t, c: lm.decode_step(cfg, p, t, c))
    for t in range(S):
        lg, cache = step(params, toks[:, t:t + 1], cache)
    scale = float(jnp.max(jnp.abs(full)))
    assert float(jnp.max(jnp.abs(full[:, -1] - lg[:, 0]))) / scale < 2e-3


def test_blockwise_attention_matches_dense():
    import repro.models.layers as L
    cfg = f32("yi-34b")
    key = jax.random.PRNGKey(0)
    B, S, H, K, hd = 2, 50, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, K, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, K, hd))
    pos = jnp.arange(S)
    old = (L.Q_BLOCK, L.KV_BLOCK)
    try:
        L.Q_BLOCK, L.KV_BLOCK = 16, 16
        dense = L._attend_dense(cfg, q, k, v, pos, pos)
        block = L._attend_blockwise(cfg, q, k, v, pos, pos)
    finally:
        L.Q_BLOCK, L.KV_BLOCK = old
    assert float(jnp.max(jnp.abs(dense - block))) < 1e-4


def test_ssd_prefill_state_matches_stepwise():
    """ssd_apply(return_state) == state after S sequential decodes."""
    from repro.models import layers as L
    from repro.models.modules import Builder, Mode
    cfg = f32("mamba2-780m").replace(ssm_chunk=8)
    b = Builder(Mode.INIT, jax.random.PRNGKey(0), jnp.float32)
    p = L.build_ssd(b, cfg)
    B, S = 2, 20
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    _, st = L.ssd_apply(cfg, p, x, return_state=True)
    cache = L.init_ssd_cache(cfg, B)
    for t in range(S):
        _, cache = L.ssd_decode(cfg, p, x[:, t:t + 1], cache)
    assert float(jnp.max(jnp.abs(st["state"] - cache["state"]))) < 1e-3
    assert float(jnp.max(jnp.abs(
        st["conv"].astype(jnp.float32)
        - cache["conv"].astype(jnp.float32)))) < 1e-4
