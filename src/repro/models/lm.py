"""Full language models: params, forward, loss, prefill, decode.

One assembly covers all ten assigned architectures; the per-layer body
dispatches on config.family:

  dense   : x += attn(n1(x));  x += mlp(n2(x))
  moe     : x += attn(n1(x));  x += moe(n2(x))   [+ dense residual inside]
  ssm     : x += ssd(n1(x))                       (attention-free)
  hybrid  : x += (attn(n1(x)) + ssd(n1(x)))/2;  x += mlp(n2(x))  (hymba)

Layers are scanned (stacked params) so HLO size is depth-independent —
required to compile 80-layer models against 512 devices in a dry run.

Frontends (assignment: STUBS taking precomputed embeddings):
  vision (internvl2): patch embeddings (B, P, vit_dim) -> MLP projector ->
    prepended to the text sequence; labels on text only.
  audio (musicgen): EnCodec token streams (B, S, n_codebooks) -> summed
    embeddings; per-codebook logit heads.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.sharding import constrain
from .config import ModelConfig
from .layers import (attention_apply, attention_decode,
                     attention_decode_paged, build_attention, build_mlp,
                     build_moe, build_rmsnorm, build_ssd, init_kv_cache,
                     init_ssd_cache, mlp_apply, moe_apply, rmsnorm,
                     ssd_apply, ssd_decode, ssd_decode_chunk)
from .modules import Builder, Mode, normal_init

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Parameter tree
# ---------------------------------------------------------------------------


def build_layer(b: Builder, cfg: ModelConfig) -> Params:
    p: Params = {"norm1": build_rmsnorm(b, "norm1", cfg.d_model)}
    if cfg.family == "ssm":
        p["ssd"] = build_ssd(b, cfg)
        return p
    p["attn"] = build_attention(b, cfg)
    if cfg.hybrid:
        p["ssd"] = build_ssd(b, cfg)
    p["norm2"] = build_rmsnorm(b, "norm2", cfg.d_model)
    if cfg.num_experts > 0:
        p["moe"] = build_moe(b, cfg)
    else:
        p["mlp"] = build_mlp(b, cfg)
    return p


def build_params(b: Builder, cfg: ModelConfig) -> Params:
    p: Params = {}
    with b.scope("model"):
        if cfg.frontend == "audio":
            p["embed"] = b.param("embed", (cfg.num_codebooks, cfg.vocab_size,
                                           cfg.d_model),
                                 ("codebooks", "vocab_tp", "embed"),
                                 normal_init(0.02))
            p["head"] = b.param("head", (cfg.num_codebooks, cfg.d_model,
                                         cfg.vocab_size),
                                ("codebooks", "embed", "vocab_tp"),
                                normal_init(0.02))
        else:
            p["embed"] = b.param("embed", (cfg.vocab_size, cfg.d_model),
                                 ("vocab_tp", "embed"), normal_init(0.02))
            if not cfg.tie_embeddings:
                p["head"] = b.param("head", (cfg.d_model, cfg.vocab_size),
                                    ("embed", "vocab_tp"), normal_init(0.02))
        if cfg.frontend == "vision":
            with b.scope("projector"):
                p["proj_in"] = b.param("in", (cfg.vit_dim, cfg.d_model),
                                       ("vit", "embed"), normal_init(0.02))
                p["proj_hidden"] = b.param("hidden", (cfg.d_model, cfg.d_model),
                                           ("embed", "act_embed"), normal_init(0.02))
        with b.scope("layers"), b.stacked(cfg.num_layers):
            p["layers"] = build_layer(b, cfg)
        p["final_norm"] = build_rmsnorm(b, "final_norm", cfg.d_model)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    b = Builder(Mode.INIT, key, cfg.param_jnp_dtype())
    return build_params(b, cfg)


def abstract_params(cfg: ModelConfig) -> Params:
    b = Builder(Mode.SHAPE, param_dtype=cfg.param_jnp_dtype())
    return build_params(b, cfg)


def param_specs(cfg: ModelConfig) -> Params:
    b = Builder(Mode.SPEC, param_dtype=cfg.param_jnp_dtype())
    return build_params(b, cfg)


# ---------------------------------------------------------------------------
# Layer body (shared by train forward / prefill)
# ---------------------------------------------------------------------------


def layer_apply(cfg: ModelConfig, lp: Params, x: jax.Array,
                positions: jax.Array, attention_impl: str = "auto"
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    aux: Dict[str, jax.Array] = {}
    h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
    if cfg.family == "ssm":
        return x + ssd_apply(cfg, lp["ssd"], h), aux
    att = attention_apply(cfg, lp["attn"], h, positions, attention_impl)
    if cfg.hybrid:
        att = 0.5 * (att + ssd_apply(cfg, lp["ssd"], h))
    x = x + att
    h2 = rmsnorm(lp["norm2"], x, cfg.norm_eps)
    if cfg.num_experts > 0:
        y, moe_aux = moe_apply(cfg, lp["moe"], h2)
        aux.update(moe_aux)
    else:
        y = mlp_apply(cfg, lp["mlp"], h2)
    return x + y, aux


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(cfg: ModelConfig, p: Params, batch: Dict[str, jax.Array]
                 ) -> Tuple[jax.Array, jax.Array]:
    """Returns (x (B,S,D), positions (S,))."""
    cdt = cfg.compute_jnp_dtype()
    if cfg.frontend == "audio":
        codes = batch["tokens"]                                  # (B,S,ncb)
        x = jnp.zeros(codes.shape[:2] + (cfg.d_model,), cdt)
        for c in range(cfg.num_codebooks):
            x = x + jnp.take(p["embed"][c], codes[..., c], axis=0).astype(cdt)
    else:
        x = jnp.take(p["embed"], batch["tokens"], axis=0).astype(cdt)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(cdt)                   # (B,P,vit)
        img = jnp.einsum("bpv,vd->bpd", pe, p["proj_in"].astype(cdt))
        img = jax.nn.gelu(img)
        img = jnp.einsum("bpd,de->bpe", img, p["proj_hidden"].astype(cdt))
        x = jnp.concatenate([img, x], axis=1)
    S = x.shape[1]
    x = constrain(x, "batch", "seq", "act_embed")
    return x, jnp.arange(S, dtype=jnp.int32)


def lm_head(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    cdt = cfg.compute_jnp_dtype()
    if cfg.frontend == "audio":
        logits = jnp.einsum("bsd,cdv->bscv", x, p["head"].astype(cdt))
        return constrain(logits, "batch", "seq", None, "act_vocab")
    w = p["embed"].T if cfg.tie_embeddings else p["head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(cdt))
    return constrain(logits, "batch", "seq", "act_vocab")


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            attention_impl: str = "auto", remat: str = "full",
            unroll: int = 1) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x, positions = embed_tokens(cfg, params, batch)

    def body(carry, lp):
        h, aux_acc = carry
        h, aux = layer_apply(cfg, lp, h, positions, attention_impl)
        for k_, v in aux.items():
            aux_acc = {**aux_acc, k_: aux_acc.get(k_, 0.0) + v}
        return (h, aux_acc), None

    aux0: Dict[str, jax.Array] = {}
    if cfg.num_experts > 0:
        aux0 = {"load_balance": jnp.zeros((), jnp.float32),
                "router_z": jnp.zeros((), jnp.float32)}
    if remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            prevent_cse=False)
    (x, aux), _ = lax.scan(body, (x, aux0), params["layers"],
                           unroll=min(unroll, cfg.num_layers))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lm_head(cfg, params, x), aux


def cross_entropy(cfg: ModelConfig, logits: jax.Array, labels: jax.Array,
                  weights: Optional[jax.Array] = None) -> jax.Array:
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if weights is None:
        return nll.mean()
    w = weights.astype(jnp.float32)
    return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)


def train_loss(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
               attention_impl: str = "auto", remat: str = "full",
               unroll: int = 1) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = forward(cfg, params, batch, attention_impl, remat, unroll)
    labels = batch["labels"]
    weights = batch.get("weights")
    if cfg.frontend == "vision":
        # logits cover [img_tokens, text]; labels are text-only
        P_img = logits.shape[1] - labels.shape[1]
        logits = logits[:, P_img:]
    if cfg.frontend == "audio":
        loss = cross_entropy(
            cfg, logits.reshape(logits.shape[0], -1, logits.shape[-1]),
            labels.reshape(labels.shape[0], -1),
            None if weights is None else jnp.repeat(weights, cfg.num_codebooks, -1))
    else:
        loss = cross_entropy(cfg, logits, labels, weights)
    metrics = {"ce_loss": loss}
    for k_, v in aux.items():
        loss = loss + v  # aux coefficients already applied per layer
        metrics[k_] = v
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Dense per-slot decode cache. ``pos`` is a per-slot clock (B,):
    every slot decodes at its own position, so a serving engine can
    admit/recycle slots independently (scalar clocks are still accepted
    by :func:`decode_step` for old callers/checkpoints)."""
    cache: Dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32)}
    L = cfg.num_layers
    if cfg.family != "ssm":
        kv = init_kv_cache(cfg, batch, max_len)
        cache["kv"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (L,) + a.shape), kv)
    if cfg.family in ("ssm", "hybrid"):
        sc = init_ssd_cache(cfg, batch)
        cache["ssd"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (L,) + a.shape), sc)
    return cache


def init_paged_cache(cfg: ModelConfig, slots: int, num_blocks: int,
                     block_size: int) -> Dict[str, Any]:
    """Paged decode cache: a physical KV block pool + per-slot SSD state.

    kv k/v are (L, num_blocks, block_size, K, hd) — one pool shared by
    all slots; block 0 is the reserved always-zero sentinel that empty
    block-table entries point at. Position clocks and block tables are
    NOT part of this pytree: the serve-side
    :class:`repro.serve.kvcache.KVCacheManager` owns them host-side and
    passes them into :func:`decode_chunk` per tick.
    """
    cache: Dict[str, Any] = {}
    L = cfg.num_layers
    if cfg.family != "ssm":
        hd = cfg.resolved_head_dim
        shape = (L, num_blocks, block_size, cfg.num_kv_heads, hd)
        dt = cfg.compute_jnp_dtype()
        cache["kv"] = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if cfg.family in ("ssm", "hybrid"):
        sc = init_ssd_cache(cfg, slots)
        cache["ssd"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (L,) + a.shape), sc)
    return cache


def decode_chunk(cfg: ModelConfig, params: Params, tokens: jax.Array,
                 cache: Dict[str, Any], block_table: jax.Array,
                 pos: jax.Array, adv: jax.Array,
                 zero_blocks: Optional[jax.Array] = None,
                 reset_slots: Optional[jax.Array] = None,
                 unroll: int = 1) -> Tuple[jax.Array, Dict[str, Any]]:
    """Continuous-batching step: C tokens per slot against the paged cache.

    tokens: (B,C) [audio: (B,C,ncb)]; block_table: (B,nb); pos: (B,)
    per-slot clocks; adv: (B,) real tokens this chunk (0 = idle slot).
    One call serves mixed phases — a slot prefilling a C-token prompt
    chunk next to a slot decoding one token (adv=1, C-1 padded rows).

    ``zero_blocks`` (fixed-size int array, padded with NB) zero-epochs
    recycled physical blocks inside this donated call — no request can
    ever attend to a predecessor's K/V even if masking were wrong;
    ``reset_slots`` (B,) bool resets recycled slots' SSD recurrence the
    same way (state is cumulative: masking alone cannot protect it).
    Returns (logits (B,C,V...) , new cache); pos/block accounting stays
    with the host-side manager.

    The ops carry ``jax.named_scope`` names in their ``op_name``
    metadata (device profiles group by them): ``cache`` (the
    zero-epoch and each layer's slice and update of the stacked pool
    and state), ``attention``, ``ssd``, ``mlp`` and ``head`` (embedding,
    final norm, LM head). A layer's norm goes with the block it feeds.
    """
    scope = jax.named_scope
    L = cfg.num_layers
    with scope("cache"):
        if zero_blocks is not None and "kv" in cache:
            cache = dict(cache)
            cache["kv"] = {
                "k": cache["kv"]["k"].at[:, zero_blocks].set(0.0, mode="drop"),
                "v": cache["kv"]["v"].at[:, zero_blocks].set(0.0, mode="drop"),
            }
        if reset_slots is not None and "ssd" in cache:
            cache = dict(cache)
            cache["ssd"] = jax.tree.map(
                lambda a: jnp.where(
                    reset_slots.reshape((1, -1) + (1,) * (a.ndim - 2)),
                    jnp.zeros((), a.dtype), a),
                cache["ssd"])

    with scope("head"):
        x, _ = embed_tokens(cfg, params, {"tokens": tokens})

    def get_layer(tree, li):
        with scope("cache"):
            return jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
                tree)

    def set_layer(tree, sub, li):
        with scope("cache"):
            return jax.tree.map(
                lambda a, s: lax.dynamic_update_index_in_dim(
                    a, s.astype(a.dtype), li, 0),
                tree, sub)

    def body(carry, scan_in):
        h, kv_all, ssd_all = carry
        lp, li = scan_in
        if cfg.family == "ssm":
            ssd_l = get_layer(ssd_all, li)
            with scope("ssd"):
                hn = rmsnorm(lp["norm1"], h, cfg.norm_eps)
                y, new_ssd = ssd_decode_chunk(cfg, lp["ssd"], hn, ssd_l, adv)
            ssd_all = set_layer(ssd_all, new_ssd, li)
            return (h + y, kv_all, ssd_all), None
        kv_l = get_layer(kv_all, li)
        with scope("attention"):
            hn = rmsnorm(lp["norm1"], h, cfg.norm_eps)
            att, new_kv = attention_decode_paged(cfg, lp["attn"], hn, kv_l,
                                                 block_table, pos, adv)
        kv_all = set_layer(kv_all, new_kv, li)
        if cfg.hybrid:
            ssd_l = get_layer(ssd_all, li)
            with scope("ssd"):
                y2, new_ssd = ssd_decode_chunk(cfg, lp["ssd"], hn, ssd_l, adv)
            ssd_all = set_layer(ssd_all, new_ssd, li)
            att = 0.5 * (att + y2)
        h = h + att
        with scope("mlp"):
            h2 = rmsnorm(lp["norm2"], h, cfg.norm_eps)
            if cfg.num_experts > 0:
                y, _ = moe_apply(cfg, lp["moe"], h2)
            else:
                y = mlp_apply(cfg, lp["mlp"], h2)
        return (h + y, kv_all, ssd_all), None

    kv0 = cache.get("kv", jnp.zeros((L, 1)))
    ssd0 = cache.get("ssd", jnp.zeros((L, 1)))
    (x, new_kv, new_ssd), _ = lax.scan(
        body, (x, kv0, ssd0),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)),
        unroll=min(unroll, cfg.num_layers))
    with scope("head"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = lm_head(cfg, params, x)
    new_cache = dict(cache)
    if "kv" in cache:
        new_cache["kv"] = new_kv
    if "ssd" in cache:
        new_cache["ssd"] = new_ssd
    return logits, new_cache


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: Dict[str, Any], unroll: int = 1
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One AR step for the whole stack. tokens: (B,1) or (B,1,ncb).

    The FULL stacked caches ride the scan *carry* (layer l is sliced /
    written back inside iteration l): carry threading lets XLA alias the
    donated input cache buffer end-to-end — one cache copy resident
    instead of three (xs + ys + temp), which is what makes 32k x 128-seq
    caches servable.
    """
    x, _ = embed_tokens(cfg, params, {"tokens": tokens})
    pos = cache["pos"]
    L = cfg.num_layers

    def get_layer(tree, li):
        return jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, li, 0,
                                                               keepdims=False),
                            tree)

    def set_layer(tree, sub, li):
        return jax.tree.map(
            lambda a, s: lax.dynamic_update_index_in_dim(a, s.astype(a.dtype),
                                                         li, 0),
            tree, sub)

    def body(carry, scan_in):
        h, kv_all, ssd_all = carry
        lp, li = scan_in
        hn = rmsnorm(lp["norm1"], h, cfg.norm_eps)
        if cfg.family == "ssm":
            y, new_ssd = ssd_decode(cfg, lp["ssd"], hn, get_layer(ssd_all, li))
            ssd_all = set_layer(ssd_all, new_ssd, li)
            return (h + y, kv_all, ssd_all), None
        att, new_kv = attention_decode(cfg, lp["attn"], hn,
                                       get_layer(kv_all, li), pos)
        kv_all = set_layer(kv_all, new_kv, li)
        if cfg.hybrid:
            y2, new_ssd = ssd_decode(cfg, lp["ssd"], hn, get_layer(ssd_all, li))
            ssd_all = set_layer(ssd_all, new_ssd, li)
            att = 0.5 * (att + y2)
        h = h + att
        h2 = rmsnorm(lp["norm2"], h, cfg.norm_eps)
        if cfg.num_experts > 0:
            y, _ = moe_apply(cfg, lp["moe"], h2)
        else:
            y = mlp_apply(cfg, lp["mlp"], h2)
        return (h + y, kv_all, ssd_all), None

    kv0 = cache.get("kv", jnp.zeros((L, 1)))
    ssd0 = cache.get("ssd", jnp.zeros((L, 1)))
    (x, new_kv, new_ssd), _ = lax.scan(
        body, (x, kv0, ssd0),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)),
        unroll=min(unroll, cfg.num_layers))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(cfg, params, x)
    new_cache = dict(cache)
    if "kv" in cache:
        new_cache["kv"] = new_kv
    if "ssd" in cache:
        new_cache["ssd"] = new_ssd
    new_cache["pos"] = pos + 1
    return logits, new_cache


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            attention_impl: str = "auto", max_len: Optional[int] = None,
            unroll: int = 1) -> Tuple[jax.Array, Dict[str, Any]]:
    """Process a full prompt, return last-position logits + primed cache.

    Cache priming recomputes K/V per layer (scan emits them); SSD state
    priming runs the chunked scan and keeps the final state. ``max_len``
    sizes the KV cache (must exceed S by the planned generation length for
    full-attention archs; SWA archs allocate the window regardless).
    """
    x, positions = embed_tokens(cfg, params, batch)
    B, S, _ = x.shape
    max_len = max_len or S

    def body(h, lp):
        hn = rmsnorm(lp["norm1"], h, cfg.norm_eps)
        emitted = {}
        if cfg.family != "ssm":
            from .layers import _qkv
            _, k_, v_ = _qkv(cfg, lp["attn"], hn, positions[None, :])
            if cfg.sliding_window > 0 and S > cfg.sliding_window:
                k_ = k_[:, -cfg.sliding_window:]
                v_ = v_[:, -cfg.sliding_window:]
            emitted["k"] = k_
            emitted["v"] = v_
        if cfg.family == "ssm" or cfg.hybrid:
            _, st = ssd_apply(cfg, lp["ssd"], hn, return_state=True)
            emitted["ssd"] = st
        h, _ = layer_apply(cfg, lp, h, positions, attention_impl)
        return h, emitted

    x, emitted = lax.scan(body, x, params["layers"],
                          unroll=min(unroll, cfg.num_layers))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(cfg, params, x[:, -1:])

    cache = init_cache(cfg, B, max(max_len, 1))
    if "kv" in cache:
        Scache = cache["kv"]["k"].shape[2]
        k_e = emitted["k"][:, :, -Scache:]
        v_e = emitted["v"][:, :, -Scache:]
        n = k_e.shape[2]
        if cfg.sliding_window > 0:
            # ring-buffer alignment: position p lives at slot p % Scache.
            # entries cover positions [S-n, S): roll so index 0 -> slot
            # (S-n) % Scache.
            shift = (S - n) % Scache
            k_e = jnp.roll(k_e, shift, axis=2)
            v_e = jnp.roll(v_e, shift, axis=2)
        cache["kv"] = {
            "k": lax.dynamic_update_slice(
                cache["kv"]["k"], k_e.astype(cache["kv"]["k"].dtype),
                (0, 0, 0, 0, 0)),
            "v": lax.dynamic_update_slice(
                cache["kv"]["v"], v_e.astype(cache["kv"]["v"].dtype),
                (0, 0, 0, 0, 0)),
        }
    if "ssd" in cache:
        cache["ssd"] = jax.tree.map(lambda c, e: e.astype(c.dtype),
                                    cache["ssd"], emitted["ssd"])
    cache["pos"] = jnp.full((B,), S, jnp.int32)
    return logits, cache
