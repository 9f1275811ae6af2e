"""Model layers, pure JAX. One param-builder + one apply per layer kind.

Attention has two execution paths with identical math:
  * einsum path (S <= BLOCKWISE_THRESHOLD): materializes (Sq, Sk) scores;
  * blockwise path: lax.map over query blocks x lax.scan over KV blocks
    with online softmax — O(block^2) memory, used for 32k prefill. The
    Pallas flash kernel (kernels/flash_attention) implements the same
    algorithm for real TPUs; `attention_impl="kernel"` selects it.

The MoE uses index-based dispatch (scatter into (E, C, dm) expert
buffers) rather than GShard one-hot einsums: memory O(E*C*dm) instead of
O(T*E*C), which is what makes arctic-480b's 1M-token batches lowerable.

Mamba-2 runs the chunked SSD algorithm (matmul-rich form) with a
lax.scan only over chunk boundaries.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.sharding import constrain
from .config import ModelConfig
from .modules import (Builder, he_normal, lecun_normal, normal_init, ones_init,
                      zeros_init)

BLOCKWISE_THRESHOLD = 8192
Q_BLOCK = 1024
KV_BLOCK = 1024

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def build_rmsnorm(b: Builder, name: str, dim: int) -> Params:
    with b.scope(name):
        return {"scale": b.param("scale", (dim,), ("norm",), ones_init)}


def rmsnorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_table(positions: jax.Array, head_dim: int, theta: float
               ) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for given absolute positions: (..., head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, ..., head_dim); cos/sin: (B?, S, head_dim//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    while cos.ndim < x.ndim:
        cos = cos[..., None, :]
        sin = sin[..., None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, causal, optional sliding window; RoPE or none)
# ---------------------------------------------------------------------------


def build_attention(b: Builder, cfg: ModelConfig) -> Params:
    hd = cfg.resolved_head_dim
    with b.scope("attn"):
        p = {
            "wq": b.param("wq", (cfg.d_model, cfg.num_heads, hd),
                          ("embed", "heads_tp", None), he_normal, fan_in=cfg.d_model),
            "wk": b.param("wk", (cfg.d_model, cfg.num_kv_heads, hd),
                          ("embed", "kv_tp", None), he_normal, fan_in=cfg.d_model),
            "wv": b.param("wv", (cfg.d_model, cfg.num_kv_heads, hd),
                          ("embed", "kv_tp", None), he_normal, fan_in=cfg.d_model),
            "wo": b.param("wo", (cfg.num_heads, hd, cfg.d_model),
                          ("heads_tp", None, "embed"), he_normal,
                          fan_in=cfg.num_heads * hd),
        }
        if cfg.qkv_bias:
            p["bq"] = b.param("bq", (cfg.num_heads, hd), ("heads_tp", None), zeros_init)
            p["bk"] = b.param("bk", (cfg.num_kv_heads, hd), ("kv_tp", None), zeros_init)
            p["bv"] = b.param("bv", (cfg.num_kv_heads, hd), ("kv_tp", None), zeros_init)
        return p


def _qkv(cfg: ModelConfig, p: Params, x: jax.Array,
         positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    cdt = cfg.compute_jnp_dtype()
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    if cfg.position_embedding == "rope":
        cos, sin = rope_table(positions, cfg.resolved_head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = constrain(q, "batch", "seq", "act_heads", None)
    k = constrain(k, "batch", "seq", "act_kv", None)
    v = constrain(v, "batch", "seq", "act_kv", None)
    return q, k, v


def _mask(q_pos: jax.Array, k_pos: jax.Array, window: int) -> jax.Array:
    """(.., Sq, Sk) bool mask: causal, optionally sliding-window."""
    m = k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


def _attend_dense(cfg: ModelConfig, q, k, v, q_pos, k_pos) -> jax.Array:
    """q: (B,Sq,H,hd) k,v: (B,Sk,K,hd) -> (B,Sq,H,hd). f32 softmax.

    Sequence-parallel layout: scores are sharded over the q-seq dim (the
    "model" mesh axis under BASE_RULES); K/V are gathered by XLA at the
    contraction. This keeps the score tensor O(S^2 / model) per device.
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    qg = constrain(qg, "batch", "seq", "act_kv", None, None)
    scale = cfg.attention_scale
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32) * scale
    scores = constrain(scores, "batch", "act_kv", None, "seq", None)
    mask = _mask(q_pos, k_pos, cfg.sliding_window)
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    w = constrain(w, "batch", "act_kv", None, "seq", None)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Sq, H, hd)


def _attend_blockwise(cfg: ModelConfig, q, k, v, q_pos, k_pos) -> jax.Array:
    """Online-softmax attention, O(Q_BLOCK*KV_BLOCK) score memory."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    # blockwise path iterates seq blocks serially: keep seq replicated so
    # per-block dynamic slices stay local (batch/head sharding only)
    q = constrain(q, "batch", None, "act_heads", None)
    k = constrain(k, "batch", None, "act_kv", None)
    v = constrain(v, "batch", None, "act_kv", None)
    scale = cfg.attention_scale
    nq = -(-S // Q_BLOCK)
    nk = -(-S // KV_BLOCK)
    pad_q = nq * Q_BLOCK - S
    pad_k = nk * KV_BLOCK - S
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    qpos = jnp.pad(q_pos, (0, pad_q), constant_values=-1)       # padded q: masked out
    kpos = jnp.pad(k_pos, (0, pad_k), constant_values=2**30)    # padded k: future
    qb = qp.reshape(B, nq, Q_BLOCK, K, G, hd).transpose(1, 0, 3, 4, 2, 5)
    kb = kp.reshape(B, nk, KV_BLOCK, K, hd).transpose(1, 0, 3, 2, 4)
    vb = vp.reshape(B, nk, KV_BLOCK, K, hd).transpose(1, 0, 3, 2, 4)
    qposb = qpos.reshape(nq, Q_BLOCK)
    kposb = kpos.reshape(nk, KV_BLOCK)

    def per_qblock(args):
        qi, qpos_i = args  # (B,K,G,Q,hd), (Q,)

        def step(carry, inp):
            acc, m, l = carry
            kj, vj, kpos_j = inp
            s = jnp.einsum("bkgqh,bksh->bkgqs", qi, kj).astype(jnp.float32) * scale
            msk = _mask(qpos_i, kpos_j, cfg.sliding_window)
            s = jnp.where(msk[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bksh->bkgqh", p.astype(qi.dtype), vj).astype(jnp.float32)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, K, G, Q_BLOCK, hd), jnp.float32)
        m0 = jnp.full((B, K, G, Q_BLOCK), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, K, G, Q_BLOCK), jnp.float32)
        (acc, m, l), _ = lax.scan(step, (acc0, m0, l0), (kb, vb, kposb))
        return (acc / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)

    out = lax.map(per_qblock, (qb, qposb))           # (nq,B,K,G,Q,hd)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * Q_BLOCK, H, hd)
    return out[:, :S]


def attention_apply(cfg: ModelConfig, p: Params, x: jax.Array,
                    positions: jax.Array,
                    attention_impl: str = "auto") -> jax.Array:
    """Training/prefill self-attention. x: (B,S,D) -> (B,S,D)."""
    B, S, _ = x.shape
    q, k, v = _qkv(cfg, p, x, positions[None, :] if positions.ndim == 1 else positions)
    pos = positions if positions.ndim == 1 else positions[0]
    if attention_impl == "kernel":
        from ..kernels.flash_attention.ops import flash_attention
        if cfg.attention_multiplier:
            # the kernel scales by 1/sqrt(hd): fold the model's scale into q
            q = q * (cfg.attention_multiplier
                     * math.sqrt(cfg.resolved_head_dim))
        out = flash_attention(q, k, v, causal=True, window=cfg.sliding_window)
    elif attention_impl == "dense" or (attention_impl == "auto"
                                       and S <= BLOCKWISE_THRESHOLD):
        out = _attend_dense(cfg, q, k, v, pos, pos)
    else:
        out = _attend_blockwise(cfg, q, k, v, pos, pos)
    out = constrain(out, "batch", "seq", "act_heads", None)
    cdt = cfg.compute_jnp_dtype()
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cdt))
    return constrain(y, "batch", "seq", "act_embed")


def attention_decode(cfg: ModelConfig, p: Params, x: jax.Array,
                     cache: Dict[str, jax.Array], pos: jax.Array
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. x: (B,1,D); cache k/v: (B,Scache,K,hd).

    ``pos`` is either a scalar () — the legacy whole-batch clock — or a
    per-slot vector (B,): each row writes and masks at its own position,
    which is what lets a serving engine admit and recycle slots
    independently instead of aligning every request to one clock.

    For sliding-window configs the cache is a ring buffer of size
    min(window, S_max); keys carry their RoPE at write time so slot order
    is irrelevant.
    """
    B, _, _ = x.shape
    cdt = cfg.compute_jnp_dtype()
    Scache = cache["k"].shape[1]
    pos = jnp.broadcast_to(pos, (B,))            # scalar clock -> per-slot
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    if cfg.position_embedding == "rope":
        cos, sin = rope_table(pos[:, None], cfg.resolved_head_dim,
                              cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    slot = pos % Scache if cfg.sliding_window > 0 else pos
    rows = jnp.arange(B)
    ck = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))
    ck = constrain(ck, "batch", "seq_kv", "act_kv", None)
    cv = constrain(cv, "batch", "seq_kv", "act_kv", None)
    H = cfg.num_heads
    K = cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    qg = q.reshape(B, 1, K, H // K, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, ck.astype(cdt)
                        ).astype(jnp.float32) * cfg.attention_scale
    idx = jnp.arange(Scache)
    if cfg.sliding_window > 0:
        valid = idx[None, :] < jnp.minimum(pos + 1, Scache)[:, None]
    else:
        valid = idx[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(cdt)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, cv.astype(cdt)).reshape(B, 1, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cdt))
    return y, {"k": ck, "v": cv}


def attention_decode_paged(cfg: ModelConfig, p: Params, x: jax.Array,
                           kv: Dict[str, jax.Array], block_table: jax.Array,
                           pos: jax.Array, adv: jax.Array
                           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunked decode against a paged (block) KV cache.

    x: (B,C,D) post-norm chunk; kv k/v: (NB, bs, K, hd) — the *physical*
    block pool shared by every slot (block 0 is the reserved always-zero
    sentinel, never written); block_table: (B, nb) slot-logical block ->
    physical block; pos: (B,) tokens already resident per slot; adv:
    (B,) real tokens in this chunk per slot (0 = slot inactive, padded
    rows are dropped).

    Queries attend to the pre-chunk resident keys (gathered through the
    block table, masked to ``kpos < pos`` and the sliding window) plus
    the in-chunk keys under a causal mask, in one softmax; the chunk's
    K/V are then scattered into the pool at positions [pos, pos+adv).
    Writes for padded rows (j >= adv) are index-dropped, so one call
    serves mixed prefill/decode/idle slots.
    """
    B, C, _ = x.shape
    cdt = cfg.compute_jnp_dtype()
    NB, bs = kv["k"].shape[0], kv["k"].shape[1]
    nb = block_table.shape[1]
    S = nb * bs
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    G = H // K
    scale = cfg.attention_scale

    qpos = pos[:, None] + jnp.arange(C, dtype=pos.dtype)[None, :]    # (B,C)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    if cfg.position_embedding == "rope":
        cos, sin = rope_table(qpos, hd, cfg.rope_theta)              # (B,C,half)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    # resident keys, gathered logical-contiguous through the block table
    ck = kv["k"][block_table].reshape(B, S, K, hd).astype(cdt)
    cv = kv["v"][block_table].reshape(B, S, K, hd).astype(cdt)
    kpos = jnp.arange(S, dtype=jnp.int32)
    mask_res = kpos[None, None, :] < pos[:, None, None]              # (B,1,S)
    mask_res = jnp.broadcast_to(mask_res, (B, C, S))
    jj = jnp.arange(C, dtype=jnp.int32)
    mask_chunk = (jj[None, :] <= jj[:, None])[None]                  # causal (1,C,C)
    mask_chunk = mask_chunk & (jj[None, None, :] < adv[:, None, None])
    if cfg.sliding_window > 0:
        w_ = cfg.sliding_window
        mask_res = mask_res & (kpos[None, None, :]
                               > qpos[:, :, None] - w_)
        mask_chunk = mask_chunk & (qpos[:, None, :]
                                   > qpos[:, :, None] - w_)

    qg = q.reshape(B, C, K, G, hd)
    s_res = jnp.einsum("bqkgh,bskh->bkgqs", qg, ck).astype(jnp.float32) * scale
    s_chk = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32) * scale
    s_res = jnp.where(mask_res[:, None, None], s_res, -1e30)
    s_chk = jnp.where(mask_chunk[:, None, None], s_chk, -1e30)
    scores = jnp.concatenate([s_res, s_chk], axis=-1)                # (B,K,G,C,S+C)
    w = jax.nn.softmax(scores, axis=-1).astype(cdt)
    out = (jnp.einsum("bkgqs,bskh->bqkgh", w[..., :S], cv)
           + jnp.einsum("bkgqs,bskh->bqkgh", w[..., S:], v))
    out = out.reshape(B, C, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cdt))

    # scatter the chunk's K/V into the pool; padded rows are dropped
    lb = jnp.clip(qpos // bs, 0, nb - 1)
    blk = jnp.take_along_axis(block_table, lb, axis=1)               # (B,C)
    writable = (jj[None, :] < adv[:, None]) & (blk > 0)
    blk = jnp.where(writable, blk, NB)                               # OOB -> drop
    off = qpos % bs
    nk = kv["k"].at[blk, off].set(k.astype(kv["k"].dtype), mode="drop")
    nv = kv["v"].at[blk, off].set(v.astype(kv["v"].dtype), mode="drop")
    return y, {"k": nk, "v": nv}


def ssd_decode_chunk(cfg: ModelConfig, p: Params, x: jax.Array,
                     cache: Dict[str, jax.Array], adv: jax.Array
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Sequential SSD decode over a chunk. x: (B,C,D); adv: (B,).

    State/conv updates are gated per token to ``j < adv`` so padded rows
    of a mixed prefill/decode chunk never advance a slot's recurrence.
    """
    B, C, _ = x.shape

    def gate(keep: jax.Array, new: Dict[str, jax.Array],
             old: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        return {key: jnp.where(keep.reshape((B,) + (1,) * (new[key].ndim - 1)),
                               new[key], old[key])
                for key in new}

    if C == 1:
        y, nc = ssd_decode(cfg, p, x, cache)
        return y, gate(adv > 0, nc, cache)

    def step(st, inp):
        xt, j = inp                                                  # (B,D), ()
        yj, ns = ssd_decode(cfg, p, xt[:, None], st)
        return gate(j < adv, ns, st), yj[:, 0]

    st, ys = lax.scan(step, cache,
                      (x.transpose(1, 0, 2), jnp.arange(C, dtype=jnp.int32)))
    return ys.transpose(1, 0, 2), st


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jax.Array]:
    dtype = dtype or cfg.compute_jnp_dtype()
    size = min(max_len, cfg.sliding_window) if cfg.sliding_window > 0 else max_len
    hd = cfg.resolved_head_dim
    shape = (batch, size, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# Dense FFN (SwiGLU / GeGLU / GELU)
# ---------------------------------------------------------------------------


def build_mlp(b: Builder, cfg: ModelConfig, name: str = "mlp",
              d_ff: Optional[int] = None) -> Params:
    d_ff = d_ff or cfg.d_ff
    with b.scope(name):
        p = {
            "w_up": b.param("w_up", (cfg.d_model, d_ff), ("embed", "ffn_tp"),
                            he_normal, fan_in=cfg.d_model),
            "w_down": b.param("w_down", (d_ff, cfg.d_model), ("ffn_tp", "embed"),
                              he_normal, fan_in=d_ff),
        }
        if cfg.act in ("swiglu", "geglu"):
            p["w_gate"] = b.param("w_gate", (cfg.d_model, d_ff),
                                  ("embed", "ffn_tp"), he_normal, fan_in=cfg.d_model)
        return p


def _ffn_use_sp_boundary(x: jax.Array, d_ff: int) -> bool:
    """Adaptive Megatron-SP boundary decision (EXPERIMENTS.md §Perf).

    Under sequence parallelism, constraining the FFN intermediate to the
    seq layout leaves no shardable dim for the 2D-sharded weights, so XLA
    replicates them (measured 6.9 TiB/step/device on qwen-110b). Gathering
    seq at the FFN boundary instead costs ~2 activation passes. Pick
    whichever moves fewer bytes:
        sp:  2 * (B/dp) * S * D          (+ w gather over data, small)
        seq: 3 * D * F                   (weights replicated over model)
    Small models keep the seq layout (danube prefill regressed 2.5x under
    unconditional SP-FFN); large-FFN models switch to the SP boundary.
    """
    from ..parallel.sharding import current_rules
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return False
    if rules.resolve("seq") is None:
        return False  # no SP in effect; both layouts are identical
    sizes = dict(zip(rules.mesh.axis_names, rules.mesh.devices.shape))
    b_axes = rules.resolve("batch") or ()
    b_axes = (b_axes,) if isinstance(b_axes, str) else b_axes
    dp = 1
    for a in b_axes:
        dp *= sizes.get(a, 1)
    B, S, D = x.shape
    seq_gather = 2 * max(B // max(dp, 1), 1) * S * D
    weight_repl = 3 * D * d_ff
    return weight_repl > seq_gather


def mlp_apply(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    cdt = cfg.compute_jnp_dtype()
    sp_boundary = _ffn_use_sp_boundary(x, p["w_up"].shape[-1])
    seq_ax = None if sp_boundary else "seq"
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(cdt))
    up = constrain(up, "batch", seq_ax, "act_ff")
    if cfg.act == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(cdt))
        h = jax.nn.silu(g) * up
    elif cfg.act == "geglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(cdt))
        h = jax.nn.gelu(g) * up
    else:
        h = jax.nn.gelu(up)
    h = constrain(h, "batch", seq_ax, "act_ff")
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(cdt))
    return constrain(y, "batch", "seq", "act_embed")


# ---------------------------------------------------------------------------
# MoE (top-k routing, index-based dispatch, EP over the "experts" axis)
# ---------------------------------------------------------------------------


def build_moe(b: Builder, cfg: ModelConfig) -> Params:
    E, D, F = cfg.num_experts, cfg.d_model, cfg.expert_d_ff
    with b.scope("moe"):
        p = {
            "router": b.param("router", (D, E), ("embed", "experts"),
                              normal_init(0.02), dtype=jnp.float32),
            "w_up": b.param("w_up", (E, D, F),
                            ("experts", "expert_embed", "expert_ffn"),
                            he_normal, fan_in=D),
            "w_gate": b.param("w_gate", (E, D, F),
                              ("experts", "expert_embed", "expert_ffn"),
                              he_normal, fan_in=D),
            "w_down": b.param("w_down", (E, F, D),
                              ("experts", "expert_ffn", "expert_embed"),
                              he_normal, fan_in=F),
        }
        if cfg.dense_residual:
            p["dense"] = build_mlp(b, cfg, "dense_residual", cfg.d_ff)
        return p


def moe_apply(cfg: ModelConfig, p: Params, x: jax.Array
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B,S,D) -> (y, aux_losses). Capacity-dropped top-k dispatch."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    cdt = cfg.compute_jnp_dtype()
    T = B * S
    xt = x.reshape(T, D)

    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (T,E)
    weights, ids = lax.top_k(probs, k)                           # (T,k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    # aux losses (Switch-style load balance + router z-loss)
    me = probs.mean(axis=0)                                      # (E,)
    ce = jnp.zeros(E).at[ids.reshape(-1)].add(1.0) / (T * k)
    lb_loss = E * jnp.sum(me * ce) * cfg.load_balance_loss
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * cfg.router_z_loss

    cap = int(math.ceil(T * k * cfg.capacity_factor / E / 128.0) * 128)
    cap = max(cap, 128)

    # slot of each (token, choice) within its expert
    flat_ids = ids.reshape(-1)                                   # (T*k,)
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)        # (T*k,E)
    slots = (jnp.cumsum(onehot, axis=0) - onehot)                # pre-count
    slot = jnp.take_along_axis(slots, flat_ids[:, None], axis=1)[:, 0]  # (T*k,)
    keep = slot < cap
    tok_idx = jnp.repeat(jnp.arange(T), k)

    buf = jnp.zeros((E, cap, D), cdt)
    buf = buf.at[flat_ids, jnp.where(keep, slot, cap - 1)].add(
        jnp.where(keep[:, None], xt[tok_idx].astype(cdt), 0))
    buf = constrain(buf, "act_experts", "moe_cap", None)

    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(cdt))
    gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(cdt))
    act = jax.nn.gelu(gate) * up if cfg.act == "geglu" else jax.nn.silu(gate) * up
    act = constrain(act, "act_experts", "moe_cap", None)
    out_buf = jnp.einsum("ecf,efd->ecd", act, p["w_down"].astype(cdt))
    out_buf = constrain(out_buf, "act_experts", "moe_cap", None)

    gathered = out_buf[flat_ids, jnp.clip(slot, 0, cap - 1)]     # (T*k,D)
    gathered = jnp.where(keep[:, None], gathered, 0)
    w_flat = weights.reshape(-1).astype(cdt)
    y = jnp.zeros((T, D), cdt).at[tok_idx].add(gathered * w_flat[:, None])
    y = y.reshape(B, S, D)

    if cfg.dense_residual:
        y = y + mlp_apply(cfg, p["dense"], x)
    y = constrain(y, "batch", "seq", "act_embed")
    return y, {"load_balance": lb_loss, "router_z": z_loss}


# ---------------------------------------------------------------------------
# Mamba-2 (SSD, chunked matmul form)
# ---------------------------------------------------------------------------


def build_ssd(b: Builder, cfg: ModelConfig) -> Params:
    D = cfg.d_model
    di = cfg.ssm_d_inner
    N = cfg.ssm_state
    H = cfg.ssm_num_heads
    convC = di + 2 * N
    with b.scope("ssd"):
        return {
            "w_in_x": b.param("w_in_x", (D, di), ("embed", "ssm_inner_tp"),
                              he_normal, fan_in=D),
            "w_in_z": b.param("w_in_z", (D, di), ("embed", "ssm_inner_tp"),
                              he_normal, fan_in=D),
            "w_in_B": b.param("w_in_B", (D, N), ("embed", "ssm_state"),
                              he_normal, fan_in=D),
            "w_in_C": b.param("w_in_C", (D, N), ("embed", "ssm_state"),
                              he_normal, fan_in=D),
            "w_in_dt": b.param("w_in_dt", (D, H), ("embed", "ssm_heads"),
                               he_normal, fan_in=D),
            "dt_bias": b.param("dt_bias", (H,), ("ssm_heads",), zeros_init,
                               dtype=jnp.float32),
            "a_log": b.param("a_log", (H,), ("ssm_heads",),
                             lambda k_, s, d, f=None: jnp.log(
                                 jnp.linspace(1.0, 16.0, s[0])).astype(d),
                             dtype=jnp.float32),
            "d_skip": b.param("d_skip", (H,), ("ssm_heads",), ones_init,
                              dtype=jnp.float32),
            "conv_w": b.param("conv_w", (cfg.conv_kernel, convC),
                              ("conv_k", "ssm_inner_tp"), normal_init(0.1)),
            "conv_b": b.param("conv_b", (convC,), ("ssm_inner_tp",), zeros_init),
            "w_out": b.param("w_out", (di, D), ("ssm_inner_tp", "embed"),
                             he_normal, fan_in=di),
            "norm": build_rmsnorm(b, "gated_norm", di),
        }


def _causal_conv(x: jax.Array, w: jax.Array, b_: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B,S,C), w: (k,C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    # windows via k shifted adds (k is tiny: 4)
    S = x.shape[1]
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        out = out + xp[:, i:i + S].astype(jnp.float32) * w[i].astype(jnp.float32)
    return (out + b_.astype(jnp.float32)).astype(x.dtype)


def _segsum(t: jax.Array) -> jax.Array:
    """(..., Q) -> (..., Q, Q) lower-tri segment sums: out[i,j]=sum(t[j+1..i])."""
    Q = t.shape[-1]
    c = jnp.cumsum(t, axis=-1)
    out = c[..., :, None] - c[..., None, :]
    ii, jj = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
    return jnp.where(ii >= jj, out, -jnp.inf)


def ssd_apply(cfg: ModelConfig, p: Params, x: jax.Array,
              return_state: bool = False):
    """Chunked SSD. x: (B,S,D) -> (B,S,D) [, final cache state]."""
    B, S, D = x.shape
    cdt = cfg.compute_jnp_dtype()
    di, N, H, P = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads, cfg.ssm_head_dim
    Q = min(cfg.ssm_chunk, S)
    pad = (-S) % Q
    nc = (S + pad) // Q

    xs = jnp.einsum("bsd,de->bse", x, p["w_in_x"].astype(cdt))
    z = jnp.einsum("bsd,de->bse", x, p["w_in_z"].astype(cdt))
    Bm = jnp.einsum("bsd,dn->bsn", x, p["w_in_B"].astype(cdt))
    Cm = jnp.einsum("bsd,dn->bsn", x, p["w_in_C"].astype(cdt))
    dt = jnp.einsum("bsd,dh->bsh", x, p["w_in_dt"].astype(cdt))
    xs = constrain(xs, "batch", "seq", "act_ff")
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_out = jax.nn.silu(_causal_conv(conv_in, p["conv_w"], p["conv_b"]))
    xs, Bm, Cm = conv_out[..., :di], conv_out[..., di:di + N], conv_out[..., di + N:]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])          # (B,S,H)
    A = -jnp.exp(p["a_log"])                                             # (H,)
    dA = dt * A                                                          # (B,S,H) log-decay

    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))

    xh = xs.reshape(B, nc, Q, H, P)
    Bc = Bm.reshape(B, nc, Q, N).astype(jnp.float32)
    Cc = Cm.reshape(B, nc, Q, N).astype(jnp.float32)
    dtc = dt.reshape(B, nc, Q, H)
    dAc = dA.reshape(B, nc, Q, H)

    xdt = xh.astype(jnp.float32) * dtc[..., None]                        # dt-scaled input
    L = jnp.exp(_segsum(dAc.transpose(0, 1, 3, 2)))                      # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)[:, :, None] * L       # (B,nc,H,Q,Q)
    y_diag = jnp.einsum("bchqk,bckhp->bcqhp", scores, xdt)

    cum = jnp.cumsum(dAc, axis=2)                                        # (B,nc,Q,H)
    total = cum[:, :, -1]                                                # (B,nc,H)
    decay_to_end = jnp.exp(total[:, :, None] - cum)                      # (B,nc,Q,H)
    chunk_states = jnp.einsum("bcqn,bcqh,bcqhp->bchnp", Bc, decay_to_end, xdt)

    def scan_fn(carry, inp):
        st, dec = inp                                                    # (B,H,N,P), (B,H)
        new = carry * jnp.exp(dec)[..., None, None] + st
        return new, carry                                                # emit prev state

    init = jnp.zeros((B, H, N, P), jnp.float32)
    final_state, prev_states = lax.scan(scan_fn,
                                        init,
                                        (chunk_states.transpose(1, 0, 2, 3, 4),
                                         total.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)                   # (B,nc,H,N,P)

    y_off = jnp.einsum("bcqn,bchnp,bcqh->bcqhp", Cc, prev_states, jnp.exp(cum))
    y = (y_diag + y_off).reshape(B, nc * Q, H, P)[:, :S]
    y = y + xs.reshape(B, nc * Q, H, P)[:, :S].astype(jnp.float32) * p["d_skip"][:, None]
    y = y.reshape(B, S, di).astype(cdt)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"].astype(cdt))
    out = constrain(out, "batch", "seq", "act_embed")
    if return_state:
        k = cfg.conv_kernel
        conv_tail = jnp.pad(conv_in, ((0, 0), (k - 1, 0), (0, 0)))[:, S:S + k - 1]
        return out, {"state": final_state, "conv": conv_tail}
    return out


def init_ssd_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32
                   ) -> Dict[str, jax.Array]:
    H, N, P = cfg.ssm_num_heads, cfg.ssm_state, cfg.ssm_head_dim
    convC = cfg.ssm_d_inner + 2 * cfg.ssm_state
    return {
        "state": jnp.zeros((batch, H, N, P), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_kernel - 1, convC),
                          cfg.compute_jnp_dtype()),
    }


def ssd_decode(cfg: ModelConfig, p: Params, x: jax.Array,
               cache: Dict[str, jax.Array]
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token SSD step. x: (B,1,D)."""
    B = x.shape[0]
    cdt = cfg.compute_jnp_dtype()
    di, N, H, P = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads, cfg.ssm_head_dim
    xt = x[:, 0]
    xs = xt @ p["w_in_x"].astype(cdt)
    z = xt @ p["w_in_z"].astype(cdt)
    Bm = xt @ p["w_in_B"].astype(cdt)
    Cm = xt @ p["w_in_C"].astype(cdt)
    dt = xt @ p["w_in_dt"].astype(cdt)

    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)                 # (B,convC)
    window = jnp.concatenate([cache["conv"], conv_in[:, None]], axis=1)  # (B,k,convC)
    conv_out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32)) + p["conv_b"].astype(jnp.float32)
    conv_out = jax.nn.silu(conv_out)
    xs = conv_out[:, :di].reshape(B, H, P)
    Bm = conv_out[:, di:di + N]
    Cm = conv_out[:, di + N:]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])      # (B,H)
    A = -jnp.exp(p["a_log"])
    dA = jnp.exp(dt * A)                                             # (B,H)
    xdt = xs * dt[..., None]                                         # (B,H,P)
    state = cache["state"] * dA[..., None, None] + \
        jnp.einsum("bn,bhp->bhnp", Bm, xdt)
    y = jnp.einsum("bn,bhnp->bhp", Cm, state) + xs * p["d_skip"][:, None]
    y = y.reshape(B, di).astype(cdt)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = (y @ p["w_out"].astype(cdt))[:, None]
    return out, {"state": state, "conv": window[:, 1:].astype(cache["conv"].dtype)}
