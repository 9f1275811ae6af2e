"""Plain float32 reference for a dense decoder with grouped-query
attention: h2o-danube-1.8b (arXiv:2401.16818), a Llama/Mistral block.

    x = embed[tokens]
    per layer:  x += Wo . attn(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))
                x += Wdown (silu(Wgate n2(x)) * Wup n2(x))
    logits = head^T rmsnorm(x)

Attention is causal with a sliding window (a key at distance >= window
is not seen), each group of ``H / K`` query heads shares one key/value
head, and RoPE rotates the two halves of each head (the "rotate half"
form) at frequencies ``theta ** (-2i / head_dim)``. Every matrix product
is float32 at ``Precision.HIGHEST``. Nothing here imports the program;
the weight layout names its leaves as the program's parameter tree does,
as a checkpoint format would.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.weights import Layout, Leaf

HI = lax.Precision.HIGHEST
Q_BLOCK = 512       # query rows per attention block: bounds the score memory

Get = Callable[[str, Any], jax.Array]   # (path, layer index) -> f32 weight


def program_config(c: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ModelConfig fields for this configuration."""
    return dict(name=c["name"], family="dense",
                num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                num_heads=c["num_attention_heads"],
                num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
                act="swiglu", norm_eps=c["rms_norm_eps"],
                rope_theta=float(c["rope_theta"]),
                sliding_window=c["sliding_window"] or 0,
                tie_embeddings=c["tie_word_embeddings"],
                param_dtype=c["torch_dtype"], compute_dtype=c["torch_dtype"])


def layout(c: Dict[str, Any]) -> Layout:
    D, F, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    H, K, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    L, dt = c["num_hidden_layers"], c["torch_dtype"]

    def he(fan_in):
        return ("normal", math.sqrt(2.0 / fan_in))

    norm = ("normal", 0.1, 1.0)
    out = {
        "embed": Leaf((V, D), dt, ("normal", 0.02)),
        "final_norm/scale": Leaf((D,), dt, norm),
        "layers/norm1/scale": Leaf((D,), dt, norm, L),
        "layers/norm2/scale": Leaf((D,), dt, norm, L),
        "layers/attn/wq": Leaf((D, H, hd), dt, he(D), L),
        "layers/attn/wk": Leaf((D, K, hd), dt, he(D), L),
        "layers/attn/wv": Leaf((D, K, hd), dt, he(D), L),
        "layers/attn/wo": Leaf((H, hd, D), dt, he(H * hd), L),
        "layers/mlp/w_gate": Leaf((D, F), dt, he(D), L),
        "layers/mlp/w_up": Leaf((D, F), dt, he(D), L),
        "layers/mlp/w_down": Leaf((F, D), dt, he(F), L),
    }
    if not c["tie_word_embeddings"]:
        out["head"] = Leaf((D, V), dt, ("normal", 0.02))
    return out


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, heads, hd)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / x.shape[-1])
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(c: Dict[str, Any], q, k, v) -> jax.Array:
    """q: (B,S,H,hd), k/v: (B,S,K,hd) -> (B,S,H,hd); causal, windowed."""
    B, S, H, hd = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)              # query head h reads kv head h // G
    v = jnp.repeat(v, G, axis=2)
    window = c["sliding_window"] or S + 1
    kpos = jnp.arange(S)
    nq = -(-S // Q_BLOCK)
    qp = jnp.pad(q, ((0, 0), (0, nq * Q_BLOCK - S), (0, 0), (0, 0)))

    def block(i):
        qi = lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK, axis=1)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HI) / math.sqrt(hd)
        seen = (kpos[None, :] <= qpos[:, None]) & (
            kpos[None, :] > qpos[:, None] - window)
        # finite, so the padded query rows past S (which see no key) give
        # a finite softmax and no NaN in a gradient
        s = jnp.where(seen[None, None], s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    out = lax.map(block, jnp.arange(nq))       # (nq, B, Q, H, hd)
    return out.transpose(1, 0, 2, 3, 4).reshape(B, nq * Q_BLOCK, H, hd)[:, :S]


def hidden(c: Dict[str, Any], get: Get, tokens: jax.Array) -> jax.Array:
    """Final normed hidden states (B, S, D) in float32."""
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    S = tokens.shape[1]
    pos = jnp.arange(S)
    x = jnp.take(get("embed", None), tokens, axis=0)

    def layer(x, i):
        h = rmsnorm(x, get("layers/norm1/scale", i), eps)
        q = rope(jnp.einsum("bsd,dhk->bshk", h, get("layers/attn/wq", i),
                            precision=HI), pos, theta)
        k = rope(jnp.einsum("bsd,dhk->bshk", h, get("layers/attn/wk", i),
                            precision=HI), pos, theta)
        v = jnp.einsum("bsd,dhk->bshk", h, get("layers/attn/wv", i),
                       precision=HI)
        x = x + jnp.einsum("bshk,hkd->bsd", attention(c, q, k, v),
                           get("layers/attn/wo", i), precision=HI)
        h = rmsnorm(x, get("layers/norm2/scale", i), eps)
        g = jnp.einsum("bsd,df->bsf", h, get("layers/mlp/w_gate", i),
                       precision=HI)
        u = jnp.einsum("bsd,df->bsf", h, get("layers/mlp/w_up", i),
                       precision=HI)
        x = x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                           get("layers/mlp/w_down", i), precision=HI)
        return x, None

    x, _ = lax.scan(layer, x, jnp.arange(c["num_hidden_layers"]))
    return rmsnorm(x, get("final_norm/scale", None), eps)


def head(c: Dict[str, Any], get: Get) -> jax.Array:
    """(D, V) output projection."""
    if c["tie_word_embeddings"]:
        return get("embed", None).T
    return get("head", None)


def logits(c: Dict[str, Any], get: Get, tokens: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,dv->bsv", hidden(c, get, tokens), head(c, get),
                      precision=HI)


def tree_getter(params: Dict[str, Any]) -> Get:
    """A getter over an explicit nested weight tree, cast to float32."""
    def get(path: str, layer):
        node = params
        for k in path.split("/"):
            node = node[k]
        node = node.astype(jnp.float32)
        return node if layer is None else node[layer]
    return get


def loss(c: Dict[str, Any], params: Dict[str, Any], tokens: jax.Array,
         labels: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy over every position, float32."""
    lg = logits(c, tree_getter(params), tokens)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


loss_and_grad = jax.value_and_grad(loss, argnums=1)
