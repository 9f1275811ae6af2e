"""Plain float32 reference for granite-4.0-h (IBM's GraniteMoeHybrid, here
without experts): Mamba-2 layers with a few grouped-query attention
layers among them, a SwiGLU MLP in every layer, and muP multipliers.

    x = embedding_multiplier * embed[tokens]
    layer i:  h = mixer_i(n1(x))      Mamba-2, or attention (layer_types)
              x += residual_multiplier * h
              x += residual_multiplier * Wdown (silu(Wgate n2(x)) * Wup n2(x))
    logits = (embed rmsnorm(x)) / logits_scaling          (tied embeddings)

Attention carries no position embedding (``position_embedding_type``
"nope"), scales its scores by ``attention_multiplier`` in place of
1/sqrt(head_dim), is causal, and shares each key/value head among
``H / K`` query heads; it is blocked over queries, as
``dense_gqa.attention`` blocks it. The Mamba-2 mixer is
``reference/mamba2.py``'s, with one group: the input projection split
into x, z, B, C and dt; a causal depthwise conv (with bias) and SiLU on
[x, B, C]; dt = softplus(dt + dt_bias), A = -exp(A_log); the recurrence
state_t = exp(dt_t A) state_{t-1} + dt_t B_t (x) x_t, run token by token;
y_t = C_t . state_t + D x_t; a gated RMSNorm of y * silu(z); the output
projection. Every matrix product is float32 at ``Precision.HIGHEST``.
Nothing here imports the program; the weight layout names its leaves as
the program's parameter tree does: one stack per layer kind.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.dense_gqa import Get, attention, rmsnorm
from chipbench.weights import Layout, Leaf

HI = lax.Precision.HIGHEST
KINDS = ("mamba", "attention")


def sizes(c: Dict[str, Any]):
    D = c["hidden_size"]
    di = c["mamba_expand"] * D
    return D, di, c["mamba_d_state"], c["mamba_d_head"], c["mamba_n_heads"]


def _check(c: Dict[str, Any]) -> None:
    """The published settings this reference and the program compute."""
    D, di, N, P, H = sizes(c)
    want = dict(mamba_n_groups=1, num_local_experts=0, attention_bias=False,
                mamba_proj_bias=False, mamba_conv_bias=True, hidden_act="silu",
                position_embedding_type="nope",
                normalization_function="rmsnorm", tie_word_embeddings=True)
    off = {k: c[k] for k, v in want.items() if c[k] != v}
    if off or H * P != di:
        raise ValueError(f"{c['name']}: not a configuration this reference "
                         f"computes: {off or {'mamba_n_heads': H}}")


def head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def program_config(c: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ModelConfig fields for this configuration."""
    _check(c)
    return dict(name=c["name"], family="hybrid",
                layer_types=tuple(c["layer_types"]),
                num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                num_heads=c["num_attention_heads"],
                num_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
                d_ff=c["shared_intermediate_size"], vocab_size=c["vocab_size"],
                act="swiglu", norm_eps=c["rms_norm_eps"],
                rope_theta=float(c["rope_theta"]),
                position_embedding=c["position_embedding_type"],
                tie_embeddings=c["tie_word_embeddings"],
                ssm_state=c["mamba_d_state"], ssm_expand=c["mamba_expand"],
                ssm_head_dim=c["mamba_d_head"],
                ssm_chunk=c["mamba_chunk_size"], conv_kernel=c["mamba_d_conv"],
                embedding_multiplier=float(c["embedding_multiplier"]),
                residual_multiplier=float(c["residual_multiplier"]),
                attention_multiplier=float(c["attention_multiplier"]),
                logits_scaling=float(c["logits_scaling"]),
                param_dtype=c["torch_dtype"], compute_dtype=c["torch_dtype"])


def layout(c: Dict[str, Any]) -> Layout:
    _check(c)
    D, di, N, P, H = sizes(c)
    F, V, dt = c["shared_intermediate_size"], c["vocab_size"], c["torch_dtype"]
    Hq, K, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    convC = di + 2 * N
    n = {k: c["layer_types"].count(k) for k in KINDS}

    def he(fan_in):
        return ("normal", math.sqrt(2.0 / fan_in))

    def lecun(fan_in):
        return ("normal", 1.0 / math.sqrt(fan_in))

    norm = ("normal", 0.1, 1.0)
    out = {"embed": Leaf((V, D), dt, ("normal", 0.02)),
           "final_norm/scale": Leaf((D,), dt, norm)}
    for kind in KINDS:
        L, pre = n[kind], f"layers/{kind}/"
        out.update({
            pre + "norm1/scale": Leaf((D,), dt, norm, L),
            pre + "norm2/scale": Leaf((D,), dt, norm, L),
            pre + "mlp/w_gate": Leaf((D, F), dt, he(D), L),
            pre + "mlp/w_up": Leaf((D, F), dt, he(D), L),
            pre + "mlp/w_down": Leaf((F, D), dt, he(F), L),
        })
    pre, L = "layers/mamba/ssd/", n["mamba"]
    out.update({
        pre + "w_in_x": Leaf((D, di), dt, lecun(D), L),
        pre + "w_in_z": Leaf((D, di), dt, lecun(D), L),
        pre + "w_in_B": Leaf((D, N), dt, lecun(D), L),
        pre + "w_in_C": Leaf((D, N), dt, lecun(D), L),
        pre + "w_in_dt": Leaf((D, H), dt, lecun(D), L),
        pre + "dt_bias": Leaf((H,), "float32", ("dt_bias", 1e-3, 1e-1), L),
        pre + "a_log": Leaf((H,), "float32", ("log_linspace", 1.0, 16.0), L),
        pre + "d_skip": Leaf((H,), "float32", norm, L),
        pre + "conv_w": Leaf((c["mamba_d_conv"], convC), dt, ("normal", 0.1), L),
        pre + "conv_b": Leaf((convC,), dt, ("normal", 0.1), L),
        pre + "w_out": Leaf((di, D), dt, lecun(di), L),
        pre + "norm/scale": Leaf((di,), dt, norm, L),
    })
    pre, L = "layers/attention/attn/", n["attention"]
    out.update({
        pre + "wq": Leaf((D, Hq, hd), dt, he(D), L),
        pre + "wk": Leaf((D, K, hd), dt, he(D), L),
        pre + "wv": Leaf((D, K, hd), dt, he(D), L),
        pre + "wo": Leaf((Hq, hd, D), dt, he(Hq * hd), L),
    })
    return out


def mamba(c: Dict[str, Any], w, h: jax.Array) -> jax.Array:
    """The Mamba-2 mixer on normed input h (B, S, D); ``w(name)`` gives
    this layer's ``ssd/<name>`` weight."""
    D, di, N, P, H = sizes(c)
    kc, eps = c["mamba_d_conv"], c["rms_norm_eps"]
    Bsz, S, _ = h.shape
    proj = {n: jnp.einsum("bsd,de->bse", h, w("w_in_" + n), precision=HI)
            for n in ("x", "z", "B", "C", "dt")}
    xbc = jnp.concatenate([proj["x"], proj["B"], proj["C"]], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + S] * w("conv_w")[j] for j in range(kc))
    xbc = jax.nn.silu(conv + w("conv_b"))
    xs = xbc[..., :di].reshape(Bsz, S, H, P)
    Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(proj["dt"] + w("dt_bias"))              # (B,S,H)
    decay = jnp.exp(dt * -jnp.exp(w("a_log")))                   # (B,S,H)

    def step(state, t):                                          # (B,H,N,P)
        upd = jnp.einsum("bn,bhp->bhnp", Bm[:, t], xs[:, t] * dt[:, t, :, None],
                         precision=HI)
        state = state * decay[:, t, :, None, None] + upd
        return state, jnp.einsum("bn,bhnp->bhp", Cm[:, t], state, precision=HI)

    _, ys = lax.scan(step, jnp.zeros((Bsz, H, N, P), jnp.float32),
                     jnp.arange(S))
    y = ys.transpose(1, 0, 2, 3) + xs * w("d_skip")[:, None]
    y = y.reshape(Bsz, S, di) * jax.nn.silu(proj["z"])
    y = rmsnorm(y, w("norm/scale"), eps)
    return jnp.einsum("bse,ed->bsd", y, w("w_out"), precision=HI)


def self_attention(c: Dict[str, Any], w, h: jax.Array) -> jax.Array:
    """NoPE causal GQA on normed input h at scale ``attention_multiplier``;
    ``w(name)`` gives this layer's ``attn/<name>`` weight."""
    q = jnp.einsum("bsd,dhk->bshk", h, w("wq"), precision=HI)
    k = jnp.einsum("bsd,dhk->bshk", h, w("wk"), precision=HI)
    v = jnp.einsum("bsd,dhk->bshk", h, w("wv"), precision=HI)
    # dense_gqa.attention divides the scores by sqrt(head_dim): the
    # published scale goes on q, and no window
    q = q * (c["attention_multiplier"] * math.sqrt(head_dim(c)))
    out = attention({"sliding_window": 0}, q, k, v)
    return jnp.einsum("bshk,hkd->bsd", out, w("wo"), precision=HI)


def hidden(c: Dict[str, Any], get: Get, tokens: jax.Array) -> jax.Array:
    """Final normed hidden states (B, S, D) in float32."""
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    x = c["embedding_multiplier"] * jnp.take(get("embed", None), tokens, axis=0)
    kinds = [KINDS.index(k) for k in c["layer_types"]]
    ranks = [c["layer_types"][:i].count(k) for i, k in enumerate(c["layer_types"])]

    def layer(kind, x, i):
        def w(name):
            return get(f"layers/{kind}/{name}", i)

        mixer = mamba if kind == "mamba" else self_attention
        sub = "ssd/" if kind == "mamba" else "attn/"
        x = x + r * mixer(c, lambda n: w(sub + n),
                          rmsnorm(x, w("norm1/scale"), eps))
        h = rmsnorm(x, w("norm2/scale"), eps)
        g = jnp.einsum("bsd,df->bsf", h, w("mlp/w_gate"), precision=HI)
        u = jnp.einsum("bsd,df->bsf", h, w("mlp/w_up"), precision=HI)
        return x + r * jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                                  w("mlp/w_down"), precision=HI)

    def step(x, kind_rank):
        kind, rank = kind_rank
        branches = [lambda x, i, k=k: layer(k, x, i) for k in KINDS]
        return lax.switch(kind, branches, x, rank), None

    x, _ = lax.scan(step, x, (jnp.asarray(kinds), jnp.asarray(ranks)))
    return rmsnorm(x, get("final_norm/scale", None), eps)


def head(c: Dict[str, Any], get: Get) -> jax.Array:
    """(D, V) output projection: the embedding, tied."""
    return get("embed", None).T


def logits(c: Dict[str, Any], get: Get, tokens: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,dv->bsv", hidden(c, get, tokens), head(c, get),
                      precision=HI) / c["logits_scaling"]
