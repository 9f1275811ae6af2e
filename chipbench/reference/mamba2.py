"""Plain float32 reference for Mamba-2 (arXiv:2405.21060), the
attention-free SSD stack of state-spaces/mamba2-780m, with one group
(B and C shared by every head).

    x = embed[tokens]
    per layer:  h = n1(x)
                xs, z, B, C, dt = h Wx, h Wz, h WB, h WC, h Wdt
                xs, B, C = silu(causal depthwise conv4([xs, B, C]) + b)
                dt = softplus(dt + dt_bias);  A = -exp(A_log)
                state_t = exp(dt_t A) state_{t-1} + dt_t B_t (x) xs_t   per head
                y_t = C_t . state_t + D xs_t
                x += Wout gated_rmsnorm(y * silu(z))
    logits = embed rmsnorm(x)            (tied embeddings)

The recurrence is run token by token, as the paper's recurrent form
states it, in float32; every matrix product is at
``Precision.HIGHEST``. Nothing here imports the program; the weight
layout names its leaves as the program's parameter tree does.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.dense_gqa import Get, rmsnorm
from chipbench.weights import Layout, Leaf

HI = lax.Precision.HIGHEST


def _sizes(c: Dict[str, Any]):
    D = c["d_model"]
    di = c["expand"] * D
    N, P = c["d_state"], c["headdim"]
    return D, di, N, P, di // P


def vocab_rows(c: Dict[str, Any]) -> int:
    """Rows of the embedding and the tied head: ``vocab_size`` padded up
    to a multiple of ``pad_vocab_size_multiple``, as mamba_ssm pads it."""
    m = int(c.get("pad_vocab_size_multiple", 1))
    return -(-int(c["vocab_size"]) // m) * m


def program_config(c: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ModelConfig fields for this configuration."""
    return dict(name=c["name"], family="ssm", num_layers=c["n_layer"],
                d_model=c["d_model"], num_heads=0, num_kv_heads=0, d_ff=0,
                vocab_size=vocab_rows(c), ssm_state=c["d_state"],
                ssm_expand=c["expand"], ssm_head_dim=c["headdim"],
                conv_kernel=c["d_conv"], tie_embeddings=c["tie_embeddings"],
                norm_eps=c["norm_epsilon"], param_dtype=c["torch_dtype"],
                compute_dtype=c["torch_dtype"])


def layout(c: Dict[str, Any]) -> Layout:
    D, di, N, P, H = _sizes(c)
    L, V, dt = c["n_layer"], vocab_rows(c), c["torch_dtype"]
    convC = di + 2 * N

    def lecun(fan_in):
        return ("normal", 1.0 / math.sqrt(fan_in))

    norm = ("normal", 0.1, 1.0)
    return {
        "embed": Leaf((V, D), dt, ("normal", 0.02)),
        "final_norm/scale": Leaf((D,), dt, norm),
        "layers/norm1/scale": Leaf((D,), dt, norm, L),
        "layers/ssd/w_in_x": Leaf((D, di), dt, lecun(D), L),
        "layers/ssd/w_in_z": Leaf((D, di), dt, lecun(D), L),
        "layers/ssd/w_in_B": Leaf((D, N), dt, lecun(D), L),
        "layers/ssd/w_in_C": Leaf((D, N), dt, lecun(D), L),
        "layers/ssd/w_in_dt": Leaf((D, H), dt, lecun(D), L),
        "layers/ssd/dt_bias": Leaf((H,), "float32", ("dt_bias", 1e-3, 1e-1), L),
        "layers/ssd/a_log": Leaf((H,), "float32", ("log_linspace", 1.0, 16.0), L),
        "layers/ssd/d_skip": Leaf((H,), "float32", norm, L),
        "layers/ssd/conv_w": Leaf((c["d_conv"], convC), dt, ("normal", 0.1), L),
        "layers/ssd/conv_b": Leaf((convC,), dt, ("normal", 0.1), L),
        "layers/ssd/w_out": Leaf((di, D), dt, lecun(di), L),
        "layers/ssd/norm/scale": Leaf((di,), dt, norm, L),
    }


def hidden(c: Dict[str, Any], get: Get, tokens: jax.Array) -> jax.Array:
    """Final normed hidden states (B, S, D) in float32."""
    D, di, N, P, H = _sizes(c)
    eps, kc = c["norm_epsilon"], c["d_conv"]
    Bsz, S = tokens.shape
    x = jnp.take(get("embed", None), tokens, axis=0)

    def layer(x, i):
        def w(name):
            return get("layers/ssd/" + name, i)

        h = rmsnorm(x, get("layers/norm1/scale", i), eps)
        proj = {n: jnp.einsum("bsd,de->bse", h, w("w_in_" + n), precision=HI)
                for n in ("x", "z", "B", "C", "dt")}
        xbc = jnp.concatenate([proj["x"], proj["B"], proj["C"]], axis=-1)
        padded = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
        conv = sum(padded[:, j:j + S] * w("conv_w")[j] for j in range(kc))
        xbc = jax.nn.silu(conv + w("conv_b"))
        xs = xbc[..., :di].reshape(Bsz, S, H, P)
        Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
        dt = jax.nn.softplus(proj["dt"] + w("dt_bias"))          # (B,S,H)
        decay = jnp.exp(dt * -jnp.exp(w("a_log")))               # (B,S,H)

        def step(state, t):                                      # (B,H,N,P)
            upd = jnp.einsum("bn,bhp->bhnp", Bm[:, t], xs[:, t] * dt[:, t, :, None],
                             precision=HI)
            state = state * decay[:, t, :, None, None] + upd
            return state, jnp.einsum("bn,bhnp->bhp", Cm[:, t], state,
                                     precision=HI)

        _, ys = lax.scan(step, jnp.zeros((Bsz, H, N, P), jnp.float32),
                         jnp.arange(S))
        y = ys.transpose(1, 0, 2, 3) + xs * w("d_skip")[:, None]
        y = y.reshape(Bsz, S, di) * jax.nn.silu(proj["z"])
        y = rmsnorm(y, w("norm/scale"), eps)
        return x + jnp.einsum("bse,ed->bsd", y, w("w_out"), precision=HI), None

    x, _ = lax.scan(layer, x, jnp.arange(c["n_layer"]))
    return rmsnorm(x, get("final_norm/scale", None), eps)


def head(c: Dict[str, Any], get: Get) -> jax.Array:
    """(D, V) output projection: the embedding, tied."""
    return get("embed", None).T


def logits(c: Dict[str, Any], get: Get, tokens: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,dv->bsv", hidden(c, get, tokens), head(c, get),
                      precision=HI)
