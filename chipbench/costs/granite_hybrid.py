"""Operations and bytes of a granite-4.0-h stack (Mamba-2 layers with a
few attention layers among them, a SwiGLU MLP in every layer), from its
shapes alone.

Counted as the least work the math needs, whatever implements it:

- a token's operations: 2 per weight of every matrix product (the
  mixers, the MLPs and the tied output head; the embedding is a
  gather); in each Mamba-2 layer the depthwise convolution and, per
  head, 5 * d_state * head_dim for the recurrence (decay, outer-product
  update and add, readout); in each attention layer 4 * H * head_dim
  per key it attends to (scores and the weighted sum);
- a serving tick's bytes: every weight once; each live slot's recurrent
  state (float32) and convolution window in every Mamba-2 layer, read
  and written once however many tokens the slot feeds; and in every
  attention layer each live slot's resident keys and values read once
  and the tick's new ones written.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import numpy as np

from chipbench.costs.dense_gqa import DTYPE_BYTES
from chipbench.reference.granite_hybrid import head_dim, sizes


def _layers(c: Dict[str, Any]) -> Tuple[int, int]:
    """(Mamba-2 layers, attention layers)."""
    t = c["layer_types"]
    return t.count("mamba"), t.count("attention")


def matmul_weights(c: Dict[str, Any]) -> int:
    D, di, N, P, H = sizes(c)
    Hq, K, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    mlp = 3 * D * c["shared_intermediate_size"]
    mamba = D * (2 * di + 2 * N + H) + di * D + mlp
    attn = D * Hq * hd + 2 * D * K * hd + Hq * hd * D + mlp
    n_m, n_a = _layers(c)
    return n_m * mamba + n_a * attn + D * c["vocab_size"]


def weight_bytes(c: Dict[str, Any]) -> int:
    """Bytes of the weights one tick reads (norms, conv and per-head
    vectors included)."""
    D, di, N, P, H = sizes(c)
    b = DTYPE_BYTES[c["torch_dtype"]]
    convC = di + 2 * N
    n_m, n_a = _layers(c)
    small_m = (2 * D + di + (c["mamba_d_conv"] + 1) * convC) * b + 3 * H * 4
    return (b * (matmul_weights(c) + D) + n_m * small_m
            + n_a * 2 * D * b)


def state_bytes_per_slot(c: Dict[str, Any]) -> int:
    """One slot's recurrent state and conv window, over the Mamba-2 layers."""
    D, di, N, P, H = sizes(c)
    conv = (c["mamba_d_conv"] - 1) * (di + 2 * N) * DTYPE_BYTES[c["torch_dtype"]]
    return _layers(c)[0] * (H * N * P * 4 + conv)


def kv_bytes_per_token(c: Dict[str, Any]) -> int:
    """One position's keys and values, over the attention layers."""
    return (2 * _layers(c)[1] * c["num_key_value_heads"] * head_dim(c)
            * DTYPE_BYTES[c["torch_dtype"]])


def token_flops(c: Dict[str, Any], ctx) -> float:
    """Operations of one token that attends to ``ctx`` keys (itself
    included) in each attention layer."""
    D, di, N, P, H = sizes(c)
    n_m, n_a = _layers(c)
    ssd = 5 * H * N * P + 2 * c["mamba_d_conv"] * (di + 2 * N)
    attn = 4 * c["num_attention_heads"] * head_dim(c) * n_a * np.asarray(ctx)
    return 2.0 * matmul_weights(c) + n_m * ssd + attn


def tick(c: Dict[str, Any], pos: Sequence[int], adv: Sequence[int]
         ) -> Tuple[float, float]:
    """(operations, bytes) of one serving tick: slot ``i`` holds
    ``pos[i]`` resident tokens and feeds ``adv[i]`` new ones."""
    flops = 0.0
    kv_read = live = 0
    for p, a in zip(pos, adv):
        if a <= 0:
            continue
        live += 1
        flops += float(np.sum(token_flops(c, p + 1 + np.arange(a))))
        kv_read += int(p)
    new = int(np.sum(np.asarray(adv).clip(0)))
    nbytes = (weight_bytes(c) + 2 * live * state_bytes_per_slot(c)
              + (kv_read + new) * kv_bytes_per_token(c))
    return flops, float(nbytes)
