"""Operations and bytes of a dense GQA decoder, from its shapes alone.

Counted as the least work the math needs, whatever implements it:

- a token's operations: 2 per weight of every matrix product (the layers
  and the output head; the embedding is a gather) and 4 * H * head_dim
  per key it attends to (scores and the weighted sum);
- a serving tick's bytes: every weight once, each live slot's resident
  keys and values read once, and the tick's new keys and values written.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import numpy as np

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _layer_matmul_weights(c: Dict[str, Any]) -> int:
    D, F = c["hidden_size"], c["intermediate_size"]
    H, K, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    return D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F


def matmul_weights(c: Dict[str, Any]) -> int:
    return (c["num_hidden_layers"] * _layer_matmul_weights(c)
            + c["hidden_size"] * c["vocab_size"])


def weight_bytes(c: Dict[str, Any]) -> int:
    """Bytes of the weights one tick reads: matrices, head and norms."""
    D, L = c["hidden_size"], c["num_hidden_layers"]
    return DTYPE_BYTES[c["torch_dtype"]] * (matmul_weights(c) + (2 * L + 1) * D)


def kv_bytes_per_token(c: Dict[str, Any]) -> int:
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * DTYPE_BYTES[c["torch_dtype"]])


def _seen(c: Dict[str, Any], ctx):
    w = c["sliding_window"]
    return np.minimum(ctx, w) if w else ctx


def token_flops(c: Dict[str, Any], ctx) -> float:
    """Operations of one token that attends to ``ctx`` keys (itself
    included)."""
    attn = (4 * c["num_attention_heads"] * c["head_dim"]
            * c["num_hidden_layers"] * _seen(c, ctx))
    return 2.0 * matmul_weights(c) + attn


def tick(c: Dict[str, Any], pos: Sequence[int], adv: Sequence[int]
         ) -> Tuple[float, float]:
    """(operations, bytes) of one serving tick: slot ``i`` holds
    ``pos[i]`` resident tokens and feeds ``adv[i]`` new ones."""
    flops = 0.0
    kv_read = 0
    for p, a in zip(pos, adv):
        if a <= 0:
            continue
        ctx = p + 1 + np.arange(a)
        flops += float(np.sum(token_flops(c, ctx)))
        kv_read += int(_seen(c, p))
    new = int(np.sum(np.asarray(adv).clip(0)))
    nbytes = weight_bytes(c) + (kv_read + new) * kv_bytes_per_token(c)
    return flops, float(nbytes)


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward (3x forward) per token of a causal sequence
    of ``seq_len``, recomputation not counted."""
    ctx = np.arange(1, seq_len + 1)
    return 3.0 * float(np.mean(token_flops(c, ctx)))
