"""Operations and bytes of a Mamba-2 (SSD) stack, from its shapes alone.

Counted as the least work the math needs, whatever implements it:

- a token's operations: 2 per weight of every matrix product (the layer
  projections and the tied output head; the embedding is a gather), the
  depthwise convolution, and per head 5 * d_state * headdim for the
  recurrence (decay, outer-product update and add, readout);
- a serving tick's bytes: every weight once, and each live slot's
  recurrent state (float32) and convolution window read and written
  once, however many tokens the slot feeds.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import numpy as np

from chipbench.costs.dense_gqa import DTYPE_BYTES
from chipbench.reference.mamba2 import vocab_rows


def _sizes(c: Dict[str, Any]):
    D = c["d_model"]
    di = c["expand"] * D
    N, P = c["d_state"], c["headdim"]
    return D, di, N, P, di // P


def matmul_weights(c: Dict[str, Any]) -> int:
    D, di, N, P, H = _sizes(c)
    layer = D * (2 * di + 2 * N + H) + di * D
    return c["n_layer"] * layer + D * vocab_rows(c)


def weight_bytes(c: Dict[str, Any]) -> int:
    """Bytes of the weights one tick reads (norms, conv and per-head
    vectors included)."""
    D, di, N, P, H = _sizes(c)
    convC = di + 2 * N
    small = (D + di + (c["d_conv"] + 1) * convC) * DTYPE_BYTES[c["torch_dtype"]] \
        + 3 * H * 4
    return (DTYPE_BYTES[c["torch_dtype"]] * (matmul_weights(c) + D)
            + c["n_layer"] * small)


def state_bytes_per_slot(c: Dict[str, Any]) -> int:
    D, di, N, P, H = _sizes(c)
    conv = (c["d_conv"] - 1) * (di + 2 * N) * DTYPE_BYTES[c["torch_dtype"]]
    return c["n_layer"] * (H * N * P * 4 + conv)


def token_flops(c: Dict[str, Any], ctx=None) -> float:
    """Operations of one token; the context length does not matter."""
    del ctx
    D, di, N, P, H = _sizes(c)
    per_layer = 5 * H * N * P + 2 * c["d_conv"] * (di + 2 * N)
    return 2.0 * matmul_weights(c) + c["n_layer"] * per_layer


def tick(c: Dict[str, Any], pos: Sequence[int], adv: Sequence[int]
         ) -> Tuple[float, float]:
    """(operations, bytes) of one serving tick: slot ``i`` feeds
    ``adv[i]`` tokens (``pos`` does not change the cost)."""
    del pos
    adv = np.asarray(adv).clip(0)
    live = int(np.count_nonzero(adv))
    flops = float(adv.sum()) * token_flops(c)
    nbytes = weight_bytes(c) + 2 * live * state_bytes_per_slot(c)
    return flops, float(nbytes)
