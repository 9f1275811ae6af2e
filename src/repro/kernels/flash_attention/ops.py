"""Public wrapper for the flash attention kernel.

Off the TPU the caller must pass ``interpret=True`` to run the Pallas
body in the interpreter; without it the call raises (see
``repro.kernels.check_backend``). The backward pass recomputes via the
jnp oracle under custom_vjp — the forward kernel is the serving/prefill
hot path; training backward reuses XLA's fused attention gradient.
"""

from __future__ import annotations

import functools

import jax

from .. import check_backend
from .flash_attention import flash_attention_fwd
from .ref import attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    check_backend(interpret)
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


def _fwd(q, k, v, causal, window, block_q, block_k, interpret):
    out = flash_attention(q, k, v, causal, window, block_q, block_k, interpret)
    return out, (q, k, v)


def _bwd(causal, window, block_q, block_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=causal,
                                         window=window), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
