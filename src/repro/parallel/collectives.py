"""Distributed-optimization collectives: compressed cross-pod grad sync.

The pod axis rides DCN (25 GB/s per host vs 2x50 GB/s ICI), so the
cross-pod gradient reduction is the bandwidth-starved collective at
multi-pod scale. We quantize gradients to int8 with per-tensor scales and
error feedback (1-bit-Adam-style residual correction) before the pod
all-reduce — 2x wire-byte reduction vs bf16, 4x vs f32, with the
compression error re-injected next step so convergence is preserved.

Implementation: ``jax.shard_map`` with ``axis_names={"pod"}`` makes only
the pod axis manual (data/model stay under the automatic partitioner),
so the quantize -> psum(int) -> dequantize pipeline is explicit in the
HLO — the dry-run's collective parser sees int8 all-reduces on the pod
axis, which is exactly how the roofline credits the 2x.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["compressed_pod_mean", "make_compressed_grad_sync", "zeros_like_tree"]


def zeros_like_tree(tree: Any, dtype=jnp.bfloat16) -> Any:
    return jax.tree.map(lambda a: jnp.zeros(a.shape, dtype), tree)


def _quantize_psum_dequantize(g: jax.Array, err: jax.Array, axis: str,
                              npods: int) -> Tuple[jax.Array, jax.Array]:
    """One leaf: error-feedback int8 pod-mean. Runs inside shard_map."""
    g32 = g.astype(jnp.float32) + err.astype(jnp.float32)
    # shared scale so dequantization is exact across pods; the grid is
    # pre-divided by npods so the SUM of quantized values still fits int8
    # and the wire stays at 1 byte/element (vs 2 for bf16, 4 for f32)
    amax = jax.lax.pmax(jnp.max(jnp.abs(g32)), axis)
    scale = jnp.maximum(amax, 1e-20) * npods / 127.0
    q = jnp.clip(jnp.round(g32 / scale), -(127 // npods),
                 127 // npods).astype(jnp.int8)
    deq_local = q.astype(jnp.float32) * scale
    new_err = (g32 - deq_local).astype(err.dtype)       # feedback residual
    summed = jax.lax.psum(q, axis)                      # int8 on the wire
    mean = summed.astype(jnp.float32) * scale / npods
    return mean.astype(g.dtype), new_err


def compressed_pod_mean(grads: Any, err: Any, axis: str = "pod",
                        npods: int = 2) -> Tuple[Any, Any]:
    """Tree-wise error-feedback compressed mean over ``axis`` (manual ctx)."""
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(err)
    outs = [_quantize_psum_dequantize(g, e, axis, npods)
            for g, e in zip(flat_g, flat_e)]
    return (treedef.unflatten([o[0] for o in outs]),
            treedef.unflatten([o[1] for o in outs]))


def make_compressed_grad_sync(mesh: Mesh, grad_fn, axis: str = "pod"):
    """Wrap a per-pod grad_fn with compressed cross-pod averaging.

    grad_fn(params, batch) -> (grads, metrics); the wrapper runs it under
    shard_map with the pod axis manual (batch sharded over pod), then
    compresses the reduction. Returns sync(params, batch, err) ->
    (grads, new_err, metrics).
    """
    npods = dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)

    def per_pod(params, batch, err):
        grads, metrics = grad_fn(params, batch)
        grads, new_err = compressed_pod_mean(grads, err, axis, npods)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axis), metrics)
        return grads, new_err, metrics

    return jax.shard_map(per_pod, mesh=mesh, in_specs=(P(), P(axis), P()),
                         out_specs=(P(), P(), P()), axis_names={axis},
                         check_vma=False)
