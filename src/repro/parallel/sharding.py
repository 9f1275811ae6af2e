"""Logical-axis sharding rules (MaxText-style) for params and activations.

Parameters and activations are annotated with *logical* axis names
("embed", "heads_tp", "batch", ...). A :class:`ShardingRules` table maps
logical names to mesh axes; the mapping is what the planner/hillclimb
vary, while model code never changes.

Baseline rules (see DESIGN.md §5):
  batch    -> ("pod", "data")   pure DP across pods, DP within pod
  embed    -> "data"            FSDP: params sharded over the data axis
  *_tp     -> "model"           tensor parallelism
  experts  -> "model"           expert parallelism shares the TP axis
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingRules", "use_rules", "current_rules", "constrain",
           "logical_to_pspec", "param_shardings", "batch_shardings",
           "BASE_RULES"]

MeshAxes = Union[None, str, Tuple[str, ...]]

# logical axis -> mesh axes (None = replicated)
BASE_RULES: Dict[str, MeshAxes] = {
    # activations
    "batch": ("pod", "data"),
    # Sequence parallelism is the BASELINE: GQA kv-head counts (8) don't
    # divide model=16, so head-TP alone would replicate attention across
    # the model axis; sharding seq over "model" keeps the axis busy and
    # cuts activation residency 16x. (Hillclimb revisits per-arch.)
    "seq": "model",
    "act_embed": None,
    "act_heads": "model",
    "act_kv": "model",
    "act_ff": "model",
    "act_vocab": "model",
    "act_experts": "model",
    "moe_cap": None,          # expert-buffer capacity dim (grok: "data")
    "seq_kv": "model",        # KV-cache sequence dim (caches shard here
                              # when kv-head counts can't split the axis)
    # params
    "layer": None,
    "embed": "data",          # FSDP dim
    "vocab_tp": "model",
    "heads_tp": "model",
    "kv_tp": "model",
    "ffn_tp": "model",
    "experts": "model",
    "expert_embed": "data",   # expert weights' d_model dim (FSDP)
    "expert_ffn": None,
    "ssm_inner_tp": "model",
    "ssm_state": None,
    "ssm_heads": None,
    "conv_k": None,
    "norm": None,
    "vit": None,
    "codebooks": None,
}


@dataclass
class ShardingRules:
    rules: Dict[str, MeshAxes] = field(default_factory=lambda: dict(BASE_RULES))
    mesh: Optional[Mesh] = None
    enabled: bool = True

    def updated(self, overrides: Dict[str, MeshAxes]) -> "ShardingRules":
        r = dict(self.rules)
        r.update(overrides)
        return ShardingRules(r, self.mesh, self.enabled)

    def resolve(self, logical: Optional[str]) -> MeshAxes:
        if logical is None:
            return None
        if logical not in self.rules:
            raise KeyError(f"unknown logical axis {logical!r}")
        axes = self.rules[logical]
        if isinstance(axes, tuple) and self.mesh is not None:
            # drop axes absent from the mesh (e.g. no "pod" on single-pod)
            axes = tuple(a for a in axes if a in self.mesh.axis_names)
            return axes if axes else None
        if isinstance(axes, str) and self.mesh is not None \
                and axes not in self.mesh.axis_names:
            return None
        return axes


_ctx = threading.local()


def current_rules() -> Optional[ShardingRules]:
    return getattr(_ctx, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[ShardingRules]):
    prev = getattr(_ctx, "rules", None)
    _ctx.rules = rules
    try:
        yield rules
    finally:
        _ctx.rules = prev


def logical_to_pspec(logical_axes: Sequence[Optional[str]],
                     rules: ShardingRules,
                     shape: Optional[Sequence[int]] = None) -> P:
    """Resolve logical axes to a PartitionSpec.

    When ``shape`` is given, mesh axes whose size does not divide the
    tensor dim are dropped (replicate-fallback): e.g. 8 KV heads cannot
    shard over model=16, so that dim replicates — recorded honestly by
    the roofline's useful-FLOPs ratio rather than hidden.
    """
    spec = []
    used: set = set()
    mesh_sizes = dict(zip(rules.mesh.axis_names, rules.mesh.devices.shape)) \
        if rules.mesh is not None else {}
    for i, ax in enumerate(logical_axes):
        m = rules.resolve(ax)
        # a mesh axis may shard at most one tensor dim
        if m is None:
            spec.append(None)
            continue
        ms = (m,) if isinstance(m, str) else tuple(m)
        ms = tuple(a for a in ms if a not in used)
        if shape is not None and ms:
            dim = shape[i]
            # drop axes from the right until the product divides the dim
            while ms:
                prod = 1
                for a in ms:
                    prod *= mesh_sizes.get(a, 1)
                if prod and dim % prod == 0:
                    break
                ms = ms[:-1]
        used.update(ms)
        if not ms:
            spec.append(None)
        elif len(ms) == 1:
            spec.append(ms[0])
        else:
            spec.append(ms)
    return P(*spec)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Apply with_sharding_constraint per the active rules (no-op outside)."""
    rules = current_rules()
    if rules is None or not rules.enabled or rules.mesh is None:
        return x
    if x.ndim != len(logical_axes):
        raise ValueError(f"constrain: rank {x.ndim} vs axes {logical_axes}")
    pspec = logical_to_pspec(logical_axes, rules, x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, pspec))


def param_shardings(spec_tree: Any, rules: ShardingRules,
                    abstract_tree: Any = None) -> Any:
    """Map a Mode.SPEC pytree (leaves = logical-axis tuples) to NamedShardings.

    ``abstract_tree`` (matching ShapeDtypeStructs) enables the
    divisibility fallback per parameter.
    """
    is_axes = lambda x: isinstance(x, tuple)
    if abstract_tree is None:
        return jax.tree.map(
            lambda axes: NamedSharding(rules.mesh, logical_to_pspec(axes, rules)),
            spec_tree, is_leaf=is_axes)
    flat_abs, treedef = jax.tree.flatten(abstract_tree)
    flat_spec = treedef.flatten_up_to(spec_tree)
    out = [NamedSharding(rules.mesh, logical_to_pspec(axes, rules, a.shape))
           for a, axes in zip(flat_abs, flat_spec)]
    return treedef.unflatten(out)


def batch_shardings(batch: Any, rules: ShardingRules) -> Any:
    """NamedShardings for a batch pytree: dim 0 is "batch", the rest
    replicate. Leaves need only a ``shape`` (arrays or ShapeDtypeStructs)."""
    def shard_one(x):
        axes = ["batch"] + [None] * (len(x.shape) - 1)
        return NamedSharding(rules.mesh, logical_to_pspec(axes, rules, x.shape))

    return jax.tree.map(shard_one, batch)
