"""Seeded random weights, made by the benchmark for the program and for
the reference alike.

A family's reference module gives the weight layout in the program's
naming (``layout(config)``): each leaf's shape, dtype, initialiser and,
for the layer stack, the number of layers. ``make`` builds the whole
tree on the device in one jitted call from the seed, in the dtype it is
served in. ``layer_leaf`` makes one layer's slice of a stacked leaf,
bit for bit the same, so the reference can make its weights layer by
layer without holding the program's.
"""

from __future__ import annotations

import functools
import hashlib
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Leaf:
    shape: Tuple[int, ...]          # of one layer, for a stacked leaf
    dtype: str
    init: Tuple                     # ("normal", std[, mean]) | ("zeros",)
                                    # | ("log_linspace", lo, hi)
                                    # | ("dt_bias", dt_lo, dt_hi)
    layers: Optional[int] = None    # stacked over this many layers


Layout = Dict[str, Leaf]            # "layers/attn/wq" -> Leaf


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words (a key made from a
    Python int keeps only its low 32 bits)."""
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def _root_key(words: jax.Array) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def _path_key(root: jax.Array, path: str) -> jax.Array:
    h = int.from_bytes(hashlib.blake2b(path.encode(), digest_size=4).digest(),
                       "big") & 0x7FFFFFFF
    return jax.random.fold_in(root, h)


# A near-normal draw from integers alone: the sum of the four bytes of a
# random uint32 (Irwin-Hall, mean 510, standard deviation 147.80), times
# one step. Threefry and the sum are exact, and one multiplication rounds
# the same in every program, so any program that makes a leaf gets the
# same bits (a float normal from erf_inv may differ in its last bit
# between two compiled programs).
_IH_MEAN = 510
_IH_STD = float(np.sqrt(4 * (256 ** 2 - 1) / 12))


def _draw(key: jax.Array, leaf: Leaf) -> jax.Array:
    kind = leaf.init[0]
    dt = jnp.dtype(leaf.dtype)
    if kind == "normal":
        step = leaf.init[1] / _IH_STD
        offset = round((leaf.init[2] if len(leaf.init) > 2 else 0.0) / step)
        bits = jax.random.bits(key, leaf.shape, jnp.uint32)
        z = sum(((bits >> s) & 0xFF).astype(jnp.int32) for s in (0, 8, 16, 24))
        return ((z + (offset - _IH_MEAN)).astype(jnp.float32)
                * jnp.float32(step)).astype(dt)
    if kind == "zeros":
        return jnp.zeros(leaf.shape, dt)
    n = leaf.shape[0]
    if kind == "log_linspace":
        v = np.log(np.linspace(leaf.init[1], leaf.init[2], n))
    elif kind == "dt_bias":
        # inverse softplus of step sizes spaced evenly in log between
        # dt_lo and dt_hi, one per head (Mamba-2's initialisation range)
        step = np.exp(np.linspace(np.log(leaf.init[1]), np.log(leaf.init[2]), n))
        v = step + np.log(-np.expm1(-step))
    else:
        raise ValueError(f"unknown initialiser {kind!r}")
    return jnp.asarray(v.astype(np.float32)).astype(dt)


def _make_leaf(root: jax.Array, path: str, leaf: Leaf) -> jax.Array:
    key = _path_key(root, path)
    if leaf.layers is None:
        return _draw(key, leaf)
    return jax.vmap(lambda i: _draw(jax.random.fold_in(key, i), leaf))(
        jnp.arange(leaf.layers))


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, value in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = value
    return out


@functools.lru_cache(maxsize=8)
def _builder(items: Tuple[Tuple[str, Leaf], ...]):
    def build(words):
        root = _root_key(words)
        return _nest({p: _make_leaf(root, p, leaf) for p, leaf in items})
    return jax.jit(build)


def make(layout: Layout, seed: int) -> Dict[str, Any]:
    """The whole weight tree, nested by path, made on the device in one
    jitted call (one program for every seed)."""
    return _builder(tuple(layout.items()))(jnp.asarray(seed_words(seed)))


def layer_leaf(layout: Layout, path: str, words: jax.Array,
               layer: jax.Array) -> jax.Array:
    """Layer ``layer`` of a stacked leaf (or the whole of an unstacked
    one), equal to the same slice of :func:`make`'s leaf. Traceable."""
    leaf = layout[path]
    key = _path_key(_root_key(words), path)
    if leaf.layers is None:
        return _draw(key, leaf)
    return _draw(jax.random.fold_in(key, layer), leaf)


def check_layout(layout: Layout, abstract: Dict[str, Any]) -> None:
    """Raise where the program's parameter tree (shapes and dtypes, as
    ``jax.eval_shape`` gives them) differs from the layout."""
    flat = {"/".join(str(getattr(k, "key", k)) for k in kp): v
            for kp, v in jax.tree_util.tree_flatten_with_path(abstract)[0]}
    want = {p: ((leaf.layers,) + leaf.shape if leaf.layers else leaf.shape,
                jnp.dtype(leaf.dtype)) for p, leaf in layout.items()}
    got = {p: (tuple(v.shape), jnp.dtype(v.dtype)) for p, v in flat.items()}
    if want != got:
        diff = sorted(map(str, set(want.items()) ^ set(got.items())))
        raise ValueError(f"weight layout differs from the program's: {diff}")
