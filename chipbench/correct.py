"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, a sample of
the requests that were served tokens, drawn from the seed and holding
the one with the longest prompt and output, is run through the family's
plain float32 reference: each prompt with its served tokens, in one
batch padded to ``max_len``. For every served
token the reference gives its best logit and the served token's logit;
the number compared is the widest gap between the two. Greedy decoding
in the program's precision keeps that gap near rounding; a wrong cache,
mask, state or token opens it to the logits' spread.

The control (``control=True``) puts the reference in the program's
place one precision step down, float8 (e4m3) weights with a scale per
tensor, and reads, at the same positions, the reference gap of the token
it would put first.
"""

from __future__ import annotations

import functools
import importlib
import json
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

FP8_MAX = 448.0     # largest finite float8_e4m3fn


@dataclass
class Served:
    prompt: Sequence[int]
    tokens: Sequence[int]          # the served (greedy) output tokens


def sample(served: List[Served], k: int, seed: int) -> List[Served]:
    """The longest request and ``k - 1`` others drawn from the seed."""
    if not served:
        return []
    order = sorted(range(len(served)),
                   key=lambda i: -(len(served[i].prompt) + len(served[i].tokens)))
    rest = order[1:]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [served[order[0]]] + [served[rest[i]] for i in sorted(pick)]


def _fp8(w: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / FP8_MAX
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _batch(reqs: List[Served], rows: int, length: int):
    tokens = np.zeros((rows, length), np.int32)
    targets = np.zeros((rows, length), np.int32)
    valid = np.zeros((rows, length), bool)
    for i, r in enumerate(reqs):
        seq = list(r.prompt) + list(r.tokens[:-1])
        if len(seq) > length:
            raise ValueError(f"request of {len(seq)} tokens past {length}")
        tokens[i, :len(seq)] = seq
        p = len(r.prompt)
        targets[i, p - 1:p - 1 + len(r.tokens)] = r.tokens
        valid[i, p - 1:p - 1 + len(r.tokens)] = True
    return tokens, targets, valid


def gap_fn(config: Dict[str, Any], ref, control: bool):
    """Jitted (seed words, tokens, targets) -> per position: the gap of
    the target, and with ``control`` the gap of the control's top token."""
    return _gap_fn(json.dumps(config, sort_keys=True), ref.__name__, control)


@functools.lru_cache(maxsize=8)
def _gap_fn(config_json: str, ref_name: str, control: bool):
    config = json.loads(config_json)
    ref = importlib.import_module(ref_name)
    lay = ref.layout(config)

    def run(words, tokens, targets):
        def get(path, layer):
            return weights.layer_leaf(lay, path, words, layer).astype(jnp.float32)

        def get_low(path, layer):
            w = get(path, layer)
            return _fp8(w) if w.ndim >= 2 else w

        with jax.default_matmul_precision("highest"):
            # the control's logits first, reduced to their argmax, so only
            # one (rows, max_len, vocab) block is alive at a time
            top = (ref.logits(config, get_low, tokens).argmax(axis=-1)
                   if control else None)
            lg = ref.logits(config, get, tokens)
            best = lg.max(axis=-1)

            def gap(t):
                return best - jnp.take_along_axis(lg, t[..., None], axis=-1)[..., 0]

            out = {"gap": gap(targets)}
            if control:
                out["control_gap"] = gap(top)
        return out

    return jax.jit(run)


def compare(config: Dict[str, Any], ref, reqs: List[Served], rows: int,
            length: int, seed: int, control: bool = False
            ) -> Dict[str, Optional[float]]:
    """Widest gap over every served token of ``reqs`` (and the control's)."""
    tokens, targets, valid = _batch(reqs, rows, length)
    fn = gap_fn(config, ref, control)
    out = fn(jnp.asarray(weights.seed_words(seed)), jnp.asarray(tokens),
             jnp.asarray(targets))
    res = {"tokens": int(valid.sum())}
    for k, v in out.items():
        v = np.asarray(v)[valid]
        res[k] = float(v.max()) if v.size else None
    return res
