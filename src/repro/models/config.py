"""Unified model configuration covering all assigned architecture families.

A model is a stack of layers, each of one *kind*; the kind says which
mixers the layer holds (``LAYER_MIXERS``): attention, the Mamba-2 SSD
mixer, or both side by side (hymba). Every layer outside the ``ssm``
family also holds a norm and an MLP (or MoE) after its mixer.
``layer_types`` spells the kind of each layer out (granite-4.0-h: a few
attention layers among Mamba-2 ones); left empty, every layer is of the
family's one kind.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax.numpy as jnp

__all__ = ["ModelConfig", "LAYER_MIXERS"]

# The mixers a layer of each kind holds, in the order they run.
LAYER_MIXERS = {
    "attention": ("attn",),
    "mamba": ("ssd",),
    "parallel": ("attn", "ssd"),     # hymba: both on one input, averaged
}


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"  # dense | moe | ssm | hybrid | vlm | audio
    # one LAYER_MIXERS kind per layer; () -> the family's kind everywhere
    layer_types: Tuple[str, ...] = ()

    # backbone
    num_layers: int = 2
    d_model: int = 128
    num_heads: int = 2
    num_kv_heads: int = 2
    head_dim: int = 0            # 0 -> d_model // num_heads
    d_ff: int = 256              # dense-path FFN hidden size (0 for pure SSM)
    vocab_size: int = 256
    act: str = "swiglu"          # swiglu | geglu | gelu
    norm_eps: float = 1e-6
    rope_theta: float = 10_000.0
    position_embedding: str = "rope"   # rope | nope (no positional signal)
    qkv_bias: bool = False       # qwen1.5 style
    sliding_window: int = 0      # 0 = full attention
    tie_embeddings: bool = False

    # MoE
    num_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0            # expert hidden size (d_ff used if 0)
    dense_residual: bool = False  # arctic: dense MLP in parallel with MoE
    capacity_factor: float = 1.25
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2

    # SSM (mamba-2 SSD)
    ssm_state: int = 0           # N (state size per head); 0 = no ssm
    ssm_expand: int = 2
    ssm_head_dim: int = 64       # P
    ssm_chunk: int = 256
    conv_kernel: int = 4

    # granite's muP multipliers; the defaults leave a model as it is
    embedding_multiplier: float = 1.0  # scales the token embeddings
    residual_multiplier: float = 1.0   # scales both residual branches
    attention_multiplier: float = 0.0  # softmax scale; 0 -> 1/sqrt(head_dim)
    logits_scaling: float = 1.0        # the logits are divided by it

    # modality frontends (STUBS: precomputed embeddings per assignment)
    frontend: str = "none"       # none | vision | audio
    vit_dim: int = 1024          # internvl: InternViT-300M width
    num_patches: int = 256
    num_codebooks: int = 4       # musicgen: EnCodec RVQ streams

    # numerics
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.layer_types:
            if len(self.layer_types) != self.num_layers:
                raise ValueError(f"{self.name}: {len(self.layer_types)} "
                                 f"layer_types for {self.num_layers} layers")
            unknown = set(self.layer_types) - set(LAYER_MIXERS)
            if unknown:
                raise ValueError(f"{self.name}: unknown layer kinds {unknown}")
        for mixer in ("attn", "ssd"):
            owners = [k for k in self.layer_kinds if mixer in LAYER_MIXERS[k]]
            if len(owners) > 1:
                # each cache is indexed by a layer's rank within its kind
                raise ValueError(f"{self.name}: kinds {owners} would share "
                                 f"the {mixer} cache")

    # ---------------------------------------------------------- layer pattern
    @property
    def layer_pattern(self) -> Tuple[str, ...]:
        """The kind of each layer, first to last."""
        if self.layer_types:
            return self.layer_types
        kind = {"ssm": "mamba", "hybrid": "parallel"}.get(self.family,
                                                           "attention")
        return (kind,) * self.num_layers

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The distinct kinds, in the order they first appear."""
        return tuple(dict.fromkeys(self.layer_pattern))

    def kind_count(self, kind: str) -> int:
        return self.layer_pattern.count(kind)

    @property
    def layer_runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """(kind, rank of the run's first layer within its kind, length)
        of each run of consecutive layers of one kind."""
        runs = []
        seen: dict = {}
        for kind in self.layer_pattern:
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, seen.get(kind, 0), 1])
            seen[kind] = seen.get(kind, 0) + 1
        return tuple(tuple(r) for r in runs)

    def mixer_layers(self, mixer: str) -> int:
        """Layers holding ``mixer`` ("attn" or "ssd"): the depth of its
        cache."""
        return sum(mixer in LAYER_MIXERS[k] for k in self.layer_pattern)

    @property
    def has_mlp(self) -> bool:
        """Every layer outside the attention-free ``ssm`` stacks."""
        return self.family != "ssm"

    @property
    def attention_scale(self) -> float:
        return self.attention_multiplier or 1.0 / math.sqrt(
            self.resolved_head_dim)

    # long-context capability flag (decides long_500k applicability)
    @property
    def subquadratic(self) -> bool:
        return self.sliding_window > 0 or self.mixer_layers("attn") == 0

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_num_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    def compute_jnp_dtype(self):
        return jnp.dtype(self.compute_dtype)

    def param_jnp_dtype(self):
        return jnp.dtype(self.param_dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---------------------------------------------------------------- counts
    def _glu(self) -> bool:
        return self.act in ("swiglu", "geglu")

    def _ffn_params(self, hidden: int) -> int:
        mult = 3 if self._glu() else 2
        return mult * self.d_model * hidden

    def _attn_params(self) -> int:
        hd = self.resolved_head_dim
        q = self.d_model * self.num_heads * hd
        kv = 2 * self.d_model * self.num_kv_heads * hd
        o = self.num_heads * hd * self.d_model
        bias = (self.num_heads + 2 * self.num_kv_heads) * hd if self.qkv_bias else 0
        return q + kv + o + bias

    def _ssm_params(self) -> int:
        di, n, h = self.ssm_d_inner, self.ssm_state, self.ssm_num_heads
        in_proj = self.d_model * (2 * di + 2 * n + h)   # x, z, B, C, dt
        conv = self.conv_kernel * (di + 2 * n)
        out = di * self.d_model
        extra = 2 * h + di                              # A, dt_bias, D... approx
        return in_proj + conv + out + extra

    def layer_param_count(self, kind: str, active_only: bool = False) -> int:
        """Parameters in one decoder layer of ``kind``."""
        mixers = LAYER_MIXERS[kind]
        n = 2 * self.d_model  # the two norms, for honesty
        if "attn" in mixers:
            n += self._attn_params()
        if "ssd" in mixers:
            n += self._ssm_params()
        if not self.has_mlp:
            return n
        if self.num_experts > 0:
            e = self.top_k if active_only else self.num_experts
            n += e * self._ffn_params(self.expert_d_ff)
            n += self.d_model * self.num_experts  # router
            if self.dense_residual:
                n += self._ffn_params(self.d_ff)
        else:
            n += self._ffn_params(self.d_ff)
        return n

    def param_count(self, active_only: bool = False) -> int:
        emb = self.vocab_size * self.d_model
        if self.frontend == "audio":
            emb *= self.num_codebooks  # per-codebook embed + heads
        head = 0 if self.tie_embeddings else self.vocab_size * self.d_model
        if self.frontend == "audio":
            head = self.num_codebooks * self.vocab_size * self.d_model
        fe = 0
        if self.frontend == "vision":
            fe = self.vit_dim * self.d_model + 2 * self.d_model * self.d_model
        layers = sum(self.kind_count(k) * self.layer_param_count(k, active_only)
                     for k in self.layer_kinds)
        return emb + head + fe + layers

    def active_param_count(self) -> int:
        return self.param_count(active_only=True)
