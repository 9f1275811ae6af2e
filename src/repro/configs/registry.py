"""Architecture registry: --arch <id> -> ModelConfig (+ reduced smoke configs)."""

from __future__ import annotations

import dataclasses
from typing import Dict

from ..models.config import ModelConfig
from . import (arctic_480b, granite_4_0_h_micro, grok_1_314b,
               h2o_danube_1_8b, hymba_1_5b, internvl2_1b, mamba2_780m,
               musicgen_medium, phi3_medium_14b, qwen1_5_110b, yi_34b)

ARCHS: Dict[str, ModelConfig] = {
    "arctic-480b": arctic_480b.CONFIG,
    "grok-1-314b": grok_1_314b.CONFIG,
    "yi-34b": yi_34b.CONFIG,
    "phi3-medium-14b": phi3_medium_14b.CONFIG,
    "h2o-danube-1.8b": h2o_danube_1_8b.CONFIG,
    "qwen1.5-110b": qwen1_5_110b.CONFIG,
    "mamba2-780m": mamba2_780m.CONFIG,
    "hymba-1.5b": hymba_1_5b.CONFIG,
    "internvl2-1b": internvl2_1b.CONFIG,
    "musicgen-medium": musicgen_medium.CONFIG,
    "granite-4.0-h-micro": granite_4_0_h_micro.CONFIG,
}


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


def smoke_config(name: str) -> ModelConfig:
    """Reduced same-family config: small layers/width/experts/vocab.

    Runs a real forward/train step on CPU (assignment: smoke tests use
    reduced configs; full configs are exercised only via the dry run).
    """
    cfg = get_config(name)
    kw = dict(
        num_layers=2,
        d_model=64,
        vocab_size=128,
        rope_theta=10_000.0,
    )
    if cfg.family != "ssm":
        heads = 4
        kv = max(1, min(cfg.num_kv_heads, 2))
        kw.update(num_heads=heads, num_kv_heads=kv, head_dim=16,
                  d_ff=0 if cfg.d_ff == 0 else 128)
    if cfg.num_experts > 0:
        kw.update(num_experts=4, top_k=2, moe_d_ff=96,
                  d_ff=128 if cfg.dense_residual else 128)
    if cfg.ssm_state > 0:
        kw.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=16, ssm_expand=2)
    if cfg.sliding_window > 0:
        kw.update(sliding_window=16)
    if cfg.frontend == "vision":
        kw.update(vit_dim=32, num_patches=8)
    if cfg.frontend == "audio":
        kw.update(num_codebooks=2, vocab_size=64)
    if cfg.layer_types:
        # every kind of the pattern, attention neither first nor last
        kw.update(num_layers=4,
                  layer_types=("mamba", "mamba", "attention", "mamba"))
    return dataclasses.replace(cfg, **kw)
