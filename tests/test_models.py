"""Per-arch smoke tests (assignment requirement): reduced config, one
forward/train step on CPU, asserting output shapes + no NaNs."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS, get_config, smoke_config
from repro.models import lm


def make_batch(cfg, key, B=2, S=32):
    if cfg.frontend == "audio":
        tokens = jax.random.randint(key, (B, S, cfg.num_codebooks), 0,
                                    cfg.vocab_size)
    else:
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jax.random.normal(
            key, (B, cfg.num_patches, cfg.vit_dim), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
class TestSmokePerArch:
    def test_forward_shapes_and_finite(self, arch):
        cfg = smoke_config(arch)
        key = jax.random.PRNGKey(0)
        params = lm.init_params(cfg, key)
        B, S = 2, 32
        batch = make_batch(cfg, key, B, S)
        logits, aux = lm.forward(cfg, params, batch, remat="none")
        S_out = S + (cfg.num_patches if cfg.frontend == "vision" else 0)
        if cfg.frontend == "audio":
            assert logits.shape == (B, S_out, cfg.num_codebooks, cfg.vocab_size)
        else:
            assert logits.shape == (B, S_out, cfg.vocab_size)
        assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    def test_train_step_decreases_loss(self, arch):
        from repro.train.optimizer import AdamW
        from repro.train.schedule import constant_schedule
        from repro.train.train_step import (StepConfig, init_train_state,
                                            make_train_step)
        cfg = smoke_config(arch)
        key = jax.random.PRNGKey(1)
        state = init_train_state(cfg, AdamW(constant_schedule(5e-3)), key)
        step = jax.jit(make_train_step(
            cfg, AdamW(constant_schedule(5e-3)), StepConfig(remat="dots")))
        batch = make_batch(cfg, key)
        losses = []
        for _ in range(4):
            state, metrics = step(state, batch)
            assert bool(jnp.isfinite(metrics["loss"]))
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0], losses


class TestFullConfigs:
    @pytest.mark.parametrize("arch", sorted(ARCHS))
    def test_full_config_matches_assignment(self, arch):
        cfg = get_config(arch)
        spec = {
            "arctic-480b": (35, 7168, 56, 8, 32000),
            "grok-1-314b": (64, 6144, 48, 8, 131072),
            "yi-34b": (60, 7168, 56, 8, 64000),
            "phi3-medium-14b": (40, 5120, 40, 10, 100352),
            "h2o-danube-1.8b": (24, 2560, 32, 8, 32000),
            "qwen1.5-110b": (80, 8192, 64, 8, 152064),
            "mamba2-780m": (48, 1536, 0, 0, 50280),
            "hymba-1.5b": (32, 1600, 25, 5, 32001),
            "internvl2-1b": (24, 896, 14, 2, 151655),
            "musicgen-medium": (48, 1536, 24, 24, 2048),
            "granite-4.0-h-micro": (40, 2048, 32, 8, 100352),
        }[arch]
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads,
                cfg.num_kv_heads, cfg.vocab_size) == spec

    def test_param_counts_in_expected_range(self):
        # sanity of the roofline's 6·N·D inputs (order of magnitude)
        expect = {"arctic-480b": (4.0e11, 5.6e11),
                  "grok-1-314b": (2.8e11, 3.6e11),
                  "yi-34b": (3.0e10, 3.9e10),
                  "phi3-medium-14b": (1.2e10, 1.6e10),
                  "h2o-danube-1.8b": (1.5e9, 2.2e9),
                  "qwen1.5-110b": (1.0e11, 1.25e11),
                  "mamba2-780m": (6.5e8, 9.5e8),
                  "hymba-1.5b": (1.1e9, 2.2e9),
                  "internvl2-1b": (6e8, 1.3e9),
                  "musicgen-medium": (1.3e9, 2.4e9),
                  "granite-4.0-h-micro": (2.9e9, 3.5e9)}
        for arch, (lo, hi) in expect.items():
            n = get_config(arch).param_count()
            assert lo <= n <= hi, (arch, n)

    def test_moe_active_params_smaller(self):
        for arch in ("arctic-480b", "grok-1-314b"):
            cfg = get_config(arch)
            assert cfg.active_param_count() < 0.45 * cfg.param_count()

    def test_subquadratic_flags(self):
        assert get_config("mamba2-780m").subquadratic
        assert get_config("hymba-1.5b").subquadratic
        assert get_config("h2o-danube-1.8b").subquadratic
        assert not get_config("yi-34b").subquadratic
        assert not get_config("musicgen-medium").subquadratic
