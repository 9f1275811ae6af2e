"""Compile the main path for a described TPU v5e, at real widths.

Nothing runs: each program is lowered and compiled by the TPU compiler
for a v5e chip that is described, not attached (``v5e:2x2``). That
catches what interpret mode and the CPU backend cannot: Mosaic lowering
failures inside the Pallas kernels, and programs that do not fit a
chip's memory. The topology is described inside a module fixture (never
at import), so every xdist worker collects the same tests and only the
worker that runs this file loads the TPU library.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

# The TPU compiler's own per-chip limit for v5e (16 GB of HBM, part of
# it reserved): it refuses a program with "Used ...G of 15.75G hbm".
V5E_HBM_BYTES = 15.75 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile ``fn`` (jitted here unless it already is) for the args'
    shardings; assert the program fits one v5e chip."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used <= V5E_HBM_BYTES, f"{used / 2**30:.2f} GiB > 15.75 GiB"
    return compiled


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_h2o_danube(one_chip):
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_fwd
    B, S, H, K, hd = 1, 2048, 32, 8, 80
    q = _spec((B, S, H, hd), jnp.bfloat16, one_chip)
    kv = _spec((B, S, K, hd), jnp.bfloat16, one_chip)
    fn = functools.partial(flash_attention_fwd, causal=True, window=4096)
    assert _has_kernel(_compile(fn, q, kv, kv))


def test_rmsnorm_compiles_h2o_danube(one_chip):
    from repro.kernels.rmsnorm.rmsnorm import rmsnorm_fwd
    x = _spec((4096, 2560), jnp.bfloat16, one_chip)
    scale = _spec((2560,), jnp.float32, one_chip)
    assert _has_kernel(_compile(rmsnorm_fwd, x, scale))


def test_ssd_chunk_compiles_mamba2(one_chip):
    from repro.kernels.ssd_scan.ssd_scan import ssd_chunk_fwd
    b, nc, Q, N, H, P = 1, 4, 256, 128, 48, 64
    cb = _spec((b, nc, Q, N), jnp.bfloat16, one_chip)
    x = _spec((b, nc, Q, H, P), jnp.bfloat16, one_chip)
    dt = _spec((b, nc, Q, H), jnp.float32, one_chip)
    assert _has_kernel(_compile(ssd_chunk_fwd, cb, cb, x, dt, dt))


@pytest.mark.parametrize("chunk", [1, 16])
def test_serve_decode_chunk_fits_one_chip(one_chip, chunk):
    """The engine's own jitted paged step for h2o-danube-1.8b at
    published widths: 4 slots, max_len 4096, 16-token blocks, C = 1
    (decode) and 16 (prefill chunk)."""
    from repro.configs.registry import get_config
    from repro.models import lm
    from repro.serve.engine import _jitted_step
    cfg = get_config("h2o-danube-1.8b")
    slots, max_len, bs = 4, 4096, 16
    nb = max_len // bs
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                          lm.abstract_params(cfg))
    cache = jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: lm.init_paged_cache(cfg, slots,
                                                   slots * nb + 1, bs)))

    def i32(*shape):
        return _spec(shape, jnp.int32, one_chip)

    _compile(_jitted_step(cfg), params, i32(slots, chunk), cache, i32(slots, nb),
             i32(slots), i32(slots), i32(slots * nb),
             _spec((slots,), jnp.bool_, one_chip))
