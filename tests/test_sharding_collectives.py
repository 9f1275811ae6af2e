"""Sharding rules + compressed collectives (multi-device via subprocess)."""

import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import (BASE_RULES, ShardingRules,
                                     logical_to_pspec)


class TestLogicalToPspec:
    def setup_method(self):
        # a fake mesh via namespace: rules.resolve checks mesh axis names
        self.mesh = jax.make_mesh((1,), ("model",),
                                  axis_types=(jax.sharding.AxisType.Auto,))

    def test_missing_axis_dropped(self):
        rules = ShardingRules(mesh=self.mesh)
        # "data"/"pod" absent from this mesh -> replicate
        assert logical_to_pspec(("batch", None), rules) == P(None, None)

    def test_duplicate_axis_used_once(self):
        rules = ShardingRules(mesh=self.mesh)
        spec = logical_to_pspec(("seq", "act_ff"), rules)
        # both map to "model" but it may shard only one dim
        assert spec == P("model", None)

    def test_divisibility_fallback(self):
        import types
        import numpy as np
        fake = types.SimpleNamespace(axis_names=("model",),
                                     devices=np.empty((4,), object))
        rules = ShardingRules(mesh=fake)
        # dim 6 not divisible by 4 -> replicated; dim 8 is -> sharded
        assert logical_to_pspec(("act_heads",), rules, (6,)) == P(None)
        assert logical_to_pspec(("act_heads",), rules, (8,)) == P("model")

    def test_unknown_logical_raises(self):
        rules = ShardingRules(mesh=self.mesh)
        with pytest.raises(KeyError):
            logical_to_pspec(("no_such_axis",), rules)

    def test_param_specs_cover_rules(self):
        """Every logical axis the models emit exists in BASE_RULES."""
        from repro.configs.registry import ARCHS, smoke_config
        from repro.models import lm
        for arch in ARCHS:
            specs = lm.param_specs(smoke_config(arch))
            for axes in jax.tree.leaves(
                    specs, is_leaf=lambda x: isinstance(x, tuple)):
                for ax in axes:
                    assert ax is None or ax in BASE_RULES, (arch, ax)


COMPRESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from repro.parallel.collectives import make_compressed_grad_sync, zeros_like_tree

mesh = jax.make_mesh((2,2,2), ("pod","data","model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*3)
def grad_fn(params, batch):
    def loss(p): return jnp.mean((batch["x"] @ p["w"] - batch["y"])**2)
    return jax.grad(loss)(params), {"loss": loss(params)}
params = {"w": jnp.array(np.random.RandomState(0).randn(16, 4), jnp.float32)}
batch = {"x": jnp.array(np.random.RandomState(1).randn(8, 16), jnp.float32),
         "y": jnp.array(np.random.RandomState(2).randn(8, 4), jnp.float32)}
err = zeros_like_tree(params, jnp.float32)
sync = jax.jit(make_compressed_grad_sync(mesh, grad_fn))
g_c, new_err, metrics = sync(params, batch, err)
g_exact, _ = grad_fn(params, batch)
rel = float(jnp.max(jnp.abs(g_c["w"] - g_exact["w"])) / jnp.max(jnp.abs(g_exact["w"])))
assert rel < 0.1, rel
# error feedback reduces cumulative bias
g2, _, _ = sync(params, batch, new_err)
cum = (g_c["w"] + g2["w"]) / 2
rel2 = float(jnp.max(jnp.abs(cum - g_exact["w"])) / jnp.max(jnp.abs(g_exact["w"])))
assert rel2 < rel, (rel2, rel)
# int8 is on the wire
hlo = jax.jit(sync).lower(params, batch, err).compile().as_text()
assert any("all-reduce" in l and "s8[" in l for l in hlo.splitlines()), "no s8 all-reduce"
print("COMPRESS_OK")
"""


SPMD_TRAIN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.launch.mesh import make_planned_mesh
from repro.configs.registry import smoke_config
from repro.models import lm
from repro.parallel.sharding import ShardingRules, use_rules, param_shardings
from repro.train.optimizer import AdamW
from repro.train.schedule import constant_schedule
from repro.train.train_step import StepConfig, init_train_state, make_train_step
from repro.data.pipeline import SyntheticLMData
from repro.core import (AxisSpec, DriverRegistry, IciDriver, MeshPlanner,
                        MeshRuntime, StructuredAllocator, TpuDriver)
from repro.topology.tpu import TpuPodSpec, build_tpu_cluster

# KND workflow on a 4x2 grid (8 chips)
cluster = build_tpu_cluster(1, TpuPodSpec(x=4, y=2))
reg = DriverRegistry(); reg.add(TpuDriver(cluster)).add(IciDriver(cluster))
reg.run_discovery()
planner = MeshPlanner(cluster)
claim = planner.make_claim("t", 8)
StructuredAllocator(reg.pool, reg.classes).allocate(claim)
plan = planner.plan([AxisSpec("data", 2, "y"), AxisSpec("model", 4, "x")],
                    "aligned", claim)
mesh = MeshRuntime().execute(plan.attachment())

cfg = smoke_config("yi-34b").replace(num_heads=4, num_kv_heads=2, d_model=64,
                                     d_ff=128)
rules = ShardingRules(mesh=mesh)
opt = AdamW(constant_schedule(1e-3))
data = SyntheticLMData(cfg, 8, 32)
with use_rules(rules):
    state = init_train_state(cfg, opt, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, opt, StepConfig(remat="dots")),
                   donate_argnums=(0,))
    losses = []
    for s in range(5):
        batch = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
assert losses[-1] < losses[0], losses
print("SPMD_TRAIN_OK", [round(x, 3) for x in losses])
"""


def _run(script: str, timeout: int = 600) -> str:
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=timeout,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_compressed_grad_sync_subprocess():
    assert "COMPRESS_OK" in _run(COMPRESS_SCRIPT)


def test_spmd_training_via_knd_mesh_subprocess():
    """Full-stack: KND claim -> aligned mesh -> sharded training, loss falls."""
    assert "SPMD_TRAIN_OK" in _run(SPMD_TRAIN_SCRIPT)
