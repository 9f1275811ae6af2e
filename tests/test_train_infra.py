"""Optimizers, checkpointing, data pipeline, trainer + NRI drivers."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import (CheckpointManager, list_checkpoints,
                                   restore_checkpoint, save_checkpoint)
from repro.configs.registry import smoke_config
from repro.data.pipeline import SyntheticLMData
from repro.train.optimizer import AdamW, Adafactor, global_norm
from repro.train.schedule import constant_schedule, cosine_schedule
from repro.train.train_step import StepConfig, init_train_state, make_train_step
from repro.train.trainer import FaultInjector, Trainer


class TestOptimizers:
    @pytest.mark.parametrize("opt_cls", [AdamW, Adafactor])
    def test_quadratic_convergence(self, opt_cls):
        opt = opt_cls(constant_schedule(0.05))
        target = jnp.array(np.random.RandomState(0).randn(8, 8), jnp.float32)
        params = {"w": jnp.zeros((8, 8))}
        state = opt.init(params)
        errs = []
        for step in range(400):
            grads = {"w": 2 * (params["w"] - target)}
            params, state = opt.update(params, grads, state,
                                       jnp.asarray(step))
            errs.append(float(jnp.max(jnp.abs(params["w"] - target))))
        assert errs[-1] < 0.1 and errs[-1] < errs[50]

    def test_adafactor_state_is_factored(self):
        opt = Adafactor(constant_schedule(1e-3), min_dim_size_to_factor=8)
        params = {"big": jnp.zeros((16, 32)), "small": jnp.zeros((4,))}
        st = opt.init(params)
        assert set(st["acc"]["big"]) == {"vr", "vc"}
        assert st["acc"]["big"]["vr"].shape == (16,)
        assert set(st["acc"]["small"]) == {"v"}

    def test_state_specs_match_init_structure(self):
        from repro.models import lm
        cfg = smoke_config("yi-34b")
        params = lm.abstract_params(cfg)
        pspecs = lm.param_specs(cfg)
        for opt in (AdamW(constant_schedule(1e-3)),
                    Adafactor(constant_schedule(1e-3))):
            st_abs = jax.eval_shape(opt.init, params)
            st_specs = opt.state_specs(pspecs, params)
            assert (jax.tree_util.tree_structure(st_abs)
                    == jax.tree_util.tree_structure(
                        jax.tree.map(lambda x: 0, st_specs,
                                     is_leaf=lambda x: isinstance(x, tuple))))

    def test_schedules(self):
        sched = cosine_schedule(1.0, 10, 100)
        assert float(sched(jnp.asarray(0))) == 0.0
        assert abs(float(sched(jnp.asarray(10))) - 1.0) < 1e-6
        assert float(sched(jnp.asarray(100))) < 0.15


class TestCheckpoint:
    def test_roundtrip(self):
        tree = {"a": jnp.arange(12).reshape(3, 4).astype(jnp.bfloat16),
                "b": {"c": jnp.ones((2,), jnp.int32)},
                "step": jnp.asarray(7)}
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 7, tree)
            restored, step = restore_checkpoint(d, tree)
            assert step == 7
            for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_commit_marker_crash_safety(self):
        tree = {"a": jnp.ones((2, 2))}
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 1, tree)
            # fake a partial write: step dir without commit marker
            os.makedirs(os.path.join(d, "step_00000002"))
            assert list_checkpoints(d) == [1]
            _, step = restore_checkpoint(d, tree)
            assert step == 1

    def test_rotation_and_async(self):
        tree = {"a": jnp.ones((4,))}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=2, async_save=True)
            for s in (1, 2, 3, 4):
                mgr.save(s, tree)
            mgr.wait()
            assert list_checkpoints(d) == [3, 4]


class TestDataPipeline:
    def test_determinism(self):
        cfg = smoke_config("yi-34b")
        d = SyntheticLMData(cfg, 16, 32, seed=3)
        b1 = d.batch(5)
        b2 = d.batch(5)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_sharding_partition_of_global(self):
        """Elastic invariant: shard layout never changes the global batch."""
        cfg = smoke_config("yi-34b")
        d = SyntheticLMData(cfg, 16, 32, seed=3)
        full = d.batch(9)["tokens"]
        for num_shards in (2, 4):
            parts = [d.batch(9, shard=i, num_shards=num_shards)["tokens"]
                     for i in range(num_shards)]
            np.testing.assert_array_equal(np.concatenate(parts), full)

    def test_labels_are_shifted_tokens(self):
        cfg = smoke_config("yi-34b")
        d = SyntheticLMData(cfg, 4, 16)
        b = d.batch(0)
        np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])

    def test_vlm_batch_has_patches(self):
        cfg = smoke_config("internvl2-1b")
        b = SyntheticLMData(cfg, 4, 16).batch(0)
        assert b["patch_embeds"].shape == (4, cfg.num_patches, cfg.vit_dim)


class TestTrainerDrivers:
    def test_fit_ckpt_resume(self):
        cfg = smoke_config("h2o-danube-1.8b")
        data = SyntheticLMData(cfg, 8, 32)
        with tempfile.TemporaryDirectory() as d:
            t = Trainer(cfg, AdamW(constant_schedule(1e-3)), data,
                        ckpt=CheckpointManager(d), ckpt_every=4,
                        step_cfg=StepConfig(remat="dots"))
            t.init()
            out = t.fit(9)
            assert out["completed"] == 9
            assert t.history[-1]["loss"] < t.history[0]["loss"]

            t2 = Trainer(cfg, AdamW(constant_schedule(1e-3)), data,
                         ckpt=CheckpointManager(d), ckpt_every=4,
                         step_cfg=StepConfig(remat="dots"))
            t2.init()
            step = t2.resume()
            assert step == 8
            out2 = t2.fit(2)
            assert out2["completed"] >= 10

    def test_sharded_init_matches_unsharded(self):
        """Under a mesh, init builds the state straight into the planned
        shardings, and training matches the unsharded trainer."""
        from jax.sharding import NamedSharding
        from repro.parallel.sharding import ShardingRules, use_rules
        cfg = smoke_config("h2o-danube-1.8b").replace(
            param_dtype="float32", compute_dtype="float32")
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)

        def run(rules):
            t = Trainer(cfg, AdamW(constant_schedule(1e-3)),
                        SyntheticLMData(cfg, 4, 16),
                        step_cfg=StepConfig(remat="none"))
            with use_rules(rules):
                t.init(0)
                leaves = jax.tree.leaves(t.state)
                t.fit(3)
            return leaves, [h["loss"] for h in t.history]

        sharded, l_sh = run(ShardingRules(mesh=mesh))
        _, l_plain = run(None)
        assert all(isinstance(a.sharding, NamedSharding)
                   and a.sharding.mesh.shape == mesh.shape for a in sharded)
        np.testing.assert_allclose(l_sh, l_plain, rtol=1e-5)

    def test_driver_isolation(self):
        """A crashing driver never breaks training (NRI isolation)."""
        from repro.core.drivers import KNDDriver
        from repro.core.nri import Events

        class Bomb(KNDDriver):
            name = "bomb"

            def register(self, bus):
                bus.subscribe(Events.STEP_END,
                              lambda e: 1 / 0, self.name)

        cfg = smoke_config("mamba2-780m")
        data = SyntheticLMData(cfg, 4, 16)
        t = Trainer(cfg, AdamW(constant_schedule(1e-3)), data,
                    drivers=[Bomb()], step_cfg=StepConfig(remat="none"))
        t.init()
        out = t.fit(3)
        assert out["completed"] == 3
        assert len(t.bus.failures()) == 3  # isolated, recorded

    def test_fault_injection_stops(self):
        cfg = smoke_config("mamba2-780m")
        data = SyntheticLMData(cfg, 4, 16)
        t = Trainer(cfg, AdamW(constant_schedule(1e-3)), data,
                    drivers=[FaultInjector(fail_at=2)],
                    step_cfg=StepConfig(remat="none"))
        t.init()
        out = t.fit(10)
        assert out == {"stopped_at": 2, "reason": "node_failure"}

    def test_microbatch_equivalence(self):
        """grad accumulation == single batch (same data, fp32)."""
        cfg = smoke_config("yi-34b").replace(param_dtype="float32",
                                             compute_dtype="float32")
        data = SyntheticLMData(cfg, 8, 16)
        batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        opt = AdamW(constant_schedule(1e-3))
        s0 = init_train_state(cfg, opt, jax.random.PRNGKey(0))
        step1 = make_train_step(cfg, opt, StepConfig(microbatches=1,
                                                     remat="none"))
        step4 = make_train_step(cfg, opt, StepConfig(microbatches=4,
                                                     remat="none"))
        s1, m1 = step1(s0, batch)
        s0b = init_train_state(cfg, opt, jax.random.PRNGKey(0))
        s4, m4 = step4(s0b, batch)
        g1 = jax.tree.leaves(s1["params"])
        g4 = jax.tree.leaves(s4["params"])
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(g1, g4))
        assert err < 5e-5, err
