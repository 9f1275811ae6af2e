"""Serving data plane: continuous batching, paged KV, router, canary.

Four layers of verification:

* unit semantics of the block/paged :class:`KVCacheManager` (strict
  reservation, sentinel hygiene, zero-epoch queues) — no model runs;
* numerical equivalence arms: the continuous-batching engine must
  produce *exactly* the seed engine's greedy tokens for a single
  request, and chunked prefill must equal token-by-token catch-up;
* regression arms for the seed engine's cross-request cache bugs —
  each one is **demonstrated against the preserved LegacyServeEngine**
  (proving the test detects the bug) and then shown fixed in the new
  engine;
* end-to-end: router dispatch/backpressure, and a canary rollback
  driven by *real engine latencies* flowing through a rolling update —
  no synthetic SLO feeds.
"""

import numpy as np
import pytest

import jax

from repro.api import CanaryRollout, FaultInjector, Workload
from repro.api.chaos import installed
from repro.configs.registry import smoke_config
from repro.core import ClaimSpec, DeviceRequest, ResourceClaimTemplate
from repro.models import lm
from repro.rollout.canary import (CanaryController, PHASE_PROMOTED,
                                  PHASE_ROLLED_BACK, spec_blob)
from repro.rollout.strategy import REVISION_LABEL
from repro.serve import (CacheOverflowError, DeadlineExceededError,
                         EmptyPromptError, KVCacheManager, LegacyServeEngine,
                         Router, RouterOverloadError, ServeEngine,
                         SloTracker)

from conftest import make_tpu_plane


def f32(name):
    return smoke_config(name).replace(compute_dtype="float32",
                                      param_dtype="float32")


@pytest.fixture(scope="module")
def cfg():
    return f32("yi-34b")


@pytest.fixture(scope="module")
def params(cfg):
    return lm.init_params(cfg, jax.random.PRNGKey(0))


def make_engine(cfg, params, **kw):
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_chunk", 4)
    return ServeEngine(cfg, params, **kw)


# ---------------------------------------------------------------------------
# KVCacheManager unit semantics (no model execution)
# ---------------------------------------------------------------------------

class TestKVCacheManager:
    def mgr(self, cfg, slots=2, max_len=64, **kw):
        return KVCacheManager(cfg, slots, max_len, **kw)

    def test_sentinel_block_never_allocated(self, cfg):
        m = self.mgr(cfg)
        seen = set()
        m.reserve(0, 64)
        m.reserve(1, 64)
        for slot in range(2):
            seen.update(int(b) for b in m.table[slot] if b)
        assert 0 not in seen
        assert len(seen) == m.used_blocks == 2 * m.blocks_per_slot

    def test_strict_reservation_and_release_roundtrip(self, cfg):
        m = self.mgr(cfg)
        total = m.free_blocks
        assert m.can_reserve(64)
        m.reserve(0, 64)
        assert m.free_blocks == total - m.blocks_per_slot
        assert m.capacity(0) == 64
        m.release(0)
        assert m.free_blocks == total
        assert (m.table[0] == 0).all() and m.pos[0] == 0

    def test_reservation_rejects_when_pool_drained(self, cfg):
        m = self.mgr(cfg, slots=2, max_len=64,
                     num_blocks=1 + 64 // 16)   # pool = one slot's worth
        m.reserve(0, 64)
        assert not m.can_reserve(16)
        with pytest.raises(RuntimeError):
            m.reserve(1, 16)

    def test_double_reserve_same_slot_raises(self, cfg):
        m = self.mgr(cfg)
        m.reserve(0, 16)
        with pytest.raises(RuntimeError):
            m.reserve(0, 16)

    def test_advance_past_capacity_raises(self, cfg):
        m = self.mgr(cfg)
        m.reserve(0, 16)           # one block
        m.advance(0, 16)
        with pytest.raises(RuntimeError):
            m.advance(0, 1)

    def test_budget_beyond_slot_width_unreservable(self, cfg):
        m = self.mgr(cfg, max_len=64)
        assert not m.can_reserve(65)

    def test_zero_queue_is_fixed_width_and_padded(self, cfg):
        m = self.mgr(cfg)
        m.reserve(0, 20)           # two blocks queued for zero-epoch
        zb = m.take_zero_blocks()
        assert zb.shape == (m.slots * m.blocks_per_slot,)
        real = zb[zb != m.num_blocks]
        assert len(real) == 2
        assert m.take_zero_blocks() is None     # drained

    def test_recycled_blocks_requeue_for_zeroing(self, cfg):
        m = self.mgr(cfg)
        m.reserve(0, 16)
        first = [int(b) for b in m.table[0] if b]
        m.take_zero_blocks()
        m.release(0)
        m.reserve(0, 16)           # LIFO: same physical block comes back
        zb = m.take_zero_blocks()
        assert set(first) <= set(int(b) for b in zb)

    def test_reset_mask_marks_reserving_slots_once(self, cfg):
        m = self.mgr(cfg)
        m.reserve(1, 16)
        rs = m.take_reset_slots()
        assert rs.tolist() == [False, True]
        assert m.take_reset_slots() is None


# ---------------------------------------------------------------------------
# Numerical equivalence vs the seed engine
# ---------------------------------------------------------------------------

PROMPT = [5, 9, 2, 7, 3]


class TestEquivalence:
    def test_single_request_greedy_matches_seed_engine(self, cfg, params):
        leg = LegacyServeEngine(cfg, params, batch_slots=2, max_len=64)
        leg.submit(PROMPT, max_new_tokens=8)
        ref = leg.run()[0].generated

        eng = make_engine(cfg, params)
        eng.submit(PROMPT, max_new_tokens=8)
        out = eng.run()
        assert len(out) == 1 and out[0].done
        assert out[0].generated == ref

    def test_chunked_prefill_equals_token_by_token(self, cfg, params):
        gens = []
        for chunk in (1, 4):
            eng = make_engine(cfg, params, prefill_chunk=chunk)
            eng.submit(PROMPT, max_new_tokens=8)
            gens.append(eng.run()[0].generated)
        assert gens[0] == gens[1]

    def test_staggered_joins_do_not_change_tokens(self, cfg, params):
        """A request's tokens are independent of who shares the batch —
        the per-slot clock/mask isolation property."""
        solo = make_engine(cfg, params)
        solo.submit(PROMPT, max_new_tokens=6)
        ref = solo.run()[0].generated

        eng = make_engine(cfg, params)
        r1 = eng.submit(PROMPT, max_new_tokens=6)
        eng.step()                              # r1 mid-prefill...
        eng.submit([8, 1, 4, 4, 2, 6], max_new_tokens=6)  # ...r2 joins
        eng.run()
        assert r1.generated == ref


# ---------------------------------------------------------------------------
# Seed bug 1: KV contamination on slot recycle
# ---------------------------------------------------------------------------

A_PROMPT = [1, 2, 3]
B_PROMPT = [9, 8, 7, 6]


class TestContaminationRegression:
    def fresh(self, cfg, params, prompt, **kw):
        eng = ServeEngine(cfg, params, batch_slots=1, max_len=64,
                          prefill_chunk=4, **kw)
        eng.submit(prompt, max_new_tokens=6)
        return eng.run()[0].generated

    def test_legacy_engine_contaminates_recycled_slot(self, cfg, params):
        """The bug demo: under the seed engine, the second request in a
        recycled slot attends to the first request's KV rows."""
        leg = LegacyServeEngine(cfg, params, batch_slots=1, max_len=64)
        leg.submit(A_PROMPT, max_new_tokens=6)
        leg.submit(B_PROMPT, max_new_tokens=6)
        second = leg.run()[1].generated
        assert second != self.fresh(cfg, params, B_PROMPT)

    def test_recycled_slot_equals_fresh_engine(self, cfg, params):
        """The fix: two sequential requests through one slot produce
        exactly what two fresh engines produce."""
        eng = ServeEngine(cfg, params, batch_slots=1, max_len=64,
                          prefill_chunk=4)
        ra = eng.submit(A_PROMPT, max_new_tokens=6)
        rb = eng.submit(B_PROMPT, max_new_tokens=6)
        out = eng.run()
        assert [r.done for r in out] == [True, True]
        assert ra.generated == self.fresh(cfg, params, A_PROMPT)
        assert rb.generated == self.fresh(cfg, params, B_PROMPT)

    def test_ssm_state_reset_is_load_bearing(self, params):
        """For recurrent families the recycled-slot reset guards the
        *cumulative* SSD state — masking alone cannot: run the same
        pair through mamba2 and require fresh-engine equality."""
        scfg = f32("mamba2-780m")
        sparams = lm.init_params(scfg, jax.random.PRNGKey(0))
        eng = ServeEngine(scfg, sparams, batch_slots=1, max_len=64,
                          prefill_chunk=4)
        eng.submit(A_PROMPT, max_new_tokens=6)
        rb = eng.submit(B_PROMPT, max_new_tokens=6)
        eng.run()
        assert rb.generated == self.fresh(scfg, sparams, B_PROMPT)


class TestLayerPattern:
    """granite-4.0-h's pattern (smoke size: mamba, mamba, attention,
    mamba): one manager holds a K/V pool as deep as the attention layers
    and an SSD state as deep as the Mamba-2 layers, and its hygiene acts
    on the layers that own each."""

    @pytest.fixture(scope="class")
    def gcfg(self):
        return f32("granite-4.0-h-micro")

    @pytest.fixture(scope="class")
    def gparams(self, gcfg):
        return lm.init_params(gcfg, jax.random.PRNGKey(0))

    def test_each_cache_is_as_deep_as_its_layers(self, gcfg):
        from repro.obs import MetricsRegistry, installed
        with installed(MetricsRegistry()) as reg:
            m = KVCacheManager(gcfg, 2, 64)
            held = {s["labels"]["cache"]: s["value"] for s in reg.collect()
                    if s["name"] == "plane_serve_cache_bytes"}
        assert gcfg.mixer_layers("attn") == 1 and gcfg.mixer_layers("ssd") == 3
        assert m.cache["kv"]["k"].shape[0] == 1
        assert {a.shape[0] for a in jax.tree.leaves(m.cache["ssd"])} == {3}
        assert held == {kind: sum(a.nbytes for a in jax.tree.leaves(m.cache[kind]))
                        for kind in ("kv", "ssd")}

    def test_uniform_stacks_hold_one_kind_of_cache(self):
        from repro.obs import MetricsRegistry, installed
        with installed(MetricsRegistry()) as reg:
            KVCacheManager(f32("mamba2-780m"), 2, 64)
            held = {s["labels"]["cache"]: s["value"] for s in reg.collect()
                    if s["name"] == "plane_serve_cache_bytes"}
        assert held["kv"] == 0 and held["ssd"] > 0

    def test_published_pool_holds_four_layers_not_forty(self):
        from repro.configs.registry import get_config
        g = get_config("granite-4.0-h-micro")
        shapes = jax.eval_shape(
            lambda: lm.init_paged_cache(g, 16, 16 * 8192 // 16 + 1, 16))
        assert shapes["kv"]["k"].shape == (4, 8193, 16, 8, 64)
        assert shapes["ssd"]["state"].shape == (36, 16, 64, 128, 64)
        kv = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes["kv"]))
        ssd = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(shapes["ssd"]))
        assert kv == 1_073_872_896 and ssd == 1_223_000_064

    def test_hygiene_acts_on_the_owning_layers(self, gcfg, gparams):
        """A tick that feeds nothing zero-epochs the queued blocks in
        every pool layer and resets the marked slot in every SSD layer,
        and touches nothing else."""
        import jax.numpy as jnp
        m = KVCacheManager(gcfg, 2, 64)
        ones = jax.tree.map(jnp.ones_like, m.cache)
        zb = np.full((m.slots * m.blocks_per_slot,), m.num_blocks, np.int32)
        zb[:2] = [2, 5]
        _, out = lm.decode_chunk(
            gcfg, gparams, jnp.zeros((2, 1), jnp.int32), ones,
            jnp.asarray(m.table), jnp.asarray(m.pos),
            jnp.zeros((2,), jnp.int32), zero_blocks=jnp.asarray(zb),
            reset_slots=jnp.asarray([False, True]))
        for a in jax.tree.leaves(out["kv"]):
            a = np.asarray(a)
            assert (a[:, [2, 5]] == 0).all()
            assert (np.delete(a, [2, 5], axis=1) == 1).all()
        for a in jax.tree.leaves(out["ssd"]):
            a = np.asarray(a)
            assert (a[:, 1] == 0).all() and (a[:, 0] == 1).all()

    def test_recycled_slot_equals_fresh_engine(self, gcfg, gparams):
        def fresh(prompt):
            eng = ServeEngine(gcfg, gparams, batch_slots=1, max_len=64,
                              prefill_chunk=4)
            eng.submit(prompt, max_new_tokens=6)
            return eng.run()[0].generated

        eng = ServeEngine(gcfg, gparams, batch_slots=1, max_len=64,
                          prefill_chunk=4)
        ra = eng.submit(A_PROMPT, max_new_tokens=6)
        rb = eng.submit(B_PROMPT, max_new_tokens=6)
        assert [r.done for r in eng.run()] == [True, True]
        assert ra.generated == fresh(A_PROMPT)
        assert rb.generated == fresh(B_PROMPT)


# ---------------------------------------------------------------------------
# Seed bugs 2-4: typed request errors instead of engine crashes
# ---------------------------------------------------------------------------

class TestRequestErrors:
    def test_legacy_engine_crashes_on_empty_prompt(self, cfg, params):
        leg = LegacyServeEngine(cfg, params, batch_slots=2, max_len=64)
        leg.submit([], max_new_tokens=4)
        with pytest.raises(IndexError):
            leg.run()

    def test_empty_prompt_fails_typed_at_submit(self, cfg, params):
        eng = make_engine(cfg, params)
        r = eng.submit([], max_new_tokens=4)
        assert r.failed and isinstance(r.error, EmptyPromptError)
        ok = eng.submit(PROMPT, max_new_tokens=4)
        out = eng.run()
        assert ok.done and {id(x) for x in out} == {id(r), id(ok)}

    def test_over_budget_prompt_fails_typed_not_silent(self, cfg, params):
        eng = make_engine(cfg, params, max_len=32)
        r = eng.submit(list(range(30)), max_new_tokens=8)
        assert r.failed and isinstance(r.error, CacheOverflowError)
        assert "max_len" in str(r.error)
        ok = eng.submit(PROMPT, max_new_tokens=4)   # engine unharmed
        eng.run()
        assert ok.done

    def test_legacy_run_drops_unfinished_requests(self, cfg, params):
        leg = LegacyServeEngine(cfg, params, batch_slots=1, max_len=64)
        leg.submit(A_PROMPT, max_new_tokens=20)
        leg.submit(B_PROMPT, max_new_tokens=20)
        got = leg.run(max_steps=3)
        assert got == []                        # both vanished (the bug)

    def test_run_reports_timeouts_instead_of_dropping(self, cfg, params):
        eng = ServeEngine(cfg, params, batch_slots=1, max_len=64)
        a = eng.submit(A_PROMPT, max_new_tokens=20)
        b = eng.submit(B_PROMPT, max_new_tokens=20)
        out = eng.run(max_steps=3)
        assert {id(r) for r in out} == {id(a), id(b)}
        assert all(r.failed and isinstance(r.error, DeadlineExceededError)
                   for r in out)
        assert eng.kv.used_blocks == 0          # slots recycled on failure

    def test_terminal_requests_carry_latency_telemetry(self, cfg, params):
        ticks = iter(range(100))
        eng = make_engine(cfg, params, clock=lambda: float(next(ticks)))
        r = eng.submit(PROMPT, max_new_tokens=4)
        eng.run()
        assert r.done
        assert r.ttft_s is not None and r.ttft_s > 0
        assert r.tpot_s is not None and r.tpot_s > 0
        assert r.latency_s >= r.ttft_s


# ---------------------------------------------------------------------------
# Router: load-aware dispatch, bounded queues, drain
# ---------------------------------------------------------------------------

class TestRouter:
    def pair(self, cfg, params, slo=None, max_queue=2):
        router = Router(slo, max_queue_per_replica=max_queue)
        router.add_replica("r0", make_engine(cfg, params), arm="baseline")
        router.add_replica("r1", make_engine(cfg, params), arm="canary")
        return router

    def test_dispatch_balances_by_load(self, cfg, params):
        router = self.pair(cfg, params, max_queue=4)
        for i in range(6):
            router.submit([1 + i, 2, 3], max_new_tokens=2)
        assert router.dispatched == {"r0": 3, "r1": 3}

    def test_backpressure_rejects_at_submit(self, cfg, params):
        router = self.pair(cfg, params, max_queue=2)
        for i in range(4):
            router.submit([1 + i, 2], max_new_tokens=2)
        with pytest.raises(RouterOverloadError):
            router.submit([1, 2], max_new_tokens=2)
        assert router.rejected == 1
        done = router.run()
        assert len(done) == 4 and all(r.done for r in done)

    def test_removed_replica_drains_instead_of_dropping(self, cfg, params):
        slo = SloTracker()
        router = self.pair(cfg, params, slo=slo, max_queue=4)
        r = router.submit(PROMPT, max_new_tokens=4)
        router.step()
        router.remove_replica("r0")             # r held by r0 (lowest name)
        assert "r0" not in router.replica_names()
        router.run()
        assert r.done
        assert slo.arm_snapshot("baseline")["samples"] == 1

    def test_slo_fed_from_actual_request_latencies(self, cfg, params):
        slo = SloTracker()
        router = self.pair(cfg, params, slo=slo, max_queue=4)
        for i in range(4):
            router.submit([1 + i, 2, 3, 4], max_new_tokens=4)
        router.run()
        for arm in ("baseline", "canary"):
            snap = slo.arm_snapshot(arm)
            assert snap["samples"] == 2
            assert snap["p95_ttft_ms"] > 0
            assert snap["p95_tpot_ms"] > 0
            assert snap["error_rate"] == 0.0


# ---------------------------------------------------------------------------
# Chaos coverage: the serve plane's sync points
# ---------------------------------------------------------------------------

class TestServeChaos:
    def test_latency_injection_does_not_change_tokens(self, cfg, params):
        eng = make_engine(cfg, params)
        eng.submit(PROMPT, max_new_tokens=6)
        ref = eng.run()[0].generated

        inj = FaultInjector(seed=3, delay_prob=0.0,
                            latency_points={"serve.step": 0.002,
                                            "router.dispatch": 0.002})
        with installed(inj):
            router = Router(max_queue_per_replica=4)
            router.add_replica("r0", make_engine(cfg, params))
            r = router.submit(PROMPT, max_new_tokens=6)
            router.run()
        assert r.generated == ref
        assert inj.hits.get("serve.step", 0) > 0
        assert inj.hits.get("serve.admit", 0) == 1
        assert inj.hits.get("serve.complete", 0) == 1
        assert inj.hits.get("router.dispatch", 0) == 1
        assert inj.latency_injections > 0


# ---------------------------------------------------------------------------
# Canary verdicts from real engine latencies through a rolling update
# ---------------------------------------------------------------------------

def canary_world(plane, *, overlay, slo, replicas=2, canary_replicas=1):
    plane.submit(ResourceClaimTemplate(name="rep", spec=ClaimSpec(
        requests=[DeviceRequest(name="chips",
                                device_class="tpu.google.com", count=1)],
        topology_scope="cluster")))
    plane.submit(Workload(claim_template="rep", replicas=replicas,
                          role="serve", max_surge=1, max_unavailable=0,
                          runtime_config={"prefill_chunk": 16}),
                 name="srv")
    plane.wait_for("Workload", "srv")
    prior = spec_blob(plane.store.get("Workload", "srv").spec)
    plane.submit(CanaryRollout(name="cr", workload="srv",
                               config=dict(overlay),
                               replicas=canary_replicas, slo=dict(slo),
                               min_samples=4))
    plane.reconcile()
    return prior


def build_router_from_claims(plane, cfg, params, slo):
    """One engine per stamped replica claim; the claim's revision label
    (vs the workload's recorded canary revision) decides the arm, and
    the arm's config decides the engine's prefill chunk — the rolling
    update's output IS the serving topology."""
    wl = plane.store.get("Workload", "srv")
    canary_rev = wl.status.outputs["rollout"].get("canary_revision")
    merged = {**wl.spec.runtime_config, **wl.spec.canary_config}
    router = Router(slo, max_queue_per_replica=8)
    arms = {}
    for obj in sorted(plane.store.list_objects(
            "ResourceClaim", selector={"workload": "srv"}),
            key=lambda o: o.meta.name):
        arm = ("canary" if obj.meta.labels.get(REVISION_LABEL) == canary_rev
               else "baseline")
        chunk = (merged if arm == "canary"
                 else wl.spec.runtime_config)["prefill_chunk"]
        router.add_replica(obj.meta.name,
                           make_engine(cfg, params, prefill_chunk=chunk),
                           arm=arm)
        arms[obj.meta.name] = arm
    return router, arms


LONG_PROMPT = list(range(1, 25))    # 24 tokens: chunked prefill = 2 ticks,
                                    # token-by-token = 24 ticks


class TestCanaryFromRealLatencies:
    def drive(self, plane, cfg, params, requests=16):
        router, arms = build_router_from_claims(plane, cfg, params, None)
        assert set(arms.values()) == {"baseline", "canary"}
        # warm-up wave: compile both arms' traces outside the
        # measurement window (TTFT must compare steady-state serving,
        # not one-time jit cost)
        for _ in range(2):
            router.submit(LONG_PROMPT, max_new_tokens=2)
        router.run()
        slo = router.slo = SloTracker()
        for i in range(requests):
            router.submit(LONG_PROMPT, max_new_tokens=2)
        finished = router.run()
        assert all(r.done for r in finished)
        slo.publish(plane, "srv")
        plane.reconcile()
        return slo

    def test_slow_canary_rolls_back_on_relative_ttft(self, cfg, params):
        """The canary overlay drops prefill_chunk to 1 (seed-style
        token-by-token catch-up). Its replicas' *measured* TTFT is ~10x
        the baseline arm's; the relative ceiling trips and the rollout
        restores the prior spec byte-identically."""
        plane = make_tpu_plane()
        prior = canary_world(plane, overlay={"prefill_chunk": 1},
                             slo={"p95_ttft_ms_vs_baseline": 3.0})
        slo = self.drive(plane, cfg, params)
        snap = slo.snapshot()
        assert (snap["canary"]["p95_ttft_ms"]
                > 3.0 * snap["baseline"]["p95_ttft_ms"])
        state = plane.store.get("CanaryRollout", "cr") \
            .status.outputs["canary"]
        assert state["phase"] == PHASE_ROLLED_BACK
        assert state["verdict"]["metric"] == "p95_ttft_ms_vs_baseline"
        assert spec_blob(plane.store.get("Workload", "srv").spec) == prior

    def test_healthy_canary_promotes_on_relative_ttft(self, cfg, params):
        """Same harness, harmless overlay (chunk unchanged): measured
        TTFTs stay comparable and the canary promotes."""
        plane = make_tpu_plane()
        canary_world(plane, overlay={"prefill_chunk": 16, "warm": 1},
                     slo={"p95_ttft_ms_vs_baseline": 3.0})
        self.drive(plane, cfg, params)
        state = plane.store.get("CanaryRollout", "cr") \
            .status.outputs["canary"]
        assert state["phase"] == PHASE_PROMOTED


class TestBreachRelativeCeilings:
    SPEC = CanaryRollout(name="cr", workload="srv", config={"x": 1},
                         slo={"p95_ttft_ms_vs_baseline": 1.5})

    def test_relative_ceiling_breaches_against_baseline(self):
        v = CanaryController._breach(self.SPEC,
                                     {"p95_ttft_ms": 40.0},
                                     {"p95_ttft_ms": 10.0})
        assert v and v["metric"] == "p95_ttft_ms_vs_baseline"
        assert v["baseline"] == 10.0 and v["observed"] == 40.0

    def test_relative_ceiling_holds_within_ratio(self):
        assert CanaryController._breach(self.SPEC,
                                        {"p95_ttft_ms": 14.0},
                                        {"p95_ttft_ms": 10.0}) is None

    def test_missing_baseline_never_breaches(self):
        assert CanaryController._breach(self.SPEC,
                                        {"p95_ttft_ms": 40.0}, {}) is None

    def test_absolute_ceilings_unchanged(self):
        spec = CanaryRollout(name="cr", workload="srv", config={"x": 1},
                             slo={"p95_latency_ms": 50.0})
        v = CanaryController._breach(spec, {"p95_latency_ms": 60.0}, {})
        assert v and v["metric"] == "p95_latency_ms"


# ---------------------------------------------------------------------------
# profiler spans in the tick, named scopes in the step
# ---------------------------------------------------------------------------

SCOPES = ("cache", "attention", "ssd", "mlp", "head")


def step_hlo(eng, C):
    """Optimized HLO text of the engine's step program for chunk C."""
    import jax.numpy as jnp
    kv = eng.kv
    return eng._step.lower(
        eng.params, jnp.zeros((eng.slots, C), jnp.int32), kv.cache,
        jnp.asarray(kv.table), jnp.asarray(kv.pos),
        jnp.zeros((eng.slots,), jnp.int32),
        jnp.full((eng.slots * kv.blocks_per_slot,), kv.num_blocks, jnp.int32),
        jnp.zeros((eng.slots,), bool)).compile().as_text()


class TestTickSpans:
    def test_traced_ticks_are_tiled_by_their_phases(self, cfg, params,
                                                    tmp_path):
        import glob
        import os

        from jax.profiler import ProfileData

        from repro.obs import Tracer, installed_tracer

        eng = make_engine(cfg, params)
        eng.submit(list(range(1, 12)), max_new_tokens=3)
        eng.submit([5, 6], max_new_tokens=4)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with installed_tracer(Tracer()):
                done = eng.run()
        finally:
            jax.profiler.stop_trace()
        assert [r.done for r in done] == [True, True]
        (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True)
        evs = sorted((e for p in ProfileData.from_file(path).planes
                      if p.name == "/host:CPU" for line in p.lines
                      for e in line.events if e.name.startswith("serve.")),
                     key=lambda e: (e.start_ns, -e.duration_ns))
        ticks = [e for e in evs if e.name == "serve.tick"]
        assert len(ticks) == eng.steps
        assert [int(dict(t.stats)["tick"]) for t in ticks] == list(range(eng.steps))
        covered = spanned = 0.0
        chunks = []
        for t in ticks:
            kids = [e for e in evs if e.name != "serve.tick"
                    and t.start_ns <= e.start_ns and e.end_ns <= t.end_ns]
            assert [k.name for k in kids] == ["serve.schedule", "serve.fetch",
                                              "serve.sample"]
            bounds = [t.start_ns] + [x for k in kids
                                     for x in (k.start_ns, k.end_ns)] + [t.end_ns]
            assert bounds == sorted(bounds)          # in order, no overlap
            covered += sum(k.duration_ns for k in kids)
            spanned += t.duration_ns
            stats = dict(kids[1].stats)
            chunks.append(int(stats["chunk"]))
            assert 1 <= int(stats["live"]) <= eng.slots
        assert covered >= 0.9 * spanned
        # 11 prompt tokens in chunks of 4 beside a 2-token prompt, then decode
        assert chunks[:3] == [4, 4, 4] and set(chunks[3:]) == {1}


class TestStepScopes:
    @pytest.mark.parametrize("name,scopes", [
        ("h2o-danube-1.8b", {"cache", "attention", "mlp", "head"}),
        ("mamba2-780m", {"cache", "ssd", "head"}),
        ("hymba-1.5b", {"cache", "attention", "ssd", "mlp", "head"}),
        ("granite-4.0-h-micro", {"cache", "attention", "ssd", "mlp", "head"}),
    ])
    def test_each_family_carries_its_scopes(self, name, scopes):
        import re
        c = f32(name)
        eng = make_engine(c, lm.init_params(c, jax.random.PRNGKey(0)))
        for C in (1, eng.prefill_chunk):
            found = {part for path in re.findall(r'op_name="([^"]*)"',
                                                 step_hlo(eng, C))
                     for part in path.split("/") if part in SCOPES}
            assert found == scopes, (name, C)


# ---------------------------------------------------------------------------
# the host copies one sampled logits row per slot, not the whole block
# ---------------------------------------------------------------------------

class FullBlockFetch(ServeEngine):
    """The whole (slots, C, V) block copied and widened on the host, each
    slot's row picked there: the fetch the sampled rows replace."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._rows = self._full_block

    @staticmethod
    def _full_block(logits, adv):
        block = np.asarray(logits, np.float32)
        if block.ndim == 4:
            block = block[:, :, 0]              # sample codebook 0
        return block[np.arange(block.shape[0]), np.maximum(np.asarray(adv) - 1, 0)]


def staggered(eng):
    """Joins that put prompt chunks beside decode, one request sampled at
    a temperature; returns each request's tokens."""
    rs = [eng.submit(list(range(3, 14)), max_new_tokens=6)]
    eng.step()
    rs.append(eng.submit([8, 1, 4, 4, 2, 6], max_new_tokens=7,
                         temperature=0.8))
    eng.step()
    eng.step()
    rs.append(eng.submit([7, 3, 9], max_new_tokens=5))
    eng.run()
    assert [r.done for r in rs] == [True] * 3
    return [r.generated for r in rs]


class TestSampledRows:
    @pytest.mark.parametrize("name", ["h2o-danube-1.8b", "mamba2-780m",
                                      "musicgen-medium"])
    def test_served_tokens_equal_the_full_block_fetch(self, name):
        c = smoke_config(name)                  # bf16, as served
        p = lm.init_params(c, jax.random.PRNGKey(1))
        out = [staggered(cls(c, p, batch_slots=3, max_len=64,
                             prefill_chunk=4, seed=11))
               for cls in (FullBlockFetch, ServeEngine)]
        assert out[0] == out[1]

    def test_one_row_per_slot_per_tick(self, params, tmp_path):
        import glob
        import os

        from jax.profiler import ProfileData

        from repro.obs import (MetricsRegistry, Tracer, installed,
                               installed_tracer)

        c = f32("yi-34b").replace(name="sampled-rows-shapes")
        with installed(MetricsRegistry()) as reg:
            eng = make_engine(c, params, batch_slots=3)

            def fetched():
                return sum(s["value"] for s in reg.collect()
                           if s["name"] == "plane_serve_fetch_bytes_total")

            row_bytes = eng.slots * c.vocab_size * 4          # float32 rows
            advanced = []

            def tick():
                before = fetched()
                eng.step()
                advanced.append(fetched() - before)

            jax.profiler.start_trace(str(tmp_path))
            try:
                with installed_tracer(Tracer()):
                    eng.submit(list(range(1, 12)), max_new_tokens=3)
                    tick()
                    eng.submit([5, 6], max_new_tokens=8)
                    tick()
                    eng.submit([9, 9, 9, 9, 9], max_new_tokens=2)
                    while eng.has_work():
                        tick()
            finally:
                jax.profiler.stop_trace()
        assert advanced == [row_bytes] * eng.steps
        (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True)
        fetches = [dict(e.stats) for p in ProfileData.from_file(path).planes
                   if p.name == "/host:CPU" for line in p.lines
                   for e in line.events if e.name == "serve.fetch"]
        assert len(fetches) == eng.steps
        assert {int(s["bytes"]) for s in fetches} == {row_bytes}
        assert len({int(s["live"]) for s in fetches}) > 1
        chunks = {int(s["chunk"]) for s in fetches}
        assert chunks == {1, eng.prefill_chunk}
        assert eng._rows._cache_size() == len(chunks)
