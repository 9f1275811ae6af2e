"""Observability plane: registry semantics + trace reconstruction.

Four layers:

* **Registry units** — exact counting under thread churn, label-schema
  enforcement, the disabled/no-op path (one shared null cell, nothing
  exported), the cardinality fuse, and both exporters (Prometheus text
  exposition + JSON).
* **Tracer units** — span well-formedness (`validate_spans` must catch
  seeded gaps/reversals), Chrome-trace structure, offline
  store-reconstruction.
* **Lifecycle traces** — a claim healed through a node kill yields a
  well-formed, monotonic, gap-free span tree with the outage as the
  seam between cycles; a request through chunked prefill yields
  queued -> prefill -> decode tiling the request span. The node-kill
  trace is exported Perfetto-loadable (to ``$OBS_TRACE_DIR`` when CI
  sets it — the acceptance artifact).
* **Chaos traces** — the pinned stress seeds (7/23/42) must leave the
  always-attached tracer with a valid span forest for every object the
  run touched.
"""

import json
import os
import threading
import time

import pytest

from repro.api import FaultInjector, Workload, CONDITION_READY
from repro.api import chaos as chaos_hooks
from repro.obs import (DEFAULT_BUCKETS, MAX_LABEL_SETS, MetricError,
                       MetricsRegistry, NULL_CELL, Span, Tracer, active,
                       catalog, chrome_trace, counter, dump_artifacts, gauge,
                       histogram, install_tracer, installed, installed_tracer,
                       quantile, span, spans_from_store, validate_spans)
from repro.obs import registry as obs_registry

from chaos import run_stress
from conftest import chip_claim, make_node_world, renew_alive

# Fixture instruments (tests own their own catalog entries; the
# metrics-discipline pass does not scan tests/)
T_COUNT = counter("plane_test_obs_count_total", "test counter")
T_GAUGE = gauge("plane_test_obs_gauge", "test gauge")
T_HIST = histogram("plane_test_obs_hist_seconds", "test histogram",
                   buckets=(0.1, 1.0, 10.0))
T_LABELED = counter("plane_test_obs_labeled_total", "labeled test counter",
                    labels=("arm",))


def drain(plane, rounds=12):
    for _ in range(rounds):
        plane.reconcile()


# ---------------------------------------------------------------------------
# registry units
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_histogram_semantics(self):
        with installed(MetricsRegistry()) as reg:
            c = T_COUNT.cell()
            c.inc()
            c.inc(2.5)
            g = T_GAUGE.cell()
            g.set(7)
            g.inc()
            g.dec(3)
            h = T_HIST.cell()
            for v in (0.05, 0.5, 5.0, 50.0):
                h.observe(v)
            assert c.value == 3.5
            assert g.value == 5.0
            snap = h.snapshot()
            assert snap["count"] == 4
            assert snap["min"] == 0.05 and snap["max"] == 50.0
            assert snap["buckets"] == {"0.1": 1, "1": 1, "10": 1, "+Inf": 1}
            assert reg is active()

    def test_concurrent_increments_are_exact(self):
        with installed(MetricsRegistry()):
            c = T_COUNT.cell()
            h = T_HIST.cell()
            n_threads, per = 8, 5000

            def worker():
                for _ in range(per):
                    c.inc()
                    h.observe(0.5)

            ts = [threading.Thread(target=worker) for _ in range(n_threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert c.value == n_threads * per
            snap = h.snapshot()
            assert snap["count"] == n_threads * per
            assert snap["buckets"]["1"] == n_threads * per

    def test_label_schema_enforced(self):
        with installed(MetricsRegistry()):
            cell = T_LABELED.cell(arm="canary")
            cell.inc()
            with pytest.raises(MetricError):
                T_LABELED.cell()                      # missing label
            with pytest.raises(MetricError):
                T_LABELED.cell(arm="x", extra="y")    # undeclared label

    def test_conflicting_redeclaration_raises(self):
        # same signature: idempotent (module re-import), same handle back
        again = counter("plane_test_obs_count_total", "test counter")
        assert again is T_COUNT
        with pytest.raises(MetricError):
            gauge("plane_test_obs_count_total", "now a gauge")
        with pytest.raises(MetricError):
            counter("plane_test_obs_count_total", "new labels",
                    labels=("x",))
        with pytest.raises(MetricError):
            counter("unprefixed_total", "missing plane_ prefix")

    def test_disabled_registry_is_noop(self):
        with installed(MetricsRegistry(enabled=False)) as reg:
            c = T_COUNT.cell()
            h = T_HIST.cell()
            assert c is NULL_CELL and h is NULL_CELL   # no per-call alloc
            c.inc()
            h.observe(1.0)
            with h.time():
                pass
            assert c.value == 0 and h.count == 0
            assert reg.collect() == []
            assert reg.render_prometheus() == ""

    def test_noop_path_is_not_slower_than_live_cells(self):
        # the "near-zero overhead" contract, loosely: a null inc must
        # not cost more than the locking live-cell inc
        with installed(MetricsRegistry()):
            live = T_COUNT.cell()
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            NULL_CELL.inc()
        t_null = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n):
            live.inc()
        t_live = time.perf_counter() - t0
        assert t_null < t_live * 3.0, (t_null, t_live)

    def test_cardinality_fuse_drops_to_null(self):
        with installed(MetricsRegistry()) as reg:
            cells = [T_LABELED.cell(arm=f"a{i}")
                     for i in range(MAX_LABEL_SETS + 10)]
            assert sum(1 for c in cells if c is NULL_CELL) == 10
            assert reg.dropped_label_sets == 10

    def test_cells_aggregate_at_export(self):
        with installed(MetricsRegistry()) as reg:
            a = T_LABELED.cell(arm="baseline")
            b = T_LABELED.cell(arm="baseline")   # second component, same arm
            c = T_LABELED.cell(arm="canary")
            a.inc(2)
            b.inc(3)
            c.inc(1)
            samples = {tuple(sorted(s["labels"].items())): s["value"]
                       for s in reg.collect()
                       if s["name"] == "plane_test_obs_labeled_total"}
            assert samples == {(("arm", "baseline"),): 5.0,
                               (("arm", "canary"),): 1.0}

    def test_prometheus_exposition_format(self):
        with installed(MetricsRegistry()) as reg:
            T_LABELED.cell(arm='q"uote').inc()
            h = T_HIST.cell()
            h.observe(0.05)
            h.observe(5.0)
            text = reg.render_prometheus()
        assert "# HELP plane_test_obs_labeled_total" in text
        assert "# TYPE plane_test_obs_labeled_total counter" in text
        assert 'plane_test_obs_labeled_total{arm="q\\"uote"} 1' in text
        # histogram buckets are cumulative, +Inf == count
        assert 'plane_test_obs_hist_seconds_bucket{le="0.1"} 1' in text
        assert 'plane_test_obs_hist_seconds_bucket{le="+Inf"} 2' in text
        assert "plane_test_obs_hist_seconds_count 2" in text

    def test_json_export_round_trips(self):
        with installed(MetricsRegistry()) as reg:
            T_COUNT.cell().inc(4)
            blob = json.loads(reg.render_json())
        entry = blob["plane_test_obs_count_total"]
        assert entry["type"] == "counter"
        assert entry["samples"][0]["value"] == 4.0

    def test_quantile_interpolation(self):
        with installed(MetricsRegistry()):
            h = T_HIST.cell()
            for v in [0.05] * 50 + [5.0] * 50:
                h.observe(v)
            snap = h.snapshot()
        assert quantile(snap, 0.25) <= quantile(snap, 0.5) \
            <= quantile(snap, 0.95)
        assert quantile(snap, 0.95) <= snap["max"]

    def test_installed_restores_previous(self):
        base = active()
        inner = MetricsRegistry()
        with installed(inner):
            assert active() is inner
        assert active() is base

    def test_catalog_records_declarations(self):
        cat = catalog()
        assert cat["plane_test_obs_labeled_total"].labels == ("arm",)
        assert cat["plane_test_obs_hist_seconds"].buckets == (0.1, 1.0, 10.0)
        # the real tree's instruments registered on import
        assert "plane_workqueue_enqueued_total" in cat

    def test_default_buckets_cover_latency_range(self):
        assert DEFAULT_BUCKETS == tuple(sorted(DEFAULT_BUCKETS))
        assert DEFAULT_BUCKETS[0] <= 1e-4 and DEFAULT_BUCKETS[-1] >= 10


# ---------------------------------------------------------------------------
# FaultInjector latency histograms (satellite)
# ---------------------------------------------------------------------------

class TestInjectorDelayHistogram:
    def test_summary_carries_per_point_distribution(self):
        with installed(MetricsRegistry()):
            inj = FaultInjector(seed=3, latency_points={
                "store.write": 0.0005, "workqueue.add": 0.001})
            with chaos_hooks.installed(inj):
                for _ in range(12):
                    chaos_hooks.sync_point("store.write")
                    chaos_hooks.sync_point("workqueue.add")
                    chaos_hooks.sync_point("workqueue.pop")  # no delay
            s = inj.summary()
        hist = s["delay_hist"]
        assert set(hist) == {"store.write", "workqueue.add"}
        for point, h in hist.items():
            assert h["count"] == 12
            assert h["sum_s"] > 0
            assert 0 < h["p50_ms"] <= h["p95_ms"]
        assert s["latency_injections"] == 24

    def test_probabilistic_delays_also_recorded(self):
        with installed(MetricsRegistry()):
            inj = FaultInjector(seed=7, delay_prob=1.0, max_delay_s=0.0005)
            with chaos_hooks.installed(inj):
                for _ in range(5):
                    chaos_hooks.sync_point("store.write")
            s = inj.summary()
        assert s["delay_hist"]["store.write"]["count"] == 5


# ---------------------------------------------------------------------------
# tracer units
# ---------------------------------------------------------------------------

class TestTracerUnits:
    def test_validate_catches_gaps_and_reversals(self):
        ok = Span("K", "o", "K/o", "lifecycle", 0.0, 2.0, children=[
            Span("K", "o", "a", "phase", 0.0, 1.0),
            Span("K", "o", "b", "phase", 1.0, 2.0)])
        assert validate_spans([ok]) == []
        gap = Span("K", "o", "K/o", "lifecycle", 0.0, 2.0, children=[
            Span("K", "o", "a", "phase", 0.0, 0.5),
            Span("K", "o", "b", "phase", 0.7, 2.0)])
        assert any("gap" in p for p in validate_spans([gap]))
        rev = Span("K", "o", "K/o", "lifecycle", 0.0, 2.0, children=[
            Span("K", "o", "a", "phase", 0.0, 3.0)])
        assert any("escapes" in p for p in validate_spans([rev]))
        back = Span("K", "o", "K/o", "lifecycle", 2.0, 1.0)
        assert any("monotonic" in p for p in validate_spans([back]))

    def test_request_emits_reconstruct_phases(self):
        clock = [100.0]
        tr = Tracer(clock=lambda: clock[0])
        tr.emit("Request", "eng:r0", "queued", prompt_len=8)
        clock[0] = 100.5
        tr.emit("Request", "eng:r0", "admitted", slot=0)
        clock[0] = 101.0
        tr.emit("Request", "eng:r0", "first_token")
        clock[0] = 102.0
        tr.emit("Request", "eng:r0", "complete", tokens=4)
        (root,) = tr.spans()
        assert [c.name for c in root.children] == ["queued", "prefill",
                                                   "decode"]
        assert [c.duration for c in root.children] == [0.5, 0.5, 1.0]
        assert root.args["prompt_len"] == 8 and root.args["tokens"] == 4
        assert validate_spans([root]) == []

    def test_failed_request_still_closes_span(self):
        tr = Tracer(clock=time.monotonic)
        tr.emit("Request", "eng:r1", "queued")
        tr.emit("Request", "eng:r1", "failed", error="EmptyPromptError")
        (root,) = tr.spans()
        assert root.t1 >= root.t0
        assert validate_spans([root]) == []

    def test_emit_without_installed_tracer_is_noop(self):
        from repro.obs import emit
        install_tracer(None)
        emit("Request", "x", "queued")          # must not raise
        tr = Tracer()
        with installed_tracer(tr):
            emit("Request", "x", "queued")
        assert len(tr.events()) == 1

    def test_span_without_tracer_opens_no_annotation(self, monkeypatch):
        import jax.profiler
        opened = []

        class Counting:
            def __init__(self, name, **stats):
                opened.append((name, stats))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
        install_tracer(None)
        first = span("serve.tick", tick=0)
        with first:
            with span("serve.fetch", chunk=16, live=3):
                pass
        assert opened == []
        assert span("serve.sample") is first      # one shared no-op context
        with installed_tracer(Tracer()):
            with span("serve.fetch", chunk=16, live=3):
                pass
        assert opened == [("serve.fetch", {"chunk": 16, "live": 3})]

    def test_chrome_trace_structure(self):
        roots = [Span("ResourceClaim", "c1", "ResourceClaim/c1#cycle0",
                      "lifecycle", 0.0, 1.0, children=[
                          Span("ResourceClaim", "c1", "Ready", "phase",
                               0.0, 1.0)])]
        trace = chrome_trace(roots)
        phases = {e["ph"] for e in trace["traceEvents"]}
        assert phases == {"M", "X"}
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"ResourceClaim/c1#cycle0",
                                           "Ready"}
        assert all(e["dur"] >= 0 for e in xs)
        meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
        assert {e["args"]["name"] for e in meta} == {"ResourceClaim", "c1"}


# ---------------------------------------------------------------------------
# lifecycle traces: node-kill heal + chunked prefill (satellite)
# ---------------------------------------------------------------------------

class TestNodeKillTrace:
    def _traced_heal(self):
        plane, nplane, clock = make_node_world()
        tracer = Tracer().attach(plane.store)
        plane.submit(chip_claim("c1", 8))
        plane.submit(Workload(claim="c1", build_mesh=False), name="w1")
        drain(plane)
        cobj = plane.store.get("ResourceClaim", "c1")
        victim = sorted({a.ref.node
                         for a in cobj.spec.allocation.devices})[0]
        nplane.agents[victim].kill()
        clock[0] += 10.0
        renew_alive(nplane)
        drain(plane)
        assert plane.store.get("Workload", "w1").is_true(CONDITION_READY,
                                                         current=True)
        tracer.detach()
        return tracer

    def test_healed_claim_span_tree_is_well_formed(self):
        tracer = self._traced_heal()
        spans = tracer.spans()
        assert validate_spans(spans) == [], validate_spans(spans)
        claim_cycles = [r for r in spans if r.kind == "ResourceClaim"
                        and r.obj == "c1"]
        # the kill is the seam: at least one pre-outage cycle and the
        # healed cycle after the Allocated fall edge
        assert len(claim_cycles) >= 2, [r.name for r in claim_cycles]
        first, last = claim_cycles[0], claim_cycles[-1]
        names0 = [c.name for c in first.children]
        assert names0[:3] == ["Scheduled", "Allocated", "Prepared"]
        assert "Allocated" in [c.name for c in last.children]
        # the workload's own tree reaches Ready again in its last cycle
        wl_cycles = [r for r in spans if r.kind == "Workload"]
        assert "Ready" in [c.name for c in wl_cycles[-1].children]

    def test_exported_trace_is_perfetto_loadable(self, tmp_path):
        tracer = self._traced_heal()
        out_dir = os.environ.get("OBS_TRACE_DIR") or str(tmp_path)
        os.makedirs(out_dir, exist_ok=True)
        path = tracer.export(os.path.join(out_dir, "node_kill_trace.json"))
        with open(path) as f:
            trace = json.load(f)
        events = trace["traceEvents"]
        assert events and isinstance(events, list)
        # Chrome-trace contract: complete events with µs ts/dur
        xs = [e for e in events if e.get("ph") == "X"]
        assert xs and all({"name", "ts", "dur", "pid", "tid"} <= set(e)
                          for e in xs)
        claim_spans = [e for e in xs if "cycle" in e["name"]
                       and "ResourceClaim" in e["name"]]
        assert len(claim_spans) >= 2          # outage seam visible

    def test_offline_reconstruction_from_store(self):
        plane, nplane, clock = make_node_world()
        plane.submit(chip_claim("c1", 4))
        drain(plane)
        roots = spans_from_store(plane.store, kinds=["ResourceClaim"])
        assert validate_spans(roots) == []
        (root,) = [r for r in roots if r.obj == "c1"]
        assert [c.name for c in root.children][:2] == ["Scheduled",
                                                       "Allocated"]


@pytest.mark.slow
class TestChunkedPrefillTrace:
    def test_request_span_through_chunked_prefill(self):
        import jax
        from repro.configs.registry import smoke_config
        from repro.models import lm
        from repro.serve.engine import ServeEngine
        cfg = smoke_config("yi-34b").replace(compute_dtype="float32",
                                             param_dtype="float32")
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        tr = Tracer()
        with installed_tracer(tr):
            eng = ServeEngine(cfg, params, batch_slots=2, max_len=64,
                              prefill_chunk=4, name="eng-test")
            # 11 tokens / chunk=4 -> 3 prefill chunks before first token
            eng.submit(list(range(1, 12)), max_new_tokens=4)
            done = eng.run()
        assert len(done) == 1 and done[0].done
        roots = [r for r in tr.spans() if r.kind == "Request"]
        (root,) = roots
        assert root.obj == "eng-test:r0"
        assert [c.name for c in root.children] == ["queued", "prefill",
                                                   "decode"]
        assert validate_spans(roots) == [], validate_spans(roots)
        # phases tile the request exactly: no gap, no overlap
        assert root.children[0].t0 == root.t0
        assert root.children[-1].t1 == root.t1
        assert root.args["tokens"] == 4 and root.args["prompt_len"] == 11


# ---------------------------------------------------------------------------
# chaos: pinned stress seeds leave a valid span forest (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestChaosTraces:
    @pytest.mark.parametrize("seed", [7, 23, 42])
    def test_stress_tracer_spans_well_formed(self, seed):
        result, plane = run_stress(seed, n_threads=2, n_claims=4, side=7,
                                   max_kills=3)
        assert result.tracer is not None
        spans = result.tracer.spans()
        assert spans, "stress run recorded no spans"
        problems = validate_spans(spans)
        assert problems == [], problems[:5]
        # every claim the run left allocated shows an Allocated phase
        # in its final cycle
        by_obj = {}
        for r in spans:
            by_obj.setdefault((r.kind, r.obj), []).append(r)
        for obj in plane.store.list_objects("ResourceClaim"):
            if not obj.spec.allocated:
                continue
            cycles = by_obj.get(("ResourceClaim", obj.meta.name))
            assert cycles, f"no spans for allocated {obj.meta.name}"
            phases = [c.name for c in cycles[-1].children]
            assert "Allocated" in phases, (obj.meta.name, phases)
        # and the trace exports clean
        trace = result.tracer.chrome_trace()
        assert any(e.get("ph") == "X" for e in trace["traceEvents"])


# ---------------------------------------------------------------------------
# artifacts + thin views
# ---------------------------------------------------------------------------

class TestArtifacts:
    def test_dump_artifacts_writes_all_three(self, tmp_path):
        with installed(MetricsRegistry()) as reg:
            T_COUNT.cell().inc()
            tr = Tracer()
            tr.emit("Request", "r", "queued")
            tr.emit("Request", "r", "complete")
            out = dump_artifacts(str(tmp_path), registry=reg, tracer=tr)
        assert set(out) == {"metrics.prom", "metrics.json", "spans.json"}
        assert "plane_test_obs_count_total 1" in \
            (tmp_path / "metrics.prom").read_text()
        blob = json.loads((tmp_path / "metrics.json").read_text())
        assert blob["plane_test_obs_count_total"]["samples"][0]["value"] == 1
        trace = json.loads((tmp_path / "spans.json").read_text())
        assert trace["traceEvents"]

    def test_thin_views_stay_exact_per_instance(self):
        """Two workqueues under one registry: telemetry() is per-queue
        while the exporter aggregates both (the queue's counters are
        sampled — flushed into cells by the registry collect hook)."""
        from repro.api.workqueue import WorkQueue
        with installed(MetricsRegistry()) as reg:
            q1, q2 = WorkQueue(), WorkQueue()
            q1.add("K", "a")
            q1.add("K", "b")
            q2.add("K", "c")
            q1.pop_ready(["K"])
            assert q1.enqueued == 2 and q2.enqueued == 1
            assert q1.popped == 2 and q2.popped == 0
            (sample,) = [s for s in reg.collect()
                         if s["name"] == "plane_workqueue_enqueued_total"]
            assert sample["value"] == 3.0

    def test_collect_flush_is_cumulative_not_double_counted(self):
        """Repeated collects apply deltas exactly once."""
        from repro.api.workqueue import WorkQueue
        with installed(MetricsRegistry()) as reg:
            q = WorkQueue()
            q.add("K", "a")

            def enq(registry):
                (s,) = [x for x in registry.collect()
                        if x["name"] == "plane_workqueue_enqueued_total"]
                return s["value"]

            assert enq(reg) == 1.0
            assert enq(reg) == 1.0                     # no double flush
            q.add("K", "b")
            assert enq(reg) == 2.0

    def test_disabled_registry_keeps_views_exact_but_exports_nothing(self):
        from repro.api.workqueue import WorkQueue
        with installed(MetricsRegistry(enabled=False)) as reg:
            q = WorkQueue()
            q.add("K", "a")
            assert q.pop_ready(["K"]) == [("K", "a")]  # behavior unchanged
            # sampled plain-int views stay exact even when export is off
            assert q.enqueued == 1 and q.popped == 1
            assert reg.collect() == []                 # nothing exported
