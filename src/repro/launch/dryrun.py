import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent:
  * jit(step).lower(**ShapeDtypeStruct inputs) with in/out shardings from
    the logical-axis rules succeeds against the production mesh;
  * .compile() succeeds (XLA SPMD partitioning, collective legalization);
  * memory_analysis() -> bytes/device (fits-in-HBM evidence);
  * cost_analysis() + HLO text -> FLOPs, bytes, collective bytes for the
    roofline (repro.roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs import SHAPES, ShapeSpec, input_specs, shape_applicable
from ..configs.registry import ARCHS, get_config
from ..models import lm
from ..models.config import ModelConfig
from ..parallel.sharding import (ShardingRules, batch_shardings,
                                 logical_to_pspec, param_shardings, use_rules)
from ..roofline.hlo import collective_bytes_by_kind
from ..train.optimizer import Adafactor, AdamW
from ..train.schedule import cosine_schedule
from ..train.train_step import (StepConfig, abstract_train_state,
                                make_train_step, train_state_shardings)
from .mesh import make_planned_mesh

BIG_MODEL_PARAMS = 60e9   # adafactor above this (HBM), adamw below

# Per-arch sharding-rule overrides (the parallelism config system).
# grok-1: 8 experts cannot shard over model=16 -> TP *within* experts
# (expert_ffn over model) instead of EP.
ARCH_RULES: Dict[str, Dict[str, Any]] = {
    # grok-1: 8 experts cannot shard over model=16. Keep expert weights
    # STATIONARY (fully sharded over data x model on the FFN dim) so no
    # FSDP gather of 38 GiB/layer ever happens; shard the dispatch
    # buffers' capacity dim over data.
    "grok-1-314b": {"experts": None, "expert_embed": None,
                    "expert_ffn": ("data", "model"),
                    "act_experts": None, "moe_cap": "data"},
}

# Baseline gradient-accumulation factors: chosen so the train_4k cell's
# activation live-set fits 16 GiB HBM (global batch stays 256).
ARCH_MICROBATCHES: Dict[str, int] = {
    "arctic-480b": 8, "grok-1-314b": 8, "yi-34b": 4, "qwen1.5-110b": 8,
    "phi3-medium-14b": 2, "musicgen-medium": 2, "internvl2-1b": 1,
}


def pick_optimizer(cfg: ModelConfig):
    lr = cosine_schedule(3e-4, 2000, 100_000)
    if cfg.param_count() >= BIG_MODEL_PARAMS:
        return Adafactor(lr)
    return AdamW(lr)


def cache_shardings(cache_abs: Any, rules: ShardingRules):
    """KV cache (L,B,S,K,hd): batch on dim1, kv heads on dim3; SSD state
    (L,B,H,N,P): batch dim1; conv (L,B,k,C): batch dim1."""
    from jax.sharding import NamedSharding

    def shard_one(s):
        if s.ndim == 5 and s.shape[3] > 1:   # kv cache
            axes = [None, "batch", "seq_kv", "act_kv", None]
        elif s.ndim >= 2:
            axes = [None, "batch"] + [None] * (s.ndim - 2)
        else:
            axes = [None] * s.ndim
        return NamedSharding(rules.mesh,
                             logical_to_pspec(axes[:s.ndim], rules, s.shape))

    return jax.tree.map(shard_one, cache_abs)


def _compile_once(cfg: ModelConfig, shape: ShapeSpec, mesh, rules,
                  unroll: bool, donate: bool,
                  step_cfg: Optional[StepConfig] = None) -> Dict[str, Any]:
    """Lower+compile one (cfg, shape) against mesh; raw measurements.

    ``unroll=False`` scans layers (memory-realistic: the loop bounds the
    live set); ``unroll=True`` unrolls them (cost-realistic: XLA counts a
    loop body ONCE, so scanned FLOPs/collective bytes would be ~L-fold
    under-reported).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    u = cfg.num_layers if unroll else 1
    t0 = time.time()
    with use_rules(rules):
        if shape.kind == "train":
            opt = pick_optimizer(cfg)
            sc = step_cfg or StepConfig(
                microbatches=ARCH_MICROBATCHES.get(cfg.name, 1),
                remat="full", attention_impl="auto")
            sc = StepConfig(**{**sc.__dict__, "unroll": u,
                               "micro_unroll": unroll})
            step = make_train_step(cfg, opt, sc)
            state_abs = abstract_train_state(cfg, opt)
            state_sh = train_state_shardings(cfg, opt, rules)
            in_specs = input_specs(cfg, shape)
            batch_sh = batch_shardings(in_specs, rules)
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,) if donate else ())
            lowered = jitted.lower(state_abs, in_specs)
        elif shape.kind == "prefill":
            in_specs = input_specs(cfg, shape)
            batch_sh = batch_shardings(in_specs, rules)
            params_abs = lm.abstract_params(cfg)
            params_sh = param_shardings(lm.param_specs(cfg), rules, params_abs)

            def prefill_step(params, batch):
                return lm.prefill(cfg, params, batch, unroll=u)

            jitted = jax.jit(prefill_step, in_shardings=(params_sh, batch_sh))
            lowered = jitted.lower(params_abs, in_specs)
        else:  # decode
            from ..configs.shapes import cache_specs
            in_specs = input_specs(cfg, shape)
            batch_sh = batch_shardings(in_specs, rules)
            params_abs = lm.abstract_params(cfg)
            params_sh = param_shardings(lm.param_specs(cfg), rules, params_abs)
            cache_abs = cache_specs(cfg, shape)
            cache_sh = cache_shardings(cache_abs, rules)
            cache_sh["pos"] = NamedSharding(mesh, P())

            def serve_step(params, tokens, cache):
                return lm.decode_step(cfg, params, tokens, cache, unroll=u)

            jitted = jax.jit(serve_step,
                             in_shardings=(params_sh, batch_sh["tokens"], cache_sh),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(params_abs, in_specs["tokens"], cache_abs)

        lower_s = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t1

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    try:
        from ..roofline.hlo import collective_bytes_by_axis_kind
        by_axis = collective_bytes_by_axis_kind(compiled.as_text(), axis_sizes)
    except Exception:  # noqa: BLE001
        by_axis = None
    return {
        "lower_s": round(lower_s, 1), "compile_s": round(compile_s, 1),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "per_device_bytes": int(mem.argument_size_in_bytes
                                    - mem.alias_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes),
        },
        "flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "collectives": collective_bytes_by_kind(compiled.as_text()),
        "collectives_by_axis": by_axis,
    }


def _extrapolate(c1: Dict[str, Any], c2: Dict[str, Any], L: int) -> Dict[str, Any]:
    """Linear two-point extrapolation: q(L) = q1 + (q2 - q1) * (L - 1).

    Exact for uniform layer stacks: every cost is fixed + L * per_layer.
    """
    def lin(a, b):
        return a + (b - a) * (L - 1)

    out = {"flops": lin(c1["flops"], c2["flops"]),
           "hlo_bytes": lin(c1["hlo_bytes"], c2["hlo_bytes"])}
    kinds = set(c1["collectives"]) | set(c2["collectives"])
    out["collectives"] = {
        k: lin(c1["collectives"].get(k, 0.0), c2["collectives"].get(k, 0.0))
        for k in kinds}
    ba1, ba2 = c1.get("collectives_by_axis"), c2.get("collectives_by_axis")
    if ba1 is not None and ba2 is not None:
        labels = set(ba1) | set(ba2)
        out["collectives_by_axis"] = {
            lab: {k: lin(ba1.get(lab, {}).get(k, 0.0),
                         ba2.get(lab, {}).get(k, 0.0))
                  for k in set(ba1.get(lab, {})) | set(ba2.get(lab, {}))}
            for lab in labels}
    return out


def lower_cell(arch: str, shape_name: str, mesh=None, multi_pod: bool = False,
               rules_overrides: Optional[Dict[str, Any]] = None,
               step_cfg: Optional[StepConfig] = None,
               donate: bool = True, exact_cost: bool = False) -> Dict[str, Any]:
    """Lower+compile one cell; returns the record for EXPERIMENTS.md.

    Three compilations:
      1. full-depth scanned module  -> compile proof + memory analysis
      2./3. depth-1 and depth-2 unrolled modules -> two-point cost
            extrapolation for FLOPs / bytes / collective traffic
    (``exact_cost=True`` swaps 2./3. for a full-depth unrolled compile.)
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": why}

    if mesh is None:
        # the KND path: claim + workload through the control plane (no
        # hand-wired jax.make_mesh in launch drivers)
        mesh, _plan = make_planned_mesh(multi_pod=multi_pod)
    rules = ShardingRules(mesh=mesh)
    if arch in ARCH_RULES:
        rules = rules.updated(ARCH_RULES[arch])
    if rules_overrides:
        rules = rules.updated(rules_overrides)

    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "axes": list(mesh.axis_names), "devices": int(mesh.devices.size),
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    if shape.kind == "train":
        record["optimizer"] = pick_optimizer(cfg).name

    # pass 1: memory + compile proof (scanned, full depth)
    main = _compile_once(cfg, shape, mesh, rules, unroll=False, donate=donate,
                         step_cfg=step_cfg)
    record["lower_s"] = main["lower_s"]
    record["compile_s"] = main["compile_s"]
    record["memory"] = main["memory"]

    # pass 2: cost fidelity (a layer pattern is no one layer times depth)
    if exact_cost or cfg.layer_types:
        full = _compile_once(cfg, shape, mesh, rules, unroll=True,
                             donate=donate, step_cfg=step_cfg)
        for k in ("flops", "hlo_bytes", "collectives", "collectives_by_axis"):
            record[k] = full[k]
        record["cost_method"] = "full-unroll"
    else:
        c1 = _compile_once(cfg.replace(num_layers=1), shape, mesh, rules,
                           unroll=True, donate=donate, step_cfg=step_cfg)
        c2 = _compile_once(cfg.replace(num_layers=2), shape, mesh, rules,
                           unroll=True, donate=donate, step_cfg=step_cfg)
        record.update(_extrapolate(c1, c2, cfg.num_layers))
        record["cost_method"] = "two-point-extrapolation"
    record["status"] = "ok"
    return record


def run_all(out_dir: str, multi_pod: bool, archs=None, shapes=None) -> int:
    os.makedirs(out_dir, exist_ok=True)
    mesh, _plan = make_planned_mesh(multi_pod=multi_pod)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    failures = 0
    for arch in (archs or ARCHS):
        for shape_name in (shapes or SHAPES):
            tag = f"{arch}__{shape_name}__{mesh_tag}"
            path = os.path.join(out_dir, tag + ".json")
            if os.path.exists(path):
                print(f"[skip-cached] {tag}")
                continue
            print(f"[lower] {tag} ...", flush=True)
            try:
                rec = lower_cell(arch, shape_name, mesh=mesh)
            except Exception as e:  # noqa: BLE001
                rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                       "status": "error", "error": repr(e),
                       "trace": traceback.format_exc(limit=8)}
                failures += 1
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            status = rec["status"]
            extra = ""
            if status == "ok":
                gb = rec["memory"]["per_device_bytes"] / 2**30
                extra = (f" mem/dev={gb:.2f}GiB flops={rec['flops']:.3g} "
                         f"lower={rec['lower_s']}s compile={rec['compile_s']}s")
            print(f"[{status}] {tag}{extra}", flush=True)
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        archs = [args.arch] if args.arch else None
        shapes = [args.shape] if args.shape else None
        failures = run_all(args.out, args.multi_pod, archs, shapes)
        sys.exit(1 if failures else 0)

    rec = lower_cell(args.arch or "h2o-danube-1.8b",
                     args.shape or "train_4k",
                     multi_pod=args.multi_pod)
    print(json.dumps(rec, indent=2))


if __name__ == "__main__":
    main()
