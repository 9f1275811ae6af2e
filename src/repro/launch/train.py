"""End-to-end training driver.

Runs the full KND workflow (discovery -> claim -> plan -> attach) when a
multi-device mesh is requested, then trains with the NRI-driven Trainer.
On the CPU container this is exercised with reduced configs
(``--smoke``), exactly as the assignment prescribes; the same driver on a
real v5e pod consumes the production mesh.

  PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m --smoke \
      --steps 50 --batch 8 --seq 64

``main(argv)`` can also be called in-process; it returns the report it
prints, with every step's loss and each device's share of the train
state in bytes.
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="dots", choices=["none", "dots", "full"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--state-dir", default=None,
                    help="control-plane state directory (WAL + snapshots); "
                         "an existing one is recovered and its in-flight "
                         "workload adopted instead of re-allocated")
    ap.add_argument("--devices", type=int, default=0,
                    help="host-platform device count (0 = real devices)")
    ap.add_argument("--mesh", default=None,
                    help="DxM data x model shape, e.g. 2x4 (needs --devices)")
    ap.add_argument("--placement", default="aligned",
                    choices=["aligned", "unaligned"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reconcile-mode", default="threaded",
                    choices=["threaded", "inline"],
                    help="threaded: background informer runtime keeps "
                         "converging while training steps execute "
                         "(default); inline: blocking reconcile() "
                         "reference arm")
    ap.add_argument("--node-plane", action="store_true",
                    help="run per-node agents (repro.node): slices are "
                         "published per host under heartbeat leases, "
                         "claims are placed by the topology scheduler, "
                         "and a dead agent is evicted + rescheduled")
    ap.add_argument("--obs-dir", default=None,
                    help="write metrics.prom/metrics.json/spans.json "
                         "here at exit (scripts/obsctl.py reads them)")
    args = ap.parse_args(argv)

    obs_tracer = None
    if args.obs_dir:
        from ..obs import Tracer, install_tracer
        obs_tracer = Tracer()
        install_tracer(obs_tracer)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    from ..ckpt.checkpoint import CheckpointManager
    from ..configs.registry import get_config, smoke_config
    from ..data.pipeline import SyntheticLMData
    from ..parallel.sharding import ShardingRules, use_rules
    from ..train.optimizer import AdamW
    from ..train.schedule import cosine_schedule
    from ..train.train_step import StepConfig
    from ..train.trainer import Trainer

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    data = SyntheticLMData(cfg, global_batch=args.batch, seq_len=args.seq,
                           seed=args.seed)
    opt = AdamW(cosine_schedule(args.lr, max(args.steps // 20, 1), args.steps))
    sc = StepConfig(microbatches=args.microbatches, remat=args.remat)

    rules = None
    plan = None
    plane = None
    informer = None
    node_plane = None
    if args.mesh:
        from .. import core
        from ..api import (ControlPlane, ControlPlaneRuntime, Workload,
                           has_state, load_store)
        from ..topology.tpu import TpuPodSpec, build_tpu_cluster
        d, m = (int(x) for x in args.mesh.split("x"))
        # declarative KND workflow on a pod big enough for the grid:
        # submit claim + workload, wait for Ready, read mesh off status
        side = max(d, m)
        cluster = build_tpu_cluster(1, TpuPodSpec(x=side, y=side))
        reg = core.DriverRegistry()
        reg.add(core.TpuDriver(cluster)).add(core.IciDriver(cluster))
        from ..ckpt.checkpoint import load_store_dump
        dump = (load_store_dump(args.ckpt_dir)
                if args.resume and args.ckpt_dir
                and not (args.state_dir and has_state(args.state_dir))
                else None)
        if dump is not None:
            # no WAL, but the checkpoint carries the network state
            plane = ControlPlane(reg, cluster, store=load_store(dump),
                                 state_dir=args.state_dir)
            print(f"[knd] adopted checkpointed store "
                  f"v{dump['resource_version']}: {plane.adopt()}")
        else:
            # kill-and-resume: an existing state dir is recovered and
            # its in-flight workload adopted
            plane = ControlPlane.open(args.state_dir, reg, cluster)
        if obs_tracer is not None:
            obs_tracer.attach(plane.store)
        if args.node_plane:
            # agents register BEFORE the informer starts: recovered
            # Nodes hold stale leases and must re-heartbeat first, else
            # the lifecycle controller would evict adopted claims
            from ..node import NodePlane
            node_plane = NodePlane(plane).start()
            print(f"[knd] node plane: {len(node_plane.agents)} agent(s), "
                  f"scheduler placing claims onto nodes")
        if args.reconcile_mode == "threaded":
            # submit-and-wait against a *running* runtime: the informer
            # threads keep reconciling (and WAL-journaling) while the
            # training steps below execute
            informer = ControlPlaneRuntime(plane).start()
        # declarative spec reconciliation: a recovered run with changed
        # CLI flags converges onto the new intent as spec edits instead
        # of silently keeping the adopted mesh
        claim_obj = plane.store.try_get("ResourceClaim", "train")
        if claim_obj is None:
            plane.submit(plane.planner.make_claim("train", d * m))
        elif claim_obj.spec.spec.requests[0].count != d * m:
            plane.edit("ResourceClaim", "train",
                       lambda c: setattr(c.spec.requests[0], "count", d * m))
        axes = [core.AxisSpec("data", d, "y"), core.AxisSpec("model", m, "x")]
        wl_obj = plane.store.try_get("Workload", "train-job")
        if wl_obj is None:
            plane.submit(Workload(claim="train", placement=args.placement,
                                  axes=axes, seed=args.seed),
                         name="train-job")
        elif (list(wl_obj.spec.axes) != axes
              or wl_obj.spec.placement != args.placement
              or wl_obj.spec.seed != args.seed):
            def retarget(w):
                w.axes, w.placement, w.seed = axes, args.placement, args.seed
            plane.edit("Workload", "train-job", retarget)
        wl = plane.wait_for("Workload", "train-job")
        plan = wl.status.outputs["plan"]
        mesh = wl.status.outputs["mesh"]
        rules = ShardingRules(mesh=mesh)
        lat = wl.status.outputs["phase_latency_s"]
        print(f"[knd] {plan.summary()}  "
              f"(submit->Ready {lat['total'] * 1e3:.1f}ms)")

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt is not None and plane is not None:
        # co-checkpoint the network state next to the model state
        from ..api import dump_store
        ckpt.store_provider = lambda: dump_store(plane.store)
    trainer = Trainer(cfg, opt, data, step_cfg=sc, ckpt=ckpt,
                      ckpt_every=args.ckpt_every)

    with use_rules(rules):
        if args.resume and ckpt is not None and ckpt.latest_step() is not None:
            step = trainer.resume()
            print(f"[resume] from step {step}")
        else:
            trainer.init(args.seed)
        t0 = time.time()
        out = trainer.fit(args.steps)
        dt = time.time() - t0

    if informer is not None:
        stats = informer.stop()
        print(f"[knd] informer runtime stopped after training: "
              f"{stats.reconciled} reconciles over "
              f"{stats.informer_rounds} rounds, {stats.panics} panics")
    if node_plane is not None:
        node_plane.stop()

    if obs_tracer is not None:
        from ..obs import dump_artifacts, install_tracer
        install_tracer(None)
        obs_tracer.detach()
        paths = dump_artifacts(args.obs_dir, tracer=obs_tracer)
        print(f"[obs] artifacts: {', '.join(sorted(paths.values()))}")

    losses = [h["loss"] for h in trainer.history]
    state_bytes: dict = {}
    for leaf in jax.tree.leaves(trainer.state):
        for shard in leaf.addressable_shards:
            key = str(shard.device.id)
            state_bytes[key] = state_bytes.get(key, 0) + shard.data.nbytes
    dev = jax.devices()[0]
    report = {
        "arch": cfg.name, "result": out,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "losses": losses,
        "loss_first": losses[0] if losses else None,
        "loss_last": losses[-1] if losses else None,
        "steps_per_s": round(len(losses) / dt, 3) if dt > 0 else None,
        "state_bytes_per_device": state_bytes,
    }
    print(json.dumps(report, indent=1))
    return report


if __name__ == "__main__":
    main()
