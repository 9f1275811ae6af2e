"""Chip smoke test: the system's main paths on a TPU, in one process.

  python chip_smoke.py              # one chip: serving at full width
  python chip_smoke.py --chips 4    # four chips: sharded training, 2x2

One chip: a serve replica is provisioned through the control plane and a
``ServeEngine`` on h2o-danube-1.8b at its published widths (bf16, random
weights from seed 0; 4 slots, ``max_len`` 4096, prefill chunk 16) serves
8 requests of 512-1024 prompt tokens and 64 new tokens each
(``repro.launch.serve.main``). Then the paged ``decode_chunk`` logits of
one prompt are compared with ``lm.forward`` on the same chip.

Four chips: ``repro.launch.train.main`` trains h2o-danube-1.8b on the
control-plane-planned 2x2 mesh for 5 steps at batch 8 x 1024. Its step-0
loss is compared with the forward loss of the same initial parameters on
the same batch, computed unsharded on one device; the losses must fall
and no device may hold more than 40% of the train state.

Everything runs in this process (a child would find the chip held). The
last line of a passing run is ``{"ok": true, "device": {...}}``. A failed
check or an exception exits 1 without it; a host with no TPU exits 2
before any work. ``--smoke`` rehearses the same control flow at a reduced
config on any backend, and off the TPU still exits 1 with no ``ok`` line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "h2o-danube-1.8b"
# Paged decode vs the full forward pass, both in bf16 compute: max |diff|
# over max |ref| across every logit of the prompt and the decoded tokens.
LOGITS_RTOL = 0.1
# Sharded step-0 loss vs the unsharded forward loss (bf16 compute, f32
# cross-entropy): relative difference.
LOSS_RTOL = 1e-2
# No device may hold more than this share of the summed train state.
STATE_SHARE_MAX = 0.40


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"[check] {'pass' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


class CompileLog:
    """Seconds per jitted program from JAX's backend-compile events (a
    persistent-cache hit reports its fetch time instead), and hits."""

    def __init__(self):
        self.seconds: dict = {}
        self.cache_hits = 0

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.seconds[name] = self.seconds.get(name, 0.0) + duration

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def report(self, device: str) -> None:
        ranked = sorted(self.seconds.items(), key=lambda kv: -kv[1])
        for name, s in ranked[:8]:
            print(f"[compile] {name}: {s:.3f} s ({device})")
        rest = ranked[8:]
        print(f"[compile] {len(ranked)} programs, {sum(self.seconds.values()):.3f}"
              f" s in all ({len(rest)} not listed: "
              f"{sum(s for _, s in rest):.3f} s); persistent cache hits: "
              f"{self.cache_hits} ({device})")


def device_label() -> str:
    import jax
    d = jax.devices()[0]
    return f"{d.platform} {d.device_kind} x{len(jax.devices())}"


def print_peaks(devices) -> None:
    # on the TPU, peak_bytes_in_use counts arrays only; a program's
    # scratch shows under peak_bytes_reserved
    for d in devices:
        stats = d.memory_stats() or {}
        for key in ("peak_bytes_in_use", "peak_bytes_reserved"):
            peak = stats.get(key)
            shown = f"{peak} B ({peak / 2**30:.3f} GiB)" if peak is not None \
                else "not reported by this backend"
            print(f"[memory] device {d.id} ({d.device_kind}) {key}: {shown}")


def paged_vs_forward(cfg, params, prompt, decode_steps, slots, max_len,
                     chunk) -> float:
    """Feed ``prompt`` through the serving engine's jitted paged step in
    ``chunk``-token pieces, then decode ``decode_steps`` greedy tokens one
    at a time; return the max relative logit error against ``lm.forward``
    over the whole sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm
    from repro.serve.engine import _jitted_step
    from repro.serve.kvcache import KVCacheManager

    step = _jitted_step(cfg)
    kv = KVCacheManager(cfg, slots, max_len)
    kv.reserve(0, len(prompt) + decode_steps)
    seq = list(prompt)
    rows = []

    def feed(tokens, width):
        n = len(tokens)
        arr = np.zeros((slots, width), np.int32)
        arr[0, :n] = tokens
        adv = np.zeros((slots,), np.int32)
        adv[0] = n
        zb = kv.take_zero_blocks()
        if zb is None:
            zb = np.full((slots * kv.blocks_per_slot,), kv.num_blocks, np.int32)
        rs = kv.take_reset_slots()
        if rs is None:
            rs = np.zeros((slots,), bool)
        logits, kv.cache = step(
            params, jnp.asarray(arr), kv.cache, jnp.asarray(kv.table),
            jnp.asarray(kv.pos), jnp.asarray(adv), jnp.asarray(zb),
            jnp.asarray(rs))
        out = np.asarray(logits[0, :n], np.float32)
        kv.advance(0, n)    # only now: the step may read kv.pos in place
        rows.append(out)
        return out

    for start in range(0, len(prompt), chunk):
        last = feed(prompt[start:start + chunk], chunk)
    for _ in range(decode_steps):
        tok = int(np.argmax(last[-1]))
        seq.append(tok)
        last = feed([tok], 1)
    paged = np.concatenate(rows)[:len(seq)]

    def reference_logits(p, t):
        return lm.forward(cfg, p, {"tokens": t}, remat="none")[0]

    ref = np.asarray(jax.jit(reference_logits)(
        params, jnp.asarray(seq, jnp.int32)[None])[0], np.float32)
    check(paged.shape == ref.shape and bool(np.isfinite(paged).all()),
          f"paged logits finite, shape {paged.shape} == forward {ref.shape}")
    rel = np.abs(paged - ref).max(axis=1) / np.abs(ref).max()
    n = len(prompt)
    agree = int((paged.argmax(axis=1) == ref.argmax(axis=1)).sum())
    print(f"[logits] max relative error: prompt rows {rel[:n].max():.6g}, "
          f"decoded rows {rel[n:].max():.6g}; argmax agrees on {agree} of "
          f"{len(seq)} rows", flush=True)
    return float(rel.max())


def serve_phase(smoke: bool) -> None:
    import jax
    import numpy as np

    from repro.configs.registry import get_config, smoke_config
    from repro.launch import serve
    from repro.models import lm

    slots, chunk, requests = 4, 16, 8
    if smoke:
        max_len, lo, hi, new = 128, 24, 48, 8
    else:
        max_len, lo, hi, new = 4096, 512, 1024, 64
    argv = ["--arch", ARCH, "--claim-chips", "1", "--replicas", "1",
            "--slots", str(slots), "--max-len", str(max_len),
            "--prefill-chunk", str(chunk), "--requests", str(requests),
            "--prompt-len", str(lo), "--prompt-len-max", str(hi),
            "--new-tokens", str(new), "--seed", "0"]
    if smoke:
        argv.append("--smoke")
    label = device_label()
    t0 = time.perf_counter()
    out = serve.main(argv)
    print(f"[phase] serve (provision + {requests} requests, compiles "
          f"included): {time.perf_counter() - t0:.3f} s ({label})", flush=True)
    check(out["completed"] == requests and out["failed"] == 0,
          f"{out['completed']} of {requests} requests completed, "
          f"{out['failed']} failed")
    check(out["generated_tokens"] == requests * new,
          f"{out['generated_tokens']} tokens generated "
          f"= {requests} x {new} (each done request has >= {new})")
    print_peaks(jax.devices()[:1])

    cfg = smoke_config(ARCH) if smoke else get_config(ARCH)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.RandomState(1).randint(0, cfg.vocab_size,
                                              size=hi - 7).tolist()
    t0 = time.perf_counter()
    err = paged_vs_forward(cfg, params, prompt, 8, slots, max_len, chunk)
    print(f"[phase] logits check ({len(prompt)} prompt + 8 decoded tokens):"
          f" {time.perf_counter() - t0:.3f} s ({label})", flush=True)
    check(err <= LOGITS_RTOL,
          f"paged decode_chunk vs lm.forward max relative logit error "
          f"{err:.6g} <= {LOGITS_RTOL} ({label})")
    print_peaks(jax.devices()[:1])


def train_phase(smoke: bool) -> None:
    import jax

    from repro.configs.registry import get_config, smoke_config
    from repro.data.pipeline import SyntheticLMData
    from repro.launch import train
    from repro.models import lm

    batch, steps = 8, 5
    # the launcher's default lr, 1e-3, suits the smoke config but
    # overshoots at 24 layers: the loss rose again at step 3 on the chip
    seq, lr = (64, "1e-3") if smoke else (1024, "1e-4")
    argv = ["--arch", ARCH, "--mesh", "2x2", "--steps", str(steps),
            "--batch", str(batch), "--seq", str(seq), "--lr", lr,
            "--seed", "0"]
    if smoke:
        argv.append("--smoke")
    label = device_label()
    t0 = time.perf_counter()
    out = train.main(argv)
    print(f"[phase] train ({steps} steps on the 2x2 mesh, compiles "
          f"included): {time.perf_counter() - t0:.3f} s ({label})", flush=True)
    losses = out["losses"]
    check(len(losses) == steps and all(map(math.isfinite, losses)),
          f"{len(losses)} finite losses: {losses}")
    # the schedule's one warm-up step has learning rate 0, so losses[1]
    # differs from losses[0] only by its batch; from there on it falls
    check(losses[-1] < losses[0]
          and all(b < a for a, b in zip(losses[1:], losses[2:])),
          f"loss fell over {steps} steps, at every step after the warm-up "
          f"step: {losses[0]:.6f} -> {losses[-1]:.6f}")
    per_dev = out["state_bytes_per_device"]
    total = sum(per_dev.values())
    for dev_id, nbytes in sorted(per_dev.items()):
        print(f"[memory] device {dev_id} holds {nbytes} B of train state "
              f"({nbytes / total:.4f} of {total} B)")
    check(len(per_dev) == 4 and max(per_dev.values()) <= STATE_SHARE_MAX * total,
          f"state spread over {len(per_dev)} devices, none above "
          f"{STATE_SHARE_MAX:.0%} of {total} B")
    print_peaks(jax.devices()[:4])

    # the same initial parameters and step-0 batch, unsharded, on one device
    cfg = smoke_config(ARCH) if smoke else get_config(ARCH)

    def init_reference_params(key):
        return lm.init_params(cfg, key)

    def reference_loss(p, b):
        return lm.train_loss(cfg, p, b, remat="none")[0]

    params = jax.jit(init_reference_params)(jax.random.PRNGKey(0))
    b0 = SyntheticLMData(cfg, global_batch=batch, seq_len=seq, seed=0).batch(0)
    t0 = time.perf_counter()
    ref = float(jax.jit(reference_loss)(params, b0))
    print(f"[phase] unsharded reference loss on {jax.devices()[0]}: "
          f"{time.perf_counter() - t0:.3f} s ({label})", flush=True)
    rel = abs(losses[0] - ref) / abs(ref)
    check(rel <= LOSS_RTOL,
          f"sharded step-0 loss {losses[0]:.6f} vs unsharded forward loss "
          f"{ref:.6f}: relative difference {rel:.6g} <= {LOSS_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: serving on one chip; 4: sharded training on "
                         "a 2x2 mesh (that phase only)")
    ap.add_argument("--smoke", action="store_true",
                    help="rehearse at a reduced config on any backend; "
                         "never reports a result off the TPU")
    args = ap.parse_args(argv)

    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing next to this "
              f"script ({e}); nothing was run", file=sys.stderr)
        return 2
    print(f"[cache] compilation cache: {enable_compile_cache()}")
    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"[devices] platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    on_tpu = d0.platform == "tpu"
    if not on_tpu and not args.smoke:
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    with CompileLog() as log:
        try:
            if args.chips == 4:
                train_phase(args.smoke)
            else:
                serve_phase(args.smoke)
        except Exception:  # noqa: BLE001 - every failure ends without ok
            traceback.print_exc()
            return 1
        finally:
            log.report(device_label())
            print(f"[phase] total: {time.perf_counter() - t0:.3f} s "
                  f"({device_label()})", flush=True)
    if not on_tpu:
        print("chip_smoke: rehearsal off the TPU; no result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
