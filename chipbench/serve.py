"""One run of a serving cell: set-up, the fill, an open-loop window, the
drain, and the records the metrics read.

The program under test is the serving path: a replica provisioned
through the control plane (``launch.serve.provision_replicas``) and a
``ServeEngine`` over the paged cache, driven tick by tick by
``engine.step()``. The harness reads only what the engine shows:
``submit``, ``step``, ``has_work``, ``active``, ``pending``,
``prefill_chunk``, ``kv.pos`` and each request's tokens and state, and,
in a traced run, the engine's ``admitted`` emits.

The fill offers the traffic for ``fill_s`` before the window opens, so
the window measures the engine at the occupancy its rate keeps; it is
not set-up, and no metric reads it. Every request is timed from its
scheduled arrival, so a tick that blocks the generator shows in the
latency of the requests it delays; how late the generator sent each
request is recorded apart.
"""

from __future__ import annotations

import contextlib
import gc
import math
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import correct, traffic, weights
from chipbench import trace as tracemod
from chipbench.cell import Cell

clock = time.perf_counter
PROGRAM = "serve_decode_chunk"
TRACE_SECONDS = 5.0         # the traced sub-window, in the window's middle


@dataclass
class Tick:
    index: int
    t0: float
    t1: float
    pos: np.ndarray          # resident tokens per slot before the tick
    adv: np.ndarray          # tokens fed per slot in the tick
    queued: int              # requests waiting for admission after it

    @property
    def decode(self) -> bool:
        return int(self.adv.max()) <= 1


@dataclass
class Sent:
    due: float               # scheduled arrival, host clock
    sent: float
    request: Any
    token_times: List[float] = field(default_factory=list)
    admitted: Optional[float] = None


@dataclass
class Record:
    """What a run leaves for the metric readers."""
    config: Dict[str, Any]
    costs: Any
    peaks: Dict[str, Any]
    t0: float
    seconds: float
    sent: List[Sent]
    ticks: List[Tick]
    claim_ready_s: float
    traced_ticks: Dict[int, float] = field(default_factory=dict)  # tick -> device s
    busy_s: Optional[float] = None
    window_s: Optional[float] = None

    @property
    def t_end(self) -> float:
        return self.t0 + self.seconds

    def in_window(self) -> List[Tick]:
        return [t for t in self.ticks if self.t0 <= t.t1 <= self.t_end]

    def offered(self) -> List[Sent]:
        """The requests due inside the window (not the fill's)."""
        return [s for s in self.sent if self.t0 <= s.due < self.t_end]


def nearest_rank(values, q: float) -> Optional[float]:
    """The ``q`` quantile as an observed value (nearest rank)."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q * len(v)) - 1)]


class Annotate:
    """``jax.profiler.TraceAnnotation`` while tracing, else nothing."""

    def __init__(self):
        self.on = False

    def __call__(self, name: str, **kw):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name, **kw)


def build_engine(cell: Cell, seed: int):
    """Provision the replica, make the weights, build the engine."""
    import jax
    from repro.launch.serve import provision_replicas
    from repro.models import lm
    from repro.models.config import ModelConfig
    from repro.serve.engine import ServeEngine

    _, wl = provision_replicas(1, cell.chips, reconcile_mode="inline")
    claim_ready_s = float(wl.status.outputs["phase_latency_s"]["total"])
    ref = cell.reference()
    cfg = ModelConfig(**ref.program_config(cell.config))
    lay = ref.layout(cell.config)
    weights.check_layout(lay, lm.abstract_params(cfg))
    params = weights.make(lay, seed)
    jax.block_until_ready(params)
    eng = cell.traffic["engine"]
    engine = ServeEngine(cfg, params, batch_slots=eng["slots"],
                         max_len=eng["max_len"], seed=seed & 0x7FFFFFFF)
    return engine, claim_ready_s


def warm_up(engine) -> None:
    """Serve one request whose prompt takes two chunked ticks and a
    one-token tick, then decode: every tick shape the window uses."""
    prompt = list(range(1, 2 * engine.prefill_chunk + 2))
    r = engine.submit(prompt, 3)
    while engine.has_work():
        engine.step()
    if not r.done:
        raise RuntimeError(f"warm-up request failed: {r.error!r}")


def drive(engine, arrivals, fill_s: float, seconds: float, drain_s: float,
          annotate: Annotate, on_tick=None):
    """Offer ``arrivals`` on schedule (the fill from ``-fill_s``, then
    the window of ``seconds``), then drain until every offered request
    has its first token, for at most ``drain_s``. Returns (t0, sent,
    ticks), ``t0`` the window's start."""
    from repro.serve.engine import STATUS_DECODE

    sent: List[Sent] = []
    ticks: List[Tick] = []
    live: Dict[int, Sent] = {}          # id(request) -> its record
    waiting = set()                     # ids of requests with no token yet
    queue = list(arrivals)
    nxt = 0
    t0 = clock() + fill_s
    stop = t0 + seconds + drain_s
    while True:
        now = clock()
        while nxt < len(queue) and t0 + queue[nxt].due_s <= now:
            a = queue[nxt]
            nxt += 1
            with annotate("chipbench.submit"):
                r = engine.submit(a.prompt.tolist(), a.max_new_tokens)
            s = Sent(t0 + a.due_s, clock(), r)
            sent.append(s)
            if not r.failed:
                live[id(r)] = s
                waiting.add(id(r))
        if engine.has_work():
            before = list(engine.active)
            states = [r.state if r is not None else None for r in before]
            pos = np.array(engine.kv.pos, np.int64)
            counts = {id(r): len(r.generated) for r in before if r is not None}
            ta = clock()
            with annotate("chipbench.tick", tick=len(ticks)):
                engine.step()
            tb = clock()
            after = list(engine.active)
            adv = np.zeros(len(before), np.int64)
            for i, (rb, ra) in enumerate(zip(before, after)):
                if ra is not None and ra is rb:
                    adv[i] = int(engine.kv.pos[i]) - pos[i]
                elif ra is not None:                 # admitted in this tick
                    adv[i] = int(engine.kv.pos[i])
                    pos[i] = 0
                elif rb is not None and states[i] == STATUS_DECODE:
                    adv[i] = 1                       # its last token
            tick = Tick(len(ticks), ta, tb, pos, adv, len(engine.pending))
            ticks.append(tick)
            for r in {id(r): r for r in before + after if r is not None}.values():
                n = len(r.generated) - counts.get(id(r), 0)
                if n:
                    live[id(r)].token_times.extend([tb] * n)
                if n or r.failed:
                    waiting.discard(id(r))
            if on_tick is not None:
                on_tick(tick)
        elif nxt < len(queue):
            time.sleep(max(0.0, t0 + queue[nxt].due_s - clock()))
        if nxt == len(queue) and not waiting:
            break
        if clock() > stop:
            break
    return t0, sent, ticks


def admitted_times(tracer, engine_name: str) -> Dict[str, float]:
    out = {}
    for t, ev, _kind, name, _args in tracer.events():
        if ev == "EMIT:admitted" and name.startswith(engine_name + ":"):
            out[name] = t
    return out


def end_to_end(rec: Record) -> Dict[str, float]:
    """TTFT from the scheduled arrival over every request offered in the
    window (a request with no first token counts with its wait up to the
    stop), every inter-token gap ending in the window, and the output
    tokens produced in it."""
    t_stop = max([rec.t_end] + [t.t1 for t in rec.ticks])
    ttft = [(s.token_times[0] if s.token_times else t_stop) - s.due
            for s in rec.offered()]
    gaps = [b - a for s in rec.sent
            for a, b in zip(s.token_times, s.token_times[1:])
            if rec.t0 <= b <= rec.t_end]
    out_tokens = sum(1 for s in rec.sent for t in s.token_times
                     if rec.t0 <= t <= rec.t_end)
    res = {"output_tokens_per_s": out_tokens / rec.seconds}
    q = nearest_rank(ttft, 0.90)
    if q is not None:
        res["ttft_p90_ms"] = q * 1e3
    for q in (90, 99):
        v = nearest_rank(gaps, q / 100)
        if v is not None:
            res[f"itl_p{q}_ms"] = v * 1e3
    return res


def window_summary(rec: Record) -> str:
    """What the window held, for the log: requests, gaps, occupancy."""
    ticks = rec.in_window()
    busy = [int((t.adv > 0).sum()) for t in ticks]
    first = ticks[0] if ticks else None
    offered = rec.offered()
    ttft = [s.token_times[0] - s.due for s in offered if s.token_times]
    return (f"{len(offered)} requests offered, {len(ttft)} with a first "
            f"token; ttft p50 {1e3 * (nearest_rank(ttft, 0.5) or 0):.1f} ms; "
            f"{len(ticks)} ticks, slots busy {sum(busy) / max(1, len(ticks)):.2f} "
            f"on average, {busy[0] if busy else 0} at the first tick, "
            f"{first.queued if first else 0} queued there; "
            + ", ".join(f"{k} {v:.4f}" for k, v in end_to_end(rec).items()))


def memory_peaks() -> Dict[str, int]:
    import jax
    out = {}
    for key in ("peak_bytes_in_use", "peak_bytes_reserved"):
        vals = [(d.memory_stats() or {}).get(key) for d in jax.local_devices()]
        vals = [v for v in vals if v is not None]
        if vals:
            out[key] = int(max(vals))
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, peaks: Dict[str, Any], log=print,
        control: bool = False, check: bool = True) -> Dict[str, Any]:
    """One run of a serving cell; returns the pieces of the result line.
    ``control`` puts the control in the program's place for the
    comparison: the gap compared is that of the token the float8
    reference puts first, so ``correct`` reads false at a sound limit
    (the program's gap is kept beside it, for calibration);
    ``check=False`` skips the comparison (rate sweeps)."""
    import jax
    from chipbench.compile_log import CompileLog
    from repro.obs import Tracer, install_tracer

    compiles = CompileLog()
    with compiles:
        engine, claim_ready_s = build_engine(cell, seed)
        warm_up(engine)
        arrivals = traffic.schedule(cell.traffic, seed, seconds,
                                    cell.config["vocab_size"])
        gc.collect()
        warm = compiles.events

        annotate = Annotate()
        tracer = Tracer(clock=clock) if trace else None
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
        traced: Dict[str, Any] = {}
        fill_s = float(cell.traffic.get("fill_s", 0.0))
        mid = fill_s + max(0.0, 0.5 * (seconds - TRACE_SECONDS))

        def on_tick(t: Tick) -> None:
            if not trace:
                return
            if "start" not in traced and t.t1 >= t_start + mid:
                jax.profiler.start_trace(trace_dir)
                annotate.on = True
                traced["start"] = jax.profiler.TraceAnnotation("chipbench.window")
                traced["start"].__enter__()
            elif ("start" in traced and "stop" not in traced
                  and t.t1 >= t_start + mid + TRACE_SECONDS):
                traced["start"].__exit__(None, None, None)
                annotate.on = False
                jax.profiler.stop_trace()
                traced["stop"] = True

        if tracer is not None:
            install_tracer(tracer)
        t_start = clock()
        setup_s = t_start - t_process
        try:
            t0, sent, ticks = drive(engine, arrivals, fill_s, seconds,
                                    float(cell.traffic["drain_s"]),
                                    annotate, on_tick)
        finally:
            if tracer is not None:
                install_tracer(None)
            if "start" in traced and "stop" not in traced:
                traced["start"].__exit__(None, None, None)
                annotate.on = False
                jax.profiler.stop_trace()
        in_window = compiles.events - warm
    lateness = [s.sent - s.due for s in sent]
    log(f"[generator] {len(sent)} requests offered in {fill_s} + {seconds} s "
        f"(fill and window); lateness "
        f"p50 {1e3 * (nearest_rank(lateness, 0.5) or 0):.3f} ms, p99 "
        f"{1e3 * (nearest_rank(lateness, 0.99) or 0):.3f} ms, max "
        f"{1e3 * max(lateness, default=0):.3f} ms")
    log(f"[compile] {warm} compile events in set-up ({compiles.cache_hits} "
        f"from the persistent cache), {in_window} inside the fill, window and "
        f"drain")
    if tracer is not None:
        adm = admitted_times(tracer, engine.name)
        for s in sent:
            s.admitted = adm.get(f"{engine.name}:r{s.request.uid}")

    mem = memory_peaks()
    rec = Record(cell.config, cell.costs(), peaks, t0, seconds, sent, ticks,
                 claim_ready_s)
    log(f"[window] {window_summary(rec)}")
    if trace and "stop" in traced:
        tl = tracemod.load(tracemod.find_xplane(trace_dir))
        rec.traced_ticks = tracemod.per_span_device_s(tl, "chipbench.tick",
                                                      PROGRAM)
        rec.busy_s = tracemod.busy_s(tl)
        rec.window_s = tl.window[1] - tl.window[0]
        traced["breakdown"] = {"device_ops": tracemod.top_ops(tl),
                               "idle_gaps": tracemod.idle_gaps(tl)}
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)

    served = [correct.Served(s.request.prompt, list(s.request.generated))
              for s in sent if s.request.generated]
    missed = sum(1 for s in sent if s.request.failed or not s.token_times)
    del engine
    gc.collect()

    res = {"record": rec, "setup_s": setup_s, "end_to_end": end_to_end(rec),
           "attempted": len(sent), "failed": missed, "memory": mem,
           "compiles_in_window": in_window,
           "breakdown": traced.get("breakdown")}
    if not check:
        return res
    chk = cell.traffic["correct"]
    pick = correct.sample(served, chk["sample_requests"], seed)
    t_ref = clock()
    cmp = correct.compare(cell.config, cell.reference(), pick,
                          chk["sample_requests"], cell.traffic["engine"]["max_len"],
                          seed, control=control)
    log(f"[correct] reference over {len(pick)} requests, {cmp['tokens']} served "
        f"tokens, in {clock() - t_ref:.3f} s"
        + (f"; the control's gap {cmp['control_gap']}, the program's "
           f"{cmp['gap']}" if control else ""))
    gap = cmp["control_gap"] if control else cmp["gap"]
    checks = {"max_logit_gap": {"value": gap, "limit": chk["max_logit_gap"]},
              "requests_compared": {"value": len(pick),
                                    "limit": chk["sample_requests"]}}
    ok = (gap is not None and gap <= chk["max_logit_gap"]
          and len(pick) == chk["sample_requests"])
    res.update(correct=ok, checks=checks, compare=cmp)
    return res
