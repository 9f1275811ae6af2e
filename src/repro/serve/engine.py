"""Continuous-batching serve engine over a paged KV cache.

The data plane the control plane orchestrates: requests join slots
independently (no shared clock), prefill in chunks so a joining request
catches up in a few engine ticks instead of one token per step, decode
one token per tick, and recycle through
:class:`~repro.serve.kvcache.KVCacheManager` — recycling releases the
slot's blocks and zero-epochs them on reuse, so no request can attend
to a predecessor's K/V or SSM state (the seed engine's contamination
bug). One jitted :func:`repro.models.lm.decode_chunk` call serves mixed
phases per tick: a slot prefilling a 16-token prompt chunk rides next
to a slot decoding its 40th token.

Request lifecycle errors are *per-request and typed* — an invalid
submit (empty prompt, budget past ``max_len``) or a cache-bounds breach
fails that request with an error subclass of :class:`ServeError`, never
the engine; ``run(max_steps=...)`` marks whatever is still unfinished
at the cap as timed out and returns it, so callers (and the rollout
SLO error-rate judging canaries) see every loss.

The seed fixed-width batcher survives as
:class:`repro.serve.legacy.LegacyServeEngine` — the benchmark baseline
and the regression oracle its bugs are demonstrated against.
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..api.chaos import sync_point
from ..models import lm
from ..models.config import ModelConfig
from ..obs import counter, emit, histogram, span
from .kvcache import KVCacheManager

__all__ = ["ServeEngine", "Request", "ServeError", "EmptyPromptError",
           "CacheOverflowError", "DeadlineExceededError",
           "STATUS_QUEUED", "STATUS_PREFILL", "STATUS_DECODE",
           "STATUS_DONE", "STATUS_FAILED"]


class ServeError(RuntimeError):
    """Base class for per-request serving failures."""


class EmptyPromptError(ServeError):
    """submit() got an empty prompt (the seed engine crashed later,
    deep in _next_tokens, via prompt[-1])."""


class CacheOverflowError(ServeError):
    """The request's token budget does not fit the slot's KV capacity
    (the seed engine silently indexed past the cache instead)."""


class DeadlineExceededError(ServeError):
    """run(max_steps=...) hit its cap with this request unfinished (the
    seed engine silently dropped such requests from its return)."""


STATUS_QUEUED = "queued"
STATUS_PREFILL = "prefill"
STATUS_DECODE = "decode"
STATUS_DONE = "done"
STATUS_FAILED = "failed"

_TERMINAL = (STATUS_DONE, STATUS_FAILED)

# Unlabeled: engines are unbounded-cardinality (one per replica per
# test); cells aggregate fleet-wide at export, per-engine reads stay
# exact through stats() (docs/OBSERVABILITY.md).
_SRV_ADMITTED = counter("plane_serve_admitted_total",
                        "requests admitted into a slot")
_SRV_COMPLETED = counter("plane_serve_completed_total",
                         "requests finished with all tokens")
_SRV_FAILED = counter("plane_serve_failed_total",
                      "requests failed with a typed ServeError")
_SRV_STEPS = counter("plane_serve_steps_total",
                     "engine ticks that fed the model")
_SRV_QUEUE_TIME = histogram("plane_serve_queue_time_seconds",
                            "submit -> slot admission wait")
_SRV_FETCH_BYTES = counter("plane_serve_fetch_bytes_total",
                           "bytes of sampled logits rows copied to the host")

# Engine names for trace emits ("eng-0:r3"): stable within a process.
_ENGINE_IDS = itertools.count()

# One jitted decode step per ModelConfig (hashable, value-equal):
# every engine on the same config shares traces instead of recompiling.
_JIT_STEPS: Dict[Any, Any] = {}


def _jitted_step(cfg: ModelConfig):
    fn = _JIT_STEPS.get(cfg)
    if fn is None:
        def serve_decode_chunk(p, t, c, bt, pos, adv, zb, rs):
            return lm.decode_chunk(cfg, p, t, c, bt, pos, adv,
                                   zero_blocks=zb, reset_slots=rs)

        fn = jax.jit(serve_decode_chunk, donate_argnums=(2,))
        _JIT_STEPS[cfg] = fn
    return fn


# ...and one program per ModelConfig that picks the rows the host samples.
_JIT_ROWS: Dict[Any, Any] = {}


def _jitted_rows(cfg: ModelConfig):
    """``logits[b, max(adv[b] - 1, 0)]`` for every slot ``b`` (codebook 0
    where the logits carry a codebook axis), widened to float32: the one
    row per slot that sampling reads, so the host copies (slots, V) and
    not the step's whole (slots, C, V) block. Its shapes depend only on
    the tick's. Widened here, not on the host: float32 rows copy faster
    than bf16 rows widened on the host, on a v5e (PERF.md)."""
    fn = _JIT_ROWS.get(cfg)
    if fn is None:
        def serve_sampled_rows(logits, adv):
            rows = logits[jnp.arange(logits.shape[0]), jnp.maximum(adv - 1, 0)]
            if rows.ndim == 3:
                rows = rows[:, 0]
            return rows.astype(jnp.float32)

        fn = jax.jit(serve_sampled_rows)
        _JIT_ROWS[cfg] = fn
    return fn


@dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    uid: int = 0
    # engine-written
    generated: List[int] = field(default_factory=list)
    state: str = STATUS_QUEUED
    error: Optional[ServeError] = None
    t_submit: float = 0.0
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None

    @property
    def done(self) -> bool:
        return self.state == STATUS_DONE

    @property
    def failed(self) -> bool:
        return self.state == STATUS_FAILED

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.t_done is None else self.t_done - self.t_submit

    @property
    def ttft_s(self) -> Optional[float]:
        """Time to first token."""
        return (None if self.t_first_token is None
                else self.t_first_token - self.t_submit)

    @property
    def tpot_s(self) -> Optional[float]:
        """Time per output token over the decode phase."""
        if (self.t_done is None or self.t_first_token is None
                or len(self.generated) < 2):
            return None
        return (self.t_done - self.t_first_token) / (len(self.generated) - 1)


class ServeEngine:
    """Continuous batching: admit/prefill/decode/recycle per slot.

    ``prefill_chunk`` bounds how many prompt tokens a slot feeds per
    tick (1 reproduces the seed's token-by-token catch-up — the
    benchmark's fixed-width reference behavior). ``num_blocks``
    overrides the KV pool size (default: exactly ``slots`` worth);
    admission reserves a request's whole budget up front, so the pool
    is the real backpressure surface.
    """

    def __init__(self, cfg: ModelConfig, params: Any, batch_slots: int = 4,
                 max_len: int = 256, seed: int = 0, *,
                 prefill_chunk: int = 16, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 clock=time.perf_counter, name: Optional[str] = None):
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.prefill_chunk = max(1, prefill_chunk)
        self.rng = np.random.RandomState(seed)
        self.clock = clock
        self.name = name if name is not None else f"eng-{next(_ENGINE_IDS)}"
        self._uid = itertools.count()
        self.kv = KVCacheManager(cfg, batch_slots, max_len,
                                 block_size=block_size,
                                 num_blocks=num_blocks)
        self._step = _jitted_step(cfg)
        self._rows = _jitted_rows(cfg)
        self.active: List[Optional[Request]] = [None] * batch_slots
        self._fed: List[int] = [0] * batch_slots   # prompt tokens fed so far
        self.pending: List[Request] = []
        self.completed: List[Request] = []
        self.failed: List[Request] = []
        self.steps = 0
        # (completed, failed) counts already returned by run()
        self._run_mark = [0, 0]
        self._c_admitted = _SRV_ADMITTED.cell()
        self._c_completed = _SRV_COMPLETED.cell()
        self._c_failed = _SRV_FAILED.cell()
        self._c_steps = _SRV_STEPS.cell()
        self._c_fetch_bytes = _SRV_FETCH_BYTES.cell()
        self._h_queue_time = _SRV_QUEUE_TIME.cell()

    def _rname(self, r: Request) -> str:
        """Trace identity for a request: engine-scoped, stable."""
        return f"{self.name}:r{r.uid}"

    # -- submission --------------------------------------------------------
    def submit(self, prompt: List[int], max_new_tokens: int = 16,
               temperature: float = 0.0) -> Request:
        """Queue a request. Invalid requests come back already failed
        with a typed ``error`` — the engine itself never crashes on bad
        input, and ``run()`` reports them with everything else."""
        r = Request(list(prompt), max_new_tokens, temperature,
                    uid=next(self._uid))
        r.t_submit = self.clock()
        emit("Request", self._rname(r), "queued",
             prompt_len=len(r.prompt), max_new_tokens=max_new_tokens)
        if not r.prompt:
            return self._fail(r, EmptyPromptError("empty prompt"))
        budget = len(r.prompt) + max_new_tokens
        if budget > self.max_len:
            return self._fail(r, CacheOverflowError(
                f"prompt ({len(r.prompt)}) + max_new_tokens "
                f"({max_new_tokens}) = {budget} exceeds max_len "
                f"{self.max_len}"))
        if max_new_tokens < 1:
            return self._fail(r, ServeError("max_new_tokens must be >= 1"))
        self.pending.append(r)
        return r

    def _fail(self, r: Request, err: ServeError,
              slot: Optional[int] = None) -> Request:
        r.state = STATUS_FAILED
        r.error = err
        r.t_done = self.clock()
        self._c_failed.inc()
        emit("Request", self._rname(r), "failed", error=type(err).__name__)
        self.failed.append(r)
        if slot is not None:
            self.kv.release(slot)
            self.active[slot] = None
        return r

    # -- scheduling --------------------------------------------------------
    def _admit(self) -> None:
        """FIFO admission under strict block reservation: the head of
        the queue is admitted only when a slot AND its whole budget's
        blocks are free — admitted requests always run to completion."""
        for i in range(self.slots):
            if not self.pending:
                return
            if self.active[i] is not None:
                continue
            head = self.pending[0]
            budget = len(head.prompt) + head.max_new_tokens
            if not self.kv.can_reserve(budget):
                return        # backpressure: pool drained, keep FIFO order
            self.pending.pop(0)
            self.kv.reserve(i, budget)
            self.active[i] = head
            self._fed[i] = 0
            head.state = STATUS_PREFILL
            self._c_admitted.inc()
            self._h_queue_time.observe(self.clock() - head.t_submit)
            emit("Request", self._rname(head), "admitted", slot=i)
            sync_point("serve.admit", slot=i, uid=head.uid)

    def has_work(self) -> bool:
        return bool(self.pending) or any(r is not None for r in self.active)

    # -- one tick ----------------------------------------------------------
    def step(self) -> bool:
        """One engine tick; returns False when there was nothing to do.

        Under an installed tracer the tick is a ``serve.tick`` profiler
        span tiled by ``serve.schedule`` (admission, the chunk plan, the
        feed's upload, the dispatch of the step and of its sampled
        rows), ``serve.fetch`` (the wait for both and the rows' copy to
        the host) and ``serve.sample`` (sampling, bookkeeping,
        release)."""
        with span("serve.tick", tick=self.steps):
            with span("serve.schedule"):
                planned = self._schedule()
            if planned is None:
                return False
            slots_live, adv, chunk, rows = planned
            with span("serve.fetch", chunk=chunk, live=len(slots_live),
                      bytes=rows.nbytes):
                rows_np = np.asarray(rows)
            self._c_fetch_bytes.inc(rows_np.nbytes)
            with span("serve.sample"):
                self._finish(slots_live, adv, rows_np)
            return True

    def _schedule(self):
        """Admit, plan the chunk, upload the feed and dispatch the step
        and its sampled rows. Returns (slots fed, tokens per slot, the
        chunk width, the rows on the device), or None when no slot is
        fed."""
        sync_point("serve.step", step=self.steps)
        self._admit()
        slots_live = [i for i, r in enumerate(self.active) if r is not None]
        if not slots_live:
            return None
        self.steps += 1
        self._c_steps.inc()

        adv = np.zeros((self.slots,), np.int32)
        for i in slots_live:
            r = self.active[i]
            remaining = len(r.prompt) - self._fed[i]
            want = min(remaining, self.prefill_chunk) if remaining > 0 else 1
            cap = self.kv.capacity(i)
            if int(self.kv.pos[i]) + want > min(cap, self.max_len):
                # strict reservation makes this unreachable through
                # submit(); kept as the typed bounds gate (seed bug #2)
                self._fail(r, CacheOverflowError(
                    f"slot {i} clock {int(self.kv.pos[i])}+{want} past "
                    f"capacity {cap}"), slot=i)
                continue
            adv[i] = want
        slots_live = [i for i in slots_live if adv[i] > 0]
        if not slots_live:
            return None

        C = 1 if int(adv.max()) <= 1 else self.prefill_chunk
        feed = np.zeros((self.slots, C), np.int32)
        for i in slots_live:
            r = self.active[i]
            n = int(adv[i])
            fed = self._fed[i]
            if fed < len(r.prompt):
                feed[i, :n] = r.prompt[fed:fed + n]
            else:
                feed[i, 0] = r.generated[-1]
        arr = jnp.asarray(feed)
        if self.cfg.frontend == "audio":
            arr = jnp.broadcast_to(arr[..., None],
                                   arr.shape + (self.cfg.num_codebooks,))

        zb = self.kv.take_zero_blocks()
        if zb is None:
            zb = np.full((self.slots * self.kv.blocks_per_slot,),
                         self.kv.num_blocks, np.int32)
        rs = self.kv.take_reset_slots()
        if rs is None:
            rs = np.zeros((self.slots,), bool)
        adv_dev = jnp.asarray(adv)
        logits, self.kv.cache = self._step(
            self.params, arr, self.kv.cache, jnp.asarray(self.kv.table),
            jnp.asarray(self.kv.pos), adv_dev,
            jnp.asarray(zb), jnp.asarray(rs))
        return slots_live, adv, C, self._rows(logits, adv_dev)

    def _finish(self, slots_live: List[int], adv: np.ndarray,
                rows_np: np.ndarray) -> None:
        """Advance the fed slots' clocks, sample the slots whose prompt
        is in from their rows, and release the requests that are
        done."""
        now = self.clock()
        for i in slots_live:
            r = self.active[i]
            n = int(adv[i])
            self.kv.advance(i, n)
            if self._fed[i] < len(r.prompt):
                self._fed[i] += n
                if self._fed[i] < len(r.prompt):
                    continue                 # more prompt chunks to go
            nxt = self._sample(rows_np[i], r)
            if r.t_first_token is None:
                r.t_first_token = now
                r.state = STATUS_DECODE
                emit("Request", self._rname(r), "first_token")
            r.generated.append(nxt)
            if len(r.generated) >= r.max_new_tokens:
                r.state = STATUS_DONE
                r.t_done = now
                self._c_completed.inc()
                emit("Request", self._rname(r), "complete",
                     tokens=len(r.generated))
                self.completed.append(r)
                self.kv.release(i)
                self.active[i] = None
                sync_point("serve.complete", slot=i, uid=r.uid)

    def _sample(self, logits: np.ndarray, r: Request) -> int:
        if r.temperature <= 0:
            return int(np.argmax(logits))
        p = np.exp((logits - logits.max()) / r.temperature)
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))

    # -- drive -------------------------------------------------------------
    def run(self, max_steps: int = 512) -> List[Request]:
        """Drive until idle or ``max_steps``. Returns EVERY request that
        reached a terminal state since the previous ``run()`` —
        completions AND failures (submit-time rejections included);
        whatever is still pending/active at the cap is failed with
        :class:`DeadlineExceededError` (the seed engine silently dropped
        them)."""
        n_done, n_fail = self._run_mark
        steps = 0
        while self.has_work() and steps < max_steps:
            self.step()
            steps += 1
        if self.has_work():
            for i, r in enumerate(self.active):
                if r is not None:
                    self._fail(r, DeadlineExceededError(
                        f"active at step cap {max_steps}"), slot=i)
            while self.pending:
                self._fail(self.pending.pop(0), DeadlineExceededError(
                    f"pending at step cap {max_steps}"))
        self._run_mark = [len(self.completed), len(self.failed)]
        return self.completed[n_done:] + self.failed[n_fail:]

    # -- telemetry ---------------------------------------------------------
    def load(self) -> float:
        """Router load score: occupied slots + queue pressure, weighted
        by KV pool exhaustion (a full pool can't admit even into an
        empty slot)."""
        occupied = sum(r is not None for r in self.active)
        pool = self.kv.used_blocks / max(1, self.kv.num_blocks - 1)
        return (occupied + len(self.pending)) / max(1, self.slots) + pool

    def stats(self) -> Dict[str, Any]:
        """Thin view over this engine's registry cells (plane_serve_*);
        zeros under a disabled MetricsRegistry (bench-only)."""
        return {"slots": self.slots,
                "active": sum(r is not None for r in self.active),
                "pending": len(self.pending),
                "completed": int(self._c_completed.value),
                "failed": int(self._c_failed.value),
                "steps": int(self._c_steps.value),
                **self.kv.stats()}
