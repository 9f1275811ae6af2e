"""Split a traced serving tick from inside the program.

The engine opens profiler spans in each tick (``serve.tick``, tiled by
``serve.schedule``, ``serve.fetch`` and ``serve.sample``) while a tracer
is installed, and ``decode_chunk``'s operations carry named scopes
(``cache``, ``attention``, ``ssd``, ``mlp``, ``head``) in their
``op_name``. Over the ticks that ``step_ms`` and ``host_ms_per_tick``
read (complete ``chipbench.tick`` spans with device time), this module
gives, per tick in ms:

- ``step_ms.<scope>``: the self time of the step's operations in that
  scope (an operation's interval less the operations nested in it, so a
  while loop's body is not counted twice), and ``step_ms.unscoped``, the
  rest of the step's device time, so the scopes sum to ``step_ms``;
- ``host_ms_per_tick.<phase>``: the phase span less the time the step's
  program was executing in it, the subtraction ``host_ms_per_tick``
  makes for the whole tick.

An operation's scope comes from the optimized HLO text of the step's
programs: the op events of a v5e trace carry no ``op_name`` (their stats
are the device offset and duration), but their names are the HLO
instructions' names and result shapes, and the text maps those to the
``op_name`` in each instruction's metadata.

    python3 -m chipbench.split --workload danube-chat --seed 7 --seconds 51

runs the cell once with ``--trace 1``, with XLA dumping the optimized
HLO of the step's programs (the persistent compile cache off, so both
tick shapes compile), prints the run's result line, then one JSON line
with the per-tick means and each traced tick's row.
"""

from __future__ import annotations

import argparse
import bisect
import contextlib
import glob
import json
import os
import re
import shutil
import sys
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench import trace as tracemod
from chipbench.trace import Event, Interval, Timeline

PROGRAM = "serve_decode_chunk"
TICK_SPAN = "chipbench.tick"
SCOPES = ("cache", "attention", "ssd", "mlp", "head")
PHASES = ("serve.schedule", "serve.fetch", "serve.sample")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

InstrKey = Tuple[str, str]                  # (instruction name, result shape)


def scope_of(path: Optional[str]) -> Optional[str]:
    """The innermost component of an ``op_name`` path that names a scope."""
    if not path:
        return None
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return None


def instr_key(text: str) -> InstrKey:
    """(name, result shape) of an HLO instruction as printed: a line of
    HLO text, or a device op event's name ("%fusion.7 = bf16[2,4]{1,0}
    fusion(...)")."""
    name, _, rest = text.strip().partition(" = ")
    name = name.split()[-1].lstrip("%") if name.split() else ""
    if rest.startswith("("):                # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return name, rest[:i + 1]
    return name, rest.split(" ", 1)[0]


def hlo_paths(texts: Iterable[str]) -> Dict[object, Optional[str]]:
    """``op_name`` by instruction, from optimized HLO texts: keyed by
    (name, result shape), and by name alone where every text agrees (the
    two tick shapes' programs reuse names). A key the texts disagree on
    maps to None."""
    out: Dict[object, Optional[str]] = {}
    for text in texts:
        for line in text.splitlines():
            if " = " not in line:
                continue
            m = _OP_NAME.search(line)
            path = m.group(1) if m else None
            key = instr_key(line)
            for k in (key, key[0]):
                if k in out and out[k] != path:
                    out[k] = None
                else:
                    out[k] = path
    return out


def op_scopes(ops: Sequence[Event], hlo: Dict[object, Optional[str]]
              ) -> List[Optional[str]]:
    """Each op event's scope, looked up in :func:`hlo_paths`' map."""
    out = []
    for e in ops:
        key = instr_key(e.name)
        path = hlo.get(key) if key in hlo else hlo.get(key[0])
        out.append(scope_of(path))
    return out


def self_times(events: Sequence[Event]) -> List[float]:
    """Each event's duration less the parts of it covered by the events
    nested in it (on one line, a child starts inside its parent)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    own = [e.end - e.start for e in events]
    stack: List[int] = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            p = events[stack[-1]]
            own[stack[-1]] -= min(e.end, p.end) - e.start
        stack.append(i)
    return [max(0.0, t) for t in own]


def tick_executions(tl: Timeline, span: str = TICK_SPAN,
                    program: str = PROGRAM) -> Dict[int, List[Event]]:
    """The executions of ``program`` that start inside each complete
    ``span`` of the window, keyed by its ``tick`` stat: the matching
    ``trace.per_span_device_s`` makes, so their durations sum to it."""
    spans = sorted((e for e in tl.host if e.name == span
                    and tl.window[0] <= e.start and e.end <= tl.window[1]),
                   key=lambda e: e.start)
    runs = tracemod.executions(tl, program)
    out: Dict[int, List[Event]] = {}
    j = 0
    for sp in spans:
        while j < len(runs) and runs[j].start < sp.start:
            j += 1
        mine = []
        while j < len(runs) and runs[j].start <= sp.end:
            mine.append(runs[j])
            j += 1
        out[int(sp.stats["tick"])] = mine
    return out


def _overlap(iv: Interval, merged: Sequence[Interval]) -> float:
    return sum(max(0.0, min(iv[1], e) - max(iv[0], s)) for s, e in merged)


def _ops_of(runs: Sequence[Event], ops: Sequence[Event],
            starts: Sequence[float]) -> Iterable[int]:
    """Indices of the ops (sorted by start) that start inside ``runs``."""
    for r in runs:
        for k in range(bisect.bisect_left(starts, r.start), len(ops)):
            if ops[k].start > r.end:
                break
            yield k


def tick_rows(tl: Timeline, hlo: Optional[Dict[object, Optional[str]]] = None,
              span: str = TICK_SPAN, program: str = PROGRAM
              ) -> List[Dict[str, float]]:
    """One row per tick with device time, in ms: ``step_ms`` and its
    split by scope, ``host_ms`` (the tick span less the device time) and
    its split by engine phase."""
    ticks = {int(e.stats["tick"]): e for e in tl.host if e.name == span
             and "tick" in e.stats}
    execs = tick_executions(tl, span, program)
    ops = tl.ops[sorted(tl.ops)[0]] if tl.ops else []
    ops = sorted(ops, key=lambda e: e.start)
    own = self_times(ops)
    scopes = op_scopes(ops, hlo or {})
    starts = [e.start for e in ops]
    phases = sorted((e for e in tl.host if e.name in PHASES),
                    key=lambda e: e.start)
    rows = []
    for tick, runs in sorted(execs.items()):
        dev = sum(r.end - r.start for r in runs)
        if dev <= 0:
            continue
        sp = ticks[tick]
        row: Dict[str, float] = {"tick": tick, "step_ms": 1e3 * dev,
                                 "host_ms": 1e3 * (sp.end - sp.start - dev)}
        by_scope: Dict[str, float] = defaultdict(float)
        for k in _ops_of(runs, ops, starts):
            if scopes[k] is not None:
                by_scope[scopes[k]] += own[k]
        for sc in SCOPES:
            row[f"step_ms.{sc}"] = 1e3 * by_scope.get(sc, 0.0)
        row["step_ms.unscoped"] = 1e3 * (dev - sum(by_scope.values()))
        busy = tracemod.union([(r.start, r.end) for r in runs])
        for ph in PHASES:
            idle = sum((e.end - e.start) - _overlap((e.start, e.end), busy)
                       for e in phases if e.name == ph
                       and sp.start <= e.start and e.end <= sp.end)
            row[f"host_ms_per_tick.{ph.split('.', 1)[1]}"] = 1e3 * idle
        row["scoped"] = bool(by_scope)
        row["phased"] = any(sp.start <= e.start and e.end <= sp.end
                            for e in phases)
        rows.append(row)
    return rows


def unscoped_ops(tl: Timeline, hlo: Dict[object, Optional[str]],
                 n: int = 12, span: str = TICK_SPAN, program: str = PROGRAM
                 ) -> List[List[object]]:
    """What ``step_ms.unscoped`` holds: the self time of the ops with no
    scope, by instruction name and ``op_name``, and the step's time in
    which no op ran (``(no op)``), in ms per tick, largest first."""
    ops = sorted(tl.ops[sorted(tl.ops)[0]], key=lambda e: e.start) if tl.ops else []
    own = self_times(ops)
    scopes = op_scopes(ops, hlo)
    starts = [e.start for e in ops]
    tot: Dict[tuple, float] = defaultdict(float)
    ticks = 0
    for runs in tick_executions(tl, span, program).values():
        dev = sum(r.end - r.start for r in runs)
        if dev <= 0:
            continue
        ticks += 1
        for k in _ops_of(runs, ops, starts):
            dev -= own[k]
            if scopes[k] is None:
                key = instr_key(ops[k].name)
                tot[(key[0], hlo.get(key, hlo.get(key[0])))] += own[k]
        tot[("(no op)", None)] += max(0.0, dev)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, path, 1e3 * v / max(1, ticks)] for (name, path), v in top]


def means(rows: List[Dict[str, float]]) -> Dict[str, float]:
    """Per-tick means of the rows' metrics. A scope metric is left out
    where no op carried a scope, a phase metric where no tick held the
    engine's spans (a program without them), ``step_ms.<scope>`` also
    where its scope had no op."""
    if not rows:
        return {}
    n = len(rows)
    out = {"ticks": n,
           "step_ms": sum(r["step_ms"] for r in rows) / n,
           "host_ms_per_tick": sum(r["host_ms"] for r in rows) / n}
    if any(r["scoped"] for r in rows):
        for sc in SCOPES + ("unscoped",):
            v = sum(r[f"step_ms.{sc}"] for r in rows) / n
            if sc == "unscoped" or v > 0:
                out[f"step_ms.{sc}"] = v
    if any(r["phased"] for r in rows):
        for ph in PHASES:
            key = f"host_ms_per_tick.{ph.split('.', 1)[1]}"
            out[key] = sum(r[key] for r in rows) / n
    return out


@contextlib.contextmanager
def keep_timeline(box: List[Timeline]):
    """Keep the timeline a traced run loads (its record holds only the
    per-tick device seconds), for the duration of the block."""
    load = tracemod.load

    def keeping(path, layout=tracemod.TPU):
        tl = load(path, layout)
        box.append(tl)
        return tl

    tracemod.load = keeping
    try:
        yield box
    finally:
        tracemod.load = load


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    dump = tempfile.mkdtemp(prefix="chipbench-hlo-")
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"), f"--xla_dump_to={dump}",
        "--xla_dump_hlo_as_text", f"--xla_dump_hlo_module_re=.*{PROGRAM}.*"]))
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    from chipbench import run                   # jax starts after the flags

    box: List[Timeline] = []
    try:
        with keep_timeline(box):
            line = run.run_cell(run.ROOT, args.workload, args.seed,
                                args.seconds, True)
    except run.NoChip as e:
        run.log(f"[device] {e}")
        return run.NO_CHIP_EXIT
    finally:
        texts = []
        for path in sorted(glob.glob(os.path.join(
                dump, f"*{PROGRAM}*after_optimizations.txt"))):
            with open(path) as f:
                texts.append(f.read())
        shutil.rmtree(dump, ignore_errors=True)
    print(json.dumps(line), flush=True)
    if not box:
        run.log("[split] the run left no trace")
        return 1
    hlo = hlo_paths(texts)
    rows = tick_rows(box[-1], hlo)
    print(json.dumps({"split": means(rows), "hlo_programs": len(texts),
                      "unscoped_ops": unscoped_ops(box[-1], hlo),
                      "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
