"""Readings that set a cell's rate and limits, made on the chip in one
process; not part of a benchmark run.

    python3 chipbench/calibrate.py --workload danube-chat --sweep 1,2,3 --seconds 30
    python3 chipbench/calibrate.py --workload danube-chat --gaps 11,12,13 --seconds 12

``--sweep``: the cell's traffic at each offered rate (requests/s): the
requests waiting for admission at a quarter, half, three quarters and
the end of the window (a queue that grows means the rate is past the
knee), the slots in use, the output rate and the tails. ``--gaps``: for
each seed, the program's widest logit gap and the control's on the same served tokens (see
``correct.py``). Each writes a JSON file under ``chiprun_out/``.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import cell as cellmod  # noqa: E402
from chipbench import run as runmod  # noqa: E402
from chipbench import serve  # noqa: E402

OUT = os.path.join(ROOT, "chiprun_out")


def queued_at(rec, t: float) -> int:
    """Requests waiting for admission after the last tick before ``t``."""
    q = [tk.queued for tk in rec.ticks if tk.t1 <= t]
    return q[-1] if q else 0


def sweep(cell, rates, seconds: float, seed: int, peaks) -> list:
    rows = []
    for rate in rates:
        c = copy.deepcopy(cell)
        c.traffic["arrivals"]["rate_per_s"] = rate
        c.traffic["drain_s"] = 10          # the queue's growth is what counts
        out = serve.run(c, seed, seconds, False, time.perf_counter(), peaks,
                        log=runmod.log, check=False)
        rec = out["record"]
        ticks = rec.in_window()
        mixed = [t for t in ticks if not t.decode]
        busy_slots = [int((t.adv > 0).sum()) for t in ticks]
        row = {"rate_per_s": rate, "offered": out["attempted"],
               "missed": out["failed"],
               "queued": [queued_at(rec, rec.t0 + f * seconds)
                          for f in (1 / 4, 1 / 2, 3 / 4, 1.0)],
               "mean_busy_slots": sum(busy_slots) / max(1, len(ticks)),
               "ticks": len(ticks), "mixed_ticks": len(mixed),
               "mean_tick_ms_decode": 1e3 * sum(t.t1 - t.t0 for t in ticks if t.decode)
               / max(1, len(ticks) - len(mixed)),
               "mean_tick_ms_mixed": 1e3 * sum(t.t1 - t.t0 for t in mixed)
               / max(1, len(mixed)),
               **serve.end_to_end(rec)}
        runmod.log(f"[sweep] {json.dumps(row)}")
        rows.append(row)
    return rows


def gaps(cell, seeds, seconds: float, peaks) -> list:
    rows = []
    for seed in seeds:
        out = serve.run(cell, seed, seconds, False, time.perf_counter(), peaks,
                        log=runmod.log, control=True)
        row = {"seed": seed, **out["compare"], "attempted": out["attempted"],
               "missed": out["failed"], "memory": out["memory"]}
        runmod.log(f"[gaps] {json.dumps(row)}")
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--sweep", default=None)
    ap.add_argument("--gaps", default=None)
    args = ap.parse_args(argv)

    cell = cellmod.load(ROOT, args.workload)
    runmod.use_cache()
    device = runmod.device_info(cell.chips, True)
    peaks = cellmod.peaks(ROOT, device["kind"])
    os.makedirs(OUT, exist_ok=True)
    tag = f"{args.workload}.{int(time.time())}"
    result = {"workload": args.workload, "seconds": args.seconds}
    if args.sweep:
        result["sweep"] = sweep(cell, [float(r) for r in args.sweep.split(",")],
                                args.seconds, args.seed, peaks)
    if args.gaps:
        result["gaps"] = gaps(cell, [int(s) for s in args.gaps.split(",")],
                              args.seconds, peaks)
    with open(os.path.join(OUT, f"calibrate.{tag}.json"), "w") as f:
        json.dump(result, f, indent=1, default=str)
    print(json.dumps(result, default=str)[:20000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
