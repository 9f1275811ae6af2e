"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload danube-chat --seed 7 --seconds 45 --trace 0

The cell's configuration, traffic and metrics are found by name through
``BENCHMARK.json`` (see ``chipbench/cell.py``). The run provisions the
cell's chips through the control plane, makes the weights on the device
from the seed, warms up the programs the cell's traffic uses (set-up,
reported as ``setup_s``), measures for ``--seconds``, then checks what
the timed path produced against the plain float32 reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
reports its per-layer metrics, reading a profiler trace of a few seconds
in the window's middle, with the device's busy time and a breakdown.
``--control 1`` (not part of a benchmark run) puts the control, the
reference one precision step down, in the program's place for the
comparison, which then has to read ``correct`` false.

What a run does is the cell's kind's: ``traffic["kind"]`` names the
module ``chipbench/<kind>.py`` whose ``run`` drives it (see
``chipbench/cell.py``).

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device``; the numbers that
decided ``correct`` follow on standard error, each beside its limit, and
close the JSON object under ``checks``. With no TPU, or fewer chips than
the cell asks for, the run exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# JAX's persistent compilation cache, at a fixed path inside the checkout
# (the path is part of the cache key), whatever the environment names.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
NO_CHIP_EXIT = 3


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def metrics_of(cell, out, trace: bool, log=log) -> dict:
    rec = out["record"]
    res = {}
    if not trace:
        values = dict(out["end_to_end"], setup_s=out["setup_s"])
        for m in cell.end_to_end:
            if m.name in values:
                res[m.name] = {"value": values[m.name], "unit": m.unit}
        return res
    for m in cell.per_layer:
        v = cell.reader(m.name).read(rec)
        if v is None:
            log(f"[metric] {m.name}: nothing to read in this run")
            continue
        res[m.name] = {"value": float(v), "unit": m.unit}
    return res


def use_cache() -> None:
    """Keep every compiled program in ``CACHE_DIR``, however small; the
    program's ``enable_compile_cache`` takes the directory it is given."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, t_process: float = T_PROCESS,
             peaks: dict = None, control: bool = False) -> dict:
    """One run; returns the result line as a dict (``checks`` last).
    ``require_chip=False`` (tests only) skips the look for a TPU and the
    persistent cache, and takes ``peaks`` in place of the table's."""
    from chipbench import cell as cellmod

    cell = cellmod.load(root, workload)
    if require_chip:
        use_cache()
    device = device_info(cell.chips, require_chip)
    log(f"[device] {device['platform']} {device['kind']} x{device['count']}")
    if peaks is None:
        peaks = cellmod.peaks(root, device["kind"])
    out = cell.kind().run(cell, seed, seconds, trace, t_process, peaks,
                          log=log, control=control)
    device["memory_peak_bytes"] = out["memory"].get("peak_bytes_in_use")
    device["memory_peak_reserved_bytes"] = out["memory"].get("peak_bytes_reserved")
    if trace:
        device["busy_s"] = out["record"].busy_s
        device["window_s"] = out["record"].window_s
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics_of(cell, out, trace),
            "device": device,
            "compiles_in_window": out["compiles_in_window"]}
    if trace and out["breakdown"] is not None:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--control", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(ROOT, args.workload, args.seed, args.seconds,
                        bool(args.trace), control=bool(args.control))
    except NoChip as e:
        log(f"[device] {e}")
        return NO_CHIP_EXIT
    for name, c in line["checks"].items():
        log(f"[check] {name} {c['value']} limit {c['limit']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
