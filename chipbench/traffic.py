"""Open-loop request schedules built from a traffic file and a seed.

A traffic file (``chipbench/traffic/<name>.json``) gives the arrival
process, the prompt and output length distributions, and ``fill_s``.

A schedule has two parts, each offered at the traffic's rate:

- the fill: ``round(rate_per_s * fill_s)`` requests due in
  ``[-fill_s, 0)``, before the window opens, so that the window finds
  the engine at the occupancy its rate keeps (about one request's
  lifetime of arrivals), not empty;
- the window: ``round(rate_per_s * seconds)`` requests due in
  ``[0, seconds)``.

The gaps, prompt lengths and output lengths of each part are one set
drawn from ``MASTER_SEED``; the run's seed orders each of them apart
(so it pairs lengths and arrivals anew) and draws the token ids. So
every seed offers the same work in its own order. At the cells' rates a
window holds ten to twenty requests, and a set of so few drawn afresh
for each seed moves the work a window holds by more than the system
moves it.

Arrivals:

- ``poisson``: exponential gaps at ``rate_per_s``;
- ``gamma``: a Gamma renewal process with coefficient of variation
  ``cv`` (shape ``1 / cv**2``) at a mean of ``rate_per_s``.

Lengths: ``lognormal`` with ``median`` and ``sigma``, rounded and
clipped to ``[min, max]``.

Each part's gaps are scaled so that its requests arrive inside its
interval: the offered rate is exact.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

MASTER_SEED = 20240117


@dataclass(frozen=True)
class Arrival:
    index: int
    due_s: float            # offset from the window's start (< 0: the fill)
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def _lengths(rng: np.random.Generator, spec: Dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rng: np.random.Generator, spec: Dict, n: int) -> np.ndarray:
    rate = float(spec["rate_per_s"])
    if spec["process"] == "poisson":
        return rng.exponential(1.0 / rate, size=n)
    if spec["process"] == "gamma":
        shape = 1.0 / float(spec["cv"]) ** 2
        return rng.gamma(shape, 1.0 / (rate * shape), size=n)
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def _draw(master: np.random.Generator, traffic: Dict, n: int):
    return (_gaps(master, traffic["arrivals"], n),
            _lengths(master, traffic["prompt_tokens"], n),
            _lengths(master, traffic["output_tokens"], n))


def _part(rng: np.random.Generator, sets, start: float, length: float):
    gaps, prompts, outputs = (rng.permutation(x) for x in sets)
    if len(gaps) == 0:
        return []
    # arrival i is due after the first i gaps; the gaps span the part
    due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (
        length / gaps.sum())
    return list(zip(due.tolist(), prompts.tolist(), outputs.tolist()))


def schedule(traffic: Dict, seed: int, seconds: float,
             vocab_size: int) -> List[Arrival]:
    """The fill's and the window's requests, in arrival order."""
    rate = float(traffic["arrivals"]["rate_per_s"])
    fill_s = float(traffic.get("fill_s", 0.0))
    n_fill = int(round(rate * fill_s))
    n_win = max(1, int(round(rate * seconds)))
    master = np.random.default_rng(MASTER_SEED)
    fill_sets = _draw(master, traffic, n_fill)
    win_sets = _draw(master, traffic, n_win)

    rng = np.random.default_rng(seed)
    parts = (_part(rng, fill_sets, -fill_s, fill_s)
             + _part(rng, win_sets, 0.0, seconds))
    return [Arrival(i, float(due),
                    rng.integers(0, vocab_size, size=int(p), dtype=np.int32),
                    int(o))
            for i, (due, p, o) in enumerate(parts)]
