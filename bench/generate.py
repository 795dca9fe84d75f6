"""The one traffic generator: a mix file of parameters in, a timed list of
requests out.

Every mix is a JSON file under ``bench/traffic/``.  Its keys:

* ``source``: where the mix's numbers come from.
* ``arrivals``: ``{"cv": c}``: open loop at the cell's ``rate_per_s``
  with gamma-distributed gaps of coefficient of variation ``c`` (1 is
  Poisson; above 1 is burstier).
* ``populations``: a list of request kinds, each with ``share`` (of all
  requests), ``prompt`` and ``output`` (lognormal lengths: ``median`` and
  ``sigma``, clipped to ``[min, max]``) and, optionally, the sampling
  knobs ``temperature`` and ``top_k`` (absent: greedy).

A run of ``seconds`` at ``rate_per_s`` holds exactly
``round(rate_per_s * seconds)`` requests, all due inside the window: the
gaps are scaled so that they span it.  Every random quantity is drawn on a
fixed grid of quantiles, each block of ``STRATA`` consecutive draws
holding one value of every stratum.  Each seed therefore gets the same
set of sizes, gaps and kinds in another order, so the work of a run does
not depend on the seed.

The engine sees only the token ids: prompts are uniform random ids drawn
from the seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np
from scipy.special import gammaincinv

STRATA = 16
_NORMAL = NormalDist()


@dataclass
class Item:
    """One request of the schedule: due ``due_s`` after the window opens."""
    due_s: float
    prompt: list
    max_new: int
    temperature: float = 0.0
    top_k: int = 0


def stratified_quantiles(n: int, rng: np.random.Generator) -> np.ndarray:
    """The ``n`` points of the quantile grid ``(j + 0.5) / n``, ordered so
    that every whole block of ``STRATA`` consecutive points holds one point
    of each of ``STRATA`` slices of the grid (as equal as ``n`` allows).
    The set of points depends on ``n`` only; the order comes from
    ``rng``."""
    grid = (np.arange(n) + 0.5) / n
    slices = [rng.permutation(s) for s in np.array_split(grid, STRATA)]
    out = []
    for i in range(len(slices[0])):
        block = [s[i] for s in slices if i < len(s)]
        out.extend(block[j] for j in rng.permutation(len(block)))
    return np.array(out)


def lognormal_lengths(u: np.ndarray, spec: dict) -> np.ndarray:
    """Lognormal quantiles ``median * exp(sigma * z(u))``, clipped."""
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)


def gamma_gaps(u: np.ndarray, mean: float, cv: float) -> np.ndarray:
    """Gamma quantiles of mean ``mean`` and coefficient of variation
    ``cv`` (shape ``1 / cv**2``; ``cv`` 1 gives exponential gaps)."""
    k = 1.0 / cv ** 2
    return mean * gammaincinv(k, u) / k


def arrival_times(mix: dict, rng, rate: float | None, seconds: float):
    """``round(rate * seconds)`` arrival times in [0, seconds): a fixed set
    of gaps in the seed's order, scaled to span the window, the first
    request opening it."""
    if not rate or rate <= 0:
        raise ValueError("an open-loop mix needs the cell's rate_per_s > 0")
    n = max(1, round(rate * seconds))
    gaps = gamma_gaps(stratified_quantiles(n, rng), 1.0 / rate,
                      float(mix["arrivals"]["cv"]))
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps) - gaps


def generate(mix: dict, *, vocab: int, seed: int, seconds: float,
             rate_per_s: float | None = None) -> list[Item]:
    """The schedule of one run, sorted by due time."""
    rng = np.random.default_rng(seed)
    times = arrival_times(mix, rng, rate_per_s, seconds)
    n = len(times)
    pops = mix["populations"]
    edges = np.cumsum([p["share"] for p in pops])
    if not math.isclose(edges[-1], 1.0):
        raise ValueError("the populations' shares do not add up to 1")
    kind = np.minimum(np.searchsorted(edges, stratified_quantiles(n, rng),
                                      side="right"), len(pops) - 1)
    sizes = np.zeros((n, 2), int)
    for i, p in enumerate(pops):
        mine = np.flatnonzero(kind == i)
        for col, key in enumerate(("prompt", "output")):
            sizes[mine, col] = lognormal_lengths(
                stratified_quantiles(len(mine), rng), p[key])
    return [Item(float(t), rng.integers(0, vocab, int(pl)).tolist(), int(ol),
                 float(pops[k].get("temperature", 0.0)),
                 int(pops[k].get("top_k", 0)))
            for t, k, (pl, ol) in zip(times, kind, sizes)]
