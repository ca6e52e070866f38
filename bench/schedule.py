"""What a traffic generator hands the harness, and the draws generators share.

A traffic mix is a data file ``bench/traffic/<traffic>.json``.  Its
``generator`` key names ``bench/generators/<generator>.py``, whose
``generate(traffic, seed, vocab, cache_len, seconds)`` returns a
``Schedule``; the schedule's ``loop`` names ``bench/loops/<loop>.py``, whose
``run(driver, schedule, t0, end)`` offers it to the engine.  A new kind of
traffic is a new data file, or a new generator or loop file beside these.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Planned:
    idx: int
    due_s: float | None          # offset from the window's start (open loop)
    prompt: np.ndarray           # (P,) int32
    max_new: int


@dataclass
class Schedule:
    loop: str                    # names bench/loops/<loop>.py
    requests: list
    prompt_range: tuple          # (shortest, longest) prompt the loop can submit
    out_max: int                 # most tokens a request may ask for
    clients: int = 0
    think_s: float = 0.0


def seed_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(int(seed)))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The n stratified draws of a length distribution, in ascending order:
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}`` (inclusive)."""
    q = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        vals = lo + np.floor(q * (hi - lo + 1))
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        vals = np.rint(np.exp(math.log(spec["median"]) + spec["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(vals, lo, hi).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """The n stratified draws of Poisson inter-arrival gaps, ascending."""
    return -np.log1p(-_quantiles(n)) / rate
