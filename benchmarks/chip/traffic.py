"""The one traffic generator: turns a traffic file's parameters and a seed
into requests.

Every seed gets the same multiset of inter-arrival gaps, prompt lengths and
output lengths (stratified quantiles of the file's distributions), in an
order and with token ids drawn from the seed.  So two seeds offer the same
work, and a run-to-run difference between seeds is the system's, not the
draw's.

An open-loop file::

    {"loop": "open", "rate_per_s": 8.0, "warmup_s": 5.0, "drain_cap_s": 60,
     "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                "min": 64, "max": 2048},
     "output": {"dist": "fixed", "value": 16}}
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of an open-loop schedule; ``due`` is in seconds from the
    opening of the measured window (negative during warm-up)."""

    rid: int
    due: float
    prompt: np.ndarray
    max_new: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator for ``stream`` of ``seed`` (any size)."""
    return np.random.default_rng([seed, stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a length distribution, in ascending order:
    ``fixed`` (``value``), ``lognormal`` (``median``, ``sigma``),
    ``loguniform``; clipped to [``min``, ``max``] where given."""
    u = _quantiles(n)
    kind = spec["dist"]
    if kind == "fixed":
        x = np.full(n, float(spec["value"]))
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(q) for q in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "loguniform":
        lo, hi = math.log(spec["min"]), math.log(spec["max"])
        x = np.exp(lo + u * (hi - lo))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    x = np.rint(x)
    if "min" in spec or "max" in spec:
        x = np.clip(x, spec.get("min", 1), spec.get("max", np.inf))
    return x.astype(np.int64)


def arrivals(rate: float, n: int, span: float, rng: np.random.Generator
             ) -> np.ndarray:
    """``n`` Poisson arrival times in [0, span): the exponential
    inter-arrival quantiles of ``rate`` in the order ``rng`` draws, scaled so
    that the ``n`` gaps fill ``span`` exactly."""
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-_quantiles(n)) / rate
    gaps = rng.permutation(gaps) * (span / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def open_loop(spec: dict, seed: int, seconds: float, vocab: int
              ) -> List[Planned]:
    """The requests of an open-loop run: ``warmup_s`` seconds of warm-up
    traffic, then ``seconds`` of measured window, sorted by due time."""
    rate, warm = spec["rate_per_s"], spec["warmup_s"]
    rng = rng_for(seed, 0)
    plan: List[Planned] = []
    for start, span in ((-warm, warm), (0.0, seconds)):
        n = int(round(rate * span))
        due = start + arrivals(rate, n, span, rng)
        p_len = rng.permutation(lengths(spec["prompt"], n))
        o_len = rng.permutation(lengths(spec["output"], n))
        for t, pl, ol in zip(due, p_len, o_len):
            plan.append(Planned(
                rid=len(plan), due=float(t),
                prompt=rng.integers(0, vocab, int(pl)).astype(np.int32),
                max_new=int(ol)))
    return plan


def output_bound(spec: dict) -> int:
    """The longest answer a file can ask for."""
    o = spec["output"]
    return int(o["value"]) if o["dist"] == "fixed" else int(o["max"])

