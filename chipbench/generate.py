"""Inputs from ``--seed``: scenario designs and open-loop request schedules.

Every seed gets the same *set* of sizes and gaps, drawn at fixed
quantiles of the traffic file's distributions, in a seed-dependent order,
with seed-dependent token ids and scenario values.  So two seeds do the
same amount of work, and the spread between runs is the system's, not
the draw's.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); seeds may exceed 32
    bits."""
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def _normal_quantiles(n: int) -> np.ndarray:
    """Standard-normal quantiles at the midpoints ``(i + 0.5) / n``."""
    from statistics import NormalDist
    nd = NormalDist()
    return np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths at fixed quantiles, shuffled.

    ``{"kind": "lognormal", "median": m, "sigma": s, "lo": a, "hi": b}``
    is ``exp(N(ln m, s))`` clipped to ``[a, b]`` (the same law as the
    serving code's ``LengthDist("lognormal")``); ``{"kind": "fixed",
    "value": v}`` is ``v``.
    """
    if spec["kind"] == "fixed":
        out = np.full(n, int(spec["value"]))
    elif spec["kind"] == "lognormal":
        z = _normal_quantiles(n)
        out = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        out = np.clip(np.rint(out), spec["lo"], spec["hi"]).astype(np.int64)
    else:
        raise ValueError(f"unknown length kind {spec['kind']!r}")
    return rng.permutation(out)


@dataclass(frozen=True)
class Request:
    due: float             # seconds after the window opens
    prompt: np.ndarray     # int32 token ids
    max_new: int


def open_loop(traffic: dict, seconds: float, seed: int, vocab: int) -> list:
    """Poisson arrivals on the wall clock at ``traffic["rate"]`` per
    second over ``seconds``: ``round(rate * seconds)`` requests whose
    gaps are exponential quantiles in a seeded order, scaled so that the
    last one is due before the window closes."""
    n = max(1, round(traffic["rate"] * seconds))
    rng = rng_for(seed, 1)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    due = np.cumsum(gaps) - gaps[0]
    due *= seconds * (n - 0.5) / n / max(due[-1] + gaps.mean(), 1e-9)
    p_len = lengths(traffic["prompt"], n, rng_for(seed, 2))
    o_len = lengths(traffic["output"], n, rng_for(seed, 3))
    tok = rng_for(seed, 4)
    return [Request(due=float(t), max_new=int(o),
                    prompt=tok.integers(0, vocab, size=int(p),
                                        dtype=np.int32))
            for t, p, o in zip(due, p_len, o_len)]


def lhs(n: int, ranges: dict, choices: dict, seed: int) -> tuple:
    """Latin-hypercube design, as the pricing engine's ``ParamGrid.sample``
    draws it: one value per ``1/n`` stratum of each numeric axis, in a
    seeded order, and near-even shuffled codes for each categorical axis.
    Returns ``(columns, codes)``: ``{axis: (n,) float64}`` and ``{axis:
    (n,) int32}`` indexing ``choices[axis]``."""
    rng = rng_for(seed, 5)
    cols = {}
    for name, (lo, hi) in ranges.items():
        u = (rng.permutation(n) + rng.uniform(size=n)) / n
        cols[name] = lo + u * (hi - lo)
    codes = {}
    for name, opts in choices.items():
        idx = np.tile(np.arange(len(opts)), -(-n // len(opts)))[:n]
        rng.shuffle(idx)
        codes[name] = idx.astype(np.int32)
    return cols, codes
