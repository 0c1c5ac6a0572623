"""What every traffic generator shares: reading a mix's data file, seeds,
and lengths drawn as a fixed grid of a distribution's quantiles.

A traffic mix is ``traffic/<mix>.json``: parameters only. Its ``kind``
names the module ``drivers/<kind>.py`` that generates its inputs from the
seed and runs the cell; that module's docstring lists the parameters it
reads. A mix may carry a ``rehearsal`` object whose keys replace its own
for a run at tiny sizes on the CPU.

Lengths are ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
"max": b}`` or ``{"dist": "uniform", "min": a, "max": b}``. A generator
takes the same ``n`` lengths for every seed (the distribution's quantiles
at ``(i + 0.5) / n``) and lets the seed order them, so the work a run can
take on does not move with the seed.
"""
from __future__ import annotations

import json
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, *, rehearsal: bool = False) -> dict:
    """The traffic mix ``bench/traffic/<name>.json``."""
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        spec = json.load(f)
    over = spec.pop("rehearsal", {})
    if rehearsal:
        spec = merge(spec, over)
    return spec


def merge(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys put in, nested objects merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (merge(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**64: both 32-bit halves."""
    import jax

    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def quantile_grid(dist: dict, n: int) -> np.ndarray:
    """The ``n`` lengths at the quantiles ``(i + 0.5) / n`` of ``dist``,
    clipped to its ``[min, max]``, as int32."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in q])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + q * (dist["max"] + 1 - dist["min"])
        x = np.floor(x)
    else:
        raise ValueError(f"unknown length dist {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int32)


def residual_grid(dist: dict, n: int, fine: int = 4096) -> np.ndarray:
    """The ``n`` quantiles of what is left of a length drawn from ``dist``
    when it is seen at a random moment of its course: ``P(R = r)`` is
    proportional to ``P(L >= r)``, for ``r`` from 1 to the longest."""
    lengths = quantile_grid(dist, fine)
    r = np.arange(1, lengths.max() + 1)
    weight = (lengths[None, :] >= r[:, None]).sum(1).astype(np.float64)
    cdf = np.cumsum(weight) / weight.sum()
    q = (np.arange(n) + 0.5) / n
    return r[np.searchsorted(cdf, q)].astype(np.int32)


def exponential_grid(n: int) -> np.ndarray:
    """The ``n`` quantiles ``(i + 0.5) / n`` of the exponential distribution,
    scaled to a mean of exactly 1: gaps between the arrivals of a Poisson
    process of rate 1."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps / gaps.mean()
