"""The one load generator: an open-loop schedule drawn from a seed.

A traffic file (``traffic/<name>.json``) holds only parameters:

``rate``          mean arrivals per second (Poisson);
``keys``          ``{"dist": "uniform"}``: each request's key is drawn
                  uniformly from the key set, as SOSD's lookups are.

Every request is one point lookup of an existing key, named by its index
into the sorted key set.  Every request due in the window is served and
timed to its answer, also after the window closes.  The whole schedule is
a pure function of (traffic, key count, seconds, seed).
"""
from __future__ import annotations

import numpy as np


def validate(traffic: dict) -> dict:
    """Raise ValueError on a traffic file the generator cannot honour."""
    if not float(traffic.get("rate", 0)) > 0:
        raise ValueError(f"traffic rate must be > 0, got {traffic.get('rate')}")
    dist = traffic.get("keys", {}).get("dist")
    if dist != "uniform":
        raise ValueError(f"unknown key distribution {dist!r}")
    return traffic


def arrivals(rng, rate: float, seconds: float) -> np.ndarray:
    """Sorted due times in [0, seconds) of a Poisson process at ``rate``."""
    mean = rate * seconds
    n = int(mean + 8 * np.sqrt(mean) + 64)
    t = np.cumsum(rng.exponential(1.0, n))
    while t[-1] < mean:
        t = np.concatenate([t, t[-1] + np.cumsum(rng.exponential(1.0, n))])
    return t[t < mean] / rate


def schedule(traffic: dict, n_keys: int, seconds: float, seed: int):
    """→ ``(due, idx)``: sorted due times (seconds from the window's start,
    float64) and the index of each request's key in the sorted key set."""
    validate(traffic)
    rng = np.random.default_rng([int(seed), 0x7AFF1C])  # apart from the data
    due = arrivals(rng, float(traffic["rate"]), float(seconds))
    return due, rng.integers(0, int(n_keys), len(due), dtype=np.int64)
