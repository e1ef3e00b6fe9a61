"""The benchmark's key set, made from the seed by the benchmark itself.

Uniform synthetic keys, after SOSD's uniform key sets (Marcus, Kipf et
al., "Benchmarking Learned Indexes", VLDB 2020): ``draw_factor · n_keys``
integers drawn uniformly from ``[1, domain_factor · n_keys)``, sorted, the
``n_keys`` smallest kept and duplicates dropped.  The reference and the
program under test both see this one array; the reference takes nothing
the program made.
"""
from __future__ import annotations

import numpy as np

# a fixed offset that keeps the key stream apart from the traffic's
STREAM = 56326


def uniform(config: dict, seed: int) -> np.ndarray:
    """Sorted unique uint64 keys of the configuration's data set."""
    if config["keys"] != "uniform":
        raise ValueError(f"unknown key set {config['keys']!r}")
    n = int(config["n_keys"])
    rng = np.random.default_rng(int(seed) + STREAM)
    draws = rng.integers(1, n * int(config["domain_factor"]),
                         int(n * float(config["draw_factor"])),
                         dtype=np.uint64)
    return np.unique(np.sort(draws)[:n])
