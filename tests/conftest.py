"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see the real
single-device CPU."""
import numpy as np
import pytest

from repro.core import KeyPositions


def make_keys(kind: str, n: int, seed: int = 0) -> np.ndarray:
    """Synthetic key distributions mirroring the paper's datasets."""
    rng = np.random.default_rng(seed)
    if kind == "uniform":            # uden64-like
        keys = rng.integers(1, 2**50, n, dtype=np.uint64)
    elif kind == "gmm":              # paper's gmm
        c = rng.uniform(2**30, 2**44, 64)
        keys = np.abs(np.concatenate(
            [rng.normal(ci, 2**26, n // 64 + 1) for ci in c]))[:n]
        keys = keys.astype(np.uint64) + 1
    elif kind == "books":            # heavy-tailed cumulative counts
        gaps = rng.zipf(1.3, n).astype(np.uint64)
        keys = np.cumsum(gaps)
    elif kind == "fb":               # piecewise near-linear with jumps
        base = np.sort(rng.integers(1, 2**34, n).astype(np.uint64))
        jumps = (rng.random(n) < 1e-4) * rng.integers(2**38, 2**40, n)
        keys = base + np.cumsum(jumps.astype(np.uint64))
    else:
        raise ValueError(kind)
    return np.unique(np.sort(keys))


@pytest.fixture(scope="session")
def gmm_small():
    keys = make_keys("gmm", 50_000)
    return KeyPositions.fixed_record(keys, 16)


@pytest.fixture(scope="session")
def uniform_small():
    keys = make_keys("uniform", 50_000)
    return KeyPositions.fixed_record(keys, 16)
