"""The plain reference of a point lookup, its control, and the comparison
that decides ``correct``.

A lookup of key k over a sorted key set with fixed-size records answers a
byte range of the data; the configuration's guarantees are that the range
holds k's whole record, lies inside the data, and spans at most
``max_answer_bytes``.  The reference is ``np.searchsorted`` over the sorted
key array the benchmark made from the seed: it imports nothing of the
program and takes nothing the program made.

Two controls put the reference in the program's place, each a step below
the precision the device path works in.  ``control_lookup`` runs the rank
search in float32, the precision the device evaluates band lines in: above
2^24 neighbouring keys collapse, so its ranges miss.  ``wide_control_lookup``
carries each end of the reference's byte range in bfloat16 and widens it
by one bfloat16 ULP, as the device widens float32 band rows by their
slack: its ranges hold their records but are far wider than the
configuration allows.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


def record_ranges(keys: np.ndarray, q: np.ndarray, record: int) -> np.ndarray:
    """Reference answers: (n, 2) int64 byte range of each query's record."""
    lo = np.searchsorted(keys, np.asarray(q, dtype=keys.dtype)).astype(np.int64)
    lo *= record
    return np.stack([lo, lo + record], axis=1)


def control_lookup(keys: np.ndarray, record: int):
    """The control: a ``lookup(q)`` that answers from float32 keys."""
    k32 = keys.astype(np.float32)

    def lookup(q):
        q32 = np.asarray(q, dtype=np.uint64).astype(np.float32)
        lo = np.searchsorted(k32, q32).astype(np.int64) * record
        return np.stack([lo, lo + record], axis=1)
    return lookup


def wide_control_lookup(keys: np.ndarray, record: int):
    """The control for ``widest_bytes``: a ``lookup(q)`` whose range ends
    are rounded to bfloat16 and widened by one of its ULPs, clamped to the
    data."""
    data_size = len(keys) * record

    def lookup(q):
        want = record_ranges(keys, q, record).astype(np.float64)
        b = want.astype(ml_dtypes.bfloat16).astype(np.float64)
        ulp = np.abs(b) * 2.0 ** -7
        lo = np.maximum(np.floor(b[:, 0] - ulp[:, 0]), 0)
        hi = np.minimum(np.ceil(b[:, 1] + ulp[:, 1]), data_size)
        return np.stack([lo, hi], axis=1).astype(np.int64)
    return lookup


def compare(keys: np.ndarray, q: np.ndarray, got: np.ndarray, *,
            record: int, max_answer_bytes: int, unanswered: int,
            block: int = 1 << 22) -> dict:
    """Judge every answer: ``{name: (number, limit)}`` for each number
    compared, and ``correct``.  Blocks of rows keep the reference's memory
    flat whatever the window held."""
    data_size = len(keys) * record
    missed = 0
    widest = 0
    for s in range(0, len(q), block):
        want = record_ranges(keys, q[s:s + block], record)
        lo, hi = got[s:s + block, 0], got[s:s + block, 1]
        missed += int(np.count_nonzero((lo > want[:, 0]) | (hi < want[:, 1])
                                       | (lo < 0) | (hi > data_size)))
        if len(lo):
            widest = max(widest, int((hi - lo).max()))
    checks = {"missed": (missed, 0), "unanswered": (int(unanswered), 0),
              "widest_bytes": (widest, int(max_answer_bytes))}
    return {"checks": checks,
            "correct": all(v <= lim for v, lim in checks.values())}


# each control, by the name ``control.py`` reports it under
CONTROLS = {"float32_rank": control_lookup, "bfloat16_ends": wide_control_lookup}
