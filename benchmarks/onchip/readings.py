"""Reductions the metric readers share: each takes the run's record
(``run.run_cell`` builds it) and returns a number, or None where the run
has nothing to read — a reader never returns 0 for a share it could not
measure.

Record keys: ``seconds``, ``window_s``; per request ``due``,
``dispatched``, ``done`` (seconds from the window's start, NaN where never
served); ``calls`` rows (start, end, lookups); ``stats``: the window's
change in every ``ServeStats`` counter; ``setup_s``; ``prefix``: the
resident layers as (kind, entries); ``trace``: ``trace.reduce``'s dict
(traced runs only); ``peaks``: the chip's row of ``peaks.PEAKS``.
"""
from __future__ import annotations

import numpy as np

import work


def latency_ms(rec, pct: float):
    """Percentile of due → answer over every answered lookup due in the
    window, computed from all of them."""
    lat = rec["done"] - rec["due"]
    lat = lat[~np.isnan(lat)]
    return float(np.percentile(lat, pct)) * 1e3 if len(lat) else None


def queue_wait_ms(rec, pct: float):
    wait = rec["dispatched"] - rec["due"]
    wait = wait[~np.isnan(wait)]
    return float(np.percentile(wait, pct)) * 1e3 if len(wait) else None


def call_ms(rec):
    c = rec["calls"]
    return float(np.mean(c[:, 1] - c[:, 0])) * 1e3 if len(c) else None


def longest_call_ms(rec):
    c = rec["calls"]
    return float(np.max(c[:, 1] - c[:, 0])) * 1e3 if len(c) else None


def per_batch_ms(rec, counter: str, batches: str = "batches"):
    n = rec["stats"].get(batches, 0)
    return rec["stats"][counter] / n * 1e3 if n else None


def disk_walk_ms(rec):
    """(Σ lookup-call wall − descent seconds) ÷ batches: the host's time
    in a call outside the resident descent."""
    c, n = rec["calls"], rec["stats"].get("batches", 0)
    if not n:
        return None
    return (float(np.sum(c[:, 1] - c[:, 0]))
            - rec["stats"]["descent_seconds"]) / n * 1e3


def hit_rate_pct(rec):
    s = rec["stats"]
    touched = s["pages_hit"] + s["pages_fetched"]
    return 100.0 * s["pages_hit"] / touched if touched else None


def kernel_us(rec):
    t = rec["trace"]
    if not t or not t["kernel_calls"]:
        return None
    return t["kernel_s"] / t["kernel_calls"] * 1e6


def roofline_pct(rec):
    """Σ least time of every call ÷ the kernel's summed device time.
    None unless the trace holds one kernel event per call."""
    t = rec["trace"]
    if not t or not t["kernel_s"] or t["kernel_calls"] != len(rec["calls"]):
        return None
    least = sum(work.least_seconds(rec["prefix"], int(q), rec["peaks"])[0]
                for q in rec["calls"][:, 2])
    return 100.0 * least / t["kernel_s"]


def idle_pct(rec):
    t = rec["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
