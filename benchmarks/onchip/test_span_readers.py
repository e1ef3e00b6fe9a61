"""CPU tests of the readers of the engine's span counters: each reads
its counters' change over the window, and a program whose ``ServeStats``
lacks them gives no reading rather than an error."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402

SPAN_COUNTERS = ("lookup_seconds", "descent_stage_seconds",
                 "descent_launch_seconds", "descent_collect_seconds",
                 "h2d_bytes", "walk_seconds", "walk_fetch_seconds",
                 "walk_windows")
STATS = {"batches": 10, "pallas_batches": 10, "descent_seconds": 0.07,
         "lookup_seconds": 0.8, "descent_stage_seconds": 0.01,
         "descent_launch_seconds": 0.04, "descent_collect_seconds": 0.015,
         "h2d_bytes": 10 * 86_024 + 4 * 20_480, "walk_seconds": 0.72,
         "walk_fetch_seconds": 0.06, "walk_windows": 11_000}
# (metric, reading of STATS worked out by hand)
EXPECTED = [
    ("engine_self_ms.rated", (0.8 - 0.07 - 0.72) / 10 * 1e3),
    ("descent_stage_ms.rated", 1.0),
    ("descent_launch_ms.rated", 4.0),
    ("descent_collect_ms.rated", 1.5),
    ("h2d_bytes_per_batch.rated", 86_024 + 4 * 2_048),
    ("walk_ms.rated", 72.0),
    ("page_fetch_ms.rated", 6.0),
    ("walk_window_us.rated", 0.66 / 11_000 * 1e6),
]


@pytest.mark.parametrize("metric,want", EXPECTED)
def test_reader_reads_its_counters(metric, want):
    assert run.reader(metric)({"stats": dict(STATS)}) == pytest.approx(want)


@pytest.mark.parametrize("metric", [m for m, _ in EXPECTED])
def test_reader_finds_nothing_without_the_counters(metric):
    older = {k: v for k, v in STATS.items() if k not in SPAN_COUNTERS}
    assert run.reader(metric)({"stats": older}) is None
