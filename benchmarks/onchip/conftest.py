"""The engine's span counters, added to the made-up run record that
``test_onchip`` checks every reader against, so that the readers of those
counters are covered there as well; ``test_span_readers`` checks their
values and their silence on a program without the counters."""
from __future__ import annotations

import pytest

# ServeStats' span counters over a made-up window, per call (seconds,
# bytes and windows); consistent with the record's descent_seconds
SPAN_COUNTERS_PER_CALL = {
    "lookup_seconds": 0.19 / 40, "descent_stage_seconds": 0.02 / 40,
    "descent_launch_seconds": 0.05 / 40, "descent_collect_seconds": 0.025 / 40,
    "h2d_bytes": 11_268, "walk_seconds": 0.08 / 40,
    "walk_fetch_seconds": 0.01 / 40, "walk_windows": 35}


@pytest.fixture(autouse=True)
def _span_counters_in_record(request, monkeypatch):
    mod = request.module
    if mod.__name__ != "test_onchip":
        return
    made_up = mod._record

    def record(*args, **kwargs):
        rec = made_up(*args, **kwargs)
        n = rec["stats"]["batches"]
        rec["stats"].update({k: v * n
                             for k, v in SPAN_COUNTERS_PER_CALL.items()})
        return rec

    monkeypatch.setattr(mod, "_record", record)
