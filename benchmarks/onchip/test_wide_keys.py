"""CPU tests of the 64-bit cell and its two readers: a cut of
``uniform_sparse64_airtune`` that keeps its keys' 64-bit domain is
``correct``, the readers of ``rebase_seconds`` and ``wide_queries`` read
their counters and stay silent on a program without them, and the wide
share reads 0 on ``uniform_airtune``'s keys and 100 on the 64-bit ones.

``test_onchip``'s made-up run record predates these counters: this module
adds them to it, as ``conftest.py`` adds the span counters, so that
``test_every_reader_reads_a_record`` covers the new readers too when the
directory's tests run together.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import deploy  # noqa: E402
import run  # noqa: E402
import test_onchip  # noqa: E402

CELL = "uniform_sparse64_airtune.uniform"
NEW_COUNTERS = ("rebase_seconds", "wide_queries")
SMALL_KEYS = 200_000
STATS = {"batches": 10, "pallas_batches": 8, "queries": 4_096,
         "rebase_seconds": 0.0004, "wide_queries": 1_024}
# (metric, reading of STATS worked out by hand)
EXPECTED = [("descent_rebase_ms.rated", 0.05),
            ("wide_query_share.rated", 25.0)]


def _with_new_counters(made_up):
    def record(*args, **kwargs):
        rec = made_up(*args, **kwargs)
        n = rec["stats"]["batches"]
        rec["stats"].update(queries=100 * n, rebase_seconds=2e-5 * n,
                            wide_queries=100 * n)
        return rec
    return record


test_onchip._record = _with_new_counters(test_onchip._record)


@pytest.mark.parametrize("metric,want", EXPECTED)
def test_new_reader_reads_its_counters(metric, want):
    assert run.reader(metric)({"stats": dict(STATS)}) == pytest.approx(want)


@pytest.mark.parametrize("metric", [m for m, _ in EXPECTED])
def test_new_reader_finds_nothing_without_the_counters(metric):
    older = {k: v for k, v in STATS.items() if k not in NEW_COUNTERS}
    assert run.reader(metric)({"stats": older}) is None


def _cut(cell: str, n_keys: int = SMALL_KEYS) -> dict:
    """The cell's parts at ``n_keys`` keys over the same key domain and a
    rate a CPU keeps up with."""
    parts = run.cell_parts(test_onchip.BENCH, cell)
    conf = parts["config"]
    domain = int(conf["n_keys"]) * int(conf["domain_factor"])
    parts["config"] = dict(conf, n_keys=n_keys,
                           domain_factor=domain // n_keys)
    parts["traffic"] = dict(parts["traffic"], rate=400)
    return parts


def test_sparse64_cut_is_correct():
    parts = _cut(CELL)
    keys = deploy.make_keys(parts["config"], 2**31 + 5)
    assert keys[-1] > 2**63       # the cut keeps the top bit in play
    out = run.run_cell(parts, seed=2**31 + 5, seconds=1.0, trace=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["widest_bytes"]["value"] <= 16520


@pytest.mark.parametrize("cell,share", [("uniform_airtune.uniform", 0.0),
                                        (CELL, 100.0)])
def test_wide_query_share_by_key_set(tmp_path, cell, share):
    conf = _cut(cell, 50_000)["config"]
    keys = deploy.make_keys(conf, 7)
    svc, _, _ = deploy.open_service(conf, keys,
                                    os.path.join(tmp_path, "index.air"))
    with svc:
        for n in (256, 512):
            svc.lookup(keys[np.random.default_rng(n).integers(0, len(keys),
                                                              n)])
        stats = run.stats_numbers(svc)
    assert stats["pallas_batches"] == stats["batches"] == 2
    assert run.reader("wide_query_share.rated")({"stats": stats}) == share
