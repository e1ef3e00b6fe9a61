"""CPU tests of the benchmark's own code: ``pytest benchmarks/onchip``.

They cover the loader against every cell of ``BENCHMARK.json``, the load
generator, the work counts, the trace reduction on a trace recorded on a
v5e, the refusal to run without a chip, and the comparison that decides
``correct``: a sound run passes it, and the control and each planted fault
fail it.  Cells run here at a few hundred thousand keys with the Pallas
kernel interpreted; the chip and compiled-Pallas checks are skipped by
calling ``run.run_cell`` without a device.
"""
from __future__ import annotations

import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))

import keys  # noqa: E402
import peaks  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import trace  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

BENCH = run.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
BENCH_UNITS = {m["name"]: m["unit"]
               for m in BENCH["end_to_end"] + BENCH["per_layer"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
FIXTURE = HERE / "testdata" / "airtune_v5e.xplane.pb"
SMALL_KEYS = 200_000
# the control needs keys above 2^24, where float32 stops telling
# neighbours apart: 8M drawn keys reach about 4.9e7
CONTROL_KEYS = 8_000_000


# -- the benchmark's data, found by name --------------------------------------
@pytest.mark.parametrize("cell", CELLS)
def test_loader_resolves_every_cell(cell):
    parts = run.cell_parts(BENCH, cell)
    traffic.validate(parts["traffic"])
    assert "setup_s" in parts["end_to_end"] and len(parts["end_to_end"]) >= 2
    assert parts["per_layer"]
    for m in parts["end_to_end"] + parts["per_layer"]:
        assert callable(run.reader(m)), m
    conf = parts["config"]
    for key in ("keys", "n_keys", "draw_factor", "domain_factor",
                "record_bytes", "page_bytes", "design",
                "serve", "max_batch", "batch_multiple", "kernel_pattern",
                "max_answer_bytes"):
        assert key in conf, key
    assert conf["max_batch"] % conf["batch_multiple"] == 0
    assert conf["serve"]["backend"] == "pallas"


def test_benchmark_names_and_moves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    names = [c["name"] for c in BENCH["configs"]] + CELLS + list(e2e) + [
        m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["per_layer"]:
        # every cell that reports a per-layer metric reports what it moves
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS)), m
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]


def _record(n_calls=40, per_call=100):
    """A run's record as ``run.run_cell`` builds it, from a made-up window."""
    rng = np.random.default_rng(4)
    n = n_calls * per_call
    due = np.sort(rng.random(n)) * 2.0
    dispatched = due + rng.random(n) * 1e-3
    done = dispatched + 5e-3
    starts = dispatched[::per_call]
    calls = np.stack([starts, starts + 5e-3, np.full(n_calls, per_call)], 1)
    stats = {"batches": n_calls, "pallas_batches": n_calls,
             "descent_seconds": 0.1, "pread_seconds": 0.02,
             "pages_hit": 30, "pages_fetched": 70}
    red = {"window_s": 2.0, "busy_s": 0.01, "kernel_calls": n_calls,
           "kernel_s": n_calls * 1e-5}
    return {"seconds": 2.0, "window_s": 2.0, "due": due,
            "dispatched": dispatched, "done": done, "calls": calls,
            "stats": stats, "setup_s": 60.0, "prefix": [("band", 184)],
            "trace": red, "peaks": peaks.peaks_for("TPU v5 lite")}


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]
                                    + BENCH["per_layer"]])
def test_every_reader_reads_a_record(metric):
    v = run.reader(metric)(_record())
    assert v is not None and math.isfinite(v) and v >= 0, (metric, v)
    if BENCH_UNITS[metric] == "%":
        assert v <= 100.0


def test_readers_find_nothing_in_an_untraced_run():
    rec = dict(_record(), trace=None)
    for m in BENCH["per_layer"]:
        if m["source"] == "device_trace":
            assert run.reader(m["name"])(rec) is None, m["name"]


# -- key set and load generator ------------------------------------------------
def test_key_set_is_a_function_of_the_seed():
    conf = {"keys": "uniform", "n_keys": 50_000, "draw_factor": 1.3,
            "domain_factor": 8}
    a = keys.uniform(conf, 2**31 + 17)
    assert np.array_equal(a, keys.uniform(conf, 2**31 + 17))
    assert not np.array_equal(a, keys.uniform(conf, 2**31 + 18))
    assert a.dtype == np.uint64 and np.all(np.diff(a) > 0)
    assert a[0] >= 1 and a[-1] < 8 * 50_000
    # the n smallest of 1.3 n draws over [1, 8 n): keys below about 6.2 n,
    # a few percent of them repeats
    assert 0.9 * 50_000 < len(a) < 50_000
    assert a[-1] == pytest.approx(8 * 50_000 / 1.3, rel=0.02)
    with pytest.raises(ValueError, match="unknown key set"):
        keys.uniform(dict(conf, keys="zipf"), 1)


def test_schedule_is_a_function_of_the_seed():
    mix = {"rate": 5000, "keys": {"dist": "uniform"}}
    a = traffic.schedule(mix, 10_000, 2.0, 2**31 + 17)
    b = traffic.schedule(mix, 10_000, 2.0, 2**31 + 17)
    c = traffic.schedule(mix, 10_000, 2.0, 2**31 + 18)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    due, idx = a
    assert np.all(np.diff(due) >= 0) and due[-1] < 2.0
    assert abs(len(due) - 10_000) < 5 * math.sqrt(10_000)
    assert idx.min() >= 0 and idx.max() < 10_000
    with pytest.raises(ValueError, match="unknown key distribution"):
        traffic.schedule(dict(mix, keys={"dist": "zipf"}), 10, 1.0, 1)




# -- work and peaks ---------------------------------------------------------------
def test_work_counts_hand_computed():
    # one band layer of 100 nodes, 4096 queries
    layers = [("band", 100)]
    assert work.call_bytes(layers, 4096) == (4096 * 4 + 2 * 1 * 4096 * 4
                                             + 100 * 16)
    assert work.call_ops(layers, 4096) == 4096 * (7 + 5)      # ⌈log2 101⌉ = 7
    # a two-layer step prefix: 5 root entries over 1410 middle entries
    layers = [("step", 5), ("step", 1410)]
    assert work.call_bytes(layers, 256) == (256 * 4 + 2 * 2 * 256 * 4
                                            + (5 + 1410) * 8)
    assert work.call_ops(layers, 256) == 256 * (3 + 11)
    t, bound = work.least_seconds(layers, 256, peaks.peaks_for("TPU v5 lite"))
    assert bound == "hbm" and t == pytest.approx(
        work.call_bytes(layers, 256) / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks_for("TPU v9 imaginary")


# -- trace reduction --------------------------------------------------------------
def test_trace_reduction_on_a_recorded_v5e_trace():
    red = trace.reduce(FIXTURE, "fused")
    assert red["chips"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["kernel_calls"] > 0 and 0 < red["kernel_s"] <= red["busy_s"]
    assert len(red["device_ops"]) <= trace.TOP
    assert len(red["idle_gaps"]) <= trace.TOP
    names = {n for n, _ in red["idle_gaps"]}
    assert names <= {"onchip.lookup", "onchip.idle", "no span"}
    gaps = [s for _, s in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    # busy plus the idle gaps never exceeds the window
    assert red["busy_s"] + sum(gaps) <= red["window_s"] + 1e-9


def test_union_of_intervals():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


# -- no chip, no result ------------------------------------------------------------
def _run_cli(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/onchip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_the_cpu():
    p = _run_cli(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""


# -- correct: a sound run passes, the control and each fault fail -------------------
def _small(cell, rate, n_keys=SMALL_KEYS):
    parts = run.cell_parts(BENCH, cell)
    parts["config"] = dict(parts["config"], n_keys=n_keys)
    parts["traffic"] = dict(parts["traffic"], rate=rate)
    return parts


def _shifted(lookup, keys):
    """Fault: every answer moved past its own end where it is produced."""
    def f(q):
        got = lookup(q)
        return got + (got[:, 1] - got[:, 0])[:, None]
    return f


def _half_batch(lookup, keys):
    """Fault: half of each batch is left out: only every other key is
    looked up, and each left-out key gets its neighbour's answer."""
    def f(q):
        return np.repeat(lookup(q[::2]), 2, axis=0)[:len(q)]
    return f


def _half_raises(lookup, keys):
    """Fault: every other call raises, so its lookups are never answered."""
    n = [0]

    def f(q):
        n[0] += 1
        if n[0] % 2:
            raise OSError("planted fault")
        return lookup(q)
    return f


def _control(lookup, keys):
    return reference.control_lookup(keys, 16)


def _wide_control(lookup, keys):
    return reference.wide_control_lookup(keys, 16)


@pytest.mark.parametrize("cell", ["uniform_airtune.uniform", "uniform_btree.uniform"])
def test_sound_run_is_correct(cell):
    out = run.run_cell(_small(cell, 400), seed=2**31 + 5, seconds=1.0,
                       trace=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"setup_s", "lookup_p50_ms"}


@pytest.mark.parametrize("wrapper,number", [(_control, "missed"),
                                            (_wide_control, "widest_bytes"),
                                            (_shifted, "missed"),
                                            (_half_batch, "missed"),
                                            (_half_raises, "unanswered")])
@pytest.mark.parametrize("cell", ["uniform_airtune.uniform", "uniform_btree.uniform"])
def test_control_and_faults_are_not_correct(cell, wrapper, number):
    n = CONTROL_KEYS if wrapper in (_control, _wide_control) else SMALL_KEYS
    out = run.run_cell(_small(cell, 400, n), seed=2**31 + 9, seconds=1.0,
                       trace=False, lookup_wrapper=wrapper)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"]


def test_widest_answer_is_held_to_the_configuration():
    keys = np.arange(1, 1001, dtype=np.uint64) * 7
    q = keys[[3, 500]]
    got = reference.record_ranges(keys, q, 16)
    ok = reference.compare(keys, q, got, record=16, max_answer_bytes=16,
                           unanswered=0)
    assert ok["correct"]
    got[1] = [0, 16 * 1000]        # holds the record, spans the whole data
    bad = reference.compare(keys, q, got, record=16, max_answer_bytes=4096,
                            unanswered=0)
    assert not bad["correct"] and bad["checks"]["missed"][0] == 0


def test_wide_control_holds_every_record_but_is_too_wide():
    keys_ = np.arange(1, 4_000_001, dtype=np.uint64) * 3
    q = keys_[::997]
    got = reference.wide_control_lookup(keys_, 16)(q)
    v = reference.compare(keys_, q, got, record=16, max_answer_bytes=40960,
                          unanswered=0)
    assert v["checks"]["missed"][0] == 0
    assert v["checks"]["widest_bytes"][0] > 40960 and not v["correct"]
