"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything about a cell is data, found by name: the cell in
``BENCHMARK.json`` names its configuration (``configs/<name>.json``) and
traffic (``traffic/<name>.json``); each metric is a reader
``metrics/<metric>.py`` with one function ``read(rec)``.  Set-up (process
start, key set, build, save, open, warm-up of every batch size) is timed
as ``setup_s``; then the dispatcher drives ``IndexService.lookup``
open-loop for ``--seconds``.  Afterwards every answered lookup is compared
with the plain reference (``reference.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``busy_s``/``window_s`` and a ``breakdown``), and last ``checks``: each
number compared beside its limit, also printed as the last lines of
standard error.  The run exits non-zero and prints no result when the
first device is not a TPU or fewer chips are present than the cell asks
for, when a batch was served by anything but compiled Pallas, or when a
program compiled inside the window.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
T_IMPORT = time.perf_counter()
# the compiled kernel serves; anything else is not the cell's path
OTHER_BACKENDS = ("interpret", "jnp", "numpy", "numpy_width",
                  "numpy_key_range", "numpy_query_range")


class RunError(RuntimeError):
    """A run that must end with a non-zero exit and no result line."""


def say(msg: str) -> None:
    print(f"onchip: {msg}", file=sys.stderr, flush=True)


def process_seconds() -> float:
    """Seconds since this process started (Linux ``/proc``), else since
    this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


# -- the benchmark's data, found by name --------------------------------------
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_parts(bench: dict, name: str) -> dict:
    """The cell, its configuration and traffic, and the names of the
    end-to-end and per-layer metrics it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def mine(metrics):
        return [m["name"] for m in metrics
                if name in m.get("workloads", [name])]
    return {"cell": cell,
            "config": load_json(ROOT / conf["file"]),
            "traffic": load_json(HERE / "traffic" / f"{cell['traffic']}.json"),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"]),
            "units": {m["name"]: m["unit"]
                      for m in bench["end_to_end"] + bench["per_layer"]}}


def reader(metric: str):
    """``metrics/<metric>.py``'s ``read``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"onchip_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the chip -------------------------------------------------------------------
def require_chips(chips: int):
    """The first device and the count; RunError unless it is a TPU and
    at least ``chips`` are present.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise RunError(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                       f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    return devs[0], len(devs)


class CompileCounter:
    """Programs compiled, or loaded from the persistent cache, so far."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1


class FullCollections:
    """Pauses of the collector's oldest generation while entered."""

    def __init__(self):
        self.pauses = []
        self._t = None

    def __call__(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def stats_numbers(svc) -> dict:
    return {k: v for k, v in dataclasses.asdict(svc.stats).items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


# -- one run --------------------------------------------------------------------
def run_cell(parts: dict, *, seed: int, seconds: float, trace: bool,
             device=None, lookup_wrapper=None) -> dict:
    """Set up, serve the window, check every answer → the result dict.

    ``device`` is ``(jax device, count)``; None skips the chip check and
    the compiled-Pallas check (CPU tests).  ``lookup_wrapper`` wraps the
    service's ``lookup`` (tests plant faults or the control through it).
    """
    from repro.compile_cache import enable_compile_cache

    import deploy
    import dispatch
    import reference
    import traffic as gen

    strict = device is not None
    cell, config, mix = parts["cell"], parts["config"], parts["traffic"]
    say(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {seed}, {seconds:g} s, trace {int(trace)}")
    say(f"compile cache {enable_compile_cache()}")
    compiles = CompileCounter()
    keys = deploy.make_keys(config, seed)
    record = int(config["record_bytes"])
    say(f"data: {config['keys']} keys, {len(keys)} unique from "
        f"{config['n_keys']} drawn, {len(keys) * record} data bytes")
    due, idx = gen.schedule(mix, len(keys), seconds, seed)
    with tempfile.TemporaryDirectory(prefix="onchip-") as work:
        svc, sizes, prefix = deploy.open_service(
            config, keys, os.path.join(work, "index.air"))
        say(f"design: layer bytes {sizes} (bottom first); resident "
            f"{prefix} (kind, entries)")
        warmed = deploy.warm_up(svc, keys, config, seed)
        say(f"warmed {len(warmed)} batch sizes {warmed[0]}..{warmed[-1]}")
        lookup = svc.lookup if lookup_wrapper is None \
            else lookup_wrapper(svc.lookup, keys)
        s0, c0 = stats_numbers(svc), compiles.n
        trace_dir = os.path.join(work, "trace")
        if trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        setup_s = process_seconds()
        try:
            with FullCollections() as collections:
                win = dispatch.serve_window(
                    lookup, keys, due, idx, seconds=seconds, drain=True,
                    max_batch=int(config["max_batch"]),
                    batch_multiple=int(config["batch_multiple"]),
                    spans=trace)
        finally:
            if trace:
                jax.profiler.stop_trace()
        in_window = compiles.n - c0
        s1 = stats_numbers(svc)
        stats = {k: s1[k] - s0.get(k, 0) for k in s1}
        peak = None
        if device is not None:
            peak = (device[0].memory_stats() or {}).get("peak_bytes_in_use")
        svc.close()
        del svc
        red = None
        if trace:
            import trace as tr
            pb = sorted(Path(trace_dir).rglob("*.xplane.pb"))
            red = tr.reduce(pb[-1], config["kernel_pattern"])
    answered = ~np.isnan(win["done"])
    n_due = len(due)
    backlog = n_due - win["served"]
    say(f"window: {n_due} due, {win['served']} handed to lookup in "
        f"{len(win['calls'])} calls, {int(answered.sum())} answered, "
        f"{win['failed']} in calls that raised, backlog at close {backlog}")
    if len(win["calls"]):
        c = win["calls"]
        k = int(np.argmax(c[:, 1] - c[:, 0]))
        say(f"longest call {(c[k, 1] - c[k, 0]) * 1e3:.3f} ms with "
            f"{int(c[k, 2])} lookups, started {c[k, 0]:.3f} s into the window")
    gcp = collections.pauses
    say(f"full collections in the window: {len(gcp)}, longest "
        f"{max(gcp, default=0.0) * 1e3:.3f} ms, total "
        f"{sum(gcp) * 1e3:.3f} ms")
    for e in win["errors"]:
        say(f"lookup raised:\n{e}")
    other = {b: int(stats.get(f"{b}_batches", 0)) for b in OTHER_BACKENDS}
    say(f"batches by backend in the window: pallas "
        f"{int(stats['pallas_batches'])} of {int(stats['batches'])}; "
        + " ".join(f"{k}={v}" for k, v in other.items()))
    say(f"programs compiled or loaded inside the window: {in_window}")
    if strict and (any(other.values())
                   or stats["pallas_batches"] != stats["batches"]):
        raise RunError(f"a batch was not served by compiled Pallas: {other}")
    if strict and in_window:
        raise RunError(f"{in_window} program(s) compiled inside the window")

    # -- correctness: every answered lookup against the reference ---------
    unanswered = win["failed"] + backlog
    verdict = reference.compare(
        keys, keys[idx[answered]], win["answers"][answered], record=record,
        max_answer_bytes=int(config["max_answer_bytes"]),
        unanswered=unanswered)

    rec = {"seconds": seconds, "window_s": win["window_s"], "due": due,
           "dispatched": win["dispatched"], "done": win["done"],
           "calls": win["calls"], "stats": stats, "setup_s": setup_s,
           "prefix": prefix, "trace": red, "peaks": None}
    if red is not None:
        import peaks
        rec["peaks"] = peaks.peaks_for(device[0].device_kind)
    names = parts["per_layer"] if trace else parts["end_to_end"]
    metrics = {}
    for name in names:
        v = reader(name)(rec)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": parts["units"][name]}
    dev = {"platform": "cpu", "kind": "cpu", "count": 0,
           "memory_peak_bytes": peak}
    if device is not None:
        dev = {"platform": device[0].platform, "kind": device[0].device_kind,
               "count": device[1], "memory_peak_bytes": peak}
    if red is not None:
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
        say(f"trace: {red['kernel_calls']} kernel events, "
            f"{red['kernel_s']} s kernel, busy {red['busy_s']} s of "
            f"{red['window_s']} s")
    out = {"correct": verdict["correct"],
           "attempted": int(win["served"]), "failed": int(win["failed"]),
           "metrics": metrics, "device": dev}
    if red is not None:
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in verdict["checks"].items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        parts = cell_parts(benchmark(), args.workload)
        device = require_chips(int(parts["cell"]["chips"]))
        say(f"device {device[0].platform} {device[0].device_kind} "
            f"(count {device[1]})")
        out = run_cell(parts, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), device=device)
    except RunError as e:
        say(f"no result: {e}")
        return 2
    for k, c in out["checks"].items():
        say(f"check {k} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
