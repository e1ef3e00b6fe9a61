"""Find the highest rate a cell sustains: one set-up, then rising rates.

    python3 benchmarks/onchip/sweep.py --workload <cell> --seed <n> \
        --start <lookups/s> [--factor 1.25] [--step-seconds 8]

Stands the cell's configuration up once, exactly as ``run.py`` does, then
offers its traffic (the mix's keys and arrivals, the rate replaced) at
``start``, ``start·factor``, ... for ``step-seconds`` each, with the window
cut at the end of each step.  A step holds when the median of the
backlog, sampled at forty points of the step's second half, is below one
``max_batch``: above capacity the backlog grows all through the step, and
a host stall of a second or so, which the queue then drains, moves the
median of those samples little.  The knee is the highest rate of a step
that held; the sweep stops after two steps in a row that did not.  One JSON line per step, then the knee.
Runs on a TPU only, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import numpy as np

import run


def backlog_at(due, dispatched, times) -> np.ndarray:
    """Requests due but not yet handed to the service, at each time."""
    d = np.sort(dispatched[~np.isnan(dispatched)])
    return (np.searchsorted(due, times, side="right")
            - np.searchsorted(d, times, side="right"))


def step(svc, keys, mix, config, rate, seconds, seed, compiles) -> dict:
    import dispatch
    import traffic as gen
    due, idx = gen.schedule(dict(mix, rate=rate), len(keys), seconds, seed)
    c0 = compiles.n
    win = dispatch.serve_window(
        svc.lookup, keys, due, idx, seconds=seconds, drain=False,
        max_batch=int(config["max_batch"]),
        batch_multiple=int(config["batch_multiple"]))
    lat = win["done"] - due
    lat = lat[~np.isnan(lat) & (win["done"] <= seconds)]
    backlog = backlog_at(due, win["dispatched"],
                         np.linspace(seconds / 2, seconds, 40))
    calls = win["calls"]
    return {"rate": rate, "due": len(due),
            "answered_per_s": float(np.count_nonzero(
                win["done"] <= seconds) / seconds),
            "p50_ms": float(np.percentile(lat, 50) * 1e3) if len(lat) else None,
            "p99_ms": float(np.percentile(lat, 99) * 1e3) if len(lat) else None,
            "calls": len(calls),
            "mean_batch": float(calls[:, 2].mean()) if len(calls) else 0.0,
            "backlog_median": float(np.median(backlog)),
            "backlog_max": int(backlog.max()), "backlog_end": int(backlog[-1]),
            "held": bool(np.median(backlog) < int(config["max_batch"])),
            "failed": win["failed"], "compiles": compiles.n - c0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--factor", type=float, default=1.25)
    ap.add_argument("--step-seconds", type=float, default=8.0)
    ap.add_argument("--max-steps", type=int, default=24)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    import deploy
    from repro.compile_cache import enable_compile_cache
    try:
        parts = run.cell_parts(run.benchmark(), args.workload)
        device = run.require_chips(int(parts["cell"]["chips"]))
    except run.RunError as e:
        run.say(f"no sweep: {e}")
        return 2
    config, mix = parts["config"], parts["traffic"]
    enable_compile_cache()
    compiles = run.CompileCounter()
    keys = deploy.make_keys(config, args.seed)
    run.say(f"sweep {args.workload} on {device[0].device_kind}: "
            f"{len(keys)} keys")
    knee, misses = None, 0
    with tempfile.TemporaryDirectory(prefix="onchip-sweep-") as work:
        svc, sizes, prefix = deploy.open_service(
            config, keys, os.path.join(work, "index.air"))
        deploy.warm_up(svc, keys, config, args.seed)
        rate = args.start
        for k in range(args.max_steps):
            s = step(svc, keys, mix, config, rate, args.step_seconds,
                     args.seed + k + 1, compiles)
            print(json.dumps(s), flush=True)
            if s["held"]:
                knee, misses = rate, 0
            else:
                misses += 1
                if misses == 2:
                    break
            rate *= args.factor
        st = svc.stats
        run.say(f"batches: pallas {st.pallas_batches} of {st.batches}, "
                f"interpret {st.interpret_batches}, jnp {st.jnp_batches}, "
                f"numpy {st.numpy_batches}")
        svc.close()
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "design_bytes": sizes, "resident": prefix}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
