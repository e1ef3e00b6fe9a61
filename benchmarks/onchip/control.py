"""Read the controls' numbers at each cell's own size and load.

    python3 benchmarks/onchip/control.py --seeds 11 12 13 [--seconds 5] \
        [--workload <cell> ...]

Each control of ``reference.CONTROLS`` (the plain reference a precision
step below the device's: the rank search in float32, and the range ends in
bfloat16) is put in the program's place behind the same dispatcher, at the
cell's key count, traffic and rate, for a short window; its answers are
then judged by the comparison that decides ``correct``.  Every cell has to
read not correct under each.  One key set per seed serves every cell of
that seed.  The benchmark's own runs never run this.  One JSON line per
cell, control and seed.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--workload", nargs="*", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    import deploy
    import dispatch
    import reference
    import traffic as gen
    bench = run.benchmark()
    cells = args.workload or [w["name"] for w in bench["workloads"]]
    parts = {c: run.cell_parts(bench, c) for c in cells}
    try:
        run.require_chips(max(int(p["cell"]["chips"]) for p in parts.values()))
    except run.RunError as e:
        run.say(f"no control: {e}")
        return 2
    data = {}                       # one key set per (key set, size)
    for seed in args.seeds:
        data.clear()
        for cell, p in parts.items():
            conf, mix = p["config"], p["traffic"]
            dkey = (conf["keys"], conf["n_keys"])
            if dkey not in data:
                data[dkey] = deploy.make_keys(conf, seed)
            keys = data[dkey]
            record = int(conf["record_bytes"])
            due, idx = gen.schedule(mix, len(keys), args.seconds, seed)
            for name, control in reference.CONTROLS.items():
                win = dispatch.serve_window(
                    control(keys, record), keys, due, idx,
                    seconds=args.seconds, drain=True,
                    max_batch=int(conf["max_batch"]),
                    batch_multiple=int(conf["batch_multiple"]))
                done = ~np.isnan(win["done"])
                v = reference.compare(
                    keys, keys[idx[done]], win["answers"][done],
                    record=record,
                    max_answer_bytes=int(conf["max_answer_bytes"]),
                    unanswered=win["failed"] + len(due) - win["served"])
                print(json.dumps({"workload": cell, "control": name,
                                  "seed": seed, "answered": int(done.sum()),
                                  "correct": v["correct"],
                                  "checks": v["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
