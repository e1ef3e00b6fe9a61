"""Find the design ``Index.tune`` picks for a configuration's data.

    python3 benchmarks/onchip/find_design.py --config uniform_airtune --seed 0

Runs AirTune (host numpy; no chip needed, ``JAX_PLATFORMS=cpu`` keeps it
off the device) on the configuration's key set at its full size and prints
the design as the configuration's ``design.layers`` list, bottom layer
first.  The benchmark rebuilds that design with the registered builders in
every run; it never tunes inside a run.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
NAME = re.compile(r"^(GStep|GBand|EBand|[a-z_]+)\((?:(\d+),)?(\d+)\)$")
FAMILY = {"GStep": "gstep", "GBand": "gband", "EBand": "eband"}


def parse_builder(name: str) -> dict:
    """``"GStep(16,4096)"`` → ``{"family": "gstep", "lam": 4096, "p": 16}``."""
    m = NAME.match(name)
    if m is None:
        raise ValueError(f"cannot parse builder name {name!r}")
    fam, p, lam = m.groups()
    out = {"family": FAMILY.get(fam, fam), "lam": int(lam)}
    if p is not None:
        out["p"] = int(p)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    from repro.api import Index, TuneSpec
    from repro.core import KeyPositions

    import deploy
    with open(HERE / "configs" / f"{args.config}.json") as f:
        config = json.load(f)
    t0 = time.perf_counter()
    keys = deploy.make_keys(config, args.seed)
    D = KeyPositions.fixed_record(keys, int(config["record_bytes"]))
    tune = config["tune"]
    idx = Index.tune(D, tune["profile"], TuneSpec.from_dict(tune["spec"]))
    res = idx.build().result
    layers = [parse_builder(n) for n in res.builder_names]
    print(json.dumps({
        "config": args.config, "seed": args.seed, "unique_keys": len(keys),
        "layers": layers,
        "layer_bytes": [int(lay.size_bytes) for lay in res.design.layers],
        "modeled_cost_s": float(res.cost),
        "seconds": time.perf_counter() - t0}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
