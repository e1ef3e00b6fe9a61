"""Tuning benchmark: cost + wall-clock + sweep-engine work per strategy.

The serving benchmark tracks how fast a tuned index *serves*; this one
tracks how fast (and how well) the tuner itself *searches*.  Every
registered strategy runs on a fixed dataset × storage-profile grid with
one shared :class:`TuneSpec`; per cell the fused sweep engine (the
default) is compared against the legacy per-builder loop
(``sweep=False``), so the JSON records both the answer quality and the
work reduction:

  * ``cost_us``        — L_SM (Eq. 6) of the returned design,
  * ``wall_s`` / ``legacy_wall_s`` — strategy wall-clock, both paths,
  * ``layers_built`` / ``layers_reused`` — construction vs cache hits,
  * ``scored``         — E[T(Δ)] evaluations actually performed,
  * ``sweeps`` / ``sweep_s_per_vertex`` — fused expansions + their cost,
  * ``work_reduction`` — legacy (built+scored) / sweep (built+scored),
  * ``sweep_matches_legacy`` — bit-identical design/cost certification.

The three strategies share one :class:`repro.core.sweep.LayerCache` per
dataset — the certification workload (brute force first, then the guided
searches, across every tier) is exactly the cross-tune reuse the cache
exists for, so the guided strategies ride the exhaustive pass's builds.

The λ-grid keeps ``brute_force`` tractable; it certifies the guided
strategies' costs on every run (``within_brute`` > 1.05 fails the run —
the CI regression guard).  A scoring micro-benchmark also records the
numpy / jnp / Pallas batched-scorer wall-clocks, labelled with the
platform that ran them and whether Pallas ran compiled or interpreted.

Prints the repo's ``name,us_per_call,derived`` CSV; ``--json PATH`` also
dumps ``BENCH_tune.json`` so the perf trajectory tracks tuner speed
(``benchmarks/run.py --tune-json`` wires this into the main harness).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

from repro.compile_cache import enable_compile_cache
from repro.api import TuneSpec
from repro.core import KeyPositions, PROFILES, batched_mean_read_costs
from repro.core.registry import SEARCH_STRATEGIES
from repro.core.sweep import LayerCache
from repro.data.datasets import sosd_like

N_KEYS = 50_000
RECORD = 16
DATASETS = ("gmm", "books")
TIERS = ("azure_ssd", "azure_nfs")
# brute force first: its exhaustive expansion warms the shared per-dataset
# LayerCache, so the guided certifications ride its builds
STRATEGIES = ("brute_force", "beam", "airtune")

# small Eq.(8) grid: 7 λ values × 3 families keeps brute_force tractable
SPEC = TuneSpec(lam_low=2.0**10, lam_high=2.0**16, lam_base=2.0,
                k=3, max_layers=4)


def emit(name, us, derived):
    print(f"{name},{us:.2f},{derived}")


def _run_cell(strat: str, D, profile, builders, cache: LayerCache) -> dict:
    fn = SEARCH_STRATEGIES.get(strat)
    kw = dict(k=SPEC.k, max_layers=SPEC.max_layers)
    res = fn(D, profile, builders, sweep=True, layer_cache=cache, **kw)
    leg = fn(D, profile, builders, sweep=False, **kw)
    s, ls = res.stats, leg.stats
    sweep_work = s.layers_built + s.candidates_scored
    legacy_work = ls.layers_built + ls.candidates_scored
    # a cell where the stopping criterion fires immediately does zero
    # work on BOTH paths — that is parity (1.0), not a 0x regression
    reduction = legacy_work / max(sweep_work, 1) if legacy_work else 1.0
    return {
        "strategy": strat,
        "cost_us": res.cost * 1e6,
        "wall_s": s.wall_seconds,
        "legacy_wall_s": ls.wall_seconds,
        "layers_built": s.layers_built,
        "layers_reused": s.layers_reused,
        "pruned": s.candidates_pruned,
        "scored": s.candidates_scored,
        "sweeps": s.sweeps,
        "sweep_s_per_vertex": s.sweep_seconds / max(s.sweeps, 1),
        "legacy_layers_built": ls.layers_built,
        "legacy_scored": ls.candidates_scored,
        "work_reduction": reduction,
        "sweep_matches_legacy": bool(
            res.cost == leg.cost
            and res.builder_names == leg.builder_names),
        "n_layers": res.design.n_layers,
        "builder_names": list(res.builder_names),
    }


def _bench_scoring_backends(C: int = 32, S: int = 8192) -> dict:
    """Wall-clock of one batched (C, S) candidate-scoring call per
    backend (see repro.kernels.candidate_score), labelled by what ran."""
    import jax

    from repro.core.storage import affine_coefficients
    from repro.kernels import interpret_mode
    from repro.kernels.candidate_score import affine_candidate_scores

    rng = np.random.default_rng(0)
    W = rng.uniform(16.0, 1e6, size=(C, S))
    weights = rng.uniform(0.5, 4.0, size=S)
    prof = PROFILES["azure_ssd"]
    out = {"candidates": C, "sample": S,
           "platform": jax.default_backend(),
           "pallas_mode": "interpret" if interpret_mode() else "compiled"}

    def _time(fn, reps=5):
        fn()                                     # warmup / jit compile
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) / reps * 1e6

    out["numpy_us"] = _time(
        lambda: batched_mean_read_costs(W, weights, prof))
    ell, inv_bw = affine_coefficients(prof)
    for backend, reps in (("jnp", 5), ("pallas", 2)):
        out[f"{backend}_us"] = _time(lambda: affine_candidate_scores(
            W, weights, ell, inv_bw, backend=backend), reps=reps)
    for backend in ("numpy", "jnp", "pallas"):
        ran = (f"{out['platform']}, {out['pallas_mode']}"
               if backend == "pallas" else
               "host" if backend == "numpy" else out["platform"])
        emit(f"tune_score_{backend}", out[f"{backend}_us"],
             f"batched ({C},{S}) candidate scoring on {ran}")
    return out


def run_tune_bench(n_keys: int = N_KEYS,
                   strategies=STRATEGIES) -> dict:
    results = {"n_keys": n_keys, "spec": SPEC.to_dict(), "rows": []}
    builders = SPEC.builders()
    for ds in DATASETS:
        D = KeyPositions.fixed_record(sosd_like(ds, n_keys), RECORD)
        cache = LayerCache()        # shared across tiers AND strategies
        for tier in TIERS:
            per_strategy = {}
            for strat in strategies:
                row = _run_cell(strat, D, PROFILES[tier], builders, cache)
                row.update({"dataset": ds, "tier": tier})
                per_strategy[strat] = row
                results["rows"].append(row)
                emit(f"tune_{ds}_{tier}_{strat}", row["wall_s"] * 1e6,
                     f"cost={row['cost_us']:.1f}us built={row['layers_built']} "
                     f"reused={row['layers_reused']} scored={row['scored']} "
                     f"red={row['work_reduction']:.1f}x "
                     f"layers={row['n_layers']}")
            if "brute_force" in per_strategy:
                ref = per_strategy["brute_force"]["cost_us"]
                for strat, row in per_strategy.items():
                    row["within_brute"] = row["cost_us"] / max(ref, 1e-12)

    # per-strategy aggregates: the trend line benchmarks/run.py prints
    per = {}
    for row in results["rows"]:
        a = per.setdefault(row["strategy"], {
            "wall_s": 0.0, "legacy_wall_s": 0.0, "layers_built": 0,
            "layers_reused": 0, "scored": 0, "legacy_layers_built": 0,
            "legacy_scored": 0, "sweeps": 0})
        for k in a:
            a[k] += row[k]
    for strat, a in per.items():
        sweep_work = a["layers_built"] + a["scored"]
        legacy_work = a["legacy_layers_built"] + a["legacy_scored"]
        a["work_reduction"] = legacy_work / max(sweep_work, 1) \
            if legacy_work else 1.0
    results["per_strategy"] = per

    results["scoring_backends"] = _bench_scoring_backends()

    guided = [r for r in results["rows"] if r["strategy"] != "brute_force"
              and "within_brute" in r]
    ok_cost = all(r["within_brute"] <= 1.05 for r in guided)
    ok_ident = all(r["sweep_matches_legacy"] for r in results["rows"])
    ok_work = all(a["work_reduction"] >= 3.0 for a in per.values())
    results["acceptance_guided_within_5pct_of_brute"] = ok_cost
    results["acceptance_sweep_bit_identical"] = ok_ident
    results["acceptance_work_reduction_3x"] = ok_work
    emit("tune_acceptance", 0.0,
         f"guided_within_5pct_of_brute_on_{len(guided)}_cells={ok_cost} "
         f"sweep_bit_identical={ok_ident} work_reduction_3x={ok_work}")
    for strat, a in per.items():
        if a["wall_s"] > a["legacy_wall_s"] * 1.2:
            # GitHub annotation; plain noise locally — wall regressions
            # warn, they do not fail the run (machine variance)
            print(f"::warning ::tune_bench {strat}: sweep wall "
                  f"{a['wall_s']:.2f}s > 1.2x legacy "
                  f"{a['legacy_wall_s']:.2f}s")
    return results


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also dump results as JSON (e.g. BENCH_tune.json)")
    ap.add_argument("--n-keys", type=int, default=N_KEYS)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    results = run_tune_bench(args.n_keys)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {args.json}", flush=True)
    # regression guard: guided search quality and sweep equivalence are
    # hard failures; wall-clock only warns (above)
    if not (results["acceptance_guided_within_5pct_of_brute"]
            and results["acceptance_sweep_bit_identical"]):
        sys.exit(1)


if __name__ == "__main__":
    main()
