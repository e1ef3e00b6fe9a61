"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (us_per_call = the headline
latency/time of the benchmark; derived = the claim it validates).

The paper's evaluation is lookup latency on real storage; this container
is CPU-only, so latencies are evaluated under the storage model L_SM
(Eq. 6) with the paper's profiled tier constants — the same objective the
paper optimizes — plus real wall-clock for build/tuning times and real
partial-read lookups against the local filesystem.
"""
from __future__ import annotations

import sys
import time

import numpy as np

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache
from repro.core import (AffineProfile, KeyPositions, PROFILES, airtune,
                        expected_latency, IndexDesign, mean_read_volume)
from repro.core.baselines import (build_fixed_btree, data_calculator,
                                  homogeneous_airtune, tune_pgm, tune_rmi)
from repro.data.datasets import DATASETS, sosd_like

N_KEYS = 400_000         # container-scale stand-in for SOSD's 200–800M
RECORD = 16
TIERS = ("azure_nfs", "azure_ssd", "azure_hdd")


def _dataset(name: str, n=N_KEYS) -> KeyPositions:
    return KeyPositions.fixed_record(sosd_like(name, n), RECORD)


def emit(name, us, derived):
    print(f"{name},{us:.2f},{derived}")


# ---------------------------------------------------------------------------
# Figure 2 — need for I/O-aware optimization (§2.1 worked example)
# ---------------------------------------------------------------------------
def bench_fig2_example():
    ssd, cloud = PROFILES["ssd_ex"], PROFILES["cloud_ex"]
    KB = 1024.0
    lk = lambda prof, n, node, page: n * float(prof(node)) + float(prof(page))
    b200_ssd, b5000_ssd = lk(ssd, 3, 4 * KB, 4 * KB), lk(ssd, 2, 100 * KB, 4 * KB)
    b200_cld, b5000_cld = lk(cloud, 3, 4 * KB, 4 * KB), lk(cloud, 2, 100 * KB, 4 * KB)
    emit("fig2_B200_ssd", b200_ssd * 1e6, "paper=416us")
    emit("fig2_B5000_ssd", b5000_ssd * 1e6, "paper=504us")
    emit("fig2_B200_cloud", b200_cld * 1e6, "paper=400160us")
    emit("fig2_B5000_cloud", b5000_cld * 1e6, "paper=302040us")
    flip = (b200_ssd < b5000_ssd) and (b5000_cld < b200_cld)
    emit("fig2_ordering_flips", 0.0, f"flip={flip} (paper: yes)")


# ---------------------------------------------------------------------------
# Figure 9 — cold-state first-query latency across datasets × storage
# ---------------------------------------------------------------------------
def bench_fig9_cold_lookup():
    for ds in DATASETS:
        D = _dataset(ds)
        for tier in TIERS:
            prof = PROFILES[tier]
            t0 = time.perf_counter()
            ours = airtune(D, prof, k=5)
            tune_s = time.perf_counter() - t0
            rows = {
                "airindex": ours.cost,
                "btree": expected_latency(build_fixed_btree(D), prof),
                "rmi": tune_rmi(D, prof).cost,
                "pgm": tune_pgm(D, prof).cost,
                "datacalc": data_calculator(D, prof).cost,
            }
            base = rows["airindex"]
            sp = {k: v / base for k, v in rows.items() if k != "airindex"}
            emit(f"fig9_{ds}_{tier}", base * 1e6,
                 "speedup_vs[" + " ".join(f"{k}={v:.2f}x"
                                          for k, v in sp.items())
                 + f"] tune={tune_s:.1f}s")


# ---------------------------------------------------------------------------
# Figure 11 — AirTune vs manual (L, λ) configurations (fb dataset)
# ---------------------------------------------------------------------------
def bench_fig11_manual_sweep():
    from repro.core.builders import build_gband
    from repro.core.nodes import outline
    D = _dataset("fb")
    for tier in ("azure_nfs", "azure_ssd"):
        prof = PROFILES[tier]
        auto = airtune(D, prof, k=5).cost
        best_manual = np.inf
        for lam in [2.0**s for s in range(10, 21, 2)]:
            for L in (1, 2, 3):
                layers, cur = [], D
                for _ in range(L):
                    lay = build_gband(cur, lam)
                    nxt = outline(lay, cur)
                    if nxt.size_bytes >= cur.size_bytes:
                        break
                    layers.append(lay)
                    cur = nxt
                c = expected_latency(IndexDesign(tuple(layers), D), prof)
                best_manual = min(best_manual, c)
        emit(f"fig11_fb_{tier}", auto * 1e6,
             f"best_manual={best_manual * 1e6:.1f}us "
             f"auto<=manual={auto <= best_manual * 1.0001}")


# ---------------------------------------------------------------------------
# Figure 12 — speedup over well-tuned baseline families (books, NFS)
# ---------------------------------------------------------------------------
def bench_fig12_tuned_baselines():
    D = _dataset("books")
    prof = PROFILES["azure_nfs"]
    ours = airtune(D, prof, k=5).cost
    best = {
        # explicit p=255 keeps this trend line on the historical legacy
        # series (decoupled fanout); the page-coupled discipline is the
        # registered `btree` family benched in baseline_bench.py
        "btree_lam": min(expected_latency(build_fixed_btree(D, p=255, lam=lam),
                                          prof)
                         for lam in (1024.0, 4096.0, 16384.0, 65536.0)),
        "rmi": tune_rmi(D, prof).cost,
        "pgm": tune_pgm(D, prof).cost,
    }
    emit("fig12_books_nfs", ours * 1e6,
         " ".join(f"{k}={v / ours:.2f}x" for k, v in best.items())
         + " (paper: 2.7x/1.5x over tuned LMDB/RMI)")


# ---------------------------------------------------------------------------
# Figure 13 — adaptivity over the latency×bandwidth spectrum (fb)
# ---------------------------------------------------------------------------
def bench_fig13_spectrum():
    D = _dataset("fb", n=150_000)
    lats = [1e-6, 1e-4, 1e-2, 1.0]
    bws = [1e4, 1e6, 1e8, 1e10]
    grid = []
    for ell in lats:
        for bw in bws:
            res = airtune(D, AffineProfile(ell, bw), k=3)
            grid.append((ell, bw, res.design.n_layers,
                         mean_read_volume(res.design)))
    by_lat = {}
    for ell, bw, L, vol in grid:
        by_lat.setdefault(ell, []).append(L)
    avg_layers = {ell: float(np.mean(v)) for ell, v in by_lat.items()}
    monotone = all(avg_layers[a] >= avg_layers[b] - 0.75
                   for a, b in zip(lats, lats[1:]))
    emit("fig13_spectrum", 0.0,
         "avg_layers_by_latency=" + "/".join(
             f"{avg_layers[l]:.1f}" for l in lats)
         + f" higher_latency->shallower={monotone}")
    for ell, bw, L, vol in grid:
        print(f"fig13_cell,0.00,lat={ell:g}s bw={bw:g}B/s layers={L} "
              f"read_volume={vol:.0f}B")


# ---------------------------------------------------------------------------
# Figure 15 — build time & search overhead vs data size (gmm)
# ---------------------------------------------------------------------------
def bench_fig15_build_time():
    for n in (125_000, 250_000, 500_000, 1_000_000):
        D = _dataset("gmm", n=n)
        prof = PROFILES["azure_ssd"]
        t0 = time.perf_counter()
        res = airtune(D, prof, k=5)
        tune_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        build_fixed_btree(D)
        btree_s = time.perf_counter() - t0
        per_key_ns = tune_s / max(D.n, 1) * 1e9
        emit(f"fig15_n{n}", tune_s * 1e6,
             f"tune={tune_s:.2f}s btree_build={btree_s:.2f}s "
             f"search_overhead={per_key_ns:.0f}ns/key "
             f"(paper: ~9.6us/key 1-core) layers_built={res.stats.layers_built}")


# ---------------------------------------------------------------------------
# Figure 20 — top-k sweep (books, SSD)
# ---------------------------------------------------------------------------
def bench_fig20_topk():
    D = _dataset("books", n=200_000)
    prof = PROFILES["azure_ssd"]
    costs = []
    for k in (1, 2, 5, 10, 20):
        t0 = time.perf_counter()
        res = airtune(D, prof, k=k)
        dt = time.perf_counter() - t0
        costs.append(res.cost)
        emit(f"fig20_k{k}", res.cost * 1e6, f"build={dt:.2f}s")
    dec = all(a >= b - 1e-9 for a, b in zip(costs, costs[1:]))
    emit("fig20_monotone", 0.0, f"cost_monotone_nonincreasing={dec}")


# ---------------------------------------------------------------------------
# §2.2 — heterogeneous vs homogeneous layers
# ---------------------------------------------------------------------------
def bench_sec22_heterogeneous():
    D = _dataset("gmm", n=200_000)
    prof = PROFILES["azure_ssd"]
    full = airtune(D, prof, k=5).cost
    step_only = homogeneous_airtune(D, prof, "step", k=5).cost
    band_only = homogeneous_airtune(D, prof, "band", k=5).cost
    emit("sec22_heterogeneous", full * 1e6,
         f"step_only={step_only / full:.2f}x band_only={band_only / full:.2f}x"
         f" hetero_best={full <= min(step_only, band_only) * 1.0001}")


# ---------------------------------------------------------------------------
# Serving engine (batched lookups + tiered block cache) — BENCH_serve.json
# ---------------------------------------------------------------------------
SERVE_JSON_PATH = None     # set by main() via --serve-json
TUNE_JSON_PATH = None      # set by main() via --tune-json
BASELINE_JSON_PATH = None  # set by main() via --baseline-json
FLEET_JSON_PATH = None     # set by main() via --fleet-json
CHAOS_JSON_PATH = None     # set by main() via --chaos-json
P99_JSON_PATH = None       # set by main() via --p99-json


def bench_serve():
    try:
        from benchmarks import serve_bench
    except ImportError:                # invoked as `python benchmarks/run.py`
        import serve_bench
    results = serve_bench.run_serve_bench()
    if SERVE_JSON_PATH:
        import json
        with open(SERVE_JSON_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {SERVE_JSON_PATH}", flush=True)


# ---------------------------------------------------------------------------
# Sharded fleet vs monolith (repro.fleet) — BENCH_fleet.json
# ---------------------------------------------------------------------------
def bench_fleet():
    try:
        from benchmarks import serve_bench
    except ImportError:                # invoked as `python benchmarks/run.py`
        import serve_bench
    results = serve_bench.run_fleet_bench()
    serve_bench.emit_fleet(results)
    if FLEET_JSON_PATH:
        import json
        with open(FLEET_JSON_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {FLEET_JSON_PATH}", flush=True)


# ---------------------------------------------------------------------------
# Fault-injection gate (retries, checksums, hot swap) — BENCH_chaos.json
# ---------------------------------------------------------------------------
def bench_chaos():
    try:
        from benchmarks import serve_bench
    except ImportError:                # invoked as `python benchmarks/run.py`
        import serve_bench
    results = serve_bench.run_chaos_bench()
    serve_bench.emit_chaos(results)
    if CHAOS_JSON_PATH:
        import json
        with open(CHAOS_JSON_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {CHAOS_JSON_PATH}", flush=True)
    fatal = serve_bench.chaos_fatal_warnings(results)
    if fatal:
        for msg in fatal:
            print(f"::error::{msg}")
        raise SystemExit(1)


# ---------------------------------------------------------------------------
# Tail-latency tuning gate (mean vs p99 objective) — BENCH_p99.json
# ---------------------------------------------------------------------------
def bench_p99():
    try:
        from benchmarks import serve_bench
    except ImportError:                # invoked as `python benchmarks/run.py`
        import serve_bench
    results = serve_bench.run_p99_bench()
    serve_bench.emit_p99(results)
    if P99_JSON_PATH:
        import json
        with open(P99_JSON_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {P99_JSON_PATH}", flush=True)
    fatal = serve_bench.p99_fatal_warnings(results)
    if fatal:
        for msg in fatal:
            print(f"::error::{msg}")
        raise SystemExit(1)


# ---------------------------------------------------------------------------
# Tuner speed per search strategy (repro.api facade) — BENCH_tune.json
# ---------------------------------------------------------------------------
def bench_tune():
    try:
        from benchmarks import tune_bench
    except ImportError:                # invoked as `python benchmarks/run.py`
        import tune_bench
    results = tune_bench.run_tune_bench()
    # compact per-strategy trend lines — the numbers to eyeball across PRs
    for strat, a in results.get("per_strategy", {}).items():
        print(f"# tune-trend {strat}: wall={a['wall_s']:.2f}s "
              f"(legacy {a['legacy_wall_s']:.2f}s) "
              f"built={a['layers_built']} reused={a['layers_reused']} "
              f"scored={a['scored']} sweeps={a['sweeps']} "
              f"work_reduction={a['work_reduction']:.1f}x", flush=True)
    sb = results.get("scoring_backends", {})
    fmt = lambda v: f"{v:.0f}us" if isinstance(v, (int, float)) else "n/a"
    print(f"# tune-trend scoring: numpy={fmt(sb.get('numpy_us'))} "
          f"jnp={fmt(sb.get('jnp_us'))} "
          f"pallas={fmt(sb.get('pallas_us'))} "
          f"({sb.get('platform')}, {sb.get('pallas_mode')})", flush=True)
    if TUNE_JSON_PATH:
        import json
        with open(TUNE_JSON_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {TUNE_JSON_PATH}", flush=True)


# ---------------------------------------------------------------------------
# Baseline families head-to-head (§7.2 dominance) — BENCH_baseline.json
# ---------------------------------------------------------------------------
def bench_baseline():
    try:
        from benchmarks import baseline_bench
    except ImportError:                # invoked as `python benchmarks/run.py`
        import baseline_bench
    results = baseline_bench.run_baseline_bench()
    # compact per-cell trend lines — AirTune's margin over the best baseline
    for row in results.get("rows", []):
        best = min(row["baseline_costs_us"].values())
        print(f"# baseline-trend {row['dataset']}/{row['tier']}: "
              f"airtune={row['airtune_cost_us']:.1f}us "
              f"best_baseline={best:.1f}us "
              f"margin={best / max(row['airtune_cost_us'], 1e-12):.2f}x "
              f"reused={row['airtune_layers_reused']}", flush=True)
    if BASELINE_JSON_PATH:
        import json
        with open(BASELINE_JSON_PATH, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {BASELINE_JSON_PATH}", flush=True)


BENCHES = [
    bench_fig2_example,
    bench_fig9_cold_lookup,
    bench_fig11_manual_sweep,
    bench_fig12_tuned_baselines,
    bench_fig13_spectrum,
    bench_fig15_build_time,
    bench_fig20_topk,
    bench_sec22_heterogeneous,
    bench_serve,
    bench_fleet,
    bench_chaos,
    bench_p99,
    bench_tune,
    bench_baseline,
]


def _take_json_flag(argv: list, flag: str, default_path: str):
    """Parse ``--flag[=PATH]`` / ``--flag PATH`` out of argv (in place)."""
    for i, arg in enumerate(argv):
        if arg == flag or arg.startswith(flag + "="):
            if "=" in arg:
                path = arg.split("=", 1)[1]
                del argv[i]
            elif i + 1 < len(argv) and argv[i + 1].endswith(".json") \
                    and not argv[i + 1].startswith("-"):
                path = argv[i + 1]                 # space-separated PATH
                del argv[i:i + 2]
            else:
                path = default_path
                del argv[i]
            return path
    return None


def main() -> None:
    global SERVE_JSON_PATH, TUNE_JSON_PATH, BASELINE_JSON_PATH, \
        FLEET_JSON_PATH, CHAOS_JSON_PATH, P99_JSON_PATH
    enable_compile_cache()
    argv = list(sys.argv[1:])
    # emit BENCH_*.json (perf trajectories)
    SERVE_JSON_PATH = _take_json_flag(argv, "--serve-json", "BENCH_serve.json")
    TUNE_JSON_PATH = _take_json_flag(argv, "--tune-json", "BENCH_tune.json")
    BASELINE_JSON_PATH = _take_json_flag(argv, "--baseline-json",
                                         "BENCH_baseline.json")
    FLEET_JSON_PATH = _take_json_flag(argv, "--fleet-json",
                                      "BENCH_fleet.json")
    CHAOS_JSON_PATH = _take_json_flag(argv, "--chaos-json",
                                      "BENCH_chaos.json")
    P99_JSON_PATH = _take_json_flag(argv, "--p99-json", "BENCH_p99.json")
    only = argv[0] if argv else None
    print("name,us_per_call,derived")
    for bench in BENCHES:
        if only and only not in bench.__name__:
            continue
        t0 = time.perf_counter()
        bench()
        print(f"# {bench.__name__} took {time.perf_counter() - t0:.1f}s",
              flush=True)


if __name__ == "__main__":
    main()
