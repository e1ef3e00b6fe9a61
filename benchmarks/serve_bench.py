"""Serving-engine benchmark: queries/sec vs cache size vs storage tier.

Exercises :class:`repro.serve.IndexService` against a paged index file:

  * **cold vs warm** — the same batch served twice; the warm pass must
    fetch strictly fewer bytes from storage and complete faster in modeled
    seconds (Eq. 5 under the tier profile) on every tier (the ISSUE's
    acceptance gate);
  * **cache sweep** — hit rate and modeled time for a skewed (Zipf-ish)
    query stream as the tiered cache grows;
  * **throughput** — wall-clock queries/sec of the batched engine vs the
    one-query-at-a-time ``lookup_serialized`` walk;
  * **pipeline** — ``lookup_batches`` (batch-i+1 prefetch overlapping
    batch-i fused descent) vs sequential ``lookup`` on ``azure_hdd``:
    windows must be identical (FATAL) and the roofline must show the
    engine pread-bound (``io_fraction >= 0.8``, FATAL); a wall-clock
    qps regression only warns;
  * **drift scenario** — tune on ``azure_ssd``, serve on a degraded tier:
    the persisted ServeStats must flag drift (``repro.api.drift``) and a
    warm-started retune must recover the cold-retune cost (within 1%)
    with strictly fewer layer builds — a failed recovery is FATAL, only
    wall-clock regressions degrade to warnings;
  * **baselines on the serve path** — the §7.2 btree/rmi/pgm designs
    served through the same ``IndexService`` + cache as the AirTune
    design, so ``BENCH_serve.json`` trends the dominance margin on the
    *real* partial-read path, not just the Eq. 6 model.

``--chaos`` / ``--chaos-only`` add the fault-injection gate: every
recoverable fault schedule (transient EIO, torn reads, stalls, corrupt
pages, flaky start, persistent coalesced-run failure) must serve
bit-identical results through the retry/repair machinery (FATAL);
past-the-budget failures must surface their typed errors (FATAL); hot
swap under live traffic must never mix epochs within a batch (FATAL); a
dead fleet shard must honor the fail-stop and ``partial_results``
contracts (FATAL); qps degradation under faults only warns.
``--chaos-json PATH`` dumps ``BENCH_chaos.json``.

Prints the repo's ``name,us_per_call,derived`` CSV; ``--json PATH`` also
dumps a machine-readable ``BENCH_serve.json`` so later PRs have a perf
trajectory to compare against (``benchmarks/run.py --serve-json`` wires
this into the main harness).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

from repro.compile_cache import enable_compile_cache
from repro.api import Index, RetryPolicy, ServeSpec, TuneSpec, detect_drift
from repro.core import (KeyPositions, PROFILES, airtune, expected_latency,
                        profile_to_dict, quantile_latency)
from repro.core.baselines import build_fixed_btree, tune_pgm, tune_rmi
from repro.core.serialize import lookup_serialized, write_index
from repro.core.storage import CachedProfile
from repro.fleet import Fleet, FleetSpec, ShardUnavailableError, \
    demand_from_design
from repro.serve import (FaultInjectingBackend, FileBackend, IndexService,
                         ReadError, StorageError)
from repro.serve.index_service import (ServeStats, demo_serving_design,
                                       distributional_backing_profile)
from repro.data.datasets import sosd_like

N_KEYS = 200_000
RECORD = 16
PAGE = 4096
TIERS = ("azure_nfs", "azure_ssd")
CACHE_SIZES = (32 << 10, 256 << 10, 2 << 20)

# drift scenario: tuned-for tier vs the degraded tier it is served on
DRIFT_TUNED = "azure_ssd"
DRIFT_SERVED = "azure_hdd"
DRIFT_SPEC = TuneSpec(lam_low=2**8, lam_high=2**17, lam_base=2.0, k=4,
                      max_layers=8, page_bytes=PAGE,
                      cache_bytes=(64 << 10, 512 << 10))


def emit(name, us, derived):
    print(f"{name},{us:.2f},{derived}")


build_serving_design = demo_serving_design


_HOT_ORDER = None       # fixed random rank→key map, shared by all sweeps


def _skewed_queries(keys: np.ndarray, n: int, rng) -> np.ndarray:
    """Zipf-ish rank sampling — the hot-key regime block caches live for.
    Ranks map through a fixed random permutation so the hot set is spread
    across the key space (not the physically-clustered smallest keys)."""
    global _HOT_ORDER
    if _HOT_ORDER is None or len(_HOT_ORDER) != len(keys):
        _HOT_ORDER = np.random.default_rng(123).permutation(len(keys))
    ranks = (rng.zipf(1.2, n) - 1) % len(keys)
    return keys[_HOT_ORDER[ranks]]


def bench_cold_warm(idx: Index, tier: str, queries: np.ndarray) -> dict:
    svc = idx.serve(profile=tier, cache_bytes=(256 << 10, 2 << 20))
    base = svc.stats.snapshot()
    t0 = time.perf_counter()
    svc.lookup(queries)
    cold_wall = time.perf_counter() - t0
    mid = svc.stats.snapshot()
    t0 = time.perf_counter()
    svc.lookup(queries)
    warm_wall = time.perf_counter() - t0
    end = svc.stats.snapshot()
    svc.close()
    cold = {k: mid[k] - base[k] for k in ("bytes_fetched", "modeled_seconds",
                                          "preads")}
    warm = {k: end[k] - mid[k] for k in ("bytes_fetched", "modeled_seconds",
                                         "preads")}
    return {
        "tier": tier,
        "cold": {**cold, "wall_s": cold_wall,
                 "qps": len(queries) / max(cold_wall, 1e-9)},
        "warm": {**warm, "wall_s": warm_wall,
                 "qps": len(queries) / max(warm_wall, 1e-9)},
        "hit_rate_final": end["hit_rate"],
        "warm_fewer_bytes": warm["bytes_fetched"] < cold["bytes_fetched"],
        "warm_faster_modeled":
            warm["modeled_seconds"] < cold["modeled_seconds"],
    }


def bench_cache_sweep(idx: Index, tier: str, keys: np.ndarray, *,
                      n_batches: int = 8, batch: int = 1024) -> list:
    rng = np.random.default_rng(7)
    stream = [_skewed_queries(keys, batch, rng) for _ in range(n_batches)]
    rows = []
    for cap in CACHE_SIZES:
        svc = idx.serve(profile=tier,
                        cache_bytes=(cap // 4, cap - cap // 4))
        base = svc.stats.snapshot()
        t0 = time.perf_counter()
        for qs in stream:
            svc.lookup(qs)
        wall = time.perf_counter() - t0
        end = svc.stats.snapshot()
        svc.close()
        rows.append({
            "tier": tier, "cache_bytes": cap,
            "hit_rate": end["hit_rate"],
            "bytes_fetched": end["bytes_fetched"] - base["bytes_fetched"],
            "bytes_from_cache": end["bytes_from_cache"],
            "modeled_seconds": end["modeled_seconds"] - base["modeled_seconds"],
            "qps": n_batches * batch / max(wall, 1e-9),
        })
    return rows


def bench_engine_vs_scalar(idx: Index, queries: np.ndarray) -> dict:
    path = idx.path
    svc = idx.serve(profile=None, cache_bytes=(2 << 20,))
    svc.lookup(queries[:64])                      # touch pages / warm python
    t0 = time.perf_counter()
    svc.lookup(queries)
    engine_wall = time.perf_counter() - t0
    svc.close()
    t0 = time.perf_counter()
    lookup_serialized(path, None, queries)
    scalar_wall = time.perf_counter() - t0
    return {"engine_qps": len(queries) / max(engine_wall, 1e-9),
            "scalar_qps": len(queries) / max(scalar_wall, 1e-9),
            "speedup": scalar_wall / max(engine_wall, 1e-9)}


def _io_split(s) -> dict:
    """Compute against I/O of served traffic: the measured wall of the
    fused resident descent (``descent_seconds``) against the modeled cost
    of every pread issued under the deployment tier
    (``pread_modeled_seconds``).  ``bound`` names the larger side."""
    compute = float(s.descent_seconds)
    io = float(s.pread_modeled_seconds)
    total = compute + io
    return {
        "compute_seconds": compute,
        "io_seconds": io,
        "io_fraction": (io / total) if total > 0 else None,
        "bound": (("pread" if io >= compute else "descent")
                  if total > 0 else None),
    }


def bench_pipeline(idx: Index, keys: np.ndarray, *, n_batches: int = 8,
                   batch: int = 512) -> dict:
    """Pipeline-on vs pipeline-off on the slow tier: ``lookup_batches``
    with batch-i+1 prefetch overlapping batch-i descent must return
    windows identical to sequential ``lookup`` (fatal gate), and the
    roofline must show the engine pread-bound on ``azure_hdd`` — the
    whole point of overlapping I/O is that I/O dominates.

    Unlike the cache sweep this cell wants *misses*: uniform queries (no
    hot set) against a cache smaller than the disk-resident layers, so
    every batch issues real preads and the modeled azure_hdd seek time
    dwarfs the fused-descent compute."""
    rng = np.random.default_rng(31)
    batches = [rng.choice(keys, batch) for _ in range(n_batches)]
    base = ServeSpec(cache_bytes=(8 << 10,))

    svc = idx.serve(profile=DRIFT_SERVED, spec=base)
    t0 = time.perf_counter()
    want = [svc.lookup(qs) for qs in batches]
    off_wall = time.perf_counter() - t0
    off_roof = _io_split(svc.stats)
    svc.close()

    svc = idx.serve(profile=DRIFT_SERVED,
                    spec=base.replace(pipeline_depth=2, prefetch_layers=2))
    t0 = time.perf_counter()
    got = svc.lookup_batches(batches)
    on_wall = time.perf_counter() - t0
    on_roof = _io_split(svc.stats)
    s = svc.stats
    row = {
        "tier": DRIFT_SERVED,
        "identical": bool(all(np.array_equal(w, g)
                              for w, g in zip(want, got))),
        "qps_off": n_batches * batch / max(off_wall, 1e-9),
        "qps_on": n_batches * batch / max(on_wall, 1e-9),
        "pipelined_batches": s.pipelined_batches,
        "overlapped_preads": s.overlapped_preads,
        "overlapped_pread_seconds": s.overlapped_pread_seconds,
        "roofline_off": off_roof,
        "roofline_on": on_roof,
        # acceptance: the pipelined engine is pread-bound on azure_hdd
        "pread_bound": bool(on_roof["bound"] == "pread"
                            and on_roof["io_fraction"] >= 0.8),
    }
    svc.close()
    row["speedup"] = row["qps_on"] / max(row["qps_off"], 1e-9)
    return row


def bench_drift(D: KeyPositions, workdir: str) -> dict:
    """The observe→retune loop end to end: tune on DRIFT_TUNED, serve on
    DRIFT_SERVED, detect drift from persisted ServeStats, then warm- vs
    cold-retune for the observed profile.  The warm search must land
    within 1% of the cold cost with strictly fewer builds (fatal gate);
    wall-clock only informs."""
    idx = Index.tune(D, DRIFT_TUNED, DRIFT_SPEC).build()
    path = os.path.join(workdir, "drift.air")
    idx.save(path)
    rng = np.random.default_rng(11)
    svc = idx.serve(profile=DRIFT_SERVED, persist_stats=True)
    for _ in range(8):
        svc.lookup(_skewed_queries(D.keys, 512, rng))
    report = detect_drift(svc)
    observed = svc.observed_profile(measured=False)   # modeled degraded
    #                                 tier + observed hit rate: CI-stable
    svc.close()

    t0 = time.perf_counter()
    cold = idx.retune(observed).build()
    cold_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = idx.retune(observed, warm_start=True).build()
    warm_wall = time.perf_counter() - t0

    recovery = warm.cost / cold.cost if cold.cost > 0 else float("inf")
    work_ok = (warm.stats.layers_reused > cold.stats.layers_reused
               and warm.stats.layers_built < cold.stats.layers_built)
    return {
        "tuned_tier": DRIFT_TUNED, "served_tier": DRIFT_SERVED,
        "report": report.to_dict(),
        "drift_detected": bool(report.drifted and report.action == "retune"),
        "recorded_cost_us": idx.cost * 1e6,
        "cold": {"cost_us": cold.cost * 1e6, "wall_s": cold_wall,
                 "built": cold.stats.layers_built,
                 "reused": cold.stats.layers_reused},
        "warm": {"cost_us": warm.cost * 1e6, "wall_s": warm_wall,
                 "built": warm.stats.layers_built,
                 "reused": warm.stats.layers_reused,
                 "seeded": warm.stats.layers_seeded},
        "recovery_ratio": recovery,          # ≤ 1.01 required
        "work_reduction": (cold.stats.layers_built
                           / max(warm.stats.layers_built, 1)),
        "warm_recovers": bool(recovery <= 1.01 and work_ok),
        "warm_wall_faster": bool(warm_wall < cold_wall),
    }


#: serve-path tuning ladder — every *tunable* family is tuned once per
#: rung and keeps its realized-best candidate.  The raw tier alone
#: mis-prices the serve path (index-layer reads hit the block cache, the
#: final data read never does), so families also tune for the cached
#: deployment at a high and a fully-warmed hit rate; selection is by
#: *observed* per-query cost through the engine, which is fair to every
#: family because they all get the same ladder and the same stream.
SERVE_LADDER = ("raw", 0.9, 1.0)


def _ladder_profile(tier: str, rung):
    if rung == "raw":
        return PROFILES[tier]
    return CachedProfile(backing=PROFILES[tier],
                         cache=PROFILES["host_dram"], hit_rate=float(rung))


def _serve_design(design, tier, stream, workdir, tag) -> dict:
    """One candidate through the engine: same cache spec, same stream."""
    path = os.path.join(workdir, f"baseline_{tag}.air")
    Index.from_design(design, spec=TuneSpec(page_bytes=PAGE),
                      profile=tier).save(path)
    svc = None
    try:
        svc = IndexService(path, profile=tier,
                           spec=ServeSpec(cache_bytes=(64 << 10, 512 << 10)))
        t0 = time.perf_counter()
        for qs in stream:
            svc.lookup(qs)
        wall = time.perf_counter() - t0
        s = svc.stats
        return {
            "layers": len(design.layers),
            "eq6_cost_us": expected_latency(design, PROFILES[tier]) * 1e6,
            "observed_us": s.query_modeled_seconds * 1e6,
            "walk_us": s.walk_query_seconds * 1e6,
            "hit_rate": s.hit_rate,
            "preads": s.preads,
            "bytes_fetched": s.bytes_fetched,
            "qps": len(stream) * len(stream[0]) / max(wall, 1e-9),
        }
    finally:
        if svc is not None:
            svc.close()
        os.unlink(path)


def bench_baseline_serve(D: KeyPositions, tier: str, workdir: str, *,
                         n_batches: int = 8, batch: int = 512) -> dict:
    """§7.2 on the real serve path: every family's candidates served
    through the SAME engine + cache against the same skewed stream, the
    dominance margin compared between per-family *realized-best*
    candidates (per-query observed E[T]).

    Each tunable family (airtune, rmi, pgm) tunes once per
    ``SERVE_LADDER`` rung — the raw tier plus cached deployments at
    h=0.9 / h=1.0 — and is judged by its best observed cost; btree is
    fixed-shape.  This closes the raw-tier mispricing gap (a raw-tuned
    design pays coarse data reads the cached path never amortizes away)
    without hand-picking a profile for AirTune only."""
    tuners = {
        "airtune": lambda prof: Index.tune(D, prof, DRIFT_SPEC)
                                     .build().result.design,
        "rmi": lambda prof: tune_rmi(D, prof).design,
        "pgm": lambda prof: tune_pgm(D, prof).design,
    }
    rng = np.random.default_rng(23)
    stream = [_skewed_queries(D.keys, batch, rng) for _ in range(n_batches)]
    rows, ladder = {}, {}
    for name, tuner in tuners.items():
        best = None
        ladder[name] = {}
        for rung in SERVE_LADDER:
            design = tuner(_ladder_profile(tier, rung))
            r = _serve_design(design, tier, stream, workdir,
                              f"{name}_{rung}")
            r["rung"] = str(rung)
            ladder[name][str(rung)] = r["observed_us"]
            if best is None or r["observed_us"] < best["observed_us"]:
                best = r
        rows[name] = best
    r = _serve_design(build_fixed_btree(D), tier, stream, workdir, "btree")
    r["rung"] = "fixed"
    ladder["btree"] = {"fixed": r["observed_us"]}
    rows["btree"] = r
    air = rows["airtune"]["observed_us"]
    for name, row in rows.items():
        if name != "airtune":
            row["margin_vs_airtune"] = row["observed_us"] / max(air, 1e-12)
    margins = [row["margin_vs_airtune"] for n, row in rows.items()
               if n != "airtune"]
    return {"tier": tier, "designs": rows, "ladder": ladder,
            "min_margin": min(margins),
            # §7.2 on the serve path: AirTune ≤ every baseline (small
            # slack: cache/residency interactions are not in the model)
            "dominates": bool(min(margins) >= 0.999)}


# ---------------------------------------------------------------------------
# Sharded fleet vs one monolithic index under skewed hot/cold traffic
# ---------------------------------------------------------------------------
# Large records put the monolith's Eq. 6 optimum at a 2-layer design with
# a multi-MB disk-resident bottom layer — the regime where a cache byte
# budget is a real resource.  The budget is half the monolith's raw
# working set, so the monolith is capacity-constrained by construction;
# the fleet must win it back through per-shard tuning plus marginal-gain
# budgeting (Fleet.retune_budgeted), not through extra memory.
FLEET_N_KEYS = 400_000
FLEET_RECORD = 1024
FLEET_SHARDS = 4
FLEET_WEIGHTS = (0.90, 0.06, 0.03, 0.01)   # hot/cold traffic per shard
FLEET_TIER = "azure_ssd"
FLEET_BATCHES, FLEET_BATCH = 24, 512
FLEET_TUNE = TuneSpec(lam_low=2**8, lam_high=2**17, lam_base=2.0, k=4,
                      max_layers=8, page_bytes=PAGE)


def _fleet_stream(keys: np.ndarray, shard_map, rng) -> list:
    """Skewed-across, uniform-within: batch keys drawn per shard with
    FLEET_WEIGHTS, uniform inside each shard's key range."""
    sl = shard_map.slice_bounds(keys)
    batches = []
    for _ in range(FLEET_BATCHES):
        sid = rng.choice(len(FLEET_WEIGHTS), size=FLEET_BATCH,
                         p=FLEET_WEIGHTS)
        b = np.empty(FLEET_BATCH, dtype=np.uint64)
        for s in range(len(FLEET_WEIGHTS)):
            m = sid == s
            if m.any():
                b[m] = keys[rng.integers(sl[s][0], sl[s][1],
                                         size=int(m.sum()))]
        batches.append(b)
    return batches


def _fleet_identity(fleet, batches, tier: str) -> dict:
    """The acceptance gate: fleet scatter-gather must be bit-identical to
    sequential per-shard IndexService lookups (+ base), and
    ``lookup_batches`` identical to per-batch ``lookup``."""
    flat = np.concatenate(batches)
    want = np.empty((len(flat), 2), dtype=np.int64)
    for sid, pos in fleet.shard_map.sub_batches(flat):
        with IndexService(fleet.shards[sid].path, profile=tier) as ref:
            want[pos] = ref.lookup(flat[pos]) + fleet.bases[sid]
    with fleet.serve(persist_stats=False) as svc:
        got = svc.lookup(flat)
        got_b = np.concatenate(svc.lookup_batches(batches))
    return {
        "scatter_gather_identical": bool(np.array_equal(got, want)),
        "batches_identical": bool(np.array_equal(got_b, want)),
    }


def _serve_mono(idx: Index, budget: int, batches, workdir, tag) -> dict:
    path = os.path.join(workdir, f"mono_{tag}.air")
    idx.save(path)
    with IndexService(path, profile=FLEET_TIER,
                      spec=ServeSpec(cache_bytes=(budget,))) as svc:
        svc.lookup_batches(batches)
        s = svc.stats
        return {"candidate": tag, "design": idx.describe(),
                "observed_us": s.query_modeled_seconds * 1e6,
                "hit_rate": s.hit_rate, "preads": s.preads}


def _serve_fleet(fleet, budget: int, batches) -> dict:
    with fleet.serve(total_cache_bytes=budget) as svc:
        svc.lookup_batches(batches)
        return svc.stats_summary()


def run_fleet_bench(n_keys: int = FLEET_N_KEYS,
                    record: int = FLEET_RECORD) -> dict:
    """Per-shard-tuned fleet vs one monolithic index, same storage tier,
    same total cache budget, same skewed stream.

    Phase 1 serves both raw-tier-tuned; phase 2 gives the fleet
    ``Fleet.retune_budgeted`` (steady-state per-shard retune + water-
    filled budget) and gives the monolith the same intelligence as three
    candidates — raw-tuned, fully-cached-tuned, and planned-hit-rate-
    tuned — keeping its realized best.  Gates: scatter-gather identity
    (fatal) and phase-2 fleet strictly below the monolith's best (fatal).
    """
    workdir = tempfile.mkdtemp(prefix="fleet_bench_")
    keys = sosd_like("gmm", n_keys)
    D = KeyPositions.fixed_record(keys, record)
    backing = PROFILES[FLEET_TIER]
    dram = PROFILES["host_dram"]
    fspec = FleetSpec(n_shards=FLEET_SHARDS, tune=FLEET_TUNE,
                      serve=ServeSpec(persist_stats=True))

    # monolith candidates: raw + the same ladder the fleet gets
    t0 = time.perf_counter()
    mono_raw = Index.tune(D, FLEET_TIER, FLEET_TUNE).build()
    mono_tune_s = time.perf_counter() - t0
    ws_raw = demand_from_design(0, mono_raw.result.design,
                                backing, cache=dram).working_set
    mono_h1 = Index.tune(D, CachedProfile(backing=backing, cache=dram,
                                          hit_rate=1.0), FLEET_TUNE).build()
    ws_h1 = demand_from_design(0, mono_h1.result.design,
                               backing, cache=dram).working_set
    # budget = 1.25x one shard's slice of the monolith's fully-cached
    # working set: scarce against the monolith's fine design (~0.31x) and
    # against the fleet's total steady-state demand, so water-filling has
    # to choose — roughly the hot shards' working sets and nothing else
    budget = max(PAGE, (int(1.25 * ws_h1 / FLEET_SHARDS) + PAGE - 1)
                 // PAGE * PAGE)
    monos = [(mono_raw, "raw"), (mono_h1, "h1.0")]
    hp = min(1.0, budget / ws_h1) if ws_h1 > 0 else 0.0
    if 0.0 < hp < 1.0:
        monos.append((Index.tune(D, CachedProfile(backing=backing,
                                                  cache=dram, hit_rate=hp),
                                 FLEET_TUNE).build(), f"h{hp:.2f}"))

    # fleet phase 1: raw per-shard tuning
    t0 = time.perf_counter()
    fleet1 = Fleet.tune(D, FLEET_TIER, fspec).build()
    fleet_tune_s = time.perf_counter() - t0
    dir1 = os.path.join(workdir, "fleet_raw")
    fleet1.save(dir1)

    rng = np.random.default_rng(42)
    batches = _fleet_stream(keys, fleet1.shard_map, rng)

    identity = _fleet_identity(fleet1, batches, FLEET_TIER)
    phase1 = _serve_fleet(fleet1, budget, batches)   # persists shard stats

    mono_rows = [_serve_mono(idx, budget, batches, workdir, tag)
                 for idx, tag in monos]

    # fleet phase 2: observed-traffic retune + water-filled budget
    t0 = time.perf_counter()
    fleet2, plan = Fleet.open(dir1, data=D).retune_budgeted(
        data=D, total_cache_bytes=budget)
    fleet2.build()
    retune_s = time.perf_counter() - t0
    dir2 = os.path.join(workdir, "fleet_budgeted")
    fleet2.save(dir2)
    phase2 = _serve_fleet(Fleet.open(dir2), budget, batches)

    mono_best = min(mono_rows, key=lambda r: r["observed_us"])
    us_fleet = phase2["query_modeled_us"]
    return {
        "n_keys": int(D.n), "record": record, "tier": FLEET_TIER,
        "n_shards": FLEET_SHARDS, "weights": list(FLEET_WEIGHTS),
        "cache_budget_bytes": budget,
        "mono_working_set_raw": int(ws_raw),
        "identity": identity,
        "mono": mono_rows,
        "mono_best": mono_best,
        "fleet_phase1": phase1,
        "fleet_phase2": phase2,
        "plan": plan.to_dict(),
        "shard_designs": [idx.describe() for idx in fleet2.shards],
        "wall": {"mono_tune_s": mono_tune_s, "fleet_tune_s": fleet_tune_s,
                 "fleet_retune_s": retune_s},
        "fleet_vs_mono": us_fleet / max(mono_best["observed_us"], 1e-12),
        "identical": bool(identity["scatter_gather_identical"]
                          and identity["batches_identical"]),
        "fleet_beats_monolith": bool(
            us_fleet < 0.999 * mono_best["observed_us"]),
    }


def emit_fleet(results: dict) -> None:
    emit("fleet_identity", 0.0,
         f"scatter_gather={results['identity']['scatter_gather_identical']} "
         f"batches={results['identity']['batches_identical']}")
    emit("fleet_phase1_raw", results["fleet_phase1"]["query_modeled_us"],
         f"hit_rate={results['fleet_phase1']['hit_rate']:.3f} "
         f"preads={results['fleet_phase1']['preads']}")
    for r in results["mono"]:
        emit(f"fleet_mono_{r['candidate']}", r["observed_us"],
             f"hit_rate={r['hit_rate']:.3f} preads={r['preads']}")
    emit("fleet_phase2_budgeted",
         results["fleet_phase2"]["query_modeled_us"],
         f"hit_rate={results['fleet_phase2']['hit_rate']:.3f} "
         f"preads={results['fleet_phase2']['preads']} "
         f"budget={results['cache_budget_bytes']}")
    shares = (results["fleet_phase2"].get("plan") or {}).get("shares", {})
    emit("fleet_cache_plan", 0.0,
         f"shares={shares} budget={results['cache_budget_bytes']}")
    emit("fleet_vs_monolith", 0.0,
         f"ratio={results['fleet_vs_mono']:.4f} "
         f"mono_best={results['mono_best']['candidate']} "
         f"beats={results['fleet_beats_monolith']}")


# ---------------------------------------------------------------------------
# chaos gate (--chaos / --chaos-only) — BENCH_chaos.json
# ---------------------------------------------------------------------------
CHAOS_PAGE = 1024
CHAOS_RETRY = RetryPolicy(max_attempts=4, backoff_s=1e-5, max_backoff_s=1e-3)
CHAOS_SPEC = ServeSpec(cache_bytes=(64 << 10,), retry=CHAOS_RETRY)
# every recoverable schedule the engine must serve bit-identically through;
# corrupt schedules gate on multi-page reads so the engine's single-page
# repair refetch comes back clean (its window key differs, but an unbounded
# rate would re-corrupt it)
CHAOS_SCHEDULES = (
    ("eio", dict(eio_rate=0.3, eio_attempts=2)),
    ("torn_read", dict(short_rate=0.4, short_attempts=2)),
    ("stall", dict(stall_rate=0.3, stall_seconds=2e-4, stall_attempts=1)),
    ("corrupt", dict(corrupt_rate=1.0, corrupt_attempts=1,
                     only_over_bytes=CHAOS_PAGE)),
    ("flaky_start", dict(fail_first=3)),
    # coalesced runs fail persistently, single pages succeed: the engine
    # must fall back to page-granularity fetches (graceful degradation)
    ("degraded_split", dict(eio_rate=1.0, eio_attempts=None,
                            only_over_bytes=CHAOS_PAGE)),
    ("combined", dict(eio_rate=0.4, eio_attempts=1, short_rate=0.4,
                      short_attempts=1, corrupt_rate=0.8, corrupt_attempts=1,
                      stall_rate=0.3, stall_seconds=2e-4, stall_attempts=1,
                      only_over_bytes=CHAOS_PAGE)),
)


def _chaos_counters(svc: IndexService) -> dict:
    s = svc.stats
    return {"preads": s.preads, "io_retries": s.io_retries,
            "io_timeouts": s.io_timeouts, "degraded_runs": s.degraded_runs,
            "corrupt_pages": s.corrupt_pages,
            "tainted_samples": sum(1 for r in s.read_samples if r[3])}


def _chaos_design(D: KeyPositions):
    """A dense 3-layer stack (hundreds of disk pages) — the demo design is
    a handful of pages that fit the cache whole, which would let most
    fault schedules run to completion without a single pread to fault."""
    from repro.core import IndexDesign
    from repro.core.builders import build_gband, build_gstep
    from repro.core.nodes import outline
    l1 = build_gstep(D, 8, 2**6)
    o1 = outline(l1, D)
    l2 = build_gband(o1, 2**9)
    l3 = build_gstep(outline(l2, o1), 8, 2**7)
    return IndexDesign(layers=(l1, l2, l3), data=D)


def _chaos_alt_design(D: KeyPositions):
    """A structurally different stack over the same data, distinguishable
    from the demo design by its windows — what a retune would hot-swap in."""
    from repro.core import IndexDesign
    from repro.core.builders import build_gband, build_gstep
    from repro.core.nodes import outline
    l1 = build_gstep(D, 8, 2**9)
    o1 = outline(l1, D)
    l2 = build_gband(o1, 2**8)
    l3 = build_gstep(outline(l2, o1), 8, 2**6)
    return IndexDesign(layers=(l1, l2, l3), data=D)


def _chaos_schedules_row(path, queries, want, meta_end: int,
                         resident_bytes: int) -> list:
    # schedules gate past the meta region: a dense schedule over the
    # multi-window header parse can exhaust the whole open budget before
    # a single data page is served (persistent header failure is its own
    # scenario under typed_failures); open-time resident-layer loads and
    # all serving preads still run through the fault schedule
    rows = []
    for name, kw in CHAOS_SCHEDULES:
        kw = dict(kw)
        if name == "degraded_split":
            # persistent failure for *coalesced* runs only: the gate must
            # also clear the one-shot resident-layer blob load at open,
            # which has no finer granularity to degrade to
            kw["only_over_bytes"] = max(CHAOS_PAGE, resident_bytes)
        svc = IndexService(
            path, profile=None, spec=CHAOS_SPEC,
            backend_factory=lambda p: FaultInjectingBackend(
                FileBackend(p), seed=11, page_bytes=CHAOS_PAGE,
                only_from_offset=meta_end, **kw))
        try:
            t0 = time.perf_counter()
            got = svc.lookup(queries)
            wall = time.perf_counter() - t0
            rows.append({"schedule": name,
                         "identical": bool(np.array_equal(want, got)),
                         "qps": len(queries) / max(wall, 1e-9),
                         **_chaos_counters(svc)})
        finally:
            svc.close()
    return rows


def _chaos_typed_failures(path, queries, meta_end: int) -> dict:
    """Past-the-budget failures must surface as *typed* errors, never as
    silent wrong answers or a bare OSError out of the engine's guts."""
    from repro.serve import CorruptPageError
    out = {}
    # the typed error may surface at open (resident-layer load) or at the
    # first lookup — both are honest fail-stops; a silent wrong answer or
    # a bare OSError out of the engine's guts is the regression
    svc = None
    try:
        svc = IndexService(
            path, profile=None, spec=CHAOS_SPEC,
            backend_factory=lambda p: FaultInjectingBackend(
                FileBackend(p), seed=2, eio_rate=1.0, eio_attempts=None,
                only_from_offset=meta_end))
        svc.lookup(queries)
        out["persistent_eio"] = {"raised": None, "ok": False}
    except ReadError as e:
        out["persistent_eio"] = {"raised": type(e).__name__,
                                 "attempts": e.attempts,
                                 "ok": e.attempts == CHAOS_RETRY.max_attempts}
    except StorageError as e:   # wrong subtype: typed but not honest
        out["persistent_eio"] = {"raised": type(e).__name__, "ok": False}
    finally:
        if svc is not None:
            svc.close()
    svc = None
    try:
        svc = IndexService(
            path, profile=None, spec=CHAOS_SPEC,
            backend_factory=lambda p: FaultInjectingBackend(
                FileBackend(p), seed=2, corrupt_rate=1.0,
                corrupt_attempts=10**9, page_bytes=CHAOS_PAGE,
                only_from_offset=meta_end))
        svc.lookup(queries)
        out["persistent_corruption"] = {"raised": None, "ok": False}
    except CorruptPageError as e:
        out["persistent_corruption"] = {"raised": type(e).__name__,
                                        "page_id": e.page_id, "ok": True}
    except StorageError as e:
        out["persistent_corruption"] = {"raised": type(e).__name__,
                                        "ok": False}
    finally:
        if svc is not None:
            svc.close()
    return out


def _chaos_swap(path_a, path_b, keys) -> dict:
    """Hot-swap under live traffic: a hammer thread runs ``lookup_batches``
    while the main thread swaps between two designs — every batch must be
    served wholly by one epoch (old or new windows, never a row-mix)."""
    import threading
    rng = np.random.default_rng(3)
    batches = [rng.choice(keys, 256) for _ in range(6)]
    spec = CHAOS_SPEC.replace(pipeline_depth=2)
    with IndexService(path_a, profile=None, spec=spec) as svc:
        want_a = [svc.lookup(b) for b in batches]
    with IndexService(path_b, profile=None, spec=spec) as svc:
        want_b = [svc.lookup(b) for b in batches]

    results, errors, stop = [], [], threading.Event()
    svc = IndexService(path_a, profile=None, spec=spec)

    def hammer():
        try:
            while not stop.is_set():
                results.append(svc.lookup_batches(batches))
        except Exception as e:
            errors.append(f"{type(e).__name__}: {e}")

    t = threading.Thread(target=hammer)
    t0 = time.perf_counter()
    t.start()
    n_swaps = 8
    try:
        for k in range(n_swaps):
            svc.swap(path_b if k % 2 == 0 else path_a)
            time.sleep(0.005)
    finally:
        stop.set()
        t.join()
        wall = time.perf_counter() - t0
        swaps_recorded = svc.stats.swaps
        svc.close()
    mixed = 0
    for run in results:
        for i, got in enumerate(run):
            if not (np.array_equal(got, want_a[i])
                    or np.array_equal(got, want_b[i])):
                mixed += 1
    served = sum(len(run) * 256 for run in results)
    return {"swaps": n_swaps, "swaps_recorded": swaps_recorded,
            "batch_runs": len(results), "errors": errors,
            "mixed_batches": mixed,
            "qps_during_swaps": served / max(wall, 1e-9),
            "ok": bool(results) and not errors and mixed == 0}


class _ChaosDeadShard(FileBackend):
    """Healthy through open, then every pread raises — a shard whose disk
    died under a live fleet."""

    armed = False

    def pread(self, nbytes, offset):
        if _ChaosDeadShard.armed:
            import errno
            raise OSError(errno.EIO, "chaos: dead shard")
        return super().pread(nbytes, offset)


def _chaos_fleet(D: KeyPositions, workdir: str) -> dict:
    """One shard of three dies under traffic: the default contract is a
    typed fail-stop, ``partial_results=True`` must keep serving the two
    healthy shards bit-identically with an honest unavailable mask."""
    from repro.fleet.fleet import _partition
    from repro.fleet.service import FleetService
    from repro.fleet.spec import ShardMap
    shard_map = ShardMap.even_keys(D.keys, 3)
    parts, bases = _partition(D, shard_map)
    paths = []
    for i, part in enumerate(parts):
        p = os.path.join(workdir, f"chaos_shard_{i}.air")
        write_index(p, _chaos_design(part), page_bytes=CHAOS_PAGE)
        paths.append(p)
    rng = np.random.default_rng(2)
    qs = rng.choice(D.keys, 1024)
    with FleetService(shard_map, paths, bases, profile=None,
                      specs=[CHAOS_SPEC] * 3) as svc:
        want = svc.lookup(qs)
    sick = 1
    _ChaosDeadShard.armed = False

    def factory(p):
        return _ChaosDeadShard(p) if p == paths[sick] else FileBackend(p)

    row = {"n_shards": 3, "sick_shard": sick}
    with FleetService(shard_map, paths, bases, profile=None,
                      specs=[CHAOS_SPEC] * 3,
                      backend_factories=factory) as svc:
        _ChaosDeadShard.armed = True
        try:
            svc.lookup(qs)
            row["fail_stop"] = {"raised": None, "ok": False}
        except ShardUnavailableError as e:
            row["fail_stop"] = {"raised": type(e).__name__, "shard": e.shard,
                                "ok": e.shard == sick}
        out, avail = svc.lookup(qs, partial_results=True)
        sick_keys = shard_map.route(qs) == sick
        row["degraded"] = {
            "mask_honest": bool(np.array_equal(avail, ~sick_keys)),
            "healthy_identical": bool(
                np.array_equal(out[avail], want[avail])),
            "unavailable_fraction": float(sick_keys.mean()),
        }
        summary = svc.stats_summary()
        row["summary_unhealthy"] = summary["unhealthy_shards"]
        row["ok"] = bool(row["fail_stop"]["ok"]
                         and row["degraded"]["mask_honest"]
                         and row["degraded"]["healthy_identical"]
                         and summary["unhealthy_shards"] == 1)
    _ChaosDeadShard.armed = False
    return row


def run_chaos_bench(n_keys: int = 60_000, n_queries: int = 2048) -> dict:
    keys = sosd_like("gmm", n_keys)
    D = KeyPositions.fixed_record(keys, RECORD)
    workdir = tempfile.mkdtemp(prefix="chaos_bench_")
    path = os.path.join(workdir, "index.air")
    write_index(path, _chaos_design(D), page_bytes=CHAOS_PAGE)
    alt = os.path.join(workdir, "alt.air")
    write_index(alt, _chaos_alt_design(D), page_bytes=CHAOS_PAGE)
    rng = np.random.default_rng(0)
    queries = rng.choice(D.keys, n_queries)

    svc = IndexService(path, profile=None, spec=CHAOS_SPEC)
    try:
        meta_end = min(lm.offset for lm in svc.meta.layers)
        n_res = len(svc._st.prefix)
        resident_bytes = max(
            (lm.size for lm in svc.meta.layers[len(svc.meta.layers) - n_res:]),
            default=0)
        t0 = time.perf_counter()
        want = svc.lookup(queries)
        clean_wall = time.perf_counter() - t0
    finally:
        svc.close()
    clean_qps = n_queries / max(clean_wall, 1e-9)

    results = {"n_keys": int(D.n), "n_queries": int(n_queries),
               "page_bytes": CHAOS_PAGE,
               "retry": CHAOS_RETRY.to_dict(),
               "clean_qps": clean_qps,
               "schedules": _chaos_schedules_row(path, queries, want,
                                                 meta_end, resident_bytes),
               "typed_failures": _chaos_typed_failures(path, queries,
                                                       meta_end),
               "swap_under_traffic": _chaos_swap(path, alt, D.keys),
               "fleet_degradation": _chaos_fleet(D, workdir)}
    for row in results["schedules"]:
        row["qps_vs_clean"] = row["qps"] / max(clean_qps, 1e-9)
    results["acceptance_chaos"] = bool(
        all(r["identical"] for r in results["schedules"])
        and all(v["ok"] for v in results["typed_failures"].values())
        and results["swap_under_traffic"]["ok"]
        and results["fleet_degradation"]["ok"])
    return results


def emit_chaos(results: dict) -> None:
    for r in results["schedules"]:
        emit(f"chaos_{r['schedule']}", 0.0,
             f"identical={r['identical']} qps={r['qps']:.0f} "
             f"({r['qps_vs_clean']:.2f}x clean) retries={r['io_retries']} "
             f"degraded={r['degraded_runs']} crc={r['corrupt_pages']}")
    for name, v in results["typed_failures"].items():
        emit(f"chaos_{name}", 0.0, f"raised={v['raised']} ok={v['ok']}")
    sw = results["swap_under_traffic"]
    emit("chaos_swap_under_traffic", 0.0,
         f"ok={sw['ok']} swaps={sw['swaps']} runs={sw['batch_runs']} "
         f"mixed={sw['mixed_batches']} qps={sw['qps_during_swaps']:.0f}")
    fl = results["fleet_degradation"]
    emit("chaos_fleet_degradation", 0.0,
         f"ok={fl['ok']} fail_stop={fl['fail_stop']['raised']} "
         f"mask_honest={fl['degraded']['mask_honest']} "
         f"unavailable={fl['degraded']['unavailable_fraction']:.2f}")
    emit("chaos_acceptance", 0.0,
         f"identity_under_faults={results['acceptance_chaos']}")


def chaos_fatal_warnings(results: dict) -> list:
    """FATAL list for the chaos gate: identity and typed-error contracts.
    Wall-clock degradation under faults only warns (the injected stalls
    and backoffs *should* cost something)."""
    fatal = []
    bad = [r["schedule"] for r in results["schedules"]
           if not r["identical"]]
    if bad:
        fatal.append(f"chaos: results diverged under recoverable fault "
                     f"schedules {bad} — retries/repairs must be "
                     f"invisible in lookup results")
    for name, v in results["typed_failures"].items():
        if not v["ok"]:
            fatal.append(f"chaos: {name} did not surface the typed error "
                         f"(raised={v['raised']})")
    sw = results["swap_under_traffic"]
    if not sw["ok"]:
        fatal.append(f"chaos: hot swap under traffic broke epoch isolation "
                     f"(mixed={sw['mixed_batches']}, errors={sw['errors']})")
    fl = results["fleet_degradation"]
    if not fl["ok"]:
        fatal.append("chaos: fleet shard degradation contract failed "
                     f"(fail_stop={fl['fail_stop']}, "
                     f"degraded={fl['degraded']})")
    for r in results["schedules"]:
        if r["qps_vs_clean"] < 0.05:
            print(f"::warning::chaos schedule {r['schedule']} qps collapsed "
                  f"to {r['qps_vs_clean']:.3f}x of fault-free serving")
    return fatal


# ---------------------------------------------------------------------------
# tail-latency gate (--p99 / --p99-only) — BENCH_p99.json
# ---------------------------------------------------------------------------
# The end-to-end tail-tuning loop: calibrate a stall-heavy *data* tier
# through the fault backend into a DistributionalProfile (ServeStats
# pread reservoir → distributional_backing_profile), tune the SAME data
# twice — mean objective vs E[T] + w·Q_0.99[T] — and serve both
# head-to-head against the SAME bursty tier, judging on realized
# per-lookup wall clock (engine walk + the final data-range read).
#
# The simulated deployment: the index file sits on a throttled but
# *reliable* tier (every pread sleeps ℓ + Δ/B), while the records live
# on a remote tier with the same affine cost plus a heavy stall tail —
# reads strictly wider than P99_STALL_OVER stall P99_STALL_SECONDS at
# rate P99_STALL_RATE (deterministic per window, unbounded attempts, so
# the schedule holds for the whole run).  Large records put the
# objectives in real tension: narrow (stall-safe) data windows need a
# deeper/fatter index — extra ℓ per lookup — while wide windows are
# cheaper in expectation (stall *mass* rate·stall ≈ 0.3 ms < ℓ) but
# carry the tail (surcharge ≈ rate·stall·w/(1−p) ≈ 30 ms).  The mean
# objective buys the wide windows; the p99 objective refuses them.
# Both tunes see the same fitted profile; only the objective differs.
P99_OBJECTIVE = {"p": 0.99, "weight": 1.0}
P99_N_KEYS = 400_000
P99_RECORD = 1024              # bytes per record (the data tier is wide)
P99_PAGE = 4096
P99_BASE_SLEEP = 1e-3          # ℓ of the simulated tiers (s per pread)
P99_BANDWIDTH = 256e6          # B of the simulated tiers (bytes/s)
P99_STALL_OVER = 32768         # data reads strictly wider can stall
P99_STALL_RATE = 0.03          # fraction of wide windows that stall
P99_STALL_SECONDS = 10e-3      # the stall itself (heavy tail >> ℓ)
P99_SEED = 5
# calibration grid: sizes × probes lands exactly at the reservoir cap, so
# the fit sees every probe (no subsampling noise on the tail estimate)
P99_CAL_SIZES = (4096, 16384, 32768, 49152, 65536, 131072, 262144)
P99_CAL_PROBES = 73            # 7 × 73 = 511 ≤ READ_SAMPLE_CAP
P99_LOOKUPS = 1200
P99_SPEC = TuneSpec(lam_low=2**10, lam_high=2**19, lam_base=2.0, k=4,
                    max_layers=6, page_bytes=P99_PAGE)
P99_SERVE_SPEC = ServeSpec(cache_bytes=(P99_PAGE,))   # ~no cache: every
#                            lookup pays the tier, stalls stay exposed


class _ThrottledBackend(FileBackend):
    """Simulated slow tier over a local file: ℓ + Δ/B of sleep per
    pread, then real bytes — realized wall clock, not a model, is what
    the two tuning arms are judged on."""

    def pread(self, nbytes: int, offset: int) -> bytes:
        time.sleep(P99_BASE_SLEEP + nbytes / P99_BANDWIDTH)
        return super().pread(nbytes, offset)


def _p99_data_backend(data_path: str) -> FaultInjectingBackend:
    """The record tier: throttled + the heavy-tailed stall schedule."""
    return FaultInjectingBackend(
        _ThrottledBackend(data_path), seed=P99_SEED,
        stall_rate=P99_STALL_RATE, stall_seconds=P99_STALL_SECONDS,
        stall_attempts=10**9, only_over_bytes=P99_STALL_OVER,
        page_bytes=P99_PAGE)


def _p99_calibrate(data_path: str) -> tuple:
    """The §3.2 profiling pass, distribution-aware: probe the (bursty)
    record tier at a grid of read sizes through the ServeStats pread
    reservoir and fit the DistributionalProfile tuning consumes."""
    be = _p99_data_backend(data_path)
    st = ServeStats()
    rng = np.random.default_rng(17)
    try:
        size = be.size()
        for nbytes in P99_CAL_SIZES:
            pages = max((size - nbytes) // P99_PAGE, 1)
            for _ in range(P99_CAL_PROBES):
                off = int(rng.integers(0, pages)) * P99_PAGE
                t0 = time.perf_counter()
                be.pread(nbytes, off)
                st.record_read(nbytes, time.perf_counter() - t0)
    finally:
        be.close()
    prof = distributional_backing_profile(st)
    if prof is None:
        raise RuntimeError("p99 calibration failed to fit a profile")
    return prof, st


def _p99_layers_identical(a, b) -> bool:
    if len(a) != len(b):
        return False
    for la, lb in zip(a, b):
        if la.kind != lb.kind:
            return False
        fields = (("piece_keys", "piece_pos", "node_piece_off")
                  if la.kind == "step" else ("node_keys", "x1", "y1", "m",
                                             "delta"))
        if not all(np.array_equal(getattr(la, f), getattr(lb, f))
                   for f in fields):
            return False
    return True


def _p99_serve(index_path: str, data_path: str, queries: np.ndarray,
               warmup: int = 16) -> dict:
    """Serve single-query lookups end to end: the engine walks the index
    through the throttled (reliable) tier, then the returned data-layer
    byte range is read through the bursty record tier — the Eq. 6 data
    read, realized.  Realized wall per lookup (engine + data read) is
    the judged quantity; a second ServeStats fed the end-to-end walls
    exercises the online reservoir p50/p99 estimator on the same stream.
    """
    walls = []
    svc = IndexService(index_path, profile=None, spec=P99_SERVE_SPEC,
                       backend_factory=_ThrottledBackend)
    data_be = _p99_data_backend(data_path)
    e2e = ServeStats()
    try:
        for q in queries[:warmup]:          # page-walk + kernel warmup
            svc.lookup(np.array([q], dtype=np.uint64))
        for q in queries:
            t0 = time.perf_counter()
            out = svc.lookup(np.array([q], dtype=np.uint64))
            lo, hi = int(out[0, 0]), int(out[0, 1])
            data_be.pread(max(hi - lo, 1), lo)
            wall = time.perf_counter() - t0
            walls.append(wall)
            e2e.record_lookup(1, wall)
        online_p50 = e2e.lookup_quantile(0.5)
        online_p99 = e2e.lookup_quantile(0.99)
        s = svc.stats
        counters = {"index_preads": int(s.preads),
                    "data_preads": len(walls),
                    "hit_rate": float(s.hit_rate)}
    finally:
        data_be.close()
        svc.close()
    w = np.asarray(walls, dtype=np.float64)
    return {
        "lookups": len(walls),
        "mean_us": float(w.mean() * 1e6),
        "p50_us": float(np.percentile(w, 50) * 1e6),
        "p99_us": float(np.percentile(w, 99) * 1e6),
        "online_p50_us": (online_p50 * 1e6
                          if online_p50 is not None else None),
        "online_p99_us": (online_p99 * 1e6
                          if online_p99 is not None else None),
        **counters,
    }


def run_p99_bench(n_keys: int = P99_N_KEYS,
                  n_lookups: int = P99_LOOKUPS) -> dict:
    keys = sosd_like("gmm", n_keys)
    D = KeyPositions.fixed_record(keys, P99_RECORD)
    workdir = tempfile.mkdtemp(prefix="p99_bench_")

    # the record tier itself: a sparse file spanning the data extent (the
    # bytes read are zeros — only offsets/sizes matter to the simulated
    # tier), giving calibration a real window population to sample
    data_path = os.path.join(workdir, "records.dat")
    with open(data_path, "wb") as f:
        f.truncate(int(D.n) * P99_RECORD)
    t0 = time.perf_counter()
    fitted, cal_stats = _p99_calibrate(data_path)
    cal_wall = time.perf_counter() - t0

    # head-to-head tunes over the SAME fitted profile
    spec_mean = P99_SPEC
    spec_p99 = P99_SPEC.replace(objective=P99_OBJECTIVE)
    mean_idx = Index.tune(D, fitted, spec_mean).build()
    p99_idx = Index.tune(D, fitted, spec_p99).build()

    # identity gate: the facade's default ("mean") objective must be
    # bit-identical to a direct strategy call without the kwarg at all
    raw = airtune(D, fitted, spec_mean.builders(), k=spec_mean.k,
                  max_layers=spec_mean.max_layers)
    identity = bool(raw.cost == mean_idx.result.cost
                    and raw.builder_names == mean_idx.result.builder_names
                    and _p99_layers_identical(raw.design.layers,
                                              mean_idx.result.design.layers))
    designs_differ = not (
        mean_idx.result.builder_names == p99_idx.result.builder_names
        and _p99_layers_identical(mean_idx.result.design.layers,
                                  p99_idx.result.design.layers))

    p, w = P99_OBJECTIVE["p"], P99_OBJECTIVE["weight"]
    predicted = {
        arm: {
            "mean_us": expected_latency(idx.result.design, fitted) * 1e6,
            "p99_us": quantile_latency(idx.result.design, fitted, p) * 1e6,
        }
        for arm, idx in (("mean", mean_idx), ("p99", p99_idx))}

    mean_path = os.path.join(workdir, "tuned_mean.air")
    p99_path = os.path.join(workdir, "tuned_p99.air")
    mean_idx.save(mean_path)
    p99_idx.save(p99_path)

    rng = np.random.default_rng(1)
    queries = rng.choice(D.keys, n_lookups)
    realized = {"mean": _p99_serve(mean_path, data_path, queries),
                "p99": _p99_serve(p99_path, data_path, queries)}

    results = {
        "n_keys": int(D.n), "n_lookups": int(n_lookups),
        "record_bytes": P99_RECORD,
        "page_bytes": P99_PAGE, "objective": P99_OBJECTIVE,
        "tier": {"base_sleep_s": P99_BASE_SLEEP,
                 "bandwidth": P99_BANDWIDTH,
                 "stall_over_bytes": P99_STALL_OVER,
                 "stall_rate": P99_STALL_RATE,
                 "stall_seconds": P99_STALL_SECONDS},
        "calibration": {
            "probes": len(cal_stats.read_samples),
            "sizes": list(P99_CAL_SIZES),
            "wall_s": cal_wall,
            "fitted_profile": profile_to_dict(fitted),
        },
        "designs": {"mean": mean_idx.describe(), "p99": p99_idx.describe()},
        "recorded_objectives": {
            "mean": mean_idx.result.objective,
            "p99": p99_idx.result.objective},
        "predicted": predicted,
        "realized": realized,
        "identity_mean_objective": identity,
        "designs_differ": designs_differ,
        "p99_wins_realized_p99":
            bool(realized["p99"]["p99_us"] < realized["mean"]["p99_us"]),
        "mean_regression_ratio":
            realized["p99"]["mean_us"] / max(realized["mean"]["mean_us"],
                                             1e-12),
    }
    return results


def emit_p99(results: dict) -> None:
    emit("p99_identity", 0.0,
         f"mean_objective_bit_identical={results['identity_mean_objective']}")
    for arm in ("mean", "p99"):
        r = results["realized"][arm]
        pr = results["predicted"][arm]
        emit(f"p99_tuned_{arm}", r["p99_us"],
             f"mean={r['mean_us']:.0f}us p50={r['p50_us']:.0f}us "
             f"p99={r['p99_us']:.0f}us "
             f"(online_p99={r['online_p99_us'] or float('nan'):.0f}us, "
             f"predicted_p99={pr['p99_us']:.0f}us) "
             f"index_preads={r['index_preads']}")
    emit("p99_acceptance", 0.0,
         f"designs_differ={results['designs_differ']} "
         f"p99_wins={results['p99_wins_realized_p99']} "
         f"mean_ratio={results['mean_regression_ratio']:.2f}")


def p99_fatal_warnings(results: dict) -> list:
    """FATAL list for the tail-latency gate: the mean-objective identity
    and the head-to-head realized-p99 win.  A realized *mean* regression
    of the p99-tuned design only warns — trading some expectation for the
    tail is the objective working as designed, but a large regression
    deserves eyes."""
    fatal = []
    if not results["identity_mean_objective"]:
        fatal.append("p99: objective='mean' tune diverged from the "
                     "pre-objective search — the default must stay "
                     "bit-identical")
    if not results["designs_differ"]:
        fatal.append("p99: mean- and p99-tuned designs are identical — "
                     "the scenario no longer separates the objectives "
                     "(retune the bench knobs)")
    if not results["p99_wins_realized_p99"]:
        fatal.append(
            f"p99: tail-tuned design lost on realized p99 "
            f"({results['realized']['p99']['p99_us']:.0f}us vs "
            f"mean-tuned {results['realized']['mean']['p99_us']:.0f}us)")
    if results["mean_regression_ratio"] > 2.0:
        print(f"::warning::p99-tuned design's realized mean is "
              f"{results['mean_regression_ratio']:.2f}x the mean-tuned "
              f"design's (expected to trade some mean for tail, but check "
              f"the margin)")
    return fatal


def run_serve_bench(n_keys: int = N_KEYS, n_queries: int = 4096) -> dict:
    keys = sosd_like("gmm", n_keys)
    D = KeyPositions.fixed_record(keys, RECORD)
    design = build_serving_design(D)
    path = os.path.join(tempfile.mkdtemp(prefix="serve_bench_"), "index.air")
    idx = Index.from_design(design, spec=TuneSpec(page_bytes=PAGE))
    idx.save(path)
    rng = np.random.default_rng(0)
    queries = rng.choice(D.keys, n_queries)

    results = {"design": design.describe(), "page_bytes": PAGE,
               "n_keys": int(D.n), "n_queries": int(n_queries),
               "cold_warm": [], "cache_sweep": [],
               "expected_latency_us": {
                   t: expected_latency(design, PROFILES[t]) * 1e6
                   for t in TIERS}}
    for tier in TIERS:
        cw = bench_cold_warm(idx, tier, queries)
        results["cold_warm"].append(cw)
        emit(f"serve_cold_{tier}", cw["cold"]["modeled_seconds"] * 1e6,
             f"bytes={cw['cold']['bytes_fetched']} preads={cw['cold']['preads']}"
             f" qps={cw['cold']['qps']:.0f}")
        emit(f"serve_warm_{tier}", cw["warm"]["modeled_seconds"] * 1e6,
             f"bytes={cw['warm']['bytes_fetched']} preads={cw['warm']['preads']}"
             f" qps={cw['warm']['qps']:.0f}"
             f" fewer_bytes={cw['warm_fewer_bytes']}"
             f" faster_modeled={cw['warm_faster_modeled']}")
        for row in bench_cache_sweep(idx, tier, D.keys):
            results["cache_sweep"].append(row)
            emit(f"serve_sweep_{tier}_{row['cache_bytes'] >> 10}KiB",
                 row["modeled_seconds"] * 1e6,
                 f"hit_rate={row['hit_rate']:.3f} qps={row['qps']:.0f} "
                 f"bytes={row['bytes_fetched']}")
    results["engine_vs_scalar"] = bench_engine_vs_scalar(idx, queries)
    ev = results["engine_vs_scalar"]
    emit("serve_engine_vs_scalar", 0.0,
         f"engine={ev['engine_qps']:.0f}q/s scalar={ev['scalar_qps']:.0f}q/s "
         f"speedup={ev['speedup']:.1f}x")

    pipe = bench_pipeline(idx, D.keys)
    results["pipeline"] = pipe
    emit(f"serve_pipeline_{DRIFT_SERVED}",
         pipe["roofline_on"]["io_seconds"] * 1e6,
         f"identical={pipe['identical']} qps_on={pipe['qps_on']:.0f} "
         f"qps_off={pipe['qps_off']:.0f} "
         f"io_fraction={pipe['roofline_on']['io_fraction']:.3f} "
         f"bound={pipe['roofline_on']['bound']} "
         f"overlapped_preads={pipe['overlapped_preads']}")

    workdir = os.path.dirname(path)
    drift = bench_drift(D, workdir)
    results["drift"] = drift
    emit(f"serve_drift_{DRIFT_TUNED}_to_{DRIFT_SERVED}",
         drift["report"]["observed_us"] or 0.0,
         f"ratio={drift['report']['ratio']:.2f} "
         f"action={drift['report']['action']} "
         f"hit_rate={drift['report']['hit_rate']:.3f}")
    emit("serve_drift_retune", drift["warm"]["cost_us"],
         f"recovery={drift['recovery_ratio']:.4f} "
         f"warm_built={drift['warm']['built']} "
         f"cold_built={drift['cold']['built']} "
         f"reused={drift['warm']['reused']} "
         f"work_reduction={drift['work_reduction']:.1f}x")

    results["baseline_serve"] = []
    for tier in ("azure_ssd", "azure_hdd"):
        bs = bench_baseline_serve(D, tier, workdir)
        results["baseline_serve"].append(bs)
        for name, r in bs["designs"].items():
            mg = r.get("margin_vs_airtune")
            emit(f"serve_baseline_{tier}_{name}", r["observed_us"],
                 f"hit_rate={r['hit_rate']:.3f} qps={r['qps']:.0f}"
                 + (f" margin={mg:.2f}x" if mg is not None else ""))
        emit(f"serve_baseline_{tier}_dominance", 0.0,
             f"min_margin={bs['min_margin']:.3f} "
             f"dominates={bs['dominates']}")

    ok = all(cw["warm_fewer_bytes"] and cw["warm_faster_modeled"]
             for cw in results["cold_warm"])
    results["acceptance_warm_beats_cold_all_tiers"] = ok
    results["acceptance_drift_recovery"] = bool(
        drift["drift_detected"] and drift["warm_recovers"])
    results["baseline_serve_dominates_all_tiers"] = all(
        bs["dominates"] for bs in results["baseline_serve"])
    results["acceptance_pipeline"] = bool(
        pipe["identical"] and pipe["pread_bound"])
    emit("serve_acceptance", 0.0,
         f"warm_beats_cold_on_{len(results['cold_warm'])}_tiers={ok} "
         f"drift_recovery={results['acceptance_drift_recovery']} "
         f"baseline_dominance={results['baseline_serve_dominates_all_tiers']} "
         f"pipeline={results['acceptance_pipeline']}")
    os.unlink(path)
    return results


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also dump results as JSON (e.g. BENCH_serve.json)")
    ap.add_argument("--n-keys", type=int, default=N_KEYS)
    ap.add_argument("--n-queries", type=int, default=4096)
    ap.add_argument("--fleet-json", metavar="PATH", default=None,
                    help="run the sharded-fleet scenario and dump its "
                         "results (e.g. BENCH_fleet.json)")
    ap.add_argument("--fleet-only", action="store_true",
                    help="run only the sharded-fleet scenario")
    ap.add_argument("--fleet-n-keys", type=int, default=FLEET_N_KEYS)
    ap.add_argument("--chaos", action="store_true",
                    help="also run the fault-injection gate (identity "
                         "under faults is FATAL, qps degradation warns)")
    ap.add_argument("--chaos-only", action="store_true",
                    help="run only the fault-injection gate")
    ap.add_argument("--chaos-json", metavar="PATH", default=None,
                    help="dump the chaos gate results "
                         "(e.g. BENCH_chaos.json); implies --chaos")
    ap.add_argument("--p99", action="store_true",
                    help="also run the tail-latency gate (tune-for-p99 vs "
                         "tune-for-mean under bursty stalls; p99 win is "
                         "FATAL, a mean regression warns)")
    ap.add_argument("--p99-only", action="store_true",
                    help="run only the tail-latency gate")
    ap.add_argument("--p99-json", metavar="PATH", default=None,
                    help="dump the tail-latency gate results "
                         "(e.g. BENCH_p99.json); implies --p99")
    args = ap.parse_args()
    print("name,us_per_call,derived")

    p99_results = None
    if args.p99 or args.p99_only or args.p99_json:
        p99_results = run_p99_bench()
        emit_p99(p99_results)
        if args.p99_json:
            with open(args.p99_json, "w") as f:
                json.dump(p99_results, f, indent=2)
            print(f"# wrote {args.p99_json}", flush=True)
        if args.p99_only:
            fatal = p99_fatal_warnings(p99_results)
            if fatal:
                for msg in fatal:
                    print(f"::error::{msg}")
                sys.exit(1)
            return

    chaos_results = None
    if args.chaos or args.chaos_only or args.chaos_json:
        chaos_results = run_chaos_bench()
        emit_chaos(chaos_results)
        if args.chaos_json:
            with open(args.chaos_json, "w") as f:
                json.dump(chaos_results, f, indent=2)
            print(f"# wrote {args.chaos_json}", flush=True)
        if args.chaos_only:
            fatal = chaos_fatal_warnings(chaos_results)
            if fatal:
                for msg in fatal:
                    print(f"::error::{msg}")
                sys.exit(1)
            return

    fleet_results = None
    if args.fleet_json or args.fleet_only:
        fleet_results = run_fleet_bench(args.fleet_n_keys)
        emit_fleet(fleet_results)
        if args.fleet_json:
            with open(args.fleet_json, "w") as f:
                json.dump(fleet_results, f, indent=2)
            print(f"# wrote {args.fleet_json}", flush=True)
        if args.fleet_only:
            fatal = []
            if not fleet_results["identical"]:
                fatal.append("fleet scatter-gather diverged from "
                             "sequential per-shard lookups")
            if not fleet_results["fleet_beats_monolith"]:
                fatal.append(
                    f"per-shard-tuned fleet did not beat the monolith: "
                    f"fleet={fleet_results['fleet_phase2']['query_modeled_us']:.1f}us vs "
                    f"mono={fleet_results['mono_best']['observed_us']:.1f}us "
                    f"(ratio={fleet_results['fleet_vs_mono']:.4f}, "
                    f"need < 0.999)")
            if fatal:
                for msg in fatal:
                    print(f"::error::{msg}")
                sys.exit(1)
            return

    results = run_serve_bench(args.n_keys, args.n_queries)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {args.json}", flush=True)

    # wall-clock signals only warn (noisy CI runners must not redden the
    # build); correctness/recovery regressions below are fatal
    if results["engine_vs_scalar"]["speedup"] < 1.0:
        print("::warning::serve engine slower than the scalar walk "
              f"(speedup={results['engine_vs_scalar']['speedup']:.2f}x)")
    if not results["drift"]["warm_wall_faster"]:
        print("::warning::warm retune not faster in wall-clock "
              f"(warm={results['drift']['warm']['wall_s']:.2f}s "
              f"cold={results['drift']['cold']['wall_s']:.2f}s)")
    if results["pipeline"]["qps_on"] < results["pipeline"]["qps_off"]:
        # wall-clock only: CPU-interpreted Pallas + python threads make
        # the overlap win noisy; correctness + roofline gates are below
        print("::warning::pipelined serving slower than unpipelined "
              f"(qps_on={results['pipeline']['qps_on']:.0f} "
              f"qps_off={results['pipeline']['qps_off']:.0f})")
    fatal = []
    if not results["baseline_serve_dominates_all_tiers"]:
        # fatal since the ladder closed the raw-tier mispricing gap:
        # every family tunes over the same cached-deployment ladder and
        # is judged by realized cost, so a loss here is a real regression
        fatal.append("baseline design beat AirTune on the serve path "
                     f"(min margins: "
                     f"{[bs['min_margin'] for bs in results['baseline_serve']]})")
    if not results["acceptance_warm_beats_cold_all_tiers"]:
        fatal.append("warm cache pass did not beat the cold pass")
    if not results["drift"]["drift_detected"]:
        fatal.append("degraded tier not flagged by drift detection")
    if not results["drift"]["warm_recovers"]:
        fatal.append(
            f"warm retune failed recovery: cost ratio "
            f"{results['drift']['recovery_ratio']:.4f} (need <= 1.01) or "
            f"no work reduction (warm built "
            f"{results['drift']['warm']['built']} vs cold "
            f"{results['drift']['cold']['built']})")
    if not results["pipeline"]["identical"]:
        fatal.append("pipelined lookup_batches diverged from sequential "
                     "lookup (prefetch must be invisible in results)")
    if not results["pipeline"]["pread_bound"]:
        fatal.append(
            f"pipelined engine not pread-bound on {DRIFT_SERVED}: "
            f"io_fraction="
            f"{results['pipeline']['roofline_on']['io_fraction']:.3f} "
            f"(need >= 0.8, bound="
            f"{results['pipeline']['roofline_on']['bound']})")
    if fleet_results is not None:
        if not fleet_results["identical"]:
            fatal.append("fleet scatter-gather diverged from sequential "
                         "per-shard lookups")
        if not fleet_results["fleet_beats_monolith"]:
            fatal.append(
                f"per-shard-tuned fleet did not beat the monolith "
                f"(ratio={fleet_results['fleet_vs_mono']:.4f}, need < 0.999)")
    if chaos_results is not None:
        fatal.extend(chaos_fatal_warnings(chaos_results))
    if p99_results is not None:
        fatal.extend(p99_fatal_warnings(p99_results))
    if fatal:
        for msg in fatal:
            print(f"::error::{msg}")
        sys.exit(1)


if __name__ == "__main__":
    main()
