"""Batched index-serving engine with a tiered block cache (ROADMAP: the
production-serving path).

``lookup_serialized`` walks the file once per query; under heavy traffic
that wastes exactly the structure AirIndex tunes for — hot upper-layer
pages are re-fetched from storage again and again, and per-query ``pread``s
of overlapping ranges each pay the tier's latency ℓ.  :class:`IndexService`
serves *batches* against one serialized index through three mechanisms:

  1. **page cache** — the file is read in fixed-size pages (the paged
     layout of :mod:`repro.core.serialize`); pages pass through a tiered
     LRU (:class:`TieredBlockCache`, e.g. a small L1 over a larger L2), so
     a skewed or repeated workload stops touching storage at all;
  2. **read coalescing** — all pages a batch misses are merged into maximal
     runs (:func:`repro.core.descent.coalesce_ranges`) before any
     ``pread`` is issued: one seek per run, not per query;
  3. **resident layers** — the top ``spec.resident_layers`` index layers
     are pinned in memory at open (the root is always read in full, per
     Alg. 1) and descended in ONE fused dispatch per batch
     (:mod:`repro.kernels.fused_descent`): the numpy backend is the
     bit-exact float64 walk; ``backend="pallas"``/``"jnp"`` run the fused
     kernel over the 64-bit keys as 32-bit words, and only a prefix wider
     than the kernel's planes goes to numpy — counted in
     :class:`ServeStats`;
  4. **two-stage pipeline** — :meth:`IndexService.lookup_batches` with
     ``spec.pipeline_depth > 0`` overlaps the fused descent + disk walk of
     batch *i* (stage 2, this thread) with the coalesced first-window
     preads of batches *i+1..i+depth* (stage 1, a single background
     worker).  The prefetch stage only warms the block cache — windows are
     identical to unpipelined serving — and its preads are tagged
     ``overlapped`` in the stats so per-pread latency fits stay honest.

Configuration arrives as a :class:`repro.api.ServeSpec` (``spec=``); the
pre-spec keyword surface survives as warn-once deprecation shims.
Per-layer descent is the same :mod:`repro.core.descent` step used by
``lookup_batch`` and ``SerializedIndex``, so all three paths agree
bit-for-bit.  Observed hit rates feed back into tuning via
:meth:`IndexService.cached_profile` (→ :class:`repro.core.CachedProfile`).

Every layer boundary of a served lookup is a :func:`repro.spans.span`
(``airindex.lookup`` → ``airindex.descent`` with its ``stage`` /
``launch`` / ``collect`` phases, ``collect`` holding ``rebase``, and
``airindex.walk`` → ``airindex.walk.fetch``): on a profiler trace beside
the device's operations, and summed into :class:`ServeStats` under the
lock.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
import warnings
from collections import OrderedDict

import numpy as np

from repro.core.descent import band_prediction, coalesce_ranges, descend_layers
from repro.core.serialize import (_BAND_DT, _STEP_DT, RECORD_BYTES,
                                  gallop_step, page_crc, page_span,
                                  parse_meta, record_aligned_range)
from repro.core.storage import (CachedProfile, DistributionalProfile,
                                MeasuredProfile, PROFILES, StorageProfile)
from repro.serve.backend import (CorruptPageError, DeadlineExceededError,
                                 FileBackend, ReadError, StorageBackend)
from repro.spans import span

DEFAULT_PAGE_BYTES = 4096

STATS_SUFFIX = ".stats.json"   # ServeStats snapshots live next to the index
STATS_WINDOW = 16              # rotating window: snapshots kept per file
READ_SAMPLE_CAP = 512          # measured (Δ, seconds) pread samples retained
LOOKUP_SAMPLE_CAP = 512        # per-lookup (n, wall) samples retained
MIN_FIT_SAMPLES = 8            # reservoir samples needed before any
#                                observed-profile fit (measured or
#                                distributional) says anything


def demo_serving_design(D):
    """Canonical 3-layer stack (step <- band <- step root) used by the
    serving benchmark, example, and tests: two disk layers below a
    resident root, so the block cache actually has something to do.
    (AirTune picks 1-layer designs at container scale — optimal for
    latency, useless for exercising a cache.)"""
    from repro.core import IndexDesign
    from repro.core.builders import build_gband, build_gstep
    from repro.core.nodes import outline
    l1 = build_gstep(D, 8, 2**10)
    o1 = outline(l1, D)
    l2 = build_gband(o1, 2**9)
    l3 = build_gstep(outline(l2, o1), 8, 2**7)
    return IndexDesign(layers=(l1, l2, l3), data=D)


# ---------------------------------------------------------------------------
# tiered LRU block cache
# ---------------------------------------------------------------------------
class TieredBlockCache:
    """LRU page cache with N capacity tiers (tier 0 = hottest).

    ``get`` probes tiers in order and promotes hits to tier 0; inserts
    cascade evictions downward (tier i's LRU page demotes to tier i+1, the
    last tier evicts to nothing) — i.e. an exclusive multi-level cache, the
    software mirror of a DRAM-over-SSD-over-object-store hierarchy.
    """

    def __init__(self, capacities_bytes, page_bytes: int):
        caps = tuple(int(c) for c in capacities_bytes)
        assert caps and all(c >= 0 for c in caps), caps
        self.page_bytes = int(page_bytes)
        self.cap_pages = [c // self.page_bytes for c in caps]
        self.tiers = [OrderedDict() for _ in caps]
        self.hits = [0] * len(caps)
        self.misses = 0

    @property
    def n_tiers(self) -> int:
        return len(self.tiers)

    def __contains__(self, page_id) -> bool:
        return any(page_id in t for t in self.tiers)

    def get(self, page_id):
        """→ page bytes (promoting to tier 0) or None on a full miss."""
        for ti, tier in enumerate(self.tiers):
            if page_id in tier:
                data = tier.pop(page_id)
                self.hits[ti] += 1
                self._insert(page_id, data)
                return data
        self.misses += 1
        return None

    def peek(self, page_id):
        """→ page bytes without promotion or hit/miss accounting — the
        prefetch stage reads through this so overlapped work never skews
        the hit-rate the tuner feeds on."""
        for tier in self.tiers:
            if page_id in tier:
                return tier[page_id]
        return None

    def put(self, page_id, data) -> None:
        for tier in self.tiers:
            tier.pop(page_id, None)
        self._insert(page_id, data)

    def _insert(self, page_id, data) -> None:
        ti = 0
        while ti < len(self.tiers):
            tier = self.tiers[ti]
            tier[page_id] = data
            tier.move_to_end(page_id)
            if len(tier) <= self.cap_pages[ti]:
                return
            page_id, data = tier.popitem(last=False)   # demote the LRU page
            ti += 1

    def stats(self) -> dict:
        return {"hits_per_tier": list(self.hits), "hits": sum(self.hits),
                "misses": self.misses,
                "pages_resident": [len(t) for t in self.tiers]}


# ---------------------------------------------------------------------------
# serving statistics
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ServeStats:
    queries: int = 0
    batches: int = 0
    preads: int = 0             # coalesced reads actually issued
    ranges_requested: int = 0   # per-query per-layer ranges before merging
    pages_fetched: int = 0
    pages_hit: int = 0
    bytes_fetched: int = 0      # from storage, excluding open-time reads
    bytes_from_cache: int = 0
    open_bytes: int = 0         # root + resident layers read at open
    retries: int = 0            # window extensions (band inter-key misses)
    # -- fault-tolerance counters (RetryPolicy + checksums + hot swap) ----
    io_retries: int = 0         # failed pread attempts that were retried
    io_timeouts: int = 0        # preads past the per-pread deadline
    degraded_runs: int = 0      # coalesced runs split to page granularity
    #                             after exhausting their retry budget
    corrupt_pages: int = 0      # CRC32 failures detected on cache fill
    #                             (each is refetched once before raising)
    swaps: int = 0              # live index hot-swaps performed (counted on
    #                             the service's NEW epoch stats)
    # resident-descent batches by the backend that served them; of the
    # Pallas ones, those run by the interpreter (the CPU backend) ...
    pallas_batches: int = 0
    interpret_batches: int = 0
    jnp_batches: int = 0
    numpy_batches: int = 0
    # ... and the batches a device backend sent to numpy, by reason
    # (repro.kernels.fused_descent.prefix_gate)
    numpy_width_batches: int = 0
    pipelined_batches: int = 0  # batches served through lookup_batches'
    #                             two-stage pipeline
    overlapped_preads: int = 0  # preads issued by the prefetch stage while
    #                             stage 2 was descending another batch
    modeled_seconds: float = 0.0   # Σ T(Δ) under the configured profile
    open_modeled_seconds: float = 0.0  # the open-time share of the above
    data_modeled_seconds: float = 0.0  # Σ T(hi−lo) of returned data ranges
    # Σ T(run) of every pread actually issued under the deployment
    # profile (serving I/O; open-time resident loads excluded)
    pread_modeled_seconds: float = 0.0
    # measured host seconds of the serving thread's spans (repro.spans),
    # each nested in the one above it: lookup ⊇ descent ⊇ its three
    # device-dispatch phases; lookup ⊇ walk ⊇ walk_fetch (cache probes
    # plus coalesced preads)
    lookup_seconds: float = 0.0
    descent_seconds: float = 0.0
    descent_stage_seconds: float = 0.0    # query words and pad
    descent_launch_seconds: float = 0.0   # the one compiled call
    descent_collect_seconds: float = 0.0  # wait, copy-back, rebase
    rebase_seconds: float = 0.0  # collect's widening of the windows to
    #                              byte offsets (int64 bases added)
    h2d_bytes: int = 0          # host arrays handed to the device: the
    #                             resident planes once per epoch, then
    #                             each batch's queries, 8 B a query
    wide_queries: int = 0       # queries handed to the device whose key
    #                             has a nonzero high 32-bit word
    plane_uploads: int = 0      # resident-prefix uploads: one per epoch
    #                             (carried forward, like ``swaps``)
    walk_seconds: float = 0.0
    walk_fetch_seconds: float = 0.0
    walk_windows: int = 0       # distinct windows the disk walk scanned
    walk_rounds: int = 0        # rounds of the disk walk: one per on-disk
    #                             layer per batch, plus each further round
    #                             that galloping misses force
    prefetch_seconds: float = 0.0  # the prefetch stage's own wall
    overlapped_pread_seconds: float = 0.0  # measured wall of tagged preads
    # what the *uncached* Alg. 1 walk (lookup_serialized) would pay for the
    # same traffic under the configured profile: per query, full price for
    # every layer window (resident ones included) plus the data read —
    # the deployment tier's Eq. 6 value realized on observed queries
    walk_modeled_seconds: float = 0.0
    pread_seconds: float = 0.0  # measured wall-clock inside os.pread
    # uniform reservoir (Vitter's Algorithm R, seeded — deterministic
    # under a fixed ``sample_seed``) of measured (Δ bytes, seconds,
    # overlapped, tainted) pread samples — the raw material of
    # observed_profile(); capped at READ_SAMPLE_CAP.  Every pread ever
    # seen has equal probability of being retained, so quantile fits
    # are not biased toward the most recent burst (the old cap-eviction
    # kept a recency window).  ``overlapped`` tags preads issued by the
    # prefetch stage: they ran concurrently with compute and other I/O,
    # so their wall time measures queueing as much as the tier.
    # ``tainted`` tags reads that needed retries, blew a deadline, or
    # repaired a corrupt page: their wall time measures the *fault*, not
    # the tier, and no profile fit may ever ingest them
    # (:func:`untainted_read_samples` is the single eligibility filter).
    read_samples: list = dataclasses.field(default_factory=list)
    reads_seen: int = 0         # total preads offered to the reservoir
    # uniform reservoir of per-lookup (n_queries, wall seconds) pairs —
    # the online p50/p99 estimates (``lookup_quantile``) that feed
    # detect_drift's observed_p50/p99 fields
    lookup_samples: list = dataclasses.field(default_factory=list)
    lookups_seen: int = 0       # total lookup batches offered
    sample_seed: int = 0        # reservoir determinism knob

    @property
    def hit_rate(self) -> float:
        touched = self.pages_hit + self.pages_fetched
        return self.pages_hit / touched if touched else 0.0

    @property
    def bytes_saved(self) -> int:
        return self.bytes_from_cache

    @property
    def query_modeled_seconds(self) -> float:
        """Observed per-query E[T]: what a lookup costs through *this*
        engine (residency + block cache + coalescing) including the final
        data-range read, under the configured profile.  Open-time reads
        are amortized out — per-lookup cost is what Eq. 6 models."""
        if self.queries == 0:
            return float("nan")
        return (self.modeled_seconds - self.open_modeled_seconds
                + self.data_modeled_seconds) / self.queries

    @property
    def walk_query_seconds(self) -> float:
        """Per-query cost of the full-price (cacheless) Alg. 1 walk on the
        observed traffic — the configured profile's *prediction* for this
        design, independent of cache warm-up state.  Compared against the
        recorded ``tune.cost`` this isolates storage-tier drift; compared
        against :attr:`query_modeled_seconds` it shows the cache's gain
        (see :mod:`repro.api.drift`)."""
        if self.queries == 0:
            return float("nan")
        return self.walk_modeled_seconds / self.queries

    def _reservoir_put(self, reservoir: list, cap: int, seen: int,
                       sample: tuple, salt: int) -> None:
        """Algorithm R step: with the reservoir full, the ``seen``-th item
        replaces a uniformly random slot with probability cap/seen.  The
        replacement draw is a pure function of (sample_seed, salt, seen),
        so a fixed seed replays the identical reservoir."""
        if len(reservoir) < cap:
            reservoir.append(sample)
            return
        rng = np.random.default_rng((int(self.sample_seed) & 0x7FFFFFFF,
                                     int(salt), int(seen)))
        j = int(rng.integers(0, seen))
        if j < cap:
            reservoir[j] = sample

    def record_read(self, nbytes: int, seconds: float,
                    overlapped: bool = False, tainted: bool = False) -> None:
        self.pread_seconds += seconds
        self.reads_seen += 1
        self._reservoir_put(self.read_samples, READ_SAMPLE_CAP,
                            self.reads_seen,
                            (int(nbytes), float(seconds), bool(overlapped),
                             bool(tainted)), salt=0)

    def record_lookup(self, n_queries: int, wall_seconds: float) -> None:
        """Feed one lookup batch's wall time into the per-lookup latency
        reservoir (uniform over all batches ever served)."""
        self.lookups_seen += 1
        self._reservoir_put(self.lookup_samples, LOOKUP_SAMPLE_CAP,
                            self.lookups_seen,
                            (int(n_queries), float(wall_seconds)), salt=1)

    def lookup_quantile(self, p: float) -> float | None:
        """Online per-query wall-latency ``p``-quantile estimate.

        Each reservoir entry contributes its per-query average weighted
        by its batch size (a 64-query batch is 64 query experiences).
        None before any lookups are recorded.  Weighted empirical
        quantile with midpoint positions, linear interpolation.
        """
        if not self.lookup_samples:
            return None
        if not 0.0 < float(p) < 1.0:
            raise ValueError(f"quantile p must be in (0, 1), got {p}")
        vals = np.asarray([s / max(int(n), 1)
                           for n, s in self.lookup_samples], dtype=np.float64)
        w = np.asarray([max(int(n), 1) for n, _ in self.lookup_samples],
                       dtype=np.float64)
        order = np.argsort(vals, kind="stable")
        vals, w = vals[order], w[order]
        pos = (np.cumsum(w) - 0.5 * w) / w.sum()
        return float(np.interp(float(p), pos, vals))

    def snapshot(self) -> dict:
        d = dataclasses.asdict(self)
        d["read_samples"] = [[int(r[0]), float(r[1]), bool(r[2]), bool(r[3])]
                             for r in self.read_samples]
        d["lookup_samples"] = [[int(r[0]), float(r[1])]
                               for r in self.lookup_samples]
        d["hit_rate"] = self.hit_rate
        # derived, human-readable tail estimates (ignored on load)
        d["lookup_p50_seconds"] = self.lookup_quantile(0.5)
        d["lookup_p99_seconds"] = self.lookup_quantile(0.99)
        # NaN (no queries yet) is not valid strict JSON — null it out
        for key in ("query_modeled_seconds", "walk_query_seconds"):
            v = getattr(self, key)
            d[key] = v if np.isfinite(v) else None
        return d

    @classmethod
    def from_snapshot(cls, d: dict) -> "ServeStats":
        """Inverse of :meth:`snapshot` (derived keys are recomputed, so
        ``from_snapshot(s.snapshot())`` round-trips exactly).  Pre-pipeline
        snapshots carried 2-element read samples (→ non-overlapped) and
        pre-reliability ones 3-element samples (→ non-tainted)."""
        if not isinstance(d, dict):
            raise TypeError(f"snapshot must be an object, "
                            f"got {type(d).__name__}")
        fields = {f.name: f for f in dataclasses.fields(cls)}
        # coerce scalars through the field's declared type so a corrupt
        # value (e.g. "queries": "oops") raises here — load_serve_stats
        # turns that into a warn-and-skip, never a poisoned ServeStats
        kw = {}
        for k, v in d.items():
            f = fields.get(k)
            if f is None or k in ("read_samples", "lookup_samples"):
                continue
            kw[k] = int(v) if isinstance(f.default, int) else float(v)
        kw["read_samples"] = [
            (int(r[0]), float(r[1]),
             bool(r[2]) if len(r) > 2 else False,
             bool(r[3]) if len(r) > 3 else False)
            for r in d.get("read_samples", [])]
        kw["lookup_samples"] = [(int(r[0]), float(r[1]))
                                for r in d.get("lookup_samples", [])]
        st = cls(**kw)
        # legacy snapshots (pre-reservoir) carry no seen counters: make
        # the reservoir state self-consistent so Algorithm R keeps
        # working (seen must be >= the retained count)
        st.reads_seen = max(st.reads_seen, len(st.read_samples))
        st.lookups_seen = max(st.lookups_seen, len(st.lookup_samples))
        return st


def _count_backend(stats: ServeStats, used: str, reason) -> None:
    """Attribute one resident-descent batch to the backend that served it
    (and, for numpy standing in for a device backend, to the reason: only
    ``"width"`` is left)."""
    if used == "pallas":
        from repro.kernels import interpret_mode
        stats.pallas_batches += 1
        stats.interpret_batches += interpret_mode()
    elif used == "jnp":
        stats.jnp_batches += 1
    else:
        stats.numpy_batches += 1
        stats.numpy_width_batches += reason == "width"


# ---------------------------------------------------------------------------
# ServeStats persistence (ROADMAP: serve-path autoscaling / observe→retune)
# ---------------------------------------------------------------------------
def stats_path(index_path: str) -> str:
    """Where an index file's ServeStats snapshots live (next to the meta)."""
    return index_path + STATS_SUFFIX


def save_stats_snapshot(index_path: str, stats: ServeStats, *,
                        profile_name: str | None = None,
                        window: int = STATS_WINDOW) -> str:
    """Append one snapshot to ``<index_path>.stats.json``, keeping only the
    last ``window`` snapshots (rotating).  Returns the stats-file path."""
    path = stats_path(index_path)
    history = load_stats_history(index_path)
    history.append({"profile": profile_name, "stats": stats.snapshot()})
    history = history[-max(int(window), 1):]
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"version": 1, "snapshots": history}, f)
    os.replace(tmp, path)      # atomic: a reader never sees a torn file
    return path


def load_stats_history(index_path: str) -> list:
    """All persisted snapshots (oldest first); [] when none/unreadable.

    Never raises: a fleet startup reads N of these, and one corrupt or
    truncated file must not take the whole fleet down.  A file that exists
    but cannot be decoded (torn write, hand edit, wrong schema) warns and
    loads as empty; individual malformed snapshot entries are skipped with
    a warning rather than poisoning the readable ones."""
    path = stats_path(index_path)
    try:
        with open(path) as f:
            d = json.load(f)
    except OSError:
        return []          # no snapshot yet: the normal cold-start case
    except ValueError:
        warnings.warn(f"corrupt stats file {path!r}: not valid JSON; "
                      f"treating as empty", RuntimeWarning, stacklevel=2)
        return []
    if not isinstance(d, dict):
        warnings.warn(f"corrupt stats file {path!r}: expected an object, "
                      f"got {type(d).__name__}; treating as empty",
                      RuntimeWarning, stacklevel=2)
        return []
    snaps = d.get("snapshots") or []
    if not isinstance(snaps, list):
        warnings.warn(f"corrupt stats file {path!r}: 'snapshots' is not a "
                      f"list; treating as empty", RuntimeWarning,
                      stacklevel=2)
        return []
    good = [s for s in snaps if isinstance(s, dict)]
    if len(good) != len(snaps):
        warnings.warn(f"stats file {path!r}: skipped "
                      f"{len(snaps) - len(good)} malformed snapshot(s)",
                      RuntimeWarning, stacklevel=2)
    return good


def load_serve_stats(index_path: str) -> ServeStats | None:
    """The latest *loadable* persisted :class:`ServeStats` for an index
    file — snapshots that fail to decode are skipped (newest first, with
    a warning) rather than raised, so one torn snapshot degrades to the
    previous one instead of failing fleet startup."""
    for snap in reversed(load_stats_history(index_path)):
        try:
            return ServeStats.from_snapshot(snap["stats"])
        except (KeyError, TypeError, ValueError, IndexError):
            warnings.warn(
                f"stats file {stats_path(index_path)!r}: skipping a "
                f"snapshot that does not decode as ServeStats",
                RuntimeWarning, stacklevel=2)
    return None


def cacheable_working_set(meta, resident_layers: int = 1) -> int:
    """Bytes the block cache can usefully hold for an index file: the
    serialized sizes of every *non-resident* layer (the engine pins the
    top ``resident_layers`` in memory at open; the data layer is read by
    the caller, not through the cache).  The fleet's budget allocator
    water-fills against exactly this figure per shard."""
    L = len(meta.layers)
    n_res = min(max(int(resident_layers), 1), L) if L else 0
    return int(sum(lm.size for lm in meta.layers[:L - n_res]))


def untainted_read_samples(stats: ServeStats) -> list:
    """Reservoir samples eligible for *any* profile fitting.

    The single source of truth for the tainted filter: samples tagged
    ``tainted`` (retried, stalled past a deadline, or part of a
    corrupt-page repair) measure the *fault*, not the tier, and no
    fitting path — measured mean or distributional — may ever ingest
    them.  A flaky disk must not read as a slow one."""
    return [r for r in stats.read_samples if not (len(r) > 3 and r[3])]


def _fit_eligible_samples(stats: ServeStats, min_samples: int) -> list:
    """Shared eligibility ladder for observed-profile fits.

    Samples tagged ``overlapped`` (issued by the pipeline's prefetch
    stage while compute and other I/O were in flight) measure queueing,
    not the tier — fitting them would *under-price* the tier exactly
    when pipelining hides latency best.  They are excluded whenever
    enough blocking samples remain; a fully-pipelined window falls back
    to all *untainted* samples — the ``overlapped`` filter is the only
    one ever relaxed; the tainted filter
    (:func:`untainted_read_samples`) is unconditional, so a scarce
    mostly-tainted window yields too few samples and the fit returns
    None rather than modeling the faults."""
    clean = untainted_read_samples(stats)
    blocking = [r for r in clean if not (len(r) > 2 and r[2])]
    return blocking if len(blocking) >= min_samples else clean


def measured_backing_profile(
        stats: ServeStats,
        min_samples: int = MIN_FIT_SAMPLES) -> MeasuredProfile | None:
    """Monotone ``T(Δ)`` through the *measured* pread samples — per-size
    median wall-clock, the §3.2 measurement applied to live serving.
    None when the window holds too few eligible samples (tainted ones
    never are — see :func:`_fit_eligible_samples`) or too few distinct
    sizes to say anything about the latency/bandwidth split."""
    samples = _fit_eligible_samples(stats, min_samples)
    if len(samples) < min_samples:
        return None
    sizes = np.asarray([r[0] for r in samples], dtype=np.float64)
    secs = np.asarray([r[1] for r in samples], dtype=np.float64)
    uniq = np.unique(sizes)
    if len(uniq) < 2:
        return None
    med = [float(np.median(secs[sizes == u])) for u in uniq]
    return MeasuredProfile(deltas=tuple(float(u) for u in uniq),
                           seconds=tuple(med), name="observed-preads")


def distributional_backing_profile(
        stats: ServeStats, min_samples: int = MIN_FIT_SAMPLES,
        qs=(0.5, 0.9, 0.95, 0.99)) -> DistributionalProfile | None:
    """Per-Δ latency *distributions* from the pread reservoir — the raw
    material of tail-latency tuning (mean + mean-excess + empirical
    quantiles per size; see
    :class:`repro.core.storage.DistributionalProfile`).  Same sample
    eligibility as :func:`measured_backing_profile`: tainted reads never
    fit, the overlapped filter relaxes only when blocking samples are
    scarce.  None when too few eligible samples or distinct sizes."""
    samples = _fit_eligible_samples(stats, min_samples)
    return DistributionalProfile.fit(
        [(r[0], r[1]) for r in samples], min_samples=min_samples, qs=qs,
        name="observed-pread-dist")


def observed_profile_from_stats(stats: ServeStats, backing: StorageProfile,
                                cache: StorageProfile | None = None, *,
                                measured: bool = True,
                                min_samples: int = MIN_FIT_SAMPLES,
                                distributional: bool = False) -> CachedProfile:
    """Fold observed serving behavior into an effective ``T(Δ)``.

    The hit rate always comes from the stats; the backing tier is replaced
    by the *measured* per-pread profile when ``measured=True`` and the
    sample window supports it, else the modeled ``backing`` is kept (so
    with ``measured=False`` this is exactly the deployment-configured
    :meth:`IndexService.cached_profile`).  ``distributional=True``
    prefers the distributional fit (mean + tail mass, the input a
    quantile-objective retune needs), degrading to the measured mean
    fit, then the modeled backing.  Pure function of the snapshot —
    a reloaded snapshot yields the identical profile."""
    eff = backing
    if measured:
        m = (distributional_backing_profile(stats, min_samples=min_samples)
             if distributional else None)
        if m is None:
            m = measured_backing_profile(stats, min_samples=min_samples)
        if m is not None:
            eff = m
    # default name kept so a measured=False observed profile compares equal
    # to IndexService.cached_profile() (frozen-dataclass field equality)
    return CachedProfile(backing=eff, cache=cache, hit_rate=stats.hit_rate)


# ---------------------------------------------------------------------------
# the on-disk walk's round search
# ---------------------------------------------------------------------------
def distinct_windows(a: np.ndarray, b: np.ndarray):
    """``np.unique`` of the rows ``(a[k], b[k])`` with ``return_inverse``
    — the distinct windows sorted by start, then end, and each query's
    window — by one ``lexsort`` (``np.unique`` sorts the rows through a
    structured view, about ten times slower)."""
    order = np.lexsort((b, a))
    sa, sb = a[order], b[order]
    new = np.ones(len(order), dtype=bool)
    new[1:] = (sa[1:] != sa[:-1]) | (sb[1:] != sb[:-1])
    inv = np.empty(len(order), dtype=np.intp)
    inv[order] = np.cumsum(new) - 1
    return np.stack([sa[new], sb[new]], axis=1), inv


def _window_runs(lm, ab, page_bytes: int):
    """The windows ``ab`` of layer ``lm`` coalesced into disjoint runs of
    file bytes, each cut at page boundaries → ``(rs, re, pid, lo, hi)``:
    the runs, then bytes ``[lo, hi)`` of page ``pid`` for each piece, run
    after run in file order (a page two runs share appears twice)."""
    rs, re_ = coalesce_ranges(lm.offset + ab[:, 0], lm.offset + ab[:, 1])
    pa, pb = page_span(rs, re_ - rs, page_bytes)
    n = pb - pa
    pid = np.repeat(pa - (np.cumsum(n) - n), n) + np.arange(int(n.sum()))
    base = pid * page_bytes
    lo = np.maximum(np.repeat(rs, n) - base, 0)
    hi = np.minimum(np.repeat(re_, n) - base, page_bytes)
    return rs, re_, pid, lo, hi


def _round_search(lm, ab, inv, q, runs, pages):
    """One round of the on-disk walk over every window at once.

    ``ab`` holds the round's distinct layer-relative windows
    (:func:`distinct_windows`), ``inv`` each query's window, ``runs``
    their :func:`_window_runs`, ``pages`` the bytes of every page those
    span.  The windows are record-aligned ranges of one sorted record
    array, so the records of their runs, concatenated, are a sorted
    subsequence of the layer: one ``searchsorted`` over it finds every
    query's covering record.  Returns ``(left, right, ok, lo, hi)``:
    :func:`repro.core.serialize.window_misses`' flags per query, the
    queries that miss neither way, and the layer's prediction for them —
    bit-identical to :func:`repro.core.serialize.predict_from_records`
    on each window."""
    rs, re_, pid, plo, phi = runs
    rsz = RECORD_BYTES[lm.kind]
    raw = b"".join([pages[p][x:y] for p, x, y in
                    zip(pid.tolist(), plo.tolist(), phi.tolist())])
    step = lm.kind == "step"
    rec = np.frombuffer(raw, dtype=_STEP_DT if step else _BAND_DT)
    keys = rec["key" if step else "x1"]
    # each window's first and last record in the concatenation
    fa, fb = lm.offset + ab[:, 0], lm.offset + ab[:, 1]
    n_rec = (re_ - rs) // rsz
    r = np.searchsorted(rs, fa, side="right") - 1
    first = (np.cumsum(n_rec) - n_rec)[r] + (fa - rs[r]) // rsz
    last = first + (fb - fa) // rsz - 1
    wf, wl = first[inv], last[inv]
    left = (keys[wf] > q) & (ab[inv, 0] > 0)
    right = (keys[wl] <= q) & (ab[inv, 1] < lm.size)
    ok = np.flatnonzero(~(left | right))
    # a window that misses neither way holds the layer's rightmost key
    # <= q (or starts the layer); the clip is covering_index's.  Sorted
    # needles keep the search cache-friendly.
    qo = q[ok]
    order = np.argsort(qo, kind="stable")
    i = np.empty(len(ok), dtype=np.intp)
    i[order] = np.searchsorted(keys, qo[order], side="right") - 1
    i = np.clip(i, wf[ok], wl[ok])
    if step:
        pos = rec["pos"]
        nxt = pos[np.minimum(i + 1, len(pos) - 1)]
        lo, hi = pos[i], np.where(i == wl[ok], np.int64(lm.end_pos), nxt)
    else:
        lo, hi = band_prediction(rec["x1"], rec["y1"], rec["m"],
                                 rec["delta"], i, qo)
    return left, right, ok, lo, hi


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
#: pre-ServeSpec constructor keywords, kept as warn-once deprecation shims
_LEGACY_KWARGS = ("cache_bytes", "cache_profile", "page_bytes",
                  "resident_layers", "use_device", "coalesce_gap",
                  "persist_stats")


def _fold_legacy_kwargs(spec, legacy: dict):
    """Fold pre-spec constructor keywords into a ServeSpec, warning once
    per keyword (hard error for ``repro.*`` callers — the repo itself must
    stay on the spec surface).  ``spec`` may be None."""
    from repro.api.spec import ServeSpec   # lazy: api sits above serve
    from repro.core.deprecation import warn_deprecated
    changes = {}
    for name, val in legacy.items():
        if name not in _LEGACY_KWARGS:
            raise TypeError(
                f"IndexService got an unexpected keyword {name!r}")
        warn_deprecated(
            f"repro.serve.IndexService({name}=...) is deprecated; pass "
            f"spec=repro.api.ServeSpec(...) instead",
            stacklevel=4, once=True)
        if name == "use_device":
            changes["backend"] = "pallas" if val else "numpy"
        elif name == "cache_bytes":
            if val is not None:      # None kept the engine default — still does
                changes["cache_bytes"] = tuple(val)
        elif name == "page_bytes":
            changes["page_bytes"] = int(val or 0)
        elif name == "cache_profile":
            if val is None or isinstance(val, str):
                changes["cache_profile"] = val
            else:                    # profile object: map back to its name
                pname = getattr(val, "name", None)
                if pname not in PROFILES:
                    raise TypeError(
                        "cache_profile objects are no longer accepted; "
                        "pass a PROFILES name (or None) via ServeSpec")
                changes["cache_profile"] = pname
        else:
            changes[name] = val
    if not changes:
        return spec
    return (spec or ServeSpec()).replace(**changes)


class _ServeState:
    """One serving *epoch*: everything :meth:`IndexService.swap` replaces
    atomically — the storage backend, decoded meta, resident prefix (and
    its packed planes on the device), block cache, page-CRC table, and
    that epoch's :class:`ServeStats`.  Lookups pin the state for their
    whole batch (``pins`` refcount under the service lock), so a swap
    never closes a backend or drops planes mid-descent and no batch ever
    mixes bytes from two index files."""

    __slots__ = ("path", "storage", "file_size", "meta", "tune_meta",
                 "page_bytes", "cache", "page_crcs", "resident",
                 "prefix_lis", "prefix", "packed", "dev_planes",
                 "numpy_reason", "stats", "pins", "retired")

    def __init__(self, path: str, storage: StorageBackend):
        self.path = path
        self.storage = storage
        self.stats = ServeStats()
        self.pins = 0
        self.retired = False
        self.packed = self.dev_planes = None

    def release(self) -> None:
        """Close the backend and let go of the device planes: called once
        the epoch is retired and its last batch has unpinned it."""
        self.storage.close()
        self.dev_planes = None


class IndexService:
    """Serve batched lookups against a serialized index file.

    Parameters
    ----------
    path:     index file written by :func:`repro.core.write_index`
              (usually via ``repro.api.Index.save``).
    profile:  storage tier of the file (name in ``PROFILES`` or a
              :class:`StorageProfile`); drives ``modeled_seconds``.  Kept
              outside the spec on purpose — the same spec serves the same
              file on any tier.
    spec:     a :class:`repro.api.ServeSpec` with everything else: cache
              tiers, residency, descent backend, pipeline knobs, the
              :class:`repro.api.RetryPolicy`, checksum verification.
              ``None`` uses the spec recorded in the file meta by
              ``Index.save(serve_spec=...)`` when present, else defaults.
              See the ServeSpec docstring for the field reference.
    backend_factory:
              ``path -> StorageBackend`` used to open the file (and every
              file later :meth:`swap`-ped in).  Defaults to
              :class:`repro.serve.FileBackend`; chaos tests pass a
              :class:`repro.serve.FaultInjectingBackend` wrapper here.

    Every byte is read through the backend with ``spec.retry`` semantics:
    failed or short preads back off and retry, a failing coalesced run
    degrades to page-granularity retries, per-page CRC32 checksums (when
    the file carries them) are verified before a page may enter the
    cache, and the typed errors of :mod:`repro.serve.backend` surface
    once the budget is spent.  All epoch-specific objects live in a
    :class:`_ServeState`; ``meta``/``cache``/``stats``/... are properties
    onto the current epoch so :meth:`swap` can replace them atomically
    under live traffic.

    The pre-spec keyword surface (``cache_bytes=``, ``use_device=``, ...)
    survives as warn-once deprecation shims that fold into the spec;
    internal (``repro.*``) callers hard-error instead.
    """

    def __init__(self, path: str, *, profile="azure_ssd", spec=None,
                 backend_factory=None, **legacy):
        self._state = None          # __del__ must be safe mid-__init__
        self._final_state = None
        self._executor = None
        self._prefetch_exc = None
        if legacy:
            spec = _fold_legacy_kwargs(spec, legacy)
        self.path = path
        self._backend_factory = backend_factory or FileBackend
        self.profile = PROFILES[profile] if isinstance(profile, str) else profile
        # one lock covers cache + stats + the epoch pointer: the prefetch
        # worker shares them with the serving thread; preads themselves
        # (and their retry sleeps) run outside it
        self._mu = threading.Lock()
        st, spec = self._open_state(path, spec)
        self._apply_spec(spec)
        self._state = st

    def _apply_spec(self, spec) -> None:
        """Service-level views of a resolved (validated) ServeSpec —
        everything that is deployment policy rather than epoch state."""
        self.spec = spec
        self.retry = spec.retry
        self.verify_checksums = bool(spec.verify_checksums)
        self.cache_profile = (PROFILES[spec.cache_profile]
                              if spec.cache_profile else None)
        self.coalesce_gap = int(spec.coalesce_gap)
        self.persist_stats = bool(spec.persist_stats)
        self.backend = spec.backend

    def _open_state(self, path: str, spec):
        """Open ``path`` into a fresh :class:`_ServeState` (meta read,
        spec resolution, CRC table, resident prefix, cold cache) without
        touching the currently-serving epoch.  Returns
        ``(state, resolved_spec)``; the backend is closed on any failure."""
        from repro.api.spec import RetryPolicy, ServeSpec
        storage = self._backend_factory(path)
        try:
            st = _ServeState(path, storage)
            st.file_size = int(storage.size())
            policy = spec.retry if spec is not None else RetryPolicy()
            st.meta = self._read_meta(st, policy)
            st.tune_meta = st.meta.tune  # facade provenance (may be None)
            if spec is None:
                spec = self._spec_from_meta(st.tune_meta)
            if spec is None:
                spec = ServeSpec()
            spec = spec.validate()
            policy = spec.retry
            # precedence: spec field > file's paged layout > default
            st.page_bytes = int(spec.page_bytes or st.meta.page_bytes
                                or DEFAULT_PAGE_BYTES)
            cache_bytes = spec.cache_bytes
            if not cache_bytes:   # TuneSpec-recorded capacities, then default
                tspec = (st.tune_meta or {}).get("spec") or {}
                cache_bytes = tuple(tspec.get("cache_bytes") or ()) or (1 << 20,)
            st.cache = TieredBlockCache(cache_bytes, st.page_bytes)
            # CRC table: file page id -> expected CRC32.  Only meaningful
            # when the engine pages exactly as the writer did — a spec
            # page_bytes override re-tiles the file and the per-page CRCs
            # no longer line up, so verification is skipped (same as an
            # old file without checksums).
            st.page_crcs = None
            if spec.verify_checksums and st.page_bytes \
                    and st.page_bytes == st.meta.page_bytes:
                table = {}
                for lm in st.meta.layers:
                    if lm.page_crcs:
                        base = int(lm.offset) // st.page_bytes
                        for k, c in enumerate(lm.page_crcs):
                            table[base + k] = int(c)
                st.page_crcs = table or None

            L = len(st.meta.layers)
            n_res = min(max(int(spec.resident_layers), 1), L) if L else 0
            st.resident = {}
            for li in range(L - n_res, L):
                lm = st.meta.layers[li]
                raw = self._load_resident(st, lm, policy)
                st.resident[li] = self._parse_layer(lm, raw)
                with self._mu:
                    st.stats.open_bytes += lm.size
                    if self.profile is not None:
                        t = float(self.profile(lm.size))
                        st.stats.modeled_seconds += t
                        st.stats.open_modeled_seconds += t
            # the resident prefix, top-down (root first) — the fused
            # kernel's layer order; row L−1 of its output feeds the disk
            # walk
            st.prefix_lis = list(range(L - 1, L - n_res - 1, -1))
            st.prefix = [st.resident[li] for li in st.prefix_lis]
            # a device backend packs the prefix and puts its planes on
            # the device once per epoch, so a batch sends only its
            # queries; a prefix wider than the kernel's planes serves on
            # numpy, and every batch is counted under the reason
            st.numpy_reason = None
            if spec.backend != "numpy" and st.prefix:
                from repro.kernels import fused_descent as fd
                st.numpy_reason = fd.prefix_gate(st.prefix)
                if st.numpy_reason is None:
                    st.packed = fd.pack_prefix(st.prefix)
                    st.dev_planes, sent = fd.upload_planes(st.packed,
                                                           spec.backend)
                    with self._mu:
                        st.stats.plane_uploads += 1
                        st.stats.h2d_bytes += sent
        except BaseException:
            storage.close()
            raise
        return st, spec

    def _read_meta(self, st, policy):
        """Decode the file header through the backend, retrying torn or
        failing header reads under ``policy`` (a short/corrupt header
        parses as ``ValueError`` — retryable, unlike the old assert)."""
        attempt = 0
        while True:
            try:
                return parse_meta(st.storage.pread)
            except (OSError, ValueError, KeyError, TypeError) as e:
                attempt += 1
                if attempt >= policy.max_attempts:
                    raise ReadError(
                        f"could not read index meta from {st.path!r} after "
                        f"{attempt} attempt(s): {e}",
                        path=st.path, offset=0, attempts=attempt) from e
                with self._mu:
                    st.stats.io_retries += 1
                time.sleep(policy.backoff(attempt - 1))

    def _load_resident(self, st, lm, policy) -> bytes:
        """One resident layer's bytes, short-read-safe and CRC-verified
        when the file carries checksums — resident bytes never pass the
        cache-fill check, so the open path must verify on its own.  A
        corrupt layer is refetched once, then raises
        :class:`CorruptPageError`."""
        raw, dt, tainted = self._pread_retry(st, lm.size, lm.offset,
                                             policy=policy)
        P = st.page_bytes
        crcs = st.page_crcs and getattr(lm, "page_crcs", None)
        if crcs:
            base = int(lm.offset) // P
            bad = [k for k in range(len(crcs))
                   if page_crc(raw[k * P:(k + 1) * P], P)
                   != st.page_crcs.get(base + k)]
            if bad:
                with self._mu:
                    st.stats.corrupt_pages += len(bad)
                    st.stats.record_read(len(raw), dt, tainted=True)
                raw, dt, _ = self._pread_retry(st, lm.size, lm.offset,
                                               policy=policy)
                tainted = True
                still = [k for k in bad
                         if page_crc(raw[k * P:(k + 1) * P], P)
                         != st.page_crcs.get(base + k)]
                if still:
                    raise CorruptPageError(
                        f"resident layer page {base + still[0]} of "
                        f"{st.path!r} failed CRC32 verification twice",
                        path=st.path, page_id=base + still[0])
        with self._mu:
            st.stats.record_read(len(raw), dt, tainted=tainted)
        return raw

    def _spec_from_meta(self, tune_meta):
        """The ServeSpec recorded by ``Index.save(serve_spec=...)``, or
        None (missing / forward-version meta serves on defaults)."""
        d = (tune_meta or {}).get("serve")
        if d is None:
            return None
        from repro.api.spec import ServeSpec
        try:
            return ServeSpec.from_dict(d)
        except (TypeError, ValueError):
            return None

    # -- epoch plumbing ------------------------------------------------------
    @property
    def _st(self):
        """Current epoch for attribute reads; after close, the final one
        (stats stay inspectable on a closed service)."""
        st = self._state
        return st if st is not None else self._final_state

    @property
    def meta(self):
        return self._st.meta

    @property
    def tune_meta(self):
        return self._st.tune_meta

    @property
    def stats(self) -> ServeStats:
        return self._st.stats

    @property
    def cache(self) -> TieredBlockCache:
        return self._st.cache

    @property
    def page_bytes(self) -> int:
        return self._st.page_bytes

    @property
    def device_planes(self) -> dict | None:
        """The packed resident prefix a device backend serves from
        (:func:`repro.kernels.fused_descent.pack_prefix`, host arrays;
        the epoch keeps a copy on the device), else None."""
        return self._st.packed

    @property
    def device_active(self) -> bool:
        return self._st.packed is not None

    @property
    def _prefix(self) -> list:
        return self._st.prefix

    @property
    def storage(self) -> StorageBackend | None:
        st = self._state
        return st.storage if st is not None else None

    @property
    def fd(self):
        """The current epoch's file descriptor when the backend has one
        (:class:`FileBackend` does); None on other backends or after
        close.  Kept for the pre-backend-seam surface."""
        st = self._state
        return getattr(st.storage, "fd", None) if st is not None else None

    def _pin(self) -> _ServeState:
        """Claim the current epoch for one batch.  Must be paired with
        :meth:`_unpin` (the last unpin of a retired epoch closes its
        backend)."""
        with self._mu:
            st = self._state
            if st is None:
                raise RuntimeError("IndexService is closed")
            st.pins += 1
            return st

    def _unpin(self, st: _ServeState) -> None:
        with self._mu:
            st.pins -= 1
            dead = st.retired and st.pins == 0
        if dead:
            st.release()

    def swap(self, path: str, *, spec=None) -> None:
        """Hot-swap serving to ``path`` (e.g. a freshly retuned index)
        under live traffic.  The new file is fully opened — meta, CRC
        table, resident prefix and its planes uploaded to the device,
        cold cache, fresh :class:`ServeStats` — *before* the switch, and
        the switch itself is one pointer move under the service lock:
        batches already in flight pinned the old epoch at entry and
        finish on its backend, planes and cache; batches arriving after
        ``swap`` returns serve entirely from the new one.  No result ever
        mixes bytes of the two files.  The old epoch's stats are
        persisted first (``persist_stats=True``); its backend closes, and
        its device planes go, when the last in-flight batch unpins it.
        With ``spec=None`` the service keeps its current (deployment)
        spec; fresh-epoch stats keep observed_profile() honest for the
        new design, carrying only the ``swaps`` and ``plane_uploads``
        counters forward.  This is the closing move of the ROADMAP's
        observe → drift → retune loop — see
        ``examples/retune_daemon.py``."""
        if self._state is None:
            raise RuntimeError("swap() on a closed IndexService")
        st_new, resolved = self._open_state(
            path, spec if spec is not None else self.spec)
        with self._mu:
            old = self._state
            if old is None:            # closed while the new epoch opened
                st_new.storage.close()
                raise RuntimeError("swap() on a closed IndexService")
            st_new.stats.swaps = old.stats.swaps + 1
            st_new.stats.plane_uploads += old.stats.plane_uploads
            self._state = st_new
            self.path = path
            old.retired = True
            dead = old.pins == 0
        if spec is not None:
            self._apply_spec(resolved)
        if self.persist_stats:
            try:
                save_stats_snapshot(old.path, old.stats,
                                    profile_name=getattr(self.profile,
                                                         "name", None))
            except OSError:
                pass
        if dead:
            old.release()

    # -- lifecycle ----------------------------------------------------------
    def close(self) -> None:
        """Idempotent; drains the prefetch worker, then (with
        ``persist_stats=True``) writes the final ServeStats snapshot to
        ``<path>.stats.json`` before releasing the backend."""
        ex = getattr(self, "_executor", None)
        if ex is not None:
            ex.shutdown(wait=True)   # no prefetch pread may outlive the fd
            self._executor = None
        mu = getattr(self, "_mu", None)
        if mu is None or getattr(self, "_state", None) is None:
            return
        with mu:
            st, self._state = self._state, None
            if st is None:
                return
            self._final_state = st
            st.retired = True
            dead = st.pins == 0
        if getattr(self, "persist_stats", False):
            try:
                save_stats_snapshot(st.path, st.stats,
                                    profile_name=getattr(self.profile,
                                                         "name", None))
            except OSError:
                pass          # a read-only deployment must still close
        if dead:              # stragglers (if any) close on last unpin
            st.release()

    def __enter__(self) -> "IndexService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        # mirror repro.api.Index.__del__: don't leak the fd when the caller
        # skips close()/the context manager
        try:
            self.close()
        # airlint: allow[typed-error-flow] -- best-effort finalizer; raising
        # from __del__ would crash interpreter shutdown, not surface errors
        except Exception:
            pass

    # -- layer materialization ---------------------------------------------
    @staticmethod
    def _parse_layer(lm, raw: bytes) -> dict:
        if lm.kind == "step":
            rec = np.frombuffer(raw, dtype=_STEP_DT)
            pos = rec["pos"].astype(np.int64)
            return {"kind": "step", "keys": rec["key"].copy(), "pos_lo": pos,
                    "pos_hi": np.append(pos[1:], np.int64(lm.end_pos))}
        rec = np.frombuffer(raw, dtype=_BAND_DT)
        return {"kind": "band", "x1": rec["x1"].copy(),
                "y1": rec["y1"].astype(np.float64), "m": rec["m"].copy(),
                "delta": rec["delta"].copy()}

    # -- fault-tolerant reads ------------------------------------------------
    def _pread_retry(self, st: _ServeState, nbytes: int, offset: int, *,
                     deadline: float | None = None, policy=None):
        """One logical read through the backend under the RetryPolicy →
        ``(data, seconds, tainted)``.

        A failed or short attempt (pread may legally return fewer bytes
        than requested only at true EOF — anything else is a torn read)
        backs off exponentially and retries up to ``max_attempts``, then
        raises :class:`ReadError`.  ``deadline`` is an absolute
        ``perf_counter`` horizon (the per-batch budget): once past it no
        further attempt is issued and :class:`DeadlineExceededError`
        surfaces.  An attempt that outlives ``pread_deadline_s`` counts
        as a timeout; if its data is good it is still served — late bytes
        beat no bytes — but the sample comes back ``tainted`` so the
        measured tier fit never prices the stall."""
        policy = policy or self.retry
        nbytes, offset = int(nbytes), int(offset)
        want = max(min(nbytes, st.file_size - offset), 0)
        attempt = 0
        tainted = False
        while True:
            if deadline is not None and time.perf_counter() >= deadline:
                with self._mu:
                    st.stats.io_timeouts += 1
                raise DeadlineExceededError(
                    f"batch deadline expired before pread({nbytes} B @ "
                    f"{offset}) on {st.path!r}")
            err = None
            t0 = time.perf_counter()
            try:
                data = st.storage.pread(nbytes, offset)
            except OSError as e:
                data, err = b"", e
            dt = time.perf_counter() - t0
            pdl = policy.pread_deadline_s
            if pdl is not None and dt > pdl:
                # stalled attempt: count it; good-but-late data still
                # serves (the caller records the sample as tainted)
                tainted = True
                with self._mu:
                    st.stats.io_timeouts += 1
            if err is None and len(data) >= want:
                return data, dt, tainted
            attempt += 1
            if attempt >= policy.max_attempts:
                if err is not None:
                    raise ReadError(
                        f"pread({nbytes} B @ {offset}) on {st.path!r} "
                        f"failed after {attempt} attempt(s): {err}",
                        path=st.path, offset=offset, nbytes=nbytes,
                        attempts=attempt) from err
                raise ReadError(
                    f"pread({nbytes} B @ {offset}) on {st.path!r} kept "
                    f"coming back short ({len(data)}/{want} B) after "
                    f"{attempt} attempt(s)", path=st.path, offset=offset,
                    nbytes=nbytes, attempts=attempt)
            tainted = True
            with self._mu:
                st.stats.io_retries += 1
            time.sleep(policy.backoff(attempt - 1))

    def _refetch_page(self, st: _ServeState, pid: int, *,
                      deadline: float | None = None) -> bytes:
        """A page failed its CRC on cache fill: drop it and refetch once
        (the retrying pread underneath gets its own attempt budget); a
        second mismatch is a typed :class:`CorruptPageError` — never a
        silently wrong lookup."""
        P = st.page_bytes
        with self._mu:
            st.stats.corrupt_pages += 1
        raw, dt, _ = self._pread_retry(st, P, pid * P, deadline=deadline)
        with self._mu:
            st.stats.record_read(len(raw), dt, tainted=True)
        if page_crc(raw, P) != st.page_crcs.get(pid):
            raise CorruptPageError(
                f"page {pid} of {st.path!r} failed CRC32 verification "
                f"twice", path=st.path, page_id=pid)
        return raw

    # -- descent ------------------------------------------------------------
    def _descend_prefix(self, st: _ServeState, q: np.ndarray,
                        timings: dict | None = None):
        """Fused walk through the whole resident prefix → float64 (L, Q)
        lo/hi rows, the backend that served, and why numpy served a batch
        a device backend was asked for (else None).  Packed prefixes run
        the requested device backend over the epoch's resident planes;
        everything else is the bit-exact float64 walk (= the old per-layer
        path exactly).  ``timings`` receives a device dispatch's phase
        seconds and bytes sent."""
        from repro.kernels import fused_descent as fd
        if st.packed is not None:
            return fd.fused_descent_with_backend(
                st.prefix, q, backend=self.backend, packed=st.packed,
                resident=st.dev_planes, timings=timings)
        lo, hi = descend_layers(st.prefix, q)
        return lo, hi, "numpy", st.numpy_reason

    def _ensure_pages(self, st: _ServeState, page_ids: list,
                      deadline: float | None = None) -> dict:
        """All requested pages → bytes, via cache then coalesced preads."""
        P = st.page_bytes
        pages, missing = {}, []
        with self._mu:
            for pid in page_ids:
                data = st.cache.get(pid)
                if data is None:
                    missing.append(pid)
                else:
                    pages[pid] = data
                    st.stats.pages_hit += 1
                    st.stats.bytes_from_cache += len(data)
            if self.cache_profile is not None and pages:
                st.stats.modeled_seconds += len(pages) * float(
                    self.cache_profile(P))
        if missing:
            pages.update(self._fetch_missing(st, missing, deadline=deadline))
        return pages

    def _fetch_missing(self, st: _ServeState, missing: list, *,
                       overlapped: bool = False,
                       deadline: float | None = None) -> dict:
        """Coalesce missing page ids into runs and pread them into the
        cache.  A run that exhausts its retry budget degrades: it is
        split and refetched page-by-page (each page with a fresh budget)
        before the typed error surfaces — one bad sector must not take
        down every page that merely coalesced next to it.  Deadline
        expiry is not degradable (splitting only takes longer) and
        re-raises immediately."""
        P = st.page_bytes
        pages = {}
        ms = np.asarray(missing, dtype=np.int64) * P
        run_s, run_e = coalesce_ranges(ms, ms + P, gap=self.coalesce_gap)
        for rs, re_ in zip(run_s, run_e):
            rs, re_ = int(rs), int(re_)
            try:
                got = self._fetch_run(st, rs, re_, overlapped=overlapped,
                                      deadline=deadline)
            except ReadError:
                with self._mu:
                    st.stats.degraded_runs += 1
                got = {}
                for po in range(rs, re_, P):
                    got.update(self._fetch_run(
                        st, po, min(po + P, re_), overlapped=overlapped,
                        deadline=deadline, tainted=True))
            pages.update(got)
        return pages

    def _fetch_run(self, st: _ServeState, rs: int, re_: int, *,
                   overlapped: bool = False,
                   deadline: float | None = None,
                   tainted: bool = False) -> dict:
        """One coalesced run → pages, through the retrying pread and (when
        the file carries checksums) per-page CRC32 verification before
        anything may enter the cache.  The pread runs outside the lock
        (so prefetch I/O really overlaps stage-2 compute); cache/stats
        mutation re-acquires it."""
        P = st.page_bytes
        raw, dt, tnt = self._pread_retry(st, re_ - rs, rs, deadline=deadline)
        tnt = tnt or tainted
        chunks = []
        for k in range(-(-len(raw) // P)):
            pid = rs // P + k
            chunk = raw[k * P:(k + 1) * P]
            if st.page_crcs is not None:
                crc = st.page_crcs.get(pid)
                if crc is not None and page_crc(chunk, P) != crc:
                    chunk = self._refetch_page(st, pid, deadline=deadline)
                    tnt = True
            chunks.append((pid, chunk))
        pages = {}
        with self._mu:
            st.stats.record_read(len(raw), dt, overlapped=overlapped,
                                 tainted=tnt)
            st.stats.preads += 1
            if overlapped:
                st.stats.overlapped_preads += 1
                st.stats.overlapped_pread_seconds += dt
            st.stats.bytes_fetched += len(raw)
            if self.profile is not None:
                t = float(self.profile(re_ - rs))
                st.stats.modeled_seconds += t
                st.stats.pread_modeled_seconds += t
            for pid, chunk in chunks:
                pages[pid] = chunk
                st.cache.put(pid, chunk)
                st.stats.pages_fetched += 1
        return pages

    def _descend_disk(self, st, lm, lo, hi, q: np.ndarray,
                      deadline: float | None = None):
        """One on-disk layer for the whole batch, in rounds: each round
        fetches the pages of its distinct windows once and searches them
        all at once (:func:`_round_search`); queries whose window missed
        gallop and go round again."""
        P = st.page_bytes
        a, b = record_aligned_range(lm.kind, lo, hi, lm.size)
        a, b = a.copy(), b.copy()       # per-query windows, grown on misses
        with self._mu:
            st.stats.ranges_requested += len(q)
            if self.profile is not None:  # full-price walk: one window/query
                st.stats.walk_modeled_seconds += float(
                    np.sum(self.profile((b - a).astype(np.float64))))
        out_lo = np.empty(len(q), dtype=np.float64)
        out_hi = np.empty(len(q), dtype=np.float64)
        pending = np.arange(len(q))
        while len(pending):
            ab, inv = distinct_windows(a[pending], b[pending])
            runs = _window_runs(lm, ab, P)
            with span("airindex.walk.fetch") as fetch:
                pages = self._ensure_pages(st, np.unique(runs[2]).tolist(),
                                           deadline)
            left, right, ok, l_, h_ = _round_search(lm, ab, inv, q[pending],
                                                    runs, pages)
            out_lo[pending[ok]] = l_
            out_hi[pending[ok]] = h_
            # gallop the missed windows toward the covering record (same
            # rule as SerializedIndex.lookup — parity preserved);
            # gallop_step never returns 0, so a degenerate zero-width
            # window still extends by ≥ one record instead of retrying
            # the same bounds forever
            w = gallop_step(lm.kind, ab[:, 0], ab[:, 1])[inv]
            rmiss = right & ~left
            a[pending[left]] = np.maximum(ab[inv[left], 0] - w[left], 0)
            b[pending[rmiss]] = np.minimum(ab[inv[rmiss], 1] + w[rmiss],
                                           lm.size)
            # next round: each window's left misses, then its right ones
            miss = np.flatnonzero(left | right)
            miss = miss[np.argsort(2 * inv[miss] + rmiss[miss],
                                   kind="stable")]
            modeled = []
            if self.profile is not None and len(miss):
                # the scalar walk re-reads each extended window; summed
                # per window, in window order, as it always was
                cuts = np.flatnonzero(np.diff(inv[miss])) + 1
                for ext in np.split(pending[miss], cuts):
                    modeled.append(float(np.sum(self.profile(
                        (b[ext] - a[ext]).astype(np.float64)))))
            with self._mu:
                st.stats.walk_fetch_seconds += fetch.seconds
                st.stats.walk_windows += len(ab)
                st.stats.walk_rounds += 1
                st.stats.retries += len(miss)
                for t in modeled:
                    st.stats.walk_modeled_seconds += t
            pending = pending[miss]
        return out_lo, out_hi

    # -- public API ---------------------------------------------------------
    def lookup(self, queries) -> np.ndarray:
        """Batched Alg. 1 → (q, 2) int64 array of data-layer byte ranges.

        The resident prefix is descended in ONE fused dispatch (all layers,
        all queries); remaining layers walk the file through the block
        cache.  On the numpy backend the results are bit-identical to
        ``lookup_serialized`` on the same file — fusion, the cache and
        coalescing only change *how* windows are computed and bytes
        obtained.  Device backends widen resident *band* layers by the
        f32-rounding slack (ranges stay valid but may be strictly wider).

        A batch pins its serving epoch at entry, so a concurrent
        :meth:`swap` never changes the file mid-descent; with
        ``spec.retry.batch_deadline_s`` set, every pread the batch
        triggers shares one absolute deadline.
        """
        st = self._pin()
        with span("airindex.lookup") as call:
            try:
                out = self._lookup_pinned(st, queries)
            finally:
                self._unpin(st)
        with self._mu:
            st.stats.lookup_seconds += call.seconds
            st.stats.record_lookup(len(out), call.seconds)
        return out

    def _lookup_pinned(self, st: _ServeState, queries) -> np.ndarray:
        q = np.atleast_1d(np.asarray(queries, dtype=np.uint64))
        bdl = self.retry.batch_deadline_s
        deadline = (time.perf_counter() + bdl) if bdl is not None else None
        with self._mu:
            st.stats.queries += len(q)
            st.stats.batches += 1
        metas = st.meta.layers
        if len(q) == 0:
            return np.empty((0, 2), dtype=np.int64)
        if not metas:
            out = np.empty((len(q), 2), dtype=np.int64)
            out[:, 0] = 0
            out[:, 1] = st.meta.data_size
            if self.profile is not None:   # (no index): scan the data layer
                t = len(q) * float(self.profile(st.meta.data_size))
                with self._mu:
                    st.stats.data_modeled_seconds += t
                    st.stats.walk_modeled_seconds += t
            return out
        lo = hi = None
        n_res = len(st.prefix)
        if n_res:
            phases: dict = {}
            with span("airindex.descent") as descent:
                plo, phi, used, reason = self._descend_prefix(st, q, phases)
            walk = 0.0
            if self.profile is not None:
                for r, li in enumerate(st.prefix_lis):
                    lm = metas[li]
                    if r == 0:
                        # Alg. 1 reads the ROOT outright per query;
                        # residency only amortizes it — the full-price
                        # walk counter must not
                        walk += len(q) * float(self.profile(lm.size))
                    else:
                        # non-root resident layers would be *window*
                        # reads in the scalar walk — charge the
                        # record-aligned window, not the layer size
                        # (first-window cost; the rare gallop retries an
                        # on-disk walk would pay are not modeled here)
                        wa, wb = record_aligned_range(
                            lm.kind, plo[r - 1], phi[r - 1], lm.size)
                        walk += float(np.sum(
                            self.profile((wb - wa).astype(np.float64))))
            with self._mu:
                st.stats.descent_seconds += descent.seconds
                st.stats.descent_stage_seconds += phases.get(
                    "stage_seconds", 0.0)
                st.stats.descent_launch_seconds += phases.get(
                    "launch_seconds", 0.0)
                st.stats.descent_collect_seconds += phases.get(
                    "collect_seconds", 0.0)
                st.stats.rebase_seconds += phases.get("rebase_seconds", 0.0)
                st.stats.h2d_bytes += phases.get("h2d_bytes", 0)
                st.stats.wide_queries += phases.get("wide_queries", 0)
                st.stats.walk_modeled_seconds += walk
                _count_backend(st.stats, used, reason)
            lo, hi = plo[-1], phi[-1]
        for li in range(len(metas) - n_res - 1, -1, -1):
            with span("airindex.walk") as walked:
                lo, hi = self._descend_disk(st, metas[li], lo, hi, q,
                                            deadline)
            with self._mu:
                st.stats.walk_seconds += walked.seconds
        lo = np.maximum(np.asarray(lo, dtype=np.int64), 0)
        hi = np.minimum(np.maximum(np.asarray(hi, dtype=np.int64), lo + 1),
                        st.meta.data_size)
        if self.profile is not None:
            # the caller's final data-range read, modeled on the same tier:
            # part of Eq. 6's E[T], charged to observed AND walk cost
            t = float(np.sum(self.profile((hi - lo).astype(np.float64))))
            with self._mu:
                st.stats.data_modeled_seconds += t
                st.stats.walk_modeled_seconds += t
        return np.stack([lo, hi], axis=1)

    def lookup_batches(self, batches) -> list:
        """Serve a sequence of query batches through the two-stage
        pipeline: while this thread descends + walks batch *i* (stage 2),
        a single background worker pre-issues the coalesced first-window
        preads of batches *i+1..i+depth* (stage 1), so storage latency
        hides behind compute.  Returns one ``lookup``-shaped array per
        batch — identical to calling :meth:`lookup` sequentially
        (``spec.pipeline_depth == 0`` does exactly that).

        A failure inside the prefetch worker (its pread retry budget
        spent, a corrupt page, a died thread) is captured and re-raised
        *here*, on the next batch boundary — never swallowed into a
        silently degraded or hung pipeline."""
        batches = [np.atleast_1d(np.asarray(b, dtype=np.uint64))
                   for b in batches]
        depth = int(self.spec.pipeline_depth)
        if depth <= 0 or len(batches) <= 1:
            return [self.lookup(b) for b in batches]
        if self._executor is None:
            from concurrent.futures import ThreadPoolExecutor
            self._executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="airindex-prefetch")
        pending: dict[int, object] = {}
        out = []
        for i in range(len(batches)):
            for j in range(i + 1, min(i + depth, len(batches) - 1) + 1):
                if j not in pending:
                    pending[j] = self._executor.submit(
                        self._prefetch_task, batches[j])
            out.append(self.lookup(batches[i]))
            with self._mu:
                self.stats.pipelined_batches += 1
            fut = pending.pop(i + 1, None)
            if fut is not None:
                # batch i+1 must be fully staged before stage 2 touches it:
                # the cache probe is the only coupling, but waiting keeps
                # the hit accounting deterministic
                fut.result()
            self._raise_prefetch_exc()
        for fut in pending.values():
            fut.result()
        self._raise_prefetch_exc()
        return out

    def _raise_prefetch_exc(self) -> None:
        """Surface the first exception the prefetch worker captured (the
        stage-1 error-propagation contract of :meth:`lookup_batches`)."""
        with self._mu:
            exc, self._prefetch_exc = self._prefetch_exc, None
        if exc is not None:
            raise exc

    def _prefetch_task(self, q: np.ndarray) -> int:
        """The unit the worker thread actually runs: pin an epoch, stage
        the batch, and *capture* any failure for the serving thread to
        re-raise at the next batch boundary — an exception escaping into
        the executor would otherwise vanish into the Future until someone
        happens to ``.result()`` it."""
        try:
            st = self._pin()
        except RuntimeError:
            return 0                 # service closed under the pipeline
        try:
            return self._prefetch_batch(st, q)
        # airlint: allow[typed-error-flow] -- not absorbed: captured in
        # _prefetch_exc and re-raised typed at the next batch boundary
        except BaseException as e:   # noqa: BLE001 — re-raised on boundary
            with self._mu:
                if self._prefetch_exc is None:
                    self._prefetch_exc = e
            return 0
        finally:
            self._unpin(st)

    def _prefetch_batch(self, st: _ServeState, q: np.ndarray) -> int:
        """Stage 1 of the pipeline: descend the resident prefix for a
        *future* batch and pread its missing first-window pages into the
        cache (tagged ``overlapped``).  Walks up to
        ``spec.prefetch_layers`` disk layers deep, advancing through
        already-cached records only — no gallop, no stats that belong to
        serving (the later :meth:`lookup` charges those).  Returns the
        number of pages staged."""
        t_start = time.perf_counter()
        metas = st.meta.layers
        n_res = len(st.prefix)
        n_disk = len(metas) - n_res
        staged = 0
        if n_disk <= 0 or len(q) == 0:
            return 0
        if n_res:
            plo, phi, _, _ = self._descend_prefix(st, q)
            lo, hi = plo[-1], phi[-1]
        else:
            lo = hi = None
        depth = min(max(int(self.spec.prefetch_layers), 1), n_disk)
        P = st.page_bytes
        for d in range(depth):
            lm = metas[n_disk - 1 - d]
            a, b = record_aligned_range(lm.kind, lo, hi, lm.size)
            ab, _ = distinct_windows(a, b)
            need = np.unique(_window_runs(lm, ab, P)[2])
            with self._mu:
                missing = [pid for pid in need.tolist()
                           if pid not in st.cache]
            if missing:
                staged += len(self._fetch_missing(st, missing,
                                                  overlapped=True))
            if d + 1 < depth:
                lo, hi, q = self._advance_windows(st, lm, a, b, q)
                if len(q) == 0:
                    break
        with self._mu:
            st.stats.prefetch_seconds += time.perf_counter() - t_start
        return staged

    def _advance_windows(self, st: _ServeState, lm, a, b, q: np.ndarray):
        """Predict the next layer's windows from *cached* pages only
        (``peek``: no promotion, no hit/miss skew), by the serving walk's
        own round search.  Queries whose window pages were evicted, or
        whose covering record lies outside the first window, simply drop
        out of the prefetch — stage 2 serves them at full fidelity."""
        P = st.page_bytes
        ab, inv = distinct_windows(a, b)
        ids = np.unique(_window_runs(lm, ab, P)[2])
        with self._mu:
            got = [st.cache.peek(p) for p in ids.tolist()]
        # evicted under pressure: a window short of a page stops here
        gone = np.concatenate([[0], np.cumsum([c is None for c in got])])
        fa = lm.offset + ab[:, 0]
        pa, pb = page_span(fa, lm.offset + ab[:, 1] - fa, P)
        whole = (gone[np.searchsorted(ids, pb)]
                 == gone[np.searchsorted(ids, pa)])
        keep = whole[inv]
        if not keep.any():
            e = np.empty(0, dtype=np.float64)
            return e, e, np.empty(0, dtype=np.uint64)
        pages = {p: c for p, c in zip(ids.tolist(), got) if c is not None}
        ab, q = ab[whole], q[keep]
        _, _, ok, lo, hi = _round_search(
            lm, ab, (np.cumsum(whole) - 1)[inv[keep]], q,
            _window_runs(lm, ab, P), pages)
        return lo, hi, q[ok]

    @property
    def tune_spec(self):
        """The TuneSpec recorded by ``repro.api.Index.save`` (or None)."""
        spec = (self.tune_meta or {}).get("spec")
        if spec is None:
            return None
        from repro.api.spec import TuneSpec   # lazy: api sits above serve
        try:
            return TuneSpec.from_dict(spec)
        except (TypeError, ValueError):
            return None   # forward-version provenance: serve anyway

    def cached_profile(self, backing: StorageProfile | None = None) -> CachedProfile:
        """Effective ``T(Δ)`` at the observed hit rate — hand this back to
        ``airtune`` to re-tune the index *for* this cache deployment."""
        backing = backing or self.profile
        if backing is None:
            raise ValueError("no backing profile: the service was opened "
                             "with profile=None — pass one explicitly")
        return CachedProfile(backing=backing, cache=self.cache_profile,
                             hit_rate=self.stats.hit_rate)

    def observed_profile(self, backing: StorageProfile | None = None, *,
                         measured: bool = True,
                         min_samples: int = MIN_FIT_SAMPLES,
                         distributional: bool = False) -> CachedProfile:
        """Effective ``T(Δ)`` from *observed* serving behavior: the block
        cache's hit rate plus (``measured=True``) the measured per-pread
        latency in place of the modeled backing tier.  This is the profile
        a drift-triggered ``Index.retune`` should tune for (see
        :mod:`repro.api.drift`).  With ``measured=False`` it equals
        :meth:`cached_profile` exactly; ``distributional=True`` prefers
        the per-Δ distribution fit (what a quantile-objective retune
        needs)."""
        backing = backing or self.profile
        if backing is None:
            raise ValueError("no backing profile: the service was opened "
                             "with profile=None — pass one explicitly")
        return observed_profile_from_stats(self.stats, backing,
                                           self.cache_profile,
                                           measured=measured,
                                           min_samples=min_samples,
                                           distributional=distributional)

    def save_stats(self, *, window: int = STATS_WINDOW) -> str:
        """Persist the current :class:`ServeStats` snapshot next to the
        index meta (``<path>.stats.json``, rotating window) — the serve
        side of the observe→retune loop.  Returns the stats-file path."""
        prof = getattr(self.profile, "name", None)
        return save_stats_snapshot(self.path, self.stats,
                                   profile_name=prof, window=window)
