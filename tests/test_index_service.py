"""Serving engine: parity with both lookup paths, cache behavior, read
coalescing, tiered LRU mechanics, CachedProfile, paged serialization."""
import numpy as np
import pytest

from repro.core import (CachedProfile, IndexDesign, KeyPositions, PROFILES,
                        airtune, build_gstep, coalesce_ranges, lookup_batch,
                        make_builders, outline, page_span, write_index)
from repro.api import ServeSpec
from repro.core.serialize import lookup_serialized
from repro.serve.index_service import (IndexService, TieredBlockCache,
                                       demo_serving_design)

from conftest import make_keys

# step <- band <- step root: exercises the disk path AND the band
# inter-key window-miss galloping
_band_stack = demo_serving_design


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    keys = make_keys("books", 120_000, seed=3)
    D = KeyPositions.fixed_record(keys, 16)
    design = _band_stack(D)
    path = str(tmp_path_factory.mktemp("svc") / "index.air")
    write_index(path, design, page_bytes=1024)
    rng = np.random.default_rng(0)
    qs = rng.choice(D.keys, 600)
    return D, design, path, qs


# ---------------------------------------------------------------------------
# parity: engine == file walk == in-memory batch, and all are valid
# ---------------------------------------------------------------------------
def test_engine_matches_file_and_memory(served):
    D, design, path, qs = served
    want_file = lookup_serialized(path, None, qs)
    mem = lookup_batch(design, qs)
    with IndexService(path, profile="azure_ssd",
                      spec=ServeSpec(cache_bytes=(64 << 10,
                                                  512 << 10))) as svc:
        got = svc.lookup(qs)
        assert np.array_equal(got, want_file)
        assert np.array_equal(got[:, 0], mem.lo)
        assert np.array_equal(got[:, 1], mem.hi)
        idx = np.searchsorted(D.keys, qs)
        assert np.all((got[:, 0] <= D.lo[idx]) & (got[:, 1] >= D.hi[idx])), \
            "engine violates Eq. (1)"
        # the band stack forces inter-key window misses; galloping must
        # have kicked in (and still produced exact parity above)
        assert svc.stats.retries > 0


def test_engine_matches_on_airtuned_design(tmp_path):
    keys = make_keys("gmm", 30_000, seed=11)
    D = KeyPositions.fixed_record(keys, 16)
    res = airtune(D, PROFILES["azure_ssd"],
                  make_builders(lam_low=2**8, lam_high=2**16, base=4.0), k=3)
    path = str(tmp_path / "index.air")
    write_index(path, res.design, page_bytes=1024)
    qs = np.random.default_rng(1).choice(D.keys, 400)
    with IndexService(path, profile="azure_ssd") as svc:
        got = svc.lookup(qs)
    assert np.array_equal(got, lookup_serialized(path, None, qs))
    mem = lookup_batch(res.design, qs)
    assert np.array_equal(got[:, 0], mem.lo)
    assert np.array_equal(got[:, 1], mem.hi)


def test_engine_serves_unpaged_legacy_files(served):
    D, design, path_unused, qs = served
    import tempfile, os
    path = os.path.join(tempfile.mkdtemp(), "legacy.air")
    write_index(path, design)                      # page_bytes=0 layout
    with IndexService(path, profile="azure_ssd") as svc:
        assert svc.meta.page_bytes == 0 and svc.page_bytes > 0
        got = svc.lookup(qs)
    assert np.array_equal(got, lookup_serialized(path, None, qs))


# ---------------------------------------------------------------------------
# cache: a repeated batch reads strictly fewer bytes than the cold batch
# ---------------------------------------------------------------------------
def test_warm_batch_reads_strictly_fewer_bytes(served):
    D, design, path, qs = served
    with IndexService(path, profile="azure_nfs",
                      spec=ServeSpec(cache_bytes=(64 << 10,
                                                  512 << 10))) as svc:
        svc.lookup(qs)
        cold = svc.stats.snapshot()
        assert cold["bytes_fetched"] > 0 and cold["preads"] > 0
        got2 = svc.lookup(qs)
        warm_bytes = svc.stats.bytes_fetched - cold["bytes_fetched"]
        warm_modeled = svc.stats.modeled_seconds - cold["modeled_seconds"]
        assert warm_bytes < cold["bytes_fetched"]
        assert warm_modeled < cold["modeled_seconds"]
        assert svc.stats.hit_rate > 0
        assert np.array_equal(got2, lookup_serialized(path, None, qs))


def test_tiny_cache_still_correct(served):
    D, design, path, qs = served
    with IndexService(path, profile=None,
                      spec=ServeSpec(cache_bytes=(0,))) as svc:
        got = svc.lookup(qs)
    assert np.array_equal(got, lookup_serialized(path, None, qs))


# ---------------------------------------------------------------------------
# read coalescing
# ---------------------------------------------------------------------------
def test_coalesce_ranges_merges_overlaps():
    s, e = coalesce_ranges([0, 8, 30], [10, 20, 40])
    assert s.tolist() == [0, 30] and e.tolist() == [20, 40]


def test_coalesce_ranges_gap_and_order():
    s, e = coalesce_ranges([30, 0, 12], [40, 10, 20], gap=2)
    assert s.tolist() == [0, 30] and e.tolist() == [20, 40]
    s, e = coalesce_ranges([30, 0, 12], [40, 10, 20], gap=0)
    assert s.tolist() == [0, 12, 30] and e.tolist() == [10, 20, 40]


def test_coalesce_ranges_contained_and_empty():
    s, e = coalesce_ranges([0, 2], [100, 4])
    assert s.tolist() == [0] and e.tolist() == [100]
    s, e = coalesce_ranges([], [])
    assert len(s) == 0 and len(e) == 0


def test_batch_coalesces_to_few_preads(served):
    D, design, path, qs = served
    with IndexService(path, profile=None,
                      spec=ServeSpec(cache_bytes=(4 << 20,))) as svc:
        svc.lookup(qs)
        # 600 queries x 2 disk layers, but contiguous pages merge into runs
        assert svc.stats.preads < svc.stats.ranges_requested / 10


# ---------------------------------------------------------------------------
# tiered LRU block cache mechanics
# ---------------------------------------------------------------------------
def test_tiered_cache_promote_demote_evict():
    c = TieredBlockCache((2 * 64, 2 * 64), page_bytes=64)   # 2 pages per tier
    for pid in (1, 2, 3, 4):
        c.put(pid, bytes(64))
    # tier0 holds {3,4}; {1,2} demoted to tier1
    assert 3 in c.tiers[0] and 4 in c.tiers[0]
    assert 1 in c.tiers[1] and 2 in c.tiers[1]
    assert c.get(1) is not None           # tier-1 hit promotes to tier 0...
    assert 1 in c.tiers[0]
    assert c.hits == [0, 1]
    c.put(5, bytes(64))                   # ...and 5 displaces the tier-0 LRU
    assert len(c.tiers[0]) == 2 and len(c.tiers[1]) == 2
    assert c.get(2) is None               # 2 fell off the last tier
    assert c.misses == 1


def test_tiered_cache_zero_capacity_tier():
    c = TieredBlockCache((0,), page_bytes=64)
    c.put(1, bytes(64))
    assert c.get(1) is None               # nothing sticks, nothing crashes


def test_tiered_cache_subpage_tier_demotes_through():
    # middle tier smaller than one page (cap_pages == 0): the demotion
    # cascade must pass straight through it and terminate
    c = TieredBlockCache((2 * 64, 32, 2 * 64), page_bytes=64)
    assert c.cap_pages == [2, 0, 2]
    for pid in range(5):
        c.put(pid, bytes(64))
    assert len(c.tiers[1]) == 0           # nothing sticks in the 0-cap tier
    # exclusive cascade == one global LRU: {4,3} hot, {2,1} demoted, 0 gone
    assert sorted(c.tiers[0]) == [3, 4] and sorted(c.tiers[2]) == [1, 2]
    assert c.get(0) is None and c.misses == 1
    assert c.get(1) is not None           # promoted through the 0-cap tier
    assert 1 in c.tiers[0] and c.hits == [0, 0, 1]


def _reference_segments(order: list, caps: list) -> list:
    """Global-LRU reference: the exclusive cascade is a segmented LRU, so
    tier i must hold slice [Σcaps[:i], Σcaps[:i+1]) of the recency order."""
    segs, at = [], 0
    for cap in caps:
        segs.append(order[at:at + cap])
        at += cap
    return segs


@pytest.mark.parametrize("caps_bytes", [(256, 512), (256, 32, 512),
                                        (64, 0, 64, 128), (0, 256)])
def test_tiered_cache_matches_global_lru_model(caps_bytes):
    """Property test: after any op sequence, tier contents equal the
    recency segments of one global LRU of capacity Σ cap_pages, pages
    live in at most one tier, and every get is consistently a hit/miss."""
    P = 64
    c = TieredBlockCache(caps_bytes, page_bytes=P)
    total = sum(c.cap_pages)
    rng = np.random.default_rng(hash(caps_bytes) & 0xFFFF)
    order: list = []            # reference recency order, hottest first
    gets = hits = 0
    for _ in range(2000):
        pid = int(rng.integers(0, 24))    # small id space: force collisions
        if rng.random() < 0.5:
            c.put(pid, bytes(P))
            if pid in order:
                order.remove(pid)
            order.insert(0, pid)
            del order[total:]
        else:
            gets += 1
            got = c.get(pid)
            assert (got is not None) == (pid in order)
            if got is not None:
                hits += 1
                order.remove(pid)
                order.insert(0, pid)
                del order[total:]
        # invariants: segment equality, exclusivity, capacity, accounting
        segs = _reference_segments(order, c.cap_pages)
        for tier, seg, cap in zip(c.tiers, segs, c.cap_pages):
            assert len(tier) <= cap
            # OrderedDict order: oldest first; segment is hottest-first
            assert list(tier) == seg[::-1]
        resident = [pid for t in c.tiers for pid in t]
        assert len(resident) == len(set(resident)), "page in two tiers"
        assert sum(c.hits) == hits and c.misses == gets - hits


# ---------------------------------------------------------------------------
# engine construction / lifecycle bugfixes
# ---------------------------------------------------------------------------
def test_explicit_page_bytes_overrides_paged_meta(served):
    """An explicit ``page_bytes=`` kwarg must win over the file's recorded
    paged layout (it used to be silently ignored whenever the meta
    recorded one)."""
    D, design, path, qs = served
    with IndexService(path, profile=None,
                      spec=ServeSpec(page_bytes=512,
                                     cache_bytes=(1 << 20,))) as svc:
        assert svc.meta.page_bytes == 1024          # file IS paged...
        assert svc.page_bytes == 512                # ...but the caller wins
        assert svc.cache.page_bytes == 512          # cache pages accordingly
        got = svc.lookup(qs)
        # every cached page is a 512-byte unit (the file tail may be short)
        sizes = {len(v) for t in svc.cache.tiers for v in t.values()}
        assert sizes and all(s <= 512 for s in sizes) and 512 in sizes
    assert np.array_equal(got, lookup_serialized(path, None, qs))
    # meta fallback unchanged: no kwarg → the file's layout
    with IndexService(path, profile=None) as svc:
        assert svc.page_bytes == 1024


def test_close_is_idempotent_and_del_closes(served):
    import os
    D, design, path, qs = served
    svc = IndexService(path, profile=None)
    svc.lookup(qs[:16])
    svc.close()
    svc.close()                                     # double close: no error
    assert svc.fd is None
    svc = IndexService(path, profile=None)
    fd = svc.fd
    os.fstat(fd)                                    # open while referenced
    del svc                                         # caller forgot close():
    import gc
    gc.collect()
    with pytest.raises(OSError):                    # ...the finalizer closed
        os.fstat(fd)


def test_gallop_step_never_zero():
    from repro.core.serialize import RECORD_BYTES, gallop_step
    # zero-width window (degenerate clamp) still extends by ≥ one record
    assert gallop_step("step", 100, 100) == RECORD_BYTES["step"]
    assert gallop_step("band", 0, 0) == RECORD_BYTES["band"]
    # sub-record windows round up to one record as well
    assert gallop_step("band", 0, 8) == RECORD_BYTES["band"]
    # normal windows keep the doubling rule
    assert gallop_step("step", 0, 64) == 64
    assert gallop_step("band", 40, 200) == 160


# ---------------------------------------------------------------------------
# CachedProfile
# ---------------------------------------------------------------------------
def test_cached_profile_between_tiers_and_monotone():
    backing = PROFILES["azure_nfs"]
    cache = PROFILES["host_dram"]
    deltas = np.array([64.0, 4096.0, 1 << 20])
    for h in (0.0, 0.5, 0.95, 1.0):
        p = CachedProfile(backing=backing, cache=cache, hit_rate=h)
        t = p(deltas)
        assert np.all(np.diff(t) >= 0), "T(Δ) must stay monotone"
        assert np.all(t <= backing(deltas) + 1e-15)
        assert np.all(t >= cache(deltas) - 1e-15)
    hot = CachedProfile(backing=backing, cache=cache, hit_rate=0.99)
    cold = CachedProfile(backing=backing, cache=cache, hit_rate=0.01)
    assert float(hot(4096)) < float(cold(4096))


def test_observed_cached_profile_retunes(served):
    D, design, path, qs = served
    with IndexService(path, profile="azure_nfs",
                      spec=ServeSpec(cache_bytes=(1 << 20,))) as svc:
        svc.lookup(qs)
        svc.lookup(qs)
        eff = svc.cached_profile()
    assert 0.0 < eff.hit_rate <= 1.0
    assert float(eff(4096)) < float(PROFILES["azure_nfs"](4096))


# ---------------------------------------------------------------------------
# paged serialization
# ---------------------------------------------------------------------------
def test_paged_layout_aligns_layers(served):
    D, design, path, qs = served
    import os
    from repro.core.serialize import read_meta
    fd = os.open(path, os.O_RDONLY)
    try:
        meta = read_meta(fd)
    finally:
        os.close(fd)
    assert meta.page_bytes == 1024
    for lm in meta.layers:
        assert lm.offset % meta.page_bytes == 0
        p0, p1 = page_span(lm.offset, lm.size, meta.page_bytes)
        assert p0 * meta.page_bytes == lm.offset
        assert (p1 - p0) == -(-lm.size // meta.page_bytes)


# ---------------------------------------------------------------------------
# device (Pallas kernel) routing for resident layers
# ---------------------------------------------------------------------------
def test_device_resident_descend_matches_numpy(tmp_path):
    rng = np.random.default_rng(0)
    keys = np.unique(rng.integers(1, 2**30, 40_000).astype(np.uint64))
    D = KeyPositions.fixed_record(keys, 16)
    l1 = build_gstep(D, 8, 2**9)
    l2 = build_gstep(outline(l1, D), 8, 2**6)
    design = IndexDesign(layers=(l1, l2), data=D)
    path = str(tmp_path / "dev.air")
    write_index(path, design, page_bytes=1024)
    qs = rng.choice(D.keys, 256)
    want = lookup_serialized(path, None, qs)
    with IndexService(path, spec=ServeSpec(backend="pallas",
                                           resident_layers=2)) as svc:
        assert svc.device_active
        got = svc.lookup(qs)
        assert svc.stats.pallas_batches > 0
        assert svc.stats.numpy_batches == 0
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# two-stage pipeline: prefetch must never change windows, only timing
# ---------------------------------------------------------------------------
def test_pipelined_batches_identical_to_sequential(served):
    D, design, path, qs = served
    rng = np.random.default_rng(7)
    batches = [rng.choice(D.keys, n) for n in (300, 1, 257, 64, 300, 128)]
    # small tiers force evictions between batches — the prefetch stage's
    # peek/drop-out paths actually execute under this pressure
    base = ServeSpec(cache_bytes=(16 << 10, 64 << 10))
    with IndexService(path, profile="azure_ssd", spec=base) as svc:
        want = [svc.lookup(b) for b in batches]
    with IndexService(path, profile="azure_ssd",
                      spec=base.replace(pipeline_depth=2,
                                        prefetch_layers=2)) as svc:
        got = svc.lookup_batches(batches)
        assert svc.stats.pipelined_batches == len(batches)
        assert svc.stats.pread_modeled_seconds > 0
    assert len(got) == len(want)
    for w, g in zip(want, got):
        assert np.array_equal(w, g)


def test_prefetch_stage_warms_cache_and_tags_overlapped(served):
    D, design, path, qs = served
    with IndexService(path, profile="azure_ssd",
                      spec=ServeSpec(cache_bytes=(256 << 10,),
                                     pipeline_depth=1,
                                     prefetch_layers=2)) as svc:
        staged = svc._prefetch_task(qs[:200])     # cold cache: must pread
        assert staged > 0
        assert svc.stats.overlapped_preads > 0
        assert svc.stats.overlapped_pread_seconds > 0
        assert svc.stats.prefetch_seconds > 0
        assert any(len(r) > 2 and r[2] for r in svc.stats.read_samples)
        # the prefetch probe must not have skewed hit/miss accounting
        assert svc.stats.pages_hit == 0 and svc.cache.misses == 0
        before = svc.stats.preads
        got = svc.lookup(qs[:200])                # serves mostly from cache
        assert svc.stats.pages_hit > 0
        # first-window pages were staged; only gallop extensions may read
        assert svc.stats.preads - before <= before
    assert np.array_equal(got, lookup_serialized(path, None, qs[:200]))


def test_lookup_batches_depth_zero_is_plain_sequential(served):
    D, design, path, qs = served
    batches = [qs[:100], qs[100:350], qs[350:]]
    spec = ServeSpec(cache_bytes=(64 << 10,))
    with IndexService(path, profile=None, spec=spec) as svc:
        want = [svc.lookup(b) for b in batches]
    with IndexService(path, profile=None, spec=spec) as svc:
        got = svc.lookup_batches(batches)
        assert svc.stats.pipelined_batches == 0
        assert svc._executor is None          # stage 1 never spun up
    for w, g in zip(want, got):
        assert np.array_equal(w, g)


def test_measured_profile_excludes_overlapped_samples():
    from repro.serve.index_service import ServeStats, measured_backing_profile
    s = ServeStats()
    for i in range(12):     # blocking samples: a plausible ~1ms/4KiB tier
        s.record_read(4096 * (1 + i % 3), 1e-3 * (1 + i % 3))
    for _ in range(30):     # overlapped: latency hidden by the pipeline
        s.record_read(4096, 1e-6, overlapped=True)
    prof = measured_backing_profile(s)
    assert prof is not None
    # the queue-hidden samples must not drag the fitted tier toward zero
    assert float(prof(4096)) >= 0.5e-3
    # but when ONLY overlapped samples exist, fall back rather than refuse
    s2 = ServeStats()
    for i in range(12):
        s2.record_read(4096 * (1 + i % 3), 1e-3, overlapped=True)
    assert measured_backing_profile(s2) is not None


# ---------------------------------------------------------------------------
# the on-disk walk searches every window of a round at once: answers and
# counters must equal the per-window walk's exactly
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def round_files(served, tmp_path_factory):
    from repro.core.baselines import build_fixed_btree
    D, design, path, qs = served
    d = tmp_path_factory.mktemp("round")
    keys = make_keys("uniform", 120_000, seed=5)
    B = KeyPositions.fixed_record(keys, 16)
    btree = str(d / "btree.air")
    write_index(btree, build_fixed_btree(B, lam=512), page_bytes=512)
    legacy = str(d / "legacy.air")
    write_index(legacy, design)                    # page_bytes=0 layout
    return {"btree": (btree, B.keys, 2), "band": (path, D.keys, 1),
            "legacy": (legacy, D.keys, 1)}


def _edge_queries(keys, rng):
    k0, k1 = int(keys[0]), int(keys[-1])
    return np.concatenate([
        np.asarray([0, 1, k0 - 1, k0, k1, k1 + 1, 2**64 - 1], dtype=np.uint64),
        rng.choice(keys, 200)])


# case -> (file, cache tiers, batch); the counters are (walk_windows,
# retries, pages_hit, walk_modeled_seconds) as the per-window walk
# recorded them on this data
_ROUND_CASES = {
    "step_bottom": ("btree", (8 << 10,),
                    lambda k, r: r.choice(k, 500)),
    "band_gallop": ("band", (64 << 10, 512 << 10),
                    lambda k, r: r.choice(k, 600)),
    "unpaged_legacy": ("legacy", (1 << 20,),
                       lambda k, r: r.choice(k, 400)),
    "cache_below_round": ("band", (2048,),
                          lambda k, r: r.choice(k, 400)),
    "edge_keys_band": ("band", (256 << 10,), _edge_queries),
    "edge_keys_step": ("btree", (8 << 10,), _edge_queries),
    "repeated_keys": ("band", (256 << 10,),
                      lambda k, r: r.permutation(
                          np.repeat(r.choice(k, 60), 5))),
}
_ROUND_COUNTERS = {
    "band_gallop": (720, 796, 518, 1.1189754057142864),
    "cache_below_round": (595, 497, 48, 0.7373968800000009),
    "edge_keys_band": (364, 240, 362, 0.37779776),
    "edge_keys_step": (156, 0, 12, 0.31381197714285713),
    "repeated_keys": (173, 260, 65, 0.5206009142857144),
    "step_bottom": (225, 0, 16, 0.7567880228571429),
    "unpaged_legacy": (595, 497, 147, 0.7373968800000009),
}


@pytest.mark.parametrize("case", sorted(_ROUND_CASES))
def test_round_walk_matches_serialized(round_files, case):
    which, cache, make = _ROUND_CASES[case]
    path, keys, resident = round_files[which]
    qs = make(keys, np.random.default_rng(11))
    batches = [qs, qs[::2]]            # the second meets a warm cache
    with IndexService(path, profile="azure_ssd",
                      spec=ServeSpec(cache_bytes=cache,
                                     resident_layers=resident)) as svc:
        got = [svc.lookup(b) for b in batches]
        st = svc.stats
        n_disk = len(svc.meta.layers) - resident
    for g, b in zip(got, batches):
        assert np.array_equal(g, lookup_serialized(path, None, b))
    windows, retries, hits, modeled = _ROUND_COUNTERS[case]
    assert (st.walk_windows, st.retries, st.pages_hit) \
        == (windows, retries, hits)
    assert st.walk_modeled_seconds == pytest.approx(modeled, rel=1e-12)
    if retries:                        # galloping forced further rounds
        assert st.walk_rounds >= 2 * len(batches)
    else:                              # one round per on-disk layer
        assert st.walk_rounds == n_disk * len(batches)


@pytest.mark.parametrize("n", [0, 1, 7, 500])
def test_distinct_windows_is_unique_rows(n):
    from repro.serve.index_service import distinct_windows
    rng = np.random.default_rng(n)
    a = rng.integers(0, 6, n).astype(np.int64) * 16
    b = a + rng.integers(1, 4, n).astype(np.int64) * 16
    ab, inv = distinct_windows(a, b)
    want, want_inv = np.unique(np.stack([a, b], axis=1).reshape(n, 2),
                               axis=0, return_inverse=True)
    assert np.array_equal(ab.reshape(-1, 2), want)
    assert np.array_equal(inv, want_inv.reshape(-1))
    assert np.array_equal(ab.reshape(-1, 2)[inv], np.stack([a, b], axis=1))
