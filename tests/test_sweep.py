"""Sweep-engine certification: bit-identity with the legacy per-builder
loop, multi-λ build equality, batched-scoring exactness, cache reuse, and
the device scoring backends."""
import numpy as np
import pytest

from repro.core import (AffineUniformProfile, CachedProfile,
                        KeyPositions, MeasuredProfile, PROFILES, airtune,
                        batched_mean_read_costs, beam_search, brute_force,
                        expected_latency, make_builders)
from repro.core.builders import (build_eband, build_eband_multi,
                                 build_gband, build_gband_multi, build_gstep,
                                 build_gstep_multi)
from repro.core.registry import BUILDER_FAMILIES, register_builder
from repro.core.sweep import LayerCache
from repro.core.storage import affine_coefficients

from conftest import make_keys

BUILDERS = make_builders(lam_low=2**10, lam_high=2**16, base=4.0)
STRATEGIES = {
    "airtune": (airtune, dict(k=3, max_layers=4)),
    "beam": (beam_search, dict(k=3, max_layers=4)),
    "brute_force": (brute_force, dict(max_layers=3)),
}


def _data(kind="gmm", n=5_000, seed=3):
    return KeyPositions.fixed_record(make_keys(kind, n, seed), 16)


def _layers_equal(a, b) -> bool:
    if len(a) != len(b):
        return False
    for la, lb in zip(a, b):
        if la.kind != lb.kind:
            return False
        if la.kind == "step":
            fields = ("piece_keys", "piece_pos", "node_piece_off")
        else:
            fields = ("node_keys", "x1", "y1", "m", "delta")
            if la.clamp_lo != lb.clamp_lo or la.clamp_hi != lb.clamp_hi:
                return False
        if not all(np.array_equal(getattr(la, f), getattr(lb, f))
                   for f in fields):
            return False
    return True


# ---------------------------------------------------------------------------
# acceptance: sweep ≡ legacy loop, bit for bit, on every strategy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["gmm", "books"])
@pytest.mark.parametrize("pname", ["azure_ssd", "azure_nfs"])
@pytest.mark.parametrize("sname", list(STRATEGIES))
def test_sweep_bit_identical_to_legacy_loop(kind, pname, sname):
    D = _data(kind)
    strat, kw = STRATEGIES[sname]
    a = strat(D, PROFILES[pname], BUILDERS, sweep=True, **kw)
    b = strat(D, PROFILES[pname], BUILDERS, sweep=False, **kw)
    assert a.cost == b.cost                       # bitwise, not approx
    assert a.builder_names == b.builder_names
    assert _layers_equal(a.design.layers, b.design.layers)


def test_sweep_stats_counters():
    D = _data("gmm", n=20_000)
    res = brute_force(D, PROFILES["azure_ssd"], BUILDERS, max_layers=4)
    s = res.stats
    assert s.sweeps > 0 and s.sweep_seconds > 0.0
    assert s.layers_reused > 0                      # λ-dedup + vertex memo
    leg = brute_force(D, PROFILES["azure_ssd"], BUILDERS, max_layers=4,
                      sweep=False)
    # the sweep never does MORE work than the loop it replaces
    assert s.layers_built <= leg.stats.layers_built
    assert s.candidates_scored <= leg.stats.candidates_scored


# ---------------------------------------------------------------------------
# shared LayerCache: cross-tier / cross-strategy reuse, results unchanged
# ---------------------------------------------------------------------------
def test_shared_layer_cache_reuse_is_bit_identical():
    D = _data("gmm", n=10_000)
    cache = LayerCache()
    warm, cold = {}, {}
    # brute force first: its exhaustive expansion warms the cache for the
    # guided strategies (the tune-bench certification runs this order)
    for pname in ("azure_ssd", "azure_nfs"):
        for sname in ("brute_force", "airtune", "beam"):
            strat, kw = STRATEGIES[sname]
            warm[pname, sname] = strat(D, PROFILES[pname], BUILDERS,
                                       layer_cache=cache, **kw)
            cold[pname, sname] = strat(D, PROFILES[pname], BUILDERS, **kw)
    assert len(cache) > 0
    total_reused = 0
    for key, w in warm.items():
        c = cold[key]
        assert w.cost == c.cost and w.builder_names == c.builder_names
        assert _layers_equal(w.design.layers, c.design.layers)
        total_reused += w.stats.layers_reused
    # later runs must ride the earlier runs' builds: the guided searches
    # only cold-build vertices deeper than brute force's expansion bound
    later_warm = sum(warm[k].stats.layers_built for k in warm
                     if k[1] != "brute_force")
    later_cold = sum(cold[k].stats.layers_built for k in cold
                     if k[1] != "brute_force")
    assert later_warm < later_cold / 3
    assert total_reused > sum(c.stats.layers_reused for c in cold.values())


# ---------------------------------------------------------------------------
# batched scoring: bit-identity of the numpy evaluator, per profile kind
# ---------------------------------------------------------------------------
PROFILES_UNDER_TEST = [
    PROFILES["azure_ssd"],
    AffineUniformProfile(1e-4, 3e-4, 1e8, 4e8),
    MeasuredProfile(deltas=(256.0, 4096.0, 65536.0, 1 << 20),
                    seconds=(1e-4, 2e-4, 9e-4, 4e-3)),
    CachedProfile(backing=PROFILES["azure_nfs"], hit_rate=0.7),
]


@pytest.mark.parametrize("profile", PROFILES_UNDER_TEST,
                         ids=lambda p: p.name)
def test_batched_mean_read_costs_bit_identical(profile):
    rng = np.random.default_rng(0)
    W = rng.uniform(1.0, 1e6, size=(7, 1023))
    weights = rng.uniform(0.5, 4.0, size=1023)
    got = batched_mean_read_costs(W, weights, profile)
    for c in range(W.shape[0]):
        scalar = float(np.average(profile(W[c]), weights=weights))
        assert got[c] == scalar        # bitwise: same reduction order


# ---------------------------------------------------------------------------
# multi-λ builders: each element ≡ the single-λ build; saturated λ dedup
# ---------------------------------------------------------------------------
LAMS = [2.0**s for s in range(8, 21, 2)]


@pytest.mark.parametrize("kind", ["gmm", "fb"])
def test_multi_lam_builds_match_single(kind):
    D = _data(kind, n=4_000)
    multi = {
        "gstep": (build_gstep_multi(D, LAMS, 16),
                  [build_gstep(D, 16, l) for l in LAMS]),
        "gband": (build_gband_multi(D, LAMS, 16),
                  [build_gband(D, l) for l in LAMS]),
        "eband": (build_eband_multi(D, LAMS, 16),
                  [build_eband(D, l) for l in LAMS]),
    }
    for fam, (got, want) in multi.items():
        assert len(got) == len(LAMS)
        for g, w in zip(got, want):
            assert _layers_equal([g], [w]), fam
    # the grid saturates on this small extent: identical partitions must
    # share one layer object (that sharing is what layers_reused counts)
    gs = multi["gstep"][0]
    assert len({id(x) for x in gs}) < len(gs)


# ---------------------------------------------------------------------------
# baseline families (btree / rmi_leaf / pgm): sweep certification
# ---------------------------------------------------------------------------
BASELINE_BUILDERS = make_builders(lam_low=2**10, lam_high=2**16, base=4.0,
                                  kinds=("gstep", "btree", "rmi_leaf", "pgm"))


@pytest.mark.parametrize("pname", ["azure_ssd", "azure_nfs"])
@pytest.mark.parametrize("sname", list(STRATEGIES))
def test_baseline_families_sweep_bit_identical(pname, sname):
    """The registered baseline families certify sweep=True ≡ sweep=False
    on every strategy × tier (btree/pgm ride multi-λ adapters; rmi_leaf
    rides the per-λ fallback with canonical-λ dedup)."""
    D = _data("gmm")
    strat, kw = STRATEGIES[sname]
    a = strat(D, PROFILES[pname], BASELINE_BUILDERS, sweep=True, **kw)
    b = strat(D, PROFILES[pname], BASELINE_BUILDERS, sweep=False, **kw)
    assert a.cost == b.cost                       # bitwise, not approx
    assert a.builder_names == b.builder_names
    assert _layers_equal(a.design.layers, b.design.layers)


def test_per_lam_fallback_family_hits_layer_cache():
    """rmi_leaf has no multi-λ entry: the per-λ fallback must still dedup
    builds (canonical λ → model count) and ride a shared LayerCache —
    TuneStats.layers_reused counts both effects."""
    D = _data("gmm", n=5_000)
    builders = make_builders(lam_low=2**8, lam_high=2**20, base=2.0,
                             kinds=("rmi_leaf",))
    cache = LayerCache()
    r1 = airtune(D, PROFILES["azure_ssd"], builders, k=3, layer_cache=cache)
    # the grid extends past the collection extent, so several λs clamp to
    # the same model count: canonical-λ dedup shows up as reuse already
    # on the first (cold-cache) run
    assert r1.stats.layers_reused > 0
    assert len(cache) > 0
    r2 = airtune(D, PROFILES["azure_ssd"], builders, k=3, layer_cache=cache)
    # a second identical tune rebuilds nothing: every fallback build is a
    # LayerCache hit, and the shared entries' score memos carry over too
    assert r2.stats.layers_built == 0
    assert r2.stats.layers_reused > 0
    assert r2.stats.candidates_scored == 0
    assert r2.cost == r1.cost
    assert r2.builder_names == r1.builder_names


def test_third_party_single_lam_family_falls_back():
    """A family registered without a multi-λ entry must still sweep —
    per-λ fallback builds, bit-identical to the legacy loop."""
    def build_wide_step(D, lam, p):
        return build_gstep(D, max(int(p) * 2, 1), lam)

    register_builder("widestep2", build_wide_step)
    try:
        D = _data("gmm", n=4_000)
        fams = ("gstep", "widestep2")
        builders = make_builders(lam_low=2**10, lam_high=2**14, base=4.0,
                                 kinds=fams)
        a = airtune(D, PROFILES["azure_ssd"], builders, k=3, sweep=True)
        b = airtune(D, PROFILES["azure_ssd"], builders, k=3, sweep=False)
        assert a.cost == b.cost and a.builder_names == b.builder_names
        assert _layers_equal(a.design.layers, b.design.layers)
    finally:
        BUILDER_FAMILIES.unregister("widestep2")


def test_unhashable_profile_is_pinned_not_id_keyed():
    """Unhashable profiles (e.g. MeasuredProfile built with list fields)
    must be pinned by the shared cache so a garbage-collected profile's
    id() can never alias another profile's memoized costs."""
    cache = LayerCache()
    D = _data("gmm", n=4_000)

    def unhashable_profile(scale):
        # list fields defeat the frozen-dataclass hash → TypeError on hash()
        return MeasuredProfile(deltas=[256.0, 4096.0, 1 << 20],
                               seconds=[scale * 1e-4, scale * 2e-4,
                                        scale * 4e-3])

    p1 = unhashable_profile(1.0)
    with pytest.raises(TypeError):
        hash(p1)
    r1 = airtune(D, p1, BUILDERS, k=3, layer_cache=cache)
    assert p1 in cache._pinned_profiles
    del p1                                   # id() may now be recycled...
    p2 = unhashable_profile(50.0)            # ...by a very different tier
    r2 = airtune(D, p2, BUILDERS, k=3, layer_cache=cache)
    fresh = airtune(D, p2, BUILDERS, k=3)    # no shared cache: ground truth
    assert r2.cost == fresh.cost and r2.builder_names == fresh.builder_names
    assert r1.cost != r2.cost


# ---------------------------------------------------------------------------
# device scoring backends (ranking fast path)
# ---------------------------------------------------------------------------
def test_affine_coefficients():
    ssd = PROFILES["azure_ssd"]
    ell, inv_bw = affine_coefficients(ssd)
    assert ell == ssd.latency and inv_bw == 1.0 / ssd.bandwidth
    cached = CachedProfile(backing=ssd, hit_rate=0.5)
    co = affine_coefficients(cached)
    assert co is not None
    np.testing.assert_allclose(cached(1e6), co[0] + 1e6 * co[1], rtol=1e-12)
    au = AffineUniformProfile(1e-4, 3e-4, 1e8, 4e8)
    ell, inv_bw = affine_coefficients(au)
    np.testing.assert_allclose(au(1e5), ell + 1e5 * inv_bw, rtol=1e-12)
    assert affine_coefficients(MeasuredProfile((1.0, 2.0), (1e-6, 2e-6))) \
        is None


def test_candidate_score_backends_agree():
    jax = pytest.importorskip("jax")     # noqa: F841 — device backends
    from repro.kernels.candidate_score import (affine_candidate_scores,
                                               candidate_scores)
    rng = np.random.default_rng(1)
    W = rng.uniform(16.0, 1e5, size=(5, 700))
    weights = rng.uniform(0.5, 3.0, size=700)
    prof = PROFILES["azure_ssd"]
    ell, inv_bw = affine_coefficients(prof)
    ref = affine_candidate_scores(W, weights, ell, inv_bw, backend="numpy")
    for backend in ("jnp", "pallas"):
        got = affine_candidate_scores(W, weights, ell, inv_bw,
                                      backend=backend)
        np.testing.assert_allclose(got, ref, rtol=3e-5)
    # dispatcher: affine tier takes the device path, measured tier the
    # numpy path; both must agree with the exact evaluator to f32 rank res
    exact = batched_mean_read_costs(W, weights, prof)
    np.testing.assert_allclose(
        candidate_scores(W, weights, prof, backend="pallas"), exact,
        rtol=3e-5)
    measured = PROFILES_UNDER_TEST[2]
    got = candidate_scores(W, weights, measured, backend="pallas")
    assert np.array_equal(got, batched_mean_read_costs(W, weights, measured))


def test_device_backend_tune_matches_numpy_cost():
    """jnp ranking may reorder float ties, but returned costs are always
    exact Eq. (6) values and should match the numpy-path optimum here."""
    pytest.importorskip("jax")
    D = _data("gmm", n=5_000)
    prof = PROFILES["azure_ssd"]
    a = airtune(D, prof, BUILDERS, k=3, score_backend="jnp")
    b = airtune(D, prof, BUILDERS, k=3)
    assert a.cost == pytest.approx(expected_latency(a.design, prof), rel=1e-9)
    assert a.cost == pytest.approx(b.cost, rel=1e-6)


def test_candidate_score_pallas_many_candidates():
    """C > BLOCK_C: several grid steps, each writing its own score column."""
    from repro.kernels.candidate_score import affine_candidate_scores
    rng = np.random.default_rng(2)
    W = rng.uniform(16.0, 1e5, size=(61, 1024))
    weights = rng.uniform(0.5, 3.0, size=1024)
    ell, inv_bw = affine_coefficients(PROFILES["azure_ssd"])
    ref = affine_candidate_scores(W, weights, ell, inv_bw, backend="numpy")
    got = affine_candidate_scores(W, weights, ell, inv_bw, backend="pallas")
    assert got.shape == (61,)
    np.testing.assert_allclose(got, ref, rtol=3e-5)


def test_candidate_score_failure_propagates(monkeypatch):
    """A refused scoring kernel raises out of the dispatcher on an
    accelerator; it is never swapped for jnp or numpy in silence."""
    import jax

    import repro.kernels.candidate_score.kernel as kernel
    from repro.kernels.candidate_score import candidate_scores

    calls = []

    def boom(*a, **k):
        calls.append(k["interpret"])
        raise RuntimeError("kernel refused")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel, "affine_scores_pallas", boom)
    W = np.full((3, 40), 100.0)
    with pytest.raises(RuntimeError, match="kernel refused"):
        candidate_scores(W, np.ones(40), PROFILES["azure_ssd"],
                         backend="pallas")
    assert calls == [False]
