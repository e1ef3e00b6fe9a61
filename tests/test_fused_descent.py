"""Fused multi-layer descent: bit-identity of the numpy backend with the
per-layer walk, device-backend step-exactness / band containment, ragged
batches, the visible numpy fallback (per reason) and failures that
propagate, the platform-chosen interpret mode, and packing guards —
across layer-family mixes (gstep/gband/eband/rmi_leaf) and prefix depths."""
import numpy as np
import pytest

from repro.api import ServeSpec
from repro.core import IndexDesign, KeyPositions, write_index
from repro.core.baselines import build_rmi_leaf
from repro.core.builders import build_eband, build_gband, build_gstep
from repro.core.descent import descend_band_layer, descend_step_layer
from repro.kernels import fused_descent as fd
from repro.core.nodes import outline
from repro.serve.index_service import IndexService

from conftest import make_keys

# bottom-up family stacks, λ shrinking upward (demo_serving_design's
# shape); every registered serving family appears in some prefix
MIXES = {
    "gstep3": ("gstep", "gstep", "gstep"),
    "step-band-step": ("gstep", "gband", "gstep"),
    "band-eband-step": ("gband", "eband", "gstep"),
    "rmi-step-step": ("rmi_leaf", "gstep", "gstep"),
}

_BUILD = {
    "gstep": lambda D, lam: build_gstep(D, 8, lam),
    "gband": build_gband,
    "eband": build_eband,
    "rmi_leaf": lambda D, lam: build_rmi_leaf(
        D, max(int(len(D.keys) // lam), 1)),
}


def _design(D, kinds):
    layers, cur = [], D
    for kind, lam in zip(kinds, (2**10, 2**9, 2**7)):
        lay = _BUILD[kind](cur, lam)
        layers.append(lay)
        cur = outline(lay, cur)
    return IndexDesign(layers=tuple(layers), data=D)


@pytest.fixture(scope="module")
def stacks(tmp_path_factory):
    """{mix name: top-down parsed resident prefix (all 3 layers)} plus
    in-domain queries — parsed through the real IndexService path.
    Keys stay below 2**30 so the device backends are eligible (int32
    packing guard, same bound as the previous use_device gating)."""
    rng0 = np.random.default_rng(11)
    keys = np.unique(rng0.integers(1, 2**30, 60_000).astype(np.uint64))
    D = KeyPositions.fixed_record(keys, 16)
    rng = np.random.default_rng(5)
    qs = rng.choice(D.keys, 600)
    root = tmp_path_factory.mktemp("fused")
    out = {}
    for name, kinds in MIXES.items():
        path = str(root / f"{name}.air")
        write_index(path, _design(D, kinds), page_bytes=1024)
        with IndexService(path, profile=None,
                          spec=ServeSpec(resident_layers=3)) as svc:
            out[name] = svc._prefix
    return out, qs


def _per_layer_walk(prefix, q):
    """The pre-fusion reference: one descend_* call per layer."""
    lo = np.empty((len(prefix), len(q)), dtype=np.float64)
    hi = np.empty_like(lo)
    for r, lay in enumerate(prefix):
        if lay["kind"] == "step":
            l_, h_ = descend_step_layer(lay["keys"], lay["pos_lo"],
                                        lay["pos_hi"], q)
        else:
            l_, h_ = descend_band_layer(lay["x1"], lay["x1"], lay["y1"],
                                        lay["m"], lay["delta"], q)
        lo[r], hi[r] = l_, h_
    return lo, hi


# ---------------------------------------------------------------------------
# numpy backend == per-layer walk, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(MIXES))
@pytest.mark.parametrize("depth", [0, 1, 2, 3])
def test_numpy_backend_bit_identical_to_per_layer(stacks, name, depth):
    prefixes, qs = stacks
    layers = prefixes[name][:depth]
    for n in (1, 7, 256, 600):
        q = qs[:n]
        want_lo, want_hi = _per_layer_walk(layers, q)
        lo, hi, used, reason = fd.fused_descent_with_backend(
            layers, q, backend="numpy")
        assert used == "numpy" and reason is None
        assert lo.shape == (depth, n) and hi.shape == (depth, n)
        np.testing.assert_array_equal(lo, want_lo)
        np.testing.assert_array_equal(hi, want_hi)


def test_empty_prefix_all_backends(stacks):
    _, qs = stacks
    for backend in ("numpy", "jnp", "pallas"):
        lo, hi, used, reason = fd.fused_descent_with_backend(
            [], qs, backend=backend)
        assert used == "numpy"          # nothing to pack → numpy serves
        assert reason is None           # ... and nothing was refused
        assert lo.shape == (0, len(qs))


# ---------------------------------------------------------------------------
# device backends: step rows exact, band rows valid-but-wider, pallas≈jnp
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(MIXES))
def test_device_backends_step_exact_band_contained(stacks, name):
    prefixes, qs = stacks
    for depth in (1, 2, 3):
        layers = prefixes[name][:depth]
        rlo, rhi = fd.fused_descent(layers, qs, backend="numpy")
        plo, phi, pu, _ = fd.fused_descent_with_backend(layers, qs,
                                                        backend="pallas")
        jlo, jhi, ju, _ = fd.fused_descent_with_backend(layers, qs,
                                                        backend="jnp")
        assert pu == "pallas" and ju == "jnp"
        packed = fd.pack_prefix(layers)
        for r, lay in enumerate(layers):
            if packed["kinds"][r] == 0:          # step: exact on both
                np.testing.assert_array_equal(plo[r], rlo[r])
                np.testing.assert_array_equal(phi[r], rhi[r])
                np.testing.assert_array_equal(jlo[r], rlo[r])
                np.testing.assert_array_equal(jhi[r], rhi[r])
            else:                                # band: contained + bounded
                assert np.all(plo[r] <= rlo[r]) and np.all(phi[r] >= rhi[r])
                assert np.all(jlo[r] <= rlo[r]) and np.all(jhi[r] >= rhi[r])
                bound = 2.0 * float(np.max(fd.band_f32_slack(
                    lay["y1"], lay["m"], lay["x1"]))) + 4.0
                assert np.max((phi[r] - plo[r]) - (rhi[r] - rlo[r])) <= bound
        # pallas vs jnp differ only by f32 FMA contraction on band mids
        assert np.max(np.abs(plo - jlo)) <= 4
        assert np.max(np.abs(phi - jhi)) <= 4


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
@pytest.mark.parametrize("name", sorted(MIXES))
def test_resident_planes_bit_identical_to_per_call(stacks, name, backend):
    """Planes uploaded once and passed as ``resident=`` give the very lo
    and hi the per-call upload gives, for every depth and ragged batch;
    only the query bytes are sent with a resident batch."""
    prefixes, qs = stacks
    for depth in (1, 2, 3):
        layers = prefixes[name][:depth]
        packed = fd.pack_prefix(layers)
        resident, plane_bytes = fd.upload_planes(packed, backend)
        assert plane_bytes == sum(
            packed[k].nbytes for k in packed
            if backend == "pallas" or k != "kinds")
        for n in (1, 255, 600):
            q = qs[:n]
            per_call, at_rest = {}, {}
            want = fd.fused_descent_with_backend(
                layers, q, backend=backend, timings=per_call)
            got = fd.fused_descent_with_backend(
                layers, q, backend=backend, packed=packed,
                resident=resident, timings=at_rest)
            assert got[2:] == want[2:] == (backend, None)
            np.testing.assert_array_equal(got[0], want[0])
            np.testing.assert_array_equal(got[1], want[1])
            assert per_call["h2d_bytes"] == at_rest["h2d_bytes"] \
                + plane_bytes


def test_ragged_batches_match_full_batch(stacks):
    prefixes, qs = stacks
    layers = prefixes["step-band-step"]
    flo, fhi = fd.fused_descent(layers, qs, backend="pallas")
    off = 0
    for n in (1, 7, 255, 256, 81):
        blo, bhi = fd.fused_descent(layers, qs[off:off + n],
                                    backend="pallas")
        np.testing.assert_array_equal(blo, flo[:, off:off + n])
        np.testing.assert_array_equal(bhi, fhi[:, off:off + n])
        off += n


# ---------------------------------------------------------------------------
# fallback: only to numpy, only for batches the int32 planes cannot hold,
# and always with a reason; a device backend's own failure propagates
# ---------------------------------------------------------------------------
def _oversized_prefix():
    """A one-layer step prefix whose keys reach 2**31 (key_range)."""
    return [{"kind": "step",
             "keys": np.array([0, 2**31 + 5], dtype=np.uint64),
             "pos_lo": np.array([0, 8], dtype=np.int64),
             "pos_hi": np.array([8, 16], dtype=np.int64)}]


def test_fallback_chain_degrades_to_jnp_then_numpy(stacks):
    """No device chain is left: a packable batch is served by exactly the
    requested backend, and numpy serves only unrepresentable batches,
    naming the reason (width / key_range / query_range)."""
    prefixes, qs = stacks
    layers = prefixes["gstep3"]
    want_lo, want_hi = fd.fused_descent(layers, qs, backend="numpy")
    for backend in ("pallas", "jnp"):
        lo, hi, used, reason = fd.fused_descent_with_backend(
            layers, qs, backend=backend)
        assert (used, reason) == (backend, None)
        np.testing.assert_array_equal(lo, want_lo)   # all-step: exact

    big_q = np.array([3, 2**31 + 1], dtype=np.uint64)
    lo, hi, used, reason = fd.fused_descent_with_backend(
        layers, big_q, backend="pallas")
    assert (used, reason) == ("numpy", "query_range")
    np.testing.assert_array_equal(
        lo, fd.fused_descent(layers, big_q, backend="numpy")[0])

    lo, hi, used, reason = fd.fused_descent_with_backend(
        _oversized_prefix(), qs, backend="pallas")
    assert (used, reason) == ("numpy", "key_range")

    n = fd.MAX_VMEM_ENTRIES + 1
    wide = [{"kind": "step", "keys": np.arange(n, dtype=np.uint64),
             "pos_lo": np.arange(n, dtype=np.int64),
             "pos_hi": np.arange(1, n + 1, dtype=np.int64)}]
    lo, hi, used, reason = fd.fused_descent_with_backend(
        wide, qs, backend="jnp")
    assert (used, reason) == ("numpy", "width")


def test_kernel_failure_propagates_off_cpu(stacks, monkeypatch):
    """On an accelerator a failing kernel raises out of the dispatcher (no
    silent jnp/numpy stand-in), and its per-batch compiled entry is asked
    to run compiled."""
    import jax

    import repro.kernels.fused_descent.kernel as kernel
    import repro.kernels.fused_descent.ref as ref

    prefixes, qs = stacks
    layers = prefixes["step-band-step"]
    calls = []

    def boom(*a, **k):
        calls.append(k)
        raise RuntimeError("kernel refused")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel, "fused_descent_windows", boom)
    with pytest.raises(RuntimeError, match="kernel refused"):
        fd.fused_descent_with_backend(layers, qs, backend="pallas")
    assert calls == [{"interpret": False}]
    monkeypatch.setattr(ref, "fused_descent_jnp", boom)
    with pytest.raises(RuntimeError, match="kernel refused"):
        fd.fused_descent_with_backend(layers, qs, backend="jnp")


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("tpu", False),
                                                ("gpu", False)])
def test_interpret_mode_follows_platform(stacks, monkeypatch, platform,
                                         interpret):
    import jax

    import repro.kernels.fused_descent.kernel as kernel
    from repro.kernels import interpret_mode

    prefixes, qs = stacks
    seen = []
    real = kernel.fused_descent_windows     # the per-batch compiled entry

    def spy(*a, **k):
        seen.append(k["interpret"])
        return real(*a, interpret=True)     # this host can only interpret

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    monkeypatch.setattr(kernel, "fused_descent_windows", spy)
    assert interpret_mode() is interpret
    fd.fused_descent_with_backend(prefixes["gstep3"], qs[:8],
                                  backend="pallas")
    assert seen == [interpret]


# ---------------------------------------------------------------------------
# packing guards: ineligible prefixes must decline, not break
# ---------------------------------------------------------------------------
def test_pack_prefix_guards():
    assert fd.pack_prefix([]) is None
    over = {"kind": "step",
            "keys": np.array([0, 2**31 - 1], dtype=np.uint64),
            "pos_lo": np.array([0, 8], dtype=np.int64),
            "pos_hi": np.array([8, 16], dtype=np.int64)}
    assert fd.pack_prefix([over]) is None
    assert fd.prefix_gate([over]) == "key_range"
    n = fd.MAX_VMEM_ENTRIES + 1
    wide = {"kind": "step", "keys": np.arange(n, dtype=np.uint64),
            "pos_lo": np.arange(n, dtype=np.int64),
            "pos_hi": np.arange(1, n + 1, dtype=np.int64)}
    assert fd.pack_prefix([wide]) is None
    assert fd.prefix_gate([wide]) == "width"
    ok = {"kind": "step", "keys": np.arange(3, dtype=np.uint64),
          "pos_lo": np.arange(3, dtype=np.int64),
          "pos_hi": np.arange(1, 4, dtype=np.int64)}
    assert fd.prefix_gate([ok]) is None
    planes = fd.pack_prefix([ok, ok])
    assert planes["kinds"].shape == (2,)
    assert planes["keys"].shape == (2, 1, 128)      # one LANE-wide row


# ---------------------------------------------------------------------------
# engine integration: fused windows feed the disk walk correctly
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_engine_numpy_backend_parity_across_depths(stacks, tmp_path, depth):
    from repro.core.serialize import lookup_serialized
    keys = make_keys("fb", 50_000, seed=13)
    D = KeyPositions.fixed_record(keys, 16)
    path = str(tmp_path / "mix.air")
    write_index(path, _design(D, MIXES["step-band-step"]), page_bytes=1024)
    rng = np.random.default_rng(2)
    qs = rng.choice(D.keys, 400)
    want = lookup_serialized(path, None, qs)
    with IndexService(path, profile=None,
                      spec=ServeSpec(resident_layers=depth)) as svc:
        got = svc.lookup(qs)
    assert np.array_equal(got, want)


def test_engine_device_backend_valid_and_attributed(stacks, tmp_path):
    rng0 = np.random.default_rng(13)
    keys = np.unique(rng0.integers(1, 2**30, 50_000).astype(np.uint64))
    D = KeyPositions.fixed_record(keys, 16)
    path = str(tmp_path / "dev.air")
    write_index(path, _design(D, MIXES["band-eband-step"]), page_bytes=1024)
    rng = np.random.default_rng(3)
    qs = rng.choice(D.keys, 300)
    with IndexService(path, profile=None,
                      spec=ServeSpec(resident_layers=3)) as ref_svc:
        want = ref_svc.lookup(qs)
    with IndexService(path, profile=None,
                      spec=ServeSpec(resident_layers=3,
                                     backend="pallas")) as svc:
        assert svc.device_active
        got = svc.lookup(qs)
        assert svc.stats.pallas_batches == 1
        assert svc.stats.interpret_batches == 1     # CPU: interpreted
        assert svc.stats.jnp_batches == svc.stats.numpy_batches == 0
        assert svc.stats.descent_seconds > 0
    # device band widening may only widen the final data window
    assert np.all(got[:, 0] <= want[:, 0]) and np.all(got[:, 1] >= want[:, 1])
    idx = np.searchsorted(D.keys, qs)
    assert np.all((got[:, 0] <= D.lo[idx]) & (got[:, 1] >= D.hi[idx]))


def test_engine_counts_batches_per_backend_and_reason(tmp_path):
    """ServeStats attributes each batch to the backend that served it: a
    packable prefix on ``pallas``; a prefix with keys ≥ 2**31 on numpy
    under ``key_range``; an out-of-range query batch under
    ``query_range``."""
    rng = np.random.default_rng(21)
    small = np.unique(rng.integers(1, 2**30, 20_000).astype(np.uint64))
    big = np.unique(rng.integers(2**31, 2**40, 20_000).astype(np.uint64))
    got = {}
    for name, keys in (("small", small), ("big", big)):
        D = KeyPositions.fixed_record(keys, 16)
        path = str(tmp_path / f"{name}.air")
        write_index(path, _design(D, MIXES["step-band-step"]),
                    page_bytes=1024)
        with IndexService(path, profile=None,
                          spec=ServeSpec(resident_layers=2,
                                         backend="pallas")) as svc:
            svc.lookup(rng.choice(D.keys, 300))
            svc.lookup(rng.choice(D.keys, 40))
            if name == "small":
                svc.lookup(np.array([5, 2**31 + 7], dtype=np.uint64))
            got[name] = svc.stats
    s = got["small"]
    assert (s.pallas_batches, s.jnp_batches, s.numpy_batches) == (2, 0, 1)
    assert s.numpy_query_range_batches == 1
    assert s.numpy_width_batches == s.numpy_key_range_batches == 0
    b = got["big"]
    assert (b.pallas_batches, b.jnp_batches, b.numpy_batches) == (0, 0, 2)
    assert b.numpy_key_range_batches == 2
    assert b.numpy_query_range_batches == b.numpy_width_batches == 0


def test_engine_uploads_planes_once_per_epoch(tmp_path):
    """A device-backed service puts its resident planes on the device once
    per epoch, however many batches it serves, through ``lookup`` and the
    pipelined ``lookup_batches`` (whose prefetch worker descends too);
    ``swap`` uploads the new epoch's.  Answers stay within the device's
    slack of the numpy backend's."""
    from repro.serve.index_service import ServeStats

    rng = np.random.default_rng(23)
    keys = np.unique(rng.integers(1, 2**30, 50_000).astype(np.uint64))
    D = KeyPositions.fixed_record(keys, 16)
    paths = []
    for name in ("band-eband-step", "step-band-step"):
        paths.append(str(tmp_path / f"{name}.air"))
        write_index(paths[-1], _design(D, MIXES[name]), page_bytes=1024)
    batches = [rng.choice(D.keys, n) for n in (300, 1, 257, 64, 512, 90)]
    spec = ServeSpec(resident_layers=2, cache_bytes=(8 << 10,),
                     pipeline_depth=2)

    def check(got, want, q):
        assert np.all(got[:, 0] <= want[:, 0])
        assert np.all(got[:, 1] >= want[:, 1])
        idx = np.searchsorted(D.keys, q)
        assert np.all((got[:, 0] <= D.lo[idx]) & (got[:, 1] >= D.hi[idx]))

    wants = {}
    for path in paths:
        with IndexService(path, profile=None, spec=spec) as ref_svc:
            wants[path] = ref_svc.lookup_batches(batches)
    with IndexService(path := paths[0], profile=None,
                      spec=spec.replace(backend="pallas")) as svc:
        assert svc.stats.plane_uploads == 1
        for q, want in zip(batches, wants[path]):
            check(svc.lookup(q), want, q)
        for q, got, want in zip(batches, svc.lookup_batches(batches),
                                wants[path]):
            check(got, want, q)
        s = svc.stats
        assert s.plane_uploads == 1 and s.pipelined_batches == len(batches)
        assert s.pallas_batches == 2 * len(batches)
        assert ServeStats.from_snapshot(s.snapshot()).plane_uploads == 1
        old = svc._st
        svc.swap(path := paths[1])
        assert old.dev_planes is None           # released: nothing pinned
        assert svc.stats.plane_uploads == 2 and svc.stats.swaps == 1
        for q, got, want in zip(batches, svc.lookup_batches(batches),
                                wants[path]):
            check(got, want, q)
        assert svc.stats.plane_uploads == 2
        assert svc.stats.pallas_batches == len(batches)
