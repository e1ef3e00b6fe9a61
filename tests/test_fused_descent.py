"""Fused multi-layer descent: bit-identity of the numpy backend with the
per-layer walk, device-backend step-exactness / band containment within
the node-local slack (also over 64-bit keys and byte offsets past 2^33),
ragged batches, the visible numpy fallback and failures that propagate,
the platform-chosen interpret mode, and packing guards — across
layer-family mixes (gstep/gband/eband/rmi_leaf) and prefix depths."""
import numpy as np
import pytest

from repro.api import ServeSpec
from repro.core import IndexDesign, KeyPositions, write_index
from repro.core.baselines import build_rmi_leaf
from repro.core.builders import build_eband, build_gband, build_gstep
from repro.core.descent import (covering_index, descend_band_layer,
                                descend_step_layer)
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
    in-domain queries — parsed through the real IndexService path, over
    keys below 2**30 (the 64-bit cases are ``wide_stacks``)."""
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


def _slack(lay, q):
    """Per query, the most a device band row may exceed the float64
    window at each end: the node-local slack at the query's own distance
    from its node, plus the float32 error it covers (itself under the
    slack), plus the ends' rounding to whole bytes."""
    j = covering_index(lay["x1"], q)
    span = lay["m"][j] * (q - lay["x1"][j]).astype(np.float64)
    return 2.0 * fd.band_f32_slack(span, lay["delta"][j]) + 1.0


def _check_device_rows(layers, packed, q, want, got):
    """Step rows bit-exact; band rows hold the float64 window and exceed
    it by no more than the slack."""
    (rlo, rhi), (lo, hi) = want, got
    for r, lay in enumerate(layers):
        if packed["kinds"][r] == 0:
            np.testing.assert_array_equal(lo[r], rlo[r])
            np.testing.assert_array_equal(hi[r], rhi[r])
        else:
            assert np.all(lo[r] <= rlo[r]) and np.all(hi[r] >= rhi[r])
            bound = _slack(lay, q)
            assert np.all(rlo[r] - lo[r] <= bound)
            assert np.all(hi[r] - rhi[r] <= bound)


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
        for got in ((plo, phi), (jlo, jhi)):
            _check_device_rows(layers, packed, qs, (rlo, rhi), got)
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
            packed[k].nbytes for k in fd.PLANES
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
# fallback: only to numpy, only for a prefix wider than the planes, and
# always with a reason; a device backend's own failure propagates
# ---------------------------------------------------------------------------
def _high_prefix():
    """A one-layer step prefix whose keys and positions pass 2**32."""
    return [{"kind": "step",
             "keys": np.array([0, 2**31 + 5, 2**40, 2**64 - 2],
                              dtype=np.uint64),
             "pos_lo": np.array([0, 8, 2**33, 2**40], dtype=np.int64),
             "pos_hi": np.array([8, 2**33, 2**40, 2**40 + 16],
                                dtype=np.int64)}]


def test_fallback_chain_degrades_to_jnp_then_numpy(stacks):
    """No device chain is left: every batch a prefix of packable width
    gives is served by exactly the requested backend — keys, positions
    and queries past 2**31 included — and numpy serves only a prefix
    wider than the planes, naming the reason (width)."""
    prefixes, qs = stacks
    layers = prefixes["gstep3"]
    want_lo, want_hi = fd.fused_descent(layers, qs, backend="numpy")
    for backend in ("pallas", "jnp"):
        lo, hi, used, reason = fd.fused_descent_with_backend(
            layers, qs, backend=backend)
        assert (used, reason) == (backend, None)
        np.testing.assert_array_equal(lo, want_lo)   # all-step: exact

    big_q = np.array([3, 2**31 + 1, 2**64 - 1], dtype=np.uint64)
    lo, hi, used, reason = fd.fused_descent_with_backend(
        layers, big_q, backend="pallas")
    assert (used, reason) == ("pallas", None)
    np.testing.assert_array_equal(
        lo, fd.fused_descent(layers, big_q, backend="numpy")[0])

    high_q = np.array([0, 2**31 + 4, 2**31 + 5, 2**40 + 1, 2**64 - 1],
                      dtype=np.uint64)
    want = fd.fused_descent(_high_prefix(), high_q, backend="numpy")
    lo, hi, used, reason = fd.fused_descent_with_backend(
        _high_prefix(), high_q, backend="pallas")
    assert (used, reason) == ("pallas", None)
    np.testing.assert_array_equal(lo, want[0])
    np.testing.assert_array_equal(hi, want[1])

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
    high = _high_prefix()
    assert fd.prefix_gate(high) is None     # 64-bit keys and offsets pack
    assert fd.pack_prefix(high)["bases"].dtype == np.int64
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
    for k in fd.PLANES[1:]:
        assert planes[k].shape == (2, 1, 128)       # one LANE-wide row
    # padded with the layer's last entry, which ranks and predicts alike
    assert np.all(planes["key_lo"][:, 0, 2:] == planes["key_lo"][:, :, 2])
    hi_base = planes["bases"][1].reshape(2, 128)
    assert np.all(hi_base[:, 2:] == 3)


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
    """ServeStats attributes each batch to the backend that served it:
    keys below 2**30 and keys past 2**31 alike on ``pallas``, a query past
    2**32 too, with no numpy batch and no reason; ``wide_queries`` counts
    the queries whose key has a nonzero high word, and ``rebase_seconds``
    the widening of every batch's windows."""
    rng = np.random.default_rng(21)
    small = np.unique(rng.integers(1, 2**30, 20_000).astype(np.uint64))
    big = np.unique(rng.integers(2**31, 2**40, 20_000).astype(np.uint64))
    big = big[big >= 2**32]
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
                svc.lookup(np.array([5, 2**31 + 7, 2**33], dtype=np.uint64))
            got[name] = svc.stats
    s = got["small"]
    assert (s.pallas_batches, s.jnp_batches, s.numpy_batches) == (3, 0, 0)
    assert s.numpy_width_batches == 0 and s.wide_queries == 1
    b = got["big"]
    assert (b.pallas_batches, b.jnp_batches, b.numpy_batches) == (2, 0, 0)
    assert b.numpy_width_batches == 0 and b.wide_queries == 340
    assert s.rebase_seconds > 0 and b.rebase_seconds > 0
    assert b.rebase_seconds <= b.descent_collect_seconds


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


# ---------------------------------------------------------------------------
# 64-bit keys: the device path against the float64 walk, keys spanning
# [1, 2**64) and data byte offsets past 2**33
# ---------------------------------------------------------------------------
# keys (and queries) at each word and sign boundary of the two-word form
EDGE_KEYS = np.array([1, 2**31 - 1, 2**32, 2**63 - 1, 2**63, 2**64 - 1],
                     dtype=np.uint64)
DATA_BASE = 2**33       # the data's first byte: every offset passes 2**33


def _dense_run(rng, start: int, n: int) -> np.ndarray:
    """``n`` keys from ``start`` about 10^9 apart: closer than float32's
    ULP there (2^39 at 2^63), so rounding a key itself to float32 moves
    its prediction by hundreds of records."""
    gaps = rng.integers(5 * 10**8, 15 * 10**8, n, dtype=np.uint64)
    return np.uint64(start) + np.cumsum(gaps, dtype=np.uint64)


@pytest.fixture(scope="module")
def wide_stacks(tmp_path_factory):
    """{mix name: top-down parsed resident prefix} over 40k keys drawn
    from all of [1, 2**64), ``EDGE_KEYS``, and two dense runs, one across
    2**63 and one ending just below 2**64 - 1; records laid out from
    ``DATA_BASE``; queries are the edge keys and a sample of the rest."""
    rng = np.random.default_rng(64)
    keys = np.concatenate([
        rng.integers(1, 2**64 - 1, 40_000, dtype=np.uint64, endpoint=True),
        EDGE_KEYS, _dense_run(rng, 2**63 - 5 * 10**12, 10_000),
        _dense_run(rng, 2**64 - 2 * 10**13, 10_000)])
    keys = np.unique(keys)
    D = KeyPositions.fixed_record(keys, 16, base=DATA_BASE)
    qs = np.concatenate([EDGE_KEYS, rng.choice(D.keys, 700)])
    root = tmp_path_factory.mktemp("fused64")
    out = {}
    for name, kinds in MIXES.items():
        path = str(root / f"{name}.air")
        write_index(path, _design(D, kinds), page_bytes=1024)
        with IndexService(path, profile=None,
                          spec=ServeSpec(resident_layers=3)) as svc:
            out[name] = svc._prefix
    return out, qs


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
@pytest.mark.parametrize("name", sorted(MIXES))
def test_device_backends_on_64bit_keys(wide_stacks, name, backend):
    prefixes, qs = wide_stacks
    layers = prefixes[name]
    want = fd.fused_descent(layers, qs, backend="numpy")
    assert want[0].max() > DATA_BASE and qs.max() == 2**64 - 1
    lo, hi, used, reason = fd.fused_descent_with_backend(layers, qs,
                                                         backend=backend)
    assert (used, reason) == (backend, None)
    _check_device_rows(layers, fd.pack_prefix(layers), qs, want, (lo, hi))


def test_band_slack_covers_wide_nodes():
    """Keys spaced evenly through [2**63, 2**64) under 4 KiB records give
    band nodes hundreds of MB wide, where float32 rounding of ``mid`` is
    tens of bytes: the slack's share of the span covers it."""
    rng = np.random.default_rng(9)
    step = 2**63 // 100_000
    keys = (np.uint64(2**63) + np.arange(100_000, dtype=np.uint64)
            * np.uint64(step) + rng.integers(0, 2**20, 100_000,
                                             dtype=np.uint64))
    D = KeyPositions.fixed_record(keys, 4096, base=DATA_BASE)
    layer = build_gband(D, 2**16)
    assert layer.n_nodes < 16
    path_layers = [{"kind": "band", "x1": layer.x1, "y1":
                    layer.y1.astype(np.float64), "m": layer.m,
                    "delta": layer.delta}]
    q = rng.choice(keys, 2000)
    want = fd.fused_descent(path_layers, q, backend="numpy")
    packed = fd.pack_prefix(path_layers)
    for backend in ("pallas", "jnp"):
        got = fd.fused_descent(path_layers, q, backend=backend)
        _check_device_rows(path_layers, packed, q, want, got)


def test_split_words_order_as_uint64():
    rng = np.random.default_rng(7)
    a = np.concatenate([EDGE_KEYS, rng.integers(0, 2**64 - 1, 2000,
                                                dtype=np.uint64)])
    b = np.concatenate([EDGE_KEYS[::-1], a[len(EDGE_KEYS):][::-1]])
    (ah, al), (bh, bl) = fd.split_words(a), fd.split_words(b)
    assert ah.dtype == np.int32
    le = (ah < bh) | ((ah == bh) & (al <= bl))
    np.testing.assert_array_equal(le, a <= b)
    # the words give the key back
    back = ((ah.view(np.uint32) ^ fd.SIGN).astype(np.uint64) << np.uint64(32)) \
        | (al.view(np.uint32) ^ fd.SIGN).astype(np.uint64)
    np.testing.assert_array_equal(back, a)


def test_band_slack_is_node_local():
    """The slack grows with the node's own byte span, not with where the
    node sits in the data: a node 8 MB wide gets about 10 bytes a side at
    any offset, where a rule on |y1| gave 12.8 KB at 3.2 GB."""
    assert fd.band_f32_slack(0.0, 0.0) == 2.0
    s = fd.band_f32_slack(8 << 20, 8192)
    assert 10.0 < s < 10.01
    assert fd.band_f32_slack(-(8 << 20), -8192) == s


def test_index_api_serves_64bit_keys_on_pallas(tmp_path):
    """``Index.from_design → save → open → serve(backend="pallas")`` over
    64-bit keys answers every lookup with a range that holds the key's
    record, against ``np.searchsorted``; every batch on Pallas."""
    from repro.api import Index

    rng = np.random.default_rng(15)
    keys = np.unique(np.concatenate([
        rng.integers(1, 2**64 - 1, 30_000, dtype=np.uint64, endpoint=True),
        EDGE_KEYS]))
    D = KeyPositions.fixed_record(keys, 16)
    path = str(tmp_path / "wide.air")
    Index.from_design(_design(D, ("gband", "gstep", "gstep"))).save(path)
    q = np.concatenate([EDGE_KEYS, rng.choice(keys, 500)])
    with Index.open(path).serve(spec=ServeSpec(
            backend="pallas", resident_layers=2)) as svc:
        got = svc.lookup(q)
        s = svc.stats
    assert s.pallas_batches == s.batches == 1 and s.numpy_batches == 0
    assert s.wide_queries == int(np.count_nonzero(q >= 2**32))
    rec = np.searchsorted(keys, q).astype(np.int64) * 16
    assert np.all((got[:, 0] <= rec) & (got[:, 1] >= rec + 16))
    assert np.all((got[:, 0] >= 0) & (got[:, 1] <= 16 * len(keys)))
