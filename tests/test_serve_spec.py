"""ServeSpec: JSON round-trip, validation, on-disk meta recording, the
legacy-kwarg deprecation shims, and the Index.observe wrappers."""
import json
import warnings

import numpy as np
import pytest

from repro.api import Index, ServeSpec, TuneSpec
from repro.api.drift import DriftReport
from repro.core import KeyPositions
from repro.serve.index_service import IndexService, demo_serving_design

from conftest import make_keys


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    keys = make_keys("gmm", 40_000, seed=9)
    D = KeyPositions.fixed_record(keys, 16)
    idx = Index.from_design(demo_serving_design(D),
                            spec=TuneSpec(page_bytes=1024,
                                          cache_bytes=(128 << 10,)),
                            profile="azure_ssd")
    path = str(tmp_path_factory.mktemp("sspec") / "index.air")
    idx.save(path)
    return D, idx, path


# ---------------------------------------------------------------------------
# value-object mechanics (symmetric with TuneSpec)
# ---------------------------------------------------------------------------
def test_serve_spec_json_roundtrip():
    spec = ServeSpec(cache_bytes=(64 << 10, 1 << 20), cache_profile=None,
                     page_bytes=512, resident_layers=2, backend="pallas",
                     coalesce_gap=64, persist_stats=True,
                     pipeline_depth=3, prefetch_layers=2)
    assert ServeSpec.from_json(spec.to_json()) == spec
    assert json.loads(spec.to_json())["cache_bytes"] == [64 << 10, 1 << 20]
    assert spec.replace(backend="jnp").backend == "jnp"
    assert spec.backend == "pallas"               # frozen: replace copies


def test_serve_spec_validate_rejects_bad_knobs():
    with pytest.raises(ValueError, match="unknown backend"):
        ServeSpec(backend="cuda").validate()
    with pytest.raises(ValueError, match="unknown cache_profile"):
        ServeSpec(cache_profile="l5_cache").validate()
    with pytest.raises(ValueError, match="negative sizes"):
        ServeSpec(page_bytes=-1).validate()
    with pytest.raises(ValueError, match="bad knobs"):
        ServeSpec(prefetch_layers=0).validate()
    with pytest.raises(ValueError, match="bad knobs"):
        ServeSpec(pipeline_depth=-1).validate()
    with pytest.raises(ValueError, match="unknown ServeSpec fields"):
        ServeSpec.from_dict({"use_device": True})
    ServeSpec().validate()                        # defaults are valid


# ---------------------------------------------------------------------------
# recorded into the meta, restored on open, honored by serve()
# ---------------------------------------------------------------------------
def test_serve_spec_recorded_and_restored(saved, tmp_path):
    D, idx, _ = saved
    want = ServeSpec(cache_bytes=(32 << 10,), resident_layers=2,
                     coalesce_gap=128, pipeline_depth=2)
    path = str(tmp_path / "withserve.air")
    idx.save(path, serve_spec=want)
    re = Index.open(path)
    assert re.serve_spec == want
    assert (re.file_meta.tune or {}).get("serve") == want.to_dict()
    with re.serve(profile=None) as svc:           # recorded spec drives it
        assert svc.spec == want
        assert svc.cache.cap_pages[0] == (32 << 10) // svc.page_bytes
        assert len(svc._prefix) == 2
    # field overrides replace on top of the recorded spec
    with re.serve(profile=None, resident_layers=1) as svc:
        assert svc.spec.resident_layers == 1
        assert svc.spec.coalesce_gap == 128       # rest kept
    # engine alone also restores it from the meta
    with IndexService(path, profile=None) as svc:
        assert svc.spec == want


def test_meta_with_retired_interpret_field_still_opens(saved, tmp_path):
    """Metas written while ServeSpec still carried ``interpret`` open: the
    key is dropped on load, the platform picks the Pallas mode."""
    from repro.core import write_index
    rng = np.random.default_rng(8)
    D = KeyPositions.fixed_record(
        np.unique(rng.integers(1, 2**30, 30_000).astype(np.uint64)), 16)
    old = ServeSpec(resident_layers=2, backend="pallas").to_dict()
    old["interpret"] = True
    assert ServeSpec.from_dict(old) == ServeSpec(resident_layers=2,
                                                 backend="pallas")
    path = str(tmp_path / "old.air")
    write_index(path, demo_serving_design(D), page_bytes=1024,
                tune={"serve": old})
    assert Index.open(path).serve_spec.backend == "pallas"
    with IndexService(path, profile=None) as svc:
        assert svc.spec.resident_layers == 2
        got = svc.lookup(D.keys[:64])
        assert svc.stats.pallas_batches == 1
    assert np.all((got[:, 0] <= D.lo[:64]) & (got[:, 1] >= D.hi[:64]))


def test_serve_rejects_unknown_override(saved):
    D, idx, path = saved
    with pytest.raises(TypeError, match="unexpected keyword"):
        Index.open(path).serve(cache_mb=64)


def test_serve_spec_property_none_without_recording(saved):
    D, idx, path = saved
    assert Index.open(path).serve_spec is None


# ---------------------------------------------------------------------------
# legacy kwargs: warn-once shims outside, hard error inside repro
# ---------------------------------------------------------------------------
def test_legacy_kwargs_fold_into_spec_and_warn_once(saved):
    D, idx, path = saved
    from repro.core.deprecation import _WARNED
    for msg in [m for m in _WARNED
                if m.startswith("repro.serve.IndexService")]:
        _WARNED.discard(msg)
    with pytest.warns(DeprecationWarning,
                      match=r"repro\.serve\.IndexService\(use_device="):
        with IndexService(path, profile=None, use_device=True,
                          resident_layers=2) as svc:
            assert svc.spec.backend == "pallas"
            assert svc.spec.resident_layers == 2
    with warnings.catch_warnings():
        warnings.simplefilter("error")            # second use: deduplicated
        with IndexService(path, profile=None, use_device=False) as svc:
            assert svc.spec.backend == "numpy"


def test_legacy_kwargs_hard_error_inside_repro(saved):
    D, idx, path = saved
    src = ("from repro.serve.index_service import IndexService\n"
           "IndexService(path, profile=None, cache_bytes=(1024,))\n")
    with pytest.raises(AssertionError,
                       match="deprecated API used from within repro"):
        exec(src, {"__name__": "repro._testshim", "path": path})


def test_legacy_unknown_kwarg_is_type_error(saved):
    D, idx, path = saved
    with pytest.raises(TypeError, match="unexpected keyword"):
        IndexService(path, profile=None, cache_mb=64)


# ---------------------------------------------------------------------------
# Index.observe / observe_offline (the facade's drift entry points)
# ---------------------------------------------------------------------------
def test_observe_wrappers(saved, tmp_path):
    D, idx, _ = saved
    path = str(tmp_path / "obs.air")
    idx.save(path, serve_spec=ServeSpec(persist_stats=True))
    re = Index.open(path)
    assert re.observe_offline() is None           # nothing persisted yet
    rng = np.random.default_rng(4)
    with re.serve() as svc:
        for _ in range(4):
            svc.lookup(rng.choice(D.keys, 200))
        rep = re.observe(svc, min_queries=256)
        assert isinstance(rep, DriftReport)
    # close() persisted the snapshot (persist_stats spec field)
    rep2 = re.observe_offline(min_queries=256)
    assert isinstance(rep2, DriftReport)
    assert rep2.observed_seconds == pytest.approx(rep.observed_seconds)
    # observe() with no service falls back to the offline snapshot
    rep3 = re.observe(min_queries=256)
    assert isinstance(rep3, DriftReport)
