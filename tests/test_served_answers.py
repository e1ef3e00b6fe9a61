"""Served answers against a plain reference, over key sets × designs × paths.

Every served answer is judged against ``np.searchsorted`` over the sorted
key array, computed here from the keys and the record size alone: the
returned ``[lo, hi)`` must hold the queried key's whole record and lie
inside the data.  Each design is written once in the paged layout and
served four ways: the whole prefix resident on the numpy and on the Pallas
descent, only the root resident (the engine always pins it) with every
other layer walked through a block cache far smaller than the index, and
the facade's ``SerializedIndex`` walk.
"""
import numpy as np
import pytest

from repro.api import Index, ServeSpec
from repro.core import (IndexDesign, KeyPositions, build_eband,
                        build_fixed_btree, build_gband, build_gstep, outline,
                        write_index)
from repro.data.datasets import sosd_like
from repro.serve import IndexService

N_KEYS = 30_000
RECORD = 16
PAGE = 4096
N_QUERIES = 500

KEY_SETS = ("books", "fb", "osm", "wiki", "gmm", "uden64")


def _gstep2(D):
    l1 = build_gstep(D, 16, 128)
    return IndexDesign(layers=(l1, build_gstep(outline(l1, D), 16, 1024)),
                       data=D)


DESIGNS = {
    "gband": lambda D: IndexDesign(layers=(build_gband(D, 4096),), data=D),
    "gstep2": _gstep2,
    "eband": lambda D: IndexDesign(layers=(build_eband(D, 4096),), data=D),
    "btree": build_fixed_btree,
}

PATHS = ("resident_numpy", "resident_pallas", "walk_small_cache", "facade")

# build_eband misses records on osm-shaped keys (ROADMAP §2, "build_eband
# on osm keys"): strict, so the fix that makes them pass must drop the mark
_EBAND_OSM = pytest.mark.xfail(
    strict=True,
    reason="build_eband's ranges miss records on osm keys (clusters between "
           "2^40 and 2^62, keys about 64 apart); ROADMAP §2 names the defect")


def _cases():
    for ks in KEY_SETS:
        for design in DESIGNS:
            for path in PATHS:
                marks = _EBAND_OSM if (ks, design) == ("osm", "eband") else ()
                yield pytest.param(ks, design, path, marks=marks,
                                   id=f"{ks}-{design}-{path}")


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """``(key set, design) -> (keys, queries, index path, layer count)``,
    each built and written once."""
    root = tmp_path_factory.mktemp("served")
    made = {}

    def get(ks, design):
        if (ks, design) not in made:
            keys = sosd_like(ks, N_KEYS, seed=3)
            d = DESIGNS[design](KeyPositions.fixed_record(keys, RECORD))
            path = str(root / f"{ks}-{design}.air")
            write_index(path, d, page_bytes=PAGE)
            q = np.random.default_rng(1).choice(keys, N_QUERIES)
            made[ks, design] = keys, q, path, d.n_layers
        return made[ks, design]
    return get


def _serve(path, q, how, n_layers):
    if how == "facade":
        with Index.open(path) as idx:
            return idx.lookup(q)
    if how == "walk_small_cache":
        spec = ServeSpec(resident_layers=0, cache_bytes=(4 * PAGE,))
    else:
        spec = ServeSpec(resident_layers=n_layers,
                         backend=how.removeprefix("resident_"))
    with IndexService(path, profile="azure_ssd", spec=spec) as svc:
        got = svc.lookup(q)
        if how == "resident_pallas":
            assert svc.stats.pallas_batches > 0, "Pallas never served a batch"
        if how == "walk_small_cache" and n_layers > 1:
            assert svc.stats.walk_rounds > 0, "no layer was walked"
    return got


@pytest.mark.parametrize("key_set,design,how", _cases())
def test_served_answer_holds_the_record(written, key_set, design, how):
    keys, q, path, n_layers = written(key_set, design)
    got = _serve(path, q, how, n_layers)
    assert got.shape == (len(q), 2)
    want_lo = np.searchsorted(keys, q).astype(np.int64) * RECORD
    lo, hi = got[:, 0], got[:, 1]
    missed = (lo > want_lo) | (hi < want_lo + RECORD)
    outside = (lo < 0) | (hi > len(keys) * RECORD)
    assert not missed.any(), \
        f"{int(missed.sum())} of {len(q)} answers miss their record"
    assert not outside.any(), \
        f"{int(outside.sum())} of {len(q)} answers leave the data"
