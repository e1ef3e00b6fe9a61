"""Data-pipeline example: AirIndex-backed random-access token store.

Builds a packed token store on the local filesystem, PROFILES the real
disk (T(Δ), §3.2), tunes the sample index with AirTune, and compares the
measured fetch path against a naive full-shard read.

Run:  PYTHONPATH=src python examples/data_pipeline.py
"""
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, "src")

from repro.data.store import ShardedTokenStore, write_token_store
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

root = tempfile.mkdtemp(prefix="airindex-data-")
rng = np.random.default_rng(0)
print("== writing 4000 variable-length samples ==")
samples = [rng.integers(0, 50_000, int(rng.integers(100, 2000)))
           .astype(np.int32) for _ in range(4000)]
write_token_store(root, samples)
total = sum(len(s) * 4 for s in samples)
print(f"store: {total / 1e6:.1f} MB packed tokens")

print("== profiling local disk + tuning the sample index ==")
store = ShardedTokenStore(root, profile="measure")
print(f"index: {store.tune.design.describe()}")
print(f"modeled lookup: {store.tune.cost * 1e6:.1f}us "
      f"(vs full-shard read {store.profile(total) * 1e6:.1f}us)")

print("== random-access fetches (real preads) ==")
ids = rng.integers(0, len(samples), 500)
t0 = time.perf_counter()
for i in ids:
    got = store.get(int(i))
    assert np.array_equal(got, samples[int(i)])
dt = (time.perf_counter() - t0) / len(ids)
print(f"500 verified fetches, {dt * 1e6:.0f}us each, "
      f"{store.index.bytes_read / max(store.index.reads, 1):.0f}B/index-read")

print("== deterministic replay (fault-tolerance contract) ==")
a = next(store.batch_iterator(8, 256, seed=3, start_step=5))
b = None
it = store.batch_iterator(8, 256, seed=3)
for _ in range(6):
    b = next(it)
assert np.array_equal(a["tokens"], b["tokens"])
print("replay from step 5 matches sequential iteration: OK")
store.close()
