"""Smoke run of the serving path on one TPU chip.

    python chip_smoke.py [--n-keys N] [--seed S]

Builds the SOSD ``wiki_ts`` shape from ``--seed`` (8-byte keys with 8-byte
payloads, so 16-byte records), then serves it through the entry points a
user calls, ``Index.open(path).serve(spec=ServeSpec(backend="pallas"))``:

  A. the tuned path: ``Index.tune(..., "azure_ssd")`` → build → save →
     open → serve with every layer resident;
  B. the multi-layer prefix: ``demo_serving_design`` (step ← band ← step)
     saved paged, served with as many layers resident as the device
     planes hold (at least 2, so the kernel's layer grid runs with L ≥ 2).

Every batch is checked against ``np.searchsorted`` on the keys: the
returned byte range must hold the key's whole record and lie within the
data.  Every batch must also have been served by the compiled Pallas
kernel: not jnp, not numpy, not the interpreter.  Any miss fails the run.

The script runs on a TPU or not at all: it exits non-zero, naming the
platform JAX found, when the first device is not a TPU.  The times it
prints are smoke timings of one run, not benchmark numbers.  The last line
of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

SOSD_KEYS = 200_000_000    # SOSD's wiki_ts has 200M keys
RECORD = 16                # 8-byte key + 8-byte payload, as in SOSD
BATCHES, BATCH = 8, 4096   # lookups served per phase: 8 batches of 4096


def _device():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind}); nothing was served")
    return dev, len(jax.devices())


def _say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


_BACKENDS = ("pallas", "interpret", "jnp", "numpy")


def _check_batch(svc, keys, data_size, q) -> float:
    """Serve one batch and return its seconds; fail on a range that misses
    the reference record or on a batch not served by compiled Pallas."""
    before = {k: getattr(svc.stats, f"{k}_batches") for k in _BACKENDS}
    t0 = time.perf_counter()
    got = svc.lookup(q)
    dt = time.perf_counter() - t0
    want_lo = np.searchsorted(keys, q).astype(np.int64) * RECORD
    bad = ((got[:, 0] > want_lo) | (got[:, 1] < want_lo + RECORD)
           | (got[:, 0] < 0) | (got[:, 1] > data_size))
    if bad.any():
        i = int(np.argmax(bad))
        raise AssertionError(
            f"{int(bad.sum())} of {len(q)} ranges miss the reference: "
            f"key {int(q[i])} got [{got[i, 0]}, {got[i, 1]}), record at "
            f"{want_lo[i]}, data_size {data_size}")
    moved = {k: getattr(svc.stats, f"{k}_batches") - before[k]
             for k in _BACKENDS}
    if moved != {"pallas": 1, "interpret": 0, "jnp": 0, "numpy": 0}:
        raise AssertionError(f"batch not served by compiled Pallas: "
                             f"{moved}")
    return dt


def _serve_phase(name, index, keys, data_size, queries, resident):
    """Open ``index`` with ``resident`` layers pinned on the Pallas
    backend, serve every batch, and report what ran."""
    from repro.api import ServeSpec
    spec = ServeSpec(backend="pallas", resident_layers=resident)
    with index.serve(spec=spec) as svc:
        planes = svc.device_planes
        if planes is None:
            raise AssertionError(f"phase {name}: the resident prefix does "
                                 f"not pack for the device")
        L, _, P = planes["key_hi"].shape
        times = [_check_batch(svc, keys, data_size, q) for q in queries]
        s = svc.stats
        _say(f"phase {name}: {L} resident layer(s), padded width P={P}, "
             f"{len(queries)} batches x {len(queries[0])} keys")
        _say(f"phase {name}: batches by backend: pallas={s.pallas_batches} "
             f"(interpret={s.interpret_batches}) jnp={s.jnp_batches} "
             f"numpy={s.numpy_batches} (width={s.numpy_width_batches})")
        _say(f"phase {name}: every range holds its searchsorted record")
        _say(f"phase {name} smoke timing, not a benchmark: first batch "
             f"(includes compile) {times[0]:.3f} s, later batches "
             f"{np.mean(times[1:]) if len(times) > 1 else float('nan'):.4f}"
             f" s each")


def _packs(index, resident: int) -> bool:
    """Whether the top ``resident`` layers of ``index`` pack for the
    device (opening a service reads them; nothing is served)."""
    from repro.api import ServeSpec
    with index.serve(spec=ServeSpec(backend="pallas",
                                    resident_layers=resident)) as svc:
        return svc.device_active


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[1])
    ap.add_argument("--n-keys", type=int, default=50_000_000,
                    help="keys drawn before de-duplication")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev, count = _device()
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.api import Index, TuneSpec
    from repro.compile_cache import enable_compile_cache
    from repro.core import KeyPositions
    from repro.data.datasets import sosd_like
    from repro.serve.index_service import demo_serving_design

    _say(f"device {dev.platform} {dev.device_kind} (count {count}); "
         f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    keys = sosd_like("wiki", args.n_keys, args.seed)
    D = KeyPositions.fixed_record(keys, RECORD)
    _say(f"data: SOSD wiki_ts shape, {len(keys)} unique keys from "
         f"N={args.n_keys} drawn (SOSD has {SOSD_KEYS}: cut "
         f"{SOSD_KEYS / args.n_keys:g}x so host tuning fits the run), "
         f"key array {keys.nbytes} host bytes, max key {int(keys.max())}, "
         f"{time.perf_counter() - t0:.1f} s")
    rng = np.random.default_rng(args.seed)
    queries = [rng.choice(keys, BATCH) for _ in range(BATCHES)]

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as work:
        t0 = time.perf_counter()
        path_a = os.path.join(work, "tuned.air")
        Index.tune(D, "azure_ssd", TuneSpec(page_bytes=4096)).build() \
            .save(path_a)
        tuned = Index.open(path_a)
        metas = tuned.file_meta.layers
        _say(f"phase A: tuned design {[lm.kind for lm in metas]} (layer "
             f"bytes {[lm.size for lm in metas]}), tune+build+save "
             f"{time.perf_counter() - t0:.1f} s")
        _serve_phase("A", tuned, keys, D.size_bytes, queries, len(metas))

        path_b = os.path.join(work, "demo.air")
        Index.from_design(demo_serving_design(D),
                          spec=TuneSpec(page_bytes=4096),
                          profile="azure_ssd").save(path_b)
        demo = Index.open(path_b)
        metas = demo.file_meta.layers
        _say(f"phase B: design {[lm.kind for lm in metas]} "
             f"(layer bytes {[lm.size for lm in metas]})")
        resident = next((n for n in range(len(metas), 0, -1)
                         if _packs(demo, n)), 0)
        if resident < 2:
            raise AssertionError(f"phase B: only {resident} layer(s) pack "
                                 f"for the device; the layer grid needs 2")
        _serve_phase("B", demo, keys, D.size_bytes, queries, resident)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
