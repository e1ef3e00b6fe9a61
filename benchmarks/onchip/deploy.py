"""Stand up a configuration: key set from the seed, its design built with
the registered builders, saved, opened and served through the entry points
a user calls (``Index.from_design(...).save`` → ``Index.open(path).serve``).

A configuration file (``configs/<name>.json``) names the data (``keys``,
``n_keys`` and the generator's knobs, ``record_bytes``), the design, and the serving
knobs (``serve``: a ``ServeSpec`` dict; ``max_batch``; ``batch_multiple``).
The design is either ``{"layers": [{"family", "lam", "p"}, ...]}``, bottom
layer first, each built on the outline of the one below, or
``{"builder": "module:function"}``, a function of the key-position
collection that returns the whole design.
"""
from __future__ import annotations

import gc
import importlib

import numpy as np


def make_keys(config: dict, seed: int) -> np.ndarray:
    """Sorted unique uint64 keys of the configuration's data set."""
    import keys
    return keys.uniform(config, seed)


def build_design(config: dict, D):
    """The configuration's design over key positions ``D``."""
    from repro.core import IndexDesign
    from repro.core.builders import LayerBuilder
    from repro.core.nodes import outline
    spec = config["design"]
    if "builder" in spec:
        mod, fn = spec["builder"].split(":")
        return getattr(importlib.import_module(mod), fn)(D)
    layers, cur = [], D
    for lay in spec["layers"]:
        layer = LayerBuilder(lay["family"], float(lay["lam"]),
                             int(lay.get("p", 16)))(cur)
        layers.append(layer)
        cur = outline(layer, cur)
    return IndexDesign(layers=tuple(layers), data=D)


def open_service(config: dict, keys: np.ndarray, path: str):
    """Build, save and open the index over ``keys`` → ``(service,
    layer sizes in bytes, resident prefix as [(kind, entries)])``."""
    from repro.api import Index, ServeSpec, TuneSpec
    from repro.core import KeyPositions
    D = KeyPositions.fixed_record(keys, int(config["record_bytes"]))
    design = build_design(config, D)
    sizes = [int(lay.size_bytes) for lay in design.layers]
    Index.from_design(design, spec=TuneSpec(
        page_bytes=int(config["page_bytes"]))).save(path)
    del design, D
    svc = Index.open(path).serve(spec=ServeSpec.from_dict(config["serve"]))
    return svc, sizes, resident_prefix(svc)


def resident_prefix(svc) -> list:
    """[(kind, entries)] of the layers the service holds resident."""
    from repro.core.serialize import RECORD_BYTES
    metas = svc.meta.layers
    n_res = min(max(int(svc.spec.resident_layers), 1), len(metas))
    return [(lm.kind, lm.size // RECORD_BYTES[lm.kind])
            for lm in metas[len(metas) - n_res:]]


def padded(n: int, multiple: int) -> int:
    return n + (-n) % multiple


def warm_up(svc, keys: np.ndarray, config: dict, seed: int) -> list:
    """One lookup at every batch size the dispatcher can send (multiples
    of ``batch_multiple`` up to ``max_batch``), so every program the window
    runs is compiled or loaded here; then the heap set-up built is frozen.
    Returns the sizes warmed."""
    rng = np.random.default_rng([int(seed), 0x3A53])
    m, top = int(config["batch_multiple"]), int(config["max_batch"])
    sizes = list(range(m, padded(top, m) + 1, m))
    for n in sizes:
        svc.lookup(keys[rng.integers(0, len(keys), n)])
    # Everything set-up allocated (JAX's modules, the service) lives for
    # the whole run, and a full collection that walks it all takes about
    # 100 ms; frozen, as a Python server freezes its heap once loaded, a
    # collection walks only what serving allocates.
    gc.collect()
    gc.freeze()
    return sizes
