"""References for the fused descent kernel.

Two oracles with different contracts:

  * :func:`fused_descent_ref` — the float64 ground truth.  Delegates to
    :func:`repro.core.descent.descend_layers`, i.e. literally the per-layer
    path the serving engine used before fusion; every numpy-backend result
    of ``ops.fused_descent`` must be bit-identical to it.
  * :func:`fused_descent_jnp` — pure-jnp f32 oracle over the *packed*
    planes, mirroring the kernel's semantics (int32 keys, f32 band math on
    the slack-widened δ, per-layer ``hi ≥ lo+1`` on band rows).  This is
    both the kernel's test oracle and the ``backend="jnp"`` path; it may
    differ from the kernel by a few ULP of the f32 band midpoint (FMA
    contraction), never more.
"""
from __future__ import annotations

import numpy as np

from repro.core.descent import descend_layers


def fused_descent_ref(layers, queries: np.ndarray):
    """Float64 (L, Q) lo/hi rows — the bit-exactness reference."""
    return descend_layers(layers, np.asarray(queries, dtype=np.uint64))


def fused_descent_jnp(planes: dict, queries):
    """jnp f32 oracle over packed planes → (lo, hi) int32 of shape (L, Q).

    ``planes`` is the dict built by ``ops.pack_prefix`` (numpy or jnp
    arrays); ``queries`` int32, in-range per the packer's guards.
    """
    import jax.numpy as jnp

    q = jnp.asarray(queries, jnp.int32)
    qf = q.astype(jnp.float32)
    kinds = np.asarray(planes["kinds"])
    rows = {k: jnp.asarray(planes[k])[:, 0] for k in
            ("keys", "pos_lo", "pos_hi", "x1", "y1", "m", "delta")}
    keys = rows["keys"]
    los, his = [], []
    for l in range(keys.shape[0]):
        # rank − 1 == searchsorted-right − 1: the covering partition
        i = jnp.clip(jnp.searchsorted(keys[l], q, side="right") - 1, 0, None)
        if kinds[l] == 1:
            x1 = rows["x1"][l][i]
            y1 = rows["y1"][l][i]
            m = rows["m"][l][i]
            d = rows["delta"][l][i]
            mid = y1 + m * (qf - x1)
            lo = jnp.floor(mid - d).astype(jnp.int32)
            hi = jnp.maximum(jnp.ceil(mid + d).astype(jnp.int32), lo + 1)
        else:
            lo = rows["pos_lo"][l][i]
            hi = rows["pos_hi"][l][i]
        los.append(lo)
        his.append(hi)
    return jnp.stack(los), jnp.stack(his)
