"""References for the fused descent kernel.

Two oracles with different contracts:

  * :func:`fused_descent_ref` — the float64 ground truth.  Delegates to
    :func:`repro.core.descent.descend_layers`, i.e. literally the per-layer
    path the serving engine used before fusion; every numpy-backend result
    of ``ops.fused_descent`` must be bit-identical to it.
  * :func:`fused_descent_jnp` — pure-jnp oracle over the *packed* planes,
    mirroring the kernel's semantics (the two-word rank, the band window
    relative to its node on the exact key difference, the same (3, L, Q)
    output).  This is both the kernel's test oracle and the
    ``backend="jnp"`` path; it may differ from the kernel by a few ULP of
    the float32 band midpoint (FMA contraction), never more.
"""
from __future__ import annotations

import numpy as np

from repro.core.descent import descend_layers


def fused_descent_ref(layers, queries: np.ndarray):
    """Float64 (L, Q) lo/hi rows — the bit-exactness reference."""
    return descend_layers(layers, np.asarray(queries, dtype=np.uint64))


def fused_descent_jnp(planes: dict, queries):
    """jnp oracle over packed planes → (3, L, Q) int32, the kernel's
    ``fused_descent_windows`` output: covering entry in the flattened
    (L·P) planes, then the band ends relative to the node as float32 bits
    (zeros on step rows).

    ``planes`` is the dict built by ``ops.pack_prefix`` (numpy or jnp
    arrays); ``queries`` the (2, Q) int32 words of ``ops.split_words``.
    """
    import jax.numpy as jnp

    from .kernel import band_window, rank_words, stack_windows

    q = jnp.asarray(queries, jnp.int32)
    kinds = np.asarray(planes["kinds"])
    rows = {k: jnp.asarray(planes[k])[:, 0]
            for k in ("key_hi", "key_lo", "m", "delta")}
    P = rows["key_hi"].shape[1]
    idx, los, his = [], [], []
    for l in range(len(kinds)):
        kh, kl = rows["key_hi"][l], rows["key_lo"][l]
        i = jnp.maximum(rank_words(kh, kl, q[0], q[1]) - 1, 0)
        idx.append(i + l * P)
        if kinds[l] == 1:
            lo, hi = band_window(q[0], q[1], kh[i], kl[i], rows["m"][l][i],
                                 rows["delta"][l][i])
        else:
            lo = hi = jnp.zeros(q.shape[1], jnp.float32)
        los.append(lo)
        his.append(hi)
    return stack_windows(jnp.stack(idx), jnp.stack(los), jnp.stack(his))
