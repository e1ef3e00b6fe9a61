"""Public dispatch for batched candidate scoring (Pallas | jnp | numpy).

``candidate_scores`` is what the sweep engine calls: it folds the storage
profile into affine coefficients when possible and runs the requested
backend; non-affine profiles land on the bit-exact numpy evaluator, and a
device backend's failure propagates.  Device backends compute in float32
— they rank candidates, they never produce the exact Eq. (6) costs.
"""
from __future__ import annotations

import functools

import numpy as np

from repro.core.latency import batched_mean_read_costs
from repro.core.storage import affine_coefficients

from . import ref


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths)


@functools.lru_cache(maxsize=None)
def _jitted_jnp(ell: float, inv_bw: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def score(W, wt):
        t = ell + W * inv_bw
        return (t * wt[None, :]).sum(axis=1) / wt.sum()
    return score


def affine_candidate_scores(widths, weights, ell: float, inv_bw: float, *,
                            backend: str = "numpy") -> np.ndarray:
    """Batched ``Ê[T(Δ)]`` under an affine tier, on the chosen backend."""
    if backend == "numpy":
        return ref.affine_scores_ref(widths, weights, ell, inv_bw)
    import jax.numpy as jnp
    W = np.asarray(widths, dtype=np.float32)
    wt = np.asarray(weights, dtype=np.float32)
    if backend == "jnp":
        out = _jitted_jnp(float(ell), float(inv_bw))(jnp.asarray(W),
                                                     jnp.asarray(wt))
        return np.asarray(out, dtype=np.float64)
    if backend == "pallas":
        from repro.kernels import interpret_mode

        from .kernel import BLOCK_C, LANE, affine_scores_pallas
        C = W.shape[0]
        Wp = _pad_to(_pad_to(W, LANE, 1), BLOCK_C, 0)
        wtp = _pad_to(wt, LANE, 0)          # zero-weight padding columns
        out = affine_scores_pallas(jnp.asarray(Wp), jnp.asarray(wtp),
                                   ell=float(ell), inv_bw=float(inv_bw),
                                   interpret=interpret_mode())
        return np.asarray(out, dtype=np.float64)[:C]
    raise ValueError(f"unknown backend {backend!r}")


def candidate_scores(widths, weights, profile, *,
                     backend: str = "pallas") -> np.ndarray:
    """Score a (C, S) widths matrix under ``profile`` → (C,) float64.

    Runs the requested backend.  Non-affine-representable profiles go
    straight to numpy — the device closed form only exists for
    ``T(Δ) = ℓ + Δ/B`` tiers.
    """
    if backend != "numpy":
        co = affine_coefficients(profile)
        if co is not None:
            return affine_candidate_scores(widths, weights, *co,
                                           backend=backend)
    return batched_mean_read_costs(widths, weights, profile)
