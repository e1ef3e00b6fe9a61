"""Pallas TPU kernel: fused affine-profile weighted row means.

One pallas_call scores a block of candidates: the (BLOCK_C, S) widths
tile and the shared (1, S) weight row live in VMEM; the kernel fuses the
affine profile ``T = ℓ + Δ·(1/B)`` with the weighted mean reduction
(multiply + row-sum on the VPU), so each candidate's Ê[T(Δ)] is produced
without materializing the profiled matrix in HBM.

Padding contract (enforced by ops.py): S padded to LANE with zero
weights — padded columns contribute nothing to either the numerator or
the weight total; C padded to BLOCK_C with arbitrary rows — padded rows
are dropped after the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_C = 8     # candidate rows per grid step (f32 sublane tile)
LANE = 128


def _score_kernel(w_ref, wt_ref, out_ref, *, ell, inv_bw):
    W = w_ref[...]                    # (BLOCK_C, S) widths
    wt = wt_ref[...]                  # (1, S) weights, zero on padding
    t = ell + W * inv_bw              # fused affine profile
    out_ref[...] = (t * wt).sum(axis=1, keepdims=True) / wt.sum()


@functools.partial(jax.jit, static_argnames=("ell", "inv_bw", "interpret"))
def affine_scores_pallas(widths, weights, *, ell: float, inv_bw: float,
                         interpret: bool = False):
    """widths (C, S) f32 — C multiple of BLOCK_C, S multiple of LANE;
    weights (S,) f32.  Returns (C,) f32 scores."""
    C, S = widths.shape
    assert C % BLOCK_C == 0 and S % LANE == 0
    grid = (C // BLOCK_C,)
    # 2-D blocks throughout: the TPU compiler tiles the last two dims by
    # (8, 128), so the weights ride as one (1, S) row and each step writes
    # a (BLOCK_C, 1) column of scores
    out = pl.pallas_call(
        functools.partial(_score_kernel, ell=ell, inv_bw=inv_bw),
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_C, S), lambda i: (i, 0)),
                  pl.BlockSpec((1, S), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((BLOCK_C, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((C, 1), jnp.float32),
        interpret=interpret,
    )(widths, weights.reshape(1, S))
    return out[:, 0]
