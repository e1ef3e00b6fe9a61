"""Pallas TPU kernel: fused multi-layer index descent (whole Alg. 1 prefix).

This kernel walks a batch of keys through the *entire* resident prefix in a
single ``pallas_call``.  The trick is the structural fact exploited by
:func:`repro.core.descent.descend_layers`: every index layer covers the full
key domain, so layer ``l``'s prediction is a function of the query key alone
— the (L, Q) prediction rows are independent and can be evaluated by one
fused grid instead of L chained dispatches.

Grid ``(n_q_blocks, L)`` — the layer dimension is innermost, and TPU grids
are executed sequentially per core, so the Pallas pipeline double-buffers
the per-layer parameter planes: while layer ``l`` computes, layer
``l+1``'s (1, P) plane blocks are already streaming into the second VMEM
buffer.

Keys are 64-bit and the chip computes in 32-bit words, so every key and
query travels as two int32 words (hi, lo), each with its sign bit flipped:
a signed compare of flipped words is the unsigned compare of the words, so
the rank counts ``key ≤ q`` in uint64 order.  Per-layer branching is
data-driven: a per-layer function-type vector ``kinds`` (0 = step, 1 =
band) selects between the two prediction forms with a ``jnp.where`` —
both are computed densely (compare-count rank + one-hot masked row-sums),
which keeps the kernel free of data-dependent control flow.

Plane layout (packed by ``ops.pack_prefix``, one row per layer, padded to a
common LANE-multiple width P with the layer's last entry):

  kinds            (L,)       int32  0 step / 1 band; whole vector in SMEM
  key_hi, key_lo   (L, 1, P)  int32  flipped words of the partition keys
                                     (a band node's key is its x1)
  m, delta         (L, 1, P)  f32    band slope and half-width (zeros on
                                     step rows)

Queries arrive as one (2, Q) block of flipped (hi, lo) words.  The kernel
returns, per layer and query, the covering entry ``idx`` (int32, counted
in the flattened (L·P) planes: ``l·P`` plus the entry's place in its row)
and, on band rows, the window's ends relative to the node's ``y1``
(:func:`band_window`, float32 integers; zeros on step rows).  The host
widens them to byte offsets with the entry's int64 bases
(``ops._rebase``), so no absolute offset ever passes through 32 bits.
Every output is (L, 1, Q).  The unit middle axis is what the TPU compiler
needs: it tiles the last two dims of every block by (8, 128) unless a dim
spans the whole array, so a layer's block is ``(None, 1, P)`` (layer axis
squeezed, a full-extent unit row, lane-aligned P); a plain (L, P) plane
with a (1, P) block is refused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import band_f32_slack

BLOCK_Q = 256
LANE = 128
KERNEL_NAME = "fused_descent_pallas"
_TWO32 = 4294967296.0


def rank_words(key_hi, key_lo, q_hi, q_lo):
    """#{keys ≤ q} in unsigned (hi, lo) order over flipped words; keys
    (P,), queries (Bq,) → (Bq,) int32."""
    kh, kl = key_hi[None, :], key_lo[None, :]
    qh, ql = q_hi[:, None], q_lo[:, None]
    le = (kh < qh) | ((kh == qh) & (kl <= ql))       # (Bq, P)
    return le.astype(jnp.int32).sum(axis=1)


def _u32_f32(w):
    """The unsigned value of int32 word ``w`` in float32 (one rounding of
    at most one ULP of the value, plus one more above 2^31)."""
    f = w.astype(jnp.float32)
    return jnp.where(w < 0, f + _TWO32, f)


def band_window(q_hi, q_lo, x_hi, x_lo, m, delta):
    """A band row's window relative to its node's y1, as float32 integers
    ``(floor(mid − w), ceil(mid + w))``: ``mid = m·(q − x1)`` with the key
    difference taken exactly on the flipped words (a borrow out of the low
    word) and rounded to float32 only after, and ``w = δ +
    ops.band_f32_slack(|mid|, δ)``."""
    d_lo = q_lo - x_lo                               # wraps: exact mod 2^32
    borrow = (q_lo < x_lo).astype(jnp.int32)
    d_hi = q_hi - x_hi - borrow
    dx = _u32_f32(d_hi) * _TWO32 + _u32_f32(d_lo)
    mid = m * dx
    w = delta + band_f32_slack(mid, delta)
    return jnp.floor(mid - w), jnp.ceil(mid + w)


def _gather(values, idx, P):
    """Exact gather via one-hot masked row-sum; values (P,), idx (Bq,)."""
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], P), 1)
              == idx[:, None])
    zero = values.dtype.type(0)
    return jnp.sum(jnp.where(onehot, values[None, :], zero), axis=1)


def _fused_kernel(kind_ref, q_ref, khi_ref, klo_ref, m_ref, d_ref,
                  idx_ref, lo_ref, hi_ref):
    l = pl.program_id(1)
    q_hi, q_lo = q_ref[0], q_ref[1]             # (Bq,) flipped words
    key_hi, key_lo = khi_ref[0], klo_ref[0]     # (P,) this layer's plane
    P = key_hi.shape[0]
    # covering entry per query; edge padding makes a pad as good as the
    # layer's last entry
    i = jnp.maximum(rank_words(key_hi, key_lo, q_hi, q_lo) - 1, 0)
    lo, hi = band_window(q_hi, q_lo, _gather(key_hi, i, P),
                         _gather(key_lo, i, P), _gather(m_ref[0], i, P),
                         _gather(d_ref[0], i, P))
    is_band = kind_ref[l] == 1
    idx_ref[0] = i + l * P
    lo_ref[0] = jnp.where(is_band, lo, 0.0)
    hi_ref[0] = jnp.where(is_band, hi, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_descent_pallas(queries, kinds, key_hi, key_lo, m, delta, *,
                         interpret=False):
    """queries (2, Q) int32 flipped words, Q a multiple of BLOCK_Q; kinds
    (L,) int32; planes (L, 1, P), P a multiple of LANE → (idx, lo, hi) of
    shape (L, 1, Q): int32, float32, float32."""
    Q = queries.shape[1]
    L, _, P = key_hi.shape
    assert Q % BLOCK_Q == 0 and P % LANE == 0 and L >= 1
    grid = (Q // BLOCK_Q, L)      # layer innermost: planes double-buffer
    kspec = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole (L,) vector
    qspec = pl.BlockSpec((2, BLOCK_Q), lambda iq, l: (0, iq))
    pspec = pl.BlockSpec((None, 1, P), lambda iq, l: (l, 0, 0))
    ospec = pl.BlockSpec((None, 1, BLOCK_Q), lambda iq, l: (l, 0, iq))
    return pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[kspec, qspec] + [pspec] * 4,
        out_specs=[ospec] * 3,
        out_shape=[jax.ShapeDtypeStruct((L, 1, Q), jnp.int32)]
        + [jax.ShapeDtypeStruct((L, 1, Q), jnp.float32)] * 2,
        interpret=interpret,
        # names the custom call in the compiled HLO (and so the kernel's
        # events in a profiler trace), whatever the wrapper is called
        name=KERNEL_NAME,
    )(kinds, queries, key_hi, key_lo, m, delta)


def stack_windows(idx, lo, hi):
    """(idx, lo, hi) rows → one (3, L, Q) int32 array, the float ends
    carried bit for bit, so the host copies them back in one transfer."""
    as_i32 = functools.partial(jax.lax.bitcast_convert_type,
                               new_dtype=jnp.int32)
    return jnp.stack([idx, as_i32(lo), as_i32(hi)])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_descent_windows(queries, kinds, key_hi, key_lo, m, delta, *,
                          interpret=False):
    """One batch in one compiled call: the arguments of
    :func:`fused_descent_pallas` → (3, L, Q) int32 (:func:`stack_windows`).
    The output assembly compiles into the kernel's program: one executable
    per (L, P, Q)."""
    idx, lo, hi = fused_descent_pallas(queries, kinds, key_hi, key_lo, m,
                                       delta, interpret=interpret)
    return stack_windows(idx[:, 0], lo[:, 0], hi[:, 0])
