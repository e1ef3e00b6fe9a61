"""Pallas TPU kernel: fused multi-layer index descent (whole Alg. 1 prefix).

The per-layer ``index_lookup`` kernels pay one dispatch per resident layer;
this kernel walks a batch of keys through the *entire* resident prefix in a
single ``pallas_call``.  The trick is the structural fact exploited by
:func:`repro.core.descent.descend_layers`: every index layer covers the full
key domain, so layer ``l``'s prediction is a function of the query key alone
— the (L, Q) prediction rows are independent and can be evaluated by one
fused grid instead of L chained dispatches.

Grid ``(n_q_blocks, L)`` — the layer dimension is innermost, and TPU grids
are executed sequentially per core, so the Pallas pipeline double-buffers
the per-layer parameter planes (the ``flash_attention`` idiom: while layer
``l`` computes, layer ``l+1``'s (1, P) plane blocks are already streaming
into the second VMEM buffer).  The query block is cast to f32 once into a
VMEM scratch that persists across the layer iterations of one query cell.

Per-layer branching is data-driven: a per-layer function-type vector
``kinds`` (0 = step, 1 = band) selects between the two prediction forms
with a ``jnp.where`` — both are computed densely (compare-count rank +
one-hot masked row-sums, the TPU-native formulation of ``index_lookup``),
which keeps the kernel free of data-dependent control flow.

Plane layout (packed by ``ops.pack_prefix``, one row per layer, padded to a
common LANE-multiple width P):

  kinds            (L,)       int32  0 step / 1 band; whole vector in SMEM
  keys             (L, 1, P)  int32  partition keys (KEY_PAD beyond the layer)
  pos_lo, pos_hi   (L, 1, P)  int32  step piece ranges    (zeros on band rows)
  x1, y1, m, delta (L, 1, P)  f32    band line params, δ pre-widened by the
                                     f32 slack              (zeros on step rows)

Queries arrive as one (1, Q) row; outputs are (L, 1, Q) int32 ``lo``/``hi``:
row ``l`` is layer ``l``'s window for every query; row ``L-1`` feeds the
on-disk walk.  The unit middle axis is what the TPU compiler needs: it
tiles the last two dims of every block by (8, 128) unless a dim spans the
whole array, so a layer's block is ``(None, 1, P)`` (layer axis squeezed,
a full-extent unit row, lane-aligned P) and a query block is
``(1, BLOCK_Q)``; a plain (L, P) plane with a (1, P) block is refused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 256
LANE = 128
KEY_PAD = jnp.iinfo(jnp.int32).max  # padding key: never ≤ any query
KERNEL_NAME = "fused_descent_pallas"


def _rank(keys, q):
    """#{keys ≤ q} per query; keys (P,), q (Bq,) → (Bq,) int32."""
    cmp = (keys[None, :] <= q[:, None]).astype(jnp.int32)   # (Bq, P)
    return cmp.sum(axis=1)


def _gather(values, idx, P):
    """Exact gather via one-hot masked row-sum; values (P,), idx (Bq,)."""
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], P), 1)
              == idx[:, None])
    zero = values.dtype.type(0)
    return jnp.sum(jnp.where(onehot, values[None, :], zero), axis=1)


def _fused_kernel(kind_ref, q_ref, keys_ref, pos_lo_ref, pos_hi_ref,
                  x1_ref, y1_ref, m_ref, d_ref, lo_ref, hi_ref, qf_ref):
    l = pl.program_id(1)
    q = q_ref[0]                                # (Bq,) int32

    @pl.when(l == 0)
    def _stage_queries():                       # f32 cast once per q-cell;
        qf_ref[...] = q_ref[...].astype(jnp.float32)  # reused by band layers

    keys = keys_ref[0]                          # (P,) this layer's plane
    P = keys.shape[0]
    i = jnp.maximum(_rank(keys, q) - 1, 0)      # covering partition per query

    # step form: piece i predicts [pos_lo[i], pos_hi[i])
    slo = _gather(pos_lo_ref[0], i, P)
    shi = _gather(pos_hi_ref[0], i, P)

    # band form: node i's line, evaluated at the (pre-staged) f32 query
    x1 = _gather(x1_ref[0], i, P)
    y1 = _gather(y1_ref[0], i, P)
    m = _gather(m_ref[0], i, P)
    d = _gather(d_ref[0], i, P)
    mid = y1 + m * (qf_ref[0] - x1)
    blo = jnp.floor(mid - d).astype(jnp.int32)
    bhi = jnp.maximum(jnp.ceil(mid + d).astype(jnp.int32), blo + 1)

    is_band = kind_ref[l] == 1
    lo_ref[0] = jnp.where(is_band, blo, slo)
    hi_ref[0] = jnp.where(is_band, bhi, shi)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_descent_pallas(queries, kinds, keys, pos_lo, pos_hi, x1, y1, m,
                         delta, *, interpret=False):
    """queries (1, Q) int32, Q multiple of BLOCK_Q; kinds (L,) int32;
    planes (L, 1, P), P multiple of LANE → (lo, hi) int32 of shape
    (L, 1, Q)."""
    Q = queries.shape[1]
    L, _, P = keys.shape
    assert Q % BLOCK_Q == 0 and P % LANE == 0 and L >= 1
    grid = (Q // BLOCK_Q, L)      # layer innermost: planes double-buffer
    kspec = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole (L,) vector
    qspec = pl.BlockSpec((1, BLOCK_Q), lambda iq, l: (0, iq))
    pspec = pl.BlockSpec((None, 1, P), lambda iq, l: (l, 0, 0))
    ospec = pl.BlockSpec((None, 1, BLOCK_Q), lambda iq, l: (l, 0, iq))
    return pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[kspec, qspec] + [pspec] * 7,
        out_specs=[ospec, ospec],
        out_shape=[jax.ShapeDtypeStruct((L, 1, Q), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((1, BLOCK_Q), jnp.float32)],  # staged q
        interpret=interpret,
        # names the custom call in the compiled HLO (and so the kernel's
        # events in a profiler trace), whatever the wrapper is called
        name=KERNEL_NAME,
    )(kinds, queries, keys, pos_lo, pos_hi, x1, y1, m, delta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_descent_windows(queries, kinds, keys, pos_lo, pos_hi, x1, y1, m,
                          delta, *, interpret=False):
    """One batch in one compiled call: queries (Q,) int32, Q a multiple of
    BLOCK_Q, and the planes of :func:`fused_descent_pallas` → (2, L, Q)
    int32, ``lo`` stacked over ``hi``, so both come back in one copy.  The
    (1, Q) reshape and the output assembly compile into the kernel's
    program: one executable per (L, P, Q)."""
    lo, hi = fused_descent_pallas(queries[None, :], kinds, keys, pos_lo,
                                  pos_hi, x1, y1, m, delta,
                                  interpret=interpret)
    return jnp.stack([lo[:, 0], hi[:, 0]])
