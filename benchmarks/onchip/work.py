"""Work of one fused-descent call that any implementation must do.

The kernel (``repro.kernels.fused_descent``) walks Q queries through L
resident layers and returns L windows per query.  What the algorithm
needs, whatever computes it:

* bytes: the Q int32 queries in, the 2·L·Q int32 window ends out, and each
  layer's parameters at their unpadded width — a step entry is its int32
  key and int32 position (8 bytes; the next entry's position closes the
  piece), a band entry its four f32 line parameters x1, y1, m, δ
  (16 bytes);
* operations: a rank search per query and layer, ⌈log2(n_l + 1)⌉
  compares, plus for a band layer the line's five: q − x1, ·m, +y1, −δ, +δ.

The dense compare-count the kernel does today (Q·P compares per layer) is
deliberately not what is counted: the roofline share then reads the same
whatever implements the search.
"""
from __future__ import annotations

import math

STEP_ENTRY_BYTES = 8
BAND_ENTRY_BYTES = 16
QUERY_BYTES = 4
WINDOW_END_BYTES = 4
BAND_LINE_OPS = 5


def call_bytes(layers, q: int) -> int:
    """``layers``: [(kind, n_entries)] of the resident prefix; ``q``:
    queries in the call."""
    params = sum(n * (BAND_ENTRY_BYTES if kind == "band" else STEP_ENTRY_BYTES)
                 for kind, n in layers)
    return q * QUERY_BYTES + 2 * len(layers) * q * WINDOW_END_BYTES + params


def call_ops(layers, q: int) -> int:
    per_query = sum(math.ceil(math.log2(n + 1))
                    + (BAND_LINE_OPS if kind == "band" else 0)
                    for kind, n in layers)
    return q * per_query


def least_seconds(layers, q: int, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for one call, and which bound
    sets it (``"hbm"`` or ``"compute"``)."""
    t_mem = call_bytes(layers, q) / peaks["hbm_bytes_per_s"]
    t_ops = call_ops(layers, q) / peaks["flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "compute")
