"""Public dispatch for the fused multi-layer descent (Pallas | jnp | numpy).

``fused_descent`` is what the serving engine calls per batch: one op walks
the queries through the whole resident layer prefix and returns the (L, Q)
per-layer windows.  The numpy backend *is*
:func:`repro.core.descent.descend_layers` — bit-identical to the per-layer
path for every registered family.  The device backends compute in
int32/float32: step rows stay exact, band rows are widened by the δ slack
of :mod:`repro.kernels.index_lookup` (ranges remain valid under Eq. 1 but
may be strictly wider).

A device backend serves exactly as requested or raises: a kernel failure
is never swallowed.  Only batches the int32 planes cannot represent go to
numpy, and :func:`fused_descent_with_backend` names why (``"width"``,
``"key_range"``, ``"query_range"``).  Pallas runs in interpret mode on the
CPU and compiled everywhere else (:func:`repro.kernels.interpret_mode`).

A long-lived caller packs its prefix once (:func:`pack_prefix`), puts the
planes on the device once (:func:`upload_planes`) and passes them as
``resident=``: each batch then sends only its queries, makes one compiled
call and copies lo and hi back in one transfer.
"""
from __future__ import annotations

import numpy as np

from repro.spans import span

from . import ref

MAX_VMEM_ENTRIES = 4096  # the fused kernel keeps one layer plane in VMEM
# numpy twins of kernel.LANE / kernel.KEY_PAD so packing never imports jax
LANE = 128
KEY_PAD = np.iinfo(np.int32).max
# device backends index with int32; KEY_PAD must stay strictly greater than
# every real key AND every query, hence the -1
_I32_LIM = 2**31 - 1
# the kernel's arguments after the queries, in order
_PLANES = ("kinds", "keys", "pos_lo", "pos_hi", "x1", "y1", "m", "delta")


def band_f32_slack(y1, m, x1) -> np.ndarray:
    """Worst-case f32 rounding of ``mid = y1 + m·(q − x1)``: a few ULP of
    |y1| plus key-quantization error |m|·ULP(x1) (same widening as
    ``index_lookup.ops.device_arrays_from_design``)."""
    return (8.0 + np.abs(np.asarray(y1, dtype=np.float64)) * 4e-6
            + np.abs(np.asarray(m, dtype=np.float64))
            * np.abs(np.asarray(x1, dtype=np.float64)) * 4e-6)


def _pad_up(n: int, mult: int) -> int:
    return n + (-n) % mult


def prefix_gate(layers) -> str | None:
    """Why a non-empty top-down prefix cannot be packed into the fused
    kernel's int32 planes: ``"width"`` when a layer is wider than the VMEM
    bound, ``"key_range"`` when a key or position reaches 2**31 - 1, else
    None (packable)."""
    widths = [len(lay["keys"] if lay["kind"] == "step" else lay["x1"])
              for lay in layers]
    if _pad_up(max(widths), LANE) > MAX_VMEM_ENTRIES:
        return "width"
    for lay in layers:
        cols = (("keys", "pos_hi") if lay["kind"] == "step" else ("x1",))
        if any(int(lay[c].max(initial=0)) >= _I32_LIM for c in cols):
            return "key_range"
    return None


def pack_prefix(layers) -> dict | None:
    """Pack a top-down resident prefix (parsed layer dicts, the
    :class:`repro.serve.IndexService` representation) into the fused
    kernel's planes: ``kinds`` (L,) and seven (L, 1, P) planes, P the
    common LANE-padded width.

    Returns None when the prefix is empty or :func:`prefix_gate` declines
    it — callers then serve on the numpy path.  Pure numpy: packing works
    without jax; only dispatch needs it.
    """
    L = len(layers)
    if L == 0 or prefix_gate(layers) is not None:
        return None
    widths = [len(lay["keys"] if lay["kind"] == "step" else lay["x1"])
              for lay in layers]
    P = _pad_up(max(widths), LANE)
    kinds = np.zeros(L, dtype=np.int32)
    keys = np.full((L, 1, P), KEY_PAD, dtype=np.int32)
    pos_lo = np.zeros((L, 1, P), dtype=np.int32)
    pos_hi = np.zeros((L, 1, P), dtype=np.int32)
    x1 = np.zeros((L, 1, P), dtype=np.float32)
    y1 = np.zeros((L, 1, P), dtype=np.float32)
    m = np.zeros((L, 1, P), dtype=np.float32)
    delta = np.zeros((L, 1, P), dtype=np.float32)
    for l, lay in enumerate(layers):
        n = widths[l]
        if lay["kind"] == "step":
            keys[l, 0, :n] = lay["keys"]
            pos_lo[l, 0, :n] = lay["pos_lo"]
            pos_hi[l, 0, :n] = lay["pos_hi"]
        else:
            kinds[l] = 1
            keys[l, 0, :n] = lay["x1"]
            x1[l, 0, :n] = lay["x1"].astype(np.float32)
            y1[l, 0, :n] = np.asarray(lay["y1"], dtype=np.float32)
            m[l, 0, :n] = np.asarray(lay["m"], dtype=np.float32)
            delta[l, 0, :n] = (np.asarray(lay["delta"], dtype=np.float64)
                               + band_f32_slack(lay["y1"], lay["m"],
                                                lay["x1"])).astype(np.float32)
    return {"kinds": kinds, "keys": keys, "pos_lo": pos_lo, "pos_hi": pos_hi,
            "x1": x1, "y1": y1, "m": m, "delta": delta}


def upload_planes(packed: dict, backend: str):
    """Put a :func:`pack_prefix` result on the device for ``backend`` →
    ``(planes, bytes sent)``: the same keys, each a device array, but for
    the jnp backend's ``kinds``, which stays on the host (it branches on it
    in Python).  A serving epoch uploads once and passes ``planes`` as
    ``resident=`` with every batch."""
    import jax

    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown device backend {backend!r}")
    names = _PLANES[1:] if backend == "jnp" else _PLANES
    planes = dict(packed)
    planes.update(zip(names, jax.device_put([packed[k] for k in names])))
    return planes, sum(packed[k].nbytes for k in names)


def _device_descent(packed: dict, q: np.ndarray, backend: str,
                    timings: dict | None = None, resident: dict | None = None):
    """One device dispatch → float64 (L, Q) rows.

    Three spans split the host's time: ``stage`` casts and pads the
    queries (and uploads the planes when no ``resident`` copy is given);
    ``launch`` is the one compiled call,
    :func:`kernel.fused_descent_windows`, which takes the host queries and
    sends them itself (the jnp backend's ops run eagerly); ``collect``
    waits for the device, copies lo and hi back together and widens them
    to float64.  ``timings``, when given, receives each phase's seconds
    and ``h2d_bytes``, the bytes of every host array handed to the
    device at the dtype sent."""
    import jax

    from repro.kernels import interpret_mode

    from . import kernel as K

    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown device backend {backend!r}")
    nq = len(q)
    with span("airindex.descent.stage") as stage:
        qh = q.astype(np.int32)     # every query is below 2**31 - 1 (gated)
        pad = (-nq) % K.BLOCK_Q if backend == "pallas" else 0
        if pad:
            qh = np.concatenate([qh, np.full(pad, qh[-1], np.int32)])
        sent = qh.nbytes
        if resident is None:        # a one-shot caller's planes go up too
            resident, plane_bytes = upload_planes(packed, backend)
            sent += plane_bytes
    with span("airindex.descent.launch") as launch:
        if backend == "jnp":
            out = ref.fused_descent_jnp(resident, qh)
        else:
            out = K.fused_descent_windows(
                qh, *(resident[k] for k in _PLANES),
                interpret=interpret_mode())
    with span("airindex.descent.collect") as collect:
        # (2, L, Q) int32 in one copy; the jnp backend's (lo, hi) pair
        host = np.asarray(out) if backend == "pallas" else jax.device_get(out)
        lo = np.asarray(host[0][:, :nq], dtype=np.float64)
        hi = np.asarray(host[1][:, :nq], dtype=np.float64)
    if timings is not None:
        timings.update(stage_seconds=stage.seconds,
                       launch_seconds=launch.seconds,
                       collect_seconds=collect.seconds,
                       h2d_bytes=sent)
    return lo, hi


def fused_descent_with_backend(layers, queries, *, backend: str = "pallas",
                               packed=None, resident=None,
                               timings: dict | None = None):
    """Like :func:`fused_descent` but also reports who served and why:
    ``(lo, hi, backend_used, numpy_reason)``.  ``numpy_reason`` is None
    unless a device backend was requested and numpy served the batch:
    then it is ``"width"`` or ``"key_range"`` (:func:`prefix_gate`) or
    ``"query_range"`` (a query reaches 2**31 - 1).  An empty prefix or
    batch has nothing to descend and serves on numpy with no reason.  A
    device backend's own failure propagates.  ``resident`` is ``packed``
    already on the device (:func:`upload_planes`, same backend); without
    it the planes go up with the batch.  ``timings``, when given,
    receives a device dispatch's phase seconds and ``h2d_bytes``
    (:func:`_device_descent`); a batch numpy serves leaves it empty."""
    q = np.atleast_1d(np.asarray(queries, dtype=np.uint64))
    reason = None
    if backend != "numpy" and layers and len(q):
        if packed is None:
            reason = prefix_gate(layers)
            packed = pack_prefix(layers) if reason is None else None
        if reason is None and int(q.max()) >= _I32_LIM:
            reason = "query_range"
        if reason is None:
            lo, hi = _device_descent(packed, q, backend, timings, resident)
            return lo, hi, backend, None
    lo, hi = ref.fused_descent_ref(layers, q)
    return lo, hi, "numpy", reason


def fused_descent(layers, queries, *, backend: str = "pallas", packed=None):
    """Walk ``queries`` through a resident prefix in one fused dispatch →
    ``(lo, hi)`` float64 arrays of shape (L, Q), row ``l`` = layer ``l``'s
    window per query (top-down; row L−1 feeds the disk walk).

    ``backend="numpy"`` (and every batch the device planes cannot
    represent) is bit-identical to the per-layer
    :func:`repro.core.descent.descend_layers` walk; device backends keep
    step rows exact and widen band rows by the f32 δ slack.  ``packed``
    lets long-lived callers reuse one :func:`pack_prefix` result across
    batches; its planes go up to the device with each call (a serving
    epoch keeps them there: :func:`upload_planes`).
    """
    lo, hi, _, _ = fused_descent_with_backend(layers, queries,
                                              backend=backend, packed=packed)
    return lo, hi
