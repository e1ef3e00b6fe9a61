"""Public dispatch for the fused multi-layer descent (Pallas | jnp | numpy).

``fused_descent`` is what the serving engine calls per batch: one op walks
the queries through the whole resident layer prefix and returns the (L, Q)
per-layer windows.  The numpy backend *is*
:func:`repro.core.descent.descend_layers` — bit-identical to the per-layer
path for every registered family.  The device backends take 64-bit keys
and queries as two 32-bit words and return, per layer, the covering entry
and a band row's window relative to its node; the host adds the entry's
int64 bases (:func:`_rebase`).  Step rows are exact; band rows are widened
by :func:`band_f32_slack` (ranges remain valid under Eq. 1 but may be
strictly wider).

A device backend serves exactly as requested or raises: a kernel failure
is never swallowed.  Only a prefix with a layer wider than the kernel's
VMEM bound goes to numpy, and :func:`fused_descent_with_backend` names
why (``"width"``).  Pallas runs in interpret mode on the CPU and compiled
everywhere else (:func:`repro.kernels.interpret_mode`).

A long-lived caller packs its prefix once (:func:`pack_prefix`), puts the
planes on the device once (:func:`upload_planes`) and passes them as
``resident=``: each batch then sends only its queries, makes one compiled
call and copies its windows back in one transfer.
"""
from __future__ import annotations

import sys

import numpy as np

from repro.spans import span

from . import ref

MAX_VMEM_ENTRIES = 4096  # the fused kernel keeps one layer plane in VMEM
# numpy twin of kernel.LANE so packing never imports jax
LANE = 128
# the sign bit of a 32-bit word: flipped, a signed compare is unsigned
SIGN = np.uint32(1 << 31)
# where a uint64's high word sits among the two uint32 words it views as
_HI = 1 if sys.byteorder == "little" else 0
# the kernel's arguments after the queries, in order
PLANES = ("kinds", "key_hi", "key_lo", "m", "delta")


def band_f32_slack(span, delta):
    """Bytes by which a device band row widens each end of its window,
    for a row whose line moved ``span = |m·(q − x1)|`` bytes from its
    node's y1 and whose half-width is ``delta``.

    The device evaluates ``mid = m·(q − x1)`` in float32 relative to the
    node, with ``q − x1`` exact in 64 bits before one rounding.  Rounding
    ``q − x1`` (at most 3 ULP), ``m`` (1) and the product (1) moves
    ``mid`` by at most 5·2^-24·span, and the sums with δ and the slack by
    2·2^-24·(span + δ) more, so ``2^-20·(span + δ)``, 16 ULP, covers the
    float32 arithmetic.  The 2 bytes cover a y1 that is not a whole byte
    (its fraction is dropped into the int64 base) and the float64
    reference's own rounding.  A node is a few MB wide in byte terms, so
    the slack is a few bytes, whatever the data's size.  Plain arithmetic,
    so the kernel evaluates the same expression on its float32 rows."""
    return 2.0 + 2.0 ** -20 * (abs(span) + abs(delta))


def split_words(keys, width: int | None = None) -> np.ndarray:
    """uint64 keys (n,) → (2, width) int32 (hi, lo) words with the sign
    bit of each flipped, so that int32 compares order them as uint64;
    columns past n repeat the last key (``width`` defaults to n)."""
    pairs = np.ascontiguousarray(keys, dtype=np.uint64).view(
        np.uint32).reshape(-1, 2)
    n = len(pairs)
    w = np.empty((2, n if width is None else width), dtype=np.uint32)
    np.bitwise_xor(pairs[:, _HI], SIGN, out=w[0, :n])
    np.bitwise_xor(pairs[:, 1 - _HI], SIGN, out=w[1, :n])
    w[:, n:] = w[:, n - 1:n]
    return w.view(np.int32)


def _pad_up(n: int, mult: int) -> int:
    return n + (-n) % mult


def _width(lay) -> int:
    return len(lay["keys"] if lay["kind"] == "step" else lay["x1"])


def prefix_gate(layers) -> str | None:
    """Why a non-empty top-down prefix cannot be packed into the fused
    kernel's planes: ``"width"`` when a layer is wider than the VMEM
    bound, else None (packable)."""
    if _pad_up(max(_width(lay) for lay in layers), LANE) > MAX_VMEM_ENTRIES:
        return "width"
    return None


def pack_prefix(layers) -> dict | None:
    """Pack a top-down resident prefix (parsed layer dicts, the
    :class:`repro.serve.IndexService` representation) into the fused
    kernel's planes: ``kinds`` (L,) and four (L, 1, P) planes, P the common
    LANE-padded width (``PLANES``), plus the host's ``bases``, (2, L·P)
    int64: each entry's window base for lo and for hi, a step piece's
    ``pos_lo``/``pos_hi``, a band node's ⌊y1⌋ for both.  Every row is
    padded with its layer's last entry, which ranks and predicts as that
    entry does.

    Returns None when the prefix is empty or :func:`prefix_gate` declines
    it — callers then serve on the numpy path.  Pure numpy: packing works
    without jax; only dispatch needs it.
    """
    L = len(layers)
    if L == 0 or prefix_gate(layers) is not None:
        return None
    P = _pad_up(max(_width(lay) for lay in layers), LANE)

    def edge(a, dtype):
        a = np.asarray(a, dtype=dtype)
        return np.pad(a, (0, P - len(a)), mode="edge")

    kinds = np.zeros(L, dtype=np.int32)
    words = np.zeros((2, L, 1, P), dtype=np.int32)
    m = np.zeros((L, 1, P), dtype=np.float32)
    delta = np.zeros((L, 1, P), dtype=np.float32)
    bases = np.zeros((2, L, P), dtype=np.int64)
    for l, lay in enumerate(layers):
        if lay["kind"] == "step":
            words[:, l, 0] = split_words(lay["keys"], P)
            bases[0, l] = edge(lay["pos_lo"], np.int64)
            bases[1, l] = edge(lay["pos_hi"], np.int64)
        else:
            kinds[l] = 1
            words[:, l, 0] = split_words(lay["x1"], P)
            m[l, 0] = edge(lay["m"], np.float32)
            delta[l, 0] = edge(lay["delta"], np.float32)
            bases[:, l] = np.floor(edge(lay["y1"], np.float64))
    return {"kinds": kinds, "key_hi": words[0], "key_lo": words[1],
            "m": m, "delta": delta, "bases": bases.reshape(2, L * P)}


def upload_planes(packed: dict, backend: str):
    """Put a :func:`pack_prefix` result's planes on the device for
    ``backend`` → ``(planes, bytes sent)``: the same keys, each kernel
    plane a device array, but for the jnp backend's ``kinds``, which stays
    on the host (it branches on it in Python), and the host's bases.  A
    serving epoch uploads once and passes ``planes`` as ``resident=``
    with every batch."""
    import jax

    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown device backend {backend!r}")
    names = PLANES[1:] if backend == "jnp" else PLANES
    planes = dict(packed)
    planes.update(zip(names, jax.device_put([packed[k] for k in names])))
    return planes, sum(packed[k].nbytes for k in names)


def _rebase(packed: dict, out: np.ndarray, nq: int):
    """The device's (3, L, Q) int32 output → float64 (L, nq) lo and hi
    byte offsets: each covering entry's int64 base plus the row's relative
    end (zero on step rows).  Every value is a whole number below 2^53,
    so float64 holds it exactly."""
    ends = packed["bases"].take(out[0, :, :nq], axis=1) \
        + out[1:, :, :nq].view(np.float32)
    return ends[0], ends[1]


def _device_descent(packed: dict, q: np.ndarray, backend: str,
                    timings: dict | None = None, resident: dict | None = None):
    """One device dispatch → float64 (L, Q) rows.

    Three spans split the host's time: ``stage`` splits the uint64
    queries into words and pads them (and uploads the planes when no
    ``resident`` copy is given); ``launch`` is the one compiled call,
    :func:`kernel.fused_descent_windows`, which takes the host queries and
    sends them itself (the jnp backend's ops run eagerly); ``collect``
    waits for the device and copies the windows back in one transfer,
    then widens them to byte offsets in its nested ``rebase`` span.
    ``timings``, when given, receives each phase's seconds, ``h2d_bytes``
    (the bytes of every host array handed to the device at the dtype
    sent) and ``wide_queries`` (queries whose key has a nonzero high
    word)."""
    from repro.kernels import interpret_mode

    from . import kernel as K

    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown device backend {backend!r}")
    nq = len(q)
    with span("airindex.descent.stage") as stage:
        wide = int(np.count_nonzero(q > 0xFFFFFFFF))
        pad = (-nq) % K.BLOCK_Q if backend == "pallas" else 0
        qw = split_words(q, nq + pad)
        sent = qw.nbytes
        if resident is None:        # a one-shot caller's planes go up too
            resident, plane_bytes = upload_planes(packed, backend)
            sent += plane_bytes
    with span("airindex.descent.launch") as launch:
        if backend == "jnp":
            out = ref.fused_descent_jnp(resident, qw)
        else:
            out = K.fused_descent_windows(
                qw, *(resident[k] for k in PLANES),
                interpret=interpret_mode())
    with span("airindex.descent.collect") as collect:
        host = np.asarray(out)          # (3, L, Q) int32 in one copy
        with span("airindex.descent.rebase") as rebase:
            lo, hi = _rebase(packed, host, nq)
    if timings is not None:
        timings.update(stage_seconds=stage.seconds,
                       launch_seconds=launch.seconds,
                       collect_seconds=collect.seconds,
                       rebase_seconds=rebase.seconds,
                       h2d_bytes=sent, wide_queries=wide)
    return lo, hi


def fused_descent_with_backend(layers, queries, *, backend: str = "pallas",
                               packed=None, resident=None,
                               timings: dict | None = None):
    """Like :func:`fused_descent` but also reports who served and why:
    ``(lo, hi, backend_used, numpy_reason)``.  ``numpy_reason`` is None
    unless a device backend was requested and numpy served the batch:
    then it is ``"width"`` (:func:`prefix_gate`).  An empty prefix or
    batch has nothing to descend and serves on numpy with no reason.  A
    device backend's own failure propagates.  ``resident`` is ``packed``
    already on the device (:func:`upload_planes`, same backend); without
    it the planes go up with the batch.  ``timings``, when given,
    receives a device dispatch's phase seconds and counts
    (:func:`_device_descent`); a batch numpy serves leaves it empty."""
    q = np.atleast_1d(np.asarray(queries, dtype=np.uint64))
    reason = None
    if backend != "numpy" and layers and len(q):
        if packed is None:
            reason = prefix_gate(layers)
            packed = pack_prefix(layers) if reason is None else None
        if reason is None:
            lo, hi = _device_descent(packed, q, backend, timings, resident)
            return lo, hi, backend, None
    lo, hi = ref.fused_descent_ref(layers, q)
    return lo, hi, "numpy", reason


def fused_descent(layers, queries, *, backend: str = "pallas", packed=None):
    """Walk ``queries`` through a resident prefix in one fused dispatch →
    ``(lo, hi)`` float64 arrays of shape (L, Q), row ``l`` = layer ``l``'s
    window per query (top-down; row L−1 feeds the disk walk).

    ``backend="numpy"`` (and every prefix the device planes cannot hold)
    is bit-identical to the per-layer
    :func:`repro.core.descent.descend_layers` walk; device backends keep
    step rows exact and widen band rows by :func:`band_f32_slack`.
    ``packed`` lets long-lived callers reuse one :func:`pack_prefix`
    result across batches; its planes go up to the device with each call
    (a serving epoch keeps them there: :func:`upload_planes`).
    """
    lo, hi, _, _ = fused_descent_with_backend(layers, queries,
                                              backend=backend, packed=packed)
    return lo, hi
