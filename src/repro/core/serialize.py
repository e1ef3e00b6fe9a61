"""On-disk index format + real partial-read lookup (paper §5.6).

Layout (single index file, layers bottom-up):

    [magic u64][json_len u64][json meta][layer_1 bytes] … [layer_L bytes]

Per-layer bytes are the concatenated node records whose byte offsets are
exactly the outline positions used during tuning, so modeled read sizes
equal real read sizes:

  * step layer — stream of 16 B pieces ``(key u64, pos i64)``; a node of
    ``p`` pieces is ``16·p`` consecutive bytes (paper §4.1);
  * band layer — 40 B records ``(x1 u64, y1 f64, m f64, δ f64, rsv u64)``.

Readers fetch *ranges* (``pread``), never whole layers (except the root,
per Alg. 1), align to record boundaries, and for step layers extend by one
record to obtain the next piece's position (fence-pointer style).

**Paged layout** (``write_index(..., page_bytes=N)``): every layer offset
is aligned up to a multiple of ``page_bytes`` (gaps are file holes), so
the file is a sequence of fixed-size pages and each page belongs to
exactly one layer.  Pages are the caching unit of the serving engine's
tiered block cache (:mod:`repro.serve.index_service`); the per-layer page
table is recoverable from the meta via :func:`page_span`.  ``page_bytes=0``
(the default) keeps the original densely-packed format — readers accept
both.

Layer descent math is shared with the in-memory path via
:mod:`repro.core.descent`, so file lookups and ``lookup_batch`` agree
bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import json
import os
import zlib

import numpy as np

from .descent import descend_band_layer, descend_step_layer
from .keyset import KeyPositions
from .latency import IndexDesign
from .nodes import BandLayer, StepLayer

MAGIC = 0x41495249  # "AIRI"
_STEP_DT = np.dtype([("key", "<u8"), ("pos", "<i8")])
_BAND_DT = np.dtype([("x1", "<u8"), ("y1", "<f8"), ("m", "<f8"),
                     ("delta", "<f8"), ("rsv", "<u8")])


@dataclasses.dataclass
class LayerMeta:
    kind: str
    offset: int      # byte offset of the layer within the file
    size: int        # serialized size (== Θ_l's s(Θ_l))
    end_pos: int     # position after the layer's last prediction target
    # per-page CRC32 table (paged layouts written with checksums=True):
    # entry k covers the layer's k-th page, computed over the page's bytes
    # zero-padded to page_bytes (alignment gaps are file holes, so a
    # padded CRC equals the CRC of what a reader actually sees — including
    # the file's final, physically-short page).  None on densely-packed
    # layouts and on files written before checksums existed; readers skip
    # verification for those.
    page_crcs: list | None = None


@dataclasses.dataclass
class IndexFileMeta:
    layers: list          # bottom-up LayerMeta
    data_size: int        # extent of the data layer (for clamping)
    data_record: int      # fixed record size of the data layer (0 = varlen)
    page_bytes: int = 0   # fixed page size (0 = densely packed, unpaged)
    tune: dict | None = None   # provenance: how the index was tuned — the
    #   ``repro.api`` facade records {"spec": TuneSpec.to_dict(), "strategy",
    #   "cost", "builder_names", "profile"} so a reopened index remembers
    #   its TuneSpec and can be re-tuned when the storage profile changes

    def to_json(self) -> str:
        d = {
            "layers": [dataclasses.asdict(l) for l in self.layers],
            "data_size": self.data_size, "data_record": self.data_record,
            "page_bytes": self.page_bytes,
        }
        if self.tune is not None:
            d["tune"] = self.tune
        return json.dumps(d)

    @staticmethod
    def from_json(s: str) -> "IndexFileMeta":
        d = json.loads(s)
        return IndexFileMeta(
            layers=[LayerMeta(**l) for l in d["layers"]],
            data_size=d["data_size"], data_record=d["data_record"],
            page_bytes=d.get("page_bytes", 0), tune=d.get("tune"))


RECORD_BYTES = {"step": 16, "band": 40}


def page_span(offset: int, size: int, page_bytes: int) -> tuple[int, int]:
    """File-global page ids [first, last) covering bytes [offset, offset+size)."""
    return offset // page_bytes, -(-(offset + size) // page_bytes)


def record_aligned_range(kind: str, lo, hi, layer_size: int):
    """Byte range of a layer to fetch for predicted positions ``[lo, hi)``.

    Vectorized over queries.  Aligns down/up to record boundaries; step
    layers extend by one record so the *next* piece's position (the range
    end, fence-pointer style) is always present.  Degenerate ``hi <= lo``
    predictions still fetch one record.
    """
    rsz = RECORD_BYTES[kind]
    a = (np.maximum(lo, 0) // rsz) * rsz
    b = -(-np.asarray(hi) // rsz) * rsz + (rsz if kind == "step" else 0)
    b = np.minimum(np.maximum(b, a + rsz), layer_size)
    a = np.minimum(a, b - rsz)
    return a.astype(np.int64), b.astype(np.int64)


def page_crc(chunk: bytes, page_bytes: int) -> int:
    """CRC32 of one page as stored on disk, zero-padded to ``page_bytes``.

    Layers are page-aligned in the paged layout, so every page holds bytes
    of exactly one layer; a layer's last page is padded by the alignment
    hole (zeros) — or physically truncated at EOF, which pads to the same
    bytes.  Padding before hashing makes the CRC independent of which of
    those two forms a reader receives."""
    if len(chunk) < page_bytes:
        chunk = chunk + b"\0" * (page_bytes - len(chunk))
    return zlib.crc32(chunk) & 0xFFFFFFFF


def layer_page_crcs(blob: bytes, page_bytes: int) -> list:
    """The per-page CRC32 table of one page-aligned layer blob."""
    return [page_crc(blob[k:k + page_bytes], page_bytes)
            for k in range(0, max(len(blob), 1), page_bytes)]


def _layer_bytes(layer) -> bytes:
    if isinstance(layer, StepLayer):
        rec = np.empty(layer.n_pieces, dtype=_STEP_DT)
        rec["key"] = layer.piece_keys
        rec["pos"] = layer.piece_pos[:-1]
        return rec.tobytes()
    rec = np.empty(layer.n_nodes, dtype=_BAND_DT)
    rec["x1"] = layer.x1
    rec["y1"] = layer.y1.astype(np.float64)
    rec["m"] = layer.m
    rec["delta"] = layer.delta
    rec["rsv"] = 0
    return rec.tobytes()


def write_index(path: str, design: IndexDesign, data_record: int = 0,
                page_bytes: int = 0, tune: dict | None = None,
                checksums: bool = True) -> IndexFileMeta:
    """Serialize a design.  ``page_bytes > 0`` aligns every layer to page
    boundaries (paged layout — the serving engine's cache unit); 0 keeps
    the densely-packed layout.  ``tune`` is an optional JSON-serializable
    provenance dict recorded into the meta (see :class:`IndexFileMeta`).
    Paged layouts also record a per-page CRC32 table into each layer's
    meta (``checksums=False`` writes the pre-checksum format — what every
    file written before the table existed looks like; readers verify only
    when the table is present)."""
    metas = []
    blobs = []
    for layer in design.layers:
        b = _layer_bytes(layer)
        assert len(b) == layer.size_bytes, "serialized size must match s(Θ_l)"
        end_pos = int(layer.piece_pos[-1]) if isinstance(layer, StepLayer) \
            else int(layer.clamp_hi)
        crcs = layer_page_crcs(b, page_bytes) \
            if page_bytes > 0 and checksums else None
        metas.append(LayerMeta(kind=layer.kind, offset=0, size=len(b),
                               end_pos=end_pos, page_crcs=crcs))
        blobs.append(b)
    meta = IndexFileMeta(layers=metas, data_size=design.data.size_bytes,
                         data_record=data_record, page_bytes=page_bytes,
                         tune=tune)

    def _align(off: int) -> int:
        return off if page_bytes == 0 else -(-off // page_bytes) * page_bytes

    def _place(base: int) -> None:
        off = base
        for m, b in zip(metas, blobs):
            m.offset = _align(off)
            off = m.offset + len(b)

    hdr = meta.to_json().encode()
    base = 16 + len(hdr)
    _place(base)
    hdr = meta.to_json().encode()  # re-encode with final offsets
    # json length changes offsets only if digit counts change; fix-point it
    while 16 + len(hdr) != base:
        base = 16 + len(hdr)
        _place(base)
        hdr = meta.to_json().encode()
    with open(path, "wb") as f:
        f.write(np.asarray([MAGIC, len(hdr)], dtype="<u8").tobytes())
        f.write(hdr)
        for m, b in zip(metas, blobs):
            f.seek(m.offset)      # alignment gaps become file holes (zeros)
            f.write(b)
    return meta


def parse_meta(pread) -> IndexFileMeta:
    """Read + decode the header through any ``pread(nbytes, offset)``
    callable — the seam that lets the serving engine's fault-tolerant
    backend (retries, fault injection) own the meta read too.  Raises
    ``ValueError`` on a bad magic or an undecodable header, so a torn
    read is retryable rather than an assert."""
    head = pread(16, 0)
    if len(head) != 16:
        raise ValueError(f"bad index file: short header ({len(head)} B)")
    magic, hlen = np.frombuffer(head, dtype="<u8")
    if magic != MAGIC:
        raise ValueError(f"bad index file: magic {int(magic):#x}")
    return IndexFileMeta.from_json(pread(int(hlen), 16).decode())


def read_meta(fd: int) -> IndexFileMeta:
    """Header from an already-open raw fd — compat seam for callers that
    own their descriptor (tests, tooling); path-based callers should use
    :func:`read_meta_path`, which reads through the StorageBackend."""
    # airlint: allow[pread-seam] -- raw-fd compat seam; the caller owns the
    # descriptor and path-based internal callers use read_meta_path instead
    return parse_meta(lambda n, off: os.pread(fd, n, off))


def open_file_backend(path: str):
    """A :class:`repro.serve.FileBackend` for ``path`` (lazy import:
    serve sits above core in the layer order)."""
    from repro.serve.backend import FileBackend
    return FileBackend(path)


def read_meta_path(path: str) -> IndexFileMeta:
    """Header of the index file at ``path``, read through the
    StorageBackend seam (so CRC/fault-injection wrappers apply)."""
    be = open_file_backend(path)
    try:
        return parse_meta(be.pread)
    finally:
        be.close()


def load_index(path: str, data: KeyPositions) -> IndexDesign:
    """Deprecated shim: use ``repro.api.Index.open(path, data=data).design``.

    Delegates to the facade (which calls :func:`materialize_design`, the
    same implementation this function used to own), so results are
    bit-identical to the old behavior.
    """
    from .deprecation import warn_deprecated
    warn_deprecated(
        "repro.core.load_index(path, data) is deprecated; use "
        "repro.api.Index.open(path, data=data).design")
    from repro.api import Index
    return Index.open(path, data=data).design


def materialize_design(path: str, data: KeyPositions) -> IndexDesign:
    """Full deserialization (round-trips, re-tuning); real lookups use ranges."""
    be = open_file_backend(path)
    try:
        meta = parse_meta(be.pread)
        layers = []
        for lm in meta.layers:
            raw = be.pread(lm.size, lm.offset)
            if lm.kind == "step":
                rec = np.frombuffer(raw, dtype=_STEP_DT)
                pos = np.append(rec["pos"].astype(np.int64), lm.end_pos)
                # node grouping is not persisted; treat each piece as a node
                off = np.arange(len(rec) + 1, dtype=np.int64)
                layers.append(StepLayer(piece_keys=rec["key"].copy(),
                                        piece_pos=pos,
                                        node_piece_off=off))
            else:
                rec = np.frombuffer(raw, dtype=_BAND_DT)
                layers.append(BandLayer(
                    node_keys=rec["x1"].copy(), x1=rec["x1"].copy(),
                    y1=rec["y1"].astype(np.int64), m=rec["m"].copy(),
                    delta=rec["delta"].copy(),
                    clamp_lo=0, clamp_hi=lm.end_pos))
        return IndexDesign(layers=tuple(layers), data=data)
    finally:
        be.close()


# ---------------------------------------------------------------------------
# real partial-read lookup (Alg. 1 against the file)
# ---------------------------------------------------------------------------
def predict_from_records(kind: str, raw: bytes, queries: np.ndarray,
                         end_pos: int) -> tuple[np.ndarray, np.ndarray]:
    """Parse fetched records and run one layer of descent for a query batch
    (Alg. 1 l. 3–5) — the same :mod:`repro.core.descent` step as the
    in-memory path.  ``end_pos`` caps the last fetched step record's range
    (its fence pointer is the next record, absent at the layer end)."""
    q = np.asarray(queries, dtype=np.uint64)
    if kind == "step":
        rec = np.frombuffer(raw, dtype=_STEP_DT)
        pos = rec["pos"].astype(np.int64)
        pos_hi = np.append(pos[1:], np.int64(end_pos))
        return descend_step_layer(rec["key"], pos, pos_hi, q)
    rec = np.frombuffer(raw, dtype=_BAND_DT)
    return descend_band_layer(rec["x1"], rec["x1"], rec["y1"], rec["m"],
                              rec["delta"], q)


def record_keys(kind: str, raw: bytes) -> np.ndarray:
    """Sorted partition keys of fetched records (covering-search domain)."""
    return np.frombuffer(raw, dtype=_STEP_DT if kind == "step" else _BAND_DT)[
        "key" if kind == "step" else "x1"]


def gallop_step(kind: str, a, b):
    """Extension step for a missed window ``[a, b)`` — the window's own
    width, but never less than one record of the layer's dtype: a
    zero-width window (``b == a`` after clamping) would otherwise retry
    with the same bounds forever.  Elementwise over arrays of windows.
    Shared by :class:`SerializedIndex` and the serving engine so their
    gallop walks stay in lockstep."""
    return np.maximum(np.subtract(b, a), RECORD_BYTES[kind])


def window_misses(kind: str, raw: bytes, a: int, b: int, layer_size: int,
                  queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-query check that a fetched window ``[a, b)`` contains the true
    covering record.

    A *band* upper layer predicts a range for the exact query key, but its
    containment guarantee (Eq. 1) is established at the outline's boundary
    keys — for keys strictly between boundaries the window can land next to
    the covering record.  (Step upper layers are piecewise-constant, so
    their windows never miss.)  Misses are detectable without extra I/O:

      * left miss  — every fetched key > q and bytes exist before the
        window: the covering record is earlier;
      * right miss — every guard fails the other way (last fetched key ≤ q)
        and bytes exist after: the covering record (or its fence pointer)
        may be later.

    Callers extend the window in the indicated direction and re-check
    (galloping — doubles per round, terminates at the layer bounds).
    """
    keys = record_keys(kind, raw)
    q = np.asarray(queries, dtype=np.uint64)
    left = (keys[0] > q) & (a > 0)
    right = (keys[-1] <= q) & (b < layer_size)
    return left, right


class SerializedIndex:
    """Handle for Alg.-1 lookups against an index file with partial reads.

    Reads flow through a :class:`repro.serve.StorageBackend` (default
    :class:`~repro.serve.FileBackend`); pass ``backend_factory`` to wrap
    the file in a fault-injecting or instrumented backend.
    """

    def __init__(self, path: str, backend_factory=None):
        factory = backend_factory or open_file_backend
        self._backend = factory(path)
        self.meta = parse_meta(self._backend.pread)
        self.bytes_read = 0
        self.reads = 0
        root = self.meta.layers[-1] if self.meta.layers else None
        self._root_raw = (self._backend.pread(root.size, root.offset)
                          if root else b"")
        if root:
            self.bytes_read += root.size
            self.reads += 1

    def close(self):
        self._backend.close()

    def lookup(self, query: int) -> tuple[int, int]:
        """→ predicted [lo, hi) byte range in the data layer."""
        metas = self.meta.layers
        if not metas:
            return 0, self.meta.data_size
        q1 = np.asarray([query], dtype=np.uint64)
        lo, hi = predict_from_records(metas[-1].kind, self._root_raw, q1,
                                      metas[-1].end_pos)
        for lm in reversed(metas[:-1]):
            a, b = record_aligned_range(lm.kind, lo, hi, lm.size)
            a, b = int(a[0]), int(b[0])
            while True:
                raw = self._backend.pread(b - a, lm.offset + a)
                self.bytes_read += b - a
                self.reads += 1
                left, right = window_misses(lm.kind, raw, a, b, lm.size, q1)
                if not (left[0] or right[0]):
                    break
                # toward the covering record
                w = int(gallop_step(lm.kind, a, b))
                if left[0]:
                    a = max(a - w, 0)
                else:
                    b = min(b + w, lm.size)
            lo, hi = predict_from_records(lm.kind, raw, q1, lm.end_pos)
        lo = max(int(lo[0]), 0)
        hi = min(max(int(hi[0]), lo + 1), self.meta.data_size)
        return lo, hi


def lookup_serialized(path: str, meta_unused, queries: np.ndarray):
    idx = SerializedIndex(path)
    try:
        return np.array([idx.lookup(int(q)) for q in np.asarray(queries)],
                        dtype=np.int64)
    finally:
        idx.close()
