"""Spans at the serving path's layer boundaries (``repro.spans``): the
counters they feed nest as the spans do, the bytes handed to the device
match a count by hand, a profiler trace holds the spans nested on the
host's clock beside the caller's own, and the numpy path never imports
jax.  The design has two resident layers (descended by the fused kernel,
interpreted on the CPU) above one layer walked on disk."""
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import numpy as np
import pytest

from repro.api import ServeSpec
from repro.core import KeyPositions, write_index
from repro.serve.index_service import IndexService, demo_serving_design
from repro.spans import span

SRC = Path(__file__).resolve().parents[1] / "src"
# ragged batch sizes: the kernel pads each to a multiple of its BLOCK_Q
BATCHES = (300, 1, 257)
PHASES = ("airindex.descent.stage", "airindex.descent.launch",
          "airindex.descent.collect")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A step <- band <- step index over keys below 2**30 (device-packable);
    with ``resident_layers=2`` the band and the root are resident and the
    bottom step layer is walked through the block cache."""
    rng = np.random.default_rng(17)
    keys = np.unique(rng.integers(1, 2**30, 40_000).astype(np.uint64))
    D = KeyPositions.fixed_record(keys, 16)
    path = str(tmp_path_factory.mktemp("spans") / "index.air")
    write_index(path, demo_serving_design(D), page_bytes=1024)
    batches = [rng.choice(D.keys, n) for n in BATCHES]
    return path, batches


def _device_service(path):
    return IndexService(path, profile=None,
                        spec=ServeSpec(resident_layers=2, backend="pallas",
                                       cache_bytes=(16 << 10,)))


def test_span_times_its_block():
    with span("test.sleep") as sp:
        time.sleep(0.01)
    assert sp.name == "test.sleep" and sp.seconds >= 0.01


def test_counters_nest_as_the_spans_do(served):
    path, batches = served
    with _device_service(path) as svc:
        assert svc.device_active and len(svc.meta.layers) == 3
        for b in batches:
            svc.lookup(b)
        s = svc.stats
    assert s.pallas_batches == len(batches)
    phases = (s.descent_stage_seconds, s.descent_launch_seconds,
              s.descent_collect_seconds)
    assert all(p > 0 for p in phases)
    assert sum(phases) <= s.descent_seconds <= s.lookup_seconds
    assert 0 < s.rebase_seconds <= s.descent_collect_seconds
    assert 0 < s.walk_fetch_seconds <= s.walk_seconds <= s.lookup_seconds
    assert s.descent_seconds + s.walk_seconds <= s.lookup_seconds
    assert s.walk_windows > 0
    # the lookup span is the wall the per-lookup reservoir records
    assert s.lookup_seconds == pytest.approx(
        sum(w for _, w in s.lookup_samples))


def test_h2d_bytes_match_a_count_by_hand(served):
    from repro.kernels.fused_descent.kernel import BLOCK_Q

    path, batches = served
    with _device_service(path) as svc:
        planes = svc.device_planes
        for b in batches:
            svc.lookup(b)
        sent = svc.stats.h2d_bytes
    from repro.kernels.fused_descent import PLANES

    L, _, P = planes["key_hi"].shape
    assert L == 2 and planes["kinds"].dtype == np.int32
    # kinds (L,) int32 and four (L, 1, P) planes of 4-byte words; the
    # int64 bases stay on the host
    plane_bytes = 4 * L + 4 * 4 * L * P
    assert plane_bytes == sum(planes[k].nbytes for k in PLANES)
    padded = sum(-(-n // BLOCK_Q) * BLOCK_Q for n in BATCHES)
    # the planes go up once, when the epoch opens; a batch sends its
    # queries as two int32 words, padded to the kernel's block
    assert sent == plane_bytes + 8 * padded


def test_numpy_backend_counts_no_device_phase(served):
    path, batches = served
    with IndexService(path, profile=None,
                      spec=ServeSpec(resident_layers=2)) as svc:
        for b in batches:
            svc.lookup(b)
        s = svc.stats
    assert s.numpy_batches == len(batches)
    assert 0 < s.descent_seconds <= s.lookup_seconds
    assert s.h2d_bytes == 0
    assert s.descent_stage_seconds == s.descent_launch_seconds \
        == s.descent_collect_seconds == 0.0
    assert s.walk_windows > 0


def _host_spans(xplane: str) -> dict:
    """{name: [(start_ns, end_ns)]} of the host's ``airindex.*`` and
    ``onchip.*`` events in a profiler trace."""
    from jax.profiler import ProfileData
    out: dict = {}
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("airindex.", "onchip.")):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return out


def _inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_profiler_trace_holds_the_spans_nested(served, tmp_path):
    import jax

    path, batches = served
    with _device_service(path) as svc:
        svc.lookup(batches[0])          # compile outside the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("onchip.lookup"):
                svc.lookup(batches[0])
        finally:
            jax.profiler.stop_trace()
    xplanes = sorted(tmp_path.rglob("*.xplane.pb"))
    assert xplanes
    spans = _host_spans(str(xplanes[-1]))
    for name in ("onchip.lookup", "airindex.lookup", "airindex.descent",
                 "airindex.walk") + PHASES:
        assert len(spans.get(name, ())) == 1, (name, spans)
    (call,), (lookup,) = spans["onchip.lookup"], spans["airindex.lookup"]
    (descent,), (walk,) = spans["airindex.descent"], spans["airindex.walk"]
    assert _inside(lookup, call)
    assert _inside(descent, lookup) and _inside(walk, lookup)
    assert descent[1] <= walk[0]
    phases = [spans[p][0] for p in PHASES]
    assert all(_inside(p, descent) for p in phases)
    assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))
    (rebase,) = spans["airindex.descent.rebase"]
    assert _inside(rebase, spans["airindex.descent.collect"][0])
    fetches = spans["airindex.walk.fetch"]
    assert fetches and all(_inside(f, walk) for f in fetches)


def test_numpy_lookup_never_imports_jax(served):
    path, batches = served
    code = textwrap.dedent(f"""
        import sys
        import numpy as np
        from repro.api import ServeSpec
        from repro.serve.index_service import IndexService
        q = np.array({batches[0][:50].tolist()!r}, dtype=np.uint64)
        with IndexService({path!r}, profile=None,
                          spec=ServeSpec(resident_layers=2)) as svc:
            svc.lookup(q)
            assert svc.stats.walk_windows > 0
        assert "jax" not in sys.modules, "the numpy path imported jax"
    """)
    env = dict(os.environ, PYTHONPATH=str(SRC))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
