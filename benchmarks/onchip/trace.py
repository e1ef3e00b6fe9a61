"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Read with ``jax.profiler.ProfileData``: the device planes are those named
``/device:TPU:<n>``, their operations the events on the line named
``XLA Ops``; the benchmark's own host spans (``onchip.*``, written with
``jax.profiler.TraceAnnotation``) sit on the host plane, on the same clock.

* busy: the union of operation intervals inside the window span, per chip,
  averaged over the chips;
* kernel: the events whose name matches the kernel's pattern — their count
  and summed device time;
* breakdown: the device operations that took most time, and the longest
  idle gaps, each named by the innermost benchmark span open at its middle.

Run as a script on a trace to see its planes, lines and busiest names.
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "onchip."
WINDOW_SPAN = "onchip.window"
TOP = 10


def _load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def host_spans(pd) -> list:
    """[(name, start_ns, end_ns)] of the benchmark's spans, any thread."""
    out = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            out.extend(ev for ev in _events(line)
                       if ev[0].startswith(SPAN_PREFIX))
    return out


def device_ops(pd) -> dict:
    """{plane name: [(op name, start_ns, end_ns)]} of every chip."""
    return {plane.name: [ev for line in plane.lines if line.name == OPS_LINE
                         for ev in _events(line)]
            for plane in pd.planes if DEVICE_PLANE.match(plane.name)}


def short_name(op: str) -> str:
    """``"%copy.1 = s32[256]... copy(...)"`` → ``"copy.1"``: the trace
    names a device op by its whole HLO instruction."""
    return op.split(" = ", 1)[0].lstrip("%")


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(path, kernel_pattern: str) -> dict:
    """The device numbers of one traced window (seconds), see module doc."""
    pd = _load(path)
    spans = host_spans(pd)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one {WINDOW_SPAN} span, "
                         f"found {len(windows)}")
    w0, w1 = windows[0][1], windows[0][2]
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    kernel = re.compile(kernel_pattern)
    chips = device_ops(pd)
    if not chips:
        raise ValueError(f"{path}: no {DEVICE_PLANE.pattern} plane")
    busy, gaps = [], []
    per_op = defaultdict(float)
    k_calls, k_ns = 0, 0.0
    for ops in chips.values():
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                   if e > w0 and s < w1]
        for n, s, e in clipped:
            per_op[short_name(n)] += e - s
            if kernel.search(n):
                k_calls += 1
                k_ns += e - s
        merged = _union((s, e) for _, s, e in clipped)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps.extend((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    gaps.sort(reverse=True)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "chips": len(chips),
        "kernel_calls": k_calls,
        "kernel_s": k_ns * 1e-9,
        "device_ops": [[n, t * 1e-9] for n, t in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_span_at(inner, (s + e) / 2), (e - s) * 1e-9]
                      for _, s, e in gaps[:TOP]],
    }


def _span_at(spans, t: float) -> str:
    """The innermost (shortest) benchmark span open at ``t``."""
    open_ = [(e - s, n) for n, s, e in spans if s <= t <= e]
    return min(open_)[1] if open_ else "no span"


def describe(path) -> None:
    """Print each plane, its lines, event counts and busiest names."""
    pd = _load(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = _events(line)
            tot = defaultdict(float)
            for n, s, e in evs:
                tot[n] += e - s
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:8]
            span = (f" {evs[0][1]:.0f}..{max(e for _, _, e in evs):.0f} ns"
                    if evs else "")
            print(f"  line {line.name!r}: {len(evs)} events{span}")
            for n, t in top:
                print(f"      {t * 1e-6:12.3f} ms  {n[:100]}")


if __name__ == "__main__":
    for p in sys.argv[1:]:
        describe(p)
