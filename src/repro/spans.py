"""Host spans at the serving path's layer boundaries.

``with span("airindex.descent") as sp: ...`` times the block with
``time.perf_counter`` and leaves the seconds in ``sp.seconds`` for the
caller to add to its counters.  When jax is already imported the span
also opens ``jax.profiler.TraceAnnotation(name)``, so a profiler trace
holds it on the host plane, on the same clock as the device's operations.
It never imports jax itself: the numpy-only path stays jax-free.  With no
profiler session an annotation costs about a microsecond, so there is no
switch.  A span records an interval and nothing else: it never waits for
the device, and it never touches shared state.
"""
from __future__ import annotations

import sys
import time


class span:
    """Context manager: one named interval of host time (see module doc)."""

    __slots__ = ("name", "seconds", "_t0", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0

    def __enter__(self) -> "span":
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        self._ann = (profiler.TraceAnnotation(self.name)
                     if profiler is not None else None)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
