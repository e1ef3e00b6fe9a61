"""The open-loop dispatcher: one loop that serves whatever is due.

Each pass takes every request that is due and not yet served, up to
``max_batch``, and hands their keys to ``lookup`` as one batch, padded to a
multiple of ``batch_multiple`` with the last key (the program compiles one
set of programs per padded size, so every size the loop can send is warmed
in set-up).  When nothing is due it sleeps until the next due time.  A
request's latency runs from its due time to the return of the call that
answered it.
"""
from __future__ import annotations

import contextlib
import time
import traceback

import numpy as np


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def serve_window(lookup, keys, due, idx, *, seconds: float, drain: bool,
                 max_batch: int, batch_multiple: int,
                 spans: bool = False) -> dict:
    """Drive ``lookup`` with the schedule ``(due, idx)`` for ``seconds``.

    ``drain``: requests due inside the window are served and timed to
    their answer even after it closes; otherwise the window ends at
    ``seconds`` and the backlog stays queued.  Returns per-request
    ``dispatched``/``done`` times (seconds from the window's start, NaN
    where never served), ``answers`` (n, 2), per-call ``calls`` rows
    (start, end, lookups), ``failed`` lookups, the first ``errors`` and
    the measured ``window_s``.
    """
    n = len(due)
    dispatched = np.full(n, np.nan)
    done = np.full(n, np.nan)
    answers = np.zeros((n, 2), dtype=np.int64)
    calls, errors = [], []
    failed = 0
    clock = time.perf_counter
    i = 0
    with _span("onchip.window", spans):
        t0 = clock()
        while i < n:
            now = clock() - t0
            if not drain and now >= seconds:
                break
            j = min(int(np.searchsorted(due, now, side="right")),
                    i + max_batch)
            if j <= i:
                wait = float(due[i]) - now
                if not drain:
                    wait = min(wait, seconds - now)
                with _span("onchip.idle", spans):
                    time.sleep(max(wait, 0.0))
                continue
            q = keys[idx[i:j]]
            pad = (-len(q)) % batch_multiple
            if pad:
                q = np.concatenate([q, np.repeat(q[-1:], pad)])
            ts = clock()
            try:
                with _span("onchip.lookup", spans):
                    out = lookup(q)
            except Exception:       # the server loop keeps serving
                out = None
                failed += j - i
                if len(errors) < 3:
                    errors.append(traceback.format_exc(limit=4))
            te = clock()
            dispatched[i:j] = ts - t0
            if out is not None:
                done[i:j] = te - t0
                answers[i:j] = out[:j - i]
            calls.append((ts - t0, te - t0, j - i))
            i = j
        window_s = max(clock() - t0, seconds) if drain else seconds
    return {"dispatched": dispatched, "done": done, "answers": answers,
            "calls": np.asarray(calls, dtype=np.float64).reshape(-1, 3),
            "failed": failed, "errors": errors, "served": i,
            "window_s": window_s}
