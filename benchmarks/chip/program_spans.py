"""Arithmetic over the program's own spans for the ``program_span`` readers.

The program (``repro.runtime.spans``) records its Engine and backend
phases while JAX's profiler runs, on ``time.perf_counter``, the harness's
clock.  A reader keeps the spans that end within the traced window's
in-window ticks, from the first tick's start to the last one's end
(``run.ticks``): every span but ``engine.queue`` lies inside a tick, and a
queue wait that began before the window and ended inside it is one of the
window's waits.  Every function returns None where there is nothing to
read: a program that records no spans (one older than them), no span of
the name in the window, or a ring that dropped records ending inside it.
"""
from __future__ import annotations

import bisect
from typing import List, Optional

from chip.harness import nearest_rank

try:
    from repro.runtime import spans as _spans
except ImportError:         # the program predates its spans
    _spans = None


def snapshot():
    """The program's span ring, or None where it has none."""
    return _spans.snapshot() if _spans is not None else None


def in_window(run) -> Optional[List]:
    """The program's span records ending inside the run's ticks, or
    None."""
    snap = snapshot()
    if snap is None or not run.ticks:
        return None
    lo = min(t.t0 for t in run.ticks)
    hi = max(t.t1 for t in run.ticks)
    if not snap.covers(lo):
        return None
    return [r for r in snap.records if lo <= r.t1 <= hi]


def durations_s(run, name: str) -> Optional[List[float]]:
    recs = in_window(run)
    if recs is None:
        return None
    out = [r.t1 - r.t0 for r in recs if r.name == name]
    return out or None


def mean_ms(run, name: str) -> Optional[float]:
    d = durations_s(run, name)
    return None if d is None else 1e3 * sum(d) / len(d)


def p95_ms(run, name: str) -> Optional[float]:
    d = durations_s(run, name)
    return None if d is None else 1e3 * nearest_rank(d, 95)


def engine_self_ms(run) -> Optional[float]:
    """Mean ms per ``engine.iter`` less the harness probe's spans inside
    it (``run.spans``: each backend ``prefill`` and ``step`` call, the
    routing of a multi-workload backend and any phase without a span of
    its own included)."""
    recs = in_window(run)
    iters = [r for r in recs or () if r.name == "engine.iter"]
    if not iters:
        return None
    calls = sorted((s.t0, s.t1) for s in run.spans)
    starts = [t0 for t0, _ in calls]
    own = 0.0
    for it in iters:
        own += it.t1 - it.t0
        k = bisect.bisect_left(starts, it.t0)
        while k < len(calls) and calls[k][1] <= it.t1:
            own -= calls[k][1] - calls[k][0]
            k += 1
    return 1e3 * own / len(iters)


def fill_pct(run) -> Optional[float]:
    """100 x rows active / rows computed over the ``backend.step`` spans."""
    recs = in_window(run)
    steps = [r for r in recs or () if r.name == "backend.step"]
    computed = sum(r.info["computed"] for r in steps)
    if computed <= 0:
        return None
    return 100.0 * sum(r.info["active"] for r in steps) / computed
