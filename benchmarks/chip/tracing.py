"""The profiler's trace of a few steady seconds, reduced to intervals.

A traced run starts JAX's profiler just before its window (the Python
tracer off, so that only the harness's own annotations and the runtime's
events mark the host), turns the harness's annotations on once the window
has run long enough to be steady, and stops the profiler a few seconds
later.  ``parse`` reads the
``.xplane.pb`` it wrote with ``jax.profiler.ProfileData`` and keeps:

* ``ops``: every operation on the device's "XLA Ops" line, as (start, end,
  name, module) in nanoseconds, ``module`` being the program (XLA module)
  whose execution encloses it;
* ``host``: the harness's annotations (``engine.tick``, ``prefill:<n>``,
  ``step:<n>``, ``generator``, ``wait``) as (start, end, name);
* ``window``: from the first annotation's start to the last one's end,
  so the ramp before the annotations were turned on is left out.

Device and host events share the trace's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

ANNOTATIONS = ("engine.tick", "prefill:", "step:", "generator", "wait")
LOOKBACK = 64

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Trace:
    ops: List[Tuple[float, float, str, str]]
    host: List[Tuple[float, float, str]]
    window: Interval

    def __post_init__(self) -> None:
        self._starts = [s for s, _, _ in self.host]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self) -> List[Interval]:
        return union(clip([(s, e) for s, e, _, _ in self.ops],
                          *self.window))

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy_intervals()) * 1e-9

    def ops_named(self, needles: Sequence[str]
                  ) -> List[Tuple[float, float, str, str]]:
        """Operations in the window whose HLO instruction name (the text
        before " = ") holds one of ``needles``."""
        lo, hi = self.window
        return [o for o in self.ops if o[0] >= lo and o[1] <= hi
                and any(n in o[2].split(" = ")[0] for n in needles)]

    def enclosing(self, t: float, prefix: str = "") -> Optional[str]:
        """Name of the innermost annotation whose name starts with
        ``prefix`` and that holds time ``t``: the latest-starting one.
        Annotations nest only a few deep, so looking back over the last
        ``LOOKBACK`` starts finds it."""
        j = bisect.bisect_right(self._starts, t) - 1
        for k in range(j, max(-1, j - LOOKBACK), -1):
            s, e, name = self.host[k]
            if e >= t and name.startswith(prefix):
                return name
        return None


class Tracer:
    """Starts the profiler before the window (starting it takes seconds,
    which inside an open loop would build a backlog), turns the probe's
    annotations on ``steady_s`` seconds into the window and stops the
    profiler ``duration`` seconds after that; writes under a temporary
    directory it removes once parsed.  ``host_window`` is the host-clock
    span in which the annotations were on: the part of the trace that is
    read."""

    def __init__(self, steady_s: float, duration: float) -> None:
        self.steady_s, self.duration = steady_s, duration
        self.dir = tempfile.mkdtemp(prefix="chip_trace_")
        self.running = False
        self.host_window: Optional[Tuple[float, float]] = None
        self._t_on: Optional[float] = None
        self.stop_s = 0.0           # host seconds stop_trace took

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.running = True

    def poll(self, elapsed: float, probe) -> None:
        if not self.running:
            return
        if self._t_on is None and elapsed >= self.steady_s:
            probe.annotate = True
            self._t_on = time.perf_counter()
        if elapsed >= self.steady_s + self.duration:
            self.stop(probe)

    def stop(self, probe) -> None:
        if not self.running:
            return
        import jax

        probe.annotate = False
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t_stop
        self.running = False
        self.host_window = (self._t_on if self._t_on is not None
                            else t_stop, t_stop)

    def parse(self) -> Optional[Trace]:
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            return parse_xplane(files[0]) if files else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def parse_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Tuple[float, float, str, str]] = []
    host: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            modules: List[Tuple[float, float, str]] = []
            plane_ops: List[Tuple[float, float, str]] = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events]
                elif line.name == "XLA Ops":
                    plane_ops = [(e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in line.events]
            ops.extend(_attach_modules(plane_ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATIONS):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    ops.sort()
    host.sort()
    if host:
        window = (host[0][0], max(e for _, e, _ in host))
    elif ops:
        window = (ops[0][0], ops[-1][1])
    else:
        window = (0.0, 0.0)
    return Trace(ops, host, window)


def _attach_modules(ops, modules):
    """(start, end, name, module) with the module whose run holds each
    operation (by its start)."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    out = []
    for s, e, name in ops:
        j = bisect.bisect_right(starts, s) - 1
        mod = (modules[j][2] if j >= 0 and modules[j][1] >= s else "")
        out.append((s, e, name, mod.split("(")[0]))
    return out


def op_key(name: str, module: str) -> str:
    """A short, stable name for one device operation: its program and its
    HLO instruction without the instance number (a Mosaic kernel's
    instruction carries the kernel's name)."""
    instr = name.split(" = ")[0].lstrip("%")
    return f"{module}/{instr.split('.')[0]}"


def breakdown(trace: Trace, top: int = 10) -> Dict[str, List]:
    """The device operations that took most time, and the idle gaps by
    what the host was doing (the innermost annotation over the gap's
    middle), each list the ``top`` largest, in seconds."""
    lo, hi = trace.window
    per_op: Dict[str, float] = {}
    for s, e, name, mod in trace.ops:
        if s >= lo and e <= hi:
            key = op_key(name, mod)
            per_op[key] = per_op.get(key, 0.0) + (e - s) * 1e-9
    busy = trace.busy_intervals()
    gaps = []
    prev = lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    per_gap: Dict[str, float] = {}
    for s, e in gaps:
        label = trace.enclosing((s + e) / 2, "") or "outside annotations"
        label = label.split(":")[0]
        per_gap[label] = per_gap.get(label, 0.0) + (e - s) * 1e-9
    return {
        "device_ops": sorted(([k, v] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in per_gap.items()),
                            key=lambda kv: -kv[1])[:top],
    }
