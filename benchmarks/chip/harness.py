"""Drive one cell once: set-up, a measured window, the check, the result.

The harness is driven by files found by name:

* ``BENCHMARK.json`` (checkout root): which end-to-end and per-layer
  metrics a cell reports, with their units;
* ``cells/<cell>.json``: the configuration and the traffic (traffic.py);
* ``configs/<config>.json``: the model, its precision, its check limit,
  and ``system``, the module under ``systems/`` that builds it;
* ``metrics/<metric>.py``, or ``metrics/<base>.py`` for the name before
  its first dot: the per-layer metric's reader, ``read(run)`` returning a
  number or None;
* ``peaks.json``: the chip's peaks by ``device_kind``.

It drives ``Engine.tick()`` itself, in this one thread, with the
generator, and stamps every request on the host clock after each tick.
An open loop times a request from when it was due; a closed loop from
when it was sent.  After the window nothing more is sent and the engine
drains for at most the cell's ``drain_s``; a request unfinished then is
missing, with latency drain end minus due time, and never counts as
failed.  ``failed`` counts only requests that raised or whose output the
check rejected.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import time
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class SetupError(RuntimeError):
    """The run cannot be made as asked (no chip, an unknown name)."""


# --------------------------------------------------------------- files
def load_json(*parts: str) -> Dict:
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SetupError(f"missing file {path}") from e


def benchmark() -> Dict:
    return load_json(ROOT, "BENCHMARK.json")


def workload_entry(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SetupError(f"workload {name!r} is not in BENCHMARK.json")


def metrics_for(bench: Dict, section: str, cell: str) -> List[Dict]:
    """The metrics of ``section`` that this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def metric_reader_path(name: str) -> str:
    """``metrics/<name>.py``, else ``metrics/<base>.py`` for the base name
    before the first dot: ``device_idle.table2`` and ``device_idle.kanffn``
    share ``device_idle.py``, while ``mfu.table2`` has a reader of its own."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            return path
    raise SetupError(f"no reader metrics/{name}.py or metrics/"
                     f"{name.split('.')[0]}.py for per-layer metric {name!r}")


def load_metric_reader(name: str) -> Callable:
    path = metric_reader_path(name)
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_system(config: Dict):
    return importlib.import_module(f"chip.systems.{config['system']}")


def peaks_for(kind: str) -> Dict:
    table = load_json(HERE, "peaks.json")["kinds"]
    if kind not in table:
        raise SetupError(f"device kind {kind!r} has no peaks in peaks.json "
                         f"(known: {sorted(table)})")
    return table[kind]


# ------------------------------------------------------------- records
@dataclasses.dataclass
class Record:
    """One request as the client saw it (host-clock seconds)."""

    index: int                  # position in the traffic stream
    start: float                # due (open loop) or sent (closed loop)
    stamps: List[float] = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    req: object = None          # the engine's Request, once done


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    info: object = None


class Probe:
    """Wraps the engine's backend without changing it: forwards every
    attribute, times ``prefill`` and ``step`` (and names them in the
    profiler's trace when one runs), and notes which requests they
    touched so the harness can stamp them after the tick."""

    def __init__(self, inner) -> None:
        self._inner = inner
        self.touched: List[object] = []
        self.spans: List[Span] = []
        self.annotate = False

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _call(self, kind: str, label: str, info, fn, *args):
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(label):
                t0 = time.perf_counter()
                out = fn(*args)
                t1 = time.perf_counter()
        else:
            t0 = time.perf_counter()
            out = fn(*args)
            t1 = time.perf_counter()
        self.spans.append(Span(kind, t0, t1, info))
        return out

    def prefill(self, state, slot, req):
        n = len(req.prompt)
        out = self._call("prefill", f"prefill:{n}", (req.workload, n),
                         self._inner.prefill, state, slot, req)
        self.touched.append(req)
        return out

    def step(self, state, slot_req):
        active = [r for r in slot_req if r is not None]
        # (workload, position of the token each request feeds)
        info = [(r.workload, len(r.prompt) + len(r.generated) - 1)
                for r in active]
        out = self._call("step", f"step:{len(slot_req)}", info,
                         self._inner.step, state, slot_req)
        self.touched.extend(active)
        return out


class CompileCounter:
    """Counts programs JAX lowers (one per new shape, cache hit or not)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self) -> None:
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.n += 1


# -------------------------------------------------------------- window
@dataclasses.dataclass
class Window:
    t0: float
    seconds: float
    drain_end: float
    loop: str
    records: List[Record]
    lateness: List[float]
    tick_spans: List[Span]
    trace_t: Optional[tuple]      # (start, stop) host clock of the trace
    errors: List[str]
    compiles: int
    ticks: int


class Driver:
    """Runs the window on a built system (see ``systems/``).

    Two things keep the host's own bookkeeping from stalling the window.
    When the engine is idle, the driver hands its finished requests back
    through ``Engine.run_until_done`` (the engine otherwise keeps every
    request it ever took), at most every ``RELEASE_S``.  And every
    ``FREEZE_S`` it moves what is alive into the collector's permanent
    generation (``gc.freeze``), so that the harness's growing records are
    not rescanned by every full collection: unchecked, full collections
    over ~10^6 records paused the window for up to 130 ms each (CPU and
    chip runs).
    """

    RELEASE_S = 0.25
    FREEZE_S = 1.0

    def __init__(self, system, cell: Dict, seconds: float, tracer=None,
                 compile_counter: Optional[CompileCounter] = None) -> None:
        self.sys, self.cell, self.seconds = system, cell, seconds
        self.engine, self.probe = system.engine, system.probe
        self.tracer = tracer
        self.cc = compile_counter
        self.recs: Dict[int, Record] = {}
        self.outstanding = 0
        self.newly_done: List[Record] = []
        self.tick_spans: List[Span] = []
        self.errors: List[str] = []
        self.ticks = 0
        self._released = self._frozen = 0.0

    def _submit(self, index: int, start: float) -> None:
        rid = self.sys.submit(index, start)
        self.recs[rid] = Record(index, start)
        self.outstanding += 1

    def _span(self, name: str):
        """A profiler annotation while a trace runs, else nothing."""
        if self.probe.annotate:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def _tick(self) -> None:
        t0 = time.perf_counter()
        with self._span("engine.tick"):
            self.engine.tick()
        now = time.perf_counter()
        self.tick_spans.append(Span("tick", t0, now))
        self.ticks += 1
        for req in self.probe.touched:
            rec = self.recs.get(req.rid)
            if rec is None:         # sent before this window
                continue
            n_out = (len(req.generated) if req.output is None else 1)
            while len(rec.stamps) < n_out:
                rec.stamps.append(now)
            if req.done and rec.done is None:
                rec.done, rec.req = now, req
                self.outstanding -= 1
                self.newly_done.append(rec)
        self.probe.touched.clear()

    def _housekeeping(self, now: float, t0: float) -> None:
        if self.tracer is not None:
            self.tracer.poll(now - t0, self.probe)
        if now - self._frozen >= self.FREEZE_S:
            gc.freeze()
            self._frozen = now
        if not self.outstanding and now - self._released >= self.RELEASE_S:
            self.engine.run_until_done(max_ticks=1)
            self._released = now

    def run(self) -> Window:
        loop = self.cell["loop"]
        drain_s = float(self.cell["drain_s"])
        compiles0 = self.cc.n if self.cc else 0
        lateness: List[float] = []
        t0 = time.perf_counter()
        end = t0 + self.seconds
        try:
            if loop == "open":
                self._open(t0, end, drain_s, lateness)
            elif loop == "closed":
                self._closed(t0, end, drain_s)
            else:
                raise SetupError(f"unknown loop {loop!r}")
        except SetupError:
            raise
        except Exception as e:      # a raising engine fails what it held
            import traceback

            traceback.print_exc()
            self.errors.append(f"{type(e).__name__}: {e}")
        drain_end = time.perf_counter()
        if self.tracer is not None:
            self.tracer.stop(self.probe)
        return Window(t0, self.seconds, drain_end, loop,
                      list(self.recs.values()), lateness, self.tick_spans,
                      self.tracer.host_window if self.tracer else None,
                      self.errors,
                      (self.cc.n - compiles0) if self.cc else -1, self.ticks)

    def _open(self, t0: float, end: float, drain_s: float,
              lateness: List[float]) -> None:
        dues = t0 + self.sys.arrivals
        n, i = len(dues), 0
        while True:
            now = time.perf_counter()
            self._housekeeping(now, t0)
            if i < n and dues[i] <= now:
                with self._span("generator"):
                    while i < n and dues[i] <= now:
                        self._submit(i, float(dues[i]))
                        lateness.append(now - float(dues[i]))
                        i += 1
            if self.outstanding:
                self._tick()
            elif i < n:
                wait = float(dues[i]) - time.perf_counter()
                if wait > 0.002:
                    with self._span("wait"):
                        time.sleep(wait - 0.001)
            else:
                return
            if now > end + drain_s:
                return

    def _closed(self, t0: float, end: float, drain_s: float) -> None:
        nxt = 0
        for _ in range(int(self.cell["clients"])):
            self._submit(nxt, t0)
            nxt += 1
        while self.outstanding:
            now = time.perf_counter()
            self._housekeeping(now, t0)
            if now > end + drain_s:
                return
            self._tick()
            done, self.newly_done = self.newly_done, []
            stamp = self.tick_spans[-1].t1
            for _ in done:
                if stamp < end:
                    self._submit(nxt, stamp)
                    nxt += 1


# ------------------------------------------------------------- metrics
def nearest_rank(xs, q: float) -> float:
    """The q-th percentile of all samples, by nearest rank."""
    s = np.sort(np.asarray(xs, np.float64))
    if not len(s):
        raise ValueError("percentile of no samples")
    return float(s[max(0, int(math.ceil(q / 100.0 * len(s))) - 1)])


def latencies(win: Window) -> np.ndarray:
    """start -> output of every request started in the window; one still
    unfinished at the drain's end counts with drain end - start."""
    return np.asarray([(r.done if r.done is not None else win.drain_end)
                       - r.start for r in win.records
                       if r.start < win.t0 + win.seconds])


def ttfts(win: Window) -> np.ndarray:
    return np.asarray([(r.stamps[0] if r.stamps else win.drain_end)
                       - r.start for r in win.records
                       if r.start < win.t0 + win.seconds])


def token_gaps(win: Window) -> np.ndarray:
    """Every gap between consecutive output tokens of every request
    started in the window; an unfinished request adds the gap from its
    last token (or its start) to the drain's end."""
    gaps: List[float] = []
    for r in win.records:
        if r.start >= win.t0 + win.seconds:
            continue
        gaps.extend(np.diff(r.stamps).tolist())
        if r.done is None:
            gaps.append(win.drain_end - (r.stamps[-1] if r.stamps
                                         else r.start))
    return np.asarray(gaps)


def completed_per_s(win: Window) -> float:
    end = win.t0 + win.seconds
    return sum(1 for r in win.records
               if r.done is not None and r.done <= end) / win.seconds


END_TO_END: Dict[str, Callable[[Window], float]] = {
    "latency_p95_ms": lambda w: 1e3 * nearest_rank(latencies(w), 95),
    "req_per_s": completed_per_s,
    "ttft_p95_ms": lambda w: 1e3 * nearest_rank(ttfts(w), 95),
    "itl_p95_ms": lambda w: 1e3 * nearest_rank(token_gaps(w), 95),
}


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader may read: the traced window's host
    spans (``spans``: the probe's prefill/step spans, ``ticks``), the
    parsed device trace (``trace``, tracing.Trace), and the shapes."""

    config: Dict
    peaks: Dict
    spans: List[Span]
    ticks: List[Span]
    trace: object
    system: object


def in_window(spans: List[Span], t: tuple) -> List[Span]:
    return [s for s in spans if s.t0 >= t[0] and s.t1 <= t[1]]


def gc_quiet() -> None:
    """Collect set-up garbage and freeze what survives, so the window's
    collections scan only objects the window made."""
    gc.collect()
    gc.freeze()
