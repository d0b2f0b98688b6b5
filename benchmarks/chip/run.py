#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout, with no PYTHONPATH: the program is imported
from ``src/``.  Set-up (weights from the seed, compiling and warming every
shape the cell uses) is timed as ``setup_s``; the window then runs for
``--seconds``; the check runs after it.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of a few steady seconds of the window, with ``breakdown``.  The last
standard-output line is one JSON object; the numbers compared and their
limits end both it (``checks``) and standard error.

Exits 1, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for; 2 when the program or a named file is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# A traced run reads the profiler's trace from STEADY_S into its window
# (an open loop starts with empty slots) for TRACE_S seconds; a short
# window is cut in the same proportions.
STEADY_S = 5.0
TRACE_S = 5.0


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None, require_tpu: bool = True, fault=None,
         files=None) -> int:
    """``require_tpu``, ``fault`` (a callable given the built system) and
    ``files`` (cell or configuration files by name, in place of those on
    disk) exist for the harness's own tests, which run on the CPU at small
    sizes, some with the timed path broken on purpose."""
    files = files or {}
    args = parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(HERE)]
    from chip import harness
    from chip.tracing import Tracer, breakdown

    try:
        from repro.utils import enable_compile_cache
    except ImportError as e:
        log(f"run.py: the program is not in this checkout ({e})")
        return 2
    enable_compile_cache()
    import jax

    try:
        bench = files.get("BENCHMARK.json") or harness.benchmark()
        entry = harness.workload_entry(bench, args.workload)
        cell = (files.get(args.workload)
                or harness.load_json(HERE, "cells", args.workload + ".json"))
        if cell["config"] != entry["config"]:
            raise harness.SetupError(
                f"cell file names {cell['config']!r}, BENCHMARK.json "
                f"{entry['config']!r}")
        config = (files.get(cell["config"]) or harness.load_json(
            HERE, "configs", cell["config"] + ".json"))
        devs = jax.devices()
        if require_tpu and (devs[0].platform != "tpu"
                            or len(devs) < entry["chips"]):
            log(f"run.py: needs {entry['chips']} TPU chip(s); JAX found "
                f"{len(devs)} {devs[0].platform} device(s)")
            return 1
        peaks = (harness.peaks_for(devs[0].device_kind) if require_tpu
                 else None)
        e2e = harness.metrics_for(bench, "end_to_end", args.workload)
        per_layer = harness.metrics_for(bench, "per_layer", args.workload)
        readers = {m["name"]: harness.load_metric_reader(m["name"])
                   for m in per_layer} if args.trace else {}
        counter = harness.CompileCounter()
        t_build = time.perf_counter()
        system = harness.load_system(config).System(config, cell, args.seed)
        if fault is not None:
            fault(system)
        system.prepare(args.seconds)
        t_warm = time.perf_counter()
        system.warmup()
        log(f"set-up: start {t_build - T_START:.2f} s, build (weights, "
            f"engine) {t_warm - t_build:.2f} s, warm-up "
            f"{time.perf_counter() - t_warm:.2f} s, {counter.n} programs")
    except harness.SetupError as e:
        log(f"run.py: {e}")
        return 2

    tracer = None
    harness.gc_quiet()
    if args.trace:
        tracer = Tracer(min(STEADY_S, 0.25 * args.seconds),
                        min(TRACE_S, 0.25 * args.seconds))
        tracer.start()
    win = harness.Driver(system, cell, args.seconds, tracer, counter).run()
    setup_s = win.t0 - T_START
    stats = devs[0].memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    spans = system.probe.spans
    system.release()
    gc.collect()
    chk = system.check(win.records)

    end = win.t0 + win.seconds
    started = [r for r in win.records if r.start < end]
    unfinished = [r for r in win.records if r.done is None]
    failed = chk["failed"] + (len(unfinished) if win.errors else 0)
    limits_set = all(c["limit"] is not None for c in chk["checks"].values())
    correct = (not win.errors and failed == 0 and limits_set
               and all(c["value"] <= c["limit"]
                       for c in chk["checks"].values()))

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    metrics, extra = {}, {}
    if not args.trace:
        values = {"setup_s": setup_s}
        for m in e2e:
            if m["name"] not in values:
                values[m["name"]] = harness.END_TO_END[m["name"]](win)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e}
    else:
        trace = tracer.parse()
        if trace is None or not trace.ops:
            log("run.py: the profiler recorded no device operation")
            return 1
        hw = win.trace_t
        view = harness.RunView(system.config, peaks,
                               harness.in_window(spans, hw),
                               harness.in_window(win.tick_spans, hw),
                               trace, system)
        for m in per_layer:
            v = readers[m["name"]](view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        extra["breakdown"] = breakdown(trace)

    if tracer is not None:
        log(f"profiler: read {tracer.steady_s:g}-"
            f"{tracer.steady_s + tracer.duration:g} s into the window; "
            f"stopping it took {tracer.stop_s:.3f} s")
    lat = sorted(win.lateness)
    if win.tick_spans:
        slow = max(win.tick_spans, key=lambda t: t.t1 - t.t0)
        log(f"longest tick: {1e3 * (slow.t1 - slow.t0):.3f} ms, "
            f"{slow.t0 - win.t0:.3f} s into the window")
    log(f"window: {win.loop} loop, {len(started)} requests started in "
        f"{win.seconds} s, {len(win.records) - len(unfinished)} finished, "
        f"{len(unfinished)} unfinished at the drain's end "
        f"({win.drain_end - end:.3f} s after the window), {win.ticks} ticks, "
        f"{win.compiles} compilations in the window")
    if lat:
        log(f"generator lateness: p50 {1e3 * lat[len(lat) // 2]:.3f} ms, "
            f"max {1e3 * lat[-1]:.3f} ms")
    log(f"check: {chk['compared']} requests compared in "
        f"{chk['seconds']:.1f} s; errors: {win.errors or 'none'}")
    checks = dict(chk["checks"])
    checks["failed"] = {"value": failed, "limit": 0}
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    result = {"correct": bool(correct), "attempted": len(started),
              "failed": int(failed), "metrics": metrics, "device": device,
              **extra, "checks": checks}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
