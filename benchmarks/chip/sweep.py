#!/usr/bin/env python3
"""Find an open-loop cell's knee once: serve its traffic at several fixed
rates, one window each, in one process, and print per rate what was
offered, what completed, and the tails.

    python benchmarks/chip/sweep.py --workload <cell> --seeds s1,s2,... \\
        --seconds <s> --rates r1,r2,...

The knee is the highest rate the engine sustains without a growing
backlog, on every seed; a cell's fixed rate is written into its file from
this, once.  Sweep in windows of the cell's own length, with its own
``drain_s``: a knee found in short windows can turn into a backlog over a
long one.  For each seed the sweep stops after the first rate that leaves
requests unfinished.  A token cell also prints its TTFT p95 over the
first and the last third of the window's requests: the latter well above
the former is a queue that grows.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def sweep_rate(system, cell, rate: float, seconds: float, token: bool):
    from chip import harness
    import numpy as np

    cell = dict(cell, arrivals=dict(cell["arrivals"], rate_rps=rate))
    system.cell = cell
    system.prepare(seconds)
    win = harness.Driver(system, cell, seconds).run()
    unfinished = sum(1 for r in win.records if r.done is None)
    row = {"rate_rps": rate, "offered": len(win.records),
           "completed_per_s": harness.completed_per_s(win),
           "unfinished": unfinished,
           "drain_s": win.drain_end - win.t0 - win.seconds,
           "lateness_p50_ms": 1e3 * float(np.median(win.lateness)),
           "ticks": win.ticks}
    if token:
        ttft = harness.ttfts(win)
        third = max(1, len(ttft) // 3)
        row["ttft_p50_ms"] = 1e3 * harness.nearest_rank(ttft, 50)
        row["ttft_p95_ms"] = 1e3 * harness.nearest_rank(ttft, 95)
        row["ttft_p95_first_third_ms"] = 1e3 * harness.nearest_rank(
            ttft[:third], 95)
        row["ttft_p95_last_third_ms"] = 1e3 * harness.nearest_rank(
            ttft[-third:], 95)
        row["itl_p95_ms"] = 1e3 * harness.nearest_rank(
            harness.token_gaps(win), 95)
    else:
        lat = harness.latencies(win)
        row["latency_p50_ms"] = 1e3 * harness.nearest_rank(lat, 50)
        row["latency_p95_ms"] = 1e3 * harness.nearest_rank(lat, 95)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(HERE)]
    from chip import harness
    from repro.utils import enable_compile_cache

    enable_compile_cache()

    cell = harness.load_json(HERE, "cells", args.workload + ".json")
    config = harness.load_json(HERE, "configs", cell["config"] + ".json")
    if cell["loop"] != "open":
        print("sweep: only open-loop cells have a rate", file=sys.stderr)
        return 2
    token = "output_len" in cell
    for seed in [int(x) for x in args.seeds.split(",")]:
        system = harness.load_system(config).System(config, cell, seed)
        system.warmup()
        for rate in [float(r) for r in args.rates.split(",")]:
            row = sweep_rate(system, cell, rate, args.seconds, token)
            print(json.dumps({"seed": seed, **row}), flush=True)
            if row["unfinished"]:
                break
        system.release()
        del system
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
