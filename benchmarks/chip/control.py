#!/usr/bin/env python3
"""Readings that set a configuration's check limit (not part of a run).

    python benchmarks/chip/control.py --workload <cell> --seeds s1,s2,... \\
        --seconds <s>

For each seed, in one process: build the cell, serve a short window of
its own traffic at its own load, then read the number its check compares
twice over the same returned requests -- once for the program, once for
the control, the plain reference computed at the configuration's
``check.control`` precision (the next precision down from the one it
states) put in the program's place.  One JSON line per seed.  The limit
lies above every program reading and below every control reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(HERE)]
    from chip import harness
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    cell = harness.load_json(HERE, "cells", args.workload + ".json")
    config = harness.load_json(HERE, "configs", cell["config"] + ".json")
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        system = harness.load_system(config).System(config, cell, seed)
        system.prepare(args.seconds)
        system.warmup()
        win = harness.Driver(system, cell, args.seconds).run()
        system.release()
        gc.collect()
        row = {"seed": seed, "requests": len(win.records),
               **system.readings(win.records),
               "control_mode": config["check"]["control"],
               "seconds": time.perf_counter() - t}
        print(json.dumps(row), flush=True)
        del system
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
