"""The control: the plain reference computed one precision below what the
configuration states, put in the program's place, reads above the check's
limit, while the reference against itself reads nothing."""
import json
import os

import numpy as np

from chip import harness
from chip.systems import vikin_stacks

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(CHIP, *parts)) as f:
        return json.load(f)


def answered(system, n):
    """Records as a window would leave them, answered by ``outputs``."""
    class Req:
        pass

    recs = []
    for i in range(n):
        r = harness.Record(i, 0.0, [0.0], 0.0)
        r.req = Req()
        recs.append(r)
    return recs


def answer_with(system, recs, outputs):
    for r in recs:
        w = system.names[system.stream.workload(r.index)]
        r.req.output = outputs[w][r.index % vikin_stacks.POOL]


def test_stack_control_fails_the_limit():
    """At the cell's own sizes (both stacks at published widths, every
    payload of the pool): bf16x3 contractions in place of the program's
    float32 ones fail the configuration's stack_err limit."""
    config = load("configs", "vikin-table2.json")
    cell = load("cells", "table2.closed.json")
    system = vikin_stacks.System(config, cell, 2**31 + 5)
    system.prepare(1.0)
    ref = system.reference("highest")
    recs = answered(system, vikin_stacks.POOL)
    answer_with(system, recs, ref)
    sound = system.compare(recs, ref)
    assert sound["checks"]["stack_err"]["value"] == 0.0
    assert sound["failed"] == 0
    answer_with(system, recs, system.reference(config["check"]["control"]))
    control = system.compare(recs, ref)
    limit = config["check"]["limit"]
    assert control["checks"]["stack_err"]["value"] > limit
    assert control["failed"] > 0
    assert np.isfinite(control["checks"]["stack_err"]["value"])


def test_transformer_control_fails_the_limit():
    """At the program's small kanffn-ci architecture (cpu_cell.py) and the
    chat cell's traffic, every finished request compared: the reference
    at fp8 picks tokens that lie further below the float32 reference's
    best than the configuration's logit_gap limit; the program does not."""
    from chip.systems.kanffn_transformer import System
    from chip.tests.cpu_cell import tiny_kanffn

    config, cell = tiny_kanffn(load("cells", "kanffn.chat.json"))
    cell["check_requests"] = 10_000
    system = System(config, cell, 2**31 + 9)
    system.prepare(2.0)
    system.warmup()
    win = harness.Driver(system, cell, 2.0).run()
    system.release()
    got = system.readings(win.records)
    limit = config["check"]["limit"]
    assert got["program"] <= limit < got["control"], got
