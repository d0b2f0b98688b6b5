"""The check that decides ``correct``: a sound run passes it, and a run
whose timed path is broken underneath fails it -- once for each fault a
one-chip serving cell can have: an answer or token altered where it is
produced, half of a batch left out, a step that returns its state
unchanged (the transformer's caches; a stack step has no state to
advance).  Each case drives the
whole harness (the look for a chip skipped) in a child process on the
CPU; the transformer cells run at the program's small kanffn-ci
architecture (cpu_cell.py)."""
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_cell(tmp_path, workload, fault=None, seed=2**31 + 77, seconds=1.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("PYTHONPATH", None)
    spec = {"workload": workload, "fault": fault, "seed": seed,
            "seconds": seconds}
    r = subprocess.run([sys.executable, "-m", "chip.tests.cpu_cell",
                        json.dumps(spec)], cwd=BENCH, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["table2.closed", "kanffn.chat",
                                      "kanffn.longprompt"])
def test_sound_run_is_correct(tmp_path, workload):
    res = run_cell(tmp_path, workload)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload,fault", [
    ("table2.closed", "answer"),
    ("table2.closed", "half_batch"),
    ("kanffn.chat", "token"),
    ("kanffn.chat", "stale_state"),
    ("kanffn.chat", "half_tokens"),
    ("kanffn.longprompt", "token"),
])
def test_broken_path_is_not_correct(tmp_path, workload, fault):
    res = run_cell(tmp_path, workload, fault)
    assert res["correct"] is False
    assert res["failed"] > 0
