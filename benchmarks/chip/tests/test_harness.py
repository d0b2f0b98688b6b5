"""Percentiles over all requests, missing requests at their drain-end
latency, and the entry point's refusals."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chip import harness

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))


def window(records, t0=100.0, seconds=10.0, drain_end=112.0):
    return harness.Window(t0, seconds, drain_end, "open", records, [], [],
                          None, [], 0, 0)


def test_nearest_rank_uses_every_sample():
    xs = list(range(1, 10001))
    assert harness.nearest_rank(xs, 95) == 9500
    assert harness.nearest_rank(xs, 50) == 5000
    assert harness.nearest_rank([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        harness.nearest_rank([], 95)


def test_missing_requests_count_at_drain_end():
    recs = [harness.Record(i, 100.0 + i * 0.001, [100.0 + i * 0.001 + 0.002],
                           100.0 + i * 0.001 + 0.002) for i in range(95)]
    recs += [harness.Record(95 + i, 105.0) for i in range(5)]
    w = window(recs)
    lat = harness.latencies(w)
    assert len(lat) == 100
    assert sorted(lat)[-5:] == pytest.approx([7.0] * 5)   # 112 - 105
    assert harness.END_TO_END["latency_p95_ms"](w) == pytest.approx(2.0)
    recs.append(harness.Record(100, 106.0))
    assert harness.END_TO_END["latency_p95_ms"](window(recs)) == \
        pytest.approx(6000.0)
    # nothing is failed for being late: completions count only done ones
    assert harness.completed_per_s(window(recs)) == pytest.approx(9.5)


def test_requests_after_the_window_are_not_sampled():
    recs = [harness.Record(0, 101.0, [101.5], 101.5),
            harness.Record(1, 111.0, [111.1], 111.1)]
    assert list(harness.latencies(window(recs))) == pytest.approx([0.5])


def test_token_gaps_and_ttft():
    done = harness.Record(0, 100.0, [100.1, 100.1, 100.3, 100.6], 100.6)
    open_ = harness.Record(1, 101.0, [101.2])
    w = window([done, open_])
    assert list(harness.ttfts(w)) == pytest.approx([0.1, 0.2])
    assert sorted(harness.token_gaps(w)) == pytest.approx(
        [0.0, 0.2, 0.3, 10.8])


def run_entry(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"),
         "--workload", "table2.closed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_entry_point_refuses_without_a_tpu(tmp_path):
    r = run_entry(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 1
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_entry_point_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run_entry(str(tmp_path), {})
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_benchmark_file_names_only_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(CHIP, "cells",
                                           w["name"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.exists(harness.metric_reader_path(m["name"]))
    assert all(isinstance(v, float) or v is None for v in
               np.array([0.0]).tolist())
