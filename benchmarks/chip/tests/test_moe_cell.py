"""The check of the KAN-FFN expert transformer's cell decides ``correct``:
a sound run passes it, and a run whose expert layer drops the rows past a
capacity of one per expert (what a capacity-bounded layer would do) fails
it.  Each case drives the whole harness (the look for a chip skipped) in a
child process on the CPU, at the program's small ``kanffn-moe-ci``
architecture (d_model 64, 16 experts, top-4, 4 held, 2 layers) in float32,
with the cell's lengths cut to a 32-token cache:

    python -m chip.tests.test_moe_cell '<json spec>'
"""
from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
BENCH = os.path.dirname(CHIP)
ROOT = os.path.dirname(BENCH)
WORKLOAD = "qwen3moe.longprompt"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def tiny_moe(cell: dict) -> tuple:
    """The kanffn-moe-ci architecture as a configuration, and the cell
    with its prompts and answers cut to a 32-token cache."""
    from chip.tests.cpu_cell import TINY_EMBED_STD

    cfg = load(CHIP, "configs", cell["config"] + ".json")
    cfg.update(name="tiny-kanffn-moe", arch="kanffn-moe-ci", d_model=64,
               d_ff=128, n_heads=4, n_kv_heads=2, head_dim=16,
               vocab_size=256, ffn_kinds=["moe", "moe"], router_experts=16,
               top_k=4, first_expert=0, num_experts=4, kan_hidden=28,
               rope_theta=10000.0, dtype="float32",
               init={"embed_std": TINY_EMBED_STD})
    cell = dict(cell, config=cfg["name"], max_len=32,
                prompt_len={"values": [8, 16], "weights": [1, 1]},
                output_len={"kind": "fixed", "value": 8}, check_requests=8)
    return cfg, cell


def capacity_one(system) -> None:
    """Every MoE layer keeps only the first row routed to each held
    expert; the expert work of the rest is dropped (their gate weights
    then scale nothing but the bias)."""
    import jax
    import jax.numpy as jnp
    from repro.models import moe

    inner = moe.group_rows

    def group_rows(expert, mine, n_held, bm, n_tiles):
        onehot = jax.nn.one_hot(jnp.where(mine, expert, n_held), n_held,
                                dtype=jnp.int32)
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, -1)
        return inner(expert, mine & (rank < 1), n_held, bm, n_tiles)

    moe.group_rows = group_rows


FAULTS = {None: None, "capacity_one": capacity_one}


def main(argv) -> int:
    spec = json.loads(argv[0])
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    from chip import run

    bench = copy.deepcopy(load(ROOT, "BENCHMARK.json"))
    cfg, cell = tiny_moe(load(CHIP, "cells", WORKLOAD + ".json"))
    for w in bench["workloads"]:
        if w["name"] == WORKLOAD:
            w["config"] = cfg["name"]
    files = {cfg["name"]: cfg, WORKLOAD: cell, "BENCHMARK.json": bench}
    return run.main(["--workload", WORKLOAD, "--seed", str(spec["seed"]),
                     "--seconds", str(spec["seconds"]), "--trace", "0"],
                    require_tpu=False, fault=FAULTS[spec["fault"]],
                    files=files)


def run_cell(tmp_path, fault=None, seed=2**31 + 91, seconds=1.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("PYTHONPATH", None)
    spec = {"fault": fault, "seed": seed, "seconds": seconds}
    r = subprocess.run([sys.executable, "-m", "chip.tests.test_moe_cell",
                        json.dumps(spec)], cwd=BENCH, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(tmp_path):
    res = run_cell(tmp_path)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_dropping_rows_past_capacity_is_not_correct(tmp_path):
    res = run_cell(tmp_path, "capacity_one")
    assert res["correct"] is False, res["checks"]
    assert res["failed"] > 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
