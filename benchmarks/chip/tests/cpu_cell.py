"""Run a benchmark cell on the CPU at a small size, the timed path broken
on purpose where asked (the harness's own tests run this in a child
process, so that its JAX set-up touches nothing else).

    python -m chip.tests.cpu_cell '<json spec>'

The spec names the ``workload``, a ``fault`` (or null) and the
``seconds``; a ``qwen2-0.5b-kanffn`` cell runs at the program's small
``kanffn-ci`` architecture, with the cell's lengths cut to match.
"""
from __future__ import annotations

import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# At d_model 64 the published embedding scale leaves the logits nearly
# flat; this spreads them as far as the full model's (std about 0.6), so
# that a wrong token reads as far below the best as it would there.
TINY_EMBED_STD = 0.08


def tiny_kanffn(cell: dict) -> tuple:
    """The kanffn-ci architecture (3 layers, d_model 64) in float32, and
    the cell with its prompts and answers cut to a 32-token cache."""
    cfg = load(CHIP, "configs", "qwen2-0.5b-kanffn.json")
    cfg.update(name="tiny-kanffn", arch="kanffn-ci", d_model=64, d_ff=128,
               n_heads=4, n_kv_heads=2, head_dim=16, vocab_size=256,
               ffn_kinds=["mlp", "kan", "mlp"], qkv_bias=False,
               rope_theta=10000.0, kan_hidden=28, dtype="float32",
               init={"embed_std": TINY_EMBED_STD})
    cell = dict(cell, config="tiny-kanffn", max_len=32,
                prompt_len={"values": [8, 16], "weights": [1, 1]},
                check_requests=4)
    if cell["output_len"]["kind"] == "fixed":
        cell["output_len"] = {"kind": "fixed", "value": 8}
    else:
        cell["output_len"] = {"kind": "loguniform", "low": 2, "high": 12}
    if cell["loop"] == "open":
        cell["arrivals"] = {"kind": "poisson", "rate_rps": 20.0}
    return cfg, cell


def alter_answer(system) -> None:
    """Every 500th stack answer produced (warm-up included) is off in one
    output."""
    seen = [0]
    for b in system.backends.values():
        inner = b.step

        def step(inputs, slot_req, inner=inner):
            out = inner(inputs, slot_req)
            for r in slot_req:
                if r is not None:
                    seen[0] += 1
                    if seen[0] % 500 == 0:
                        r.output = r.output.copy()
                        r.output[0] += 1e-3
            return out

        b.step = step


def half_batch(system) -> None:
    """Each stack step computes only the first half of its requests; the
    rest come back zero."""
    import numpy as np

    for b in system.backends.values():
        inner = b.step

        def step(inputs, slot_req, inner=inner):
            active = [s for s, r in enumerate(slot_req) if r is not None]
            keep = set(active[: (len(active) + 1) // 2])
            out = inner(inputs, [r if s in keep else None
                                 for s, r in enumerate(slot_req)])
            for s in active:
                if s not in keep:
                    slot_req[s].output = np.zeros(
                        system.models[slot_req[s].workload]["sizes"][-1],
                        np.float32)
                    slot_req[s].done = True
            return out

        b.step = step


def alter_token(system) -> None:
    """Every request's second generated token is replaced where the
    decode step produces it."""
    b = system.backend
    inner = b.step
    vocab = system.config["vocab_size"]

    def step(caches, slot_req):
        out = inner(caches, slot_req)
        for r in slot_req:
            if r is not None and len(r.generated) == 2:
                r.generated[-1] = (r.generated[-1] + 1) % vocab
        return out

    b.step = step


def stale_state(system) -> None:
    """The decode step returns the caches it was given: the token is
    produced, but its keys and values never reach the cache."""
    b = system.backend
    inner = b.step

    def step(caches, slot_req):
        inner(caches, slot_req)
        return caches

    b.step = step


def half_tokens(system) -> None:
    """The decode step serves the first half of its requests; the rest get
    their previous token again instead of the one computed for them."""
    b = system.backend
    inner = b.step

    def step(caches, slot_req):
        active = [r for r in slot_req if r is not None]
        out = inner(caches, slot_req)
        for r in active[(len(active) + 1) // 2:]:
            if len(r.generated) >= 2:
                r.generated[-1] = r.generated[-2]
        return out

    b.step = step


FAULTS = {None: None, "answer": alter_answer, "half_batch": half_batch,
          "token": alter_token, "stale_state": stale_state,
          "half_tokens": half_tokens}


def main(argv) -> int:
    spec = json.loads(argv[0])
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(CHIP)]
    from chip import run

    name = spec["workload"]
    bench = load(ROOT, "BENCHMARK.json")
    cell = load(CHIP, "cells", name + ".json")
    files = {}
    if cell["config"] == "qwen2-0.5b-kanffn":
        cfg, cell = tiny_kanffn(cell)
        bench = copy.deepcopy(bench)
        for w in bench["workloads"]:
            if w["name"] == name:
                w["config"] = cfg["name"]
        files = {cfg["name"]: cfg, name: cell, "BENCHMARK.json": bench}
    return run.main(["--workload", name, "--seed", str(spec["seed"]),
                     "--seconds", str(spec["seconds"]), "--trace", "0"],
                    require_tpu=False, fault=FAULTS[spec["fault"]],
                    files=files)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
