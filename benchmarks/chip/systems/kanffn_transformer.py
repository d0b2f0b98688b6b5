"""The KAN-FFN transformer served token by token through the Engine.

The timed path is the program's own: ``Engine.tick`` ->
``TransformerBackend.prefill`` (one request, its exact prompt length) and
``TransformerBackend.step`` (one token for every slot), in the
configuration's dtype, the KAN-FFN layers through the fused KAN kernel
and the pattern matmul (``impl`` "auto" on a TPU).  The benchmark makes
the weights and prompts from the seed.

The check: once the window has closed and the engine is gone, a sample of
the finished requests drawn from the seed, the one with the most served
tokens always among them, is run through the plain reference
(reference/transformer.py) over its prompt and served tokens.  The number
compared is the widest gap by which a served token's reference logit lies
below the reference's best at its position: greedy serving that agrees
with the reference up to rounding reads near zero.  It covers the prefill
(the first token) and the decode through the cache (the rest), every
layer, and the tied head.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chip import traffic, weights
from chip.harness import Probe, SetupError
from chip.reference.transformer import (ReferenceLM, served_sequence,
                                        token_gaps)


def normalized(config: Dict) -> Dict:
    """The configuration with its kept-index shorthands expanded."""
    cfg = dict(config)
    if cfg["kan_hidden_keep"] == "all":
        cfg["kan_hidden_keep"] = list(range(cfg["kan_hidden"]))
    return cfg


def leaf_spec(cfg: Dict):
    d, nb = cfg["d_model"], cfg["kan_grid"] + cfg["kan_order"]
    embed_std = cfg["init"]["embed_std"]

    def spec(path: str, shape: tuple):
        if path.endswith("embed/table"):
            return 0.0, embed_std
        if path.endswith("/scale"):
            return 1.0, 0.06
        if path.endswith("/bias"):
            return 0.0, 0.1
        if path.endswith("/kernel"):
            return 0.0, 1.0 / np.sqrt(shape[0])
        if path.endswith("kan_up/w_b"):
            return 0.0, 1.0 / np.sqrt(d)
        if path.endswith("kan_up/t"):
            return 0.0, 1.0 / np.sqrt(d * nb)
        if path.endswith("ffn/w"):
            return 0.0, 1.0 / np.sqrt(shape[0])
        if path.endswith("ffn/b"):
            return 0.0, 0.02
        raise SetupError(f"no weight rule for parameter {path}")

    return spec


class System:
    def __init__(self, config: Dict, cell: Dict, seed: int) -> None:
        from repro.configs.registry import KANFFN_ARCHS
        from repro.models import transformer as T
        from repro.runtime.backends import TransformerBackend
        from repro.runtime.server import Engine

        self.config = cfg = normalized(config)
        self.cell, self.seed = cell, seed
        arch = KANFFN_ARCHS[cfg["arch"]]
        self._match(arch, cfg)
        precision = {"bfloat16": "bf16", "float32": "f32"}[cfg["dtype"]]
        arch = dataclasses.replace(arch, dtype=cfg["dtype"])
        template = jax.eval_shape(functools.partial(T.init_params, cfg=arch),
                                  jax.random.key(0))
        self.params = weights.make_tree(template, leaf_spec(cfg),
                                        arch.param_dtype, seed)
        jax.block_until_ready(self.params)
        self.max_len = int(cell["max_len"])
        self.backend = TransformerBackend(arch, self.params,
                                          impl=cfg["impl"],
                                          precision=precision)
        self.probe = Probe(self.backend)
        self.engine = Engine(self.probe, n_slots=cfg["slots"],
                             max_len=self.max_len, admission="unbounded",
                             drop_expired=False)
        self.stream = traffic.Stream(cell, seed)
        self.arrivals = None
        self.prompts: Dict[int, np.ndarray] = {}

    @staticmethod
    def _match(arch, cfg: Dict) -> None:
        """The program's model must be the configuration's."""
        up = arch.ffn_cfg(0)
        got = {"d_model": arch.d_model, "n_heads": arch.n_heads,
               "n_kv_heads": arch.n_kv_heads, "head_dim": arch.hd,
               "d_ff": arch.d_ff, "vocab_size": arch.vocab_size,
               "ffn_kinds": list(arch.ffn_kinds), "qkv_bias": arch.qkv_bias,
               "rope_theta": arch.rope_base,
               "tie_word_embeddings": arch.tied_embeddings,
               "kan_hidden": up.kanffn_hidden, "kan_grid": arch.kan_grid,
               "kan_order": arch.kan_order}
        bad = {k: (v, cfg[k]) for k, v in got.items() if v != cfg[k]}
        dense = (arch.ffn_masks is None and arch.pattern_rate == 0
                 and arch.norm == "rms" and arch.norm_offset == 0.0)
        if bad or not dense or len(cfg["kan_basis_keep"]) != (
                arch.kan_grid + arch.kan_order):
            raise SetupError(f"{arch.name}: program differs from the "
                             f"configuration: {bad or 'masks or norm'}")

    # ------------------------------------------------------------ window
    def prepare(self, seconds: float) -> None:
        if self.cell["loop"] == "open":
            self.arrivals = traffic.arrival_times(self.cell["arrivals"],
                                                  seconds, self.seed)
            self.stream = traffic.Stream(self.cell, self.seed,
                                         block=len(self.arrivals))

    def prompt(self, index: int) -> np.ndarray:
        if index not in self.prompts:
            rng = traffic.rng_for(self.seed, 10_000 + index)
            self.prompts[index] = rng.integers(
                0, self.config["vocab_size"], self.stream.prompt_len(index)
            ).astype(np.int32)
        return self.prompts[index]

    def warmup(self) -> None:
        """Compile what the window runs: the prefill at every prompt
        length of the cell, the splice into each of the slots, and the
        decode step; one request per slot, the lengths in turn."""
        lengths = self.cell["prompt_len"]["values"]
        rng = traffic.rng_for(self.seed, 5)
        for s in range(self.config["slots"]):
            n = lengths[s % len(lengths)]
            self.engine.submit(rng.integers(0, self.config["vocab_size"], n
                                            ).astype(np.int32),
                               max_new_tokens=2)
        self.engine.run_until_done(max_ticks=10_000)
        self.probe.spans.clear()
        self.probe.touched.clear()

    def submit(self, index: int, start: float) -> int:
        return self.engine.submit(self.prompt(index),
                                  max_new_tokens=self.stream.output_len(
                                      index), t_submit=start)

    def release(self) -> None:
        """Drop the engine, its caches and the backend before the check."""
        self.engine = self.probe = self.backend = None

    # ------------------------------------------------------------- check
    def sample(self, records) -> List:
        done = [r for r in records if r.req is not None]
        if not done:
            return []
        longest = max(done, key=lambda r: (len(r.req.generated),
                                           len(r.req.prompt), -r.index))
        rest = [r for r in done if r is not longest]
        k = min(len(rest), int(self.cell["check_requests"]) - 1)
        rng = traffic.rng_for(self.seed, 6)
        pick = rng.choice(len(rest), size=k, replace=False) if k else []
        return [longest] + [rest[i] for i in sorted(pick)]

    def gaps(self, sample, modes=("highest",)) -> Dict[str, List]:
        """Per sampled request, the largest gap of its served tokens under
        the reference (``highest``); with another mode also the gap of the
        token that mode's reference puts first at each position."""
        out_len = self.cell["output_len"]
        n_pos = int(out_len.get("high", out_len.get("value")))
        ref = ReferenceLM(self.params, self.config, "highest", n_pos)
        others = {m: ReferenceLM(self.params, self.config, m, n_pos)
                  for m in modes if m != "highest"}
        out: Dict[str, List] = {m: [] for m in modes}
        for r in sample:
            served = list(r.req.generated)
            want = self.stream.output_len(r.index)
            if len(served) != want:
                out["highest"].append(np.inf)
                continue
            seq, pos = served_sequence(self.prompt(r.index), served,
                                       self.max_len)
            lg = ref.logits(seq, pos)
            if "highest" in out:
                out["highest"].append(float(np.max(token_gaps(lg, served))))
            for m, other in others.items():
                picked = np.asarray(jnp.argmax(other.logits(seq, pos), -1))
                out[m].append(float(np.max(token_gaps(lg, picked))))
        return out

    def check(self, records) -> Dict:
        t = time.perf_counter()
        limit = self.config["check"]["limit"]
        sample = self.sample(records)
        per_req = self.gaps(sample)["highest"] if sample else []
        worst = max(per_req) if per_req else float("inf")
        failed = (sum(1 for g in per_req if g > limit)
                  if limit is not None else 0)
        return {"checks": {"logit_gap": {"value": worst, "limit": limit}},
                "failed": failed, "compared": len(per_req),
                "tokens_compared": sum(len(r.req.generated) for r in sample),
                "seconds": time.perf_counter() - t}

    def readings(self, records) -> Dict[str, float]:
        """The number compared, for the program and for the control (the
        reference at the control precision, reading the gap of the token
        it puts first), over the same sample of requests."""
        mode = self.config["check"]["control"]
        g = self.gaps(self.sample(records), modes=("highest", mode))
        return {"program": max(g["highest"]), "control": max(g[mode])}

    # ----------------------------------------------------------- counts
    def step_flops(self, info) -> float:
        from chip import counts

        return counts.decode_flops(self.config, [c for _, c in info])

    def prefill_flops(self, length: int) -> float:
        from chip import counts

        return counts.prefill_flops(self.config, length)
