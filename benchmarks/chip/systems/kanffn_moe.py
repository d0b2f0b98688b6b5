"""The Qwen3-MoE transformer with KAN-FFN experts, served through the Engine.

The timed path is the program's own, as for the dense KAN-FFN transformer
(kanffn_transformer.py, whose window, sample and check this reuses):
``Engine.tick`` -> ``TransformerBackend.prefill`` / ``.step`` in bfloat16,
each MoE layer the dropless KAN-FFN expert layer over the experts this
chip holds (models/moe.moe_kanffn: the grouped KAN kernel up, a grouped
matmul down).  The benchmark makes the weights and prompts from the seed.

The check is the dense transformer's logit gap, against the plain
reference of this model (reference/moe_transformer.py): it covers the
prefill, the decode through the cache, every layer's routing over all
the experts and the held experts' work, and the untied head.

A program without the configuration's arch (one older than it) cannot run
the cell: building the system raises ``SetupError`` at once.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chip import counts_moe, traffic, weights
from chip.harness import Probe, SetupError
from chip.reference.moe_transformer import ReferenceMoE
from chip.reference.transformer import served_sequence, token_gaps
from chip.systems import kanffn_transformer


def leaf_spec(cfg: Dict):
    """(mean, std) of every weight: linear kernels 1/sqrt(fan_in), norm
    scales 1 +- 0.06, the embedding ``init.embed_std``, each expert's fused
    KAN weights 1/sqrt(d_model * (G+K+1)), its down-projection
    1/sqrt(kan_hidden) and that projection's bias 0.1."""
    d, nb = cfg["d_model"], cfg["kan_grid"] + cfg["kan_order"]
    embed_std = cfg["init"]["embed_std"]

    def spec(path: str, shape: tuple):
        if path.endswith("embed/table"):
            return 0.0, embed_std
        if path.endswith("/scale"):
            return 1.0, 0.06
        if path.endswith("/kernel"):
            return 0.0, 1.0 / np.sqrt(shape[0])
        if path.endswith("experts/wt"):
            return 0.0, 1.0 / np.sqrt(d * (nb + 1))
        if path.endswith("experts/w"):
            return 0.0, 1.0 / np.sqrt(cfg["kan_hidden"])
        if path.endswith("experts/b"):
            return 0.0, 0.1
        raise SetupError(f"no weight rule for parameter {path}")

    return spec


class System(kanffn_transformer.System):
    def __init__(self, config: Dict, cell: Dict, seed: int) -> None:
        from repro.configs.registry import KANFFN_ARCHS
        from repro.models import transformer as T
        from repro.runtime.backends import TransformerBackend
        from repro.runtime.server import Engine

        if config["arch"] not in KANFFN_ARCHS:
            raise SetupError(f"the program has no arch {config['arch']!r}")
        self.config = cfg = config
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
        moe = arch.moe_cfg()
        got = {"d_model": arch.d_model, "n_heads": arch.n_heads,
               "n_kv_heads": arch.n_kv_heads, "head_dim": arch.hd,
               "d_ff": arch.d_ff, "vocab_size": arch.vocab_size,
               "ffn_kinds": list(arch.ffn_kinds), "qk_norm": arch.qk_norm,
               "qkv_bias": arch.qkv_bias, "rope_theta": arch.rope_base,
               "tie_word_embeddings": arch.tied_embeddings,
               "router_experts": arch.n_experts, "top_k": arch.top_k,
               "first_expert": moe.first_expert, "num_experts": moe.held,
               "kan_hidden": moe.expert_ffn_cfg().kanffn_hidden,
               "kan_grid": arch.kan_grid, "kan_order": arch.kan_order}
        bad = {k: (v, cfg[k]) for k, v in got.items() if v != cfg[k]}
        plain = (moe.ffn_kind == "kanffn" and not arch.shared_expert
                 and arch.norm == "rms" and arch.norm_offset == 0.0)
        if bad or not plain or len(cfg["kan_basis_keep"]) != (
                arch.kan_grid + arch.kan_order):
            raise SetupError(f"{arch.name}: program differs from the "
                             f"configuration: {bad or 'experts or norm'}")

    # ------------------------------------------------------------- check
    def gaps(self, sample, modes=("highest",)) -> Dict[str, List]:
        """As the dense transformer's, against this model's reference."""
        out_len = self.cell["output_len"]
        n_pos = int(out_len.get("high", out_len.get("value")))
        refs = {m: ReferenceMoE(self.params, self.config, m, n_pos)
                for m in set(modes) | {"highest"}}
        out: Dict[str, List] = {m: [] for m in modes}
        for r in sample:
            served = list(r.req.generated)
            if len(served) != self.stream.output_len(r.index):
                out["highest"].append(np.inf)
                continue
            seq, pos = served_sequence(self.prompt(r.index), served,
                                       self.max_len)
            lg = refs["highest"].logits(seq, pos)
            if "highest" in out:
                out["highest"].append(float(np.max(token_gaps(lg, served))))
            for m in modes:
                if m != "highest":
                    picked = np.asarray(jnp.argmax(refs[m].logits(seq, pos),
                                                   -1))
                    out[m].append(float(np.max(token_gaps(lg, picked))))
        return out

    # ----------------------------------------------------------- counts
    def step_flops(self, info) -> float:
        return counts_moe.decode_flops(self.config, [c for _, c in info])

    def prefill_flops(self, length: int) -> float:
        return counts_moe.prefill_flops(self.config, length)
