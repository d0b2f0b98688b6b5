"""Operations and bytes of the KAN-FFN expert transformer, from shapes alone.

The MoE configuration's counterpart of counts.py (same conventions: a FLOP
is one multiply or one add, bytes count each operand and result once in
the served dtype, and nothing a kernel repeats is counted).  The expert
work of a token is taken at its expectation over the router: ``top_k``
assignments, of which the share ``num_experts / router_experts`` lands on
the experts held here.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

from chip.counts import transformer_dims


def item(cfg: Dict) -> int:
    return 2 if cfg["dtype"] == "bfloat16" else 4


def grouped_kan(cfg: Dict, rows: int, active: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of the grouped KAN up-projection over ``rows`` rows
    routed to ``active`` held experts: one MAC per (row, input, output)
    for the silu branch and each kept basis; the rows read once, each
    active expert's fused weights read once, the hidden rows written
    once."""
    d, h = cfg["d_model"], cfg["kan_hidden"]
    fused = d * (len(cfg["kan_basis_keep"]) + 1) * h
    flops = 2.0 * rows * fused
    nbytes = item(cfg) * (rows * d + active * fused + rows * h)
    return flops, float(nbytes)


def expert_params(cfg: Dict) -> int:
    """One KAN-FFN expert: fused KAN up, linear down and its bias."""
    d, h = cfg["d_model"], cfg["kan_hidden"]
    return d * h * (len(cfg["kan_basis_keep"]) + 1) + h * d + d


def token_params(cfg: Dict) -> float:
    """Weights one token passes through, the LM head aside: attention,
    norms and router whole, the experts at their expected share."""
    m = transformer_dims(cfg)
    d, hd = m["d"], m["hd"]
    attn = d * m["q"] + 2 * d * m["kv"] + m["q"] * d + 2 * hd
    routed = cfg["top_k"] * cfg["num_experts"] / cfg["router_experts"]
    layer = (attn + 2 * d + d * cfg["router_experts"]
             + routed * expert_params(cfg))
    return len(cfg["ffn_kinds"]) * layer + d


def prefill_flops(cfg: Dict, length: int) -> float:
    """One prompt of ``length`` tokens: 2 FLOPs per weight per token,
    causal attention, and the LM head at the one position read."""
    m = transformer_dims(cfg)
    seen = length * (length + 1) / 2.0
    attn = len(cfg["ffn_kinds"]) * 2 * 2 * seen * m["q"]
    return (2.0 * token_params(cfg) * length + attn
            + 2.0 * m["d"] * m["V"])


def decode_flops(cfg: Dict, contexts: Sequence[int]) -> float:
    """One decode step: per active request, its weights once, attention
    over the ``context + 1`` positions it sees, and the LM head."""
    m = transformer_dims(cfg)
    n_layers = len(cfg["ffn_kinds"])
    per_tok = 2.0 * token_params(cfg) + 2.0 * m["d"] * m["V"]
    return sum(per_tok + n_layers * 2 * 2 * (c + 1) * m["q"]
               for c in contexts)
