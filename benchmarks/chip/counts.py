"""Operations and bytes each layer's algorithm needs, from shapes alone.

These counts are the yardstick for roofline and utilization shares: they
say what a call has to do, not what today's kernel does.  The fused KAN
kernel, for one, repeats every input feature nbk+1 times and recomputes
its spline tile for every output block; none of that is counted, so a
better kernel raises its share and no kernel can read above 100%.

A FLOP is one multiply or one add (a MAC is two).  Bytes count each
operand and result once, in the served dtype.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple


def kan_layer(rows: int, n_in: int, n_out: int, n_kept: int,
              itemsize: int) -> Tuple[float, float]:
    """KAN layer (paper Eq. 3) with ``n_kept`` basis functions kept per
    input: the silu branch and the kept spline branches, one MAC each per
    (row, input, output); reads the input and the fused [w_b; t] weights
    once, writes the output once.  The spline evaluation runs on the
    vector units and adds no MXU work."""
    flops = 2.0 * rows * n_in * (n_kept + 1) * n_out
    nbytes = itemsize * (rows * n_in + n_in * (n_kept + 1) * n_out
                         + rows * n_out)
    return flops, float(nbytes)


def pattern_matmul(rows: int, k_kept: int, n_out: int, itemsize: int,
                   bias: bool = True) -> Tuple[float, float]:
    """Pattern-sparse linear: the kept m-of-4 inputs only, against the
    compacted weights (plus the bias, read once)."""
    flops = 2.0 * rows * k_kept * n_out
    nbytes = itemsize * (rows * k_kept + k_kept * n_out + rows * n_out
                         + (n_out if bias else 0))
    return flops, float(nbytes)


def least_time_s(flops: float, nbytes: float, peaks: Dict) -> float:
    """The shortest time the chip could take: compute- or memory-bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


# --------------------------------------------------------------- stacks
def stack_request_flops(layers: list) -> float:
    """Model FLOPs of one request (one row) through a KAN/MLP stack, given
    its ``stack_layers``."""
    total = 0.0
    for layer in layers:
        if layer["kind"] == "kan":
            total += kan_layer(1, layer["n_in"], layer["n_out"],
                               len(layer["basis_keep"]), 4)[0]
        else:
            total += pattern_matmul(1, len(layer["in_keep"]),
                                    layer["n_out"], 4)[0]
    return total


def stack_layers(model: Dict) -> list:
    """Per-layer shapes of a stack model entry of a configuration file."""
    sizes = model["sizes"]
    out = []
    for i, kind in enumerate(model["kinds"]):
        a, b = sizes[i], sizes[i + 1]
        keep = model["keep"][i]
        if kind == "kan":
            out.append({"kind": "kan", "n_in": a, "n_out": b,
                        "basis_keep": keep})
        else:
            out.append({"kind": "mlp", "n_in": a, "n_out": b,
                        "in_keep": keep if keep is not None
                        else list(range(a))})
    return out


# ---------------------------------------------------------- transformer
def transformer_dims(cfg: Dict) -> Dict[str, int]:
    d, hd = cfg["d_model"], cfg["head_dim"]
    return {"d": d, "hd": hd, "q": cfg["n_heads"] * hd,
            "kv": cfg["n_kv_heads"] * hd, "V": cfg["vocab_size"]}


def ffn_params(cfg: Dict, kind: str) -> int:
    d = cfg["d_model"]
    if kind == "kan":
        h = cfg["kan_hidden"]
        nk = len(cfg["kan_basis_keep"])
        return d * h * (nk + 1) + len(cfg["kan_hidden_keep"]) * d + d
    return 3 * d * cfg["d_ff"]


def nonembedding_params(cfg: Dict) -> int:
    """Weights every token passes through, the LM head aside."""
    m = transformer_dims(cfg)
    d = m["d"]
    attn = d * m["q"] + 2 * d * m["kv"] + m["q"] * d + m["q"] + 2 * m["kv"]
    total = d      # final norm
    for kind in cfg["ffn_kinds"]:
        total += attn + ffn_params(cfg, kind) + 2 * d
    return total


def prefill_flops(cfg: Dict, length: int) -> float:
    """One prompt of ``length`` tokens: 2 FLOPs per weight per token,
    causal attention (scores and values over the positions each query
    sees), and the LM head at the one position whose logits are used."""
    m = transformer_dims(cfg)
    n_layers = len(cfg["ffn_kinds"])
    seen = length * (length + 1) / 2.0
    attn = n_layers * 2 * 2 * seen * m["q"]
    return (2.0 * nonembedding_params(cfg) * length + attn
            + 2.0 * m["d"] * m["V"])


def decode_flops(cfg: Dict, contexts: Sequence[int]) -> float:
    """One decode step: per active request, every weight once, attention
    over the ``context + 1`` positions it sees, and the LM head."""
    m = transformer_dims(cfg)
    n_layers = len(cfg["ffn_kinds"])
    per_tok = 2.0 * nonembedding_params(cfg) + 2.0 * m["d"] * m["V"]
    return sum(per_tok + n_layers * 2 * 2 * (c + 1) * m["q"]
               for c in contexts)


def kanffn_kernel_calls(cfg: Dict, rows: int) -> Dict[str, Tuple[float,
                                                                 float]]:
    """(FLOPs, bytes) of ONE call of each kernel of a KAN-FFN layer for a
    ``rows``-row operand, in the served dtype."""
    item = 2 if cfg["dtype"] == "bfloat16" else 4
    d, h = cfg["d_model"], cfg["kan_hidden"]
    return {
        "kan": kan_layer(rows, d, h, len(cfg["kan_basis_keep"]), item),
        "pmm": pattern_matmul(rows, len(cfg["kan_hidden_keep"]), d, item),
    }
