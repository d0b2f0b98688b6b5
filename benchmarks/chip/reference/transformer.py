"""Reference forward of the Qwen2 decoder with KAN-FFN layers, float32.

Each layer is pre-norm: ``x += Attn(RMSNorm(x))`` then ``x += FFN(RMSNorm(x))``.
Attention is causal grouped-query attention (query head h reads key/value
head h // (n_heads / n_kv_heads)) with biased q, k, v projections, rotary
embeddings on the two halves of each head (base ``rope_theta``), and an
unbiased output projection.  The FFN is SwiGLU, ``down(silu(gate x) *
up x)``, or the KAN-FFN, a KAN layer up (stacks.kan_layer) and a linear
layer down over the kept hidden lanes.  The LM head is the tied
embedding.  The model runs one layer at a time on one sequence, so it
fits beside nothing else on the chip.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import HIGHEST, matmul
from .stacks import kan_layer


def rmsnorm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def rope(x: jax.Array, base: float) -> jax.Array:
    """Rotary embedding of (L, heads, hd) at positions 0..L-1."""
    length, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x: jax.Array, p: Dict, cfg: Dict, mode: str) -> jax.Array:
    length = x.shape[0]
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]

    def proj(w):
        y = matmul(x, w["kernel"], mode)
        return y + w["bias"] if "bias" in w else y

    q = rope(proj(p["wq"]).reshape(length, h, hd), cfg["rope_theta"])
    k = rope(proj(p["wk"]).reshape(length, kv, hd), cfg["rope_theta"])
    v = proj(p["wv"]).reshape(length, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((length, length), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    return matmul(o.reshape(length, h * hd), p["wo"]["kernel"], mode)


def ffn(x: jax.Array, p: Dict, kind: str, cfg: Dict, mode: str
        ) -> jax.Array:
    if kind == "mlp":
        g = jax.nn.silu(matmul(x, p["gate"]["kernel"], mode))
        return matmul(g * matmul(x, p["up"]["kernel"], mode),
                      p["down"]["kernel"], mode)
    hid = kan_layer(x, p["kan_up"]["w_b"], p["kan_up"]["t"],
                    cfg["kan_basis_keep"], cfg["spline"], cfg["kan_grid"],
                    cfg["kan_order"], mode)
    keep = jnp.asarray(cfg["kan_hidden_keep"])
    return matmul(hid[:, keep], p["w"][keep], mode) + p["b"]


def layer(x: jax.Array, p: Dict, kind: str, cfg: Dict, mode: str
          ) -> jax.Array:
    eps = cfg["rms_norm_eps"]
    x = x + attention(rmsnorm(x, p["attn_norm"]["scale"], eps), p["attn"],
                      cfg, mode)
    return x + ffn(rmsnorm(x, p["ffn_norm"]["scale"], eps), p["ffn"], kind,
                   cfg, mode)


class ReferenceLM:
    """The model on float32 copies of the served weights.  Every weight is
    an argument of the jitted pieces, never a constant folded into them."""

    def __init__(self, params: Dict, cfg: Dict, mode: str = "highest",
                 positions: int = 256) -> None:
        self.params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                   params)
        self.cfg, self.mode = cfg, mode
        self.positions = positions
        self._layer = {kind: jax.jit(functools.partial(
            layer, kind=kind, cfg=cfg, mode=mode))
            for kind in set(cfg["ffn_kinds"])}
        self._head = jax.jit(functools.partial(
            _head, eps=cfg["rms_norm_eps"], mode=mode))

    def logits(self, tokens: np.ndarray, positions: Sequence[int]
               ) -> jax.Array:
        """Logits (len(positions), vocab) of one sequence, each position
        seeing the tokens up to and including it.  Positions are padded
        to a multiple of ``self.positions`` so that one program serves
        every request of a cell."""
        table = self.params["embed"]["table"]
        x = table[jnp.asarray(tokens)]
        for p, kind in zip(self.params["extra"], self.cfg["ffn_kinds"]):
            x = self._layer[kind](x, p)
        n = len(positions)
        padded = np.zeros(-(-n // self.positions) * self.positions, np.int32)
        padded[:n] = positions
        return self._head(x, jnp.asarray(padded), table,
                          self.params["final_norm"]["scale"])[:n]


def _head(x: jax.Array, positions: jax.Array, table: jax.Array,
          scale: jax.Array, eps: float, mode: str) -> jax.Array:
    """Final norm and the tied LM head at ``positions``."""
    return matmul(rmsnorm(x[positions], scale, eps), table.T, mode)


def token_gaps(ref_logits: jax.Array, tokens: Sequence[int]) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best at its position (0 where the token is the reference's argmax)."""
    lg = jnp.asarray(ref_logits, jnp.float32)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(tokens, jnp.int32)[:, None], axis=1)[:, 0]
    return np.asarray(jnp.max(lg, axis=1) - picked)


def served_sequence(prompt: np.ndarray, served: List[int], pad_to: int
                    ) -> tuple:
    """(padded token sequence, positions whose logits chose ``served``):
    served token j came from the logits at position len(prompt) - 1 + j,
    after the prompt and served tokens 0..j-1.  Padding after the last
    position changes no earlier position under the causal mask."""
    seq = np.concatenate([np.asarray(prompt, np.int64),
                          np.asarray(served[:-1], np.int64)])
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} tokens exceeds {pad_to}")
    padded = np.zeros(pad_to, np.int32)
    padded[: len(seq)] = seq
    start = len(prompt) - 1
    return padded, list(range(start, start + len(served)))
