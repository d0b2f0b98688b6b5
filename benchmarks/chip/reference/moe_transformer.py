"""Reference forward of the Qwen3-MoE decoder with KAN-FFN experts, float32.

The Qwen3-MoE block (Qwen/Qwen3-235B-A22B config.json, arXiv:2505.09388)
is pre-norm: ``x += Attn(RMSNorm(x))`` then ``x += MoE(RMSNorm(x))``.
Attention is causal grouped-query attention (query head h reads key/value
head h // (n_heads / n_kv_heads)) with unbiased q, k, v, o projections,
an RMSNorm over each head's dims of q and of k (``qk_norm``) before the
rotary embedding on the two halves of each head (base ``rope_theta``).
The MoE layer's router is a bias-free linear layer to all
``router_experts`` logits; softmax in float32, the top ``top_k``, their
weights renormalised to sum to 1, and the output is sum_k w_k expert_k(x).
The LM head is untied.  The norm and the rotary embedding are the Qwen2
reference's (reference/transformer.py).

Departures from the published model, as the configuration states them:

* every expert is the KAN-FFN (a KAN layer up, stacks.kan_layer, then a
  linear layer down with bias) in place of the SwiGLU;
* the experts are one chip's share: only experts ``first_expert`` ..
  ``first_expert + num_experts - 1`` are computed, and an assignment to
  any other expert adds nothing here (as in the program: the other chips
  of the expert-parallel group would add it);
* ``num_hidden_layers`` of the published 94 (one pipeline stage, which
  here also holds the embedding and the head);
* no multi-token-prediction head.

Every token is run through every held expert, weighted by its gate for
that expert (zero where the router did not pick it): no grouping, no
capacity.  The weights are the served ones, in the program's layout: a
layer's ``moe/experts/wt`` holds each expert's KAN weights as (hidden,
input) with the columns of input feature p at p*(G+K+1) ..
p*(G+K+1)+G+K, first the silu weight ``w_b[p]`` and then the spline
weights ``t[p, 0..G+K-1]``; ``w`` (hidden, d_model) and ``b`` are the
down-projection.  The hidden axis of ``wt`` and ``w`` is stored rounded
up to 8 rows; the rows past ``kan_hidden`` are not part of the model.
The model runs one layer at a time and the head in vocabulary blocks,
each converted to float32 only while it is used, so that it fits beside
the bfloat16 weights.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import HIGHEST, matmul
from .stacks import kan_layer
from .transformer import rmsnorm, rope

# Widest vocabulary block of the head converted to float32 at a time.
HEAD_BLOCK = 32768


def attention(x: jax.Array, p: Dict, cfg: Dict, mode: str) -> jax.Array:
    length = x.shape[0]
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = matmul(x, p["wq"]["kernel"], mode).reshape(length, h, hd)
    k = matmul(x, p["wk"]["kernel"], mode).reshape(length, kv, hd)
    v = matmul(x, p["wv"]["kernel"], mode).reshape(length, kv, hd)
    q = rope(rmsnorm(q, p["q_norm"]["scale"], eps), cfg["rope_theta"])
    k = rope(rmsnorm(k, p["k_norm"]["scale"], eps), cfg["rope_theta"])
    causal = jnp.tril(jnp.ones((length, length), bool))
    group = h // kv
    outs = []
    for j in range(kv):             # one key/value head's queries at a time
        qj = q[:, j * group:(j + 1) * group]
        s = jnp.einsum("qhd,kd->hqk", qj, k[:, j],
                       precision=HIGHEST) / np.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, axis=-1),
                               v[:, j], precision=HIGHEST))
    o = jnp.concatenate(outs, axis=1).reshape(length, h * hd)
    return matmul(o, p["wo"]["kernel"], mode)


def experts(x: jax.Array, p: Dict, cfg: Dict, mode: str) -> jax.Array:
    """The held experts' part of the MoE layer's output."""
    d, nb = cfg["d_model"], cfg["kan_grid"] + cfg["kan_order"]
    held, first = cfg["num_experts"], cfg["first_expert"]
    probs = jax.nn.softmax(matmul(x, p["router"]["kernel"], mode), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg["top_k"])
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    e, h = p["experts"], cfg["kan_hidden"]

    def one(i, out):
        def pick(a):
            return jax.lax.dynamic_index_in_dim(a, i, keepdims=False)

        gate = jnp.sum(jnp.where(top_e == first + i, top_p, 0.0), axis=-1)
        w = pick(e["wt"])[:h].reshape(h, d, nb + 1).transpose(1, 2, 0)
        hid = kan_layer(x, w[:, 0], w[:, 1:],
                        cfg["kan_basis_keep"], cfg["spline"],
                        cfg["kan_grid"], cfg["kan_order"], mode)
        y = matmul(hid, pick(e["w"])[:h], mode) + pick(e["b"]).astype(
            jnp.float32)
        return out + gate[:, None] * y

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))


def layer(x: jax.Array, p: Dict, cfg: Dict, mode: str) -> jax.Array:
    eps = cfg["rms_norm_eps"]
    x = x + attention(rmsnorm(x, p["attn_norm"]["scale"], eps), p["attn"],
                      cfg, mode)
    return x + experts(rmsnorm(x, p["moe_norm"]["scale"], eps), p["moe"],
                       cfg, mode)


def _head(h: jax.Array, kernel: jax.Array, mode: str) -> jax.Array:
    return matmul(h, kernel, mode)


class ReferenceMoE:
    """The model on the served weights, each layer's converted to float32
    inside its own call.  Every weight is an argument of the jitted
    pieces, never a constant folded into them."""

    def __init__(self, params: Dict, cfg: Dict, mode: str = "highest",
                 positions: int = 16) -> None:
        self.params, self.cfg, self.mode = params, cfg, mode
        self.positions = positions
        self._layer = jax.jit(functools.partial(layer, cfg=cfg, mode=mode))
        self._norm = jax.jit(functools.partial(
            rmsnorm, eps=cfg["rms_norm_eps"]))
        self._head = jax.jit(functools.partial(_head, mode=mode))

    def logits(self, tokens: np.ndarray, positions: Sequence[int]
               ) -> jax.Array:
        """Logits (len(positions), vocab) of one sequence, each position
        seeing the tokens up to and including it.  Positions are padded
        to a multiple of ``self.positions`` so that one program serves
        every request of a cell."""
        p = self.params
        x = p["embed"]["table"][jnp.asarray(tokens)].astype(jnp.float32)
        for lp in p["extra"]:
            x = self._layer(x, lp)
        n = len(positions)
        padded = np.zeros(-(-n // self.positions) * self.positions, np.int32)
        padded[:n] = positions
        h = self._norm(x[jnp.asarray(padded)], p["final_norm"]["scale"])
        kernel = p["lm_head"]["kernel"]
        vocab = kernel.shape[1]
        blocks = -(-vocab // HEAD_BLOCK)
        width = -(-vocab // blocks)
        return jnp.concatenate(
            [self._head(h, kernel[:, a:a + width])
             for a in range(0, vocab, width)], axis=1)[:n]
