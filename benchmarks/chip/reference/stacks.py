"""Reference forward of a KAN/MLP stack (paper Eqs. 1-5), float32.

A KAN layer is

    phi(x)_q = sum_p w_b[p, q] silu(x_p) + sum_p sum_i t[p, i, q] B_i(x_p)

with B_i the order-K B-splines on a uniform grid of G intervals over the
spline domain, extended by K knots on each side (Cox-de Boor recursion,
Eqs. 4-5); the bases see the input clamped into the domain, the silu
branch the raw input.  Only the kept bases enter the sum (stage-2
sparsity).  An MLP layer is ``act(x[kept] @ w[kept] + b)`` with ReLU on
every layer but the last.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from . import matmul


def bspline_bases(x: jax.Array, grid: int, order: int, lo: float,
                  hi: float) -> jax.Array:
    """All ``grid + order`` basis values at ``x``: shape x.shape + (G+K,)."""
    h = (hi - lo) / grid
    knots = lo + (jnp.arange(grid + 2 * order + 1, dtype=jnp.float32)
                  - order) * h
    xe = x[..., None]
    b = ((xe >= knots[:-1]) & (xe < knots[1:])).astype(jnp.float32)
    for k in range(1, order + 1):
        left = (xe - knots[:-(k + 1)]) / (knots[k:-1] - knots[:-(k + 1)])
        right = (knots[k + 1:] - xe) / (knots[k + 1:] - knots[1:-k])
        b = left * b[..., :-1] + right * b[..., 1:]
    return b


def kan_layer(x: jax.Array, w_b: jax.Array, t: jax.Array, keep: List[int],
              spline: Dict, grid: int, order: int, mode: str) -> jax.Array:
    lo, hi = spline["domain"]
    xc = jnp.clip(x, lo, hi - spline["clamp_eps"] * (hi - lo))
    bases = bspline_bases(xc, grid, order, lo, hi)[..., jnp.asarray(keep)]
    t_kept = t.astype(jnp.float32)[:, jnp.asarray(keep), :]
    rows, n_in = x.shape
    y = matmul(jax.nn.silu(x), w_b, mode)
    return y + matmul(bases.reshape(rows, n_in * len(keep)),
                      t_kept.reshape(n_in * len(keep), -1), mode)


def mlp_layer(x: jax.Array, w: jax.Array, b: jax.Array, keep: List[int],
              last: bool, mode: str) -> jax.Array:
    idx = jnp.asarray(keep)
    y = matmul(x[:, idx], w.astype(jnp.float32)[idx], mode)
    y = y + b.astype(jnp.float32)
    return y if last else jax.nn.relu(y)


def stack_forward(params: list, x: jax.Array, layers: List[Dict],
                  spline: Dict, grid: int, order: int,
                  mode: str = "highest") -> jax.Array:
    """The stack on a (rows, n_in) float32 batch; ``layers`` from
    ``counts.stack_layers``."""
    h = x.astype(jnp.float32)
    for i, (p, layer) in enumerate(zip(params, layers)):
        if layer["kind"] == "kan":
            h = kan_layer(h, p["w_b"], p["t"], layer["basis_keep"], spline,
                          grid, order, mode)
        else:
            h = mlp_layer(h, p["w"], p["b"], layer["in_keep"],
                          i == len(layers) - 1, mode)
    return h
