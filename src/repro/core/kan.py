"""KAN layers (paper Eq. 1-3) as composable, functional JAX modules.

A KAN layer phi: R^{n_in} -> R^{n_out} is

    phi(x)_q = sum_p  w_b[p,q] silu(x_p)  +  sum_p sum_i  t[p,i,q] B_i(x_p)

with t_i = w_s * c_i pre-folded (hardware-friendly form, Eq. 3).  Stage-2
pattern sparsity over the basis dimension is carried in the config as a
static mask; weights are compacted at trace time so every execution path
(Pallas fused kernel, XLA) contracts over the shrunken dimension.

Accuracy scaling: ``extend_grid`` refits the spline coefficients onto a finer
grid (larger G) by least squares -- the paper's "boost accuracy without
retraining from scratch" mechanism (Sec. II-B, Fig. 8).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparsity import PatternMask, tiled_mask
from repro.core.splines import (
    SplineSpec,
    bases_dense,
    dense_eval_op_count,
    silu,
    spu_op_count,
)
from repro.kernels.kan_fused.ops import (
    DEFAULT_VERSION,
    flatten_t,
    fuse_wt,
    kan_linear,
)

Params = Dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class KANConfig:
    n_in: int
    n_out: int
    spec: SplineSpec = SplineSpec(4, 3)          # paper default: G=4, K=3
    pattern: Optional[Tuple[int, ...]] = None    # tiled 4-bit stage-2 mask
    # calibrated (grouped, per-group independent) mask: explicit kept basis
    # indices, e.g. from core/calibrate.  Takes precedence over ``pattern``.
    basis_keep: Optional[Tuple[int, ...]] = None
    impl: str = "auto"                           # kernel dispatch
    version: int = DEFAULT_VERSION               # fused-kernel generation
    blocks: Optional[Tuple[int, int, int]] = None  # (bm, bi, bn) override;
    # None -> autotune-cache lookup, then kernel defaults

    @property
    def basis_mask(self) -> Optional[PatternMask]:
        if self.basis_keep is not None:
            keep = np.zeros(self.spec.n_bases, bool)
            keep[list(self.basis_keep)] = True
            return PatternMask(keep)
        if self.pattern is None:
            return None
        return tiled_mask(self.spec.n_bases, self.pattern)

    @property
    def kb(self) -> Optional[Tuple[int, ...]]:
        """Kept basis indices (static) under the stage-2 mask."""
        m = self.basis_mask
        return None if m is None else tuple(int(i) for i in m.indices())

    @property
    def n_bases_kept(self) -> int:
        kb = self.kb
        return self.spec.n_bases if kb is None else len(kb)

    def param_count(self) -> int:
        return self.n_in * self.n_out * (1 + self.spec.n_bases)


def kan_init(key: jax.Array, cfg: KANConfig,
             dtype: Any = jnp.float32) -> Params:
    """KAN-paper style init: w_b Kaiming-ish, spline coefficients small."""
    k1, k2 = jax.random.split(key)
    scale_b = 1.0 / np.sqrt(cfg.n_in)
    w_b = jax.random.uniform(
        k1, (cfg.n_in, cfg.n_out), dtype, -scale_b, scale_b
    )
    # noise-scale init of c_i (KAN reference uses scale_noise=0.1 on grid);
    # the numpy scale would promote a bf16 draw to f32, so cast back
    t = (0.1 * scale_b * jax.random.normal(
        k2, (cfg.n_in, cfg.spec.n_bases, cfg.n_out), dtype
    )).astype(dtype)
    return {"w_b": w_b, "t": t}


def kan_apply(params: Params, x: jax.Array, cfg: KANConfig) -> jax.Array:
    """Apply the layer; leading batch dims arbitrary."""
    t_flat = flatten_t(params["t"], cfg.kb)
    return kan_linear(x, params["w_b"], t_flat, cfg.spec, cfg.kb,
                      impl=cfg.impl, version=cfg.version, blocks=cfg.blocks)


def kan_fused_weights(params: Params, cfg: KANConfig) -> jax.Array:
    """Build-time fused [w_b ; t] layout shared by the v2 kernel and the jnp
    path (rows interleaved per input feature; see ops.fuse_wt)."""
    return fuse_wt(params["w_b"], flatten_t(params["t"], cfg.kb),
                   cfg.n_bases_kept)


def kan_stack_apply(
    params_list: Sequence[Params], x: jax.Array,
    cfgs: Sequence[KANConfig], return_hidden: bool = False
) -> Union[jax.Array, Tuple[jax.Array, List[jax.Array]]]:
    """Compose L KAN layers: KAN(x) = phi_{L-1} o ... o phi_0 (paper Eq. 1)."""
    hidden = []
    for p, c in zip(params_list, cfgs):
        x = kan_apply(p, x, c)
        hidden.append(x)
    return (x, hidden) if return_hidden else x


# ---------------------------------------------------------------------------
# Accuracy scaling: grid extension (coarse G -> fine G) by least squares.
# ---------------------------------------------------------------------------

def extend_grid(
    params: Params, cfg: KANConfig, new_grid_size: int, n_samples: int = 512
) -> Tuple[Params, KANConfig]:
    """Refit spline coefficients on a finer grid; function preserved approx.

    Solves min_t' || A_new t' - A_old t ||^2 on a dense x sample, per
    (input feature, output) pair, sharing one pseudo-inverse.
    """
    old, new = cfg.spec, dataclasses.replace(cfg.spec, grid_size=new_grid_size)
    xs = jnp.linspace(old.x0, old.x1 - 1e-5, n_samples, dtype=jnp.float32)
    a_old = bases_dense(xs, old)                      # (S, nb_old)
    a_new = bases_dense(xs, new)                      # (S, nb_new)
    pinv = jnp.linalg.pinv(a_new)                     # (nb_new, S)
    # y[s, p, o] = sum_i a_old[s, i] t[p, i, o]
    y = jnp.einsum("si,pio->spo", a_old, params["t"].astype(jnp.float32))
    t_new = jnp.einsum("ns,spo->pno", pinv, y).astype(params["t"].dtype)
    new_cfg = dataclasses.replace(cfg, spec=new)
    return {"w_b": params["w_b"], "t": t_new}, new_cfg


# ---------------------------------------------------------------------------
# Operation accounting (feeds engine.py, Fig. 8 and the roofline tables).
# ---------------------------------------------------------------------------

def kan_op_counts(cfg: KANConfig, batch: int = 1) -> Dict[str, float]:
    """Theoretical op counts for one layer application.

    "dense"  -- all G+K bases evaluated and MAC'd (what Fig. 8's "ops" axis
                counts; grows with G).
    "vikin"  -- stage-1 zero-free: K+1 basis evals (SPU) + K+1 MACs per
                (input, output), silu branch unchanged.
    "vikin_pattern" -- additionally drops masked basis nodes from the MAC.
    """
    s = cfg.spec
    n_in, n_out = cfg.n_in, cfg.n_out
    silu_ops = 6 * n_in                       # sigmoid approx + mul
    dense_mac = 2 * n_in * n_out * (s.n_bases + 1)
    dense_eval = n_in * dense_eval_op_count(s)
    spu_eval = n_in * spu_op_count(s)
    nnz = s.n_active
    vikin_mac = 2 * n_in * n_out * (nnz + 1)
    kept = cfg.n_bases_kept
    # kept basis columns that are also inside the structural K+1 window:
    # expected overlap = nnz * kept / n_bases for a tiled mask.
    kept_nnz = nnz * kept / s.n_bases
    pattern_mac = 2 * n_in * n_out * (kept_nnz + 1)
    return {
        "dense": batch * (silu_ops + dense_eval + dense_mac),
        "vikin": batch * (silu_ops + spu_eval + vikin_mac),
        "vikin_pattern": batch * (silu_ops + spu_eval + pattern_mac),
        "dense_mac": batch * dense_mac,
        "vikin_mac": batch * vikin_mac,
        "pattern_mac": batch * pattern_mac,
        "spu_eval": batch * spu_eval,
        "silu": batch * silu_ops,
    }


def kan_reference_dense(params: Params, x: jax.Array,
                        cfg: KANConfig) -> jax.Array:
    """Slow dense-oracle apply (tests); honors the stage-2 mask."""
    xf = x.reshape(-1, cfg.n_in).astype(jnp.float32)
    b = bases_dense(cfg.spec.clip(xf), cfg.spec)
    m = cfg.basis_mask
    if m is not None:
        b = b * jnp.asarray(m.keep.astype(np.float32))
    y = silu(xf) @ params["w_b"].astype(jnp.float32)
    y = y + jnp.einsum("bpi,pio->bo", b, params["t"].astype(jnp.float32))
    return y.reshape(*x.shape[:-1], cfg.n_out).astype(x.dtype)
