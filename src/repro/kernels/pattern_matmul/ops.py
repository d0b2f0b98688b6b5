"""Public entry: pattern-sparse linear layer (static m-of-4 compaction).

``pattern_linear`` takes the ORIGINAL weight and a PatternMask over its input
dimension; compaction happens here (static, at trace time) so both the Pallas
path and the XLA fallback contract over the shrunken dimension -- the FLOP /
byte saving is visible to cost_analysis either way.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparsity import PatternMask
from repro.kernels import autotune
from repro.kernels.pattern_matmul.pattern_matmul import (
    DEFAULT_BK,
    DEFAULT_BM,
    DEFAULT_BN,
    matmul_compact_pallas,
    matmul_q8_pallas,
)
from repro.kernels.epilogue import bias_act, scale_bias_act
from repro.kernels.tiling import gemm_rows


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_blocks(
    M: int, K: int, N: int, dtype,
    blocks: Optional[Tuple[int, int, int]] = None,
    backend: Optional[str] = None,
) -> Dict[str, int]:
    """(bm, bk, bn) for the compact matmul: explicit > cached > defaults.

    ``backend`` selects the cache namespace: interpret-mode callers pass
    "cpu" to reach entries stored by ``tune_pattern_matmul(interpret=True)``.
    """
    if blocks is not None:
        bm, bk, bn = blocks
        return {"bm": bm, "bk": bk, "bn": bn}
    hit = autotune.lookup_blocks("pattern_matmul", (M, K, N), dtype,
                                 backend=backend)
    if hit is not None:
        return hit
    return {"bm": DEFAULT_BM, "bk": DEFAULT_BK, "bn": DEFAULT_BN}


def pattern_linear(
    x: jax.Array,
    w: jax.Array,
    mask: Optional[PatternMask] = None,
    bias: Optional[jax.Array] = None,
    *,
    act: Optional[str] = None,
    impl: str = "auto",
    blocks: Optional[Tuple[int, int, int]] = None,
) -> jax.Array:
    """y = act(x[..., keep] @ w[keep, :] + bias).

    x: (..., K); w: (K, N).  With mask=None this is a plain fused linear.
    ``blocks`` overrides the (bm, bk, bn) tiles; None consults the autotune
    cache before falling back to the defaults.
    """
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    if mask is not None:
        idx = jnp.asarray(mask.indices())
        xf = jnp.take(xf, idx, axis=1)       # static gather (slices/copies)
        w = jnp.take(w, idx, axis=0)         # folded at compile time
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"
    if impl in ("pallas", "pallas_interpret"):
        bk = resolve_blocks(
            xf.shape[0], xf.shape[1], w.shape[1], x.dtype, blocks,
            backend="cpu" if impl == "pallas_interpret" else None)
        y = matmul_compact_pallas(xf, w, bias, act=act,
                                  interpret=(impl == "pallas_interpret"),
                                  **bk)
    elif impl == "jnp":
        acc = jnp.dot(gemm_rows(xf), w,
                      preferred_element_type=jnp.float32)[:xf.shape[0]]
        # the SAME epilogue the Pallas kernel fuses (VL002 contract)
        y = bias_act(acc, bias, act, x.dtype)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return y.reshape(*lead, w.shape[-1])


def pattern_linear_q8(
    x_q: jax.Array,
    w_q: jax.Array,
    col_scale: jax.Array,
    mask: Optional[PatternMask] = None,
    bias: Optional[jax.Array] = None,
    *,
    act: Optional[str] = None,
    impl: str = "auto",
    blocks: Optional[Tuple[int, int, int]] = None,
) -> jax.Array:
    """Int8 pattern-sparse linear: y = act(dq(x_q) @ dq(w_q) + bias), f32 out.

    x_q: (..., K) int8; w_q: (K, N) int8; col_scale: (N,) f32 = s_x * s_w
    per output channel.  Both operands stay int8 through compaction and
    DMA; both impls accumulate exact f32 integers (products <= 127^2 and
    K small enough that partial sums stay < 2^24, so tiling order cannot
    change the accumulator), then share ONE epilogue below -- which makes
    the tiled Pallas path and the jnp oracle BITWISE identical (see
    core/quant's f32-accumulate contract).  Output is always f32 (the
    caller requantizes to the next layer's scale, or emits as-is).
    """
    lead = x_q.shape[:-1]
    xf = x_q.reshape(-1, x_q.shape[-1])
    if mask is not None:
        idx = jnp.asarray(mask.indices())
        xf = jnp.take(xf, idx, axis=1)       # int8 gather, still compacted
        w_q = jnp.take(w_q, idx, axis=0)
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"
    if impl in ("pallas", "pallas_interpret"):
        bk = resolve_blocks(
            xf.shape[0], xf.shape[1], w_q.shape[1], x_q.dtype, blocks,
            backend="cpu" if impl == "pallas_interpret" else None)
        acc = matmul_q8_pallas(xf, w_q,
                               interpret=(impl == "pallas_interpret"), **bk)
    elif impl == "jnp":
        acc = jnp.dot(xf.astype(jnp.float32), w_q.astype(jnp.float32),
                      preferred_element_type=jnp.float32)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    # Shared dequantization epilogue: applied once AFTER full accumulation,
    # identically for both impls (keeping it out of the kernel avoids an
    # FMA single-rounding divergence between interpret and eager jnp).
    y = scale_bias_act(acc, col_scale, bias, act)
    return y.reshape(*lead, w_q.shape[-1])
