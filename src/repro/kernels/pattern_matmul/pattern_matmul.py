"""Pallas TPU kernel: pattern-sparse matmul (TSE stage-2 on the MXU).

The m-of-4 pattern mask is static, so the contraction dimension is
pre-compacted OUTSIDE the kernel (weight rows dropped at build time,
activation lanes gathered by ``ops.py``).  The kernel itself is then a dense
tiled matmul over the *shrunken* K dimension with fp32 accumulation in VMEM
scratch and a fused bias+activation epilogue -- the MXU analogue of the PE
array receiving a zero-free dense stream from the TSE (paper Fig. 5b).

Tiling: grid (M/bm, N/bn, Kc/bk), k innermost so the (bm,bn) accumulator
lives across k-steps.  Every call fits its blocks to the shape
(``fit_blocks``): bk and bn 128-lane aligned or the whole axis, bm a
multiple of the dtype's sublane rows; defaults keep x-block + w-block + acc
comfortably inside one core's VMEM (bm*bk + bk*bn at 2B plus bm*bn at 4B
~= 196 KiB at 128/512/128).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogue import bias_act
from repro.kernels.tiling import LANES, fit_block, fit_rows, mxu_precision

DEFAULT_BM = 128
DEFAULT_BK = 512
DEFAULT_BN = 128


def fit_blocks(M: int, Kc: int, N: int, dtype, bm: int, bk: int,
               bn: int):
    """Blocks Mosaic accepts for an (M, Kc) x (Kc, N) contraction."""
    return (fit_rows(bm, M, dtype), fit_block(bk, Kc, LANES),
            fit_block(bn, N, LANES))


def _mm_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, k_steps: int, act):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], precision=mxu_precision(x_ref.dtype),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        # shared with the jnp fallback and the dense oracle (VL002 contract)
        o_ref[...] = bias_act(acc_ref[...], b_ref[...], act, o_ref.dtype)


def _mm_kernel_q8(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    # Int8 variant: operands arrive as raw int8 codes and are widened to
    # f32 ON LOAD; the accumulator then holds exact integers (|x*w| <=
    # 127^2, K small enough that partial sums stay < 2^24), so tiled
    # accumulation is bitwise identical to a single dot regardless of
    # k-step order.  The kernel emits the RAW integer accumulator: the
    # symmetric scale s_x * s_w[col], bias, and activation are applied by
    # the shared wrapper epilogue (ops.pattern_linear_q8) -- fusing them
    # here would FMA `acc * s + b` into one rounding while the eager jnp
    # oracle rounds twice, breaking the bitwise jnp==pallas contract.
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "interpret"),
)
def matmul_q8_pallas(
    x_q: jax.Array,          # (M, Kc) int8 pre-compacted activations
    w_q: jax.Array,          # (Kc, N) int8 pre-compacted weights
    *,
    bm: int = DEFAULT_BM,
    bk: int = DEFAULT_BK,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> jax.Array:
    """Exact-integer int8 matmul: f32 out holding sum(x_q * w_q) per cell."""
    M, Kc = x_q.shape
    Kc2, N = w_q.shape
    assert Kc == Kc2, (Kc, Kc2)

    bm, bk, bn = fit_blocks(M, Kc, N, x_q.dtype, bm, bk, bn)
    # Int8 zero pads are matmul-neutral just like f32 zeros.
    pm, pk, pn = -M % bm, -Kc % bk, -N % bn
    xp = jnp.pad(x_q, ((0, pm), (0, pk)))
    wp = jnp.pad(w_q, ((0, pk), (0, pn)))
    Mp, Kp, Np = M + pm, Kc + pk, N + pn
    k_steps = Kp // bk

    out = pl.pallas_call(
        functools.partial(_mm_kernel_q8, k_steps=k_steps),
        grid=(Mp // bm, Np // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, wp)
    return out[:M, :N]


@functools.partial(
    jax.jit,
    static_argnames=("act", "bm", "bk", "bn", "interpret", "out_dtype"),
)
def matmul_compact_pallas(
    x_c: jax.Array,          # (M, Kc) pre-compacted activations
    w_c: jax.Array,          # (Kc, N) pre-compacted weights
    bias: Optional[jax.Array] = None,   # (N,)
    *,
    act: Optional[str] = None,
    bm: int = DEFAULT_BM,
    bk: int = DEFAULT_BK,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    M, Kc = x_c.shape
    Kc2, N = w_c.shape
    assert Kc == Kc2, (Kc, Kc2)
    out_dtype = out_dtype or x_c.dtype
    if bias is None:
        bias = jnp.zeros((N,), out_dtype)

    bm, bk, bn = fit_blocks(M, Kc, N, x_c.dtype, bm, bk, bn)
    # Pad every dim up to its block size (zero pads are matmul-neutral).
    pm, pk, pn = -M % bm, -Kc % bk, -N % bn
    xp = jnp.pad(x_c, ((0, pm), (0, pk)))
    wp = jnp.pad(w_c, ((0, pk), (0, pn)))
    bp = jnp.pad(bias, (0, pn))[None, :]  # (1, Np) so it blocks along N
    Mp, Kp, Np = M + pm, Kc + pk, N + pn
    k_steps = Kp // bk

    out = pl.pallas_call(
        functools.partial(_mm_kernel, k_steps=k_steps, act=act),
        grid=(Mp // bm, Np // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, wp, bp)
    return out[:M, :N]
