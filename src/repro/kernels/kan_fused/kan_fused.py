"""Pallas TPU kernel: VIKIN *pipeline mode* as one fused VMEM pass.

On the FPGA, pipeline mode chains SIMD (silu) -> SPU array (bases) -> TSE
(zero-free compaction + pattern filter) -> PE array (MAC) so the sparse
(B, n_in, G+K) intermediate never leaves the datapath.  The TPU-native
equivalent is kernel fusion: one pallas_call computes, per (bm x bn) output
tile and bi-wide input-feature chunk,

  1. SIMD:  silu(x) on the VPU,
  2. SPU :  the K+1 non-zero basis values via the stage-buffer de Boor
            recursion (INV_LUT reciprocals, f32 interval location),
  3. TSE :  broadcast iota-comparison scatter of those values directly into
            the *compacted* activation layout -- when the stage-2 pattern
            mask is a tiled 4-bit pattern, only the kept basis columns are
            ever produced, so the MXU contraction below shrinks by keep/4
            (real stage-2 saving, batch-uniform),
  4. PE  :  MXU contraction(s) accumulated in fp32 VMEM scratch.

The (B, n_in*(G+K)) intermediate never touches HBM: that is the pipeline.

Two kernel generations are kept:

* **v1** (``kan_fused_pallas``): two MXU dispatches per grid step --
  ``silu(x) @ w_b`` and ``act_scattered @ t_compact`` accumulate separately
  into the same scratch.  Retained as the measured baseline for
  ``benchmarks/kernel_bench.py``.
* **v2** (``kan_fused_pallas_v2``, the default dispatch): ONE MXU dispatch
  per grid step.  The kernel forms a single activation tile
  ``[silu(x) | scattered_bases]`` of shape ``(bm, bi*(nbk+1))`` and
  contracts it once against a build-time row-interleaved weight matrix
  ``[w_b ; t]`` (``ops.fuse_wt``): per input feature, one silu row followed
  by its nbk spline rows.  Halves MXU dispatches and accumulator
  read-modify-writes per step; VPU work is unchanged.

TSE scatter: both kernels receive the kept-basis indices as an int32 *input
array* (Pallas forbids captured constant arrays) and scatter with
``delta = kb - cell`` plus exactly K+1 where-selects -- O(K+1) independent of
nbk, replacing the old Python-unrolled O(nbk*(K+1)) select chain.

v2 builds its fused tile in 2-D only, the layout Mosaic lowers: the wrapper
repeats each input feature nbk+1 times along the lanes (``x_rep``, column
``c`` holds feature ``c // (nbk+1)``), and a (1, bi*(nbk+1)) int32 row
``kb_cols`` names each column's slot: -1 for the silu slot, the kept basis
index for a spline slot.  The SPU/TSE arithmetic then runs per column on
(bm, bi*(nbk+1)) values, element for element the same as the per-feature
form, and no (bm, bi, nbk) value or lane-splitting reshape is ever formed.

Weight layouts: v1 takes ``t_flat`` (n_in * nbk, n_out), rows grouped by
input feature, basis-index fastest.  v2 takes the fused ``wt``
(n_in * (nbk+1), n_out) with the silu row interleaved first per feature.
kb (kept basis indices, static tuple) selects which of the G+K columns
exist; kb = range(G+K) when no pattern mask is set.

Block sizes (bm, bi, bn) are tunable per shape/dtype/backend through
``repro.kernels.autotune`` (see DESIGN.md Sec. 9); the defaults below are
the untuned fallback.  Every kernel call first fits its blocks to the shape
(``fit_blocks``): bi and bn 128-lane aligned or the whole axis, bm a
multiple of the dtype's sublane rows, and bm halved until the v2 tile fits
``VMEM_LIMIT`` under ``vmem_bytes``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.splines import INV_LUT, SplineSpec
from repro.kernels.tiling import (
    LANES,
    fit_block,
    fit_rows,
    mxu_precision,
    padded_bytes,
    sublanes,
)

DEFAULT_BM = 128
DEFAULT_BI = 128
DEFAULT_BN = 128

# Scoped VMEM a kernel may use: Mosaic's default limit on TPU v5e.
VMEM_LIMIT = 16 * 1024 * 1024

# MXU contractions issued per (bm, bn, i) grid step -- the quantity v2
# halves.  kernel_bench verifies these against the traced jaxpr.
MXU_DISPATCHES_PER_STEP = {1: 2, 2: 1}


def _spu_tile(x, spec: SplineSpec):
    """SIMD + SPU stages shared by both kernel generations.

    Returns (silu(x), [K+1 local basis value planes], cell int32), all shaped
    like ``x`` except the list entries.
    """
    dtype = x.dtype
    K = spec.order

    # --- SIMD core: silu branch (raw, un-clipped input; Eq. 3). -----------
    xf32 = x.astype(jnp.float32)
    s = (xf32 * jax.lax.logistic(xf32)).astype(dtype)

    # --- SPU array: interval location (f32, exact) + stage-buffer de Boor.
    eps = 1e-6 * (spec.x1 - spec.x0)
    xc = jnp.clip(xf32, spec.x0, spec.x1 - eps)
    u = (xc - spec.x0) * jnp.asarray(spec.inv_h, jnp.float32)
    cell = jnp.clip(jnp.floor(u), 0, spec.grid_size - 1)
    r = (u - cell).astype(dtype)
    cell_i = cell.astype(jnp.int32)

    rights = [jnp.asarray(d + 1.0, dtype) - r for d in range(K)]   # stage buf
    lefts = [r + jnp.asarray(d, dtype) for d in range(K)]
    vals = [jnp.ones_like(r)] + [jnp.zeros_like(r) for _ in range(K)]
    for j in range(1, K + 1):
        inv = jnp.asarray(INV_LUT[j], dtype)
        saved = jnp.zeros_like(r)
        for rr in range(j):
            temp = vals[rr] * inv
            vals[rr] = saved + rights[rr] * temp
            saved = lefts[j - rr - 1] * temp
        vals[j] = saved
    return s, vals, cell_i


def _tse_scatter(vals, cell_i, kb_row, nbk: int):
    """TSE: broadcast iota-comparison scatter into the kept-basis columns.

    ``kb_row`` is the (1, nbk) int32 kept-index array (a kernel INPUT, not a
    captured constant).  O(K+1) selects regardless of nbk.
    """
    bm, bi = cell_i.shape
    delta = kb_row.reshape(1, 1, nbk) - cell_i[..., None]    # (bm, bi, nbk)
    act = jnp.zeros((bm, bi, nbk), vals[0].dtype)
    for j in range(len(vals)):
        act = act + jnp.where(delta == j, vals[j][..., None], 0.0)
    return act


def _fused_tile(x_rep, kb_cols, spec: SplineSpec):
    """SIMD + SPU + TSE on the repeated input: the (bm, bi*(nbk+1)) fused
    ``[silu | bases]`` activation tile, per column the values
    ``_tse_scatter`` would place there, with the silu slot (``kb_cols`` <
    0, which no ``delta`` in 0..K can match) selecting silu(x)."""
    s, vals, cell_i = _spu_tile(x_rep, spec)
    delta = kb_cols - cell_i
    act = jnp.zeros_like(s)
    for j in range(len(vals)):
        act = act + jnp.where(delta == j, vals[j], 0.0)
    return jnp.where(kb_cols < 0, s, act)


def _kan_kernel(
    x_ref, kb_ref, wb_ref, t_ref, o_ref, acc_ref,
    *, spec: SplineSpec, nbk: int, i_steps: int,
):
    """v1: two MXU dispatches per step (silu branch + spline branch)."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                       # (bm, bi)
    s, vals, cell_i = _spu_tile(x, spec)
    acc_ref[...] += jnp.dot(s, wb_ref[...], preferred_element_type=jnp.float32)

    act = _tse_scatter(vals, cell_i, kb_ref[...], nbk)    # (bm, bi, nbk)

    # --- PE array: MAC against the compacted spline weights. --------------
    bm, bi = x.shape
    act2 = act.reshape(bm, bi * nbk)
    acc_ref[...] += jnp.dot(
        act2, t_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(i == i_steps - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kan_kernel_v2(
    x_ref, kb_ref, wt_ref, o_ref, acc_ref, *, spec: SplineSpec, i_steps: int,
):
    """v2: ONE MXU dispatch per step on the fused [silu | bases] tile."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- PE array: single fused contraction.  Per feature p the activation
    # columns are [silu(x_p), B_{kb0}(x_p), ..., B_{kb(nbk-1)}(x_p)],
    # matching fuse_wt's row interleave [w_b[p] ; t[p, kb]].
    fused = _fused_tile(x_ref[...], kb_ref[...], spec)    # (bm, bi*(nbk+1))
    acc_ref[...] += jnp.dot(fused, wt_ref[...],
                            precision=mxu_precision(fused.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(i == i_steps - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kan_kernel_v2_q8(
    x_ref, kb_ref, wt_ref, rs_ref, o_ref, acc_ref,
    *, spec: SplineSpec, i_steps: int, x_scale: float,
):
    """v2 int8 variant: dequantize-on-load, f32 SPU/accumulate, f32 out.

    The activation tile is real-valued (silu + spline bases of the
    dequantized input), so unlike the pattern-matmul q8 kernel the MXU
    contraction here cannot stay in integer codes -- both operands widen
    on load.  ``x_scale`` is the layer's static input scale; ``rs_ref``
    is the (bi*(nbk+1), 1) column of per-row weight scales: fuse_wt's row
    interleave ([w_b ; t[kb]] per input feature) gives each row slot its
    own symmetric scale.
    """
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32) * x_scale          # dequant on load
    fused = _fused_tile(x, kb_ref[...], spec)
    wt = wt_ref[...].astype(jnp.float32) * rs_ref[...]
    acc_ref[...] += jnp.dot(fused, wt, precision=mxu_precision(wt.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(i == i_steps - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def vmem_bytes(bm: int, bi: int, bn: int, nbk: int, dtype,
               order: int = 3) -> int:
    """Scoped VMEM one v2 grid step needs, every value counted in its
    native (sublane, 128) tiles: double-buffered input, kb_cols, weight
    and output blocks (plus the q8 row-scale column), the f32
    accumulator, and the body's f32 temporaries."""
    kc = bi * (nbk + 1)
    q8 = jnp.dtype(dtype) == jnp.int8
    buffers = (padded_bytes(bm, kc, dtype) + padded_bytes(1, kc, jnp.int32)
               + padded_bytes(kc, bn, dtype)
               + padded_bytes(bm, bn, jnp.float32)
               + (padded_bytes(kc, 1, jnp.float32) if q8 else 0))
    # f32 (bm, kc) values the body may keep live: the K+1 basis planes, K
    # left and K right stage-buffer terms, silu / cell / delta / act / the
    # fused tile
    body_tiles = 3 * order + 6
    return (2 * buffers + padded_bytes(bm, bn, jnp.float32)
            + body_tiles * padded_bytes(bm, kc, jnp.float32))


def fit_blocks(B: int, n_in: int, n_out: int, nbk: int, dtype,
               bm: int, bi: int, bn: int,
               order: int = 3) -> Tuple[int, int, int]:
    """Blocks Mosaic accepts for this shape: bi/bn 128-lane aligned or the
    whole axis, bm a multiple of the sublane rows, halved while the step
    exceeds ``VMEM_LIMIT``."""
    bi = fit_block(bi, n_in, LANES)
    bn = fit_block(bn, n_out, LANES)
    bm = fit_rows(bm, B, dtype)
    sub = sublanes(dtype)
    while bm > sub and vmem_bytes(bm, bi, bn, nbk, dtype,
                                  order) > VMEM_LIMIT:
        bm = fit_rows(bm // 2, B, dtype)
    return bm, bi, bn


@functools.partial(
    jax.jit,
    static_argnames=("spec", "kb", "bm", "bi", "bn", "interpret", "out_dtype"),
)
def kan_fused_pallas(
    x: jax.Array,            # (B, n_in)
    w_b: jax.Array,          # (n_in, n_out)
    t_flat: jax.Array,       # (n_in * nbk, n_out), feature-major rows
    spec: SplineSpec,
    kb: Optional[Tuple[int, ...]] = None,
    *,
    bm: int = DEFAULT_BM,
    bi: int = DEFAULT_BI,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """v1 kernel: separate silu / spline contractions (2 dispatches/step).

    ``out_dtype`` (default: x.dtype) lets bf16 inputs emit the f32
    accumulator directly (mixed-precision serving / oracle comparison).
    """
    out_dtype = out_dtype or x.dtype
    B, n_in = x.shape
    n_out = w_b.shape[1]
    kb = tuple(range(spec.n_bases)) if kb is None else tuple(kb)
    nbk = len(kb)
    assert t_flat.shape == (n_in * nbk, n_out), (t_flat.shape, n_in, nbk)

    bm, bi, bn = fit_blocks(B, n_in, n_out, nbk, x.dtype, bm, bi, bn,
                            order=spec.order)
    pb, pi, pn = -B % bm, -n_in % bi, -n_out % bn
    # Pad inputs with x0 (in-range) and weights with zeros: contributes
    # nothing because the padded w_b/t rows are zero.
    xp = jnp.pad(x, ((0, pb), (0, pi)), constant_values=spec.x0)
    wbp = jnp.pad(w_b, ((0, pi), (0, pn)))
    tp = jnp.pad(t_flat, ((0, pi * nbk), (0, pn)))
    kb_arr = jnp.asarray(kb, jnp.int32)[None, :]          # (1, nbk) input
    Bp, Ip, Np = B + pb, n_in + pi, n_out + pn
    i_steps = Ip // bi

    out = pl.pallas_call(
        functools.partial(_kan_kernel, spec=spec, nbk=nbk, i_steps=i_steps),
        grid=(Bp // bm, Np // bn, i_steps),
        in_specs=[
            pl.BlockSpec((bm, bi), lambda b, n, i: (b, i)),
            pl.BlockSpec((1, nbk), lambda b, n, i: (0, 0)),
            pl.BlockSpec((bi, bn), lambda b, n, i: (i, n)),
            pl.BlockSpec((bi * nbk, bn), lambda b, n, i: (i, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda b, n, i: (b, n)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, kb_arr, wbp, tp)
    return out[:B, :n_out]


def _v2_call(body, x, wt, extra, spec: SplineSpec, kb: Tuple[int, ...],
             blocks: Tuple[int, int, int], pad_value: float, out_dtype,
             interpret: bool) -> jax.Array:
    """Shared v2 wrapper: fit the blocks, pad, repeat every input feature
    across its nbk+1 fused columns, and run ``body`` over the grid.

    ``extra`` is the q8 (nbk+1,) slot-scale vector, expanded here into the
    kernel's (bi*(nbk+1), 1) row-scale column, or None for f32/bf16.
    """
    B, n_in = x.shape
    n_out = wt.shape[1]
    nbk = len(kb)
    assert wt.shape == (n_in * (nbk + 1), n_out), (wt.shape, n_in, nbk)
    bm, bi, bn = fit_blocks(B, n_in, n_out, nbk, x.dtype, *blocks,
                            order=spec.order)
    kc = bi * (nbk + 1)
    pb, pi, pn = -B % bm, -n_in % bi, -n_out % bn
    # Padded features read pad_value (in-range) against zero weight rows,
    # so they contribute nothing.
    xp = jnp.pad(x, ((0, pb), (0, pi)), constant_values=pad_value)
    x_rep = jnp.repeat(xp, nbk + 1, axis=1)       # (Bp, Ip*(nbk+1))
    wtp = jnp.pad(wt, ((0, pi * (nbk + 1)), (0, pn)))
    slots = jnp.asarray((-1,) + kb, jnp.int32)
    kb_cols = jnp.tile(slots, bi)[None, :]        # (1, kc), same every i
    Bp, Ip, Np = B + pb, n_in + pi, n_out + pn
    i_steps = Ip // bi
    in_specs = [
        pl.BlockSpec((bm, kc), lambda b, n, i: (b, i)),
        pl.BlockSpec((1, kc), lambda b, n, i: (0, 0)),
        pl.BlockSpec((kc, bn), lambda b, n, i: (i, n)),
    ]
    args = [x_rep, kb_cols, wtp]
    if extra is not None:
        rs = jnp.tile(extra.astype(jnp.float32).reshape(nbk + 1), bi)
        in_specs.append(pl.BlockSpec((kc, 1), lambda b, n, i: (0, 0)))
        args.append(rs[:, None])
    out = pl.pallas_call(
        functools.partial(body, spec=spec, i_steps=i_steps),
        grid=(Bp // bm, Np // bn, i_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda b, n, i: (b, n)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*args)
    return out[:B, :n_out]


@functools.partial(
    jax.jit,
    static_argnames=("spec", "kb", "bm", "bi", "bn", "interpret", "out_dtype"),
)
def kan_fused_pallas_v2(
    x: jax.Array,            # (B, n_in)
    wt: jax.Array,           # (n_in * (nbk+1), n_out), fused rows (fuse_wt)
    spec: SplineSpec,
    kb: Optional[Tuple[int, ...]] = None,
    *,
    bm: int = DEFAULT_BM,
    bi: int = DEFAULT_BI,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """v2 kernel: single fused contraction (1 MXU dispatch/step).

    ``out_dtype`` (default: x.dtype) lets bf16 inputs emit the f32
    accumulator directly (mixed-precision serving / oracle comparison).
    """
    kb = tuple(range(spec.n_bases)) if kb is None else tuple(kb)
    return _v2_call(_kan_kernel_v2, x, wt, None, spec, kb, (bm, bi, bn),
                    spec.x0, out_dtype or x.dtype, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "kb", "x_scale", "bm", "bi", "bn", "interpret",
                     "out_dtype"),
)
def kan_fused_pallas_v2_q8(
    x_q: jax.Array,          # (B, n_in) int8
    wt_q: jax.Array,         # (n_in * (nbk+1), n_out) int8, fused rows
    slot_scales: jax.Array,  # (1, nbk+1) f32: [s_wb, s_t[kb0], ...]
    spec: SplineSpec,
    kb: Optional[Tuple[int, ...]] = None,
    *,
    x_scale: float,
    bm: int = DEFAULT_BM,
    bi: int = DEFAULT_BI,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """v2 int8 kernel: int8 x / fused-weight stream, f32 accumulate + out.

    The int8 weight stream is what the DMA-byte saving in
    ``core/engine.serving_report`` models; the arithmetic contract is
    core/quant's (dequantize on load, accumulate f32, emit f32 -- the
    caller requantizes).  Int8 zero pads dequantize to 0.0, which the SPU
    clips into the spline domain.
    """
    kb = tuple(range(spec.n_bases)) if kb is None else tuple(kb)
    body = functools.partial(_kan_kernel_v2_q8, x_scale=float(x_scale))
    return _v2_call(body, x_q, wt_q, slot_scales, spec, kb, (bm, bi, bn),
                    0, out_dtype, interpret)
