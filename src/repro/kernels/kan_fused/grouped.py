"""Grouped fused KAN layer: one call runs every expert of a KAN-FFN MoE layer.

The rows arrive sorted by expert, each expert's group padded to a whole
number of ``bm``-row tiles (models/moe.group_rows).  A scalar-prefetched
map names the expert of every tile, and ``n_used`` counts the tiles that
hold rows.  Each grid step reads the tile's expert's block of the fused
``[w_b ; t]`` weights and runs kan_fused_pallas_v2's spline and
contraction body on the tile.  The weights are stored transposed,
``(E, n_out, n_in*(nbk+1))``: each expert's ops.fuse_wt matrix turned on
its side, so that the long fused axis is the minor one.  (Stored the
other way, a hidden width such as 341 that is not a whole number of
128-lane tiles makes XLA lay the array out column-major, and every call
would copy every expert's weights into the kernel's row-major layout.)
Tiles past the last used one compute nothing and keep the block indices
of the last used step, so they fetch nothing: an expert that got no rows
reads no weights.

The input block is (bm, bi), not v2's repeated (bm, bi*(nbk+1)): the
kernel repeats each feature across its nbk+1 fused columns itself, by a
contraction with a 0/1 expansion matrix (exact: one nonzero product per
output), so the sorted rows are never stored nbk+1 times over in HBM.

Blocks are chosen from the shape: ``tile_rows`` sets bm from the rows an
expert expects, ``grouped_blocks`` takes the whole output axis (up to
512 lanes) and the widest input chunk whose step fits ``VMEM_LIMIT``.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.splines import SplineSpec, bases_local, scatter_kept, silu
from repro.kernels.kan_fused.kan_fused import (
    VMEM_LIMIT,
    _fused_tile,
    vmem_bytes,
)
from repro.kernels.tiling import (
    LANES,
    fit_block,
    mxu_precision,
    padded_bytes,
    sublanes,
)
from repro.utils import next_pow2

MAX_BM = 128
MAX_BN = 512


def tile_rows(rows_per_expert: float, dtype) -> int:
    """Row tile for groups of about ``rows_per_expert`` rows: the power of
    two that holds one group, between the dtype's sublane rows and 128."""
    want = next_pow2(max(1, math.ceil(rows_per_expert)))
    return min(MAX_BM, max(sublanes(dtype), want))


def grouped_vmem_bytes(bm: int, bi: int, bn: int, nbk: int, dtype,
                       order: int = 3) -> int:
    """v2's step (``vmem_bytes``, which counts the input block at its
    repeated width) plus the double-buffered expansion matrix."""
    kc = bi * (nbk + 1)
    return (vmem_bytes(bm, bi, bn, nbk, dtype, order)
            + 2 * padded_bytes(bi, kc, dtype))


def grouped_blocks(bm: int, n_in: int, n_out: int, nbk: int, dtype,
                   order: int = 3) -> Tuple[int, int]:
    """(bi, bn) for ``bm``-row tiles: bn the whole output axis up to 512
    lanes, bi the widest of 512/256/128 input features that fits."""
    bn = fit_block(MAX_BN, n_out, LANES)
    for bi in (512, 256, 128):
        bi = fit_block(bi, n_in, LANES)
        if grouped_vmem_bytes(bm, bi, bn, nbk, dtype, order) <= VMEM_LIMIT:
            return bi, bn
    return bi, bn


def _grouped_kernel(te_ref, nu_ref, x_ref, ex_ref, kb_ref, wt_ref, o_ref,
                    acc_ref, *, spec: SplineSpec, i_steps: int):
    del te_ref                      # read by the index maps only
    t, i = pl.program_id(0), pl.program_id(2)

    @pl.when(t < nu_ref[0])
    def _tile():
        @pl.when(i == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]                                    # (bm, bi)
        x_rep = jnp.dot(x, ex_ref[...], precision=mxu_precision(x.dtype),
                        preferred_element_type=jnp.float32).astype(x.dtype)
        fused = _fused_tile(x_rep, kb_ref[...], spec)     # (bm, bi*(nbk+1))
        acc_ref[...] += jax.lax.dot_general(
            fused, wt_ref[...], (((1,), (1,)), ((), ())),
            precision=mxu_precision(fused.dtype),
            preferred_element_type=jnp.float32)

        @pl.when(i == i_steps - 1)
        def _epilogue():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("spec", "bm", "bi", "bn", "interpret"))
def kan_grouped_pallas(
    x: jax.Array,            # (R, n_in), rows sorted by expert, R % bm == 0
    wt: jax.Array,           # (E, n_out, n_in * (nbk+1)): fused, transposed
    tile_expert: jax.Array,  # (R // bm,) int32: the expert of each tile
    n_used: jax.Array,       # () int32: tiles that hold rows
    spec: SplineSpec,
    *,
    bm: int,
    bi: int,
    bn: int,
    interpret: bool = False,
) -> jax.Array:
    """phi_e(x) (paper Eq. 3) of every row under its tile's expert e; the
    rows of unused tiles are left unwritten."""
    kb = tuple(range(spec.n_bases))
    nbk = len(kb)
    R, n_in = x.shape
    E, n_out, _ = wt.shape
    assert R % bm == 0 and wt.shape[2] == n_in * (nbk + 1), (
        x.shape, wt.shape, bm, nbk)
    kc = bi * (nbk + 1)
    pi, pn = -n_in % bi, -n_out % bn
    # padded features read x0 (in range) against zero weight rows; the
    # served widths need no padding, and the weights are copied only if
    # they do
    xp = jnp.pad(x, ((0, 0), (0, pi)), constant_values=spec.x0)
    wtp = (jnp.pad(wt, ((0, 0), (0, pn), (0, pi * (nbk + 1))))
           if pi or pn else wt)
    expand = (jnp.arange(kc)[None, :] // (nbk + 1)
              == jnp.arange(bi)[:, None]).astype(x.dtype)
    kb_cols = jnp.tile(jnp.asarray((-1,) + kb, jnp.int32), bi)[None, :]
    n_tiles, n_steps, i_steps = R // bm, (n_out + pn) // bn, (n_in + pi) // bi

    def pin(t, n, i, nu):
        """A used tile's own blocks; past the last used tile, the last
        used step's blocks, so nothing new is fetched or written."""
        used = t < nu[0]
        last = jnp.maximum(nu[0] - 1, 0)
        return (jnp.where(used, t, last), jnp.where(used, n, n_steps - 1),
                jnp.where(used, i, i_steps - 1))

    def x_map(t, n, i, te, nu):
        tt, _, ii = pin(t, n, i, nu)
        return tt, ii

    def w_map(t, n, i, te, nu):
        tt, nn, ii = pin(t, n, i, nu)
        return te[tt], nn, ii

    def o_map(t, n, i, te, nu):
        tt, nn, _ = pin(t, n, i, nu)
        return tt, nn

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, n_steps, i_steps),
        in_specs=[
            pl.BlockSpec((bm, bi), x_map),
            pl.BlockSpec((bi, kc), lambda t, n, i, te, nu: (0, 0)),
            pl.BlockSpec((1, kc), lambda t, n, i, te, nu: (0, 0)),
            pl.BlockSpec((None, bn, kc), w_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, spec=spec, i_steps=i_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, n_out + pn), x.dtype),
        interpret=interpret,
        name="kan_grouped_pallas",
    )(tile_expert.astype(jnp.int32),
      jnp.reshape(n_used, (1,)).astype(jnp.int32), xp, expand, kb_cols, wtp)
    return out[:, :n_out]


@functools.partial(jax.jit, static_argnames=("spec", "bm"))
def _kan_grouped_jnp(x, wt, tile_expert, spec: SplineSpec, bm: int):
    """The kernel's math in jnp: the fused [silu | kept bases] activation
    of every row against its tile's expert's fused weights."""
    R, n_in = x.shape
    nbk = spec.n_bases
    vals, cell = bases_local(spec.clip(x), spec)
    act = scatter_kept(vals, cell, jnp.arange(nbk, dtype=jnp.int32),
                       spec.n_active)
    s = silu(x.astype(jnp.float32)).astype(x.dtype)
    fused = jnp.concatenate([s[..., None], act], axis=-1)
    y = jnp.einsum("tbk,tnk->tbn",
                   fused.reshape(R // bm, bm, n_in * (nbk + 1)),
                   wt[tile_expert], preferred_element_type=jnp.float32)
    return y.reshape(R, -1).astype(x.dtype)


def kan_grouped(x: jax.Array, wt: jax.Array, tile_expert: jax.Array,
                n_used: jax.Array, spec: SplineSpec, *, bm: int,
                impl: str = "auto") -> jax.Array:
    """Dispatch: the Pallas kernel on a TPU (``impl`` "auto" or "pallas"),
    in interpret mode (``"pallas_interpret"``), else the jnp path."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl in ("pallas", "pallas_interpret"):
        bi, bn = grouped_blocks(bm, x.shape[1], wt.shape[1], spec.n_bases,
                                x.dtype, spec.order)
        return kan_grouped_pallas(x, wt, tile_expert, n_used, spec,
                                  bm=bm, bi=bi, bn=bn,
                                  interpret=impl == "pallas_interpret")
    if impl == "jnp":
        return _kan_grouped_jnp(x, wt, tile_expert, spec, bm)
    raise ValueError(f"unknown impl {impl!r}")
