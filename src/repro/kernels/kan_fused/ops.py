"""Public entry for the fused KAN layer with impl dispatch.

"jnp" is the XLA path used by CPU tests and the multi-pod dry-run: it keeps
the same structural sparsity (local K+1 evaluation + static column
compaction) expressed in jnp ops, so cost_analysis sees the real op mix.
The jnp path shares the *fused* weight layout with the v2 Pallas kernel
(``fuse_wt``): both contract one [silu(x) | scattered_bases] activation
against the row-interleaved [w_b ; t] matrix, so the two paths are
numerically step-for-step equivalent (the jnp oracle the kernel is validated
against at 1e-4).

Block sizes for the Pallas path resolve, in order: explicit ``blocks``
argument > autotune cache hit for (shape bucket, dtype, backend) > module
defaults (see ``repro.kernels.autotune``); the kernel then fits them to
the call's shape (``kan_fused.fit_blocks``).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.splines import SplineSpec, bases_local, scatter_kept, silu
from repro.kernels import autotune
from repro.kernels.tiling import gemm_rows
from repro.kernels.kan_fused.kan_fused import (
    DEFAULT_BI,
    DEFAULT_BM,
    DEFAULT_BN,
    kan_fused_pallas,
    kan_fused_pallas_v2,
    kan_fused_pallas_v2_q8,
)

DEFAULT_VERSION = 2


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flatten_t(t: jax.Array, kb: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """(n_in, n_bases, n_out) -> (n_in*nbk, n_out), rows feature-major.

    ``kb`` selects the kept basis indices (stage-2 compaction at build time).
    """
    if kb is not None:
        t = jnp.take(t, jnp.asarray(kb, jnp.int32), axis=1)
    n_in, nbk, n_out = t.shape
    return t.reshape(n_in * nbk, n_out)


def fuse_wt(w_b: jax.Array, t_flat: jax.Array, nbk: int) -> jax.Array:
    """Row-interleave [w_b ; t] into the v2 fused weight layout.

    (n_in, n_out) + (n_in*nbk, n_out) -> (n_in*(nbk+1), n_out): per input
    feature p, row p*(nbk+1) is w_b[p] (the silu branch) and rows
    p*(nbk+1)+1.. are its nbk kept spline rows -- matching the kernel's
    [silu | bases] activation tile flatten.
    """
    n_in, n_out = w_b.shape
    assert t_flat.shape == (n_in * nbk, n_out), (t_flat.shape, n_in, nbk)
    t3 = t_flat.reshape(n_in, nbk, n_out)
    wt = jnp.concatenate([w_b[:, None, :], t3], axis=1)
    return wt.reshape(n_in * (nbk + 1), n_out)


def resolve_blocks(
    B: int, n_in: int, n_out: int, nbk: int, dtype,
    blocks: Optional[Tuple[int, int, int]] = None,
    version: int = DEFAULT_VERSION,
    backend: Optional[str] = None,
) -> Dict[str, int]:
    """(bm, bi, bn) for the fused kernel: explicit > cached > defaults.

    ``backend`` selects the cache namespace: interpret-mode callers pass
    "cpu" so entries stored by ``tune_kan_fused(interpret=True)`` are
    reachable; None means the current jax backend.
    """
    if blocks is not None:
        bm, bi, bn = blocks
        return {"bm": bm, "bi": bi, "bn": bn}
    hit = autotune.lookup_blocks(
        f"kan_fused_v{version}", (B, n_in, n_out, nbk), dtype,
        backend=backend)
    if hit is not None:
        return hit
    return {"bm": DEFAULT_BM, "bi": DEFAULT_BI, "bn": DEFAULT_BN}


@functools.partial(
    jax.jit, static_argnames=("spec", "kb", "version", "out_dtype"))
def _kan_linear_jnp(
    x: jax.Array, w_b: jax.Array, t_flat: jax.Array, spec: SplineSpec,
    kb: Tuple[int, ...], version: int, out_dtype=None,
) -> jax.Array:
    n_rows, n_in = x.shape
    nbk = len(kb)
    x = gemm_rows(x)
    # Stage 1: only K+1 basis values are computed (VPU-op saving); stage 2:
    # broadcast iota-comparison scatter straight into the kept-basis columns
    # (K+1 selects, independent of nbk) -- same TSE form as the kernels.
    vals, cell = bases_local(spec.clip(x), spec)           # (B, n_in, K+1)
    kbv = jnp.asarray(kb, jnp.int32)
    act = scatter_kept(vals, cell, kbv, spec.n_active)     # (B, n_in, nbk)
    # silu in f32 then cast, matching the kernel's SIMD stage exactly.
    s = silu(x.astype(jnp.float32)).astype(x.dtype)
    if version >= 2:
        # Fused layout: one contraction, same layout as the v2 kernel.
        wt = fuse_wt(w_b, t_flat, nbk)
        fused = jnp.concatenate([s[..., None], act], axis=-1)
        y = jnp.dot(
            fused.reshape(-1, n_in * (nbk + 1)), wt,
            preferred_element_type=jnp.float32,
        )
    else:
        y = jnp.dot(s, w_b, preferred_element_type=jnp.float32)
        y = y + jnp.dot(
            act.reshape(-1, n_in * nbk), t_flat,
            preferred_element_type=jnp.float32,
        )
    return y[:n_rows].astype(out_dtype or x.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "kb", "impl", "version", "blocks", "out_dtype"),
)
def kan_linear(
    x: jax.Array,            # (..., n_in)
    w_b: jax.Array,          # (n_in, n_out)
    t_flat: jax.Array,       # (n_in * nbk, n_out)
    spec: SplineSpec,
    kb: Optional[Tuple[int, ...]] = None,
    *,
    impl: str = "auto",
    version: int = DEFAULT_VERSION,
    blocks: Optional[Tuple[int, int, int]] = None,
    out_dtype=None,
) -> jax.Array:
    """phi(x) per Eq. 3 with two-stage sparsity; batch dims preserved.

    ``version`` selects the kernel generation (2 = single-MXU-pass fused
    contraction, 1 = legacy two-dispatch); ``blocks`` overrides the
    (bm, bi, bn) tile sizes, else the autotune cache is consulted.
    ``out_dtype`` (default x.dtype) emits the fp32 accumulator un-rounded
    when set to float32 with bf16 inputs.

    jit note: weight fusion and the autotune-cache lookup run at trace
    time, i.e. once per (shape, static-args) combination -- eager callers
    pay them once, not per step.  A cache entry tuned AFTER the first trace
    of a shape is picked up on the next process (or jit-cache clear), not
    mid-process.
    """
    lead = x.shape[:-1]
    n_in = x.shape[-1]
    xf = x.reshape(-1, n_in)
    kb = tuple(range(spec.n_bases)) if kb is None else tuple(kb)
    nbk = len(kb)

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"
    if impl in ("pallas", "pallas_interpret"):
        interpret = impl == "pallas_interpret"
        bk = resolve_blocks(xf.shape[0], n_in, w_b.shape[1], nbk, x.dtype,
                            blocks, version,
                            backend="cpu" if interpret else None)
        if version >= 2:
            wt = fuse_wt(w_b, t_flat, nbk)
            y = kan_fused_pallas_v2(xf, wt, spec, kb, interpret=interpret,
                                    out_dtype=out_dtype, **bk)
        else:
            y = kan_fused_pallas(xf, w_b, t_flat, spec, kb,
                                 interpret=interpret, out_dtype=out_dtype,
                                 **bk)
    elif impl == "jnp":
        y = _kan_linear_jnp(xf, w_b, t_flat, spec, kb, version, out_dtype)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return y.reshape(*lead, w_b.shape[-1])


def _dequant_wt(wt_q: jax.Array, slot_scales: Tuple[float, ...],
                nbk: int) -> jax.Array:
    """(n_in*(nbk+1), n_out) int8 fused weights -> f32 under per-slot scales.

    Shared by the jnp oracle below; the Pallas q8 kernel performs the
    identical per-row-slot multiply on each loaded tile, so both paths
    see bit-identical dequantized weights.
    """
    n_rows, n_out = wt_q.shape
    ss = jnp.asarray(slot_scales, jnp.float32).reshape(1, nbk + 1, 1)
    wt = wt_q.astype(jnp.float32).reshape(n_rows // (nbk + 1), nbk + 1, n_out)
    return (wt * ss).reshape(n_rows, n_out)


@functools.partial(
    jax.jit,
    static_argnames=("slot_scales", "spec", "kb", "x_scale", "out_dtype"))
def _kan_linear_q8_jnp(
    x_q: jax.Array, wt_q: jax.Array, slot_scales: Tuple[float, ...],
    spec: SplineSpec, kb: Tuple[int, ...], x_scale: float,
    out_dtype=jnp.float32,
) -> jax.Array:
    from repro.core.quant import dequantize

    n_in = x_q.shape[-1]
    nbk = len(kb)
    x = dequantize(x_q, x_scale)                           # f32
    vals, cell = bases_local(spec.clip(x), spec)
    kbv = jnp.asarray(kb, jnp.int32)
    act = scatter_kept(vals, cell, kbv, spec.n_active)     # (B, n_in, nbk)
    s = silu(x)                                            # already f32
    wt = _dequant_wt(wt_q, slot_scales, nbk)
    fused = jnp.concatenate([s[..., None], act], axis=-1)
    y = jnp.dot(
        fused.reshape(-1, n_in * (nbk + 1)), wt,
        preferred_element_type=jnp.float32,
    )
    return y.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("slot_scales", "spec", "kb", "x_scale", "impl",
                     "blocks", "out_dtype"),
)
def kan_linear_q8(
    x_q: jax.Array,          # (..., n_in) int8
    wt_q: jax.Array,         # (n_in * (nbk+1), n_out) int8, fused (fuse_wt)
    slot_scales: Tuple[float, ...],   # (nbk+1,) [s_wb, s_t[kb0], ...]
    spec: SplineSpec,
    kb: Optional[Tuple[int, ...]] = None,
    *,
    x_scale: float,
    impl: str = "auto",
    blocks: Optional[Tuple[int, int, int]] = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Int8 phi(x): dequantize-on-load, f32 accumulate, f32 out.

    Activations and fused weights stream int8 (the DMA saving the engine
    charges); the spline/silu math runs on the DEQUANTIZED f32 input, so
    the Pallas kernel and this module's jnp oracle agree to the same
    ~1e-4 tile-accumulation tolerance as the f32 kernels (the activation
    tile is real-valued -- no integer-exact bitwise contract here, unlike
    pattern_linear_q8).  Scales are static: one trace per calibration.
    """
    lead = x_q.shape[:-1]
    n_in = x_q.shape[-1]
    xf = x_q.reshape(-1, n_in)
    kb = tuple(range(spec.n_bases)) if kb is None else tuple(kb)
    nbk = len(kb)
    slot_scales = tuple(float(s) for s in slot_scales)
    if len(slot_scales) != nbk + 1:
        raise ValueError(
            f"slot_scales has {len(slot_scales)} entries for nbk={nbk}")

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "jnp"
    if impl in ("pallas", "pallas_interpret"):
        interpret = impl == "pallas_interpret"
        bk = resolve_blocks(xf.shape[0], n_in, wt_q.shape[1], nbk, x_q.dtype,
                            blocks, 2, backend="cpu" if interpret else None)
        ss = jnp.asarray(slot_scales, jnp.float32)[None, :]
        y = kan_fused_pallas_v2_q8(xf, wt_q, ss, spec, kb,
                                   x_scale=float(x_scale),
                                   interpret=interpret,
                                   out_dtype=out_dtype, **bk)
    elif impl == "jnp":
        y = _kan_linear_q8_jnp(xf, wt_q, slot_scales, spec, kb,
                               float(x_scale), out_dtype)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return y.reshape(*lead, wt_q.shape[-1])
