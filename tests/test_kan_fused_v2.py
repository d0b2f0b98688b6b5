"""v2 fused KAN kernel: single-MXU-pass correctness, padding, dtypes.

Coverage the v1-era tests lacked: non-trivial kb subsets, bf16 AND f32, and
shapes that exercise the padding path (B / n_in / n_out not multiples of
bm / bi / bn).  The bar is <= 1e-4 max error vs the jnp oracle (the
matching-precision path sharing the fused weight layout) for both dtypes,
and <= 1e-4 vs the dense fp32 reference for f32.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kan import KANConfig, kan_fused_weights, kan_init
from repro.core.splines import SplineSpec
from repro.kernels.kan_fused.kan_fused import (
    MXU_DISPATCHES_PER_STEP,
    kan_fused_pallas,
    kan_fused_pallas_v2,
)
from repro.kernels.kan_fused.ops import flatten_t, fuse_wt, kan_linear
from repro.kernels.kan_fused.ref import kan_layer_ref

jax.config.update("jax_enable_x64", False)


def _layer(n_in, n_out, pattern, dtype, seed=0, spec=SplineSpec(4, 3)):
    cfg = KANConfig(n_in, n_out, spec, pattern=pattern)
    params = kan_init(jax.random.key(seed), cfg)
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    return cfg, params


# Shapes chosen so B, n_in, n_out are NOT multiples of the block sizes used
# below (bm=64, bi=128, bn=128; blocks are fitted per call, so an axis the
# block covers runs whole in one step) -> every padding branch runs, and
# n_in > 128 splits the input-feature axis into several accumulated steps.
PAD_SHAPES = [(100, 72, 96), (37, 50, 33), (129, 30, 130), (37, 150, 33)]
PATTERNS = [None, (1, 0, 1, 0), (1, 0, 0, 0)]


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("shape", PAD_SHAPES)
def test_v2_f32_vs_dense_ref(shape, pattern):
    B, n_in, n_out = shape
    cfg, params = _layer(n_in, n_out, pattern, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (B, n_in), jnp.float32)
    wt = kan_fused_weights(params, cfg)
    got = kan_fused_pallas_v2(x, wt, cfg.spec, cfg.kb,
                              bm=64, bi=128, bn=128, interpret=True)
    want = kan_layer_ref(x, params["w_b"], params["t"], cfg.spec,
                         basis_mask=cfg.basis_mask)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("pattern", PATTERNS)
def test_v2_vs_jnp_oracle_both_dtypes(pattern, dtype):
    # n_in 300 -> 3 padded i-steps of 128; n_out 150 -> 2 padded n-steps
    B, n_in, n_out = 100, 300, 150
    cfg, params = _layer(n_in, n_out, pattern, dtype)
    x = jax.random.normal(jax.random.key(2), (B, n_in), dtype)
    t_flat = flatten_t(params["t"], cfg.kb)
    wt = kan_fused_weights(params, cfg)
    # out_dtype=f32 compares the fp32 accumulators directly: the kernel and
    # the oracle agree far below 1e-4; only the final bf16 output rounding
    # can tie-break differently (one ulp), which is not a kernel property.
    got = kan_fused_pallas_v2(x, wt, cfg.spec, cfg.kb, bm=64, bi=128,
                              bn=128, interpret=True, out_dtype=jnp.float32)
    want = kan_linear(x, params["w_b"], t_flat, cfg.spec, cfg.kb, impl="jnp",
                      out_dtype=jnp.float32)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err <= 1e-4, (pattern, dtype, err)
    # the rounded bf16 outputs agree to one output ulp
    got_r = kan_fused_pallas_v2(x, wt, cfg.spec, cfg.kb, bm=64, bi=128,
                                bn=128, interpret=True)
    want_r = kan_linear(x, params["w_b"], t_flat, cfg.spec, cfg.kb,
                        impl="jnp")
    ulp = 1e-4 if dtype == jnp.float32 else 2 ** -8
    scale = float(jnp.max(jnp.abs(want))) + 1.0
    err_r = float(jnp.max(jnp.abs((got_r - want_r).astype(jnp.float32))))
    assert err_r <= ulp * scale, (pattern, dtype, err_r)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_v2_bf16_padding_path(dtype):
    """Padding path with a kb subset at reduced precision: n_in 150 pads
    to two i-steps of 128."""
    B, n_in, n_out = 37, 150, 33
    cfg, params = _layer(n_in, n_out, (1, 1, 0, 0), dtype)
    x = jax.random.normal(jax.random.key(3), (B, n_in), dtype)
    t_flat = flatten_t(params["t"], cfg.kb)
    got = kan_linear(x, params["w_b"], t_flat, cfg.spec, cfg.kb,
                     impl="pallas_interpret", blocks=(64, 128, 128),
                     out_dtype=jnp.float32)
    want = kan_linear(x, params["w_b"], t_flat, cfg.spec, cfg.kb, impl="jnp",
                      out_dtype=jnp.float32)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err <= 1e-4
    # and bf16 stays within bf16-rounding distance of the fp32 dense oracle
    ref = kan_layer_ref(x, params["w_b"], params["t"], cfg.spec,
                        basis_mask=cfg.basis_mask)
    ref_err = float(jnp.max(jnp.abs((got - ref).astype(jnp.float32))))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    assert ref_err <= tol


def test_v1_v2_agree():
    # n_in 150 / n_out 150: two padded i- and n-steps of 128 each
    cfg, params = _layer(150, 150, (1, 0, 1, 0), jnp.float32)
    x = jax.random.normal(jax.random.key(4), (64, 150))
    t_flat = flatten_t(params["t"], cfg.kb)
    wt = fuse_wt(params["w_b"], t_flat, cfg.n_bases_kept)
    v1 = kan_fused_pallas(x, params["w_b"], t_flat, cfg.spec, cfg.kb,
                          bm=32, bi=128, bn=128, interpret=True)
    v2 = kan_fused_pallas_v2(x, wt, cfg.spec, cfg.kb,
                             bm=32, bi=128, bn=128, interpret=True)
    assert float(jnp.max(jnp.abs(v1 - v2))) <= 1e-5


def test_v2_single_mxu_dispatch_per_step():
    """Acceptance: v2 issues exactly ONE MXU contraction per grid step.

    Counted on the traced kernel jaxpr (interpret mode embeds the kernel
    body): one dot_general for v2, two for v1.
    """
    spec = SplineSpec(4, 3)
    kb = tuple(range(spec.n_bases))
    nbk = len(kb)
    n_in, n_out, B = 24, 16, 32
    x = jnp.zeros((B, n_in))
    wb = jnp.zeros((n_in, n_out))
    tf = jnp.zeros((n_in * nbk, n_out))
    wt = fuse_wt(wb, tf, nbk)
    j1 = jax.make_jaxpr(lambda x, wb, tf: kan_fused_pallas(
        x, wb, tf, spec, kb, bm=16, bi=8, bn=16, interpret=True))(x, wb, tf)
    j2 = jax.make_jaxpr(lambda x, wt: kan_fused_pallas_v2(
        x, wt, spec, kb, bm=16, bi=8, bn=16, interpret=True))(x, wt)
    assert str(j1).count("dot_general") == MXU_DISPATCHES_PER_STEP[1] == 2
    assert str(j2).count("dot_general") == MXU_DISPATCHES_PER_STEP[2] == 1


def test_fused_weight_layout_row_interleave():
    """fuse_wt row p*(nbk+1) is w_b[p]; the next nbk rows are t[p, kb]."""
    n_in, nbk, n_out = 3, 4, 5
    w_b = jnp.arange(n_in * n_out, dtype=jnp.float32).reshape(n_in, n_out)
    t_flat = 100 + jnp.arange(n_in * nbk * n_out, dtype=jnp.float32
                              ).reshape(n_in * nbk, n_out)
    wt = fuse_wt(w_b, t_flat, nbk)
    assert wt.shape == (n_in * (nbk + 1), n_out)
    for p in range(n_in):
        np.testing.assert_array_equal(wt[p * (nbk + 1)], w_b[p])
        np.testing.assert_array_equal(
            wt[p * (nbk + 1) + 1: (p + 1) * (nbk + 1)],
            t_flat[p * nbk: (p + 1) * nbk])


@pytest.mark.parametrize("g,k", [(2, 1), (8, 2), (16, 4)])
def test_v2_other_spline_specs(g, k):
    spec = SplineSpec(g, k)
    # n_in 150 -> two padded i-steps of 128
    cfg, params = _layer(150, 24, None, jnp.float32, spec=spec)
    x = jax.random.normal(jax.random.key(5), (53, 150))
    wt = kan_fused_weights(params, cfg)
    got = kan_fused_pallas_v2(x, wt, spec, cfg.kb,
                              bm=32, bi=128, bn=128, interpret=True)
    want = kan_layer_ref(x, params["w_b"], params["t"], spec)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-4


@pytest.mark.parametrize("pattern", PATTERNS)
def test_v2_q8_multi_step_vs_jnp_oracle(pattern):
    """int8 kernel over several padded i- and n-steps (n_in 300 -> 3 of
    128, n_out 150 -> 2) against the q8 jnp oracle."""
    from repro.kernels.kan_fused.ops import kan_linear_q8

    B, n_in, n_out = 37, 300, 150
    cfg, params = _layer(n_in, n_out, pattern, jnp.float32)
    nbk = cfg.n_bases_kept
    wt = np.asarray(kan_fused_weights(params, cfg)).reshape(n_in, nbk + 1,
                                                             n_out)
    slot_scales = np.abs(wt).max(axis=(0, 2)) / 127.0        # per row slot
    wt_q = np.round(wt / slot_scales[None, :, None]).astype(np.int8)
    wt_q = jnp.asarray(wt_q.reshape(n_in * (nbk + 1), n_out))
    x = np.asarray(jax.random.normal(jax.random.key(6), (B, n_in)))
    x_scale = float(np.abs(x).max() / 127.0)
    x_q = jnp.asarray(np.round(x / x_scale).astype(np.int8))
    ss = tuple(float(s) for s in slot_scales)
    got = kan_linear_q8(x_q, wt_q, ss, cfg.spec, cfg.kb, x_scale=x_scale,
                        impl="pallas_interpret", blocks=(64, 128, 128))
    want = kan_linear_q8(x_q, wt_q, ss, cfg.spec, cfg.kb, x_scale=x_scale,
                         impl="jnp")
    scale = float(jnp.max(jnp.abs(want))) + 1.0
    err = float(jnp.max(jnp.abs(got - want)))
    assert got.dtype == jnp.float32 and err <= 1e-4 * scale, (pattern, err)
