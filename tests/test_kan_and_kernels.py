"""KAN layer + kan_fused + pattern_matmul kernels vs oracles; sparsity."""

from _hypothesis_fallback import hypothesis, st  # skips, not errors, when absent
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kan import (
    KANConfig,
    extend_grid,
    kan_apply,
    kan_init,
    kan_op_counts,
    kan_stack_apply,
)
from repro.core.modes import ExecMode, LayerKind, ModePlan
from repro.core.sparsity import (
    compact_rows,
    magnitude_mask,
    spline_nnz_rate,
    sparsity_to_pattern,
    tiled_mask,
)
from repro.core.splines import SplineSpec
from repro.kernels.kan_fused.kan_fused import kan_fused_pallas
from repro.kernels.kan_fused.ops import flatten_t, kan_linear
from repro.kernels.kan_fused.ref import kan_layer_ref
from repro.kernels.pattern_matmul.ops import pattern_linear
from repro.kernels.pattern_matmul.pattern_matmul import matmul_compact_pallas
from repro.kernels.pattern_matmul.ref import pattern_matmul_ref


def _kan_setup(n_in=9, n_out=13, g=4, k=3, pattern=None, seed=0, dtype=jnp.float32):
    cfg = KANConfig(n_in, n_out, SplineSpec(g, k), pattern=pattern)
    params = jax.tree.map(
        lambda a: a.astype(dtype), kan_init(jax.random.key(seed), cfg)
    )
    x = jax.random.normal(jax.random.key(seed + 1), (17, n_in), dtype) * 0.7
    return cfg, params, x


# ---------------------------------------------------------------------------
# kan_fused kernel sweeps vs ref oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("g,k", [(2, 1), (4, 3), (8, 2), (16, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kan_fused_kernel_vs_ref(g, k, dtype):
    # n_in 150: blocks are fitted 128-lane aligned, so two padded i-steps
    cfg, params, x = _kan_setup(n_in=150, g=g, k=k, dtype=dtype)
    t_flat = flatten_t(params["t"])
    got = kan_fused_pallas(
        x, params["w_b"], t_flat, cfg.spec, bm=8, bi=128, bn=128,
        interpret=True,
    )
    want = kan_layer_ref(x, params["w_b"], params["t"], cfg.spec)
    atol = 1e-4 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )


@pytest.mark.parametrize("rate", [0.25, 0.5, 0.75])
def test_kan_fused_kernel_pattern_sparsity(rate):
    """Compacted kernel == dense oracle with multiplicative mask."""
    pattern = sparsity_to_pattern(rate)
    cfg, params, x = _kan_setup(n_in=150, g=8, k=3, pattern=pattern)
    t_flat = flatten_t(params["t"], cfg.kb)
    got = kan_fused_pallas(
        x, params["w_b"], t_flat, cfg.spec, cfg.kb, bm=8, bi=128, bn=128,
        interpret=True,
    )
    want = kan_layer_ref(
        x, params["w_b"], params["t"], cfg.spec, basis_mask=cfg.basis_mask
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("shape", [(1, 1, 3), (5, 9, 13), (33, 72, 96)])
def test_kan_linear_jnp_vs_ref_shapes(shape):
    b, n_in, n_out = shape
    cfg, params, _ = _kan_setup(n_in=n_in, n_out=n_out)
    x = jax.random.normal(jax.random.key(2), (b, n_in)) * 1.5
    got = kan_linear(x, params["w_b"], flatten_t(params["t"]), cfg.spec,
                     impl="jnp")
    want = kan_layer_ref(x, params["w_b"], params["t"], cfg.spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_kan_linear_impls_agree():
    cfg, params, x = _kan_setup(pattern=(1, 0, 1, 0))
    t_flat = flatten_t(params["t"], cfg.kb)
    a = kan_linear(x, params["w_b"], t_flat, cfg.spec, cfg.kb, impl="jnp")
    b = kan_linear(x, params["w_b"], t_flat, cfg.spec, cfg.kb,
                   impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_kan_apply_batch_dims():
    cfg, params, _ = _kan_setup()
    x = jax.random.normal(jax.random.key(5), (2, 3, 9))
    y = kan_apply(params, x, cfg)
    assert y.shape == (2, 3, 13)
    assert not bool(jnp.any(jnp.isnan(y)))


def test_kan_stack_composition():
    key = jax.random.key(0)
    cfgs = [KANConfig(72, 32), KANConfig(32, 96)]  # paper KAN-3 body
    ps = [kan_init(k, c) for k, c in zip(jax.random.split(key, 2), cfgs)]
    x = jax.random.normal(jax.random.key(9), (4, 72))
    y = kan_stack_apply(ps, x, cfgs)
    assert y.shape == (4, 96)


# ---------------------------------------------------------------------------
# grid extension (accuracy scaling)
# ---------------------------------------------------------------------------

def test_extend_grid_preserves_function():
    cfg, params, x = _kan_setup(g=4, k=3)
    p2, cfg2 = extend_grid(params, cfg, 16)
    assert cfg2.spec.grid_size == 16
    y1 = kan_apply(params, x, cfg)
    y2 = kan_apply(p2, x, cfg2)
    # finer grid can represent the coarser spline exactly up to lstsq noise
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-3)


# ---------------------------------------------------------------------------
# pattern_matmul kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(1, 4, 3), (16, 64, 32), (130, 260, 70)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel_vs_dense(m, k, n, dtype):
    kx, kw = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (k, n), dtype)
    got = matmul_compact_pallas(x, w, bm=16, bk=32, bn=16, interpret=True)
    want = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    atol = 1e-4 * k if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=atol
    )


@pytest.mark.parametrize("rate", [0.0, 0.25, 0.5, 0.75])
@pytest.mark.parametrize("act", [None, "relu", "gelu"])
def test_pattern_linear_vs_ref(rate, act):
    mask = tiled_mask(64, sparsity_to_pattern(rate))
    kx, kw, kb = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(kx, (10, 64))
    w = jax.random.normal(kw, (64, 24))
    bias = jax.random.normal(kb, (24,))
    got = pattern_linear(x, w, mask, bias, act=act, impl="jnp")
    got_pl = pattern_linear(x, w, mask, bias, act=act,
                            impl="pallas_interpret")
    want = pattern_matmul_ref(x, w, mask, bias, act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_pl), np.asarray(want), atol=1e-4)


def test_pattern_linear_compaction_shrinks_contraction():
    mask = tiled_mask(64, (1, 0, 1, 0))
    w = jnp.ones((64, 8))
    assert compact_rows(w, mask).shape == (32, 8)


# ---------------------------------------------------------------------------
# sparsity machinery
# ---------------------------------------------------------------------------

def test_tiled_mask_and_rates():
    m = tiled_mask(19, (1, 0, 1, 0))
    assert m.n == 19 and m.keep[16:].all()  # trailing partial group kept
    assert m.is_tiled() is not None
    assert abs(tiled_mask(64, (1, 0, 0, 0)).sparsity - 0.75) < 1e-9


def test_magnitude_mask_keeps_largest():
    sal = np.array([1.0, 9.0, 2.0, 8.0, 0.1, 0.2, 0.4, 0.3])
    m = magnitude_mask(sal, keep_per_group=2)
    assert m.keep.tolist() == [False, True, False, True,
                               False, False, True, True]
    assert m.is_tiled() is None  # per-group masks are not tiled


def test_spline_structural_sparsity_matches_paper():
    # G=16,K=3: only 4/19 bases non-zero -> 79% structural sparsity; combined
    # with a 75% pattern mask the PE-array work drops by ~87.5%+ (Sec. IV-C).
    assert abs(spline_nnz_rate(16, 3) - 4 / 19) < 1e-9


@hypothesis.given(
    rate=st.sampled_from([0.0, 0.25, 0.5, 0.75]),
    n=st.integers(8, 200),
)
@hypothesis.settings(max_examples=30, deadline=None)
def test_property_mask_semantics(rate, n):
    """Property: compacted matmul == dense matmul with zeroed lanes."""
    mask = tiled_mask(n, sparsity_to_pattern(rate))
    x = jnp.asarray(np.random.default_rng(n).normal(size=(3, n)), jnp.float32)
    w = jnp.asarray(np.random.default_rng(n + 1).normal(size=(n, 5)),
                    jnp.float32)
    got = pattern_linear(x, w, mask, impl="jnp")
    want = pattern_matmul_ref(x, w, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


# ---------------------------------------------------------------------------
# op accounting + modes
# ---------------------------------------------------------------------------

def test_op_counts_fig8_ratio():
    """Fig. 8: G=16 model has ~3-4x the dense ops of G=2 at K=3."""
    base = kan_op_counts(KANConfig(72, 32, SplineSpec(2, 3)))
    big = kan_op_counts(KANConfig(72, 32, SplineSpec(16, 3)))
    ratio = big["dense"] / base["dense"]
    assert 2.5 < ratio < 4.5
    # ...but VIKIN's sparse MAC work is nearly flat in G:
    assert big["vikin_mac"] == base["vikin_mac"]


def test_mode_plan():
    plan = ModePlan.for_layers(
        [LayerKind.MLP, LayerKind.MLP, LayerKind.KAN, LayerKind.MLP]
    )
    assert plan.modes[2] is ExecMode.PIPELINE
    assert plan.n_switches == 2
    assert plan.segments() == [
        (ExecMode.PARALLEL, 2), (ExecMode.PIPELINE, 1), (ExecMode.PARALLEL, 1)
    ]
