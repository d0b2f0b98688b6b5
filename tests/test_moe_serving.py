"""The KAN-FFN expert transformer (``kanffn-moe-ci``: the served
qwen3-moe-235b-a22b-kanffn's blocks at d_model 64, 16 experts, top-4, 4
held) against the plain reference of the benchmark
(benchmarks/chip/reference/moe_transformer.py), float32 on seeded random
weights:

* prefill then cached decode through ``Engine`` + ``TransformerBackend``
  agrees on logits with the reference's full forward;
* the expert layer is dropless: a token's output is the same alone, in an
  8-row decode step and in a 64-row prefill;
* the share test: the 4 disjoint shares of the experts add up to the
  uncut reference layer;
* the grouped KAN kernel (interpret mode, and its jnp path) matches
  kan_fused/ref.py expert by expert, with an empty group and groups that
  are not a whole number of row tiles;
* the counts the jitted programs return are the routing's.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.reference import moe_transformer as R
from repro.configs.registry import KANFFN_ARCHS
from repro.core.splines import SplineSpec
from repro.kernels.kan_fused.grouped import kan_grouped
from repro.kernels.kan_fused.ops import flatten_t, fuse_wt
from repro.kernels.kan_fused.ref import kan_layer_ref
from repro.models import moe as M
from repro.models import transformer as T
from repro.runtime.backends import TransformerBackend
from repro.runtime.server import Engine

ARCH = KANFFN_ARCHS["kanffn-moe-ci"]
IMPLS = ["jnp", "pallas_interpret"]
# float32 throughout; the served and reference sums differ only in order
ATOL = 2e-4


def ref_cfg(arch=ARCH) -> dict:
    """The reference's configuration of ``arch`` (benchmark key names)."""
    first, held = arch.held_experts
    return {"d_model": arch.d_model, "n_heads": arch.n_heads,
            "n_kv_heads": arch.n_kv_heads, "head_dim": arch.hd,
            "rope_theta": arch.rope_base, "rms_norm_eps": 1e-6,
            "router_experts": arch.n_experts, "top_k": arch.top_k,
            "first_expert": first, "num_experts": held,
            "kan_hidden": arch.moe_cfg().expert_ffn_cfg().kanffn_hidden,
            "kan_grid": arch.kan_grid, "kan_order": arch.kan_order,
            "kan_basis_keep": list(range(arch.kan_grid + arch.kan_order)),
            "spline": {"domain": [-1.0, 1.0], "clamp_eps": 1e-6}}


@pytest.fixture(scope="module")
def params():
    p = T.init_params(jax.random.key(3), ARCH)
    # spread the tiny model's logits and expert outputs as far as the
    # served model's, so a wrong expert shows
    p["embed"]["table"] = p["embed"]["table"] * 4.0
    for lp in p["extra"]:
        lp["moe"]["experts"]["w"] = lp["moe"]["experts"]["w"] * 3.0
        lp["moe"]["experts"]["b"] = (
            0.1 * jax.random.normal(jax.random.key(5),
                                    lp["moe"]["experts"]["b"].shape))
    return p


@pytest.mark.parametrize("impl", IMPLS)
def test_prefill_then_cached_decode_matches_reference(params, impl):
    be = TransformerBackend(ARCH, params, impl=impl)
    eng = Engine(be, n_slots=8, max_len=32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, ARCH.vocab_size, n).astype(np.int32)
               for n in (5, 11, 16)]
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    out = eng.run_until_done()
    ref = R.ReferenceMoE(params, ref_cfg(), positions=8)
    for rid, prompt in zip(rids, prompts):
        served = out[rid]
        seq = np.concatenate([prompt, served[:-1]])
        want = np.asarray(ref.logits(seq, range(len(prompt) - 1, len(seq))))
        # served tokens are the reference's argmax at every position
        assert list(np.argmax(want, -1)) == list(served), rid
        # and the served logits, prefill then decode through the cache,
        # are the reference's
        logits, cache = be.prefill_logits(prompt[None, :])
        got = [np.asarray(logits)[0, -1]]
        for tok in served[:-1]:
            lg, cache, _ = be._decode(be.params,
                                      jnp.asarray([[tok]], jnp.int32), cache)
            got.append(np.asarray(lg)[0, -1])
        np.testing.assert_allclose(np.stack(got), want, atol=ATOL)


@pytest.mark.parametrize("impl", IMPLS)
def test_expert_layer_is_dropless_batch_invariant(params, impl):
    """Token 0's output alone, as one row of an 8-row step and of a 64-row
    prefill: the same (a capacity-bounded layer drops it in the prefill,
    where 64 rows outnumber its slots)."""
    cfg = dataclasses.replace(ARCH.moe_cfg(), impl=impl)
    x = jax.random.normal(jax.random.key(1), (64, ARCH.d_model))
    # every row routed like row 0, whose first pick is held expert 0:
    # 64 rows for the same experts
    x = x.at[1:].set(x[0] + 0.01 * x[1:])
    p = dict(params["extra"][0]["moe"])
    p["router"] = {"kernel": p["router"]["kernel"].at[:, 0].set(x[0] / 4)}
    alone = M.moe_apply(p, x[None, :1], cfg)["out"][0, 0]
    step = M.moe_apply(p, x[:8, None], cfg)["out"][0, 0]
    prefill = M.moe_apply(p, x[None], cfg)
    np.testing.assert_allclose(step, alone, atol=1e-5)
    np.testing.assert_allclose(prefill["out"][0, 0], alone, atol=1e-5)
    assert int(prefill["counts"]["max_group"]) == 64


def test_shares_add_up_to_the_uncut_layer(params):
    """Four chips' shares (experts 0-3, 4-7, 8-11, 12-15) of a layer whose
    experts all exist: their outputs sum to the uncut reference layer."""
    full = dataclasses.replace(ARCH, held_experts=(0, 16))
    p = T.init_params(jax.random.key(7), full)["extra"][0]["moe"]
    x = jax.random.normal(jax.random.key(2), (1, 24, ARCH.d_model))
    parts = []
    for first in range(0, 16, 4):
        share = dataclasses.replace(ARCH, held_experts=(first, 4))
        sp = {"router": p["router"],
              "experts": jax.tree.map(lambda a: a[first:first + 4],
                                      p["experts"])}
        parts.append(M.moe_apply(sp, x, share.moe_cfg())["out"])
    want = R.experts(x[0], p, ref_cfg(full), "highest")
    np.testing.assert_allclose(sum(parts)[0], want, atol=ATOL)
    whole = M.moe_apply(p, x, full.moe_cfg())["out"]
    np.testing.assert_allclose(whole[0], want, atol=ATOL)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("sizes", [(5, 0, 11), (8, 3, 0, 16)])
def test_grouped_kernel_matches_ref_per_expert(impl, sizes):
    spec = SplineSpec(4, 3)
    d, h, bm = 64, 28, 8
    n = len(sizes)
    k1, k2, k3 = jax.random.split(jax.random.key(4), 3)
    w_b = jax.random.normal(k1, (n, d, h)) / 8
    t = jax.random.normal(k2, (n, d, spec.n_bases, h)) / 8
    wt = jnp.stack([fuse_wt(w_b[e], flatten_t(t[e]), spec.n_bases).T
                    for e in range(n)])
    xs = 0.7 * jax.random.normal(k3, (sum(sizes), d))
    blocks, tile_expert, offs = [], [], np.cumsum((0,) + sizes)
    for e, s in enumerate(sizes):
        padded = -(-s // bm) * bm
        blocks.append(jnp.zeros((padded, d)).at[:s].set(
            xs[offs[e]:offs[e] + s]))
        tile_expert += [e] * (padded // bm)
    n_used = len(tile_expert)
    x = jnp.concatenate(blocks + [jnp.zeros((2 * bm, d))])   # 2 unused
    tile_expert = jnp.asarray(tile_expert + [n - 1] * 2, jnp.int32)
    y = kan_grouped(x, wt, tile_expert, jnp.int32(n_used), spec, bm=bm,
                    impl=impl)
    row = 0
    for e, s in enumerate(sizes):
        want = kan_layer_ref(xs[offs[e]:offs[e] + s], w_b[e], t[e], spec)
        np.testing.assert_allclose(y[row:row + s], want, atol=1e-5)
        row += -(-s // bm) * bm


def test_programs_count_the_routing(params):
    """The counts beside the prefill's logits are the routing's: rows of
    the prompt routed to held experts, held experts with a row, and the
    largest group, each summed (largest: taken) over the layers."""
    be = TransformerBackend(ARCH, params, impl="jnp")
    be.n_slots, be.max_len = 8, 32
    prompt = np.random.default_rng(1).integers(
        0, ARCH.vocab_size, (1, 12)).astype(np.int32)
    _, _, counts = be._prefill_fn(12)(be.params, jnp.asarray(prompt))
    first, held = ARCH.held_experts
    x = T._embed_in(params, ARCH, jnp.asarray(prompt))
    want = {"routed": 0, "active": 0, "max_group": 0}
    for lp in params["extra"]:
        xn = T._norm(ARCH, lp["moe_norm"], x + T.A.attention(
            lp["attn"], T._norm(ARCH, lp["attn_norm"], x), ARCH.attn_cfg()))
        logits = xn[0] @ lp["moe"]["router"]["kernel"]
        top = np.asarray(jax.lax.top_k(logits, ARCH.top_k)[1]) - first
        sizes = np.bincount(top[(top >= 0) & (top < held)], minlength=held)
        want["routed"] += int(sizes.sum())
        want["active"] += int((sizes > 0).sum())
        want["max_group"] = max(want["max_group"], int(sizes.max()))
        x, _ = T.block_apply(lp, x, ARCH, "attn")
    assert {k: int(v) for k, v in counts.items()} == want
    # an arch without MoE layers returns no counts
    dense = TransformerBackend(KANFFN_ARCHS["kanffn-ci"], T.init_params(
        jax.random.key(0), KANFFN_ARCHS["kanffn-ci"]), impl="jnp")
    dense.n_slots, dense.max_len = 8, 32
    assert dense._prefill_fn(12)(dense.params, jnp.asarray(prompt))[2] == {}


def test_counts_reach_the_backend_spans(params, tmp_path):
    """Under a profiler, each ``backend.prefill`` / ``backend.step`` record
    holds the counts its program returned: the prefill's are those of the
    prompt alone, and no step routes more rows than 8 slots' top-4."""
    from repro.runtime import spans

    be = TransformerBackend(ARCH, params, impl="jnp")
    eng = Engine(be, n_slots=8, max_len=32)
    prompts = [np.arange(n, dtype=np.int32) * 7 % ARCH.vocab_size
               for n in (6, 9)]
    spans.RECORDER.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for p in prompts:
            eng.submit(p, max_new_tokens=3)
        eng.run_until_done()
        recs = spans.snapshot().records
    finally:
        jax.profiler.stop_trace()
        spans.RECORDER.clear()
    prefills = [r.info for r in recs if r.name == "backend.prefill"]
    for info, p in zip(prefills, prompts):
        want = be._prefill_fn(len(p))(be.params, jnp.asarray(p[None]))[2]
        assert {k: info[k] for k in want} == {k: int(v)
                                              for k, v in want.items()}
    steps = [r.info for r in recs if r.name == "backend.step"]
    assert len(prefills) == 2 and steps
    for info in steps:
        assert 0 <= info["max_group"] <= 8
        assert info["active"] <= ARCH.n_layers * ARCH.held_experts[1]
        assert info["routed"] <= ARCH.n_layers * 8 * ARCH.top_k
