"""KAN-FFN transformer serving through the engine (DESIGN.md Sec. 17).

Same protocol as tests/test_scheduler.py, pointed at a kan-ffn hybrid:

  * batched greedy decode through ``Engine`` == fresh single-request
    engines at the SAME n_slots, token-exact;
  * ModePlan flip-count pins for the mixed ``("mlp", "kan", "mlp")`` stack
    -- the hybrid's plan opens and closes in parallel mode, so fifo and
    mode-affinity charge IDENTICAL flips and the carried interconnect mode
    never pays an entry flip between kan-ffn batches;
  * per-layer cycle attribution sums exactly to the serving report, and
    the engine's run total factorizes as (model instances) x (batch=1
    cycles) -- the cycle model has no hidden batch interaction.
"""
import numpy as np
import pytest

import jax

from repro.configs.registry import KANFFN_ARCHS
from repro.core.engine import serving_report
from repro.core.modes import RECONFIG_CYCLES, ExecMode
from repro.models import transformer as T
from repro.runtime.backends import TransformerBackend
from repro.runtime.server import Engine


@pytest.fixture(scope="module")
def ci_setup():
    cfg = KANFFN_ARCHS["kanffn-ci"]
    params = T.init_params(jax.random.key(0), cfg)
    return cfg, params


def _prompts(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size,
                         size=int(rng.integers(3, 8))).astype(np.int32)
            for _ in range(n)]


def test_batched_equals_single_token_exact(ci_setup):
    cfg, params = ci_setup
    backend = TransformerBackend(cfg, params, impl="jnp")
    prompts = _prompts(cfg, 5)
    eng = Engine(backend, n_slots=4, max_len=32)
    rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    batched = eng.run_until_done()
    for i, p in enumerate(prompts):
        eng1 = Engine(backend, n_slots=4, max_len=32)
        rid = eng1.submit(p, max_new_tokens=4)
        single = eng1.run_until_done()[rid]
        assert batched[rids[i]] == single, f"request {i} diverged"


def test_mode_plan_shape(ci_setup):
    cfg, params = ci_setup
    backend = TransformerBackend(cfg, params, impl="jnp")
    plan = backend.plan
    # ("mlp", "kan", "mlp"): attention + mlp phases parallel, one pipeline
    # segment for the kan up-projection, closing parallel
    assert plan.summary()["segments"] == [
        ("parallel", 4), ("pipeline", 1), ("parallel", 4)]
    assert plan.n_switches == 2
    assert plan.first_mode == plan.last_mode == ExecMode.PARALLEL


def test_stream_switches_carry_over(ci_setup):
    cfg, params = ci_setup
    plan = TransformerBackend(cfg, params, impl="jnp").plan
    # cold start: no entry flip; boundaries are free (last == first)
    assert plan.stream_switches(3, None) == (6, ExecMode.PARALLEL)
    # carried parallel mode agrees with the plan's first mode: still free
    assert plan.stream_switches(3, ExecMode.PARALLEL) == (
        6, ExecMode.PARALLEL)
    # carried pipeline mode pays exactly one entry flip
    assert plan.stream_switches(3, ExecMode.PIPELINE) == (
        7, ExecMode.PARALLEL)


@pytest.mark.parametrize("policy", ["fifo", "mode-affinity"])
def test_engine_flip_count_pins(ci_setup, policy):
    """N requests cost exactly (prompt tokens + decode steps) x n_switches
    flips with no entry or boundary extras, under BOTH policies (the plan
    opens and closes parallel, so policy order cannot change the charge)."""
    cfg, params = ci_setup
    backend = TransformerBackend(cfg, params, impl="jnp")
    prompts = _prompts(cfg, 4)
    new_tokens = 4
    eng = Engine(backend, n_slots=2, max_len=32, policy=policy)
    for p in prompts:
        eng.submit(p, max_new_tokens=new_tokens)
    eng.run_until_done()
    # one model instance per prefilled prompt token + one per decode step
    # (the first generated token comes out of prefill)
    instances = sum(len(p) for p in prompts) + len(prompts) * (new_tokens - 1)
    assert eng.stats["mode_switches"] == 2 * instances
    assert eng.stats["reconfig_cycles"] == 2 * instances * RECONFIG_CYCLES
    assert eng.hw_mode == ExecMode.PARALLEL


def test_cycle_attribution_sums_to_report(ci_setup):
    cfg, params = ci_setup
    backend = TransformerBackend(cfg, params, impl="jnp")
    for batch in (1, 2, 5):
        for prev in (None, ExecMode.PARALLEL, ExecMode.PIPELINE):
            att = backend.cycle_attribution(batch, prev_mode=prev)
            rep = serving_report(backend.layers, backend.hw, batch=batch,
                                 prev_mode=prev, precision="f32")
            total = sum(att["per_layer_cycles"]) + att["reconfig_cycles"]
            assert np.isclose(total, rep["sim_cycles"], rtol=1e-12), (
                batch, prev, total, rep["sim_cycles"])
            assert len(att["per_layer_cycles"]) == len(backend.layers)


def test_engine_total_factorizes(ci_setup):
    """stats['sim_cycles'] == instances x batch=1 cycles: batches stream
    through one engine instance and no cross-batch charge hides in the
    totals (the per-layer attribution covers everything)."""
    cfg, params = ci_setup
    backend = TransformerBackend(cfg, params, impl="jnp")
    prompts = _prompts(cfg, 3, seed=7)
    eng = Engine(backend, n_slots=2, max_len=32)
    for p in prompts:
        eng.submit(p, max_new_tokens=3)
    eng.run_until_done()
    instances = sum(len(p) for p in prompts) + len(prompts) * 2
    per_instance = serving_report(backend.layers, backend.hw, batch=1,
                                  precision="f32")["sim_cycles"]
    assert np.isclose(eng.stats["sim_cycles"], instances * per_instance,
                      rtol=1e-9)


def test_plain_arch_keeps_null_report(ci_setup):
    """Archs without ffn_kinds keep the no-hardware-model contract."""
    import dataclasses

    cfg, _ = ci_setup
    plain = dataclasses.replace(cfg, name="plain", ffn_kinds=None,
                                ffn_masks=None)
    params = T.init_params(jax.random.key(0), plain)
    backend = TransformerBackend(plain, params)
    assert backend.plan is None and backend.layers is None
    assert backend.batch_report(2) is None


@pytest.mark.parametrize("kinds,precision,served", [
    (None, "f32", "bfloat16"),          # plain arch keeps its config dtype
    (None, "bf16", "bfloat16"),
    (("mlp", "kan", "mlp"), "f32", "float32"),   # kan-ffn follows precision
    (("mlp", "kan", "mlp"), "bf16", "bfloat16"),
])
def test_served_dtype(ci_setup, kinds, precision, served):
    """The dtype a bf16-configured arch is served in, per precision."""
    import dataclasses

    import jax.numpy as jnp

    cfg, _ = ci_setup
    cfg = dataclasses.replace(cfg, name="bf16-arch", ffn_kinds=kinds,
                              ffn_masks=None, dtype="bfloat16")
    params = T.init_params(jax.random.key(0), cfg)
    backend = TransformerBackend(cfg, params, impl="jnp", precision=precision)
    assert backend.cfg.dtype == served
    floats = [a.dtype for a in jax.tree.leaves(backend.params)
              if jnp.issubdtype(a.dtype, jnp.floating)]
    assert floats and all(d == jnp.dtype(served) for d in floats)


def test_masked_serving_runs(ci_setup):
    """Calibrated two-stage masks thread end to end: calibrate -> serve."""
    cfg, params = ci_setup
    from repro.core.calibrate import calibrate_kanffn_masks

    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 12)).astype(np.int32)
    masks = calibrate_kanffn_masks(params, cfg, tokens, keep_per_group=2,
                                   impl="jnp")
    assert len(masks) == cfg.n_layers
    assert masks[0] is None and masks[2] is None
    bk, hk = masks[1]
    assert len(bk) >= 1 and len(hk) >= 1
    backend = TransformerBackend(cfg, params, impl="jnp", masks=masks)
    eng = Engine(backend, n_slots=2, max_len=32)
    rid = eng.submit(np.array([3, 1, 4], np.int32), max_new_tokens=3)
    out = eng.run_until_done()
    assert len(out[rid]) == 3
    # the cycle model charges the measured mask sparsity: masked serving
    # must be strictly cheaper per instance than dense
    dense = TransformerBackend(cfg, params, impl="jnp")
    c_masked = serving_report(backend.layers, backend.hw, batch=1,
                              precision="f32")["sim_cycles"]
    c_dense = serving_report(dense.layers, dense.hw, batch=1,
                             precision="f32")["sim_cycles"]
    assert c_masked < c_dense


@pytest.mark.slow
@pytest.mark.parametrize("arch,scale", [("kanffn-ci", "full"),
                                        ("qwen2-0.5b-kanffn", "smoke")])
def test_serve_launcher_e2e(arch, scale):
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", arch,
         "--scale", scale, "--requests", "3", "--new-tokens", "3",
         "--impl", "jnp"],
        capture_output=True, text=True, cwd=repo, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "kan-ffn hybrid" in r.stdout
    assert "simulated VIKIN" in r.stdout
