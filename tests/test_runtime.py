"""Fault-tolerant trainer + batched server behaviour tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import KANFFN_ARCHS, get_config
from repro.data.lm import LMDataConfig, Prefetcher, SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import StepOptions
from repro.models import transformer as T
from repro.runtime.backends import TransformerBackend
from repro.runtime.server import Engine, Server
from repro.runtime.trainer import SimulatedFailure, Trainer, TrainerConfig


def _tiny_setup(tmp_path, max_steps=8, failure_at=None, ckpt_every=2,
                seed=0):
    cfg = get_config("qwen2-0.5b").reduce(n_layers=2, d_model=32, d_ff=64,
                                          vocab_size=64)
    data = SyntheticLM(LMDataConfig(vocab_size=64, seq_len=16,
                                    global_batch=4, seed=7))
    tcfg = TrainerConfig(max_steps=max_steps, ckpt_dir=str(tmp_path / "ck"),
                         ckpt_every=ckpt_every, failure_at=failure_at,
                         log_every=100, seed=seed)
    mesh = make_host_mesh()
    # lr high enough that 12 steps beat the zipf-unigram noise floor by a
    # clear margin (at 1e-3 the loss hovers within noise of ln(vocab) and
    # the decrease assertion is a coin flip on the pinned toolchain)
    opts = StepOptions(lr=1e-2, total_steps=max_steps, warmup=0)
    return Trainer(cfg, tcfg, mesh, data, opts)


def test_trainer_loss_decreases(tmp_path):
    tr = _tiny_setup(tmp_path, max_steps=12)
    out = tr.run()
    losses = [m["loss"] for m in out["metrics"]]
    assert out["final_step"] == 12
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_failure_injection_raises(tmp_path):
    tr = _tiny_setup(tmp_path, max_steps=8, failure_at=3, ckpt_every=2)
    with pytest.raises(SimulatedFailure):
        tr.run()


@pytest.mark.slow
def test_restart_recovers_and_is_deterministic(tmp_path):
    """Kill at step 5, restart from ckpt at 4 -> final params identical to
    an uninterrupted run (deterministic data + step-keyed state)."""
    clean = _tiny_setup(tmp_path / "a", max_steps=8)
    clean_out = clean.run()
    faulty = _tiny_setup(tmp_path / "b", max_steps=8, failure_at=5,
                         ckpt_every=1)
    out = faulty.run_with_restarts(max_restarts=2)
    assert out["final_step"] == 8
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=1e-5),
        clean.state["params"], faulty.state["params"])


def test_straggler_watchdog_fires(tmp_path):
    events = []
    tr = _tiny_setup(tmp_path, max_steps=10)
    tr.on_straggler = lambda s, dt: events.append(s)
    # inject an artificially slow "step" time via the watchdog directly
    for s in range(6):
        tr._watchdog(s, 0.01)
    tr._watchdog(6, 0.5)
    assert events == [6]


def test_prefetcher_orders_batches():
    src = SyntheticLM(LMDataConfig(vocab_size=16, seq_len=4, global_batch=2))
    pf = Prefetcher(src, start_step=3, depth=2)
    steps = [next(pf)[0] for _ in range(4)]
    pf.close()
    assert steps == [3, 4, 5, 6]


def test_shard_determinism():
    base = LMDataConfig(vocab_size=97, seq_len=8, global_batch=4, n_shards=2)
    s0 = SyntheticLM(dataclasses.replace(base, shard_id=0))
    s1 = SyntheticLM(dataclasses.replace(base, shard_id=1))
    a0, a1 = s0.batch_at(5)["tokens"], s1.batch_at(5)["tokens"]
    assert a0.shape == (2, 9)
    assert not np.array_equal(a0, a1)          # disjoint shard streams
    np.testing.assert_array_equal(a0, s0.batch_at(5)["tokens"])  # replayable


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------

def _server_setup(n_slots=3, max_len=64):
    cfg = get_config("qwen2-0.5b").reduce(n_layers=2, d_model=32, d_ff=64,
                                          vocab_size=64)
    params = T.init_params(jax.random.key(0), cfg)
    return cfg, params, Server(cfg, params, n_slots=n_slots, max_len=max_len)


def test_server_single_request_matches_manual_decode():
    cfg, params, srv = _server_setup()
    prompt = np.array([5, 9, 2, 7], np.int32)
    rid = srv.submit(prompt, max_new_tokens=6)
    out = srv.run_until_done()
    # manual greedy decode
    logits, caches = T.prefill(params, cfg, jnp.asarray(prompt[None, :]),
                               max_len=64)
    toks = [int(T.greedy_token(logits)[0, 0])]
    for _ in range(5):
        lg, caches = T.decode_step(
            params, cfg, jnp.asarray([[toks[-1]]], jnp.int32), caches)
        toks.append(int(T.greedy_token(lg)[0, 0]))
    assert out[rid] == toks


def test_server_batched_requests_isolated():
    """Concurrent requests must not contaminate each other's outputs."""
    cfg, params, srv = _server_setup(n_slots=3)
    prompts = [np.array([1, 2, 3], np.int32),
               np.array([10, 20, 30, 40, 50], np.int32),
               np.array([7], np.int32)]
    rids = [srv.submit(p, max_new_tokens=5) for p in prompts]
    batched = srv.run_until_done()

    for p, rid in zip(prompts, rids):
        cfg2, params2, solo = _server_setup(n_slots=1)
        srid = solo.submit(p, max_new_tokens=5)
        solo_out = solo.run_until_done()
        assert batched[rid] == solo_out[srid], f"slot contamination on {rid}"


def test_server_slot_reuse():
    cfg, params, srv = _server_setup(n_slots=1)
    r1 = srv.submit(np.array([3, 4], np.int32), max_new_tokens=3)
    r2 = srv.submit(np.array([9, 8, 7], np.int32), max_new_tokens=3)
    out = srv.run_until_done()
    assert len(out[r1]) == 3 and len(out[r2]) == 3


def _manual_decode(cfg, params, prompt, n_new, max_len):
    """Greedy prefill + decode_step of one request alone (batch 1)."""
    logits, caches = T.prefill(params, cfg, jnp.asarray(prompt[None, :]),
                               max_len=max_len)
    decode = jax.jit(lambda p, t, c: T.decode_step(p, cfg, t, c))
    toks = [int(T.greedy_token(logits)[0, 0])]
    while len(toks) < n_new:
        lg, caches = decode(params, jnp.asarray([[toks[-1]]], jnp.int32),
                            caches)
        toks.append(int(T.greedy_token(lg)[0, 0]))
    return toks


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "kanffn-ci"])
def test_server_every_slot_and_reused_slot_match_manual_decode(arch):
    """Prefills spliced into slots 1 and 2, and into slots freed by a
    finished request, decode what each request decodes alone."""
    if arch == "kanffn-ci":
        cfg, impl = KANFFN_ARCHS[arch], "jnp"
    else:
        cfg, impl = get_config(arch).reduce(n_layers=2, d_model=32, d_ff=64,
                                            vocab_size=64), None
    be = TransformerBackend(cfg, T.init_params(jax.random.key(0), cfg),
                            impl=impl)
    slots = []
    prefill = be.prefill

    def recording_prefill(caches, slot, req):
        slots.append(slot)
        return prefill(caches, slot, req)

    be.prefill = recording_prefill
    eng = Engine(be, n_slots=3, max_len=32)
    rng = np.random.default_rng(3)
    work = [(rng.integers(0, cfg.vocab_size, size=n).astype(np.int32), m)
            for n, m in ((4, 2), (6, 5), (4, 3), (6, 4), (4, 3))]
    rids = [eng.submit(p, max_new_tokens=m) for p, m in work]
    out = eng.run_until_done()
    assert sorted(set(slots)) == [0, 1, 2] and len(slots) == len(work)
    for (p, m), rid in zip(work, rids):
        assert out[rid] == _manual_decode(be.cfg, be.params, p, m, 32), rid


def _random_tree(tree, seed):
    """``tree``'s shapes and dtypes, filled with seeded values, so a lane
    written to the wrong place or in the wrong dtype shows."""
    leaves, treedef = jax.tree.flatten(tree)
    rng = np.random.default_rng(seed)
    out = []
    for a in leaves:
        if jnp.issubdtype(a.dtype, jnp.integer):
            v = rng.integers(-100, 100, size=a.shape)
        else:
            v = rng.standard_normal(a.shape)
        out.append(jnp.asarray(v, a.dtype))
    return jax.tree.unflatten(treedef, out)


def _eager_splice(full_tree, new_tree, slot, n_slots):
    """The per-leaf ``.at[].set`` the jitted splice replaced."""
    def put(full, new):
        for d in range(min(2, full.ndim)):
            if (full.shape[d] == n_slots and d < new.ndim
                    and new.shape[d] == 1):
                sl = tuple([slice(None)] * d + [slice(slot, slot + 1)])
                return full.at[sl].set(new.astype(full.dtype))
        return full
    return jax.tree.map(put, full_tree, new_tree)


# batch on dim 0 in the extra list (with 'len'); on dim 1 under the unit
# stack; int8 KV with fp16 scales; recurrent, xLSTM and cross-attention caches
SPLICE_ARCHS = {
    "kanffn-ci": lambda: KANFFN_ARCHS["kanffn-ci"],
    "qwen2-0.5b": lambda: get_config("qwen2-0.5b").reduce(),
    "qwen2-0.5b-kv-int8": lambda: get_config("qwen2-0.5b").reduce(
        kv_quant=True),
    "recurrentgemma-9b": lambda: get_config("recurrentgemma-9b").reduce(),
    "xlstm-125m": lambda: get_config("xlstm-125m").reduce(),
    "whisper-medium": lambda: get_config("whisper-medium").reduce(),
}
SPLICE_SLOTS = 4


def _splice_setup(arch):
    """(backend, slot caches, fresh batch-1 cache), both filled with seeded
    values of the served dtypes."""
    cfg = SPLICE_ARCHS[arch]()
    be = TransformerBackend(cfg, T.init_params(jax.random.key(0), cfg))
    full = _random_tree(be.init_state(SPLICE_SLOTS, 16), 0)
    new = _random_tree(T.init_caches(be.cfg, 1, 16), 1)
    return be, full, new


@pytest.mark.parametrize("slot", range(SPLICE_SLOTS))
@pytest.mark.parametrize("arch", sorted(SPLICE_ARCHS))
def test_transformer_splice_bitwise_equals_eager_set(arch, slot):
    be, full, new = _splice_setup(arch)
    got = be._splice(full, new, np.int32(slot))
    want = _eager_splice(full, new, slot, SPLICE_SLOTS)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w, f in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(full)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        # every leaf of these archs has a slot lane: none passes through
        assert not np.array_equal(np.asarray(g), np.asarray(f))


@pytest.mark.parametrize("arch", sorted(SPLICE_ARCHS))
def test_transformer_splice_one_program_serves_every_slot(arch):
    be, full, new = _splice_setup(arch)
    got, want = full, full
    for slot in range(SPLICE_SLOTS):
        got = be._splice(got, new, np.int32(slot))
        want = _eager_splice(want, new, slot, SPLICE_SLOTS)
    assert be._splice._cache_size() == 1
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# VIKIN backend (stacked KAN/MLP feed-forward serving)
# ---------------------------------------------------------------------------

from repro.configs.vikin_models import VIKIN_ARCHS
from repro.models.ffn import vikin_stack_apply, vikin_stack_init
from repro.runtime.backends import VikinBackend
from repro.runtime.server import Engine


def _vikin_engine(arch="vikin-small", n_slots=4, seed=0, impl="auto"):
    model = VIKIN_ARCHS[arch]
    params = vikin_stack_init(jax.random.key(seed), model)
    return model, params, Engine(VikinBackend(model, params, impl=impl),
                                 n_slots=n_slots)


def _feature_burst(model, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.random(model.sizes[0], dtype=np.float32) for _ in range(n)]


def test_vikin_batched_equals_single_bitwise():
    """Serving N mixed KAN/MLP requests across slots must be BITWISE
    identical to one-at-a-time execution (zero-padded shape buckets +
    row-independent contractions; min_bucket=2 avoids XLA's gemv path)."""
    model, params, eng = _vikin_engine("vikin-mixed", n_slots=4)
    prompts = _feature_burst(model, 6)
    rids = [eng.submit(p) for p in prompts]
    batched = eng.run_until_done()

    _, _, solo_eng = _vikin_engine("vikin-mixed", n_slots=4)
    for p, rid in zip(prompts, rids):
        srid = solo_eng.submit(p)
        solo = solo_eng.run_until_done()
        assert np.array_equal(batched[rid], solo[srid]), (
            f"batched != single for request {rid}")


def test_vikin_slot_reuse_and_completion():
    model, params, eng = _vikin_engine(n_slots=2)
    rids = [eng.submit(p) for p in _feature_burst(model, 5)]
    out = eng.run_until_done()
    assert sorted(out) == sorted(rids)
    assert all(out[r].shape == (model.sizes[-1],) for r in rids)
    assert eng.stats["served"] == 5
    assert eng.stats["ticks"] == 3          # 2 + 2 + 1 across 2 slots


def test_vikin_stats_report_simulated_cycles_and_modes():
    model, params, eng = _vikin_engine("vikin-small", n_slots=4)
    for p in _feature_burst(model, 4):
        eng.submit(p)
    eng.run_until_done()
    s = eng.stats
    assert s["sim_cycles"] > 0 and s["sim_latency_s"] > 0
    # vikin-small is mlp->kan: one internal switch per served instance,
    # plus -- under the carry-over contract (DESIGN.md Sec. 14) -- one
    # boundary flip per instance boundary because the plan exits PIPELINE
    # and re-enters PARALLEL.  4 rows, one batch, cold start: 4 + 3.
    assert s["mode_switches"] == 4 + 3
    assert s["reconfig_cycles"] == 7 * 8
    tp = eng.throughput()
    assert tp["requests"] == 4 and tp["sim_rps"] > 0


def test_vikin_step_matches_direct_stack_apply():
    """The engine's output is the plain stack forward on the same bucket."""
    model, params, eng = _vikin_engine("vikin-small", n_slots=2)
    prompts = _feature_burst(model, 2)
    rids = [eng.submit(p) for p in prompts]
    out = eng.run_until_done()
    direct = np.asarray(vikin_stack_apply(
        params, jnp.asarray(np.stack(prompts)), model))
    for j, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid], direct[j])


def test_vikin_results_returned_exactly_once():
    """Successive run_until_done calls hand each request back once (no
    unbounded result accumulation in a long-lived engine)."""
    model, params, eng = _vikin_engine(n_slots=2)
    first = [eng.submit(p) for p in _feature_burst(model, 2, seed=1)]
    out1 = eng.run_until_done()
    assert sorted(out1) == sorted(first)
    second = [eng.submit(p) for p in _feature_burst(model, 2, seed=2)]
    out2 = eng.run_until_done()
    assert sorted(out2) == sorted(second)       # no historical results
    assert eng.stats["served"] == 4


def test_vikin_rejects_wrong_feature_width_at_submit():
    """Bad payloads are rejected before queueing, so a malformed request
    can never abort a run mid-flight and drop admitted work."""
    model, params, eng = _vikin_engine()
    good = eng.submit(np.zeros(model.sizes[0], np.float32))
    with pytest.raises(ValueError, match="features"):
        eng.submit(np.zeros(model.sizes[0] + 1, np.float32))
    out = eng.run_until_done()          # the good request still completes
    assert out[good].shape == (model.sizes[-1],)


def test_vikin_bucket_quantization():
    model, params, eng = _vikin_engine(n_slots=8)
    b = eng.backend
    assert [b.bucket(n) for n in (1, 2, 3, 4, 5, 8)] == [2, 2, 4, 4, 8, 8]
    # non-pow2 slot counts still serve pow2 buckets (determinism regime)
    _, _, eng3 = _vikin_engine(n_slots=3)
    assert [eng3.backend.bucket(n) for n in (1, 2, 3)] == [2, 2, 4]


# ---------------------------------------------------------------------------
# Engine bug sweep regressions (ISSUE 5 satellites).
# ---------------------------------------------------------------------------

from repro.runtime.server import IncompleteRunError


def test_run_until_done_raises_instead_of_dropping_on_max_ticks():
    """Hitting max_ticks used to silently delete unfinished requests from
    the engine and return the partial result set as if complete."""
    model, params, eng = _vikin_engine(n_slots=1)
    rids = [eng.submit(p) for p in _feature_burst(model, 4)]
    with pytest.raises(IncompleteRunError) as exc:
        eng.run_until_done(max_ticks=2)
    # the two served ticks completed two requests; the rest are pending,
    # not dropped
    assert len(exc.value.completed) == 2
    assert len(exc.value.pending) == 2
    assert set(exc.value.completed) | set(exc.value.pending) == set(rids)
    # nothing was lost: a follow-up call hands back the FULL result set
    out = eng.run_until_done()
    assert sorted(out) == sorted(rids)
    assert all(out[r].shape == (model.sizes[-1],) for r in rids)


def test_freed_slots_readmit_within_the_same_tick():
    """Slots recycled at the end of tick() must be re-staged immediately:
    under a saturated queue every lane leaves the tick busy, and ticks to
    drain stays at the ceil(n/slots) floor."""
    model, params, eng = _vikin_engine(n_slots=2)
    for p in _feature_burst(model, 6):
        eng.submit(p)
    eng.tick()
    assert eng.stats["served"] == 2
    # the freed lanes already hold the next batch (was: both None until
    # the next tick's admission)
    assert all(r is not None for r in eng.slot_req)
    ticks = 1
    while eng.stats["served"] < 6:
        eng.tick()
        ticks += 1
    assert ticks == 3                       # 6 requests / 2 slots
    assert all(r is None for r in eng.slot_req)


def test_throughput_reports_wall_rps_when_tick_driven_directly():
    """tick() times itself, so wall throughput no longer depends on going
    through run_until_done."""
    model, params, eng = _vikin_engine(n_slots=2)
    for p in _feature_burst(model, 4):
        eng.submit(p)
    while eng.stats["served"] < 4:
        eng.tick()
    assert eng.stats["wall_s"] > 0
    tp = eng.throughput()
    assert tp["requests"] == 4 and tp["wall_rps"] > 0
