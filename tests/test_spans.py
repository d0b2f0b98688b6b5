"""The serving path's host spans (runtime/spans.py): off without a profiler,
complete and well nested under one, their counts in the profiler's trace,
and a ring that owns up to its drops."""
import jax
import numpy as np
import pytest

from repro.configs.registry import KANFFN_ARCHS
from repro.configs.vikin_models import VIKIN_ARCHS
from repro.models import transformer as T
from repro.models.ffn import vikin_stack_init
from repro.runtime import spans
from repro.runtime.backends import (
    ModelBackend,
    MultiWorkloadBackend,
    TransformerBackend,
    VikinBackend,
)
from repro.runtime.server import Engine

STACKS = ("vikin-kan2", "vikin-mlp3")

# the parent each span runs under (None: a root)
PARENT = {
    "engine.iter": None, "engine.queue": None,
    "engine.expire": "engine.iter", "engine.admit": "engine.iter",
    "engine.report": "engine.iter", "engine.retire": "engine.iter",
    "engine.select": "engine.admit",
    "backend.step": "engine.iter",
    "backend.step.inputs": "backend.step",
    "backend.step.dispatch": "backend.step",
    "backend.step.pick": "backend.step",
    "backend.step.readback": "backend.step",
    "backend.step.outputs": "backend.step",
    "backend.prefill": "engine.admit",
    "backend.prefill.dispatch": "backend.prefill",
    "backend.prefill.pick": "backend.prefill",
    "backend.prefill.readback": "backend.prefill",
    "backend.prefill.splice": "backend.prefill",
}
# the stacks have no prefill and no greedy pick
STACK_SPANS = {n for n in PARENT if not n.startswith("backend.prefill")
               and n != "backend.step.pick"}


class CountingAnnotation(jax.profiler.TraceAnnotation):
    entered = 0
    args = []           # (name, counts) of every annotation made

    def __init__(self, name, **kwargs):
        CountingAnnotation.args.append((name, kwargs))
        super().__init__(name, **kwargs)

    def __enter__(self):
        CountingAnnotation.entered += 1
        return super().__enter__()


@pytest.fixture
def annotations(monkeypatch):
    CountingAnnotation.entered = 0
    CountingAnnotation.args = []
    monkeypatch.setattr(spans, "TraceAnnotation", CountingAnnotation)
    spans.RECORDER.clear()
    yield CountingAnnotation
    spans.RECORDER.clear()


def stacks_engine():
    backends = {}
    for i, name in enumerate(STACKS):
        model = VIKIN_ARCHS[name]
        params = vikin_stack_init(jax.random.key(i), model)
        backends[name] = VikinBackend(model, params, impl="jnp")
    return Engine(MultiWorkloadBackend(backends), n_slots=4)


def serve_stacks(eng, n=10):
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.random(72, dtype=np.float32),
                       workload=STACKS[i % 2]) for i in range(n)]
    reqs = [eng._requests[r] for r in rids]
    eng.run_until_done()
    return reqs


def transformer_engine():
    cfg = KANFFN_ARCHS["kanffn-ci"]
    params = T.init_params(jax.random.key(0), cfg)
    return Engine(TransformerBackend(cfg, params, impl="jnp"), n_slots=2,
                  max_len=32)


def serve_transformer(eng, n=3):
    rng = np.random.default_rng(1)
    rids = [eng.submit(rng.integers(0, 256, 6 + i).astype(np.int32),
                       max_new_tokens=3) for i in range(n)]
    reqs = [eng._requests[r] for r in rids]
    eng.run_until_done()
    return reqs


def test_without_a_profiler_nothing_is_recorded(annotations):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    serve_stacks(stacks_engine())
    serve_transformer(transformer_engine())
    assert annotations.entered == 0
    snap = spans.snapshot()
    assert snap.records == [] and snap.dropped == 0
    with spans.span("x", n=1):
        pass
    assert spans.span("y") is spans.span("z")     # the shared no-op


def check_tree(records, reqs, names):
    by_index = {r.index: r for r in records}
    assert {r.name for r in records} == names
    for r in records:
        assert r.t0 <= r.t1
        want = PARENT[r.name]
        if want is None:
            assert r.parent is None, r
            continue
        parent = by_index[r.parent]
        assert parent.name == want, (r, parent)
        assert parent.t0 <= r.t0 and r.t1 <= parent.t1
    # one queue span per request, from its submit stamp to its selection
    queued = sorted((r.t0, r.t1) for r in records
                    if r.name == "engine.queue")
    assert queued == sorted((q.t_submit, q.t_admit) for q in reqs)
    for r in records:
        if r.name == "backend.step":
            assert 1 <= r.info["active"] <= r.info["computed"]
        if r.name == "engine.admit":
            assert set(r.info) == {"queued", "free"}
    return by_index


def test_under_a_profiler_every_span_is_recorded(annotations, tmp_path):
    eng, tf = stacks_engine(), transformer_engine()
    jax.profiler.start_trace(str(tmp_path))
    try:
        stack_reqs = serve_stacks(eng)
        stack_recs = spans.snapshot().records
        spans.RECORDER.clear()
        tf_reqs = serve_transformer(tf)
        tf_recs = spans.snapshot().records
    finally:
        jax.profiler.stop_trace()
    # every span but the queue's (recorded after the fact) lies in the
    # profiler's trace too, with the counts its record keeps
    traced = [r for r in stack_recs + tf_recs if r.name != "engine.queue"]
    assert annotations.entered == len(traced)
    assert sorted(annotations.args, key=repr) == sorted(
        ((r.name, r.info) for r in traced), key=repr)

    check_tree(stack_recs, stack_reqs, STACK_SPANS)
    steps = [r for r in stack_recs if r.name == "backend.step"]
    # one step per sub-backend stepped, each request served in one step,
    # and the padded bucket a power of two of at least 2
    assert sum(r.info["active"] for r in steps) == len(stack_reqs)
    assert all(r.info["computed"] in (2, 4) for r in steps)

    check_tree(tf_recs, tf_reqs, set(PARENT))
    prefills = [r for r in tf_recs if r.name == "backend.prefill"]
    assert [r.info["rid"] for r in prefills] == [q.rid for q in tf_reqs]
    assert [r.info["tokens"] for r in prefills] == [6, 7, 8]
    decode = [r for r in tf_recs if r.name == "backend.step"]
    assert all(r.info["computed"] == 2 for r in decode)
    # two decode steps per request of three tokens (the first is prefill's)
    assert sum(r.info["active"] for r in decode) == 2 * len(tf_reqs)


def test_full_ring_counts_its_drops(monkeypatch):
    monkeypatch.setattr(spans, "_enabled", lambda: True)
    ring = spans.Recorder(capacity=4)
    for i in range(6):
        with ring.span(f"s{i}"):
            pass
    snap = ring.snapshot()
    assert [r.name for r in snap.records] == ["s2", "s3", "s4", "s5"]
    assert [r.index for r in snap.records] == [2, 3, 4, 5]
    assert snap.dropped == 2
    # s1 was dropped: a window from its end on is incomplete, one that
    # starts after it ended is whole
    assert not snap.covers(snap.lost_until)
    assert snap.lost_until < snap.records[0].t0
    assert snap.covers(snap.records[0].t0)
    # records are kept in the order they end: a span open while its
    # children fill the ring outlives the first of them
    with ring.span("long"):
        for i in range(4):
            with ring.span(f"t{i}"):
                pass
    snap = ring.snapshot()
    assert [r.name for r in snap.records] == ["t1", "t2", "t3", "long"]
    assert snap.dropped == 7        # s0..s5 and t0
    long = snap.records[-1]
    assert [r.parent for r in snap.records[:3]] == [long.index] * 3
    # t0 ended inside "long": a window from its start is no longer whole
    assert not snap.covers(long.t0)
    ring.record("q", 1.0, 2.0)
    assert ring.snapshot().records[-1][1:] == ("q", 1.0, 2.0, None, {})
    with pytest.raises(ValueError):
        spans.Recorder(capacity=0)


class _SlowPrefill(ModelBackend):
    """A one-shot backend whose prefill takes one second of engine clock."""

    def __init__(self, clock):
        self.clock = clock

    def init_state(self, n_slots, max_len):
        return None

    def prefill(self, state, slot, req):
        self.clock.now += 1.0
        return state

    def step(self, state, slot_req):
        for r in slot_req:
            if r is not None:
                r.output, r.done = np.zeros(1), True
        return state


class _Clock:
    now = 0.0

    def __call__(self):
        return self.now


def test_queue_wait_ends_before_the_requests_own_prefill():
    clock = _Clock()
    eng = Engine(_SlowPrefill(clock), n_slots=1, clock=clock)
    a = eng.submit(np.zeros(1))
    b = eng.submit(np.zeros(1))
    reqs = {r: eng._requests[r] for r in (a, b)}
    eng.run_until_done()
    # a is selected at once; its prefill is service, not queue wait
    assert reqs[a].t_admit == 0.0 and reqs[a].t_done == 1.0
    # b waits through a's prefill, and is selected before its own
    assert reqs[b].t_admit == 1.0 and reqs[b].t_done == 2.0
    lat = eng.latency_stats()
    assert lat["p95_queue_wait_wall_s"] == 1.0
    assert lat["p95_service_wall_s"] == 1.0
