"""The trace reducers and the per-layer readers on a small synthetic
trace whose answers are worked out by hand."""
import json
import os

import pytest

from chip import counts, harness, tracing
from chip.systems.kanffn_transformer import normalized

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1e6    # nanoseconds per millisecond
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# as the TPU trace names the two Mosaic kernels' operations
KAN = ('%kan_fused_pallas_v2.3 = bf16[1024,1080]{1,0} custom-call(...), '
       'custom_call_target="tpu_custom_call"')
PMM = ('%matmul_compact_pallas.1 = bf16[1024,896]{1,0} custom-call('
       '%kan_fused_pallas_v2.3), custom_call_target="tpu_custom_call"')


def synthetic():
    """10 ms window: a prefill of 1024 tokens (0-4 ms) holding one KAN
    and one pattern-matmul call, a decode step of 8 slots (5-8 ms)
    holding one KAN call, and a fusion that overlaps the first kernel."""
    host = [(0.0, 10 * MS, "engine.tick"), (0.0, 4 * MS, "prefill:1024"),
            (5 * MS, 8 * MS, "step:8")]
    ops = [(1 * MS, 2 * MS, KAN, "jit_fn"),
           (1.5 * MS, 2.5 * MS, "%fusion.1 = f32[8]{0} fusion(...)",
            "jit_fn"),
           (3 * MS, 3.5 * MS, PMM, "jit_fn"),
           (6 * MS, 7 * MS, KAN, "jit__lambda")]
    return tracing.Trace(ops, host, (0.0, 10 * MS))


def view(trace):
    with open(os.path.join(CHIP, "configs", "qwen2-0.5b-kanffn.json")) as f:
        cfg = normalized(json.load(f))
    return harness.RunView(cfg, PEAKS, [], [], trace, None)


def test_union_and_busy():
    assert tracing.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    t = synthetic()
    # 1-2.5, 3-3.5, 6-7 ms
    assert t.busy_s == pytest.approx(3e-3)
    assert t.window_s == pytest.approx(10e-3)
    idle = harness.load_metric_reader("device_idle.kanffn")(view(t))
    assert idle == pytest.approx(70.0)


def test_enclosing_picks_the_innermost_annotation():
    t = synthetic()
    assert t.enclosing(1.5 * MS) == "prefill:1024"
    assert t.enclosing(4.5 * MS) == "engine.tick"
    assert t.enclosing(6 * MS, "step:") == "step:8"
    assert t.enclosing(11 * MS) is None


def test_roofline_share_by_hand():
    v = view(synthetic())
    cfg = v.config
    pre = counts.kanffn_kernel_calls(cfg, 1024)
    dec = counts.kanffn_kernel_calls(cfg, 8)
    want_kan = 100 * (counts.least_time_s(*pre["kan"], PEAKS)
                      + counts.least_time_s(*dec["kan"], PEAKS)) / 2e-3
    want_pmm = 100 * counts.least_time_s(*pre["pmm"], PEAKS) / 0.5e-3
    kan = harness.load_metric_reader("kan_roofline.kanffn")(v)
    pmm = harness.load_metric_reader("pmm_roofline.kanffn")(v)
    assert kan == pytest.approx(want_kan)
    assert pmm == pytest.approx(want_pmm)
    assert 0 < kan <= 100 and 0 < pmm <= 100


def test_reader_without_kernels_returns_none():
    t = tracing.Trace([(0.0, MS, "%fusion = f32[] fusion()", "jit_f")],
                      [(0.0, 2 * MS, "engine.tick")], (0.0, 2 * MS))
    assert harness.load_metric_reader("kan_roofline.kanffn")(view(t)) is None


def test_breakdown_lists_ops_and_idle_gaps():
    b = tracing.breakdown(synthetic())
    ops = dict(b["device_ops"])
    assert ops["jit_fn/kan_fused_pallas_v2"] == pytest.approx(1e-3)
    assert ops["jit__lambda/kan_fused_pallas_v2"] == pytest.approx(1e-3)
    gaps = dict(b["idle_gaps"])
    # idle 0-1 and 2.5-3 (middles in the prefill), 3.5-6 and 7-10
    # (middles 4.75 and 8.5: the tick alone); each gap goes whole to the
    # annotation over its middle
    assert gaps == pytest.approx({"prefill": 1.5e-3, "engine.tick": 5.5e-3})
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_host_span_readers():
    spans = [harness.Span("prefill", 0.0, 0.010, (None, 128)),
             harness.Span("step", 0.010, 0.012, [(None, 130)] * 8),
             harness.Span("step", 0.012, 0.016, [(None, 131)] * 8)]
    ticks = [harness.Span("tick", 0.0, 0.012), harness.Span("tick", 0.012,
                                                             0.016)]
    v = harness.RunView({}, PEAKS, spans, ticks, None, None)
    for name in ("prefill_ms.kanffn", "prefill_ms.chat"):
        assert harness.load_metric_reader(name)(v) == pytest.approx(10.0)
    assert harness.load_metric_reader("decode_step_ms.kanffn")(v) == \
        pytest.approx(3.0)
    assert harness.load_metric_reader("tick_ms.table2")(v) == \
        pytest.approx(8.0)


def test_reader_found_by_full_name_then_base_name():
    path = harness.metric_reader_path
    assert path("device_idle.table2") == path("device_idle.kanffn") == \
        os.path.join(CHIP, "metrics", "device_idle.py")
    assert path("mfu.kanffn") == path("mfu.chat") == \
        os.path.join(CHIP, "metrics", "mfu.py")
    assert path("mfu.table2") == os.path.join(CHIP, "metrics",
                                              "mfu.table2.py")
    with pytest.raises(harness.SetupError):
        path("no_such_metric.kanffn")


def test_tracer_reads_only_the_steady_part(monkeypatch):
    """The profiler runs from before the window; the probe's annotations,
    and so the part of the trace that is read, run from ``steady_s`` to
    ``steady_s + duration`` into it."""
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))

    class P:
        annotate = False

    probe, tr = P(), tracing.Tracer(5.0, 5.0)
    try:
        tr.start()
        assert calls == ["start"] and not probe.annotate
        tr.poll(4.9, probe)
        assert not probe.annotate
        tr.poll(5.0, probe)
        assert probe.annotate and tr.running
        tr.poll(9.9, probe)
        assert probe.annotate and tr.running
        tr.poll(10.0, probe)
        assert calls == ["start", "stop"] and not probe.annotate
        lo, hi = tr.host_window
        assert lo <= hi
        tr.poll(11.0, probe)
        assert calls == ["start", "stop"]
    finally:
        tr.parse()      # removes its directory; no trace was written
    assert not os.path.exists(tr.dir)
