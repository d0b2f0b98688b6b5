"""The readers of the program's own spans (program_spans.py) on span
records built by hand, whose answers are worked out below."""
import glob
import os
import re

import pytest

from chip import harness, program_spans, tracing
from repro.runtime import spans

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
MS = 1e-3
T = 100.0               # the window's start, perf_counter seconds


def rec(index, name, t0_ms, t1_ms, parent=None, **info):
    return spans.Record(index, name, T + t0_ms * MS, T + t1_ms * MS,
                        parent, info)


# Two ticks, 0-10 and 20-30 ms.  The first iteration (1-9 ms) prefills one
# request (2 ms, 1 of it the splice) and steps 3 rows in a bucket of 4
# (3 ms); the second (21-29 ms) steps 2 rows in a bucket of 2 (4 ms).  The
# harness's probe times each backend call from 0.1 ms outside the
# backend's own span (PROBE).
RECORDS = [
    rec(0, "engine.iter", 1, 9),
    rec(1, "engine.admit", 1, 4, 0, queued=1, free=1),
    rec(2, "backend.prefill", 2, 4, 1, rid=1, tokens=16),
    rec(3, "backend.prefill.splice", 3, 4, 2),
    rec(4, "backend.step", 5, 8, 0, active=3, computed=4),
    rec(5, "backend.step.dispatch", 5, 6, 4),
    rec(6, "backend.step.readback", 6, 7.5, 4),
    rec(7, "engine.iter", 21, 29),
    rec(8, "backend.step", 22, 26, 7, active=2, computed=2),
    rec(9, "backend.step.dispatch", 22, 24, 8),
    rec(10, "backend.step.readback", 24, 25, 8),
    rec(11, "engine.queue", 0, 2),
    rec(12, "engine.queue", -10, 21),   # began before the window: kept
    rec(13, "engine.queue", 20, 21.5),
    rec(14, "engine.iter", 31, 35),     # after the last tick
    rec(15, "engine.queue", -10, -5),   # ended before the window
]
TICKS = [harness.Span("tick", T, T + 10 * MS),
         harness.Span("tick", T + 20 * MS, T + 30 * MS)]
PROBE = [harness.Span(kind, T + t0 * MS, T + t1 * MS, None)
         for kind, t0, t1 in (("prefill", 1.9, 4.1), ("step", 4.9, 8.1),
                              ("step", 21.9, 26.1), ("step", 31.5, 34))]

WANT = {
    # each iteration less the probe's calls inside it
    "engine_self_ms.table2": (8 - 2.2 - 3.2 + 8 - 4.2) / 2,
    # nearest-rank p95 of 2, 31 and 1.5
    "queue_wait_ms.table2": 31.0,
    "queue_wait_ms.kanffn": 31.0,
    "bucket_fill.table2": 100 * 5 / 6,
    "step_dispatch_ms.table2": 1.5,
    "step_dispatch_ms.kanffn": 1.5,
    "step_readback_ms.table2": 1.25,
    "step_readback_ms.kanffn": 1.25,
    "prefill_splice_ms.kanffn": 1.0,
}


def view(ticks=TICKS, probe=PROBE):
    return harness.RunView({}, {}, probe, ticks, None, None)


def use(monkeypatch, records, dropped=0, lost_until=float("-inf")):
    snap = spans.Snapshot(list(records), dropped, lost_until)
    monkeypatch.setattr(program_spans, "snapshot", lambda: snap)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_by_hand(monkeypatch, name):
    use(monkeypatch, RECORDS)
    got = harness.load_metric_reader(name)(view())
    assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_none_where_nothing_is_whole(monkeypatch, name):
    read = harness.load_metric_reader(name)
    use(monkeypatch, [])                        # no spans recorded
    assert read(view()) is None
    use(monkeypatch, RECORDS)                   # no tick in the window
    assert read(view([])) is None
    # the ring dropped a record that ended inside the window
    use(monkeypatch, RECORDS[5:], dropped=5, lost_until=T + 4 * MS)
    assert read(view()) is None
    # a program older than its spans: the real snapshot has none to read
    monkeypatch.undo()
    monkeypatch.setattr(program_spans, "_spans", None)
    assert read(view()) is None


def test_drops_before_the_window_leave_it_whole(monkeypatch):
    use(monkeypatch, RECORDS, dropped=3, lost_until=T - 1 * MS)
    read = harness.load_metric_reader("engine_self_ms.table2")
    assert read(view()) == pytest.approx(WANT["engine_self_ms.table2"])


def test_a_name_absent_from_the_window_reads_none(monkeypatch):
    use(monkeypatch,
        [r for r in RECORDS if r.name != "backend.prefill.splice"])
    assert harness.load_metric_reader("prefill_splice_ms.kanffn")(
        view()) is None
    use(monkeypatch, [r for r in RECORDS if r.name != "backend.step"])
    assert harness.load_metric_reader("bucket_fill.table2")(view()) is None


def program_span_names():
    """Every span name the serving path passes to ``span`` or ``record``."""
    names = set()
    for path in glob.glob(os.path.join(ROOT, "src", "repro", "runtime",
                                       "*.py")):
        with open(path) as f:
            names |= set(re.findall(r'\b(?:span|record)\(\s*"([^"]+)"',
                                    f.read()))
    return names


def test_no_program_span_moves_the_traced_window():
    """The trace's window runs from the first to the last host event named
    with a harness prefix (tracing.ANNOTATIONS); the program's spans run
    from the profiler's start, so one so named would stretch the window
    back over the ramp."""
    names = program_span_names()
    assert {"engine.iter", "engine.queue", "backend.step",
            "backend.step.dispatch", "backend.prefill.splice"} <= names
    assert len(names) == 18
    for n in names:
        assert not n.startswith(tracing.ANNOTATIONS), n
