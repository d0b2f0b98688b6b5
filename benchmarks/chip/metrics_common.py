"""Arithmetic shared by the per-layer metric readers in ``metrics/``."""
from __future__ import annotations

from typing import Optional, Sequence

from chip import counts


def mean_span_ms(run, name: str) -> Optional[float]:
    """Mean host milliseconds of the traced window's ``name`` spans."""
    spans = [s for s in run.spans if s.name == name]
    if not spans:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / len(spans)


def call_rows(run, t: float) -> Optional[int]:
    """Rows of the kernel call running at trace time ``t``: the prompt
    length of the prefill, or the slot count of the decode step, that
    the host was in."""
    label = run.trace.enclosing(t, "prefill:") or run.trace.enclosing(
        t, "step:")
    return int(label.split(":")[1]) if label else None


def roofline_share(run, names: Sequence[str], kernel: str
                   ) -> Optional[float]:
    """Sum of least times over sum of device times of the KAN-FFN
    ``kernel``'s calls ("kan" or "pmm") in the traced window, in percent;
    None when the trace holds none of them."""
    least = device = 0.0
    for s, e, _, _ in run.trace.ops_named(names):
        rows = call_rows(run, (s + e) / 2)
        if rows is None:
            continue
        flops, nbytes = counts.kanffn_kernel_calls(run.config, rows)[kernel]
        least += counts.least_time_s(flops, nbytes, run.peaks)
        device += (e - s) * 1e-9
    if device <= 0:
        return None
    return 100.0 * least / device
