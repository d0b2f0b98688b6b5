"""Rows each held expert gets per MoE layer in a prefill: the rows the
traced window's prefills routed to the experts held here over the experts
that got any (both counted by the program on its ``backend.prefill``
spans, summed over the layers).  More rows per expert read each expert's
weights for more work; it should move ``ttft_p95_ms``.  None where the
program counts nothing."""

from chip import program_spans


def read(run):
    recs = program_spans.in_window(run)
    calls = [r.info for r in recs or () if r.name == "backend.prefill"]
    if not calls or any("routed" not in c for c in calls):
        return None
    active = sum(c["active"] for c in calls)
    return sum(c["routed"] for c in calls) / active if active else None
