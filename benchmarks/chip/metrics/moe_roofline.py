"""The grouped KAN kernel's share of its roofline in the traced window:
the least time its calls could take over their device time, in percent.

Each backend call (a prefill or a decode step) runs the kernel once per
MoE layer; the program counts on the call's ``backend.prefill`` /
``backend.step`` span the rows it routed to the held experts and the
active experts, summed over the layers.  A call's least time is
``counts_moe.grouped_kan`` of those sums, compute- or memory-bound at the
chip's peaks (no more than the sum of its layers' least times), summed
over the traced window's calls; the device time is that of the kernel's
operations inside a call's host annotation.  None where the program
counts nothing (a program older than its counts) or the trace holds no
such operation."""

from chip import counts, counts_moe, program_spans

# how the trace names the kernel's device operation
NAMES = ("kan_grouped_pallas",)
CALLS = ("backend.prefill", "backend.step")


def read(run):
    recs = program_spans.in_window(run)
    calls = [r.info for r in recs or () if r.name in CALLS]
    if not calls or any("routed" not in c for c in calls):
        return None
    least = sum(counts.least_time_s(*counts_moe.grouped_kan(
        run.config, c["routed"], c["active"]), run.peaks) for c in calls)
    device = sum((e - s) * 1e-9 for s, e, _, _ in run.trace.ops_named(NAMES)
                 if run.trace.enclosing((s + e) / 2, "prefill:")
                 or run.trace.enclosing((s + e) / 2, "step:"))
    if device <= 0:
        return None
    return 100.0 * least / device
