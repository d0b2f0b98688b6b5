"""The transformer's whole step as a share of the chip's bf16 peak: model
FLOPs of the prefill and decode calls in the traced window (counts.py:
2 per weight per token, causal attention, the LM head where logits are
used) over their summed host time, in percent.  The stack cells have a
reader of their own, ``mfu.table2.py``."""


def read(run):
    spans = [s for s in run.spans if s.name in ("prefill", "step")]
    seconds = sum(s.t1 - s.t0 for s in spans)
    if not spans or seconds <= 0:
        return None
    flops = sum(run.system.prefill_flops(s.info[1]) if s.name == "prefill"
                else run.system.step_flops(s.info) for s in spans)
    return 100.0 * flops / seconds / run.peaks["bf16_flops_per_s"]
