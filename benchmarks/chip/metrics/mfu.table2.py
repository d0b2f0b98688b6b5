"""The stacks' whole backend step as a share of the chip's bf16 peak:
model FLOPs of the requests the traced window's backend steps served,
over those steps' summed host time (each ends in its read-back), in
percent.  The bf16 peak stands for both configurations, whatever they
serve in."""


def read(run):
    steps = [s for s in run.spans if s.name == "step"]
    seconds = sum(s.t1 - s.t0 for s in steps)
    if not steps or seconds <= 0:
        return None
    flops = sum(run.system.step_flops(s.info) for s in steps)
    return 100.0 * flops / seconds / run.peaks["bf16_flops_per_s"]
