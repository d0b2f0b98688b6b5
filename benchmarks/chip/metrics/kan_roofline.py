"""The fused KAN kernel's share of its roofline in the traced window:
the least time its calls could take (counts.kan_layer for each call's
rows, compute- or memory-bound at the chip's peaks) over their device
time, in percent.  A call's rows come from the host annotation around it:
the prompt length of a ``prefill:<n>`` call, the slot count of a
``step:<n>`` call."""

from chip.metrics_common import roofline_share

# how the trace names the kernel's device operation
NAMES = ("kan_fused_pallas_v2",)


def read(run):
    return roofline_share(run, NAMES, "kan")
