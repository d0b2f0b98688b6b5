"""The pattern-matmul kernel's share of its roofline in the traced window
(the KAN-FFN's down projection): least time (counts.pattern_matmul) over
device time of its calls, in percent, rows as for kan_roofline."""

from chip.metrics_common import roofline_share

NAMES = ("matmul_compact_pallas",)


def read(run):
    return roofline_share(run, NAMES, "pmm")
