"""Mean host milliseconds per ``TransformerBackend.step`` (one token for
every slot, read back to the host) in the traced window; it should move
``itl_p95_ms``."""


from chip.metrics_common import mean_span_ms


def read(run):
    return mean_span_ms(run, "step")
