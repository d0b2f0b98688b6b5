"""Mean host milliseconds per ``TransformerBackend.prefill`` call in the
traced window (the call ends in its ``device_get``); it should move
``ttft_p95_ms``."""


from chip.metrics_common import mean_span_ms


def read(run):
    return mean_span_ms(run, "prefill")
