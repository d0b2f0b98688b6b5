"""Mean host milliseconds per ``Engine.tick`` (the program's
``engine.iter`` span) less the harness probe's spans of the backend calls
inside it (``prefill`` and ``step``): the Engine's self time, in queue
expiry, admission, the batch policy, the cycle report and retirement,
with the probe's own bookkeeping around each call and, in a traced run,
the cost of the spans themselves. It should move ``latency_p95_ms``."""

from chip import program_spans


def read(run):
    return program_spans.engine_self_ms(run)
