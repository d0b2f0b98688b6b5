"""p95 (nearest rank) of the program's ``engine.queue`` spans that end in
the traced window, in ms: from a request's submit stamp to its selection
by the batch policy, its own prefill left out.  It should move the cell's
latency tail: ``latency_p95_ms`` for the stacks, ``ttft_p95_ms`` for the
transformer."""

from chip import program_spans


def read(run):
    return program_spans.p95_ms(run, "engine.queue")
