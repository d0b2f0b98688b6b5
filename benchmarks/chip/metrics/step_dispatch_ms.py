"""Mean host milliseconds of the program's ``backend.step.dispatch`` span:
the jitted step's call up to its return (flattening its arguments and
enqueuing the program; the transformer's greedy pick is a span of its
own).  It should move the step-bound tail: ``latency_p95_ms`` for the
stacks, ``itl_p95_ms`` for the transformer."""

from chip import program_spans


def read(run):
    return program_spans.mean_ms(run, "backend.step.dispatch")
