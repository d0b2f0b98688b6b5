"""Mean host milliseconds of the program's ``backend.prefill.splice``
span: the prefilled request's cache written into its slot of the batch's
caches, one eager update per cache leaf.  It should move
``ttft_p95_ms``."""

from chip import program_spans


def read(run):
    return program_spans.mean_ms(run, "backend.prefill.splice")
