"""Mean host milliseconds of the program's ``backend.step.readback`` span:
the step's result brought to the host (``np.asarray`` / ``device_get``),
which waits for the device to finish the step.  It should move
``latency_p95_ms`` for the stacks, ``itl_p95_ms`` for the transformer."""

from chip import program_spans


def read(run):
    return program_spans.mean_ms(run, "backend.step.readback")
