"""Mean host milliseconds per ``Engine.tick`` in the traced window.

A tick of the stacks ends in the backend's read-back, so its host time
holds the device's work; it should move ``latency_p95_ms``.
"""


def read(run):
    if not run.ticks:
        return None
    return 1e3 * sum(t.t1 - t.t0 for t in run.ticks) / len(run.ticks)
