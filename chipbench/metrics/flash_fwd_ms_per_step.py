"""Device time of the Pallas flash-attention forward per step, per chip:
the trace's ``tpu_custom_call`` ops, the only Pallas kernel in the step."""


def read(m):
    if not m.trace.of_kind("pallas"):
        return None
    return 1e3 * m.trace.kind_seconds("pallas") / m.steps
