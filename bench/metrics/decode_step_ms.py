"""decode_step_ms (runner/workers): device-busy milliseconds per traced
decode step: the union of the device's operation intervals inside the
traced span, over the number of steps in it."""


def read(run):
    t = run.trace
    if t is None or not t.n_steps:
        return None
    return 1e3 * t.busy_s / t.n_steps
