"""decode_sample_idle_ms (engine (host)): milliseconds per traced decode
step in which the device ran nothing while the host was inside the
program's ``engine.sample`` span (the argmax and its read to the host)."""

from bench.metrics import _spans as sp


def read(run):
    t = run.trace
    if t is None or not t.n_steps:
        return None
    if not sp.host_intervals(t, "engine.step"):
        return None
    return 1e-6 * sp.idle_ns(t, sp.host_intervals(t, "engine.sample")) \
        / t.n_steps
