"""decode_host_idle_ms (engine (host)): milliseconds per traced decode
step in which the device ran nothing while the host was inside the
program's ``engine.step`` span but outside its ``engine.sample`` span:
scheduling, building and dispatching the inputs (``engine.schedule``,
``runner.decode``) and the per-request bookkeeping (``engine.emit``)."""

from bench.metrics import _spans as sp


def read(run):
    t = run.trace
    if t is None or not t.n_steps:
        return None
    step = sp.host_intervals(t, "engine.step")
    if not step:
        return None
    sample = sp.host_intervals(t, "engine.sample")
    return 1e-6 * (sp.idle_ns(t, step) - sp.idle_ns(t, sample)) / t.n_steps
