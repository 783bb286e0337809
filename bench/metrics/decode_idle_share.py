"""decode_idle_share (device): share of the traced span of decode steps,
from the first traced step's host span to the last one's end, in which
no operation ran on the device, in percent."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
