"""decode_mfu (whole step): model FLOPs of the traced decode steps, from
each step's requests and context lengths (counted by the configuration's
architecture module, ``decode_step_flops``), over the traced span times
the chip's peak bf16 FLOP/s, in percent."""

from bench.lib.check import reference_module


def read(run):
    t = run.trace
    if t is None or not run.traced_steps or t.window_s <= 0:
        return None
    arch = reference_module(run.cfg)
    flops = sum(arch.decode_step_flops(run.cfg, lens)
                for lens in run.traced_steps)
    return 100.0 * flops / (t.window_s * run.peak["bf16_flops_per_s"])
