"""paged_decode_roofline (kernels, ``kernels/decode_attention.py``): the
least time the chip needs for the paged decode attention calls of the
traced steps, over their device time, in percent.

The least time is the larger of the calls' operations over the peak
FLOP/s and their bytes over the peak HBM bandwidth, with the calls,
operations and bytes of each step counted by the configuration's
architecture module (``paged_decode_work``) from each request's actual
context length, not from the block table's width. The kernel is found in
the trace as the operation named ``KERNEL``, or the one whose
``kernel_metadata`` names it. A program that names neither (before it
gave its kernels metadata) is read by the decode step's Pallas kernels,
the custom calls to ``PALLAS``, of which the paged decode attention was
the only one. Either way it reads only where it finds as many calls as
the module counts for the traced steps, and leaves the metric out
otherwise.
"""

from bench.lib import trace as tr
from bench.lib.check import reference_module
from bench.lib.flops import roofline_seconds

KERNEL = "paged_decode_attention"
PALLAS = "tpu_custom_call"


def read(run):
    t = run.trace
    if t is None or not run.traced_steps:
        return None
    ns, n_calls = tr.kernel_ns(t.events, t.lo, t.hi, KERNEL)
    if not n_calls:
        ns, n_calls = tr.custom_call_ns(t.events, t.lo, t.hi, PALLAS)
    arch = reference_module(run.cfg)
    calls = flops = nbytes = 0
    for lens in run.traced_steps:
        c, f, b = arch.paged_decode_work(run.cfg, lens)
        calls += c
        flops += f
        nbytes += b
    if ns <= 0 or n_calls != calls:
        return None
    least, _ = roofline_seconds(flops, nbytes, run.peak)
    return 100.0 * least / (ns * 1e-9)
