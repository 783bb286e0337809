"""paged_decode_roofline (kernels, ``kernels/decode_attention.py``): the
least time the chip needs for the paged decode attention calls of the
traced steps, over their device time, in percent.

The least time is the larger of the calls' operations over the peak
FLOP/s and their bytes over the peak HBM bandwidth, with operations and
bytes from each request's actual context length (``bench.lib.flops``),
not from the block table's width. The kernel is found in the trace by
its function name, ``KERNEL``. The program gives its ``pallas_call``s no
name yet, and then its trace holds none: the reader then takes the
decode step's Pallas kernels (``PALLAS``), of which the paged decode
attention is the only one. Either way it reads only where it finds one
call per attention layer per traced step, and leaves the metric out
otherwise.
"""

from bench.lib import trace as tr
from bench.lib.flops import paged_decode_call, roofline_seconds

KERNEL = "paged_decode_attention"
PALLAS = 'custom_call_target="tpu_custom_call"'


def read(run):
    t = run.trace
    if t is None or not run.traced_steps:
        return None
    ns, n_calls = tr.kernel_ns(t.events, t.lo, t.hi, KERNEL)
    if not n_calls:
        ns, n_calls = tr.kernel_ns(t.events, t.lo, t.hi, PALLAS)
    layers = run.cfg["num_hidden_layers"]
    if ns <= 0 or n_calls != layers * len(run.traced_steps):
        return None
    flops = nbytes = 0
    for lens in run.traced_steps:
        f, b = paged_decode_call(run.cfg, lens)
        flops += layers * f
        nbytes += layers * b
    least, _ = roofline_seconds(flops, nbytes, run.peak)
    return 100.0 * least / (ns * 1e-9)
