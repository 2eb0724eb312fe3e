"""The gated short convolution's share of its roofline: the least time the
chip could take to move what one step's convolutions must read and write
(``flops_lfm2.short_conv_cost``: 4 values of hidden a position forward, 7
backward, a ``conv`` layer; the memory's bound) over the device time under
``conv.mix``, recomputation under ``remat`` included in the measured time
and not in the least."""

from chipbench.flops import roofline_seconds
from chipbench.jobs.common import say
from chipbench.lfm2_trace import CONV_MIX, per_chip, scope_seconds, shape_of


def read(trace, spans, counters, cell):
    per_step = scope_seconds(trace, counters, cell, CONV_MIX)
    if per_step is None or not cell.get("peaks"):
        return None
    from chipbench.flops_lfm2 import short_conv_cost

    least, bound = roofline_seconds(
        short_conv_cost(**per_chip(shape_of(cell), counters)), cell["peaks"])
    say(check="conv.mix_roofline_pct", bound=bound, least_ms=1e3 * least,
        mix_ms=1e3 * per_step)
    return 100.0 * least / per_step
