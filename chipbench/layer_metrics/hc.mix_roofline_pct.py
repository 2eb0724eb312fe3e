"""The hyper-connections' share of their roofline: the least time the chip
could take to move what one step's mixes must read and write
(``flops_xing.hc_mix_cost``: the streams three times and once forward,
five times backward; the memory's bound) over the device time under
``hc.mix`` and ``hc.sinkhorn``, recomputation under ``remat`` included in
the measured time and not in the least."""

from chipbench.flops import roofline_seconds
from chipbench.jobs.common import say
from chipbench.sambay_trace import seconds_per_step
from chipbench.xing_trace import HC, per_chip, shape_of


def read(trace, spans, counters, cell):
    shape = shape_of(cell)
    if shape is None or not cell.get("peaks"):
        return None
    per_step = seconds_per_step(trace, counters, HC)
    if per_step is None:
        return None
    from chipbench.flops_xing import hc_mix_cost

    least, bound = roofline_seconds(hc_mix_cost(**per_chip(shape, counters)),
                                    cell["peaks"])
    say(check="hc.mix_roofline_pct", bound=bound, least_ms=1e3 * least,
        mix_ms=1e3 * per_step)
    return 100.0 * least / per_step
