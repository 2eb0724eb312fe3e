"""The causal grouped-query flash kernels' share of their roofline: the
least time the chip could take for one step's attention (7 products over
the ALLOWED causal pairs, q/o/do/dq over the query heads and k/v/dk/dv
over the key-value heads, ``flops_lfm2``; the larger of the two bounds)
over the kernels' measured time, recomputation under ``remat`` included in
the measured time and not in the least."""

from chipbench.flops import roofline_seconds
from chipbench.jobs.common import say
from chipbench.lfm2_trace import kernel_seconds, per_chip, shape_of


def read(trace, spans, counters, cell):
    per_step = kernel_seconds(trace, counters, cell)
    if per_step is None or not cell.get("peaks"):
        return None
    from chipbench.flops_lfm2 import gqa_attention_kernel_cost

    cost = gqa_attention_kernel_cost(**per_chip(shape_of(cell), counters))
    least, bound = roofline_seconds(cost, cell["peaks"])
    say(check="attn.gqa_roofline_pct", bound=bound, least_ms=1e3 * least,
        kernel_ms=1e3 * per_step)
    return 100.0 * least / per_step
