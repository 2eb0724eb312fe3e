"""The latent-attention flash kernels' share of their roofline: the least
time the chip could take for one step's attention (7 products over the
ALLOWED causal pairs, four at the keys' width and three at the value's,
and the q/k/v/o bytes, ``flops_xing``; the larger of the two bounds) over
the kernels' measured time, recomputation under ``remat`` included in the
measured time and not in the least."""

from chipbench.flops import roofline_seconds
from chipbench.jobs.common import say
from chipbench.xing_trace import kernel_seconds, per_chip, shape_of


def read(trace, spans, counters, cell):
    per_step = kernel_seconds(trace, cell)
    if per_step is None or not cell.get("peaks"):
        return None
    from chipbench.flops_xing import mla_attention_kernel_cost

    cost = mla_attention_kernel_cost(**per_chip(shape_of(cell), counters))
    least, bound = roofline_seconds(cost, cell["peaks"])
    say(check="attn.mla_roofline_pct", bound=bound, least_ms=1e3 * least,
        kernel_ms=1e3 * per_step)
    return 100.0 * least / per_step
