"""The grouped products' share of their roofline: their least time
(``flops_sdar.grouped_matmul_cost``: the expected pairs held x 3 matrices
x 3 passes, weight and activation bytes; the same work whether Pallas or
XLA computes it) over the device time under ``moe.experts``."""

from chipbench.flops import roofline_seconds
from chipbench.flops_sdar import grouped_matmul_cost
from chipbench.jobs.common import say
from chipbench.scope_time import EXPERTS, seconds_per_step


def read(trace, spans, counters, cell):
    per_step = seconds_per_step(trace, counters, EXPERTS)
    if per_step is None or not cell.get("peaks"):
        return None
    s = cell["shape"]
    cost = grouped_matmul_cost(**dict(s, rows=s["rows"] // counters["chips"]))
    least, bound = roofline_seconds(cost, cell["peaks"])
    say(check="moe.gmm_roofline_pct", bound=bound, least_ms=1e3 * least,
        experts_ms=1e3 * per_step,
        pairs_held_per_step=counters.get("moe_pairs_held_per_step"))
    return 100.0 * least / per_step
