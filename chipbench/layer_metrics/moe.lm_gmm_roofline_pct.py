"""The grouped products' share of their roofline in a cell whose rows run
once (``moe.gmm_roofline_pct`` counts ``sdar_moe``'s doubled rows): their
least time (``flops_sdar.grouped_matmul_cost`` over ``flops_xing``'s
expected pairs held, the prediction module's expert layer among the
layers) over the device time under ``moe.experts``."""

from chipbench.flops import roofline_seconds
from chipbench.jobs.common import say
from chipbench.scope_time import EXPERTS, seconds_per_step
from chipbench.xing_trace import per_chip, shape_of


def read(trace, spans, counters, cell):
    shape = shape_of(cell)
    if shape is None or not cell.get("peaks"):
        return None
    per_step = seconds_per_step(trace, counters, EXPERTS)
    if per_step is None:
        return None
    from chipbench.flops_sdar import grouped_matmul_cost
    from chipbench.flops_xing import pairs_held

    s = per_chip(shape, counters)
    cost = grouped_matmul_cost(
        hidden=s["hidden"], expert_width=s["expert_width"],
        experts_held=s["experts_held"], dtype_bytes=s["dtype_bytes"],
        layers=s["expert_layers"] + s["mtp_modules"], pairs=pairs_held(**s))
    least, bound = roofline_seconds(cost, cell["peaks"])
    say(check="moe.lm_gmm_roofline_pct", bound=bound, least_ms=1e3 * least,
        experts_ms=1e3 * per_step,
        pairs_held_per_step=counters.get("moe_pairs_held_per_step"))
    return 100.0 * least / per_step
