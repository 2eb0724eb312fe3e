"""The grouped products' share of their roofline, written THROUGH THE
SHAPE: their least time (``flops_lfm2.rows_grouped_matmul_cost`` from
``hidden``, ``expert_width``, ``experts``, ``experts_held``, ``top_k``,
``expert_layers`` and the positions a row runs as: the expected pairs
held x 3 matrices x 3 passes, weight and activation bytes) over the device
time under ``moe.experts``. Any family whose shape carries those keys
reads the same way (``moe.gmm_roofline_pct`` and
``moe.lm_gmm_roofline_pct`` each know one family: ROADMAP D17); absent
where a key is missing."""

from chipbench.flops import roofline_seconds
from chipbench.jobs.common import say
from chipbench.scope_time import EXPERTS, seconds_per_step

KEYS = ("rows", "seq", "hidden", "expert_width", "experts", "experts_held",
        "top_k", "expert_layers", "dtype_bytes")


def read(trace, spans, counters, cell):
    shape = cell.get("shape") or {}
    if any(k not in shape for k in KEYS) or not cell.get("peaks"):
        return None
    per_step = seconds_per_step(trace, counters, EXPERTS)
    if per_step is None:
        return None
    from chipbench.flops_lfm2 import rows_grouped_matmul_cost

    cost = rows_grouped_matmul_cost(
        **dict(shape, rows=shape["rows"] // counters["chips"]))
    least, bound = roofline_seconds(cost, cell["peaks"])
    say(check="moe.rows_gmm_roofline_pct", bound=bound, least_ms=1e3 * least,
        experts_ms=1e3 * per_step,
        pairs_held_per_step=counters.get("moe_pairs_held_per_step"))
    return 100.0 * least / per_step
