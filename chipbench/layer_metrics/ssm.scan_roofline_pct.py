"""The selective scans' share of their roofline: the least time the chip
could take to move what one step's scans must read and write
(``flops_sambay.scan_cost``; the memory's bound) over the device time
under ``ssm.scan``, recomputation under ``remat`` included in the
measured time and not in the least."""

from chipbench.flops import roofline_seconds
from chipbench.jobs.common import say
from chipbench.sambay_trace import SCAN, seconds_per_step


def read(trace, spans, counters, cell):
    per_step = seconds_per_step(trace, counters, SCAN)
    shape = cell.get("shape") or {}
    if per_step is None or not cell.get("peaks") or "d_state" not in shape:
        return None
    from chipbench.flops_sambay import scan_cost

    cost = scan_cost(**dict(shape, rows=shape["rows"] // counters["chips"]))
    least, bound = roofline_seconds(cost, cell["peaks"])
    say(check="ssm.scan_roofline_pct", bound=bound, least_ms=1e3 * least,
        scan_ms=1e3 * per_step)
    return 100.0 * least / per_step
