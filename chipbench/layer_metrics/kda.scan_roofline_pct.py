"""The gated delta rule's chunked scan's share of its roofline: the least
time the chip could take for what one step's scans must do and move at
the cell's chunk (``flops_kda.kda_scan_cost``: 3x the chunked form's
operations; q, k, v, log-decays and betas read and the output and the
chunks' states written forward, and back; the larger of the two bounds)
over the device time under ``kda.scan``, recomputation under ``remat``
included in the measured time and not in the least."""

from chipbench.flops import roofline_seconds
from chipbench.jobs.common import say
from chipbench.sambay_trace import seconds_per_step

SCAN = r"^kda\.scan$"


def read(trace, spans, counters, cell):
    shape = cell.get("shape") or {}
    if "kda_layers" not in shape or not cell.get("peaks"):
        return None
    per_step = seconds_per_step(trace, counters, SCAN)
    if per_step is None:
        return None
    from chipbench.flops_kda import kda_scan_cost

    cost = kda_scan_cost(**dict(shape, rows=shape["rows"] // counters["chips"]))
    least, bound = roofline_seconds(cost, cell["peaks"])
    say(check="kda.scan_roofline_pct", bound=bound, least_ms=1e3 * least,
        scan_ms=1e3 * per_step)
    return 100.0 * least / per_step
