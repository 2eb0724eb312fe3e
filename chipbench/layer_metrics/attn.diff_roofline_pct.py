"""The differential-attention kernels' share of their roofline: the
least time the chip could take for one step's attention (10 x 2 x
head_dim operations over the ALLOWED pairs of every softmax map and the
q/k/v/o bytes by pairs, ``flops_sambay.diff_attention_kernel_cost``; the
larger of the two bounds) over the kernels' measured time, recomputation
under ``remat`` included in the measured time and not in the least."""

from chipbench.flops import roofline_seconds
from chipbench.jobs.common import say
from chipbench.sambay_trace import DIFF_KERNELS
from chipbench.trace_reduce import seconds_per_step


def read(trace, spans, counters, cell):
    shape = cell.get("shape") or {}
    if (not trace or "by_name" not in trace or not cell.get("peaks")
            or "window_layers" not in shape):
        return None
    per_step = seconds_per_step(trace, DIFF_KERNELS)
    if per_step is None:
        return None
    from chipbench.flops_sambay import diff_attention_kernel_cost

    cost = diff_attention_kernel_cost(**dict(
        shape, rows=shape["rows"] // counters["chips"]))
    least, bound = roofline_seconds(cost, cell["peaks"])
    say(check="attn.diff_roofline_pct", bound=bound, least_ms=1e3 * least,
        kernel_ms=1e3 * per_step)
    return 100.0 * least / per_step
