"""The flash attention kernels' share of their roofline: the least time
the chip could take for one step's attention (operations and bytes from
``flops.attention_kernel_cost``, the larger of the two bounds) over the
kernels' measured time. Which bound it is goes on an earlier line."""

from chipbench.flops import attention_kernel_cost, roofline_seconds
from chipbench.jobs.common import say
from chipbench.trace_reduce import ATTENTION_KERNEL, seconds_per_step


def read(trace, spans, counters, cell):
    per_step = seconds_per_step(trace, ATTENTION_KERNEL)
    if per_step is None:
        return None
    s = cell["shape"]
    cost = attention_kernel_cost(
        rows=s["rows"] // counters["chips"], seq=s["seq"], heads=s["heads"],
        head_dim=s["head_dim"], layers=s["layers"], causal=s["causal"],
        dtype_bytes=s["dtype_bytes"])
    least, bound = roofline_seconds(cost, cell["peaks"])
    say(check="flash_attention_roofline", bound=bound, least_ms=1e3 * least,
        kernel_ms=1e3 * per_step)
    return 100.0 * least / per_step
