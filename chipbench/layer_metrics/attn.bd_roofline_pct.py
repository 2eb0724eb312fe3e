"""The block-diffusion flash kernels' share of their roofline: the least
time the chip could take for one step's masked attention (7 products over
the ALLOWED pairs and the q/k/v/o bytes, ``flops_sdar``; the larger of
the two bounds) over the kernels' measured time, recomputation under
``remat`` included in the measured time and not in the least."""

from chipbench.flops import roofline_seconds
from chipbench.flops_sdar import bd_attention_kernel_cost
from chipbench.jobs.common import say
from chipbench.scope_time import bd_kernel_seconds


def read(trace, spans, counters, cell):
    per_step = bd_kernel_seconds(trace)
    if per_step is None or not cell.get("peaks"):
        return None
    s = cell["shape"]
    cost = bd_attention_kernel_cost(**dict(
        s, rows=s["rows"] // counters["chips"]))
    least, bound = roofline_seconds(cost, cell["peaks"])
    say(check="attn.bd_roofline_pct", bound=bound, least_ms=1e3 * least,
        kernel_ms=1e3 * per_step)
    return 100.0 * least / per_step
