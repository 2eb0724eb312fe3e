"""Device time of the attention kernels (the Pallas flash forward and its
two backward kernels) per step, on the first device. Absent where the
einsum path runs: no such event is in the trace."""

from chipbench.trace_reduce import ATTENTION_KERNEL, seconds_per_step


def read(trace, spans, counters, cell):
    per_step = seconds_per_step(trace, ATTENTION_KERNEL)
    return None if per_step is None else 1e3 * per_step
