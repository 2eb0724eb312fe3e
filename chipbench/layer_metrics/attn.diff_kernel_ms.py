"""Device time of the differential-attention flash kernels (the window
mask's ``flash_win_*`` and the causal ones with a value twice as wide as
the keys, ``flash_wide_*``: forward, dq, dk/dv; under ``remat`` the
forward runs twice) per step, on the first device, by the kernels' own
names. Absent where they do not run."""

from chipbench.sambay_trace import DIFF_KERNELS
from chipbench.trace_reduce import seconds_per_step


def read(trace, spans, counters, cell):
    if not trace or "by_name" not in trace:
        return None
    per_step = seconds_per_step(trace, DIFF_KERNELS)
    return None if per_step is None else 1e3 * per_step
