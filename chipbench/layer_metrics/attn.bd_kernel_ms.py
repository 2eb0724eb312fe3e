"""Device time of the three block-diffusion flash kernels (forward, dq,
dk/dv; under ``remat`` the forward runs twice) per step, on the first
device, by the kernels' own names. Absent where they do not run."""

from chipbench.scope_time import bd_kernel_seconds


def read(trace, spans, counters, cell):
    per_step = bd_kernel_seconds(trace)
    return None if per_step is None else 1e3 * per_step
