"""Device time of the latent-attention flash kernels (causal, 192-wide
q and k over a 128-wide value: forward, dq, dk/dv; under ``remat`` the
forward runs twice) per step, on the first device, by the kernels' own
names. Absent where they do not run or the cell is another family's."""

from chipbench.xing_trace import kernel_seconds


def read(trace, spans, counters, cell):
    per_step = kernel_seconds(trace, cell)
    return None if per_step is None else 1e3 * per_step
