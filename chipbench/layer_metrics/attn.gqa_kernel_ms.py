"""Device time of the causal grouped-query flash kernels (32 query over 8
key-value heads of 64: forward, dq, dk/dv; under ``remat`` the forward
runs twice) per step, on the first device: the Pallas custom calls under
the scope ``attn.gqa`` (``lfm2_trace.py``). Absent where they do not run
or the cell is another family's."""

from chipbench.lfm2_trace import kernel_seconds


def read(trace, spans, counters, cell):
    per_step = kernel_seconds(trace, counters, cell)
    return None if per_step is None else 1e3 * per_step
