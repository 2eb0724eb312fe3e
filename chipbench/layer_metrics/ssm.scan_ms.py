"""Device time a step under the scope of the selective scans
(``ssm.scan``: the chunked recurrence forward, again under ``remat``,
and its backward pass, whatever implements them), on the first device.
The scans are XLA loops: the operations of their bodies are counted,
not the loops' own events, which span them (``sambay_trace.py``)."""

from chipbench.sambay_trace import SCAN, seconds_per_step


def read(trace, spans, counters, cell):
    per_step = seconds_per_step(trace, counters, SCAN)
    return None if per_step is None else 1e3 * per_step
