"""Median of the worker's ``wire.encode`` spans: the jitted encode on its
device, the device-to-host copies, the pack."""

from chipbench.stats import durations_ms, percentile


def read(trace, spans, counters, cell):
    return percentile(durations_ms(spans, "wire.encode"), 50)
