"""Median of the worker's ``wire.send`` spans: seal + CRC, the push, the
wait for a free mailbox."""

from chipbench.stats import durations_ms, percentile


def read(trace, spans, counters, cell):
    return percentile(durations_ms(spans, "wire.send"), 50)
