"""Median of the worker's ``worker.read_params`` spans: the copy of the
published parameters out of the mailbox."""

from chipbench.stats import durations_ms, percentile


def read(trace, spans, counters, cell):
    return percentile(durations_ms(spans, "worker.read_params"), 50)
