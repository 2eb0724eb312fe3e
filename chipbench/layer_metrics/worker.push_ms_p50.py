"""Median of the worker's ``worker.push_grad`` spans: encode, frame, and
the wait for the mailbox."""

from chipbench.stats import durations_ms, percentile


def read(trace, spans, counters, cell):
    return percentile(durations_ms(spans, "worker.push_grad"), 50)
