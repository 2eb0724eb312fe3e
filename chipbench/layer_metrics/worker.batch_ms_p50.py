"""Median of the worker's ``worker.batch`` spans: ``batch_fn`` makes the
next batch."""

from chipbench.stats import durations_ms, percentile


def read(trace, spans, counters, cell):
    return percentile(durations_ms(spans, "worker.batch"), 50)
