"""Median of the server's ``serve.update`` spans: optimizer update and
publish of one applied gradient."""

from chipbench.stats import durations_ms, percentile


def read(trace, spans, counters, cell):
    return percentile(durations_ms(spans, "serve.update"), 50)
