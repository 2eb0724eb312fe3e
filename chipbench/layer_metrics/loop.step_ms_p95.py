"""95th percentile of the ``trainer.step`` spans of the window."""

from chipbench.stats import durations_ms, percentile


def read(trace, spans, counters, cell):
    return percentile(durations_ms(spans, "trainer.step"), 95)
