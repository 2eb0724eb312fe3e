"""Median host time of one ``Trainer.fit`` step (``trainer.step`` span:
dispatch, the device's work and the loss fetch)."""

from chipbench.stats import durations_ms, percentile


def read(trace, spans, counters, cell):
    return percentile(durations_ms(spans, "trainer.step"), 50)
