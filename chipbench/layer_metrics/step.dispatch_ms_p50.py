"""Median host time of ``ps.dispatch``, the call of the compiled step until
it returns — read from the trace's host plane, since the sync job hands
the readers no recorder span but ``trainer.step``."""

from chipbench.host_phases import host_ms
from chipbench.stats import percentile


def read(trace, spans, counters, cell):
    return percentile(host_ms(trace, cell, "ps.dispatch"), 50)
