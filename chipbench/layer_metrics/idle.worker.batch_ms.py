"""Idle ms a step of the first device while the host was inside
``worker.batch``: ``batch_fn`` makes the next batch."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "worker.batch")
