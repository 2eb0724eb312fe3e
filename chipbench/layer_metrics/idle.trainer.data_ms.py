"""Idle ms a step of the first device while the host was inside
``trainer.data``: the loop waits for the input pipeline's next batch."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "trainer.data")
