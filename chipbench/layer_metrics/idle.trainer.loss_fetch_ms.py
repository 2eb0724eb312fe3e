"""Idle ms a step of the first device while the host was inside
``trainer.loss_fetch``: ``float(loss)``."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "trainer.loss_fetch")
