"""Idle ms a step of the first device while the host was in ``worker.step``
itself, outside its phases: the cycle's bookkeeping."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "worker.step")
