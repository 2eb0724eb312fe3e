"""Idle ms a step of the first device while the host was in ``ps.step``
itself, outside its phases: unpacking the step's outputs, its
bookkeeping."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "ps.step")
