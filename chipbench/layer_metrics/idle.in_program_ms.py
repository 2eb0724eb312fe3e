"""Idle ms a step of the first device INSIDE a running program (the pauses
between its operations): not the host's doing, whatever span it was in."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "in_program")
