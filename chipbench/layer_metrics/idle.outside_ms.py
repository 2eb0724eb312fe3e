"""Idle ms a step of the first device, between programs, while the host was
in no span of the program at all."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "outside")
