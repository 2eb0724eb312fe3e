"""Idle ms a step of the first device while the host was inside
``ps.dispatch``: the call of the compiled step until it returns."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "ps.dispatch")
