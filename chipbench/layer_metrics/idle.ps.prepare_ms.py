"""Idle ms a step of the first device while the host was inside
``ps.prepare``: the metrics schema, the rng split and the lookup of the
compiled step."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "ps.prepare")
