"""``idle.outside_ms`` in the async cell: idle ms a cycle of the worker's
chip, between programs, under no span of the program."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "outside")
