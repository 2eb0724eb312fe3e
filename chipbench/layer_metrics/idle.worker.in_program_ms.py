"""``idle.in_program_ms`` in the async cell (an entry names ONE metric it
moves): idle ms a cycle of the worker's chip inside a running program."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "in_program")
