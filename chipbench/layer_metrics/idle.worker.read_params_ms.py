"""Idle ms a step of the first device while the host was inside
``worker.read_params``: the copy of the published parameters out of the
mailbox."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "worker.read_params")
