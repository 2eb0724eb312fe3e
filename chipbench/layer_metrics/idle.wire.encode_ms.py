"""Idle ms a step of the first device while the host was inside
``wire.encode``: the jitted encode, the device-to-host copies, the pack."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "wire.encode")
