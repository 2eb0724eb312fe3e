"""Idle ms a step of the first device while the host was inside
``wire.send``: seal + CRC, the push, the wait for a free mailbox."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "wire.send")
