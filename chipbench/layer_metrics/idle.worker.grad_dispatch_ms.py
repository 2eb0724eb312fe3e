"""Idle ms a step of the first device while the host was inside
``worker.grad_dispatch``: the call of the gradient program until it
returns (parameters and batch go to the chip here)."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "worker.grad_dispatch")
