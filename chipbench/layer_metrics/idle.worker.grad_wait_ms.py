"""Idle ms a step of the first device while the host was inside
``worker.grad_wait`` (``block_until_ready``): the device is done and the
host has not woken."""

from chipbench.host_phases import idle_ms


def read(trace, spans, counters, cell):
    return idle_ms(trace, cell, "worker.grad_wait")
