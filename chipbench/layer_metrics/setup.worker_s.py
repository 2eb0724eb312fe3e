"""Seconds inside the first worker's ``setup.worker``: entry of
``worker_main`` to its first acknowledged push."""

from chipbench.setup_phases import WORKER, phase_s


def read(trace, spans, counters, cell):
    return phase_s(spans, cell, "setup.worker", WORKER)
