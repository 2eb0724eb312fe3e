"""Seconds inside the first worker's ``setup.worker.attach``: the
runtime's attach to the chip, which ``setup_s`` cannot leave out in the
asynchronous cell (it happens in another process)."""

from chipbench.setup_phases import WORKER, phase_s


def read(trace, spans, counters, cell):
    return phase_s(spans, cell, "setup.worker.attach", WORKER)
