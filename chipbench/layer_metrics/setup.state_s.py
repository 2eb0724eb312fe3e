"""Seconds inside ``setup.state``: ``MPI_PS.__init__`` through
``_place_state``, the optimizer's state built and put on the mesh."""

from chipbench.setup_phases import phase_s


def read(trace, spans, counters, cell):
    return phase_s(spans, cell, "setup.state")
