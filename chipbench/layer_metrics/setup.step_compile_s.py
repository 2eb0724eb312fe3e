"""Seconds the backend took to hand over the step program:
``backend_s`` of the ``compile.program`` row that ``setup.step_build``
names, the compile on a cache miss and the load on a hit (the row
``"check": "setup_phases"`` says which)."""

from chipbench.setup_phases import step_s


def read(trace, spans, counters, cell):
    return step_s(spans, cell, "backend_s")
