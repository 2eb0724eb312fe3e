"""Seconds jax spent lowering the step program to a module:
``lower_s`` of the ``compile.program`` row that ``setup.step_build``
names."""

from chipbench.setup_phases import step_s


def read(trace, spans, counters, cell):
    return step_s(spans, cell, "lower_s")
