"""Seconds jax spent tracing the step program: ``trace_s`` of the
``compile.program`` row that ``setup.step_build`` names."""

from chipbench.setup_phases import step_s


def read(trace, spans, counters, cell):
    return step_s(spans, cell, "trace_s")
