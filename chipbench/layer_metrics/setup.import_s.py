"""Seconds inside the program's ``setup.import`` span: the package's
own import, first line to last, in the benchmark's process (jax is in it
only where nothing imported jax before; the row's ``jax_already_imported``
says)."""

from chipbench.setup_phases import phase_s


def read(trace, spans, counters, cell):
    return phase_s(spans, cell, "setup.import")
