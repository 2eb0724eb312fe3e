"""Seconds inside ``setup.first_step``: from the entry of the
trainer's first ``fit`` call to its first loss on the host."""

from chipbench.setup_phases import phase_s


def read(trace, spans, counters, cell):
    return phase_s(spans, cell, "setup.first_step")
