"""Programs the backend was asked for before the window, in all the
run's processes: the ``compile.program`` rows of the set-up log."""

from chipbench.setup_phases import summary


def read(trace, spans, counters, cell):
    s = summary(spans, cell)
    return len(s["programs"]) if s else None
