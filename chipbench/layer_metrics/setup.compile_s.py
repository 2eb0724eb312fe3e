"""Seconds inside the backend over every program asked for before
the window, in all the run's processes: the sum of ``backend_s`` over
the ``compile.program`` rows (a compile, or on a hit the load:
``retrieval_s`` lies inside ``backend_s`` and is not added again). The
harness's own programs are in it, by name in the check row."""

from chipbench.setup_phases import summary


def read(trace, spans, counters, cell):
    s = summary(spans, cell)
    return sum(p["backend_s"] for p in s["programs"]) if s else None
