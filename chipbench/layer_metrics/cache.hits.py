"""Of the programs asked for before the window, those the persistent
cache held (``cache == "hit"`` on the ``compile.program`` row); beside
``cache.misses``, which counts the programs written to it."""

from chipbench.setup_phases import summary


def read(trace, spans, counters, cell):
    s = summary(spans, cell)
    return sum(p["cache"] == "hit" for p in s["programs"]) if s else None
