"""Peak bytes in use on the fullest chip after the system's warm-up and
before the reference ran (``memory_stats()``), in GB."""


def read(trace, spans, counters, cell):
    peak = counters.get("peak_hbm_bytes")
    return peak / 1e9 if peak else None
