"""Programs compiled and written to the persistent cache by the run's
processes (``utils/compile_cache.py``); 0 in every run of a checkout
but its first."""


def read(trace, spans, counters, cell):
    return counters.get("cache_misses")
