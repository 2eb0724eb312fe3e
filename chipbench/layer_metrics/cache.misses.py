"""Programs compiled and written to the persistent cache by the run's
processes (``utils/compile_cache.py``); 0 in every run of a checkout
but its first. A synchronous run counts them up to the window's open,
as ``setup_s`` and ``cache.hits`` do: the comparison with the reference
and the clock control come after it."""


def read(trace, spans, counters, cell):
    return counters.get("cache_misses")
