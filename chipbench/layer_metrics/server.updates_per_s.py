"""Updates a second at the server: one over the MEDIAN time from one
published version to the next inside the window (the count over the
window's length swings with the neighbours on a shared host)."""


def read(trace, spans, counters, cell):
    if not counters.get("cycle_s_p50"):
        return None
    return 1.0 / counters["cycle_s_p50"]
