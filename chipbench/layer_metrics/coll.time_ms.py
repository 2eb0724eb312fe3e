"""Time per step during which a collective is under way (an asynchronous
one from its ``-start`` to its ``-done``), mean over the devices."""


def read(trace, spans, counters, cell):
    if not trace or not trace["steps"] or counters["chips"] == 1:
        return None
    return 1e3 * trace["collective_s"] / trace["steps"]
