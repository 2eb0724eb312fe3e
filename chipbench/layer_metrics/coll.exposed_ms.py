"""The part of ``coll.time_ms`` during which no other operation runs on
that device: what the exchange costs the step."""


def read(trace, spans, counters, cell):
    if not trace or not trace["steps"] or counters["chips"] == 1:
        return None
    return 1e3 * trace["collective_exposed_s"] / trace["steps"]
