"""Time a step during which an asynchronous all-reduce is under way: from
the beginning of an ``%async-collective-start.<n>`` fusion to the end of
its ``%async-collective-done.<n>``, the union over the step's exchanges,
mean over the step's runs and the devices. ``coll.time_ms`` does not see
these (no collective opcode in their text). A longer time is no loss in
itself: it is how long the links were given, not what the step paid."""

from chipbench.async_collectives import read as read_async


def read(trace, spans, counters, cell):
    return read_async(trace, cell, "under_way_ms")
