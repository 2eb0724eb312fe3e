"""Time a step the core spends in the ``%async-collective-start.<n>`` and
``%async-collective-done.<n>`` fusions themselves: issuing an
asynchronous all-reduce (microseconds) and waiting for what of it is
still under way when its result is needed. The least such an exchange
still costs the step; ``coll.exposed_ms`` reads only the synchronous
collectives beside it."""

from chipbench.async_collectives import read as read_async


def read(trace, spans, counters, cell):
    return read_async(trace, cell, "wait_ms")
