"""Device time a step under the expert layer's scopes outside the grouped
products: ``moe.route`` (router product, softmax, top-k), ``moe.dispatch``
(the sorts, the plan, the gather into the buffer) and ``moe.combine`` (the
gate weights and the sum back to the positions), forward and backward, on
the first device."""

from chipbench.scope_time import seconds_per_step

SCOPES = r"^moe\.(route|dispatch|combine)$"


def read(trace, spans, counters, cell):
    per_step = seconds_per_step(trace, counters, SCOPES)
    return None if per_step is None else 1e3 * per_step
