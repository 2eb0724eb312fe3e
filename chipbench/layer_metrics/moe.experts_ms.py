"""Device time a step under the scope of the grouped products
(``moe.experts``: the three ragged products forward, six backward, the
forward again under ``remat``, and the SwiGLU elementwise pass between
them), on the first device."""

from chipbench.scope_time import EXPERTS, seconds_per_step


def read(trace, spans, counters, cell):
    per_step = seconds_per_step(trace, counters, EXPERTS)
    return None if per_step is None else 1e3 * per_step
