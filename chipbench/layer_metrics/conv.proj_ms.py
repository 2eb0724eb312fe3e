"""Device time a step under the short convolution's projections
(``conv.proj``: ``hidden x 3 hidden`` in and ``hidden x hidden`` out of
every ``conv`` layer, forward, again under ``remat``, and backward with
each matrix's weight-gradient product), on the first device. Absent where
the cell is another family's."""

from chipbench.lfm2_trace import CONV_PROJ, scope_seconds


def read(trace, spans, counters, cell):
    per_step = scope_seconds(trace, counters, cell, CONV_PROJ)
    return None if per_step is None else 1e3 * per_step
