"""Device time a step under the gated short convolution's scope
(``conv.mix``: the two gates and the tap sum of every ``conv`` layer,
forward, again under ``remat``, and backward), on the first device; a
loop's own event is left out beside its body's (``sambay_trace.py``).
Absent where the cell is another family's."""

from chipbench.lfm2_trace import CONV_MIX, scope_seconds


def read(trace, spans, counters, cell):
    per_step = scope_seconds(trace, counters, cell, CONV_MIX)
    return None if per_step is None else 1e3 * per_step
