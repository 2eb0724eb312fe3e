"""Device time a step under the hyper-connections' scopes (``hc.mix``:
the mixing weights' product, the width and the depth mix; ``hc.sinkhorn``:
the projection onto the doubly stochastic matrices; the prediction
module's behind ``mtp.``), forward, again under ``remat``, and backward,
on the first device; a loop's own event is left out beside its body's
(``sambay_trace.py``)."""

from chipbench.sambay_trace import seconds_per_step
from chipbench.xing_trace import HC, shape_of


def read(trace, spans, counters, cell):
    if shape_of(cell) is None:
        return None
    per_step = seconds_per_step(trace, counters, HC)
    return None if per_step is None else 1e3 * per_step
