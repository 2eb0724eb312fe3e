"""Device time a step under the scope of the gated delta rule's chunked
scan (``kda.scan``: the intra-chunk products, the triangular solve, the
pass over the chunks and the output, forward, again under ``remat``, and
backward), on the first device. The pass over the chunks is an XLA loop:
the operations of its body are counted, not the loop's own event, which
spans them (``sambay_trace.py``). Absent where the cell is another
family's."""

from chipbench.sambay_trace import seconds_per_step

SCAN = r"^kda\.scan$"


def read(trace, spans, counters, cell):
    if "kda_layers" not in (cell.get("shape") or {}):
        return None
    per_step = seconds_per_step(trace, counters, SCAN)
    return None if per_step is None else 1e3 * per_step
