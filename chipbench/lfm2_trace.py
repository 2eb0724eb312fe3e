"""What the ``lfm2`` family's readers share: how a reader knows the cell
is this family's, the scopes of its short convolution, and the device time
of its causal grouped-query flash kernels.

The causal flash kernels of one width carry no name of their own
(``ops/attention_pallas.py``: they keep the name XLA gives them), so they
are told by where they sit: the Pallas custom calls (``trace_reduce``'s
tag) among the instructions the step program's text puts under the scope
``attn.gqa`` (the run's ``scopes`` counter,
``jobs/sync_train_streamed.py``). The layout transposes and the logsumexp's
rides around them sit under the same scope and are no custom call: they
are left out, as every other ``attn.*_kernel_ms`` leaves them out. Scopes
are read through ``sambay_trace.seconds_per_step``, which leaves out a
loop's own event beside its body's.
"""

from __future__ import annotations

import re

from chipbench import sambay_trace
from chipbench.scope_time import INSTRUCTION
from chipbench.trace_reduce import PALLAS_TAG
from chipbench.xing_trace import per_chip  # noqa: F401

CONV_MIX = r"^conv\.mix$"
CONV_PROJ = r"^conv\.proj$"
GQA = "attn.gqa"


def shape_of(cell: dict) -> dict | None:
    """The cell's FLOP shape where it is an ``lfm2`` cell's, else None."""
    shape = cell.get("shape") or {}
    return shape if "taps" in shape and "conv_layers" in shape else None


def scope_seconds(trace: dict | None, counters: dict, cell: dict,
                  scope: str) -> float | None:
    """Seconds a step under ``scope`` (loops' own events left out); None
    where nothing ran there or the cell is another family's."""
    if shape_of(cell) is None:
        return None
    return sambay_trace.seconds_per_step(trace, counters, scope)


def kernel_seconds(trace: dict | None, counters: dict,
                   cell: dict) -> float | None:
    """Seconds a step, on the first device, of the Pallas kernels under
    the scope ``attn.gqa``; None where none ran, the run made no scope
    table or the cell is another family's."""
    scopes = counters.get("scopes")
    if (not trace or not trace.get("steps") or not scopes
            or "by_name" not in trace or shape_of(cell) is None):
        return None
    events, seconds = 0, 0.0
    for name, (count, secs) in trace["by_name"].items():
        m = INSTRUCTION.match(name)
        if m and name.endswith(PALLAS_TAG) and scopes.get(m[1]) == GQA:
            events, seconds = events + count, seconds + secs
    return seconds / trace["steps"] if events else None
