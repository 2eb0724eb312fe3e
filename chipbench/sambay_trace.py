"""What the ``sambay`` family's readers share: the names of its flash
kernels, the scope of its scans, and device time under a scope that
holds XLA loops.

A ``while`` instruction has an event of its own on the trace's ``XLA
Ops`` line, as long as the loop runs, AND every operation of its body
has its events there too. ``scope_time.seconds_per_step`` sums every
event of a scope, so under a scope that is made of loops it counts a
loop's body twice and an inner loop's three times (the selective scan of
``phi4-mini-flash.lm8k``: 149.6 ms read where the device spent 64.0; my
chip runs B1 and B2, PR 31). Here the loops' own events are left out and
their bodies' operations are what is counted. What that loses is the
loop's control between two operations (over the whole step 1.3 of 494.3
ms: the operations that are no loop sum to 493.0).
"""

from __future__ import annotations

import re

from chipbench.scope_time import INSTRUCTION

LOOP = re.compile(r"^%while[.\d]* = ")   # XLA's name for a while instruction
SCAN = r"^ssm\.scan$"
# the window kernels and the causal ones with a value wider than the keys
# carry their own names (ops/attention_pallas.py), whatever wraps them
DIFF_KERNELS = (r"^%\S*flash_(win|wide)_(fwd|dq|dkv)\S* = "
                r".*\[tpu_custom_call\]$")


def seconds_per_step(trace: dict | None, counters: dict,
                     scope: str) -> float | None:
    """Seconds a step, on the first device, of the operations under the
    scope matching ``scope`` that are not themselves loops; None where
    none ran or the run made no scope table."""
    scopes = counters.get("scopes")
    if not trace or not trace.get("steps") or not scopes:
        return None
    rx = re.compile(scope)
    events, seconds = 0, 0.0
    for name, (count, secs) in trace["by_name"].items():
        m = INSTRUCTION.match(name)
        if m and not LOOP.match(name) and rx.search(scopes.get(m[1], "")):
            events, seconds = events + count, seconds + secs
    return seconds / trace["steps"] if events else None
