"""Device time by the model's named scopes.

A trace event is named by its HLO instruction's text, which holds no
``op_name``: a ``jax.named_scope`` is nowhere in a trace (PERF.md section
3, "Reading a trace"). The job (``jobs/sync_train_streamed.py``) reads the
step program's optimized text, where every instruction has its
``op_name``, and puts instruction name -> scope into the run's counters
(``scopes``); this module joins the two. Without that counter (a job
that does not make it, a program that cannot give its text) there is
nothing to read and every function returns None.
"""

from __future__ import annotations

import re

from chipbench import trace_reduce

INSTRUCTION = re.compile(r"^(%[\w.\-]+) = ")
# the three block-diffusion flash kernels carry their own names
# (ops/attention_pallas.py), whatever transformation wraps them
BD_KERNELS = r"^%\S*flash_bd_(fwd|dq|dkv)\S* = .*\[tpu_custom_call\]$"
EXPERTS = r"^moe\.experts$"   # the grouped products and the SwiGLU pass


def bd_kernel_seconds(trace: dict | None) -> float | None:
    """Seconds a step of the block-diffusion kernels' events; None where
    none ran."""
    if not trace or "by_name" not in trace:
        return None
    return trace_reduce.seconds_per_step(trace, BD_KERNELS)


def seconds_per_step(trace: dict | None, counters: dict,
                     scope: str) -> float | None:
    """Seconds a step, on the first device, of the operations whose scope
    matches the regular expression ``scope``; None where none ran."""
    scopes = counters.get("scopes")
    if not trace or not trace.get("steps") or not scopes:
        return None
    rx = re.compile(scope)
    events, seconds = 0, 0.0
    for name, (count, secs) in trace["by_name"].items():
        m = INSTRUCTION.match(name)
        if m and rx.search(scopes.get(m[1], "")):
            events, seconds = events + count, seconds + secs
    return seconds / trace["steps"] if events else None
