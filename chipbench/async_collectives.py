"""libtpu's asynchronous all-reduce, as a trace shows it (PR 28).

``trace_reduce.COLLECTIVES`` finds a collective by the opcode in its
event's text, and ``coll.time_ms`` / ``coll.exposed_ms`` read what it
finds. An all-reduce that libtpu runs beside other work has no such
opcode: on the ``XLA Ops`` line it is a fusion
``%async-collective-start.<n> = (...) fusion(...)`` that issues it (a few
microseconds), then the fusions that carry it (ordinary ``%fusion.<m>``
events: the exchange runs inside them), then
``%async-collective-done.<n> = f32[...] fusion(...)``, which lasts as long
as the core still has to wait for the exchange. So the two accepted
readers see only what stayed synchronous, and this module reads the rest:

``under_way_ms``  a step, the time during which at least one such exchange
                  is between the beginning of its ``start`` and the end of
                  its ``done`` (paired by ``<n>`` within a run of the
                  program);
``wait_ms``       a step, the durations of the ``start`` and ``done``
                  events themselves: the core does nothing but issue or
                  await an exchange. It is the least the exchange still
                  costs the step; what the carrying fusions lose by
                  carrying is in no event of its own.

Both are means over the runs of the step program that lie whole inside
the window ``trace_reduce.summarize`` used, and over the devices. A
program without such fusions (every program before PR 28, every one-chip
program) gives None.
"""

from __future__ import annotations

import re

from chipbench.trace_reduce import (
    Trace,
    length,
    step_module,
    union,
    window_of,
)

ASYNC_EVENT = re.compile(r"^%async-collective-(start|done)(?:\.(\d+))? = ")


def pairs(events, lo: float, hi: float):
    """``(intervals, wait_ns)`` of the events of ``events``
    (``(name, start_ns, dur_ns)``) that begin within ``[lo, hi)``, one
    run of a program: an interval from each ``start``'s beginning to the
    end of the ``done`` with its number, and the summed durations of both
    kinds of event. A ``done`` without its ``start`` counts from its own
    beginning."""
    intervals, wait, began = [], 0, {}
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        m = ASYNC_EVENT.match(name)
        if not m or not lo <= start < hi:
            continue
        wait += dur
        if m[1] == "start":
            began[m[2]] = start
        else:
            intervals.append((began.pop(m[2], start), start + dur))
    return intervals, wait


def per_step(trace: Trace) -> dict | None:
    """``{"under_way_ms", "wait_ms", "pairs"}`` of one run of the step
    program: the mean over its runs inside the window on each device,
    then over the devices that ran any; None where no run of it held an
    asynchronous exchange."""
    window = window_of(trace)
    if window is None:
        return None
    lo, hi = window
    rows = []
    for d, events in sorted(trace.ops.items()):
        mine = [e for e in events if ASYNC_EVENT.match(e[0])]
        runs = [(s, s + n) for _, s, n in step_module(trace, d)
                if lo <= s and s + n <= hi]
        found = [pairs(mine, *run) for run in runs]
        if any(intervals for intervals, _ in found):
            rows.append([sum(x) / len(runs) for x in zip(*(
                (length(union(iv)), wait, len(iv)) for iv, wait in found))])
    if not rows:
        return None
    under_way, wait, count = (sum(col) / len(rows) for col in zip(*rows))
    return {"under_way_ms": under_way / 1e6, "wait_ms": wait / 1e6,
            "pairs": count}


def read(summary, cell: dict, key: str):
    """``per_step(...)[key]`` of this run's trace of ``cell``; None off a
    trace, and where the program ran no asynchronous exchange."""
    from chipbench import host_phases

    path = host_phases.find(cell) if summary and summary["steps"] else None
    if not path:
        return None
    found = per_step(host_phases.read_xplane(path)[0])
    return found and found[key]
