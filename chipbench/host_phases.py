"""What the host was in while the chip waited: the first device's idle
time of the traced window, split by the program's own spans.

The program opens its hot-path spans (``telemetry.span``) as
``jax.profiler`` annotations too, so they sit on the ``/host:CPU`` plane
of the very trace that holds the device's operations, on one clock.
``find`` locates the run's trace, ``read_xplane`` reads it once more for
what ``trace_reduce.read_xplane`` leaves out — the program's spans, and
how far the device's clock is off the host's (``clock_shift``) — and
``idle_by_span`` gives every idle nanosecond of the first device to the
innermost span that covers it.
One reader a metric under ``layer_metrics/`` (``idle.<span>_ms``) takes
its number from ``idle_ms``; ``host_ms`` gives a span's own durations
where the job hands the readers no recorder span of that name.
"""

from __future__ import annotations

import functools
import glob
import os
from collections import defaultdict

from chipbench.jobs.common import say
from chipbench.run import ROOT
from chipbench.trace_reduce import (
    DEVICE_PLANE,
    HOST_SPANS,
    MODULES_LINE,
    OPS_LINE,
    Trace,
    clip,
    intersection_length,
    length,
    short,
    union,
    window_of,
)

# what the program's span names begin with: the helper's own constant,
# the yardstick imports no list of names from the program it measures
SPAN_PREFIXES = ("trainer.", "ps.", "worker.", "wire.")
IN_PROGRAM = "in_program"  # idle inside a running program: not the host's
OUTSIDE = "outside"        # idle under no program span at all


@functools.lru_cache(maxsize=None)
def read_xplane(path: str):
    """``(Trace, spans)``: the trace as ``trace_reduce.read_xplane``
    reads it, with each device's events moved onto the host plane's clock
    (``clock_shift``), and the program's span events of ``/host:CPU``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, host, spans = defaultdict(list), defaultdict(list), [], []
    launched, started = {}, defaultdict(list)
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name in (OPS_LINE, MODULES_LINE):
                into = (ops if line.name == OPS_LINE else modules)[int(dev[1])]
                into.extend((short(e.name), e.start_ns, e.duration_ns)
                            for e in line.events if e.duration_ns > 0)
                if line.name == MODULES_LINE:
                    started[int(dev[1])] += [
                        (f, e.start_ns) for e in line.events
                        if (f := flow(e, "_ct", "_c"))]
            elif plane.name == "/host:CPU":
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.name, e.start_ns, e.duration_ns))
                    elif e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, e.start_ns, e.duration_ns))
                    elif f := flow(e, "_pt", "_p"):
                        launched[f] = e.start_ns
    shift = {d: clock_shift(launched, started[d]) for d in ops}
    say(check="host_phases", device_clock_shift_ms={
        d: ns / 1e6 for d, ns in sorted(shift.items())})
    move = lambda events, d: [(n, s + shift[d], dur) for n, s, dur in events]
    return Trace({d: move(evs, d) for d, evs in ops.items()},
                 {d: move(evs, d) for d, evs in modules.items()
                  if d in shift}, host), spans


def flow(event, kind: str, ident: str):
    """The profiler's link between an event that launches work and the
    event that is the work: the launching one carries it as the stats
    ``_pt`` / ``_p``, the launched one as ``_ct`` / ``_c``. None where
    the event carries no such pair."""
    stats = dict(event.stats)
    return (str(stats[kind]), str(stats[ident])) if ident in stats else None


def clock_shift(launched: dict, started: list) -> float:
    """Nanoseconds to add to a device plane's clock. The two planes of a
    trace disagree by 0.5-1.5 ms from run to run, the device's reading
    early: its programs begin before the runtime's event that launched
    them. No program can; so the device's clock is moved by the least
    that puts every program of ``started`` (``(flow, start_ns)``) at or
    after the start of the host event that launched it (``launched``:
    ``{flow: start_ns}``). What stays is the launch latency of the
    program that started soonest after its launch. 0 where the trace
    links no program to a launch."""
    late = [launched[f] - t for f, t in started if f in launched]
    return max(late, default=0.0)


def find(cell: dict):
    """The trace file of this run of ``cell``, where the harness always
    writes it; None without one."""
    scratch = os.path.join(ROOT, ".chipbench_run", cell["name"])
    files = [f for sub in ("trace", "worker-trace") for f in glob.glob(
        os.path.join(scratch, sub, "**", "*.xplane.pb"), recursive=True)]
    return max(files, key=os.path.getmtime) if files else None


def complement(disjoint, lo, hi):
    """Of a disjoint sorted interval list, within [lo, hi)."""
    edges = [lo] + [t for iv in disjoint for t in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def innermost(spans):
    """``{name: disjoint sorted intervals}``: of the time the events
    cover, each stretch under the name of the SHORTEST event covering it,
    so a parent keeps only its self time."""
    cuts = sorted({t for _, s, n in spans for t in (s, s + n)})
    out = defaultdict(list)
    for lo, hi in zip(cuts, cuts[1:]):
        cover = [e for e in spans if e[1] <= lo and hi <= e[1] + e[2]]
        if cover:
            out[min(cover, key=lambda e: e[2])[0]].append((lo, hi))
    return out


def idle_by_span(ops, modules, spans, window, steps: int) -> dict:
    """``{span: ms per step}`` of one device's idle time in ``window``:
    the complement of the union of its operations (``ops``), as
    ``trace_reduce.idle_gaps`` takes it. What lies within an event of
    ``modules`` — the pauses between a running program's operations —
    goes to ``in_program``, whatever the host was in; the rest to the
    innermost of ``spans`` covering it, ``outside`` where none does.
    All three are lists of ``(name, start_ns, dur_ns)``; the values sum
    to the whole idle time."""
    lo, hi = window
    iv = lambda events: union(clip([(s, s + n) for _, s, n in events], lo, hi))
    idle = complement(iv(ops), lo, hi)
    between = complement(iv(ops + modules), lo, hi)  # idle, and in no program
    ns = {name: intersection_length(between, stretches)
          for name, stretches in innermost(spans).items()}
    ns[OUTSIDE] = length(between) - sum(ns.values())
    ns[IN_PROGRAM] = length(idle) - length(between)
    return {name: v / 1e6 / steps for name, v in ns.items()}


@functools.lru_cache(maxsize=None)
def first_device_idle(path: str, steps: int) -> dict:
    """``idle_by_span`` of the first device of the trace at ``path``,
    over the window ``trace_reduce.summarize`` used."""
    trace, spans = read_xplane(path)
    first = min(d for d, evs in trace.ops.items() if evs)
    return idle_by_span(trace.ops[first], trace.modules.get(first, []), spans,
                        window_of(trace), steps)


def idle_ms(summary, cell: dict, span: str):
    """Idle ms a step of the first device under ``span``; None off a
    trace, or where the program opened no such span."""
    path = find(cell) if summary and summary["steps"] else None
    return path and first_device_idle(path, summary["steps"]).get(span)


def host_ms(summary, cell: dict, span: str) -> list:
    """Durations in ms of the program's ``span`` events inside the
    window, read from the trace's host plane."""
    path = find(cell) if summary else None
    if not path:
        return []
    trace, spans = read_xplane(path)
    lo, hi = window_of(trace)
    return [n / 1e6 for name, s, n in spans
            if name == span and lo <= s and s + n <= hi]
