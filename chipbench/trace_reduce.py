"""From a profiler trace to numbers: device busy and idle time, collective
and exposed collective time, time by operation name, the step program's
duration, and the longest idle gaps labelled by what the host was doing.

A trace is read once into plain lists (``Trace``) and reduced from
there, so the same reduction runs on a ``*.xplane.pb`` the profiler
wrote and on the small recorded trace kept with the tests
(``dump`` writes one, ``load`` reads either).

What the planes of a TPU trace hold is written down in PERF.md section
3 ("Reading a trace"); in short: one plane ``/device:TPU:<n>`` per chip
with a line ``XLA Ops`` (one event per executed HLO operation, serial)
and a line ``XLA Modules`` (one event per executed program), and one
plane ``/host:CPU`` whose thread lines carry the benchmark's
``TraceAnnotation`` spans on the same clock.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import statistics
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_SPANS = ("data.next", "fit.call")
COLLECTIVES = ("all-reduce|all-gather|reduce-scatter|collective-permute|"
               "all-to-all|collective-broadcast")
# an operation's event is named by its whole HLO instruction, and only its
# opcode says what it is: XLA names an all-reduce %all-reduce.36 when it
# combined several and %psum.1204 when jax.lax.psum made it. So the opcode
# is read from the whole text and kept as a tag on the shortened name; a
# Pallas kernel is a custom call with this target, named after the flax
# module that called it (the flash kernels: %SelfAttention_0.<n>)
OPCODE = re.compile(rf"[\])}}] ({COLLECTIVES})(-start|-done)?\(")
COLLECTIVE = re.compile(rf" \[({COLLECTIVES})(-start|-done)?\]$")
PALLAS = 'custom_call_target="tpu_custom_call"'
PALLAS_TAG = " [tpu_custom_call]"
ATTENTION_KERNEL = r"^%SelfAttention\S* = .*\[tpu_custom_call\]$"
LAYOUT = re.compile(r"\{[^{}]*\}")

Event = tuple  # (name, start_ns, duration_ns)


def short(text: str, keep: int = 120) -> str:
    """An HLO instruction as an event name: layouts dropped, cut to
    ``keep`` characters, and tagged with what only the whole text says:
    a collective's opcode, a Pallas kernel's custom call."""
    name = LAYOUT.sub("", text)[:keep]
    opcode = OPCODE.search(text)
    if opcode:
        return f"{name} [{opcode[1]}{opcode[2] or ''}]"
    return name + PALLAS_TAG if PALLAS in text else name


@dataclasses.dataclass
class Trace:
    ops: dict          # device id -> [Event] of the XLA Ops line
    modules: dict      # device id -> [Event] of the XLA Modules line
    host: list         # [Event] of the benchmark's host annotations


def read_xplane(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, host = defaultdict(list), defaultdict(list), []
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name in (OPS_LINE, MODULES_LINE):
                into = (ops if line.name == OPS_LINE else modules)[int(dev[1])]
                into.extend((short(e.name), e.start_ns, e.duration_ns)
                            for e in line.events if e.duration_ns > 0)
            elif plane.name == "/host:CPU":
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name in HOST_SPANS)
    return Trace(dict(ops), dict(modules), host)


def dump(trace: Trace, path: str) -> None:
    doc = {"ops": {str(d): ev for d, ev in trace.ops.items()},
           "modules": {str(d): ev for d, ev in trace.modules.items()},
           "host": trace.host}
    with gzip.open(path, "wt") as f:
        json.dump(doc, f, separators=(",", ":"))


def load(path: str) -> Trace | None:
    """``path`` is a recorded trace (``.json.gz``) or a directory the
    profiler wrote into. None when there is no trace there."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        as_events = lambda evs: [tuple(e) for e in evs]
        return Trace({int(d): as_events(e) for d, e in doc["ops"].items()},
                     {int(d): as_events(e) for d, e in doc["modules"].items()},
                     as_events(doc["host"]))
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    return read_xplane(max(files, key=os.path.getmtime)) if files else None


# -- interval arithmetic over [start, end) pairs ----------------------------

def union(intervals):
    """Merge [start, end) intervals into a disjoint sorted list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(disjoint) -> float:
    return sum(e - s for s, e in disjoint)


def intersection_length(a, b) -> float:
    """Of two disjoint sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


# -- the reduction ------------------------------------------------------------

def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(name))


def collective_intervals(events):
    """The time each collective is under way: a synchronous one for its
    own event; an asynchronous one from the beginning of its ``-start``
    to the end of the ``-done`` that follows it (first in, first out per
    kind)."""
    out, pending = [], defaultdict(list)
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        m = COLLECTIVE.search(name)
        if not m:
            continue
        kind, phase = m[1], m[2]
        if phase == "-start":
            pending[kind].append(start)
        elif phase == "-done" and pending[kind]:
            out.append((pending[kind].pop(0), start + dur))
        else:
            out.append((start, start + dur))
    return out


def window_of(trace: Trace):
    """The traced window on the trace's clock: the benchmark's
    ``fit.call`` annotations but the first, which absorbs the profiler's
    start-up (the first traced step runs ~50 ms late); without
    annotations, from the first to the last device operation."""
    calls = sorted(e for e in trace.host if e[0] == "fit.call")
    evs = calls[1:] or calls or [e for d in trace.ops.values() for e in d]
    if not evs:
        return None
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def step_module(trace: Trace, dev: int):
    """The events of the program that took most of the device's time:
    the fused training step."""
    by_name = defaultdict(list)
    for e in trace.modules.get(dev, []):
        by_name[e[0]].append(e)
    return max(by_name.values(), key=lambda evs: sum(e[2] for e in evs),
               default=[])


def summarize(trace: Trace) -> dict | None:
    """Seconds, averaged over the devices that ran anything; None for a
    trace with no device operation."""
    win = window_of(trace)
    devs = sorted(d for d, evs in trace.ops.items() if evs)
    if win is None or not devs:
        return None
    lo, hi = win
    busy = coll = exposed = 0.0
    step_s, steps = [], []
    for d in devs:
        evs = trace.ops[d]
        all_iv = union(clip([(s, s + n) for _, s, n in evs], lo, hi))
        coll_iv = union(clip(collective_intervals(evs), lo, hi))
        other_iv = union(clip([(s, s + n) for name, s, n in evs
                               if not is_collective(name)], lo, hi))
        busy += length(all_iv)
        coll += length(coll_iv)
        exposed += length(coll_iv) - intersection_length(coll_iv, other_iv)
        mod = [e for e in step_module(trace, d) if lo <= e[1] and e[1] + e[2] <= hi]
        if mod:
            step_s.append(statistics.median(e[2] for e in mod))
            steps.append(len(mod))
    n = len(devs)
    first = devs[0]
    by_name = defaultdict(lambda: [0, 0.0])
    for name, s, dur in trace.ops[first]:
        if lo <= s < hi:
            by_name[name][0] += 1
            by_name[name][1] += dur / 1e9
    busy_first = union(clip([(s, s + n_) for _, s, n_ in trace.ops[first]], lo, hi))
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "collective_s": coll / n / 1e9,
        "collective_exposed_s": exposed / n / 1e9,
        "step_device_s": statistics.mean(step_s) / 1e9 if step_s else None,
        "steps": min(steps) if steps else 0,
        "by_name": {k: tuple(v) for k, v in by_name.items()},
        "gaps": idle_gaps(busy_first, lo, hi, trace.host),
    }


def idle_gaps(busy, lo, hi, host, keep: int = 5):
    """The longest intervals in which the first device ran nothing, each
    labelled by the shortest benchmark annotation that covers its middle
    (``none`` where the host was in none)."""
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i])
                   for i in range(0, len(edges), 2)), reverse=True)[:keep]
    out = []
    for dur, start in gaps:
        if dur <= 0:
            continue
        mid = start + dur / 2
        cover = [e for e in host if e[1] <= mid <= e[1] + e[2]]
        label = min(cover, key=lambda e: e[2])[0] if cover else "none"
        out.append([label, dur / 1e9])
    return out


def top_ops(summary: dict, keep: int = 8):
    rows = sorted(summary["by_name"].items(), key=lambda kv: -kv[1][1])
    return [[name, secs] for name, (_, secs) in rows[:keep]]


def seconds_matching(summary: dict, pattern: str) -> tuple[int, float]:
    """(events, seconds) on the first device of the operations whose name
    matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [v for k, v in summary["by_name"].items() if rx.search(k)]
    return sum(c for c, _ in hits), sum(s for _, s in hits)


def seconds_per_step(summary: dict | None, pattern: str) -> float | None:
    """Of the operations matching ``pattern``; None where none ran."""
    if not summary or not summary["steps"]:
        return None
    events, seconds = seconds_matching(summary, pattern)
    return seconds / summary["steps"] if events else None


def inspect(path: str, samples: int = 3, names: int = 30) -> None:
    """Print what a trace holds, for reading one by hand: every plane and
    line, its heaviest event names, and a few events with their stats."""
    import jax

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            by_name = defaultdict(lambda: [0, 0.0])
            for e in events:
                by_name[e.name][0] += 1
                by_name[e.name][1] += e.duration_ns
            for name, (count, ns) in sorted(
                    by_name.items(), key=lambda kv: -kv[1][1])[:names]:
                print(f"    {ns / 1e6:12.3f} ms {count:6d} x {name[:100]!r}")
            for e in events[:samples]:
                stats = {k: str(v)[:160] for k, v in e.stats}
                print(f"    e.g. {e.name[:80]!r} start={e.start_ns} "
                      f"dur={e.duration_ns} {stats}")


if __name__ == "__main__":
    import sys

    inspect(sys.argv[1])
