"""Profiler integration: the deep-dive layer above the per-step metrics
dicts (SURVEY §5.1's disposition: keep the reference's returned-timings
contract and add ``jax.profiler`` traces for what host clocks can't see
inside a fused XLA program)."""

from __future__ import annotations

import collections
import glob
import os
import shutil
import tempfile
from typing import Any, Callable, Dict, Optional, Tuple

import jax


# HLO/primitive names that are interconnect work. Covers both the jax
# primitive names XLA:CPU surfaces (``psum.7``) and the HLO collective op
# names TPU planes use (``all-reduce-start.1`` etc.).
_COMM_SUBSTRINGS = (
    "psum", "all-reduce", "allreduce", "all-gather", "allgather",
    "reduce-scatter", "reducescatter", "collective", "ppermute",
    "all-to-all", "alltoall",
)


def _interval_union(intervals):
    """Merge [start, end) intervals; returns disjoint sorted list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _interval_intersection_len(a, b):
    """Total length of the intersection of two DISJOINT-SORTED interval
    lists (outputs of :func:`_interval_union`)."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _iter_hlo_events(trace_dir: str):
    """Yield ``(device, name, start_ns, dur_ns)`` for every device op
    execution (events carrying an ``hlo_op`` stat) in a trace dir."""
    for f in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True):
        try:
            pd = jax.profiler.ProfileData.from_file(f)
        except Exception:
            continue
        for plane in pd.planes:
            for line in plane.lines:
                for e in line.events:
                    dur = e.duration_ns or 0.0
                    if dur <= 0:
                        continue
                    st = dict(e.stats)
                    if "hlo_op" not in st:
                        continue
                    dev = st.get("device_ordinal", plane.name)
                    yield dev, str(e.name), float(e.start_ns or 0.0), dur


def _participant_lanes(events):
    """The execution lanes that PARTICIPATED in the traced program.

    An HLO collective instruction name is unique within its module
    (SSA), so the set of lanes (devices / executor threads) that
    emitted an execution event for it is exactly the collective's
    participant set — the same number the lowered program's
    collective-launch counters (``bucketing.count_collectives``, one
    launch executed once per participant) predict.  Counting distinct
    LANES (not events) stays correct when a loop executes the same
    collective several times per lane.  With no collective events,
    every lane counts.

    Returns ``(participant_lanes, all_lanes)``; callers restrict the
    comm/compute interval math to the participants so host-side result
    -fetch programs (which also carry ``hlo_op`` stats on jax 0.4.x
    CPU) cannot dilute the per-device means."""
    by_name: Dict[str, set] = {}
    lanes_all = set()
    for dev, name, _start, _dur in events:
        lanes_all.add(dev)
        if any(s in name.lower() for s in _COMM_SUBSTRINGS):
            by_name.setdefault(name, set()).add(dev)
    if by_name:
        widest = max(by_name.values(), key=len)
        return set(widest), lanes_all
    return set(lanes_all), lanes_all


def _launch_derived_devices(events, lowered) -> int:
    """Fallback participant count when the trace carries NO per-lane
    attribution at all (every event on one merged lane): divide the
    trace's collective-event count by the lowered program's
    collective-launch count (``bucketing.count_collectives``) — one
    launch executes once per participant, so for a single traced run
    ``events / launches`` IS the participant count.  Returns 0 when it
    cannot be derived (no lowered text, no collectives)."""
    if lowered is None:
        return 0
    try:
        text = lowered() if callable(lowered) else lowered
        from pytorch_ps_mpi_tpu.bucketing import count_collectives

        launches = int(count_collectives(text)["total"])
    except Exception:
        return 0
    if launches <= 0:
        return 0
    comm_events = sum(
        1 for _dev, name, _s, _d in events
        if any(s in name.lower() for s in _COMM_SUBSTRINGS))
    return comm_events // launches if comm_events >= launches else 0


def profiled_overlap(thunk: Callable[[], Any]) -> Tuple[Any, Dict[str, Any]]:
    """Run ``thunk()`` once under the profiler and measure how much of
    the communication time actually EXECUTES CONCURRENTLY with compute —
    the timeline-level fact :func:`profiled_device_split` (duration sums)
    cannot see, and the reference's signature design claim (encode/comm
    overlapped with backprop via hooks + a 200-thread pool,
    ``/root/reference/ps.py:65-66,85``) that this framework delegates to
    XLA's scheduler.

    Per device: union the [start, end) intervals of collective ops
    (``_COMM_SUBSTRINGS``) and of every other device op, then intersect.
    Returns ``(out, d)`` with per-device MEANS in seconds: ``comm_s``/
    ``compute_s`` (union lengths, so a thread blocked inside one psum
    event counts once), ``overlap_s`` (comm∩compute), ``overlap_frac``
    (overlap_s / comm_s — 1.0 means every comm nanosecond rode under
    compute), ``busy_union_s`` (comm∪compute — the device's critical
    path through this step), and ``serial_equiv_s`` (comm_s + compute_s
    — what the step would cost with zero overlap). ``devices=0`` when
    the backend emits no device events."""
    d = tempfile.mkdtemp(prefix="jaxtrace_")
    try:
        jax.profiler.start_trace(d)
        try:
            out = thunk()
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        events = list(_iter_hlo_events(d))
        lanes, _all = _participant_lanes(events)
        comm_iv: Dict[Any, list] = collections.defaultdict(list)
        comp_iv: Dict[Any, list] = collections.defaultdict(list)
        for dev, name, start, dur in events:
            if dev not in lanes:
                continue  # host-side fetch lane, not a participant
            tgt = comm_iv if any(
                s in name.lower() for s in _COMM_SUBSTRINGS
            ) else comp_iv
            tgt[dev].append((start, start + dur))
        devs = sorted(set(comm_iv) | set(comp_iv), key=str)
        n = len(devs)
        if not n:
            return out, {"devices": 0, "comm_s": 0.0, "compute_s": 0.0,
                         "overlap_s": 0.0, "overlap_frac": 0.0,
                         "busy_union_s": 0.0, "serial_equiv_s": 0.0}
        comm = compute = overlap = busy = 0.0
        for dev in devs:
            cu = _interval_union(comm_iv.get(dev, []))
            pu = _interval_union(comp_iv.get(dev, []))
            comm += sum(e - s for s, e in cu)
            compute += sum(e - s for s, e in pu)
            overlap += _interval_intersection_len(cu, pu)
            busy += sum(e - s for s, e in _interval_union(
                list(comm_iv.get(dev, [])) + list(comp_iv.get(dev, []))
            ))
        scale = 1e9 * n
        comm_s, compute_s = comm / scale, compute / scale
        overlap_s = overlap / scale
        return out, {
            "devices": n,
            "comm_s": comm_s,
            "compute_s": compute_s,
            "overlap_s": overlap_s,
            "overlap_frac": overlap_s / comm_s if comm_s > 0 else 0.0,
            "busy_union_s": busy / scale,
            "serial_equiv_s": comm_s + compute_s,
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def profiled_device_split(
    thunk: Callable[[], Any], *, lowered=None,
) -> Tuple[Any, Dict[str, Any]]:
    """Run ``thunk()`` once under the JAX profiler and split *device* op
    time into communication vs compute.

    This measures the real fused program — the split host wall-clocks
    around separate sub-programs (``MPI_PS`` ``instrument=True``)
    structurally cannot see, because splitting the program changes what
    XLA can overlap. Only events carrying an ``hlo_op`` stat (device op
    executions) are counted; host-side compile/dispatch events have no
    ``hlo_op`` and are excluded, so tracing a first (compiling) call
    still yields a clean device split.

    Returns ``(thunk result, split)`` where split has per-device *mean*
    seconds: ``device_busy_s``, ``comm_s``, ``compute_s``, plus
    ``devices`` and the ``top_ops`` time sinks. Empty split (zeros,
    ``devices=0``) when the backend emits no device events.

    ``devices`` is the measured PARTICIPANT count: the lanes that
    executed the program's collectives (per-device planes on real
    backends, per-executor-thread lines on XLA:CPU, which attributes no
    ``device_ordinal``).  ``lowered`` — the lowered
    program text, or a zero-arg callable producing it — arms the
    launch-counter fallback: on a build whose trace carries NO per-lane
    attribution at all, the participant count is derived as collective
    trace events over lowered collective launches
    (``bucketing.count_collectives``) instead of being misreported
    as 1.
    """
    d = tempfile.mkdtemp(prefix="jaxtrace_")
    try:
        jax.profiler.start_trace(d)
        try:
            out = thunk()
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        events = list(_iter_hlo_events(d))
        lanes, _all = _participant_lanes(events)
        per_dev: Dict[Any, list] = collections.defaultdict(lambda: [0.0, 0.0])
        top: collections.Counter = collections.Counter()
        for dev, name, _start, dur in events:
            if dev not in lanes:
                continue  # host-side fetch lane, not a participant
            per_dev[dev][1] += dur
            top[name] += dur
            if any(s in name.lower() for s in _COMM_SUBSTRINGS):
                per_dev[dev][0] += dur
        ndev = len(per_dev)
        if ndev == 1:
            est = _launch_derived_devices(events, lowered)
            if est > 1:
                # merged-lane trace: the interval sums cover every
                # participant already, so the launch-derived count is
                # both the honest ``devices`` and the mean denominator
                ndev = est
        scale = 1e9 * max(1, ndev)
        comm = sum(v[0] for v in per_dev.values()) / scale
        busy = sum(v[1] for v in per_dev.values()) / scale
        return out, {
            "devices": ndev,
            "device_busy_s": busy,
            "comm_s": comm,
            "compute_s": busy - comm,
            "top_ops": [
                (name, ns / 1e9) for name, ns in top.most_common(8)
            ],
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def device_memory_stats() -> Optional[dict]:
    """Per-device HBM stats where the backend exposes them."""
    try:
        dev = jax.devices()[0]
        return dev.memory_stats()
    except Exception:
        return None
