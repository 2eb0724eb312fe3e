"""FlightRecorder: bounded, thread-safe structured event/span log.

Every record is one flat dict (the JSONL row):

``name``       event/span name (``"ps.step"``, ``"worker.push_grad"``)
``kind``       ``"span"`` (has ``dur``) or ``"event"`` (a point)
``ts``         seconds, ``time.monotonic()`` — ordering/duration truth
               within one process
``wall``       seconds, ``time.time()`` — the cross-process alignment
               hint (monotonic epochs differ between processes)
``dur``        span duration in seconds (spans only)
``worker``     worker id (recorder default, overridable per record)
``step``       training/serve step the record belongs to
``staleness``  gradient staleness, when the record is about one gradient
``parent``     name of the span that was open on the recording thread when
               this one began (module-level :func:`span` only)
``attrs``      everything else (free-form, JSON-serializable)

The buffer is a ``deque(maxlen=capacity)``: recording never blocks on
I/O and never grows without bound — old records are evicted and counted
in ``dropped`` (surfaced in the JSONL header row so a truncated recording
is never mistaken for a complete one).

A process-global recorder is installed with :func:`configure`; call
sites guard on :func:`get_recorder` returning ``None`` — the disabled
cost is one module attribute read, which is what lets the recorder ride
inside every training mode unconditionally.

The hot paths (``Trainer.fit``, ``MPI_PS.step``, ``worker_main``, the
transports' ``push_grad``) open their spans with the module-level
:func:`span`: besides the row it enters a ``jax.profiler`` annotation of
the same name, so that under a profiler session the span also sits on
the ``/host:CPU`` plane of the device trace, on the profiler's clock.

Set-up — imports, placing the state, building and loading programs —
is over before any caller can have configured a recorder. Its rows go
to the process's **set-up log** (:func:`setup_span`,
:func:`setup_event`, read with :func:`setup_rows`): a recorder of 4,096
rows of its own that is always on, and whose rows a recorder takes over
when :func:`configure` or :func:`install` installs it, so that a
``dump_jsonl`` carries the way to the first step in front of the steps.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

import jax

_HEADER_KIND = "recorder_meta"


class FlightRecorder:
    """Bounded thread-safe event/span log with JSONL export."""

    def __init__(self, capacity: int = 65536,
                 worker: Optional[Any] = None) -> None:
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = int(capacity)
        self.worker = worker
        self._events: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.dropped = 0
        self.written = 0  # rows ever appended here; never reset
        self._t0_monotonic = time.monotonic()
        self._t0_wall = time.time()
        # of the process's set-up log: how many of its rows this recorder
        # has been handed, and how many it had evicted by then
        self._setup_seen = 0
        self.setup_dropped = 0

    # -- recording --------------------------------------------------------
    def event(
        self,
        name: str,
        *,
        kind: str = "event",
        ts: Optional[float] = None,
        dur: Optional[float] = None,
        step: Optional[int] = None,
        worker: Optional[Any] = None,
        staleness: Optional[int] = None,
        parent: Optional[str] = None,
        **attrs: Any,
    ) -> None:
        """Append one record. ``ts`` defaults to now (monotonic); pass an
        explicit start time (also ``time.monotonic()``-based) when the
        duration was measured by the caller."""
        now_m = time.monotonic()
        rec: Dict[str, Any] = {
            "name": name,
            "kind": kind,
            "ts": now_m if ts is None else float(ts),
            # wall derived from the same instant so the two clocks in one
            # record always describe the same moment
            "wall": self._t0_wall + ((ts if ts is not None else now_m)
                                     - self._t0_monotonic),
        }
        if dur is not None:
            rec["dur"] = float(dur)
        if step is not None:
            rec["step"] = int(step)
        w = worker if worker is not None else self.worker
        if w is not None:
            rec["worker"] = w
        if staleness is not None:
            rec["staleness"] = int(staleness)
        if parent is not None:
            rec["parent"] = parent
        if attrs:
            rec["attrs"] = attrs
        self._append(rec)

    def _append(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(rec)
            self.written += 1

    @contextlib.contextmanager
    def span(self, name: str, *, step: Optional[int] = None,
             worker: Optional[Any] = None, **attrs: Any) -> Iterator[None]:
        """Context manager recording a ``kind="span"`` row on exit with
        the measured duration (exceptions still record the span)."""
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.event(name, kind="span", ts=t0,
                       dur=time.monotonic() - t0, step=step, worker=worker,
                       **attrs)

    # -- reading ----------------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    # -- JSONL ------------------------------------------------------------
    def dump_jsonl(self, path: str) -> str:
        """Write the buffer to ``path`` as JSONL: one meta header row
        (kind ``recorder_meta`` — capacity, dropped count, clock epochs)
        then one row per record. Returns ``path``."""
        rows = self.events()
        header = {
            "kind": _HEADER_KIND,
            "capacity": self.capacity,
            "dropped": self.dropped,
            "n_events": len(rows),
            "worker": self.worker,
            "setup_dropped": self.setup_dropped,
            "t0_monotonic": self._t0_monotonic,
            "t0_wall": self._t0_wall,
        }
        with open(path, "w") as f:
            f.write(json.dumps(header) + "\n")
            for rec in rows:
                f.write(json.dumps(rec, default=_json_default) + "\n")
        return path


def _json_default(obj: Any) -> Any:
    """Last-resort serializer: numpy scalars/arrays and anything else a
    call site stuffed into attrs degrade to floats/strings, never crash
    the export."""
    try:
        import numpy as np

        if isinstance(obj, np.ndarray):
            return obj.tolist()
        if isinstance(obj, np.generic):
            return obj.item()
    except Exception:
        pass
    return str(obj)


def load_jsonl(path: str):
    """Read a recorder JSONL back: returns ``(meta, events)`` where
    ``meta`` is the header row (``{}`` for a headerless file) and
    ``events`` the record list — the inverse of
    :meth:`FlightRecorder.dump_jsonl`."""
    meta: Dict[str, Any] = {}
    events: List[Dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if rec.get("kind") == _HEADER_KIND and not events and not meta:
                meta = rec
            else:
                events.append(rec)
    return meta, events


# -- process-global recorder ------------------------------------------------

_recorder: Optional[FlightRecorder] = None

SETUP_LOG_ROWS = 4096


class _SetupLog(FlightRecorder):
    """The process's set-up log: a ring of ``SETUP_LOG_ROWS`` rows that
    is written whether or not a recorder is installed (a training
    process asks for few programs after its set-up, a pytest worker for
    thousands: the newest rows are kept, the evicted counted in
    ``dropped``). A row written while a recorder is on goes to it too."""

    def event(self, name: str, *, ts: Optional[float] = None,
              **kw: Any) -> None:
        ts = time.monotonic() if ts is None else ts
        super().event(name, ts=ts, **kw)
        rec = _recorder
        if rec is not None:
            rec.event(name, ts=ts, **kw)
            rec._setup_seen = self.written

    def hand_over(self, rec: FlightRecorder) -> None:
        """Copy into ``rec`` the rows it has not been handed yet (all
        that are held, for a new recorder; none twice)."""
        with self._lock:
            rows, written = list(self._events), self.written
            rec.setup_dropped = self.dropped
        unseen = written - rec._setup_seen
        rec._setup_seen = written
        for row in rows[len(rows) - min(unseen, len(rows)):]:
            if rec.worker is not None and "worker" not in row:
                row = dict(row, worker=rec.worker)
            rec._append(row)


_setup_log = _SetupLog(capacity=SETUP_LOG_ROWS)
setup_event = _setup_log.event  # one point row: setup_event(name, **attrs)
setup_rows = _setup_log.events


def setup_dropped() -> int:
    """Rows the set-up log has evicted: 0 in a process that trains."""
    return _setup_log.dropped


def configure(capacity: int = 65536,
              worker: Optional[Any] = None) -> FlightRecorder:
    """Install (and return) the process-global recorder. Call sites all
    over the codebase pick it up via :func:`get_recorder`. It starts with
    the rows of the process's set-up log."""
    return install(FlightRecorder(capacity=capacity, worker=worker))


def install(recorder: FlightRecorder) -> FlightRecorder:
    """Install an existing recorder as the process-global one — the
    re-enable path (``disable()`` then ``install(rec)`` pauses and
    resumes one buffer without discarding it, unlike ``configure``
    which starts fresh). The recorder is handed the set-up rows written
    since it was last on."""
    global _recorder
    _setup_log.hand_over(recorder)
    _recorder = recorder
    return recorder


def disable() -> None:
    """Remove the process-global recorder; instrumented paths return to
    their zero-cost guard."""
    global _recorder
    _recorder = None


def get_recorder() -> Optional[FlightRecorder]:
    """The process-global recorder, or None when telemetry is disabled —
    the one branch every instrumented hot path pays."""
    return _recorder


def record_event(name: str, **kw: Any) -> None:
    """Module-level convenience: record on the global recorder, no-op
    when disabled."""
    rec = _recorder
    if rec is not None:
        rec.event(name, **kw)


# the spans open on each thread, innermost last
_open_spans = threading.local()
# the per-step parents: a StepTraceAnnotation, so the trace knows the step
_STEP_SPANS = frozenset(("trainer.step", "worker.step"))


class _NoSpan:
    """What :func:`span` hands out while the recorder is off."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc: Any) -> None:
        return None


_NO_SPAN = _NoSpan()


class _Span:
    """One open span: a ``jax.profiler`` annotation around the body, one
    row on exit, in the global recorder or (:func:`setup_span`) in the
    set-up log. Set-up spans keep a stack of their own beside the hot
    paths': they lie under whatever is open, and no hot-path span ever
    has one for its parent."""

    __slots__ = ("rec", "name", "step", "attrs", "parent", "stack",
                 "annotation", "t0")

    def __init__(self, rec: FlightRecorder, name: str, step: Optional[int],
                 attrs: Dict[str, Any], parent: Optional[str] = None) -> None:
        self.rec, self.name, self.step, self.attrs = rec, name, step, attrs
        self.parent = parent

    def __enter__(self) -> Dict[str, Any]:
        try:
            stack = _open_spans.stack
        except AttributeError:
            stack = _open_spans.stack = []
        under = stack
        if self.rec is _setup_log:
            try:
                stack = _open_spans.setup
            except AttributeError:
                stack = _open_spans.setup = []
            under = stack or under
        self.stack = stack
        if under:
            if self.parent is None:  # a phase of SetupPhases names its own
                self.parent = under[-1].name
            if self.step is None:
                self.step = under[-1].step
        if _recorder is None:  # a set-up span: nobody traces set-up today
            self.annotation = _NO_SPAN
        elif self.name in _STEP_SPANS and self.step is not None:
            self.annotation = jax.profiler.StepTraceAnnotation(
                self.name, step_num=self.step)
        else:
            self.annotation = jax.profiler.TraceAnnotation(self.name)
        stack.append(self)
        self.t0 = time.monotonic()
        self.annotation.__enter__()
        return self.attrs

    def __exit__(self, *exc: Any) -> None:
        self.annotation.__exit__(*exc)
        dur = time.monotonic() - self.t0
        self.stack.pop()
        self.rec.event(self.name, kind="span", ts=self.t0, dur=dur,
                       step=self.step, parent=self.parent, **self.attrs)


def span(name: str, *, step: Optional[int] = None, **attrs: Any):
    """The one span primitive of the hot paths, on two clocks.

    Recorder off: a shared do-nothing context (``as`` gives None), no
    annotation object made. Recorder on: a ``jax.profiler`` annotation
    named ``name`` is open around the body — on the device trace's clock
    whenever a profiler session runs — and on exit (also when the body
    raises) one ``kind="span"`` row is recorded with ``parent``, the span
    open on this thread when this one began, and with ``step`` inherited
    from it when not given. ``as`` gives the row's attribute dict, to
    which the body may add what is known only at the end (``loss``, a
    step's ``data``)."""
    rec = _recorder
    if rec is None:
        return _NO_SPAN
    return _Span(rec, name, step, attrs)


# -- set-up: the way to the first step ---------------------------------------

def setup_span(name: str, **attrs: Any) -> _Span:
    """A span of set-up (``setup.cache``, ``setup.step_build``, ...):
    :func:`span`'s class with the set-up log for its sink, so the row is
    written with the recorder off, also when the body raises, with the
    span open on this thread for its ``parent``. Never on a path that
    runs once a step: it makes an object and a row every time."""
    return _Span(_setup_log, name, None, attrs)


def open_setup_span() -> Optional[str]:
    """Name of the innermost set-up span open on this thread."""
    stack = getattr(_open_spans, "setup", None)
    return stack[-1].name if stack else None


class SetupPhases:
    """``setup.<what>``: a process's way to its first unit of work, open
    from construction to :meth:`done`, which code between two iterations
    of a loop calls and no ``with`` block can hold. ``phase(name)`` is
    a set-up span ``setup.<what>.<name>`` under it until then, and the
    do-nothing context afterwards, so a loop may keep the call."""

    def __init__(self, what: str, **attrs: Any) -> None:
        self.name = f"setup.{what}"
        self.attrs = attrs
        self.open = True
        self.t0 = time.monotonic()

    def phase(self, name: str, **attrs: Any):
        if not self.open:
            return _NO_SPAN
        return _Span(_setup_log, f"{self.name}.{name}", None, attrs,
                     parent=self.name)

    def done(self) -> None:
        """Write the row, once: what comes after is no set-up."""
        if self.open:
            self.open = False
            setup_event(self.name, kind="span", ts=self.t0,
                        dur=time.monotonic() - self.t0, **self.attrs)
