"""One placement rule for JAX's persistent compilation cache, and the
one listener for what the backend is asked to compile.

Every process that compiles — the CLIs, ``chipbench``, ``chip_smoke.py``,
``__graft_entry__.py`` and the fleet's child processes — calls
:func:`enable_compilation_cache` before its first jit. The cache
directory is no program's argument: it is where
``JAX_COMPILATION_CACHE_DIR`` says when that variable is set (jax reads
it itself; nothing is set here, so an outside placement is never
overridden), and otherwise one fixed directory inside the checkout. A
path that moves between runs (``/tmp``, a pid, a timestamp) never hits.

From then on every program the backend is asked for is one row
``compile.program`` of the process's set-up log
(``telemetry.setup_rows()``): its name, the seconds of its trace, its
lowering and its compile (or its load from the cache), and whether the
cache hit.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict

from pytorch_ps_mpi_tpu.telemetry.recorder import (
    open_setup_span,
    setup_event,
    setup_span,
)

# <repo>/.jax_cache — listed in .gitignore
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"


class CompileCacheStats:
    """This process's persistent-cache directory and its hit / miss
    counts since :func:`enable_compilation_cache` (jax counts a miss
    when it writes a newly compiled program to the cache), with
    ``programs``, the number the backend was asked for since.

    The class is also the process's ONE listener to ``jax.monitoring``
    (:meth:`listen`), however many instances there are. jax reports a
    program in pieces, all on the thread that asked for it: the trace's
    and the lowering's duration under the function's and the module's
    name, then — inside the backend's span, without a name — a hit with
    its retrieval time and the compile time it saved, or a miss once the
    new program is written, then the backend's span itself. The pieces
    wait in ``_pending`` and the last one writes the row."""

    _totals = {"hits": 0, "misses": 0, "programs": 0}  # the process's
    _pending = threading.local()
    _listening = False
    _lock = threading.Lock()

    def __init__(self, directory: str) -> None:
        self.dir = directory
        self._since = dict(self._totals)

    def _counted(what: str) -> property:  # since this instance was made
        return property(lambda self: self._totals[what] - self._since[what])

    hits, misses, programs = map(_counted, ("hits", "misses", "programs"))

    def as_dict(self) -> Dict[str, Any]:
        return {"dir": self.dir, "hits": self.hits, "misses": self.misses,
                "programs": self.programs}

    # -- the listener ---------------------------------------------------------
    @classmethod
    def listen(cls) -> None:
        """Register with ``jax.monitoring``, once a process: jax offers
        no way to take a listener back."""
        import jax

        with cls._lock:
            if cls._listening:
                return
            cls._listening = True
        jax.monitoring.register_event_listener(cls._on_event)
        jax.monitoring.register_event_duration_secs_listener(cls._on_duration)
        jax.monitoring.register_event_time_span_listener(cls._on_span)

    @classmethod
    def _piece(cls) -> Dict[str, Any]:
        try:
            return cls._pending.piece
        except AttributeError:
            cls._pending.piece = {}
            return cls._pending.piece

    @classmethod
    def _on_event(cls, event: str, **_: Any) -> None:
        if event == _HIT:
            cls._piece()["cache"] = "hit"
        elif event == _MISS:
            cls._piece()["cache"] = "miss"

    @classmethod
    def _on_duration(cls, event: str, duration: float, **_: Any) -> None:
        if event == _RETRIEVAL:
            cls._piece()["retrieval_s"] = duration
        elif event == _SAVED:
            cls._piece()["saved_s"] = duration

    @classmethod
    def _on_span(cls, event: str, start: float, end: float,
                 fun_name: str = "", **_: Any) -> None:
        """``start`` and ``end`` are ``time.time()`` readings."""
        if event == _TRACE:
            cls._piece().setdefault("traced", {})[fun_name] = (start, end)
        elif event == _LOWER:
            cls._piece()["lowered"] = (fun_name, start, end)
        elif event == _BACKEND:
            piece = cls._piece()
            cls._pending.piece = {}
            cls._program(fun_name, start, end, piece)

    @classmethod
    def _program(cls, name: str, start: float, end: float,
                 piece: Dict[str, Any]) -> None:
        cache = piece.get("cache", "off")  # below jax's threshold, or disabled
        with cls._lock:
            cls._totals["programs"] += 1
            if cache == "hit":
                cls._totals["hits"] += 1
            elif cache == "miss":
                cls._totals["misses"] += 1
        attrs: Dict[str, Any] = {"program": name, "backend_s": end - start,
                                 "cache": cache}
        lowered = piece.get("lowered")
        if lowered is not None and lowered[0] == name:
            attrs["lower_s"] = lowered[2] - lowered[1]
            start = min(start, lowered[1])
        # the module is named after the outermost function traced for it:
        # "jit(f)" (a pmap's "pmap_f")
        fun = max((f for f in piece.get("traced", ())
                   if name.endswith((f"({f})", f"_{f}"))),
                  key=len, default=None)
        if fun is not None:
            traced = piece["traced"][fun]
            attrs["trace_s"] = traced[1] - traced[0]
            start = min(start, traced[0])
        for key in ("retrieval_s", "saved_s"):
            if key in piece:
                attrs[key] = piece[key]
        # on the row's clocks: the wall readings jax took, as monotonic
        ago = time.time() - start
        setup_event("compile.program", kind="span",
                    ts=time.monotonic() - ago, dur=end - start,
                    parent=open_setup_span(), **attrs)


def enable_compilation_cache() -> CompileCacheStats:
    """Turn the persistent cache on; returns the counters for this
    process (callers that report nothing may drop them)."""
    import jax

    with setup_span("setup.cache") as row:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        # cache every program, not only those over jax's 1 s default: a cold
        # process otherwise compiles its many sub-second programs again
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        CompileCacheStats.listen()
        stats = CompileCacheStats(jax.config.jax_compilation_cache_dir)
        row["dir"] = stats.dir
        row["entries"], row["bytes"] = _directory_size(stats.dir)
    return stats


def _directory_size(directory: str):
    """Entries and bytes of the cache directory as it is now: one
    ``os.scandir``, nothing opened."""
    try:
        with os.scandir(directory) as it:
            sizes = [e.stat().st_size for e in it if e.is_file()]
    except OSError:  # not there yet: a first run
        return 0, 0
    return len(sizes), sum(sizes)
