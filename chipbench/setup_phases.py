"""Set-up from the inside: what the program's own set-up log says the
seconds before the window were made of.

The program writes its set-up phases as spans (``setup.import``,
``setup.cache``, ``setup.state``, ``setup.step_build``,
``setup.first_step``; ``setup.worker`` / ``setup.serve`` with their
phases in the asynchronous job) and one row ``compile.program`` per
program the backend was asked for, into a log that is on from the
package's import (``telemetry.setup_rows()``), and into a process's
``worker-<n>.jsonl`` where that process dumps a recorder. ``rows`` gathers
them, ``one_run`` keeps those of this run (a rehearsal's process holds
several runs), and ``summary`` reduces them, printing ONE row ``"check":
"setup_phases"``. One reader a metric under ``layer_metrics/``
(``setup.*``, ``cache.hits``) takes its number from ``summary``.

A program that keeps no such log (the parent of the PR that brought this
file) gives no rows, and every reader None.
"""

from __future__ import annotations

import json
import os
import sys
import time

from chipbench.jobs.common import say
from chipbench.run import ROOT

PROGRAM = "compile.program"
# the rows the step's trace leaves: what the program planned
PLANS = ("attn.flash_tiles", "ssm.scan_plan", "moe.row_moves", "hc.plan",
         "ps.step_program")
# what opens the window when the job hands over no step span
LAST_OF_SETUP = ("setup.first_step", "setup.worker.first_push")
MAIN, WORKER = "main", "worker-0"


def mine(row: dict) -> bool:
    return row["name"].startswith(("setup.", PROGRAM)) or row["name"] in PLANS


def rows(cell: dict) -> dict:
    """``{process: (rows, dropped)}``: this process's set-up log, and
    the asynchronous job's first worker's from the file it dumped."""
    out = {}
    try:
        from pytorch_ps_mpi_tpu import telemetry

        out[MAIN] = (telemetry.setup_rows(), telemetry.setup_dropped())
    except (ImportError, AttributeError):  # a program without the log
        pass
    path = os.path.join(ROOT, ".chipbench_run", cell["name"], WORKER + ".jsonl")
    if os.path.exists(path):
        from pytorch_ps_mpi_tpu.telemetry.recorder import load_jsonl

        meta, events = load_jsonl(path)
        out[WORKER] = ([e for e in events if mine(e)],
                       meta.get("setup_dropped", 0))
    return out


def end(row: dict) -> float:
    return row["wall"] + row.get("dur", 0.0)


def window_opens(spans: dict, every: list):
    """Wall time the window opened at: the first step span the job
    handed over, else the end of the newest last phase of set-up."""
    steps = [e["wall"] for name in ("trainer.step", "worker.step")
             for e in spans.get(name, [])]
    if steps:
        return min(steps)
    last = [end(r) for r in every if r["name"] in LAST_OF_SETUP]
    return max(last) if last else None


def one_run(process_rows: list, opens: float) -> list:
    """Of one process's rows, this run's: from the newest ``setup.cache``
    that began before the window (every job calls
    ``enable_compilation_cache()`` first) to the window's opening, with
    the process's import rows put before them."""
    before = [r for r in process_rows if r["wall"] < opens]
    starts = [r["wall"] for r in before if r["name"] == "setup.cache"]
    if not starts:
        return []
    imports = [r for r in process_rows if r["name"].startswith("setup.import")]
    # (a span that began before the cache row and ended after it, as
    # setup.worker does, is this run's)
    run = [r for r in before
           if end(r) >= max(starts) and r not in imports]
    return sorted(imports, key=lambda r: r["wall"]) + sorted(
        run, key=lambda r: r["wall"])


def top_level(row: dict) -> bool:
    return row["name"].startswith("setup.") and row["name"].count(".") == 1


def step_program(run: list):
    """The ``compile.program`` row of the step program: the one named as
    the first ``setup.step_build`` says, asked for inside that span."""
    build = next((r for r in run if r["name"] == "setup.step_build"), None)
    if build is None:
        return None
    return next((r for r in run if r["name"] == PROGRAM
                 and r["attrs"]["program"] == build["attrs"].get("program")
                 and build["wall"] <= r["wall"] <= end(build)), None)


def covered(run: list, lo: float, hi: float) -> float:
    """Seconds of [lo, hi) that some row of ``run`` covers."""
    total, reach = 0.0, lo
    for a, b in sorted((max(r["wall"], lo), min(end(r), hi)) for r in run):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


def since_start(run: list, opens: float) -> dict:
    """How much of process start to window the main process's rows
    cover, where the harness is the command and took its reading of the
    start (``chipbench.run.T0`` of ``__main__``; a rehearsal has none)."""
    t0 = getattr(sys.modules.get("__main__"), "T0", None)
    if t0 is None:
        return {}
    started = time.time() - (time.perf_counter() - t0)
    return {"start_to_window_s": opens - started,
            "covered_s": covered(run, started, opens)}


_last = (None, None)  # (key, summary) of the newest run reduced


def summary(spans: dict, cell: dict):
    """What the metrics read, or None where there is no run: ``phases``
    (``{process: {name: [seconds, ...]}}``, every ``setup.*`` span),
    ``programs`` (every ``compile.program`` row before the window),
    ``step`` (the step program's), ``serve_wait_s``. Prints the check row
    the first time a run is reduced."""
    global _last
    if "config" not in cell:
        return None
    gathered = rows(cell)
    opens = window_opens(spans, [r for rs, _ in gathered.values() for r in rs])
    if opens is None:
        return None
    key = (cell["name"], opens)
    if _last[0] == key:
        return _last[1]
    runs = {p: one_run(rs, opens) for p, (rs, _) in gathered.items()}
    runs = {p: run for p, run in runs.items() if run}
    if not runs:
        return None
    phases = {p: {} for p in runs}
    for p, run in runs.items():
        for r in run:
            if r["name"].startswith("setup."):
                phases[p].setdefault(r["name"], []).append(r["dur"])
    programs = sorted(
        ({"process": p, **r["attrs"]} for p, run in runs.items()
         for r in run if r["name"] == PROGRAM),
        key=lambda a: -a["backend_s"])
    step = step_program(runs.get(MAIN, []))
    waits = [r["attrs"].get("wait_s") or 0.0 for r in runs.get(MAIN, [])
             if r["name"] == "setup.serve.first_update"]
    out = {"phases": phases, "programs": programs,
           "step": step and step["attrs"], "serve_wait_s": sum(waits)}
    say(check="setup_phases",
        top_level={p: [[r["name"], r["dur"]] for r in run if top_level(r)]
                   for p, run in runs.items()},
        phases=phases, step_program=out["step"],
        programs=[[a["process"], a["program"], a.get("trace_s"),
                   a.get("lower_s"), a["backend_s"], a["cache"]]
                  for a in programs],
        plans=[[r["name"], r.get("attrs", {})] for run in runs.values()
               for r in run if r["name"] in PLANS],
        # asked for in the window (step.compiles_in_window says how many:
        # here by name) or after it, by the harness's own checks
        later_programs=[[p, r["wall"] - opens, r["attrs"]["program"],
                         r["attrs"]["backend_s"], r["attrs"]["cache"]]
                        for p, (rs, _) in gathered.items() for r in rs
                        if r["name"] == PROGRAM and r["wall"] >= opens],
        # every row a process's log holds, and their bytes as JSON
        log={p: [len(rs), len(json.dumps(rs, default=str))]
             for p, (rs, _) in gathered.items()},
        dropped={p: d for p, (_, d) in gathered.items()},
        **since_start(runs.get(MAIN, []), opens))
    _last = (key, out)
    return out


def phase_s(spans: dict, cell: dict, name: str, process: str = MAIN):
    """Seconds inside the ``name`` spans of one process of the run."""
    s = summary(spans, cell)
    durs = s and s["phases"].get(process, {}).get(name)
    return sum(durs) if durs else None


def step_s(spans: dict, cell: dict, key: str):
    """``trace_s`` / ``lower_s`` / ``backend_s`` of the step program."""
    s = summary(spans, cell)
    return s["step"].get(key) if s and s["step"] else None
