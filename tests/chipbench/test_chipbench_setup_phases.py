"""``chipbench/setup_phases.py``: the run's set-up rows cut out of a
process's log, on hand-made rows whose answers are known; the twelve
entries under ``setup_s`` and their readers; and the two counts a
rehearsal prints off the chip."""

import json
import os
import shutil

import pytest
from test_chipbench_rehearsal import ROOT, rehearsal_manifest, run_cell

from chipbench import setup_phases as sp
from chipbench.run import Manifest

NEW = ["setup.import_s", "setup.state_s", "setup.step_trace_s",
       "setup.step_lower_s", "setup.step_compile_s", "setup.first_step_s",
       "setup.compile_s", "setup.programs", "cache.hits", "setup.worker_s",
       "setup.worker_attach_s", "setup.serve_s"]
ASYNC = "resnet18-cifar.async1"


def span(name, wall, dur, parent=None, **attrs):
    row = {"name": name, "kind": "span", "ts": wall - 1000.0, "wall": wall,
           "dur": dur}
    if parent:
        row["parent"] = parent
    if attrs:
        row["attrs"] = attrs
    return row


def program(name, wall, backend_s, cache="hit", **attrs):
    return span("compile.program", wall, backend_s + 0.3, program=name,
                backend_s=backend_s, cache=cache, trace_s=0.1, lower_s=0.2,
                **attrs)


def a_process():
    """A rehearsal's process: an older run (100-160), then this one: cache
    at 200, state 201-203, the harness's ``jit(init)`` at 204, the first
    step 210-225 with the step's build 211-224 and in it ``jit(spmd)`` 212-223;
    another ``jit(spmd)`` (the reference's) at 230; the window opens at 300
    and a program is asked for inside it at 310."""
    return [
        span("setup.import.telemetry", 1.5, 0.5, parent="setup.import"),
        span("setup.import", 1.0, 3.0, jax_already_imported=True),
        span("setup.cache", 100.0, 0.01, dir="d"),
        span("setup.state", 101.0, 9.0),
        program("jit(spmd)", 110.0, 40.0, cache="miss"),
        span("setup.cache", 200.0, 0.01, dir="d"),
        span("setup.state", 201.0, 2.0, leaves=3),
        program("jit(init)", 204.0, 1.0, cache="miss"),
        program("jit(spmd)", 212.0, 10.7, retrieval_s=9.0, saved_s=20.0),
        span("setup.step_build", 211.0, 13.0, key="fused",
             program="jit(spmd)"),
        span("setup.first_step", 210.0, 15.0),
        {"name": "attn.flash_tiles", "kind": "event", "ts": 0.0, "wall": 212.5,
         "attrs": {"full": 1}},
        program("jit(spmd)", 230.0, 5.0, cache="off"),
        program("jit(late)", 310.0, 2.0, cache="miss"),
    ]


STEPS = {"trainer.step": [{"wall": 300.0, "dur": 0.04},
                          {"wall": 300.05, "dur": 0.04}]}


def test_one_run_is_cut_at_its_cache_row_and_at_the_window():
    rows = a_process()
    assert sp.window_opens(STEPS, rows) == 300.0
    run = sp.one_run(rows, 300.0)
    assert [r["name"] for r in run[:2]] == ["setup.import",
                                            "setup.import.telemetry"]
    assert all(200.0 <= r["wall"] < 300.0 for r in run[2:])
    assert [r["attrs"]["program"] for r in run
            if r["name"] == "compile.program"] == [
        "jit(init)", "jit(spmd)", "jit(spmd)"]
    assert sum(r["name"] == "setup.state" for r in run) == 1
    # no step span handed over: the newest last phase of set-up closes it
    assert sp.window_opens({}, rows) == 225.0
    assert sp.window_opens({"worker.grad": []}, []) is None
    # a process that never enabled its cache has no run to show
    assert sp.one_run([r for r in rows if r["name"] != "setup.cache"],
                      300.0) == []


def test_the_step_program_is_picked_by_name_inside_its_build():
    run = sp.one_run(a_process(), 300.0)
    step = sp.step_program(run)
    assert step["wall"] == 212.0 and step["attrs"]["retrieval_s"] == 9.0
    other = [dict(r, attrs=dict(r["attrs"], program="jit(accum)"))
             if r["name"] == "setup.step_build" else r for r in run]
    assert sp.step_program(other) is None
    assert sp.step_program([r for r in run
                            if r["name"] != "setup.step_build"]) is None


def test_covered_is_the_union_of_the_rows():
    run = sp.one_run(a_process(), 300.0)
    # 200-200.01, 201-203, 204-205.3, 210-225, 230-235.3 of 190-300
    assert sp.covered(run, 190.0, 300.0) == pytest.approx(
        0.01 + 2.0 + 1.3 + 15.0 + 5.3)
    assert sp.covered(run, 212.0, 214.0) == pytest.approx(2.0)
    assert sp.covered([], 0.0, 10.0) == 0.0


def test_the_start_is_the_commands_own_reading(monkeypatch):
    import sys
    import time

    run = sp.one_run(a_process(), 300.0)
    assert sp.since_start(run, 300.0) == {}  # pytest is no chipbench.run
    now = time.time()
    monkeypatch.setattr(sys.modules["__main__"], "T0",
                        time.perf_counter() - 50.0, raising=False)
    got = sp.since_start([dict(r, wall=r["wall"] + now - 240.0) for r in run],
                         now - 50.0 + 110.0)  # the window opens at "300"
    assert got["start_to_window_s"] == pytest.approx(110.0, abs=0.05)
    # of 190-300: cache, state, init, the first step, the reference's spmd
    assert got["covered_s"] == pytest.approx(23.61, abs=0.05)


@pytest.fixture
def a_run(monkeypatch):
    """``a_process`` as this process's log, and a worker's file beside it."""
    cell = {"name": "setup-phases-selftest", "config": {}}
    scratch = os.path.join(ROOT, ".chipbench_run", cell["name"])
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    worker = [
        {"kind": "recorder_meta", "setup_dropped": 2, "worker": 0},
        span("setup.import", 150.0, 4.0, jax_already_imported=False),
        span("setup.cache", 202.0, 0.01),
        span("setup.worker.attach", 203.0, 7.0, parent="setup.worker"),
        program("jit(loss_fn)", 215.0, 6.0, cache="miss"),
        span("setup.worker.first_push", 222.0, 1.0, parent="setup.worker"),
        span("setup.worker", 202.0, 21.0, platform="tpu"),
        span("worker.step", 300.0, 0.2),
        span("wire.send", 300.1, 0.01),
    ]
    with open(os.path.join(scratch, "worker-0.jsonl"), "w") as f:
        f.write("\n".join(json.dumps(r) for r in worker))
    main = a_process() + [
        span("setup.serve.first_update", 240.0, 30.0, parent="setup.serve",
             wait_s=25.0),
        span("setup.serve", 205.0, 65.0, workers=1)]
    from pytorch_ps_mpi_tpu import telemetry

    monkeypatch.setattr(telemetry, "setup_rows", lambda: main)
    monkeypatch.setattr(telemetry, "setup_dropped", lambda: 0)
    monkeypatch.setattr(sp, "_last", (None, None))
    yield cell
    shutil.rmtree(scratch, ignore_errors=True)


def test_summary_of_two_processes_and_its_one_check_row(a_run, capfd):
    spans = dict(STEPS, **{"worker.step": [{"wall": 300.0, "dur": 0.2}]})
    s = sp.summary(spans, a_run)
    assert sp.summary(spans, a_run) is s  # reduced once
    rows = [json.loads(l) for l in capfd.readouterr().out.splitlines()]
    (check,) = [r for r in rows if r.get("check") == "setup_phases"]
    assert s["phases"]["main"]["setup.state"] == [2.0]
    assert s["phases"]["worker-0"]["setup.worker"] == [21.0]
    assert [p["program"] for p in s["programs"]] == [
        "jit(spmd)", "jit(loss_fn)", "jit(spmd)", "jit(init)"]  # by backend_s
    assert [p["process"] for p in s["programs"]] == [
        "main", "worker-0", "main", "main"]
    assert s["step"]["backend_s"] == 10.7 and s["serve_wait_s"] == 25.0
    assert check["top_level"]["main"] == [
        ["setup.import", 3.0], ["setup.cache", 0.01], ["setup.state", 2.0],
        ["setup.serve", 65.0], ["setup.first_step", 15.0],
        ["setup.step_build", 13.0]]
    assert check["top_level"]["worker-0"] == [
        ["setup.import", 4.0], ["setup.cache", 0.01], ["setup.worker", 21.0]]
    assert check["programs"][0] == ["main", "jit(spmd)", 0.1, 0.2, 10.7, "hit"]
    assert len(check["programs"]) == 4
    assert check["plans"] == [["attn.flash_tiles", {"full": 1}]]
    assert check["later_programs"] == [["main", 10.0, "jit(late)", 2.0, "miss"]]
    assert check["dropped"] == {"main": 0, "worker-0": 2}
    assert check["step_program"]["cache"] == "hit"
    # the twelve, through their readers
    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    got = {m: man.reader(m)(None, spans, {}, a_run) for m in NEW}
    assert got == {
        "setup.import_s": 3.0, "setup.state_s": 2.0,
        "setup.step_trace_s": 0.1, "setup.step_lower_s": 0.2,
        "setup.step_compile_s": 10.7, "setup.first_step_s": 15.0,
        "setup.compile_s": pytest.approx(1.0 + 10.7 + 5.0 + 6.0),
        "setup.programs": 4, "cache.hits": 1, "setup.worker_s": 21.0,
        "setup.worker_attach_s": 7.0, "setup.serve_s": 40.0}


def test_nothing_to_read_is_none(a_run, monkeypatch):
    assert sp.summary(STEPS, {"name": a_run["name"]}) is None  # no run at all
    from pytorch_ps_mpi_tpu import telemetry

    # a program that keeps no log, and a run that left no file
    monkeypatch.delattr(telemetry, "setup_rows")
    shutil.rmtree(os.path.join(ROOT, ".chipbench_run", a_run["name"]))
    assert sp.rows(a_run) == {}
    assert sp.summary(STEPS, a_run) is None
    assert sp.phase_s(STEPS, a_run, "setup.state") is None
    assert sp.step_s(STEPS, a_run, "backend_s") is None


def test_the_twelve_entries_are_appended_with_their_cells_written_out():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entries = doc["per_layer"][-12:]
    assert [m["name"] for m in entries] == NEW
    cells = [c["name"] for c in doc["workloads"]]
    sync = [c for c in cells if c != ASYNC]
    for m in entries:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] == "setup_s"
        counts = m["name"] in ("setup.programs", "cache.hits")
        assert m["unit"] == ("count" if counts else "s")
        assert m["source"] == ("program_counter" if counts else "program_span")
        assert m["layer"] == ("compile cache" if m["name"] == "cache.hits"
                              else "set-up")
        assert m["better"] == ("higher" if m["name"] == "cache.hits"
                               else "lower")
        want = ([ASYNC] if m["name"] in NEW[-3:] else
                sync if m["name"] in NEW[1:6] else cells)
        assert sorted(m["workloads"]) == sorted(want), m["name"]


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_reads_nothing_of_a_cell_without_a_run(metric):
    read = Manifest(os.path.join(ROOT, "BENCHMARK.json")).reader(metric)
    assert read(None, {}, {}, {"name": "no-such-run"}) is None
    assert read({"steps": 3}, STEPS, {}, {"name": "no-such-run"}) is None


@pytest.mark.parametrize("cell", ["tiny-gpt.lm", "tiny-resnet.async"])
def test_a_rehearsal_prints_one_runs_counts(cell, tmp_path, capfd):
    manifest, _ = rehearsal_manifest(str(tmp_path))
    line, earlier = run_cell(capfd, manifest, cell, trace=1)
    (check,) = [r for r in earlier if r.get("check") == "setup_phases"]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # off the chip: the two counts, and no time
    assert not [k for k in metrics if k.startswith("setup.") and
                k != "setup.programs"]
    assert metrics["setup.programs"] == len(check["programs"]) > 0
    assert (metrics["cache.hits"] + metrics["cache.misses"]
            <= metrics["setup.programs"])
    assert metrics["cache.hits"] == sum(p[5] == "hit"
                                        for p in check["programs"])
    top = [name for name, _ in check["top_level"]["main"]]
    assert top.count("setup.cache") == 1  # this run's, not the process's
    if cell.endswith("async"):
        assert "setup.serve" in top
        assert "setup.worker" in [n for n, _ in check["top_level"]["worker-0"]]
        assert {p[0] for p in check["programs"]} == {"main", "worker-0"}
    else:
        assert {"setup.state", "setup.first_step", "setup.step_build"} <= set(top)
        assert check["step_program"]["program"] == "jit(spmd)"
