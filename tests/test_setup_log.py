"""The set-up log: the recorder's rows before any recorder can be on —
the ring itself, its hand-over to a recorder, ``setup_span``, the rows
``MPI_PS`` / ``Trainer.fit`` / ``worker_main`` / ``serve`` write on
their way to the first step, one ``compile.program`` row per program the
backend is asked for, and the five plan rows of the step's trace."""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from pytorch_ps_mpi_tpu import SGD, telemetry
from pytorch_ps_mpi_tpu.telemetry import recorder
from pytorch_ps_mpi_tpu.trainer import Trainer
from pytorch_ps_mpi_tpu.utils import compile_cache
from pytorch_ps_mpi_tpu.utils.compile_cache import CompileCacheStats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def recorder_off():
    """Whatever an earlier file's test left installed in this worker."""
    telemetry.disable()
    yield
    telemetry.disable()


def names(rows):
    return [r["name"] for r in rows]


def named(name, rows=None):
    rows = telemetry.setup_rows() if rows is None else rows
    return [r for r in rows if r["name"] == name]


# -- the ring and its hand-over -----------------------------------------------

def test_the_log_keeps_rows_with_no_recorder_installed():
    assert telemetry.get_recorder() is None
    telemetry.setup_event("setup.test", answer=42)
    with telemetry.setup_span("setup.test_span", why="because") as attrs:
        attrs["late"] = 1
    event, span = telemetry.setup_rows()
    assert (event["name"], event["kind"], event["attrs"]) == (
        "setup.test", "event", {"answer": 42})
    assert (span["name"], span["kind"]) == ("setup.test_span", "span")
    assert span["attrs"] == {"why": "because", "late": 1} and span["dur"] >= 0
    # the recorder's row format and clocks
    assert {"name", "kind", "ts", "wall"} <= set(event) and "parent" not in span
    assert telemetry.get_recorder() is None and telemetry.setup_dropped() == 0


def test_the_log_holds_4096_rows_and_counts_the_rest():
    assert recorder._setup_log.capacity == recorder.SETUP_LOG_ROWS == 4096
    log = recorder._SetupLog(capacity=recorder.SETUP_LOG_ROWS)
    for i in range(4096 + 5):
        log.event("compile.program", program=str(i))
    rows = log.events()
    assert len(rows) == 4096 and log.dropped == 5 and log.written == 4101
    assert rows[0]["attrs"]["program"] == "5"  # the newest are kept
    rec = telemetry.FlightRecorder(capacity=8192)
    log.hand_over(rec)
    assert len(rec) == 4096 and rec.setup_dropped == 5 and rec.dropped == 0


def test_configure_takes_the_rows_over_once(tmp_path):
    telemetry.setup_event("setup.before", n=1)
    try:
        first = telemetry.configure(worker=3)
        assert names(first.events()) == ["setup.before"]
        assert first.events()[0]["worker"] == 3  # stamped as its own
        assert "worker" not in telemetry.setup_rows()[0]
        # written while a recorder is on: in the log and in the recorder
        telemetry.setup_event("setup.during")
        assert names(first.events()) == ["setup.before", "setup.during"]
        second = telemetry.configure()  # a new recorder: all, not doubled
        assert names(second.events()) == ["setup.before", "setup.during"]
        telemetry.disable()
        telemetry.setup_event("setup.while_off")
        telemetry.install(second)  # resumed: only what it has not seen
        telemetry.install(second)
        assert names(second.events()) == ["setup.before", "setup.during",
                                          "setup.while_off"]
        assert names(first.events()) == ["setup.before", "setup.during"]
        path = second.dump_jsonl(str(tmp_path / "server.jsonl"))
    finally:
        telemetry.disable()
    meta, rows = telemetry.load_jsonl(path)
    assert meta["setup_dropped"] == 0 and len(rows) == 3
    assert names(telemetry.setup_rows()) == names(rows)


def test_the_report_shows_the_set_up_of_a_dumped_recorder(tmp_path):
    from tools.telemetry_report import summarize

    with telemetry.setup_span("setup.cache", dir="x"):
        pass
    try:
        path = telemetry.configure().dump_jsonl(str(tmp_path / "server.jsonl"))
    finally:
        telemetry.disable()
    (row,) = summarize([path])["spans"]
    assert row["name"] == "setup.cache" and row["count"] == 1


# -- setup_span ---------------------------------------------------------------

def test_a_setup_span_nests_under_the_threads_open_span(annotations_made):
    with telemetry.setup_span("setup.outer"):
        with telemetry.setup_span("setup.inner"):
            assert recorder.open_setup_span() == "setup.inner"
    assert recorder.open_setup_span() is None
    inner, outer = telemetry.setup_rows()
    assert inner["parent"] == "setup.outer" and "parent" not in outer
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-9
    assert annotations_made == []  # no recorder: nobody traces set-up
    rec = telemetry.configure()
    try:
        with telemetry.span("trainer.step", step=7):
            with telemetry.setup_span("setup.late"):
                with telemetry.span("ps.step"):
                    pass
    finally:
        telemetry.disable()
    rows = {e["name"]: e for e in rec.events()}
    # under the hot path's open span, with its step; and no hot-path span
    # ever has a set-up span for its parent
    assert rows["setup.late"]["parent"] == "trainer.step"
    assert rows["setup.late"]["step"] == 7
    assert rows["ps.step"]["parent"] == "trainer.step"
    assert sorted(n for _, n, _ in annotations_made) == [
        "ps.step", "setup.late", "trainer.step"]


def test_a_setup_span_records_when_its_body_raises():
    with pytest.raises(KeyError):
        with telemetry.setup_span("setup.cache", dir="d"):
            raise KeyError("boom")
    (row,) = telemetry.setup_rows()
    assert row["name"] == "setup.cache" and row["attrs"] == {"dir": "d"}
    assert recorder.open_setup_span() is None  # the stack unwound


def test_spans_of_two_threads_do_not_nest():
    seen = {}

    def other():
        with telemetry.setup_span("setup.there"):
            seen["there"] = recorder.open_setup_span()

    with telemetry.setup_span("setup.here"):
        t = threading.Thread(target=other)
        t.start()
        t.join(10)
        assert not t.is_alive()
    assert seen == {"there": "setup.there"}
    assert all("parent" not in r for r in telemetry.setup_rows())


def test_setup_phases_close_once_and_then_do_nothing():
    starting = telemetry.SetupPhases("worker", worker=1)
    with starting.phase("attach") as attrs:
        attrs["platform"] = "cpu"
    starting.attrs["platform"] = "cpu"
    starting.done()
    assert not starting.open
    assert starting.phase("first_read") is recorder._NO_SPAN
    starting.done()  # the worker's ``finally``: nothing twice
    attach, whole = telemetry.setup_rows()
    assert (attach["name"], attach["parent"]) == ("setup.worker.attach",
                                                  "setup.worker")
    assert whole["name"] == "setup.worker" and whole["kind"] == "span"
    assert whole["worker"] == 1 and whole["attrs"] == {"platform": "cpu"}
    assert whole["ts"] <= attach["ts"]
    assert attach["ts"] + attach["dur"] <= whole["ts"] + whole["dur"] + 1e-9


def test_the_package_wrote_its_import_rows_first():
    """The conftest's fixture has cleared this process's: a new process
    shows them."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, sys; import pytorch_ps_mpi_tpu as p; "
         "from pytorch_ps_mpi_tpu import telemetry; "
         "print(json.dumps(telemetry.setup_rows()))"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, check=True)
    child, whole = json.loads(out.stdout.splitlines()[-1])
    assert (child["name"], child["parent"]) == ("setup.import.telemetry",
                                                "setup.import")
    assert whole["name"] == "setup.import" and "parent" not in whole
    assert whole["attrs"] == {"jax_already_imported": False}
    assert whole["ts"] <= child["ts"] and 0 < child["dur"] < whole["dur"]


# -- the synchronous path's rows ----------------------------------------------

def quad_loss(p, b):
    return jnp.mean((b @ p["w"]) ** 2)


def batches():
    while True:
        yield jnp.ones((8, 4))


@pytest.fixture
def listening():
    CompileCacheStats.listen()


def test_fit_yields_one_row_a_phase_and_the_step_programs_row(listening):
    # a loss no other test compiles: this process must trace and build it
    loss = lambda p, b: quad_loss(p, b) * 1.2345
    data = batches()
    t = Trainer(SGD({"w": jnp.ones((4, 2))}, lr=0.1, average=True), loss)
    t.fit(data, 2)
    rows = telemetry.setup_rows()
    (state,) = named("setup.state", rows)
    (build,) = named("setup.step_build", rows)
    (first,) = named("setup.first_step", rows)
    assert state["attrs"] == {"leaves": 1, "param_bytes": 32,
                              "state_bytes": 36,
                              "devices": len(jax.devices()),
                              "mode": "allgather"}
    assert build["attrs"] == {"key": "fused", "program": "jit(spmd)"}
    assert first["step"] == 1 and "parent" not in first
    end = lambda r: r["ts"] + r["dur"]
    assert end(state) <= first["ts"] <= build["ts"]
    assert end(build) <= end(first) + 1e-9
    (program,) = [r for r in named("compile.program", rows)
                  if r["attrs"]["program"] == "jit(spmd)"]
    assert program["parent"] == "setup.step_build"
    assert build["ts"] <= program["ts"] and end(program) <= end(build) + 1e-3
    a = program["attrs"]
    assert a["trace_s"] > 0 and a["lower_s"] > 0 and a["backend_s"] > 0
    assert a["trace_s"] + a["lower_s"] + a["backend_s"] <= build["dur"]
    assert a["cache"] in ("off", "hit", "miss")
    # a second fit, and fifty further steps, add no row at all
    before = len(telemetry.setup_rows())
    t.fit(data, 2)
    t.fit(data, 50)
    for _ in range(3):
        t.opt.step(loss_fn=loss, batch=next(data))
    assert len(telemetry.setup_rows()) == before


@pytest.mark.parametrize("path, key", [("grads", "grads"),
                                       ("accum", "accum")])
def test_the_other_step_paths_build_under_the_same_span(path, key, mesh8,
                                                        listening):
    opt = SGD({"w": jnp.ones((4, 2))}, mesh=mesh8, lr=0.1, average=True)
    if path == "grads":
        step = lambda: opt.step(grads={"w": jnp.ones((8, 4, 2))})
    else:
        step = lambda: opt.step_accumulate(quad_loss, jnp.ones((2, 8, 4)))
    step()
    (build,) = named("setup.step_build")
    assert build["attrs"] == {"key": key, "program": "jit(spmd)"}
    step()
    assert len(named("setup.step_build")) == 1


def test_a_step_that_fails_to_trace_still_closes_its_span():
    opt = SGD({"w": jnp.ones((4, 2))}, lr=0.1)

    def bad(p, b):
        raise RuntimeError("no loss")

    with pytest.raises(RuntimeError, match="no loss"):
        opt.step(loss_fn=bad, batch=jnp.ones((8, 4)))
    (build,) = named("setup.step_build")
    assert build["attrs"]["key"] == "fused"
    assert recorder.open_setup_span() is None


# -- the listener -------------------------------------------------------------

def test_the_listeners_are_registered_once_a_process(monkeypatch, tmp_path):
    from jax._src import monitoring

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    old = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    count = lambda: (len(monitoring.get_event_listeners()),
                     len(monitoring.get_event_duration_listeners()),
                     len(monitoring.get_event_time_span_listeners()))
    try:
        # jax reads the variable as it is imported: say it again
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        CompileCacheStats.listen()
        before = count()
        first = compile_cache.enable_compilation_cache()
        jax.jit(lambda x: x * 3.25 + 1)(jnp.ones(3)).block_until_ready()
        second = compile_cache.enable_compilation_cache()
        assert count() == before
        # each counts from its own call
        assert first.programs >= 1 and second.programs == 0
        assert first.hits + first.misses <= first.programs
        jax.jit(lambda x: x * 4.75 - 1)(jnp.ones(3)).block_until_ready()
        assert second.programs >= 1
        assert first.programs >= second.programs + 1
        assert set(second.as_dict()) == {"dir", "hits", "misses", "programs"}
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
        # jax opens its cache once a process: not at a directory that goes
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    cache = named("setup.cache")
    assert len(cache) == 2
    assert cache[0]["attrs"]["dir"] == str(tmp_path)
    assert cache[0]["attrs"]["entries"] == 0 and cache[0]["attrs"]["bytes"] == 0


CACHE_CHILD = """
import json, sys
import jax, jax.numpy as jnp
from pytorch_ps_mpi_tpu import telemetry
from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache
stats = enable_compilation_cache()
@jax.jit
def set_up_log_probe(x):
    return jnp.sin(x) @ x.T + 0.125
set_up_log_probe(jnp.ones((16, 16))).block_until_ready()
row = [r for r in telemetry.setup_rows() if r["name"] == "compile.program"
       and r["attrs"]["program"] == "jit(set_up_log_probe)"]
print(json.dumps({"rows": row, "stats": stats.as_dict(), "cache": [
    r["attrs"] for r in telemetry.setup_rows() if r["name"] == "setup.cache"]}))
"""


def test_a_second_process_finds_the_program_in_the_cache(tmp_path):
    def child():
        out = subprocess.run(
            [sys.executable, "-c", CACHE_CHILD], cwd=ROOT,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
            capture_output=True, text=True, timeout=120, check=True)
        return json.loads(out.stdout.splitlines()[-1])

    cold, warm = child(), child()
    (row,) = cold["rows"]
    assert row["attrs"]["cache"] == "miss" and "retrieval_s" not in row["attrs"]
    assert cold["stats"]["misses"] >= 1 and cold["stats"]["hits"] == 0
    assert cold["cache"][0]["entries"] == 0
    (row,) = warm["rows"]
    a = row["attrs"]
    assert a["cache"] == "hit" and a["retrieval_s"] > 0
    assert a["retrieval_s"] <= a["backend_s"]  # the load is inside it
    assert a["trace_s"] > 0 and a["lower_s"] > 0 and "saved_s" in a
    assert warm["stats"]["hits"] >= 1 and warm["stats"]["misses"] == 0
    assert warm["stats"]["hits"] <= warm["stats"]["programs"]
    assert warm["cache"][0]["entries"] >= 1 and warm["cache"][0]["bytes"] > 0


# -- the asynchronous path's rows ---------------------------------------------

WORKER_PHASES = ["attach", "problem", "open", "first_read", "first_grad",
                 "first_push"]
SERVE_PHASES = ["problem", "optimizer", "first_update"]


def test_two_workers_and_a_server_write_their_way_to_the_first_step(tmp_path):
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel import dcn
    from pytorch_ps_mpi_tpu.parallel.async_train import (
        join_workers,
        make_problem,
        serve,
        spawn_worker,
    )

    cfg = {"model": "mlp", "model_kw": {"features": (16, 4)},
           "in_shape": (8,), "batch": 16, "seed": 5, "codec": "sign",
           "codec_kw": {"use_pallas": False}, "optim": "sgd",
           "hyper": {"lr": 0.02}, "steps": 3,
           "telemetry_dir": str(tmp_path)}
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_setup_{os.getpid()}"
    server = dcn.ShmPSServer(
        name, num_workers=2, template=params0, max_staleness=8,
        code=get_codec(cfg["codec"], **cfg["codec_kw"]))
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(2)]
        serve(server, dict(cfg, telemetry_dir=None), total_grads=0,
              total_received=6, timeout=240.0)
        assert join_workers(procs, timeout=120) == [0, 0]
    finally:
        server.close()

    def family(rows, top, phases):
        (whole,) = named(top, rows)
        assert "parent" not in whole
        for phase in phases:
            (child,) = named(f"{top}.{phase}", rows)
            assert child["parent"] == top, child
            assert whole["ts"] <= child["ts"]
            assert (child["ts"] + child["dur"]
                    <= whole["ts"] + whole["dur"] + 1e-6), child
        mine = [r for r in rows if r["name"].startswith(top + ".")]
        assert names(mine) and set(names(mine)) == {
            f"{top}.{p}" for p in phases}
        return whole

    serve_row = family(telemetry.setup_rows(), "setup.serve", SERVE_PHASES)
    assert serve_row["attrs"] == {"workers": 2, "codec": "sign"}
    (update,) = named("setup.serve.first_update")
    assert 0 <= update["attrs"]["wait_s"] <= update["dur"]
    for wid in range(2):
        meta, rows = telemetry.load_jsonl(str(tmp_path / f"worker-{wid}.jsonl"))
        whole = family(rows, "setup.worker", WORKER_PHASES)
        assert whole["worker"] == wid and whole["attrs"] == {"platform": "cpu"}
        assert meta["setup_dropped"] == 0
        # the process's own import and cache rows are in its file too
        assert len(named("setup.import", rows)) == 1
        assert len(named("setup.cache", rows)) == 1
        # the gradient program was built inside first_grad, by name
        grads = [r for r in named("compile.program", rows)
                 if r.get("parent") == "setup.worker.first_grad"]
        assert [r["attrs"]["program"] for r in grads] == ["jit(loss_fn)"]
        # once: the later cycles wrote no set-up row
        steps = named("worker.step", rows)
        assert len(steps) == 3
        assert all(r["ts"] + r["dur"] <= steps[1]["ts"] for r in rows
                   if r["name"].startswith("setup."))


# -- the five plan rows -------------------------------------------------------

def trace_flash():
    from pytorch_ps_mpi_tpu.ops.attention_pallas import flash_attention

    q = jnp.zeros((1, 512, 1, 64), jnp.bfloat16)
    jax.eval_shape(lambda q: flash_attention(q, q, q), q)


def trace_scan():
    from pytorch_ps_mpi_tpu.ops.selective_scan import selective_scan

    b, t, e, n = 1, 16, 8, 4
    z = jnp.zeros
    jax.eval_shape(lambda *a: selective_scan(*a, chunk=8),
                   z((b, t, e)), z((b, t, e)), z((e, n)), z((b, t, n)),
                   z((b, t, n)), z((e,)))


def trace_moe():
    from pytorch_ps_mpi_tpu.parallel.dropless import dropless_moe

    p, d, f, n = 32, 128, 64, 4
    z = jnp.zeros
    jax.eval_shape(
        lambda x, r, g, u, w: dropless_moe(
            x, r, g, u, w, top_k=2, experts_held=(0, n), capacity_factor=2.0),
        z((p, d)), z((d, n)), z((n, d, f)), z((n, d, f)), z((n, f, d)))


def trace_hc():
    from pytorch_ps_mpi_tpu.ops.hyper_connection import record_plan

    record_plan(jnp.zeros((4, 1, 8, 16), jnp.bfloat16), iters=20, sub_layers=10)


def trace_hc_tiled():
    from pytorch_ps_mpi_tpu.ops.hyper_connection import record_plan

    record_plan(jax.ShapeDtypeStruct((4, 1, 4096, 3584), jnp.bfloat16),
                iters=20, sub_layers=10)


def read_step_program():
    opt = SGD({"w": jnp.ones((4, 2))}, lr=0.1)
    opt.step_memory_analysis(quad_loss, jnp.ones((8, 4)))


@pytest.mark.parametrize("name, trace, some", [
    ("attn.flash_tiles", trace_flash, {"block_q": 512, "full": 1}),
    ("ssm.scan_plan", trace_scan, {"T": 16, "chunk": 8, "chunks": 2}),
    ("moe.row_moves", trace_moe, {"rows": 32, "slots": 2, "width": 128}),
    ("hc.plan", trace_hc, {"streams": 4, "iterations": 20, "sub_layers": 10,
                           "stream_bytes": 1024, "mover": "jnp", "tile": 0}),
    ("hc.plan", trace_hc_tiled, {"streams": 4, "iterations": 20,
                                 "sub_layers": 10, "mover": "kernel",
                                 "tile": 256}),
    ("ps.step_program", read_step_program, {"async_collectives": 0}),
])
def test_a_plan_row_lands_in_the_log_with_the_recorder_off(name, trace, some):
    assert telemetry.get_recorder() is None
    trace()
    (row,) = named(name)
    assert row["kind"] == "event"
    assert some.items() <= row["attrs"].items(), row
