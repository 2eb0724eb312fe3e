"""The split of the first device's idle time by the program's spans: on
hand-made events whose answers are known, on the trace recorded on the
chip with program spans synthesised into its gaps, on a trace the CPU
backend's profiler wrote, and every new reader where there is nothing to
read."""

import json
import os
import shutil

import pytest

from chipbench import host_phases as hp
from chipbench import trace_reduce as tr
from chipbench.run import ROOT, Manifest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000  # nanoseconds

# the per-layer metrics the benchmark began with
FIRST = {"loop.step_ms_p50", "loop.step_ms_p95", "step.device_ms",
        "step.compiles_in_window", "model.mfu_pct", "attn.kernel_ms",
        "flash_attention_roofline", "attn.roofline_pct", "coll.time_ms",
        "coll.exposed_ms", "wire.mb_per_update", "server.update_ms_p50",
        "server.updates_per_s", "worker.grad_share_pct", "worker.push_ms_p50",
        "cache.misses", "device.idle_pct", "device.peak_hbm_gb"}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    _DOC = json.load(_f)
    NEW_METRICS = [m for m in _DOC["per_layer"] if m["name"] not in FIRST]
# the idle split and the spans' metrics this file's readers brought
SYNC_IDLE = ["trainer.data", "ps.prepare", "ps.dispatch", "ps.wait",
             "trainer.loss_fetch", "ps.step", "trainer.step", "in_program",
             "outside"]
ASYNC_IDLE = ["worker.read_params", "worker.batch", "worker.grad_dispatch",
              "worker.grad_wait", "wire.encode", "wire.send", "worker.step",
              "worker.in_program", "worker.outside"]
SPAN_METRICS = {"step.dispatch_ms_p50": "fused step",
                "worker.read_ms_p50": "async worker",
                "worker.batch_ms_p50": "async worker",
                "wire.encode_ms_p50": "codec / wire",
                "wire.send_ms_p50": "codec / wire"}
ASYNC_CELL = "resnet18-cifar.async1"


def hand_made():
    """Two steps of 10 ms: the program runs 0-8 with a pause 4-5 between
    its two operations, the device idles 8-10. The host: ``trainer.step``
    0-10 and 10-19, and in the first ``ps.step`` 1-9.5 with ``ps.wait``
    2-8.5 inside it, then ``trainer.loss_fetch`` 9.5-9.75. So the second
    step's last millisecond is under no span."""
    ops = [("a", 0, 4 * MS), ("b", 5 * MS, 3 * MS),
           ("a", 10 * MS, 4 * MS), ("b", 15 * MS, 3 * MS)]
    modules = [("jit_step", 0, 8 * MS), ("jit_step", 10 * MS, 8 * MS)]
    spans = [("trainer.step", 0, 10 * MS),
             ("ps.step", 1 * MS, 8 * MS + MS // 2),
             ("ps.wait", 2 * MS, 6 * MS + MS // 2),
             ("trainer.loss_fetch", 9 * MS + MS // 2, MS // 4),
             ("trainer.step", 10 * MS, 9 * MS)]
    return ops, modules, spans


def test_idle_goes_to_the_innermost_span():
    ops, modules, spans = hand_made()
    got = hp.idle_by_span(ops, modules, spans, (0, 20 * MS), steps=2)
    assert got == {
        "in_program": pytest.approx(1.0),         # 4-5 and 14-15
        "ps.wait": pytest.approx(0.25),           # 8-8.5: innermost wins
        "ps.step": pytest.approx(0.5),            # 8.5-9.5: its self time
        "trainer.loss_fetch": pytest.approx(0.125),
        "trainer.step": pytest.approx(0.625),     # 9.75-10 and 18-19
        "outside": pytest.approx(0.5),            # 19-20
    }
    assert sum(got.values()) == pytest.approx(6.0 / 2)  # all the idle time


def test_idle_inside_a_program_is_not_the_hosts():
    ops, modules, spans = hand_made()
    spans = spans + [("ps.dispatch", 4 * MS, MS)]  # over the pause 4-5
    got = hp.idle_by_span(ops, modules, spans, (0, 20 * MS), steps=2)
    assert got["ps.dispatch"] == 0.0 and got["in_program"] == pytest.approx(1.0)


def test_the_window_clips_everything():
    ops, modules, spans = hand_made()
    got = hp.idle_by_span(ops, modules, spans, (9 * MS, 19 * MS), steps=1)
    assert got["ps.step"] == pytest.approx(0.5)       # 9-9.5
    assert got["trainer.step"] == pytest.approx(1.25)
    assert got["outside"] == 0.0 and got["ps.wait"] == 0.0
    assert sum(got.values()) == pytest.approx(3.0)


def test_no_span_at_all_is_all_outside():
    ops, modules, _ = hand_made()
    got = hp.idle_by_span(ops, modules, [], (0, 20 * MS), steps=2)
    assert got == {"outside": pytest.approx(2.0),
                   "in_program": pytest.approx(1.0)}


def test_interval_helpers():
    assert hp.complement([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert hp.complement([(0, 3)], 0, 3) == []
    inner = hp.innermost([("p", 0, 10), ("c", 2, 3), ("c", 7, 1)])
    assert dict(inner) == {"p": [(0, 2), (5, 7), (8, 10)],
                           "c": [(2, 5), (7, 8)]}


def test_the_device_clock_is_moved_by_the_least_that_restores_causality():
    """Three programs; the device's clock reads 1.5 ms early, so each
    seems to begin before the host event that launched it. The second
    began 0.2 ms after its launch (its latency), the others waited for
    the device: the shift is the least that puts none before its launch."""
    launched = {("12", "a"): 10 * MS, ("12", "b"): 20 * MS, ("12", "c"): 21 * MS,
                ("7", "b"): 99 * MS}  # another kind of link: not a launch of b
    true_start = {"a": 13 * MS, "b": 20 * MS + MS // 5, "c": 30 * MS}
    early = 3 * MS // 2
    started = [(("12", k), t - early) for k, t in true_start.items()]
    shift = hp.clock_shift(launched, started)
    assert shift == early - MS // 5  # all but the soonest program's latency
    assert all(t + shift >= launched[f] for f, t in started)
    # a clock that reads late is moved back as far as causality allows
    late = [(f, t + 2 * early) for f, t in started]
    assert hp.clock_shift(launched, late) == -early - MS // 5
    # no link between a program and a launch: nothing to go by
    assert hp.clock_shift({}, started) == 0.0
    assert hp.clock_shift(launched, [(("12", "zz"), 5)]) == 0.0


# -- the recorded trace, with the program's spans put into its gaps -----------

def synthesised_spans(trace, dev):
    """The spans ``Trainer.fit`` would have opened around the step
    programs of the recorded trace: each step from the end of the one
    before to its own end (the last to the window's), with the phases of
    ``MPI_PS.step`` inside."""
    steps = sorted(tr.step_module(trace, dev), key=lambda e: e[1])
    spans, last_end = [], steps[0][1] - 8 * MS
    for _, start, dur in steps:
        end = start + dur
        gap = start - last_end
        until = tr.window_of(trace)[1] if start == steps[-1][1] else end + gap // 8
        spans += [("trainer.step", last_end, until - last_end),
                  ("trainer.data", last_end + gap // 8, gap // 8),
                  ("ps.step", last_end + gap // 4, end - last_end - gap // 4),
                  ("ps.prepare", last_end + gap // 4, gap // 2),
                  ("ps.dispatch", last_end + 3 * gap // 4, gap // 4),
                  ("ps.wait", start, dur),
                  ("trainer.loss_fetch", end, gap // 16)]
        last_end = end + gap // 8
    return spans


def test_recorded_trace_idle_sums_to_what_summarize_implies():
    trace = tr.load(os.path.join(DATA, "bert-base.mlm128.dp4.trace.json.gz"))
    first = min(trace.ops)
    alone = tr.Trace({first: trace.ops[first]},
                     {first: trace.modules[first]}, trace.host)
    s = tr.summarize(alone)
    spans = synthesised_spans(trace, first)
    got = hp.idle_by_span(trace.ops[first], trace.modules[first], spans,
                          tr.window_of(trace), s["steps"])
    idle_ms_a_step = 1e3 * (s["window_s"] - s["busy_s"]) / s["steps"]
    assert sum(got.values()) == pytest.approx(idle_ms_a_step, rel=1e-9)
    # the pauses inside the step programs, counted independently
    lo, hi = tr.window_of(trace)
    in_program = sum(
        min(s_ + n, hi) - max(s_, lo) for _, s_, n in trace.modules[first]
        if min(s_ + n, hi) > max(s_, lo)) - 1e9 * s["busy_s"]
    assert got["in_program"] == pytest.approx(
        in_program / 1e6 / s["steps"], rel=1e-6)
    # every span that had a gap under it got some, and they cover the rest
    for name in ("trainer.data", "ps.prepare", "ps.dispatch", "trainer.step"):
        assert got[name] > 0, name
    assert got["ps.wait"] == 0.0  # wholly inside the program
    assert got["outside"] == pytest.approx(0.0, abs=1e-9)


# -- a trace the profiler wrote (CPU backend: host plane only) ----------------

def test_read_xplane_finds_the_programs_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu import telemetry

    cell = {"name": "host-phases-selftest"}
    scratch = os.path.join(ROOT, ".chipbench_run", cell["name"])
    shutil.rmtree(scratch, ignore_errors=True)
    assert hp.find(cell) is None and hp.idle_ms({"steps": 3}, cell, "x") is None
    assert hp.host_ms({"steps": 3}, cell, "ps.dispatch") == []
    telemetry.configure()
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(os.path.join(scratch, "trace"),
                                 profiler_options=options)
        with jax.profiler.TraceAnnotation("fit.call"):
            with telemetry.span("trainer.step", step=1):
                with telemetry.span("ps.dispatch"):
                    jnp.ones(8).block_until_ready()
            with jax.profiler.TraceAnnotation("not.the.programs"):
                pass
        jax.profiler.stop_trace()
        trace, spans = hp.read_xplane(hp.find(cell))
        assert [e[0] for e in trace.host] == ["fit.call"]
        assert sorted(e[0] for e in spans) == ["ps.dispatch", "trainer.step"]
        (call,), by = trace.host, {e[0]: e for e in spans}
        assert (call[1] <= by["trainer.step"][1] <= by["ps.dispatch"][1]
                and by["ps.dispatch"][1] + by["ps.dispatch"][2]
                <= by["trainer.step"][1] + by["trainer.step"][2]
                <= call[1] + call[2])
        assert trace.ops == {}  # no TPU plane off the chip
        (ms,) = hp.host_ms({"steps": 1}, cell, "ps.dispatch")
        assert ms == pytest.approx(by["ps.dispatch"][2] / 1e6)
    finally:
        telemetry.disable()
        shutil.rmtree(scratch, ignore_errors=True)


# -- the new readers -----------------------------------------------------------

def test_the_new_entries_are_the_issues():
    """Each entry this file's readers brought, by name: its fields, its
    reader, and the kind of cell that reads it (the async worker's for
    ``idle.worker.*`` / ``idle.wire.*`` and the worker's and wire's
    spans, the synchronous step's for the rest)."""
    by_name = {m["name"]: m for m in _DOC["per_layer"]}
    sync = {c["name"] for c in _DOC["workloads"] if c["name"] != ASYNC_CELL}
    idle = ([f"idle.{s}_ms" for s in SYNC_IDLE]
            + [f"idle.{s}_ms" for s in ASYNC_IDLE])
    for name in idle + list(SPAN_METRICS):
        m = by_name[name]
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["unit"] == "ms" and m["better"] == "lower"
        if name in idle:
            assert m["source"] == "device_trace" and m["layer"] == "device"
        else:
            assert m["source"] == "program_span"
            assert m["layer"] == SPAN_METRICS[name]
        on_worker = name.startswith(("idle.worker.", "idle.wire.",
                                     "worker.", "wire."))
        assert m["moves"] == ("staleness_mean" if on_worker
                              else "tokens_per_s"), name
        if on_worker:
            assert ASYNC_CELL in m["workloads"]
        else:
            assert ASYNC_CELL not in m["workloads"]
            assert set(m["workloads"]) & sync
        assert callable(Manifest(os.path.join(ROOT, "BENCHMARK.json"))
                        .reader(name))


@pytest.mark.parametrize("metric", [m["name"] for m in NEW_METRICS])
def test_a_new_reader_reads_nothing_where_nothing_is(metric):
    read = Manifest(os.path.join(ROOT, "BENCHMARK.json")).reader(metric)
    cell = {"name": "no-such-run"}
    assert read(None, {}, {}, cell) is None           # no trace
    summary = {"steps": 3, "window_s": 1.0, "busy_s": 0.5}
    assert read(summary, {}, {}, cell) is None        # no file, no spans
    assert read(dict(summary, steps=0), {"worker.grad": []}, {}, cell) is None
