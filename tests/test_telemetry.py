"""Unified telemetry subsystem: canonical server schema (shm == TCP),
Prometheus ``/metrics`` HTTP scrape, FlightRecorder JSONL round-trip,
merged trace export, and the report CLI's aggregation."""

import json
import os
import urllib.request

import numpy as np
import pytest

from pytorch_ps_mpi_tpu import telemetry
from pytorch_ps_mpi_tpu.telemetry import (
    PS_SERVER_METRIC_KEYS,
    FlightRecorder,
    MetricsHTTPServer,
    MetricsRegistry,
    export_chrome_trace,
    load_jsonl,
)


@pytest.fixture(autouse=True)
def _no_global_recorder():
    """Tests must not leak a process-global recorder into each other."""
    telemetry.disable()
    yield
    telemetry.disable()


def _template(n=6):
    return {"w": np.zeros((n,), np.float32)}


def _make_server(transport, template, **kw):
    if transport == "shm":
        from pytorch_ps_mpi_tpu.parallel import dcn

        if dcn.get_lib() is None:
            pytest.skip("native toolchain unavailable")
        return dcn.ShmPSServer(f"/psq_tel_{os.getpid()}_{transport}",
                               num_workers=1, template=template, **kw)
    from pytorch_ps_mpi_tpu.parallel import tcp

    if tcp.get_lib() is None:
        pytest.skip("native toolchain unavailable")
    return tcp.TcpPSServer(0, num_workers=1, template=template, **kw)


# -- canonical server schema ------------------------------------------------

@pytest.mark.parametrize("transport", ["shm", "tcp"])
def test_server_metrics_canonical_schema(transport):
    """Every PS server emits exactly the canonical keys, all floats —
    the schema is one shared implementation, not per-transport dicts."""
    server = _make_server(transport, _template())
    try:
        m = server.metrics()
        assert tuple(sorted(m)) == tuple(sorted(PS_SERVER_METRIC_KEYS))
        assert all(type(v) is float for v in m.values()), m
        # the fleet-poller ordering/aging fields: ts is the wall clock
        # at metrics() time, uptime_s the server generation's monotonic
        # age — fresh server, so small but nonnegative and advancing
        import time

        assert abs(m["ts"] - time.time()) < 60.0
        assert 0.0 <= m["uptime_s"] < 60.0
        m2 = server.metrics()
        assert m2["ts"] >= m["ts"] and m2["uptime_s"] >= m["uptime_s"]
    finally:
        server.close()


def test_server_metrics_identical_across_transports():
    """Same template, same codec config → byte-for-byte identical
    metrics dicts from the shm and TCP servers."""
    tpl = _template()
    s1 = _make_server("shm", tpl)
    s2 = _make_server("tcp", tpl)
    try:
        m1, m2 = s1.metrics(), s2.metrics()
        # ts/uptime_s are clock-valued by design (the fleet poller's
        # sample-ordering fields) — present on both, compared apart
        for m in (m1, m2):
            assert "ts" in m and "uptime_s" in m
        drop = ("ts", "uptime_s")
        assert {k: v for k, v in m1.items() if k not in drop} \
            == {k: v for k, v in m2.items() if k not in drop}
    finally:
        s1.close()
        s2.close()


@pytest.mark.parametrize("transport", ["shm", "tcp"])
def test_server_prometheus_scrape_method(transport):
    """Both transports expose the same registry as Prometheus text; the
    staleness histogram mirrors ``staleness_seen`` at scrape time."""
    server = _make_server(transport, _template(), max_staleness=4)
    try:
        server.staleness_seen.update({0: 3, 2: 1})
        server.grads_received = 4
        text = server.prometheus_text()
        assert "ps_grads_received_total 4" in text
        assert "ps_staleness_count 4" in text
        assert 'ps_staleness_bucket{le="0"} 3' in text
        assert 'ps_staleness_bucket{le="2"} 4' in text
    finally:
        server.close()


def test_huge_max_staleness_does_not_explode_buckets():
    """max_staleness=10**9 (the disable-drops idiom) must produce a
    bounded bucket list, not a billion-entry range."""
    server = _make_server("shm", _template(), max_staleness=10**9)
    try:
        hist = server.scrape_registry().get("ps_staleness")
        assert hist is None or True  # registry builds lazily
        text = server.prometheus_text()
        assert text.count("ps_staleness_bucket") < 64
    finally:
        server.close()


def test_tcp_metrics_http_endpoint():
    """A stock HTTP GET of /metrics returns the Prometheus text; any
    other path 404s; the port survives until close()."""
    server = _make_server("tcp", _template())
    try:
        port = server.start_metrics_http(0, host="127.0.0.1")
        assert port == server.start_metrics_http(0)  # idempotent
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        assert "# TYPE ps_grads_received_total counter" in body
        assert "ps_publish_version 0" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=10)
    finally:
        server.close()


# -- FlightRecorder ---------------------------------------------------------

def test_flight_recorder_jsonl_roundtrip(tmp_path):
    rec = FlightRecorder(capacity=128, worker=3)
    with rec.span("phase.compute", step=1, note="hi"):
        pass
    rec.event("grad", step=2, staleness=1, bytes=4096)
    path = rec.dump_jsonl(str(tmp_path / "r.jsonl"))
    meta, events = load_jsonl(path)
    assert meta["dropped"] == 0 and meta["n_events"] == 2
    assert meta["worker"] == 3
    span, ev = events
    assert span["name"] == "phase.compute" and span["kind"] == "span"
    assert span["dur"] >= 0 and span["step"] == 1
    assert span["attrs"] == {"note": "hi"}
    assert ev["name"] == "grad" and ev["staleness"] == 1
    assert ev["worker"] == 3  # recorder default rides every record
    assert ev["attrs"]["bytes"] == 4096
    # wall/monotonic clocks describe the same instants, in order
    assert span["ts"] <= ev["ts"] and span["wall"] <= ev["wall"]


def test_flight_recorder_bounded_and_counts_drops(tmp_path):
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.event("e", step=i)
    assert len(rec) == 4 and rec.dropped == 6
    meta, events = load_jsonl(rec.dump_jsonl(str(tmp_path / "r.jsonl")))
    assert meta["dropped"] == 6
    assert [e["step"] for e in events] == [6, 7, 8, 9]  # newest kept


def test_global_recorder_zero_cost_guard():
    assert telemetry.get_recorder() is None
    telemetry.record_event("ignored")  # no-op, must not raise
    with telemetry.span("ignored.span"):
        pass
    rec = telemetry.configure(capacity=16, worker="t")
    with telemetry.span("live.span"):
        pass
    telemetry.record_event("live.event")
    assert [e["name"] for e in rec.events()] == ["live.span", "live.event"]
    telemetry.disable()
    assert telemetry.get_recorder() is None


# -- the span primitive of the hot paths: two clocks --------------------------

def test_span_off_does_nothing(annotations_made):
    made = annotations_made
    with telemetry.span("trainer.step", step=3, loss=1.0) as attrs:
        assert attrs is None
    assert made == [] and telemetry.get_recorder() is None


def test_span_on_records_parent_and_inherited_step(annotations_made):
    made = annotations_made
    rec = telemetry.configure(capacity=64, worker="t")
    with telemetry.span("trainer.step", step=7) as attrs:
        with telemetry.span("ps.step"):
            with telemetry.span("ps.wait"):
                pass
            with telemetry.span("ps.late", step=9, seq=4):
                pass
        attrs["loss"] = 0.25  # known only at the end
    with telemetry.span("alone"):
        pass
    rows = {e["name"]: e for e in rec.events()}
    assert list(rows) == ["ps.wait", "ps.late", "ps.step", "trainer.step",
                          "alone"]  # written on exit, innermost first
    assert "parent" not in rows["trainer.step"]
    assert "parent" not in rows["alone"]
    assert rows["ps.step"]["parent"] == "trainer.step"
    assert rows["ps.wait"]["parent"] == rows["ps.late"]["parent"] == "ps.step"
    assert [rows[n]["step"] for n in ("trainer.step", "ps.step", "ps.wait",
                                      "ps.late")] == [7, 7, 7, 9]
    assert "step" not in rows["alone"]
    assert rows["trainer.step"]["attrs"] == {"loss": 0.25}
    assert rows["ps.late"]["attrs"] == {"seq": 4}
    assert all(e["kind"] == "span" and e["dur"] >= 0 and e["worker"] == "t"
               for e in rows.values())
    # a child lies inside its parent on the recorder's clock too
    for child, parent in (("ps.wait", "ps.step"), ("ps.step", "trainer.step")):
        c, p_ = rows[child], rows[parent]
        assert p_["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= p_["ts"] + p_["dur"]
    # one annotation a span; the per-step parent is a StepTraceAnnotation
    assert [(c, n) for c, n, _ in made] == [
        ("StepTraceAnnotation", "trainer.step"),
        ("TraceAnnotation", "ps.step"), ("TraceAnnotation", "ps.wait"),
        ("TraceAnnotation", "ps.late"), ("TraceAnnotation", "alone")]
    assert made[0][2] == {"step_num": 7}


def test_span_stack_is_per_thread():
    import threading

    rec = telemetry.configure(capacity=64)
    inside = threading.Event()
    release = threading.Event()

    def other():
        inside.wait(5)
        with telemetry.span("worker.step", step=0):
            with telemetry.span("worker.grad"):
                pass
        release.set()

    t = threading.Thread(target=other)
    t.start()
    with telemetry.span("trainer.step", step=1):
        inside.set()
        assert release.wait(5)
        with telemetry.span("trainer.data"):
            pass
    t.join()
    rows = {e["name"]: e for e in rec.events()}
    assert "parent" not in rows["worker.step"]  # not the main thread's span
    assert rows["worker.grad"]["parent"] == "worker.step"
    assert rows["worker.grad"]["step"] == 0
    assert rows["trainer.data"]["parent"] == "trainer.step"


def test_span_still_records_when_the_body_raises():
    rec = telemetry.configure(capacity=64)
    with pytest.raises(ZeroDivisionError):
        with telemetry.span("trainer.step", step=2):
            with telemetry.span("ps.step") as attrs:
                attrs["reached"] = True
                1 / 0
    with telemetry.span("after"):
        pass
    rows = {e["name"]: e for e in rec.events()}
    assert rows["ps.step"]["parent"] == "trainer.step"
    assert rows["ps.step"]["attrs"] == {"reached": True}
    assert rows["trainer.step"]["step"] == 2
    assert "parent" not in rows["after"]  # the stack unwound


def test_fit_spans_sit_on_the_profilers_host_plane(tmp_path):
    """Under a profiler session the spans of ``Trainer.fit`` are events
    of ``/host:CPU`` in the written trace — the plane a chip's trace
    keeps its host threads on, on the device operations' clock."""
    import glob

    import jax
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.trainer import Trainer

    def batches():
        while True:
            yield jnp.ones((8, 4)), jnp.zeros((8, 2))

    loss_fn = lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2)
    trainer = Trainer(SGD({"w": jnp.ones((4, 2))}, lr=0.1, average=True),
                      loss_fn)
    trainer.fit(batches(), 1)  # compiled outside the trace
    telemetry.configure()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # annotations only, as the benchmark
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        trainer.fit(batches(), 3)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
              for line in host.lines for e in line.events
              if e.name.startswith(("trainer.", "ps."))]
    names = sorted(e[0] for e in events)
    assert names == sorted(3 * ["trainer.step", "trainer.data", "ps.step",
                                "ps.prepare", "ps.dispatch", "ps.wait",
                                "trainer.loss_fetch"])
    steps = sorted(e for e in events if e[0] == "trainer.step")
    assert [e[3]["step_num"] for e in steps] == [2, 3, 4]
    parents = {"trainer.data": "trainer.step", "ps.step": "trainer.step",
               "trainer.loss_fetch": "trainer.step", "ps.prepare": "ps.step",
               "ps.dispatch": "ps.step", "ps.wait": "ps.step"}
    inside = lambda lo, hi, parent: sum(
        p[1] <= lo and hi <= p[2] for p in events if p[0] == parent)
    fetches = sorted(e for e in events if e[0] == "trainer.loss_fetch")
    for name, lo, hi, _ in events:
        if name in parents and (name, lo) != fetches[-1][:2]:
            assert inside(lo, hi, parents[name]) == 1, name
    # the loss of the call's last step is fetched after the loop
    assert inside(*fetches[-1][1:3], "trainer.step") == 0
    assert fetches[-1][1] >= steps[-1][2]


# -- registry primitives ----------------------------------------------------

def test_registry_prometheus_text_and_types():
    reg = MetricsRegistry()
    reg.counter("c_total", "help").inc(2)
    reg.gauge("g").set(1.5)
    h = reg.histogram("h_seconds", [0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.prometheus_text()
    assert "# TYPE c_total counter" in text
    assert "# TYPE h_seconds histogram" in text
    assert 'h_seconds_bucket{le="0.1"} 1' in text
    assert 'h_seconds_bucket{le="1"} 2' in text
    assert 'h_seconds_bucket{le="+Inf"} 3' in text
    assert "h_seconds_count 3" in text
    with pytest.raises(ValueError):
        reg.gauge("c_total")  # kind clash must not silently alias
    with pytest.raises(ValueError):
        reg.counter("c_total").inc(-1)


def test_histogram_quantile_and_load():
    from pytorch_ps_mpi_tpu.telemetry import Histogram

    h = Histogram("x", buckets=[1, 2, 4, 8])
    h.load({1: 50, 4: 45, 8: 5})
    assert h.count == 100
    assert h.quantile(0.5) == 1
    assert h.quantile(0.95) == 4
    assert h.quantile(1.0) == 8


def test_histogram_approx_quantile_interpolates():
    """The satellite: a VALUE from cumulative buckets (linear
    interpolation, Prometheus histogram_quantile semantics), not just
    'somewhere <= bound' — what the ps_staleness_p* gauges export."""
    import math

    from pytorch_ps_mpi_tpu.telemetry import Histogram

    h = Histogram("x", buckets=[1, 2, 4, 8])
    assert math.isnan(h.approx_quantile(0.5))  # empty: explicit NaN
    h.load({1: 50, 4: 45, 8: 5})
    assert h.approx_quantile(0.50) == 1.0   # exactly fills bucket 1
    assert h.approx_quantile(0.95) == 4.0
    assert abs(h.approx_quantile(0.99) - 7.2) < 1e-9  # interpolated
    # overflow observations clamp to the highest finite bound
    h2 = Histogram("y", buckets=[1.0])
    h2.observe(50.0)
    assert h2.approx_quantile(0.99) == 1.0
    with pytest.raises(ValueError):
        h.approx_quantile(1.5)


# -- trace export + report --------------------------------------------------

def test_chrome_trace_export_merges_processes(tmp_path):
    r1 = FlightRecorder(worker="server")
    with r1.span("serve.update", step=1):
        pass
    r2 = FlightRecorder(worker=0)
    r2.event("worker.push", step=1)
    events = r1.events() + r2.events()
    path, counts = export_chrome_trace(str(tmp_path / "t.json"), events)
    assert counts == {"host": 2, "flow": 0, "fresh_flow": 0, "hop": 0}
    trace = json.load(open(path))
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in trace["traceEvents"]}
    assert "serve.update" in names and "worker.push" in names
    # anchored at the earliest record (a few µs of float slack: wall
    # epochs are ~1.7e9 s, where float64 granularity is sub-µs)
    assert all(e["ts"] >= -5.0 for e in xs)
    # distinct workers land on distinct tracks
    tids = {e.get("tid") for e in trace["traceEvents"] if e["ph"] != "M"}
    assert len(tids) == 2


def test_report_summarize_by_worker(tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.telemetry_report import format_table, summarize

    paths = []
    for w, dur in ((0, 0.01), (1, 0.03)):
        rec = FlightRecorder(worker=w)
        rec.event("worker.grad", kind="span", dur=dur, step=0)
        rec.event("worker.grad", kind="span", dur=dur, step=1)
        rec.event("crash", step=1)
        paths.append(rec.dump_jsonl(str(tmp_path / f"w{w}.jsonl")))

    merged = summarize(paths)
    (row,) = [r for r in merged["spans"] if r["name"] == "worker.grad"]
    assert row["count"] == 4
    assert abs(row["total_s"] - 0.08) < 1e-9

    per = summarize(paths, by_worker=True)
    rows = {r["worker"]: r for r in per["spans"]}
    assert rows[0]["count"] == 2 and rows[1]["count"] == 2
    assert rows[1]["mean_ms"] > rows[0]["mean_ms"]  # the straggler view
    table = format_table(per)
    assert "worker.grad" in table and "crash" in table
