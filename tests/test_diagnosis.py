"""Online health diagnosis: EWMA/MAD anomaly gates, straggler
attribution (compute vs wire vs churn), sync-round critical-path
gating, the /health endpoint and ps_top rendering."""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from pytorch_ps_mpi_tpu import telemetry
from pytorch_ps_mpi_tpu.telemetry import MetricsRegistry
from pytorch_ps_mpi_tpu.telemetry.diagnosis import (
    BeaconWriter,
    Ewma,
    HealthMonitor,
    MadWindow,
    read_beacon_rows,
)


@pytest.fixture(autouse=True)
def _no_global_recorder():
    telemetry.disable()
    yield
    telemetry.disable()


def _template(n=8):
    return {"w": np.zeros((n,), np.float32)}


def _make_server(transport, template, **kw):
    if transport == "shm":
        from pytorch_ps_mpi_tpu.parallel import dcn

        if dcn.get_lib() is None:
            pytest.skip("native toolchain unavailable")
        return dcn.ShmPSServer(
            f"/psq_diagt_{os.getpid()}_{transport}", num_workers=2,
            template=template, **kw)
    from pytorch_ps_mpi_tpu.parallel import tcp

    if tcp.get_lib() is None:
        pytest.skip("native toolchain unavailable")
    return tcp.TcpPSServer(0, num_workers=2, template=template, **kw)


# -- primitives -------------------------------------------------------------

def test_ewma_warms_from_first_sample():
    e = Ewma(alpha=0.5)
    assert e.value is None
    assert e.update(10.0) == 10.0  # no zero prior drowning the start
    assert e.update(20.0) == 15.0


def test_mad_window_flags_spike_after_warmup_only():
    w = MadWindow(maxlen=32, k=4.0, floor=0.05, min_samples=5)
    flags = [w.check_and_add(0.01) for _ in range(10)]
    assert not any(flags)  # warmup + steady state: clean
    assert w.check_and_add(2.0) is True  # the injected-delay shape
    # the floor absorbs sub-floor jitter even with MAD == 0
    assert w.check_and_add(0.04) is False


def test_beacon_writer_incremental_tail(tmp_path):
    b = BeaconWriter(str(tmp_path), worker=1)
    b.step(0, 0.002, 0.5, retries=1)
    rows, off = read_beacon_rows(b.path, 0)
    assert len(rows) == 1 and rows[0]["wire_s"] == 0.5
    # a torn (unterminated) trailing line is left for the next read
    with open(b.path, "a") as f:
        f.write('{"worker": 1, "step": 1')
    rows2, off2 = read_beacon_rows(b.path, off)
    assert rows2 == [] and off2 == off
    with open(b.path, "a") as f:
        f.write(', "compute_s": 1.0, "wire_s": 0.0}\n')
    rows3, _ = read_beacon_rows(b.path, off2)
    assert len(rows3) == 1 and rows3[0]["compute_s"] == 1.0
    b.close(retries=2)
    rows4, _ = read_beacon_rows(b.path, 0)
    assert rows4[-1]["done"] is True and rows4[-1]["retries"] == 2


# -- anomaly detection + verdicts ------------------------------------------

def test_monitor_flags_only_the_slow_worker():
    mon = HealthMonitor(num_workers=2, cfg={})
    t = 0.0
    for i in range(30):
        t += 0.01
        mon.observe_grad(0, 0, now=t)
        mon.observe_grad(1, 0, now=t)
    mon.observe_grad(1, 0, now=t + 2.0)  # one 2 s straggle on worker 1
    snap = mon.snapshot(now=t + 2.0)
    w0, w1 = snap["workers"]
    assert w0["verdict"] == "ok" and w0["anomalies"] == 0
    assert w1["verdict"] == "slow" and w1["anomalies"] >= 1
    assert w1["last_anomaly"]["kind"] == "push_latency"
    assert w1["cause"] == "unknown"  # no beacons: step can't be split


def test_monitor_staleness_anomaly():
    mon = HealthMonitor(num_workers=1, cfg={})
    t = 0.0
    for i in range(20):
        t += 0.01
        mon.observe_grad(0, 1, now=t)
    mon.observe_grad(0, 40, now=t + 0.01)  # staleness explosion
    w0 = mon.snapshot(now=t + 0.01)["workers"][0]
    assert w0["anomalies"] >= 1
    assert w0["last_anomaly"]["kind"] == "staleness"


def test_attribution_from_beacons(tmp_path):
    """The compute/wire split rides the beacon EWMAs: a wire-heavy slow
    worker is wire-bound, a compute-heavy one compute-bound, and a
    churning one (retry/reconnect counters) trumps both."""
    cfg = {"health_dir": str(tmp_path)}
    for wid, (compute, wire) in ((0, (0.5, 0.001)), (1, (0.002, 0.6))):
        b = BeaconWriter(str(tmp_path), worker=wid)
        for s in range(6):
            b.step(s, compute, wire)
        b.close()
    b2 = BeaconWriter(str(tmp_path), worker=2)
    b2.step(0, 0.002, 0.001, retries=2, reconnects=2)
    b2.close(retries=2, reconnects=2)

    mon = HealthMonitor(num_workers=3, cfg=cfg)
    t = 0.0
    for i in range(30):  # all three equally slow on the wire clock
        t += 0.01
        for wid in range(3):
            mon.observe_grad(wid, 0, now=t)
    for wid in range(3):
        mon.observe_grad(wid, 0, now=t + 3.0)  # everyone spikes
    mon.tick()
    snap = mon.snapshot(now=t + 3.0)
    assert snap["workers"][0]["cause"] == "compute-bound"
    assert snap["workers"][1]["cause"] == "wire-bound"
    assert snap["workers"][2]["verdict"] == "churning"
    assert snap["workers"][2]["cause"] == "reconnect-churn"


def test_round_gating_critical_path_attribution():
    """The last-ready worker is billed for the gap it kept the round
    open past the second-slowest — cumulative, per worker, and exported
    as labeled counters."""
    mon = HealthMonitor(num_workers=3, cfg={})
    for r in range(3):
        t0 = 10.0 * r
        mon.observe_round({0: t0 + 0.01, 1: t0 + 0.02, 2: t0 + 0.52},
                          active=[0, 1, 2])
    mon.observe_round({0: 100.01, 1: 100.6}, active=[0, 1])  # 2 excluded
    snap = mon.snapshot()
    g = {w["worker"]: w["gating"] for w in snap["workers"]}
    assert g[2]["rounds"] == 3 and abs(g[2]["seconds"] - 1.5) < 1e-6
    assert g[1]["rounds"] == 1 and abs(g[1]["seconds"] - 0.59) < 1e-6
    assert g[0] == {"rounds": 0, "seconds": 0.0}
    assert snap["fleet"]["rounds"] == 4

    reg = MetricsRegistry()
    mon.register(reg)
    text = reg.prometheus_text()
    assert 'ps_rounds_gated_total{worker="2"} 3' in text
    assert 'ps_round_gating_seconds{worker="2"} 1.5' in text
    assert 'ps_worker_health{worker="0"}' in text


# -- live servers: /health + /metrics on both transports --------------------

@pytest.mark.parametrize("transport", ["shm", "tcp"])
def test_health_endpoint_and_anomaly_metrics(transport):
    """/health round-trips JSON over HTTP on BOTH transports, the
    anomaly/gating/health instruments land in /metrics, and close()
    tears the endpoint down (no leaked sockets across a supervisor
    restart)."""
    server = _make_server(transport, _template())
    try:
        mon = HealthMonitor(server, {})
        assert server.health_monitor is mon
        # anchored at the real clock: the scrape-time verdict (the HTTP
        # thread) has no synthetic-now override
        t = time.monotonic() - 5.2
        for i in range(20):
            t += 0.01
            mon.observe_grad(0, 0, now=t)
            mon.observe_grad(1, 1, now=t)
        mon.observe_grad(1, 1, now=t + 5.0)
        port = server.start_metrics_http(0, host="127.0.0.1")
        assert port == server.start_metrics_http(0)  # idempotent
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=10).read().decode())
        assert doc["armed"] is True and doc["n_workers"] == 2
        assert doc["workers"][1]["anomalies"] >= 1
        assert {w["worker"] for w in doc["workers"]} == {0, 1}
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert 'ps_worker_anomaly_total{worker="1"} 1' in text
        assert 'ps_worker_anomaly_total{worker="0"} 0' in text
        assert "ps_staleness_p50" in text and "ps_staleness_p95" in text
        assert 'ps_worker_health{worker="1"} 1' in text  # slow
    finally:
        server.close()
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/health",
                               timeout=2)


def test_health_endpoint_unarmed_is_explicit():
    server = _make_server("shm", _template())
    try:
        port = server.start_metrics_http(0, host="127.0.0.1")
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=10).read().decode())
        assert doc["armed"] is False and doc["workers"] == []
        # even the unarmed document carries the fleet poller's
        # ordering/aging fields (this PR's satellite)
        assert doc["ts"] > 0 and doc["uptime_s"] >= 0.0
    finally:
        server.close()


# -- serve-loop integration: the deterministic slow-worker scenario --------

def test_serve_flags_delayed_worker_wire_bound(tmp_path):
    """The satellite scenario, in-process: two thread workers over shm,
    worker 1 straggled by FaultInjector ``delay`` faults (wire-side by
    the worker loop's accounting, mirrored into its beacons) — the
    monitor must flag exactly worker 1 as slow and wire-bound."""
    from pytorch_ps_mpi_tpu.parallel import dcn
    from pytorch_ps_mpi_tpu.parallel.async_train import make_problem, serve
    from pytorch_ps_mpi_tpu.resilience import FaultInjector

    if dcn.get_lib() is None:
        pytest.skip("native toolchain unavailable")

    steps = 16
    plan = [{"at_step": s, "worker": 1, "kind": "delay",
             "delay_ms": 600.0} for s in (8, 10, 12, 14)]
    cfg = {
        "model": "mlp", "model_kw": {"features": (8, 4)}, "in_shape": (8,),
        "batch": 8, "seed": 1, "optim": "sgd", "hyper": {"lr": 0.01},
        "health_dir": str(tmp_path),
        "health_kw": {"mad_floor_s": 0.2, "min_samples": 4,
                      "anomaly_decay_s": 300.0},
        "fault_plan": plan, "fault_seed": 0,
    }
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_diagserve_{os.getpid()}"
    server = dcn.ShmPSServer(name, num_workers=2, template=params0,
                             max_staleness=10**9)
    workers, threads = [], []
    try:
        def worker_body(wid):
            import jax

            inj = FaultInjector.from_cfg(cfg, role=wid)
            w = dcn.ShmPSWorker(name, wid, params0, timeout=30.0)
            workers.append(w)
            beacon = BeaconWriter(str(tmp_path), wid)
            g = jax.tree.map(
                lambda x: np.full(np.shape(x), 1e-3, np.float32), params0)
            for step in range(steps):
                t0 = time.monotonic()
                delay_s = 0.0
                for f in (inj.faults_at(step) if inj else ()):
                    if f["kind"] == "delay":
                        inj.fire(f)
                        time.sleep(float(f["delay_ms"]) / 1e3)
                        delay_s = float(f["delay_ms"]) / 1e3
                _, ver = w.read_params(timeout=30.0)
                compute_s = 0.002
                time.sleep(compute_s)
                w.push_grad(g, ver, timeout=30.0)
                beacon.step(step, compute_s,
                            max(0.0, time.monotonic() - t0 - compute_s))
                time.sleep(0.02)
            beacon.close()

        threads = [threading.Thread(target=worker_body, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        params, m = serve(server, cfg, total_grads=2 * steps,
                          timeout=120.0)
        for t in threads:
            t.join(timeout=60)
    finally:
        for w in workers:
            w.close()
        server.close()

    health = m["health"]
    w0, w1 = health["workers"]
    assert w1["verdict"] == "slow", health
    assert w1["cause"] == "wire-bound", health
    assert w1["anomalies"] >= 1
    assert w0["verdict"] not in ("slow", "churning"), health
    assert w1["anomalies"] > w0["anomalies"]
    # canonical staleness quantiles rode the serve metrics
    assert "staleness_p95" in m


# -- ps_top rendering -------------------------------------------------------

def test_ps_top_render_table():
    from tools.ps_top import normalize_url, render_table

    mon = HealthMonitor(num_workers=2, cfg={})
    t = 0.0
    for i in range(20):
        t += 0.01
        mon.observe_grad(0, 0, now=t)
        mon.observe_grad(1, 2, now=t)
    mon.observe_grad(1, 2, now=t + 4.0)
    frame = render_table(mon.snapshot(now=t + 4.0), sort="verdict")
    lines = frame.splitlines()
    assert "ps_top" in lines[0]
    # verdict sort puts the flagged worker first
    first_row = lines[3]
    assert first_row.strip().startswith("1") and "slow" in first_row
    assert render_table({"armed": False}).startswith("health monitor not")
    assert normalize_url("9100") == "http://127.0.0.1:9100/health"
    assert normalize_url("host:91") == "http://host:91/health"
    assert (normalize_url("http://h:91/health")
            == "http://h:91/health")


# -- telemetry_report: labeled series --------------------------------------

def test_report_tabulates_worker_labeled_series(tmp_path):
    from tools.telemetry_report import (
        format_table,
        parse_prometheus_text,
        summarize,
    )

    prom = tmp_path / "metrics.prom"
    prom.write_text(
        "# HELP ps_frames_rejected_total rejections\n"
        "# TYPE ps_frames_rejected_total counter\n"
        'ps_frames_rejected_total{worker="0"} 0\n'
        'ps_frames_rejected_total{worker="1"} 3\n'
        "ps_grads_received_total 44\n"
        'ps_staleness_bucket{le="+Inf"} 44\n'
    )
    series = parse_prometheus_text(prom.read_text())
    assert {"name": "ps_frames_rejected_total", "labels": {"worker": "1"},
            "value": 3.0} in series

    summary = summarize([str(prom)])
    labeled = summary["labeled_metrics"]
    # per-worker series tabulated; histogram bucket rows excluded
    assert [(s["labels"]["worker"], s["value"]) for s in labeled
            if s["name"] == "ps_frames_rejected_total"] == [("0", 0.0),
                                                            ("1", 3.0)]
    assert all("le" not in s["labels"] for s in labeled)
    table = format_table(summary)
    assert "ps_frames_rejected_total{worker=1}: 3" in table


def test_report_directory_mode_picks_up_prom(tmp_path):
    from pytorch_ps_mpi_tpu.telemetry import FlightRecorder
    from tools.telemetry_report import collect_files, summarize

    rec = FlightRecorder(worker=0)
    rec.event("worker.grad", kind="span", dur=0.01, step=0)
    rec.dump_jsonl(str(tmp_path / "worker-0.jsonl"))
    (tmp_path / "metrics.prom").write_text(
        'ps_worker_anomaly_total{worker="0"} 2\n')
    (tmp_path / "beacon-0.jsonl").write_text('{"worker": 0}\n')
    (tmp_path / "faults-0.jsonl").write_text('{"id": 0}\n')
    files = collect_files([str(tmp_path)])
    names = {os.path.basename(f) for f in files}
    assert names == {"worker-0.jsonl", "metrics.prom"}
    summary = summarize(files)
    assert summary["spans"][0]["name"] == "worker.grad"
    assert summary["labeled_metrics"][0]["name"] == "ps_worker_anomaly_total"
