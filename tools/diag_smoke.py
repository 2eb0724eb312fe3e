"""Diagnosis smoke gate: the health layer must NAME the right straggler.

What it does (CPU-only, shm transport, ~half a minute):

1. Runs a 2-worker async MLP job with a fault plan injecting repeated
   ``delay`` faults into worker 1's push path (the deterministic
   slow-worker scenario — compute untouched, wire time inflated) with
   the :class:`HealthMonitor` armed and the ``/metrics`` + ``/health``
   HTTP endpoint live on the shm server.
2. Asserts the diagnosis is RIGHT, where an operator would look:

   - the ``/health`` JSON scraped over HTTP names worker 1 ``slow`` with
     cause ``wire-bound`` and does NOT flag worker 0;
   - the ``tools/ps_top.py`` rendering of that same document shows the
     attribution;
   - ``/metrics`` carries ``ps_worker_anomaly_total{worker="1"} >= 1``
     (and more anomalies than worker 0) plus a nonzero
     ``ps_staleness_p95`` gauge.

Run via ``make diag-smoke``. Exits nonzero on any wrong verdict.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

from pytorch_ps_mpi_tpu.parallel import dcn
from pytorch_ps_mpi_tpu.parallel.async_train import (
    join_workers,
    make_problem,
    serve,
    spawn_worker,
)

STEPS = 24
DELAY_MS = 500.0
#: repeated wire-side delays on worker 1, late enough that every
#: worker's MAD window is armed (min_samples) and recent enough that the
#: end-of-run scrape still sees the anomaly (anomaly_decay_s)
FAULT_PLAN = [
    {"at_step": s, "worker": 1, "kind": "delay", "delay_ms": DELAY_MS}
    for s in (12, 14, 16, 18, 20, 22)
]


def run_job(workdir: str) -> tuple:
    """One monitored async run; returns (metrics, health_doc, ps_top
    frame, prometheus text)."""
    cfg = {
        "model": "mlp", "model_kw": {"features": (16, 4)}, "in_shape": (8,),
        "batch": 32, "seed": 3, "optim": "sgd", "hyper": {"lr": 0.05},
        "steps": STEPS,
        "open_timeout": 60.0, "push_timeout": 60.0,
        "frame_check": True,
        "fault_plan": FAULT_PLAN, "fault_seed": 1,
        "health_dir": os.path.join(workdir, "health"),
        # tolerate this container's scheduler stalls on the HEALTHY
        # worker while still catching the 500 ms injected delays; the
        # decay keeps the verdict visible through the end-of-run scrape
        "health_kw": {"mad_floor_s": 0.2, "min_samples": 5,
                      "anomaly_decay_s": 120.0},
    }
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_diag_{os.getpid()}"
    server = dcn.ShmPSServer(name, num_workers=2, template=params0,
                             max_staleness=10**9, frame=True)
    procs = []
    try:
        port = server.start_metrics_http(0, host="127.0.0.1")
        procs = [spawn_worker(name, i, cfg) for i in range(2)]
        params, m = serve(server, cfg, total_grads=0,
                          total_received=2 * STEPS, timeout=300.0)
        codes = join_workers(procs, timeout=120.0)
        if codes != [0, 0]:
            raise SystemExit(f"workers exited {codes}")
        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=10).read().decode())
        prom = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        from tools.ps_top import render_table

        frame = render_table(health, sort="verdict")
        return m, health, frame, prom
    finally:
        server.close()
        join_workers(procs, timeout=5.0)


def check(m: dict, health: dict, frame: str, prom: str) -> list:
    bad = []
    workers = {w["worker"]: w for w in health["workers"]}
    w0, w1 = workers[0], workers[1]
    if w1["verdict"] != "slow":
        bad.append(f"worker 1 verdict {w1['verdict']!r} != 'slow'")
    if w1["cause"] != "wire-bound":
        bad.append(f"worker 1 cause {w1['cause']!r} != 'wire-bound'")
    if w0["verdict"] in ("slow", "churning"):
        bad.append(f"worker 0 flagged {w0['verdict']!r} (healthy worker)")
    if w1["anomalies"] < 1:
        bad.append(f"worker 1 anomalies {w1['anomalies']} < 1")
    if w1["anomalies"] <= w0["anomalies"]:
        bad.append(f"anomalies w1={w1['anomalies']} <= w0={w0['anomalies']}")
    if "wire-bound" not in frame:
        bad.append("ps_top frame does not show the wire-bound attribution")
    p95 = None
    anom = {}
    for line in prom.splitlines():
        if line.startswith("ps_staleness_p95 "):
            p95 = float(line.rsplit(" ", 1)[1])
        if line.startswith("ps_worker_anomaly_total{"):
            wid = line.split('worker="')[1].split('"')[0]
            anom[wid] = float(line.rsplit(" ", 1)[1])
    if not p95 or p95 <= 0:
        bad.append(f"ps_staleness_p95 gauge is {p95} (expected > 0)")
    if anom.get("1", 0) < 1:
        bad.append(f"ps_worker_anomaly_total{{worker=1}} = {anom.get('1')}")
    if m["health"]["workers"][1]["cause"] != "wire-bound":
        bad.append("returned metrics['health'] disagrees with /health")
    return bad


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="diag_smoke_")
    print(f"diag-smoke: 2-worker async run, {len(FAULT_PLAN)} injected "
          f"{DELAY_MS:.0f}ms delays on worker 1 (workdir {workdir})")
    m, health, frame, prom = run_job(workdir)

    print(frame)
    failures = check(m, health, frame, prom)

    if failures:
        print("\nDIAG-SMOKE FAILED:", file=sys.stderr)
        for b in failures:
            print(f"  - {b}", file=sys.stderr)
        return 1
    print("\ndiag-smoke PASSED: straggle attributed to worker 1 "
          "(wire-bound), staleness p95 nonzero")
    return 0


if __name__ == "__main__":
    sys.exit(main())
