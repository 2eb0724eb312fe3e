"""Numerics smoke gate: the numerics layer must catch the right poison.

What it does (CPU-only, shm transport, a few minutes):

1. **Quarantine**: runs a 2-worker async MLP job with a fault plan
   injecting ``nan`` faults into worker 1's gradients from mid-run, the
   :class:`NumericsMonitor` armed with the default ``skip`` policy and
   the ``/metrics`` + ``/health`` endpoint live. Asserts the layer is
   RIGHT where an operator would look:

   - exactly worker 1 is quarantined (worker 0 untouched), every NaN
     push counted (``ps_nonfinite_total``, per-worker
     ``ps_worker_nonfinite_total``), and the healthy worker kept the
     loss improving THROUGH the poison;
   - a ``postmortem-*.json`` landed on disk and
     ``tools/telemetry_report.py`` parses the run directory into a
     numerics section naming it (no misparse as an event JSONL);
   - ``/health`` carries the ``numerics`` verdict section, the worker
     row says ``quarantined``, and the ``tools/ps_top.py`` rendering
     shows the NaN column.

2. **Codec fidelity**: two short runs with online probes armed — the
   ``sign`` codec must report a solidly nonzero ``ps_codec_rel_error``
   and ``identity`` must report ~0 (the probe measures the codec, not
   itself).

Run via ``make numerics-smoke``. Exits nonzero on any wrong verdict.
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

STEPS = 14
NAN_FROM = 7  # worker 1 pushes NaN gradients from this step on


def base_cfg(workdir: str) -> dict:
    return {
        "model": "mlp", "model_kw": {"features": (16, 4)}, "in_shape": (8,),
        "batch": 32, "seed": 3, "optim": "sgd", "hyper": {"lr": 0.05},
        "steps": STEPS,
        "open_timeout": 60.0, "push_timeout": 60.0,
        "frame_check": True,
        "numerics": True,
        "numerics_dir": os.path.join(workdir, "telemetry"),
        "telemetry_dir": os.path.join(workdir, "telemetry"),
        "numerics_kw": {"policy": "skip", "probe_every": 3},
    }


def run_quarantine(workdir: str) -> tuple:
    """The NaN-injection run; returns (metrics, health doc, ps_top
    frame, prometheus text)."""
    cfg = base_cfg(workdir)
    cfg.update({
        "fault_plan": [{"at_step": s, "worker": 1, "kind": "nan"}
                       for s in range(NAN_FROM, STEPS)],
        "fault_seed": 1,
        "health": True, "health_dir": os.path.join(workdir, "health"),
    })
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_numsmoke_{os.getpid()}"
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

        frame = render_table(health, sort="numerics")
        return m, health, frame, prom
    finally:
        server.close()
        join_workers(procs, timeout=5.0)


def run_codec(workdir: str, codec: str, codec_kw: dict) -> dict:
    """A short probing run with ``codec`` on the wire; returns metrics."""
    from pytorch_ps_mpi_tpu.codecs import get_codec

    cfg = base_cfg(workdir)
    cfg.update({"codec": codec, "codec_kw": codec_kw, "steps": 6})
    cfg["numerics_dir"] = os.path.join(workdir, f"numerics_{codec}")
    cfg["telemetry_dir"] = cfg["numerics_dir"]
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_numprobe_{codec}_{os.getpid()}"
    server = dcn.ShmPSServer(name, num_workers=2, template=params0,
                             max_staleness=10**9, frame=True,
                             code=get_codec(codec, **codec_kw))
    procs = []
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(2)]
        _, m = serve(server, cfg, total_grads=0, total_received=2 * 6,
                     timeout=180.0)
        codes = join_workers(procs, timeout=120.0)
        if codes != [0, 0]:
            raise SystemExit(f"workers exited {codes}")
        return m
    finally:
        server.close()
        join_workers(procs, timeout=5.0)


def check_quarantine(m: dict, health: dict, frame: str, prom: str,
                     workdir: str) -> list:
    bad = []
    num = m.get("numerics") or {}
    expect_nan = STEPS - NAN_FROM
    if num.get("quarantined") != [1]:
        bad.append(f"quarantined {num.get('quarantined')} != [1]")
    if num.get("nonfinite_total") != expect_nan:
        bad.append(f"nonfinite_total {num.get('nonfinite_total')} "
                   f"!= {expect_nan}")
    if not (m["loss_final"] < m["loss_initial"]):
        bad.append(f"healthy worker did not converge through the poison: "
                   f"loss {m['loss_initial']:.4f} -> {m['loss_final']:.4f}")
    if m.get("nonfinite_total") != float(expect_nan):
        bad.append("canonical metrics key nonfinite_total missing/wrong")
    if m.get("frames_rejected_by_worker", {}).get(1) != expect_nan:
        bad.append("NaN pushes were not counted through _reject_frame")
    if not num.get("postmortems"):
        bad.append("no postmortem written")
    else:
        pm_path = num["postmortems"][0]
        if not os.path.exists(pm_path):
            bad.append(f"postmortem path missing: {pm_path}")
        else:
            pm = json.load(open(pm_path))
            if pm.get("reason") != "nonfinite" or pm.get("worker") != 1:
                bad.append(f"postmortem blames the wrong thing: {pm}")
            if not pm.get("step_stats_ring"):
                bad.append("postmortem ring buffer is empty")
    # telemetry_report must parse the dir WITHOUT choking on the
    # postmortem/numerics files, and must surface them
    from tools.telemetry_report import collect_files, format_table, summarize

    summary = summarize(collect_files([os.path.join(workdir, "telemetry")]))
    nsec = summary.get("numerics")
    if not nsec or not nsec.get("postmortems"):
        bad.append("telemetry_report numerics section missing postmortem")
    if not nsec or not (nsec.get("trajectory") or {}).get("rows"):
        bad.append("telemetry_report numerics section has no trajectory")
    format_table(summary)  # must render without raising
    # /health + ps_top
    hnum = health.get("numerics") or {}
    if hnum.get("quarantined") != [1]:
        bad.append("/health numerics section missing quarantine verdict")
    w1 = {w["worker"]: w for w in health["workers"]}[1]
    if w1["verdict"] != "quarantined":
        bad.append(f"/health worker 1 verdict {w1['verdict']!r}")
    if "quarantined" not in frame:
        bad.append("ps_top frame does not show the quarantined verdict")
    # /metrics gauges
    vals = {}
    for line in prom.splitlines():
        if line.startswith("#"):
            continue
        if " " in line:
            k, v = line.rsplit(" ", 1)
            try:
                vals[k] = float(v)
            except ValueError:
                pass
    if vals.get("ps_nonfinite_total", 0) < 1:
        bad.append(f"ps_nonfinite_total = {vals.get('ps_nonfinite_total')}")
    if vals.get('ps_worker_nonfinite_total{worker="1"}', 0) != expect_nan:
        bad.append("ps_worker_nonfinite_total{worker=1} wrong")
    if vals.get('ps_worker_nonfinite_total{worker="0"}', -1) != 0:
        bad.append("healthy worker has nonzero nonfinite count")
    if vals.get("ps_grad_norm", 0) <= 0:
        bad.append(f"ps_grad_norm = {vals.get('ps_grad_norm')}")
    return bad


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="numerics_smoke_")
    print(f"numerics-smoke: 2-worker async run, worker 1 pushes NaN "
          f"gradients from step {NAN_FROM} (workdir {workdir})")
    m, health, frame, prom = run_quarantine(workdir)
    print(frame)
    failures = check_quarantine(m, health, frame, prom, workdir)

    m_sign = run_codec(workdir, "sign", {"use_pallas": False})
    m_ident = run_codec(workdir, "identity", {})
    rel_sign = m_sign.get("codec_rel_error", 0.0)
    rel_ident = m_ident.get("codec_rel_error", 1.0)
    print(f"codec fidelity: sign rel-err={rel_sign:.4f}  "
          f"identity rel-err={rel_ident:.2e}")
    if rel_sign <= 0.05:
        failures.append(f"sign codec rel_error {rel_sign} not > 0.05")
    if rel_ident >= 1e-5:
        failures.append(f"identity codec rel_error {rel_ident} not ~0")

    if failures:
        print("\nNUMERICS-SMOKE FAILED:", file=sys.stderr)
        for b in failures:
            print(f"  - {b}", file=sys.stderr)
        return 1
    print("\nnumerics-smoke PASSED: NaN worker quarantined (healthy one "
          "converged), postmortem parseable, codec probes honest")
    return 0


if __name__ == "__main__":
    sys.exit(main())
