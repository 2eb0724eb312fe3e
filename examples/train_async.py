"""Async (AsySG-InCon) training CLI — the reference's README pseudo-code
(``/root/reference/README.md:61-81``: workers compute gradients against
whatever parameters they last read; a parameter server applies them in
arrival order) as an actual runnable, with real jitted compute in every
process (``parallel/async_train.py``).

The server runs in this process, on the host backend: it must not hold
a chip. Each worker is its own OS process with its own JAX runtime —
on the host backend too unless the caller of :func:`main` places it
(``worker_env``), because a chip belongs to one process at a time.
Gradients travel as codec-encoded payload bytes through the native
shared-memory transport (``native/psqueue.cpp``).

Examples:
  python examples/train_async.py --model mlp --workers 4 --steps 50
  python examples/train_async.py --model resnet18 --codec sign \
      --workers 4 --steps 10 --straggler-ms 500 --max-staleness 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # server process: host backend

from pytorch_ps_mpi_tpu.parallel import dcn
from pytorch_ps_mpi_tpu.parallel.async_train import (
    join_workers,
    make_problem,
    serve,
    spawn_worker,
)
from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache


def main(argv=None, worker_env=None):
    """``worker_env`` is handed to every ``spawn_worker`` as its ``env``
    — how a caller that owns a chip (``chip_smoke.py`` phase (d)) puts
    one worker on it; the command line places nothing."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["mlp", "resnet18", "resnet50"],
                    default="mlp")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--steps", type=int, default=50,
                    help="gradient pushes per worker")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--optim", choices=["sgd", "adam"], default="sgd")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--codec", default=None,
                    help="codec registry name (e.g. sign, int8, threshold)")
    ap.add_argument("--bucket-mb", type=float, default=0.0,
                    help="with --codec (a bucketable one): ship dtype-"
                         "grouped ~N MB flat bucket payloads per push "
                         "instead of per-leaf fragments; one flag "
                         "configures server AND workers (the wire "
                         "agreement has a single source)")
    ap.add_argument("--max-staleness", type=int, default=4)
    ap.add_argument("--straggler-ms", type=float, default=0.0,
                    help="inject this delay into the last worker's loop")
    ap.add_argument("--sync-barrier", action="store_true",
                    help="synchronous-PS oracle mode (for comparison runs)")
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--transport", default="shm", choices=["shm", "tcp"],
                    help="PS wire: shm (co-hosted processes) or tcp (the "
                         "cross-host DCN-role transport)")
    ap.add_argument("--port", type=int, default=0,
                    help="tcp transport: listen port (0 = auto)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="checkpoint the PS state every --checkpoint-every "
                         "applied gradients")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest PS checkpoint before serving")
    ap.add_argument("--telemetry-dir", default=None,
                    help="ONE flag, full telemetry: FlightRecorder JSONL "
                         "from the server and every worker, a Prometheus "
                         "/metrics endpoint (tcp transport; port in the "
                         "final metrics line), a merged host+device "
                         "Perfetto trace (trace.json), and a per-phase "
                         "report — all dropped in this directory")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics (+ /health) on this "
                         "port — both transports (0 = auto; implied =0 "
                         "by --telemetry-dir)")
    ap.add_argument("--health-port", type=int, default=None,
                    help="arm the online HealthMonitor and serve its "
                         "/health JSON (per-worker verdicts, straggler "
                         "attribution, anomaly flags) beside /metrics "
                         "on this port (0 = auto). Worker beacon files "
                         "land in --telemetry-dir when set, else a temp "
                         "dir")
    ap.add_argument("--ps-top", action="store_true",
                    help="run the tools/ps_top.py live dashboard against "
                         "the /health endpoint for the duration of the "
                         "run (implies --health-port 0; with --supervise "
                         "pass an explicit --health-port so the pinned "
                         "port survives server restarts)")
    ap.add_argument("--trace", action="store_true", default=None,
                    help="arm end-to-end gradient lineage tracing: every "
                         "framed push carries a causal trace ID (worker, "
                         "step, seq) + encode timestamp, every published "
                         "version gets a lineage-server.jsonl row naming "
                         "its composing pushes, exact per-push e2e/"
                         "staleness land in /metrics, and the merged "
                         "trace.json gains cross-process flow arrows "
                         "(worker push span -> server consume span, "
                         "clock-skew corrected). Needs --telemetry-dir "
                         "(artifacts land there) and frame checking "
                         "(the trace ID rides the v2 frame header)")
    ap.add_argument("--no-trace", dest="trace", action="store_false",
                    help="disable lineage tracing (it is otherwise "
                         "implied by --telemetry-dir)")
    ap.add_argument("--numerics", action="store_true",
                    help="arm the NumericsMonitor: every consumed push "
                         "is validated (NaN/Inf counted per worker, the "
                         "worker quarantined), grad-norm/update-ratio "
                         "stats flow into /metrics + /health, workers "
                         "probe codec fidelity online, and a NaN or "
                         "norm spike writes a postmortem-*.json into "
                         "the numerics dir (--telemetry-dir when set)")
    ap.add_argument("--numerics-policy", choices=["skip", "zero", "abort"],
                    default="skip",
                    help="what happens to a non-finite push: skip it "
                         "(default), zero its bad elements and apply "
                         "the rest, or abort the run with a postmortem")
    ap.add_argument("--numerics-probe-every", type=int, default=25,
                    help="codec-fidelity probe / trajectory-row cadence "
                         "(steps)")
    ap.add_argument("--read-port", type=int, default=None,
                    help="arm the parameter-serving read tier on this "
                         "port (0 = auto; bound port in the final "
                         "metrics line as read_port): versioned "
                         "snapshot ring, version-conditional reads "
                         "(not-modified / delta / full), request "
                         "coalescing, admission-control load shedding. "
                         "Readers: pytorch_ps_mpi_tpu.serving."
                         "ServingReader")
    ap.add_argument("--snapshot-ring", type=int, default=None,
                    help="with --read-port: versions kept for delta "
                         "reads (default 8)")
    ap.add_argument("--history", action="store_true",
                    help="arm the in-process metrics TSDB: every "
                         "canonical metric key retained as ring-"
                         "buffered history (raw + 1s/10s/60s tiers), "
                         "persisted as timeseries-server.jsonl in "
                         "--telemetry-dir and served at /history")
    ap.add_argument("--profile", action="store_true",
                    help="arm the continuous sampling profiler (~100 Hz "
                         "collapsed-stack flamegraph text with a hard "
                         "self-overhead budget) in the server AND every "
                         "worker; profile-*.txt land in --telemetry-dir "
                         "and merge in the report")
    ap.add_argument("--slo", action="store_true",
                    help="arm the SLO burn-rate watchdog over the "
                         "metrics history (implies --history): latched "
                         "breach/recover verdicts into slo-server.jsonl "
                         "+ the flight recorder, an 'slo' section in "
                         "/health, and ps_slo_* scrape instruments")
    ap.add_argument("--slo-target", action="append", default=[],
                    help="override one SLO target, KEY=VALUE "
                         "(repeatable; e.g. push_e2e_p95_ms=250)")
    ap.add_argument("--freshness", action="store_true",
                    help="arm the read-path freshness tracker: every "
                         "published version's FRS1 birth record becomes "
                         "publish->visible latency distributions, the "
                         "serving_age_ms age-of-information gauge, and "
                         "freshness-server.jsonl propagation rows in "
                         "--telemetry-dir")
    ap.add_argument("--hop-anatomy", action="store_true",
                    help="arm leader-hop occupancy tracing (tree "
                         "topology): per-round sub-stage timelines "
                         "(ingest_wait/validate/fold/finalize/encode/"
                         "push) from bounded native interval rings, "
                         "hop-leaderN.jsonl rows, the hop_busy_frac / "
                         "hop_stream_headroom_ratio scoreboard")
    ap.add_argument("--control", action="store_true",
                    help="arm the self-driving controller (requires "
                         "--telemetry-dir for its action/replay rows): "
                         "verdicts become recorded reversible actions — "
                         "staleness LR de-weighting, evict/readmit, "
                         "read-tier tuning, and (with a ladder via "
                         "cfg['control_kw']) codec renegotiation")
    ap.add_argument("--fleet-dir", default=None,
                    help="fleet registration directory: this server "
                         "registers its endpoint there (re-registering "
                         "across supervisor restarts) and serves the "
                         "merged /fleet snapshot; watch the pane with "
                         "tools/ps_top.py --fleet DIR")
    ap.add_argument("--no-frame-check", action="store_true",
                    help="disable the self-verifying wire frames (CRC + "
                         "config fingerprint on every push; on by default "
                         "— one cfg configures both ends, so the frame "
                         "header is part of the wire agreement)")
    ap.add_argument("--resilient", action="store_true",
                    help="workers retry/backoff on timeouts and reconnect "
                         "on EOF instead of dying (survives a server "
                         "restart-from-checkpoint)")
    ap.add_argument("--supervise", action="store_true",
                    help="run under the resilience Supervisor: dead "
                         "workers are respawned, a crashed server is "
                         "restarted with --resume from --checkpoint-dir; "
                         "implies --resilient")
    ap.add_argument("--fault-plan", default=None,
                    help="deterministic chaos: a JSON fault-plan list, or "
                         "@path/to/plan.json (entries "
                         "{at_step, worker, kind}; kinds drop/delay/"
                         "duplicate/corrupt/crash_worker/crash_server)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for fault randomness (corrupt byte "
                         "positions, backoff jitter): same plan + seed = "
                         "same injected-event log")
    ap.add_argument("--fault-log-dir", default=None,
                    help="directory for per-process injected-fault JSONLs "
                         "(defaults to --telemetry-dir when set)")
    args = ap.parse_args(argv)
    enable_compilation_cache()
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    if args.supervise:
        args.resilient = True
    fault_plan = None
    if args.fault_plan:
        try:  # parse ONCE; validation and cfg use the same object
            fault_plan = _parse_fault_plan(args.fault_plan)
        except (ValueError, OSError) as e:
            ap.error(f"--fault-plan is not valid JSON (or @file): {e}")
        if not args.supervise:
            # the plain serve path stops on a FIXED received count, which
            # drop/corrupt faults make unreachable (600 s hang) and
            # crash_worker turns into a dead fleet member nobody respawns
            # — only the supervisor's workers-done stop condition
            # tolerates a fault plan
            ap.error("--fault-plan requires --supervise")
        if any(f.get("kind") == "crash_server" for f in fault_plan
               ) and not args.checkpoint_dir:
            ap.error("a crash_server fault needs --checkpoint-dir to be "
                     "survivable")

    in_shape = (8,) if args.model == "mlp" else (32, 32, 3)
    cfg = {
        "model": args.model,
        "model_kw": {"num_classes": 10} if args.model != "mlp" else
                    {"features": (64, 8)},
        "in_shape": list(in_shape),
        "batch": args.batch,
        "seed": 0,
        "optim": args.optim,
        "hyper": {"lr": args.lr},
        "steps": args.steps,
        "open_timeout": args.timeout,
        "push_timeout": args.timeout,
    }
    if args.codec:
        cfg["codec"] = args.codec
        if args.bucket_mb:
            cfg["bucket_mb"] = args.bucket_mb
    if args.straggler_ms:
        cfg["slow_ms"] = {str(args.workers - 1): args.straggler_ms}
    # one flag, both ends: the frame header joins the wire agreement the
    # way the codec config and bucket_mb already do
    cfg["frame_check"] = not args.no_frame_check
    if args.resilient:
        cfg["resilient"] = True
        # resilient workers need SHORT op timeouts — the retry/backoff
        # loop supplies the patience, and a failover is only detected
        # when a push times out (a push into a dead server's orphaned
        # mailbox blocks the full timeout before the reconnect fires)
        cfg["push_timeout"] = min(float(args.timeout), 10.0)
    if fault_plan is not None:
        cfg["fault_plan"] = fault_plan
        cfg["fault_seed"] = args.fault_seed
        fault_log = args.fault_log_dir or args.telemetry_dir
        if fault_log:
            import glob

            os.makedirs(fault_log, exist_ok=True)
            # fault logs APPEND (respawned workers must extend, not
            # clobber, their generation-0 rows) — so a reused dir must
            # be cleared at RUN start or the identical-replay comparison
            # sees the previous run's rows too
            for stale in glob.glob(os.path.join(fault_log,
                                                "faults-*.jsonl")):
                os.remove(stale)
            cfg["fault_log_dir"] = fault_log
    if args.telemetry_dir:
        import glob

        os.makedirs(args.telemetry_dir, exist_ok=True)
        # a reused dir must not leak a previous run's files into this
        # run's merged trace/report (worker counts can differ) —
        # numerics trajectories and postmortems included
        for stale in glob.glob(os.path.join(args.telemetry_dir, "*.jsonl")) \
                + glob.glob(os.path.join(args.telemetry_dir, "trace.json")) \
                + glob.glob(os.path.join(args.telemetry_dir,
                                         "postmortem-*.json")) \
                + glob.glob(os.path.join(args.telemetry_dir,
                                         "profile-*.txt")):
            os.remove(stale)
        cfg["telemetry_dir"] = args.telemetry_dir
        if args.metrics_port is None:
            args.metrics_port = 0
    if (args.history or args.slo or args.profile) \
            and not args.telemetry_dir:
        ap.error("--history/--slo/--profile need --telemetry-dir (their "
                 "timeseries-/slo-/profile- artifacts land there)")
    if args.slo_target and not args.slo:
        ap.error("--slo-target needs --slo")
    if args.history or args.slo:
        cfg["timeseries"] = True
    if args.slo:
        cfg["slo"] = True
        if args.slo_target:
            targets = {}
            for kv in args.slo_target:
                k, _, v = kv.partition("=")
                try:
                    targets[k] = float(v)
                except ValueError:
                    ap.error(f"--slo-target {kv!r} is not KEY=FLOAT")
            cfg["slo_kw"] = {"targets": targets}
    if args.profile:
        cfg["profile"] = True
    if args.freshness:
        cfg["freshness"] = True
    if args.hop_anatomy:
        cfg["hop_anatomy"] = True
    if args.control:
        if not args.telemetry_dir:
            ap.error("--control needs --telemetry-dir (action rows, "
                     "replay input rows and control-epoch.json land "
                     "there)")
        cfg["control"] = True
        cfg["control_dir"] = args.telemetry_dir
    if args.fleet_dir:
        cfg["fleet_dir"] = args.fleet_dir
        if args.metrics_port is None:
            args.metrics_port = 0  # registration needs a live endpoint
    # lineage tracing: explicit --trace demands its prerequisites; the
    # default (no flag) arms it whenever they are already met — one
    # --telemetry-dir flag keeps meaning "full telemetry"
    if args.trace:
        if not args.telemetry_dir:
            ap.error("--trace needs --telemetry-dir (lineage rows and "
                     "the flow-event trace land there)")
        if not cfg["frame_check"]:
            ap.error("--trace needs frame checking (the trace ID rides "
                     "the v2 frame header); drop --no-frame-check")
    if (args.trace or (args.trace is None and args.telemetry_dir
                       and cfg["frame_check"])):
        cfg["lineage"] = True
        cfg["lineage_dir"] = args.telemetry_dir
    if args.numerics:
        import tempfile

        cfg["numerics"] = True
        # one dir, both ends: workers append probe rows here, the server
        # tails them and drops postmortems beside them
        cfg["numerics_dir"] = (args.telemetry_dir
                               or tempfile.mkdtemp(prefix="ps_numerics_"))
        cfg["numerics_kw"] = {"policy": args.numerics_policy,
                              "probe_every": args.numerics_probe_every}
    if args.metrics_port is not None:
        cfg["metrics_port"] = args.metrics_port
    if args.read_port is not None:
        cfg["read_port"] = args.read_port
        if args.snapshot_ring is not None:
            cfg["serving_kw"] = {"ring": args.snapshot_ring}
    elif args.snapshot_ring is not None:
        ap.error("--snapshot-ring needs --read-port (it sizes the read "
                 "tier's snapshot ring)")
    if args.ps_top and args.health_port is None:
        if args.supervise:
            ap.error("--ps-top with --supervise needs an explicit "
                     "--health-port (the dashboard must re-find the "
                     "endpoint across server restarts)")
        args.health_port = 0
    if args.health_port is not None:
        cfg["health_port"] = args.health_port
        if "health_dir" not in cfg:
            import tempfile

            cfg["health_dir"] = (args.telemetry_dir
                                 or tempfile.mkdtemp(prefix="ps_health_"))

    if args.supervise:
        from pytorch_ps_mpi_tpu.resilience import Supervisor

        if args.transport == "tcp":
            cfg["transport"] = "tcp"
        cfg["max_staleness"] = args.max_staleness
        if args.resume:
            cfg["resume"] = True
        sup = Supervisor(
            cfg, args.workers, port=args.port,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            sync_barrier=args.sync_barrier, timeout=args.timeout,
        )
        top = _spawn_ps_top(args.health_port) if args.ps_top else None
        try:
            params, metrics = sup.run()
        finally:
            _stop_ps_top(top)
        if args.telemetry_dir:
            # merged trace + report from the per-process JSONLs
            metrics.update(_export_telemetry(args.telemetry_dir))
        print(json.dumps(metrics, default=str))
        return metrics

    code = None
    if args.codec:
        from pytorch_ps_mpi_tpu.codecs import get_codec

        code = get_codec(args.codec)

    _, params0, _, _ = make_problem(cfg)
    if args.transport == "tcp":
        from pytorch_ps_mpi_tpu.parallel import tcp

        cfg["transport"] = "tcp"
        server = tcp.TcpPSServer(
            args.port, num_workers=args.workers, template=params0,
            max_staleness=args.max_staleness, code=code,
            bucket_mb=cfg.get("bucket_mb", 0.0),
            frame=cfg["frame_check"],
        )
        name = f"127.0.0.1:{server.port}"
        print(f"tcp PS listening on {name}")
    else:
        name = f"/psq_train_{os.getpid()}"
        server = dcn.ShmPSServer(
            name, num_workers=args.workers, template=params0,
            max_staleness=args.max_staleness, code=code,
            bucket_mb=cfg.get("bucket_mb", 0.0),
            frame=cfg["frame_check"],
        )
    total = args.workers * args.steps
    procs = []
    top = None
    if args.ps_top:
        # bind the /metrics + /health endpoint NOW (serve()'s own call is
        # idempotent and returns this same port) so the dashboard can
        # attach before the first gradient flows — on the SAME port
        # serve() would pick (metrics_port wins over health_port there),
        # so an explicit --metrics-port is honored, never shadowed
        bound = server.start_metrics_http(
            args.metrics_port if args.metrics_port is not None
            else args.health_port)
        print(f"/health live on port {bound}")
        top = _spawn_ps_top(bound)
    try:
        procs = [spawn_worker(name, i, cfg, env=worker_env)
                 for i in range(args.workers)]
        params, metrics = serve(
            server, cfg, total_grads=0, total_received=total,
            sync_barrier=args.sync_barrier, timeout=args.timeout,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every, resume=args.resume,
        )
        for rc in join_workers(procs, timeout=args.timeout):
            if rc != 0:
                raise SystemExit(f"worker exited {rc}")
    finally:
        _stop_ps_top(top)
        # server.close() also tears down the /metrics + /health endpoint
        # (PSServerTelemetry.close_metrics_http) — no leaked sockets
        server.close()
        # never leave orphan workers if serve() raised: terminate + reap
        join_workers(procs, timeout=5.0)

    if args.telemetry_dir:
        metrics.update(_export_telemetry(args.telemetry_dir))
    print(json.dumps(metrics, default=str))
    return metrics


def _spawn_ps_top(port):
    """Launch the live dashboard against the local /health endpoint."""
    import subprocess

    script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "ps_top.py",
    )
    return subprocess.Popen([sys.executable, script, str(int(port))])


def _stop_ps_top(proc) -> None:
    if proc is None or proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=5)
    except Exception:
        proc.kill()


def _parse_fault_plan(spec: str):
    """A fault plan from the CLI: inline JSON, or ``@file.json``."""
    if spec.startswith("@"):
        with open(spec[1:]) as f:
            return json.load(f)
    return json.loads(spec)


def _export_telemetry(tdir: str) -> dict:
    """Merge every process's JSONL into trace.json, print the per-phase
    report, return artifact paths.

    When lineage files are present (``--trace``), the worker JSONLs are
    first shifted onto the server's clock by the per-worker offsets
    fitted from the frame send/recv timestamp pairs, and the trace gains
    cross-process flow events (arrows) linking each worker push span to
    its server consume span."""
    import glob

    from pytorch_ps_mpi_tpu.telemetry import (
        clock_offsets_from_rows,
        export_chrome_trace,
        is_sidecar,
        load_jsonl,
        load_lineage_rows,
    )
    from tools.telemetry_report import format_table, summarize

    # sidecar JSONLs (fault logs, beacons, numerics trajectories,
    # lineage compositions, anatomy rounds, retained histories, SLO
    # verdicts, controller actions) are not flight-recorder files: the
    # shared SIDECAR_PREFIXES registry (pytorch_ps_mpi_tpu.telemetry)
    # routes them away from the merged trace here AND from
    # telemetry_report's dir-mode span merge — one list, enforced by
    # psanalyze's sidecar-registry rule, instead of the two
    # hand-patched copies every observability PR used to edit
    files = sorted(f for f in glob.glob(os.path.join(tdir, "*.jsonl"))
                   if not is_sidecar(f))
    events = []
    for f in files:
        events.extend(load_jsonl(f)[1])
    lineage_files = sorted(glob.glob(os.path.join(tdir, "lineage-*.jsonl")))
    lineage_rows = []
    for f in lineage_files:
        lineage_rows.extend(load_lineage_rows(f))
    offsets = clock_offsets_from_rows(lineage_rows) if lineage_rows else None
    # hop-anatomy rows add one trace track per tree leader (sub-stage
    # spans the composed lineage arrows thread through)
    from pytorch_ps_mpi_tpu.telemetry import load_hop_rows

    hop_rows = []
    for f in sorted(glob.glob(os.path.join(tdir, "hop-*.jsonl"))):
        hop_rows.extend(load_hop_rows(f))
    trace_path, counts = export_chrome_trace(
        os.path.join(tdir, "trace.json"), events,
        lineage_rows=lineage_rows or None, clock_offsets=offsets,
        hop_rows=hop_rows or None,
    )
    # every sidecar with a report route joins the printed report through
    # its own section (numerics/lineage/anatomy/history/slo/actions),
    # never the span merge — the same registry decides both directions
    from pytorch_ps_mpi_tpu.telemetry import (
        SIDECAR_PREFIXES,
        sidecar_prefix,
    )

    section_files = sorted(
        f for f in glob.glob(os.path.join(tdir, "*.jsonl"))
        if SIDECAR_PREFIXES.get(sidecar_prefix(f) or "") is not None)
    obs_files = sorted(glob.glob(os.path.join(tdir, "profile-*.txt")))
    print(format_table(summarize(files + section_files + obs_files,
                                 by_worker=False)))
    out = {
        "telemetry_trace": trace_path,
        "telemetry_trace_host_events": counts["host"],
        "telemetry_files": files,
    }
    if lineage_rows:
        out["telemetry_trace_flow_events"] = counts["flow"]
        out["clock_offsets"] = offsets
    if hop_rows:
        out["telemetry_trace_hop_events"] = counts["hop"]
    return out


if __name__ == "__main__":
    main()
