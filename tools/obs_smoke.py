"""Observability-plane smoke gate (``make obs-smoke``, in ``make test``).

Five legs, each a hard assert, ~a minute on CPU:

1. **armed run** — a 2-worker sync-barrier shm run over the int8 codec
   wire with EVERYTHING armed (metrics history + SLO watchdog +
   continuous profiler + lineage + fleet registration): the ``/history``
   route answers windowed queries with monotone timestamps, the
   windowed ``push_e2e_p95_ms`` history agrees with the exact lineage
   distribution within downsampling error, the collapsed-stack
   flamegraph contains the serve-loop frames, and the native fold
   cycle counters prove the C++ hot path ran;
2. **overhead** — with everything armed, the self-timed observability
   cost (TSDB sampling + SLO evaluation + profiler self-overhead) stays
   within the standing ≤5% telemetry budget;
3. **watchdog discipline** — an injected 400 ms straggler under a tight
   staleness bound trips EXACTLY ONE latched SLO burn verdict
   (``stale_drops`` burn over both windows), the healthy leg-1 run
   trips ZERO, and replaying the persisted ``timeseries-*.jsonl``
   re-derives the same verdict (PR 3 determinism discipline);
4. **fleet pane** — one ``/fleet`` scrape (served by the read tier's
   own endpoint) covers every live shard server AND the read tier,
   with summed counters and the per-shard skew section;
5. **supervisor rejoin** — a supervised run through an injected server
   crash re-registers each server generation in the fleet directory
   (two distinct registrations observed), so the respawned generation
   rejoins the pane instead of orphaning it.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

failures = []


def check(name: str, cond: bool, detail: str = "") -> None:
    status = "ok" if cond else "FAIL"
    print(f"  [{status}] {name}" + (f" — {detail}" if detail else ""),
          flush=True)
    if not cond:
        failures.append(f"{name} ({detail})")


def _get(port: int, path: str):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return json.loads(r.read().decode())


def _base_cfg(workdir: str, steps: int) -> dict:
    return {
        "model": "mlp", "model_kw": {"features": (32, 8)},
        "in_shape": [8], "batch": 16, "seed": 0, "steps": steps,
        "optim": "sgd", "hyper": {"lr": 0.05},
        "frame_check": True, "open_timeout": 120.0,
        "push_timeout": 120.0,
        "telemetry_dir": workdir,
        "timeseries": True, "slo": True, "profile": True,
        "metrics_port": 0, "tick_interval": 0.1,
    }


def leg_armed_run(workdir: str) -> None:
    """Leg 1+2: the fully-armed healthy run."""
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel import dcn
    from pytorch_ps_mpi_tpu.parallel.async_train import (
        join_workers,
        make_problem,
        serve,
        spawn_worker,
    )
    from pytorch_ps_mpi_tpu.telemetry.profiler import native_counters

    steps, workers = 8, 2
    cfg = _base_cfg(workdir, steps)
    cfg.update({
        "codec": "int8",
        "lineage": True, "lineage_dir": workdir,
        "fleet": True, "fleet_dir": os.path.join(workdir, "fleet"),
        # healthy run must be SILENT: explicit generous targets on the
        # latency rules, defaults elsewhere (stale_drops 0.2/s etc.)
        "slo_kw": {"targets": {"push_e2e_p95_ms": 10_000.0},
                   "short_window_s": 2.0, "long_window_s": 6.0,
                   "eval_every_s": 0.2},
    })
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_obs_smoke_{os.getpid()}"
    server = dcn.ShmPSServer(name, num_workers=workers,
                             template=params0,
                             code=get_codec("int8"), frame=True)
    procs = [spawn_worker(name, i, cfg) for i in range(workers)]
    t0 = time.perf_counter()
    port = None
    try:
        params, m = serve(server, cfg, total_grads=0,
                          total_received=workers * steps,
                          sync_barrier=True, timeout=180.0)
        port = m.get("metrics_port")
        wall = time.perf_counter() - t0
        codes = join_workers(procs, timeout=60.0)
        check("armed run completes", codes == [0] * workers,
              f"exit codes {codes}")

        # -- /history: queryable, monotone, matches lineage ---------------
        listing = _get(port, "/history")
        check("history keys retained", listing["keys"] >= 30
              and listing["samples"] > 0,
              f"{listing['keys']} keys, {listing['samples']} samples")
        doc = _get(port, "/history?key=grads_received&window=600")
        ts = [p[0] for p in doc["points"]]
        vals = [p[1] for p in doc["points"]]
        check("history window monotone",
              ts == sorted(ts) and vals == sorted(vals)
              and doc["stats"]["n"] > 0,
              f"{len(ts)} points, last={vals[-1] if vals else None}")
        check("history final counter state",
              vals and vals[-1] == float(workers * steps),
              f"last={vals[-1] if vals else None} want {workers * steps}")
        e2e = _get(port, "/history?key=push_e2e_p95_ms&window=600")
        lin_p95 = m["lineage"]["e2e_ms"]["p95"]
        hist_last = e2e["stats"].get("last", 0.0)
        rel = (abs(hist_last - lin_p95)
               / max(lin_p95, 1e-9)) if lin_p95 else 0.0
        check("windowed e2e p95 matches lineage",
              lin_p95 > 0 and (rel < 0.35 or abs(hist_last - lin_p95) < 5.0),
              f"history last={hist_last:.2f}ms lineage p95="
              f"{lin_p95:.2f}ms rel={rel:.2f}")

        # -- profiler: serve frames + native fold counters ----------------
        from pytorch_ps_mpi_tpu.telemetry.profiler import load_profile

        prof_path = os.path.join(workdir, "profile-server.txt")
        check("server profile written", os.path.exists(prof_path),
              prof_path)
        _, counts = load_profile(prof_path)
        has_serve = any("serve" in s and "async_train" in s
                        for s in counts)
        check("flamegraph contains serve frames", has_serve,
              f"{len(counts)} stacks")
        nat = native_counters().get("wirecodec") or {}
        check("native fold cycle counters nonzero",
              nat.get("fold_calls", 0) > 0
              and nat.get("fold_ns", 0) > 0,
              f"{nat}")
        check("aggregation really folded",
              m["agg_mode"] == 1.0 and m["decodes_per_publish"] == 1.0,
              f"agg={m['agg_mode']} dec/pub={m['decodes_per_publish']}")

        # -- SLO healthy: silent --------------------------------------------
        check("healthy run trips zero SLO verdicts",
              m["slo"]["breaches_total"] == 0,
              f"breaches={m['slo']['breaches_total']} "
              f"burning={m['slo']['burning']}")

        # -- overhead: everything armed within the ≤5% budget --------------
        hist_oh = m["history"]["overhead_s"]
        slo_oh = m["slo"]["overhead_s"]
        prof_oh = m["profile"]["overhead_frac"]
        total_frac = (hist_oh + slo_oh) / max(wall, 1e-9) + prof_oh
        check("armed observability within 5% budget",
              total_frac <= 0.05,
              f"tsdb+slo {(hist_oh + slo_oh) * 1e3:.1f}ms / "
              f"{wall:.1f}s + profiler {prof_oh * 100:.2f}% = "
              f"{total_frac * 100:.2f}%")

        # -- fleet self-registration ----------------------------------------
        fleet = _get(port, "/fleet")
        check("server registered in its own fleet pane",
              fleet["n_ok"] >= 1 and "server" in fleet["members"],
              f"members={list(fleet['members'])}")
    finally:
        server.close()
        join_workers(procs, timeout=5.0)


def leg_straggler(workdir: str) -> None:
    """Leg 3: the injected straggler trips exactly one burn verdict."""
    from pytorch_ps_mpi_tpu.parallel import dcn
    from pytorch_ps_mpi_tpu.parallel.async_train import (
        join_workers,
        make_problem,
        serve,
        spawn_worker,
    )
    from pytorch_ps_mpi_tpu.telemetry.slo import SLOWatchdog
    from pytorch_ps_mpi_tpu.telemetry.timeseries import (
        load_timeseries_rows,
    )

    # paced so the straggle and the fast stream genuinely OVERLAP (both
    # ends pay the same jax-import/compile startup): worker 0 pushes
    # every ~60 ms for ~3 s while worker 1 sleeps 500 ms per step — each
    # slow push sees ~8 published versions => staleness >> max_staleness
    # => a sustained stale-drop stream for the burn windows
    fast_steps, slow_steps = 50, 6
    cfg = _base_cfg(workdir, fast_steps)
    cfg.update({
        "worker_steps": {"0": fast_steps, "1": slow_steps},
        "slow_ms": {"0": 60.0, "1": 500.0},
        "slo_kw": {"targets": {"push_e2e_p95_ms": 10_000.0},
                   "short_window_s": 2.0, "long_window_s": 6.0,
                   "eval_every_s": 0.2},
    })
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_obs_strag_{os.getpid()}"
    server = dcn.ShmPSServer(name, num_workers=2, template=params0,
                             max_staleness=2, frame=True)
    procs = [spawn_worker(name, i, cfg) for i in range(2)]
    try:
        _, m = serve(server, cfg, total_grads=0,
                     total_received=fast_steps + slow_steps,
                     timeout=180.0)
        codes = join_workers(procs, timeout=60.0)
        check("straggler run completes", codes == [0, 0],
              f"exit codes {codes}")
        check("straggler actually dropped pushes", m["stale_drops"] >= 2,
              f"stale_drops={m['stale_drops']}")
        breaches = [v for v in m["slo"]["recent_verdicts"]
                    if v["kind"] == "breach"]
        check("straggler trips EXACTLY one burn verdict",
              m["slo"]["breaches_total"] == 1 and len(breaches) == 1
              and breaches[0]["rule"] == "stale_drops",
              f"breaches={m['slo']['breaches_total']} "
              f"verdicts={[(v['kind'], v['rule']) for v in m['slo']['recent_verdicts']]}")

        # -- replay: the persisted history re-derives the verdict ----------
        rows = load_timeseries_rows(
            os.path.join(workdir, "timeseries-server.jsonl"))
        replayed = SLOWatchdog.replay(rows, **cfg["slo_kw"])
        re_breaches = [v for v in replayed if v["kind"] == "breach"]
        check("verdict replays from persisted history",
              len(re_breaches) == 1
              and re_breaches[0]["rule"] == "stale_drops",
              f"replayed {[(v['kind'], v['rule']) for v in replayed]}")
    finally:
        server.close()
        join_workers(procs, timeout=5.0)


def leg_fleet_live(workdir: str) -> None:
    """Leg 4 (live form): scrape /fleet WHILE shards + read tier are up."""
    from pytorch_ps_mpi_tpu.parallel.dcn import _flatten
    from pytorch_ps_mpi_tpu.parallel.async_train import (
        join_workers,
        make_problem,
    )
    from pytorch_ps_mpi_tpu.parallel.sharded import (
        read_server_port,
        spawn_shard_server,
        spawn_sharded_worker,
    )
    from pytorch_ps_mpi_tpu.serving import ServingCore
    from pytorch_ps_mpi_tpu.telemetry.fleet import list_endpoints

    fleet_dir = os.path.join(workdir, "fleet")
    steps, n_shards = 8, 2
    cfg = {
        "model": "mlp", "model_kw": {"features": (32, 8)},
        "in_shape": [8], "batch": 16, "seed": 0, "steps": steps,
        "optim": "sgd", "hyper": {"lr": 0.05},
        "n_workers": 1, "metrics_port": 0,
        "timeseries": True, "fleet_dir": fleet_dir,
        # a slow shard keeps the fleet alive long enough to scrape it
        # mid-run AND exercises the skew detector
        "server_slow_ms": {"1": 150.0},
        "server_timeout": 120.0,
    }
    _, params0, _, _ = make_problem(cfg)
    core = ServingCore(None, {"read_port": 0, "metrics_port": 0,
                              "fleet_dir": fleet_dir},
                       template=params0)
    servers, snap = [], None
    worker = None
    try:
        core.publish(flat=_flatten(params0).copy())
        for sid in range(n_shards):
            servers.append(spawn_shard_server(
                sid, n_shards, cfg,
                os.path.join(workdir, f"shard{sid}.npz")))
        addrs = [f"127.0.0.1:{read_server_port(p)}" for p in servers]
        worker = spawn_sharded_worker(
            addrs, 0, cfg, os.path.join(workdir, "w0.json"))
        # wait until both shards registered, then ONE /fleet scrape
        # from the read tier's endpoint must cover all three members
        deadline = time.time() + 60.0
        while time.time() < deadline:
            names = {e["name"] for e in list_endpoints(fleet_dir)}
            if {"shard0", "shard1", "read-tier"} <= names:
                break
            time.sleep(0.1)
        best = {"n_ok": 0, "fleet": {"grads_received": 0}}

        def _score(s):
            return (s["n_ok"], s.get("fleet", {}).get(
                "grads_received", 0))

        while time.time() < deadline:
            snap = _get(core.metrics_http_port, "/fleet?force=1")
            if _score(snap) > _score(best):
                best = snap
            if best["n_ok"] >= 3 and best["fleet"].get(
                    "grads_received", 0) > 0:
                break
            if worker.poll() is not None and all(
                    p.poll() is not None for p in servers):
                break
            time.sleep(0.15)
        snap = best
        members = snap.get("members", {})
        check("one /fleet scrape covers shards + read tier",
              snap["n_ok"] >= 3
              and {"shard0", "shard1", "read-tier"} <= set(members),
              f"ok={snap['n_ok']} members={sorted(members)}")
        roles = {m["name"]: m["role"] for m in members.values()}
        check("fleet roles tagged",
              roles.get("shard0") == "shard"
              and roles.get("read-tier") == "read", f"{roles}")
        check("fleet sums shard counters",
              snap["fleet"]["grads_received"] > 0,
              f"grads={snap['fleet']['grads_received']}")
        check("skew section present", isinstance(snap.get("skew"), dict),
              f"skew={snap.get('skew')}")
        codes = join_workers([worker] + servers, timeout=120.0)
        check("sharded fleet exits cleanly", codes == [0] * (1 + n_shards),
              f"rc={codes}")
        # clean close deregistered the shards
        left = {e["name"] for e in list_endpoints(fleet_dir)}
        check("shards deregister on clean close",
              "shard0" not in left and "shard1" not in left,
              f"left={left}")
        # ps_top --fleet renders the same snapshot (pure renderer)
        from tools.ps_top import render_fleet

        frame = render_fleet(snap)
        check("ps_top --fleet renders the pane",
              "shard0" in frame and "read-tier" in frame, "")
    finally:
        for p in servers:
            if p.poll() is None:
                p.terminate()
        if worker is not None and worker.poll() is None:
            worker.terminate()
        core.close()


def leg_supervisor_rejoin(workdir: str) -> None:
    """Leg 5: a restarted server generation re-registers (rejoins)."""
    from pytorch_ps_mpi_tpu.resilience import Supervisor
    from pytorch_ps_mpi_tpu.telemetry.fleet import (
        FleetMonitor,
        list_endpoints,
    )

    fleet_dir = os.path.join(workdir, "fleet")
    cfg = {
        "model": "mlp", "model_kw": {"features": (32, 8)},
        "in_shape": [8], "batch": 16, "seed": 0, "steps": 14,
        "optim": "sgd", "hyper": {"lr": 0.05},
        "frame_check": True, "resilient": True,
        "metrics_port": 0,
        "timeseries": True,
        "fleet": True, "fleet_dir": fleet_dir,
        "fault_plan": [{"id": 0, "at_step": 8, "worker": "server",
                        "kind": "crash_server"}],
        "fault_seed": 0,
        "tick_interval": 0.1,
    }
    sup = Supervisor(cfg, 2, checkpoint_dir=os.path.join(workdir, "ckpt"),
                     checkpoint_every=3, timeout=150.0)
    result = {}

    def run():
        try:
            result["params"], result["metrics"] = sup.run()
        except BaseException as e:  # surfaced by the main thread
            result["error"] = repr(e)

    t = threading.Thread(target=run)
    t.start()
    registrations = []
    polled_ok = 0
    mon = FleetMonitor(fleet_dir=fleet_dir, min_poll_s=0.0)
    deadline = time.time() + 150.0
    while t.is_alive() and time.time() < deadline:
        for e in list_endpoints(fleet_dir):
            if e["name"] == "server" and (
                    not registrations
                    or e["registered_wall"]
                    != registrations[-1]["registered_wall"]):
                registrations.append(e)
                snap = mon.poll(force=True)
                member = snap["members"].get("server", {})
                if member.get("ok"):
                    polled_ok += 1
        time.sleep(0.05)
    t.join(timeout=30)
    check("supervised run completed", "metrics" in result,
          result.get("error", ""))
    m = result.get("metrics", {})
    check("server crash recovered",
          m.get("server_restarts", 0) >= 1,
          f"restarts={m.get('server_restarts')}")
    check("each generation re-registered (rejoined the pane)",
          len(registrations) >= 2,
          f"{len(registrations)} registrations, "
          f"{polled_ok} polled ok")
    check("live generations scrapable through the pane",
          polled_ok >= 1, f"polled_ok={polled_ok}")


def main() -> int:
    base = tempfile.mkdtemp(prefix="obs_smoke_")

    print("== leg 1+2: fully-armed run (history/profiler/SLO/fleet, "
          "overhead gate)")
    leg_armed_run(os.path.join(base, "armed"))

    print("== leg 3: straggler trips exactly one SLO burn verdict")
    os.makedirs(os.path.join(base, "strag"), exist_ok=True)
    leg_straggler(os.path.join(base, "strag"))

    print("== leg 4: one /fleet scrape covers shards + read tier")
    os.makedirs(os.path.join(base, "shards"), exist_ok=True)
    leg_fleet_live(os.path.join(base, "shards"))

    print("== leg 5: supervisor restart rejoins the fleet pane")
    os.makedirs(os.path.join(base, "sup"), exist_ok=True)
    leg_supervisor_rejoin(os.path.join(base, "sup"))

    print("== report sections over the armed run's artifacts")
    from tools.telemetry_report import summarize

    summary = summarize([os.path.join(base, "armed", f)
                         for f in os.listdir(os.path.join(base, "armed"))
                         if f.endswith((".jsonl", ".txt", ".prom"))])
    check("report history/profile sections",
          (summary.get("history") or {}).get("samples", 0) > 0
          and (summary.get("profile") or {}).get("samples", 0) > 0,
          "")

    if failures:
        print("\nOBS-SMOKE FAILED:", file=sys.stderr)
        for b in failures:
            print(f"  - {b}", file=sys.stderr)
        return 1
    print("\nobs-smoke PASSED: history queryable+monotone, profiler saw "
          "the serve loop + native folds, the watchdog flagged exactly "
          "the injected regression, one /fleet scrape covered the whole "
          "fleet incl. a supervisor restart, all within the ≤5% budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())
