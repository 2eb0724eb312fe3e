"""Read-path freshness smoke gate: age-of-information, end to end.

One star run, one seeded fault, one structural heal — the whole
freshness plane exercised live:

- The root server publishes versions with FRS1 birth records; a driver
  thread builds a REAL two-hop replica chain beside it (standalone
  ``ServingCore`` + ``FollowerLoop`` per hop) and an edge reader that
  requests freshness trailers. Healthy-phase edge delivery ages must
  stay under the gate (same-host clocks: the age is real wall delta).
- The seeded ``delay`` fault (role ``follower0``, deterministic event
  row in ``faults-follower0.jsonl``) stalls the edge follower's polls
  mid-run. The edge core keeps serving its last version, its
  ``ps_serving_age_ms`` gauge ramps, the fleet poller's
  ``serving_age_ms_max`` rollup carries it into the controller's
  persisted row, and the topo rule must trip EXACTLY ONE latched
  ``edge_age_burn`` replica scale-out whose action row carries the
  freshness evidence (``verdict.edge_age_ms``). The stall persists to
  run end, so the idle scale-in never fires — one verdict, zero flaps.
- Causal join: a worker push trace ID from the write-path lineage of a
  delivered version must resolve through the freshness flow events to
  the wall age at which the two-hop edge replica served that version.
- ``Controller.replay`` over the persisted TSDB rows must re-derive the
  action sequence (including the edge_age_burn verdict) byte-identically.

Run via ``make fresh-smoke``. Exits nonzero on any wrong verdict.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

STEPS = 60
WORKERS = 2
SERVING_KW = {"admission_depth": 64, "ring": 8, "retry_after_s": 0.01}
AGE_HI_MS = 2000.0       # controller trip point (replica_age_hi_ms)
HEALTHY_P95_MS = 1500.0  # healthy-phase edge delivery age gate


def check(name: str, cond: bool, detail: str = "") -> None:
    status = "ok" if cond else "FAIL"
    print(f"  [{status}] {name}" + (f" — {detail}" if detail else ""),
          flush=True)
    if not cond:
        raise SystemExit(f"fresh_smoke: {name} failed ({detail})")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def smoke_cfg(workdir: str) -> dict:
    tdir = os.path.join(workdir, "telemetry")
    return {
        # template MUST match serve_readonly's replica default (mlp,
        # features (64, 8), in_shape 8): the delta stream is typed
        "model": "mlp", "model_kw": {"features": (64, 8)},
        "in_shape": (8,), "batch": 32, "seed": 3,
        "optim": "sgd", "hyper": {"lr": 0.05},
        "steps": STEPS, "frame_check": True, "codec": "identity",
        "open_timeout": 60.0, "push_timeout": 60.0,
        "telemetry_dir": tdir, "control_dir": tdir,
        "lineage": True, "lineage_dir": tdir,
        "freshness": True,
        "fleet_dir": os.path.join(workdir, "fleet"),
        # paced so the stall -> age ramp -> verdict cycle completes
        # well before the workers run out of pushes
        "slow_ms": {str(w): 300.0 for w in range(WORKERS)},
        "topo_actions": True,
        "control_kw": {
            "pin": ("codec", "lr_scale", "evict", "read_tier"),
            "eval_every_s": 0.2, "warmup_s": 0.5, "window_s": 2.0,
            "replan_max": 0,
            "replica_min": 0, "replica_max": 1,
            "replica_cooldown_s": 3.0,
            # shed path neutralized: the AGE burn must be what fires
            "replica_shed_per_s": 10 ** 9,
            "replica_lag_hi": 10 ** 9,
            "replica_age_hi_ms": AGE_HI_MS,
        },
        "read_port": _free_port(),
        "serving_kw": dict(SERVING_KW),
        # the seeded slow-follower fault: an arbitrary-role delay entry
        # the driver fires deterministically at chain-build time
        "fault_plan": [{"at_step": 0, "worker": "follower0",
                        "kind": "delay", "delay_ms": 10 ** 6}],
        "fault_seed": 1, "fault_log_dir": tdir,
    }


def main() -> int:
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel import dcn
    from pytorch_ps_mpi_tpu.parallel.async_train import (
        join_workers,
        make_problem,
        serve,
        spawn_worker,
    )
    from pytorch_ps_mpi_tpu.resilience.faults import FaultInjector
    from pytorch_ps_mpi_tpu.serving import (
        FollowerLoop,
        ServingCore,
        ServingReader,
    )
    from pytorch_ps_mpi_tpu.telemetry.freshness import (
        FreshnessTracker,
        freshness_flow_events,
        load_fresh_rows,
    )
    from pytorch_ps_mpi_tpu.telemetry.lineage import trace_id

    print("== fresh_smoke: slow follower -> edge_age_burn ==", flush=True)
    workdir = tempfile.mkdtemp(prefix="fresh_smoke_")
    cfg = smoke_cfg(workdir)
    tdir = cfg["telemetry_dir"]
    _, params0, _, _ = make_problem(cfg)

    name = f"/psq_freshsmoke_{os.getpid()}"
    server = dcn.ShmPSServer(name, num_workers=WORKERS,
                             template=params0, max_staleness=10 ** 9,
                             frame=True, code=get_codec("identity"))
    state = {"error": None, "healthy_ages": [], "stall_ages": [],
             "deliveries": 0, "fault_fired": 0, "scaled_out": False,
             "joined_version": 0, "joined_age_ms": 0.0}
    stop = threading.Event()
    chain: dict = {}

    def driver():
        """Build the two-hop chain, run a healthy phase, fire the
        seeded stall, and watch for the structural heal — all against
        the live run."""
        try:
            inj = FaultInjector.from_cfg(cfg, role="follower0")
            # steady state first: version 1 lands during the workers'
            # compile warmup, so gating ages from it would measure the
            # compile gap, not propagation
            while (server.serving_core is None
                   or server.serving_core.latest_version() < 4):
                if stop.is_set():
                    return
                time.sleep(0.05)
            core_a = ServingCore(None, {
                "serving": True, "read_port": 0,
                "serving_kw": dict(SERVING_KW)}, template=params0)
            core_b = ServingCore(None, {
                "serving": True, "read_port": 0,
                "serving_kw": dict(SERVING_KW),
                # the edge publishes its own /metrics endpoint and
                # fleet card: ps_serving_age_ms is what the root's
                # fleet poller rolls up into serving_age_ms_max
                "metrics_port": 0, "fleet_dir": cfg["fleet_dir"],
                "fleet_name": "replica-edge", "fleet_role": "replica",
            }, template=params0)
            fa = FollowerLoop(core_a, "127.0.0.1", cfg["read_port"],
                              template=params0, poll_s=0.01,
                              serving_kw=SERVING_KW)
            fb = FollowerLoop(core_b, "127.0.0.1", core_a.read_port,
                              template=params0, poll_s=0.01,
                              serving_kw=SERVING_KW)
            reader = ServingReader("127.0.0.1", core_b.read_port,
                                   params0, serving_kw=SERVING_KW)
            tracker = FreshnessTracker(cfg=cfg, core=core_b,
                                       name="edge", dir=tdir)
            chain.update(core_a=core_a, core_b=core_b, fa=fa, fb=fb,
                         reader=reader, tracker=tracker)

            # -- healthy phase: both hops stepping, edge ages bounded
            for _ in range(30):
                if stop.is_set():
                    return
                fa.step()
                fb.step()
                _, ver = reader.read_params()
                if reader.fresh is not None \
                        and reader.fresh["version"] == ver:
                    row = reader.fresh_delivery_row(reader="edge0")
                    tracker.note_delivery(row)
                    state["deliveries"] += 1
                    state["healthy_ages"].append(float(row["age_ms"]))
                    if row["hop_count"] == 2:
                        state["joined_version"] = int(row["version"])
                        state["joined_age_ms"] = float(row["age_ms"])
                time.sleep(0.08)

            # -- the seeded stall: follower0 (the edge hop) stops
            # polling; its served version's age ramps unbounded
            for f in inj.faults_at(0):
                inj.fire(f)
                state["fault_fired"] += 1
            deadline = time.time() + 45.0
            last_mark = 0.0
            while time.time() < deadline and not stop.is_set():
                fa.step()  # hop 1 stays fresh — only the EDGE is stale
                if time.time() - last_mark >= 1.0:
                    last_mark = time.time()
                    row = reader.fresh_delivery_row(reader="edge0")
                    tracker.note_delivery(row)
                    state["stall_ages"].append(float(row["age_ms"]))
                ctl = getattr(server, "controller", None)
                sc = getattr(ctl, "_replicas", None) if ctl else None
                if sc is not None and sc.live >= 1:
                    state["scaled_out"] = True
                    # hold the stall to run end: age stays hot, the
                    # idle scale-in can never fire — ONE clean verdict
                time.sleep(0.1)
        except Exception as e:
            state["error"] = repr(e)

    procs = []
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(WORKERS)]
        t = threading.Thread(target=driver, daemon=True)
        t.start()
        params, m = serve(server, cfg, total_grads=0,
                          total_received=WORKERS * STEPS,
                          timeout=300.0)
        codes = join_workers(procs, timeout=120.0)
        stop.set()
        t.join(timeout=30.0)
    finally:
        stop.set()
        server.close()
        join_workers(procs, timeout=5.0)
        for k in ("reader", "fa", "fb", "tracker", "core_a", "core_b"):
            obj = chain.get(k)
            if obj is not None:
                try:
                    obj.close()
                except Exception:
                    pass

    check("workers exited cleanly", codes == [0] * WORKERS,
          f"codes={codes}")
    check("driver ran the chain without error", state["error"] is None,
          str(state["error"]))
    check("seeded slow-follower fault fired from the plan",
          state["fault_fired"] == 1
          and os.path.exists(os.path.join(tdir,
                                          "faults-follower0.jsonl")))
    ages = state["healthy_ages"]
    check("healthy two-hop deliveries observed",
          state["deliveries"] >= 10 and state["joined_version"] >= 1,
          f"deliveries={state['deliveries']}")
    p95 = sorted(ages)[min(len(ages) - 1,
                           int(round(0.95 * (len(ages) - 1))))]
    check("healthy edge p95 age under the gate",
          0.0 < p95 < HEALTHY_P95_MS, f"p95={p95:.0f}ms")
    check("stalled edge age ramped past the trip point",
          bool(state["stall_ages"])
          and max(state["stall_ages"]) >= AGE_HI_MS,
          f"max={max(state['stall_ages'] or [0]):.0f}ms")
    check("replica scaled OUT while the edge was stale",
          state["scaled_out"])

    actions = [json.loads(line) for line in
               open(os.path.join(tdir, "control-server.jsonl"))]
    rep = [a for a in actions if a["rule"] == "topo"
           and a["action"] == "replica"]
    check("exactly ONE latched edge-age verdict, freshness evidence "
          "on the row",
          len(rep) == 1 and rep[0]["new"] == 1
          and rep[0]["verdict"]["kind"] == "edge_age_burn"
          and float(rep[0]["verdict"]["edge_age_ms"]) >= AGE_HI_MS,
          json.dumps(rep))
    check("no flaps across the stall", m["control"]["flaps"] == 0,
          f"flaps={m['control']['flaps']}")

    # -- causal join: worker push trace ID -> wall age at the edge ----
    fresh_rows = load_fresh_rows(os.path.join(tdir,
                                              "freshness-edge.jsonl"))
    lineage_rows = [json.loads(line) for line in
                    open(os.path.join(tdir, "lineage-server.jsonl"))]
    ver = state["joined_version"]
    pub = next((r for r in lineage_rows if r.get("kind") == "publish"
                and int(r.get("version", -1)) == ver), None)
    check("delivered version has write-path lineage",
          pub is not None and bool(pub.get("pushes")),
          f"version={ver}")
    p0 = pub["pushes"][0]
    tid = trace_id(p0["worker"], p0.get("step", 0), p0["seq"])
    ev = freshness_flow_events(fresh_rows, lineage_rows)
    fid = next((e["id"] for e in ev if e["ph"] == "s"
                and e["args"].get("version") == ver
                and tid in e["args"].get("trace_ids", [])), None)
    check("worker push trace ID resolves into the freshness flow",
          fid is not None, f"tid={tid} version={ver}")
    hops = [e for e in ev if e["id"] == fid and e["ph"] == "t"]
    served = next((e for e in ev if e["id"] == fid
                   and e["ph"] == "f"), None)
    first_del = next((r for r in fresh_rows
                      if r.get("kind") == "delivery"
                      and int(r.get("version", -1)) == ver), None)
    check("trace ID resolves to the wall age the two-hop edge served "
          "that version at",
          len(hops) == 2 and served is not None
          and first_del is not None
          and float(served["args"]["age_ms"]) > 0.0
          and abs(float(served["args"]["age_ms"])
                  - float(first_del["age_ms"])) < 0.5,
          f"hops={len(hops)} "
          f"age={served['args']['age_ms'] if served else None}")

    # -- byte-identical replay from the persisted TSDB rows -----------
    from pytorch_ps_mpi_tpu.control import Controller
    from pytorch_ps_mpi_tpu.telemetry.timeseries import (
        load_timeseries_rows,
    )

    rows = load_timeseries_rows(
        os.path.join(tdir, "timeseries-control-server.jsonl"))
    replayed = Controller.replay(
        rows, num_workers=WORKERS, cfg=cfg,
        depth=SERVING_KW["admission_depth"], ring=SERVING_KW["ring"])
    check("replay re-derives the edge_age_burn byte-identically",
          json.dumps(replayed) == json.dumps(actions),
          f"live={len(actions)} replayed={len(replayed)}")

    print(f"fresh_smoke: PASS — healthy p95 "
          f"{p95:.0f}ms, stall max {max(state['stall_ages']):.0f}ms, "
          f"1 edge_age_burn, 0 flaps")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
