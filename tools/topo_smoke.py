"""Structural-control smoke gate: topology as a control action, live.

Two injected hotspots, each auto-healed mid-run by the engine's ``topo``
rule — no restart, no operator, zero flaps — against the SAME scenario
left static:

1. **slow_leader → group replan.** A 2-group tree whose leader 0 sleeps
   inside every fold (the ``slow_leader`` fault kind). The anatomy
   advisor must rank ``leader_fold`` the top stage and ``hot_hop`` must
   name group 0; the engine's latched ``group_replan`` action (carrying
   that verdict) promotes a new leader through run_tree's supervision
   lists, and the moved leaf repoints via ``control-topo.json``. Healed
   means the round cadence visibly recovers: the controlled run's
   serve-phase span (first→last hop of the slow leader) must beat the
   static run's, with exact composed accounting across the transition.
2. **reader_storm → replica scale-out / idle scale-in.** A star run
   with a deliberately tiny read-tier admission depth and the
   ``read_tier`` rule pinned; a storm driver (driven by the seeded
   ``reader_storm`` fault plan, role ``reader0``) fires pipelined read
   bursts until the shed burn makes the engine scale a
   ``serve_readonly --follow-endpoint`` replica OUT. Healed means the
   replica serves real parameters (probed through its own read port)
   and registered its fleet card (the /fleet membership change); the
   storm then stops and the idle tier must scale back IN — card
   deregistered, verdict ``tier_idle`` — before the run ends.
   ``Controller.replay`` over the persisted TSDB rows must re-derive
   the whole action sequence byte-identically.

Run via ``make topo-smoke``. Exits nonzero on any wrong verdict.
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

TREE_STEPS = 16
STAR_STEPS = 100
STAR_WORKERS = 2


def check(name: str, cond: bool, detail: str = "") -> None:
    status = "ok" if cond else "FAIL"
    print(f"  [{status}] {name}" + (f" — {detail}" if detail else ""),
          flush=True)
    if not cond:
        raise SystemExit(f"topo_smoke: {name} failed ({detail})")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# leg 1: slow_leader -> group replan (the tree heals its own shape)
# ---------------------------------------------------------------------------

def tree_cfg(workdir: str, controlled: bool) -> dict:
    cfg = {
        "model": "mlp", "model_kw": {"features": (16, 4)},
        "in_shape": (8,), "batch": 32, "seed": 3,
        "codec": "topk", "codec_kw": {"fraction": 0.25},
        "optim": "sgd", "hyper": {"lr": 0.05},
        "frame_check": True, "transport": "tcp",
        "max_staleness": 10 ** 9,
        "steps": TREE_STEPS, "n_workers": 4, "group_size": 2,
        "lineage": True, "lineage_dir": workdir,
        # paced leaves: one push per ~450 ms keeps traffic FLOWING for
        # the whole run (free-running leaves would queue every step at
        # the slow leader in the first second, leaving the split
        # nothing to carry)
        "slow_ms": {str(w): 450.0 for w in range(4)},
        # every fold on leader 0 sleeps 400 ms: service (0.8 s/round
        # for 2 members) falls behind arrival — a sustained structural
        # hotspot only a topology change can halve
        "fault_plan": [{"at_step": 0, "worker": "leader0",
                        "kind": "slow_leader", "slow_ms": 400}],
        "fault_seed": 1,
    }
    if controlled:
        cfg.update({
            "control_dir": workdir, "topo_actions": True,
            "control_kw": {
                "pin": ("codec", "lr_scale", "evict", "read_tier"),
                "eval_every_s": 0.2, "warmup_s": 0.5,
                "replan_cooldown_s": 0.5,
                "leader_fold_hot_frac": 0.05,
                "leader_churn_replan": 10 ** 9,  # fold-heat path only
                "replica_max": 0,
            },
        })
    return cfg


def _hop_span(lineage_dir: str, group: int) -> float:
    ts = []
    for line in open(os.path.join(lineage_dir,
                                  f"lineage-leader{group}.jsonl")):
        r = json.loads(line)
        if r.get("kind") == "hop":
            ts.append(float(r["t"]))
    return max(ts) - min(ts) if len(ts) > 1 else 0.0


def tree_leg() -> dict:
    from pytorch_ps_mpi_tpu.parallel import tree

    print("== leg 1: slow_leader -> group replan ==", flush=True)
    wd_ctl = tempfile.mkdtemp(prefix="topo_smoke_tree_ctl_")
    _, m_ctl = tree.run_tree(tree_cfg(wd_ctl, True), timeout=280.0)
    wd_st = tempfile.mkdtemp(prefix="topo_smoke_tree_static_")
    _, m_st = tree.run_tree(tree_cfg(wd_st, False), timeout=280.0)

    check("tree workers exited cleanly (both runs)",
          m_ctl["tree"]["worker_codes"] == [0] * 4
          and m_st["tree"]["worker_codes"] == [0] * 4)
    events = m_ctl["tree"].get("topo_events", [])
    replans = [e for e in events if e["act"] == "replanned"]
    check("group replan committed live, mid-run",
          bool(replans), json.dumps(events[-3:]) if events else "none")
    check("replan carries the hot-fold verdict for group 0",
          replans[0]["group"] == 0
          and replans[0]["verdict"]["kind"] == "leader_fold_hot",
          json.dumps(replans[0]))
    check("membership changed: a third group exists, leaf moved",
          len(m_ctl["tree"]["groups"]) == 3
          and m_ctl["tree"]["groups"][2] == [1],
          json.dumps(m_ctl["tree"]["groups"]))
    check("static run never reshaped",
          len(m_st["tree"]["groups"]) == 2)
    check("structural controller never flapped",
          m_ctl["control"]["flaps"] == 0
          and m_ctl["control"]["group_replans"] >= 1,
          f"flaps={m_ctl['control']['flaps']}")

    # exact composed accounting across the transition: every worker
    # push composed at the root or positively logged lost — none
    # silently dropped, none double-counted
    lost = set()
    for g in range(3):
        p = os.path.join(wd_ctl, f"lineage-leader{g}.jsonl")
        if not os.path.exists(p):
            continue
        for line in open(p):
            r = json.loads(line)
            if r.get("kind") == "leader_consume" and r.get("lost"):
                lost.add((r["worker"], r["step"], r["seq"]))
    ids = set()
    for line in open(os.path.join(wd_ctl, "lineage-server.jsonl")):
        r = json.loads(line)
        pushes = (r.get("pushes") or []) + (
            [r["push"]] if "push" in r else [])
        for p in pushes:
            for e in p.get("composed") or []:
                ids.add((e["worker"], e["step"], e["seq"]))
    expect = {(w, s, s) for w in range(4) for s in range(TREE_STEPS)}
    check("exact composed accounting across the split",
          (ids | lost) == expect and not (ids & lost),
          f"composed={len(ids)} lost={len(lost)} "
          f"expect={len(expect)}")

    # the promoted leader actually carried traffic (not vacuous: the
    # moved leaf's LATER pushes composed through it)
    hops2 = 0
    p2 = os.path.join(wd_ctl, "lineage-leader2.jsonl")
    if os.path.exists(p2):
        hops2 = sum(1 for line in open(p2)
                    if json.loads(line).get("kind") == "hop")
    check("promoted leader carried the moved leaf's pushes",
          hops2 >= 1, f"leader2 hops={hops2}")

    # healed: the slow leader gates every round, so the serve-phase
    # span (its first->last hop) contracts once its group is halved
    span_ctl = _hop_span(wd_ctl, 0)
    span_st = _hop_span(wd_st, 0)
    ratio = span_ctl / max(span_st, 1e-9)
    check("controlled beats static: round cadence recovered",
          ratio < 0.95, f"controlled={span_ctl:.2f}s "
          f"static={span_st:.2f}s ratio={ratio:.3f}")
    return {"span_ratio": ratio,
            "replans": int(m_ctl["control"]["group_replans"])}


# ---------------------------------------------------------------------------
# leg 2: reader_storm -> replica scale-out / idle scale-in
# ---------------------------------------------------------------------------

def star_cfg(workdir: str) -> dict:
    tdir = os.path.join(workdir, "telemetry")
    return {
        # template MUST match serve_readonly's replica default (mlp,
        # features (64, 8), in_shape 8): the delta stream is typed
        "model": "mlp", "model_kw": {"features": (64, 8)},
        "in_shape": (8,), "batch": 32, "seed": 3,
        "optim": "sgd", "hyper": {"lr": 0.05},
        "steps": STAR_STEPS, "frame_check": True, "codec": "identity",
        "open_timeout": 60.0, "push_timeout": 60.0,
        "telemetry_dir": tdir, "control_dir": tdir,
        "fleet_dir": os.path.join(workdir, "fleet"),
        # paced so the run outlives the full out -> quiet -> idle-in
        # cycle (~2s rate decay + 2x replica_cooldown_s of quiet)
        "slow_ms": {str(w): 300.0 for w in range(STAR_WORKERS)},
        "topo_actions": True,
        "control_kw": {
            # read_tier pinned: depth stays tiny, so the shed burn is
            # the topo rule's to fix — by adding a replica
            "pin": ("codec", "lr_scale", "evict", "read_tier"),
            "eval_every_s": 0.2, "warmup_s": 0.5, "window_s": 2.0,
            "replan_max": 0,
            "replica_min": 0, "replica_max": 1,
            # idle scale-in waits 2x this quiet: long enough for the
            # replica's boot + the smoke's serve probe, short enough
            # to fire well before the run ends
            "replica_cooldown_s": 6.0, "replica_shed_per_s": 0.5,
            "replica_lag_hi": 10 ** 9,  # idle path scales in
        },
        "read_port": _free_port(),
        "serving_kw": {"admission_depth": 2, "ring": 4,
                       "retry_after_s": 0.01},
        "fault_plan": [{"at_step": 0, "worker": "reader0",
                        "kind": "reader_storm", "bursts": 4}],
        "fault_seed": 1, "fault_log_dir": tdir,
    }


def _storm_once(port: int) -> int:
    """One pipelined burst (4 sockets x 6 back-to-back full reads,
    written before any reply is read) — overload by construction
    against admission_depth=2. Returns shed (retry) replies."""
    from pytorch_ps_mpi_tpu.serving.net import _REP, pack_request

    socks, sheds = [], 0
    try:
        for _ in range(4):
            s = socket.create_connection(("127.0.0.1", port),
                                         timeout=10.0)
            s.sendall(pack_request(0, False) * 6)
            socks.append(s)
        for s in socks:
            s.settimeout(10.0)
            for _ in range(6):
                hdr = b""
                while len(hdr) < _REP.size:
                    hdr += s.recv(_REP.size - len(hdr))
                _, kind, _, _, _, _, _, plen = _REP.unpack(hdr)
                left = int(plen)
                while left:
                    left -= len(s.recv(min(left, 65536)))
                if kind == 3:
                    sheds += 1
    finally:
        for s in socks:
            s.close()
    return sheds


def replica_leg() -> dict:
    from pytorch_ps_mpi_tpu.parallel import dcn
    from pytorch_ps_mpi_tpu.parallel.async_train import (
        join_workers,
        make_problem,
        serve,
        spawn_worker,
    )
    from pytorch_ps_mpi_tpu.resilience.faults import FaultInjector
    from pytorch_ps_mpi_tpu.telemetry.fleet import list_endpoints

    print("== leg 2: reader_storm -> replica scale-out/in ==",
          flush=True)
    workdir = tempfile.mkdtemp(prefix="topo_smoke_star_")
    cfg = star_cfg(workdir)
    tdir = cfg["telemetry_dir"]
    _, params0, _, _ = make_problem(cfg)
    from pytorch_ps_mpi_tpu.codecs import get_codec

    name = f"/psq_toposmoke_{os.getpid()}"
    server = dcn.ShmPSServer(name, num_workers=STAR_WORKERS,
                             template=params0, max_staleness=10 ** 9,
                             frame=True, code=get_codec("identity"))
    state = {"storms": 0, "sheds": 0, "error": None,
             "replica_card": None, "replica_version": 0,
             "card_gone_live": False, "storm_fired": 0,
             "scaled_out": False}
    stop = threading.Event()

    def storm_driver():
        """The reader fleet, as a seeded fault plan: fire the planned
        reader_storm (deterministic event row in faults-reader0.jsonl),
        keep bursting until the tier heals (replica up + serving), then
        go quiet so the idle scale-in can fire — all before run end."""
        try:
            inj = FaultInjector.from_cfg(cfg, role="reader0")
            port = cfg["read_port"]
            while (server.serving_core is None
                   or server.serving_core.latest_version() == 0):
                if stop.is_set():
                    return
                time.sleep(0.05)
            cycle, storming = 0, False
            deadline = time.time() + 60.0
            while time.time() < deadline and not stop.is_set():
                for f in inj.faults_at(cycle):
                    if f["kind"] == "reader_storm":
                        inj.fire(f)
                        state["storm_fired"] += 1
                        storming = True
                cycle += 1
                ctl = getattr(server, "controller", None)
                sc = getattr(ctl, "_replicas", None) if ctl else None
                if storming:
                    state["sheds"] += _storm_once(port)
                    state["storms"] += 1
                    if sc is not None and sc.live >= 1:
                        # the engine acted: stop bursting NOW so the
                        # tier sees ONE clean out -> quiet -> idle-in
                        # cycle (bursts landing during the heal probe
                        # re-trip scale-out and count as flaps)
                        storming = False
                        state["scaled_out"] = True
                    else:
                        time.sleep(0.4)
                    continue
                if (state["scaled_out"] and sc is not None
                        and state["replica_card"] is None):
                    # quiet side: verify the heal once — hello, fleet
                    # card, and a real read through the replica's port
                    hellos = sc.hellos(timeout=60.0)
                    cards = []
                    for _ in range(40):  # card rides the replica boot
                        cards = [e for e in list_endpoints(cfg["fleet_dir"])
                                 if e["name"].startswith("replica-")]
                        if cards:
                            break
                        time.sleep(0.25)
                    if hellos and cards:
                        from pytorch_ps_mpi_tpu.serving import (
                            ServingReader,
                        )

                        r = ServingReader("127.0.0.1",
                                          int(hellos[0]["read_port"]),
                                          params0)
                        v = 0
                        try:
                            for _ in range(120):  # follower syncs async
                                try:
                                    _, v = r.read_params()
                                except Exception:
                                    v = 0
                                if v >= 1:
                                    break
                                time.sleep(0.25)
                        finally:
                            r.client.close()
                        state["replica_card"] = cards[0]["name"]
                        state["replica_version"] = int(v)
                    continue
                # healed + quiet: watch for the live scale-in
                cards = [e for e in list_endpoints(cfg["fleet_dir"])
                         if e["name"].startswith("replica-")]
                if state["replica_card"] and not cards:
                    state["card_gone_live"] = True
                    return
                time.sleep(0.25)
        except Exception as e:
            state["error"] = repr(e)

    procs = []
    try:
        procs = [spawn_worker(name, i, cfg)
                 for i in range(STAR_WORKERS)]
        t = threading.Thread(target=storm_driver, daemon=True)
        t.start()
        params, m = serve(server, cfg, total_grads=0,
                          total_received=STAR_WORKERS * STAR_STEPS,
                          timeout=300.0)
        codes = join_workers(procs, timeout=120.0)
        t.join(timeout=90.0)
    finally:
        stop.set()
        server.close()
        join_workers(procs, timeout=5.0)

    check("star workers exited cleanly", codes == [0] * STAR_WORKERS,
          f"codes={codes}")
    check("storm driver ran from the seeded fault plan",
          state["error"] is None and state["storm_fired"] == 1
          and state["storms"] >= 1, json.dumps(state))
    check("reader_storm event row persisted deterministically",
          os.path.exists(os.path.join(tdir, "faults-reader0.jsonl")))
    check("shed burn built under the pinned depth",
          state["sheds"] > 0 and m["reads_shed"] > 0,
          f"sheds={state['sheds']}")
    check("replica scaled OUT and served the model (fleet card up)",
          state["replica_card"] is not None
          and state["replica_version"] >= 1,
          json.dumps({k: state[k] for k in
                      ("replica_card", "replica_version")}))

    actions = [json.loads(line) for line in
               open(os.path.join(tdir, "control-server.jsonl"))]
    rep = [a for a in actions if a["rule"] == "topo"
           and a["action"] == "replica"]
    check("scale-out carried the shed_pressure verdict",
          bool(rep) and rep[0]["new"] == 1
          and rep[0]["verdict"]["kind"] == "shed_pressure",
          json.dumps(rep[0]) if rep else "none")
    check("idle tier scaled back IN before run end (one clean cycle)",
          len(rep) == 2 and rep[-1]["new"] == 0
          and rep[-1]["verdict"]["kind"] == "tier_idle"
          and state["card_gone_live"],
          json.dumps(rep))
    check("every action row carries its verdict id + rule",
          all(isinstance(a.get("verdict"), dict)
              and "id" in a["verdict"] and "rule" in a["verdict"]
              for a in actions))
    check("no flaps across the storm cycle",
          m["control"]["flaps"] == 0,
          f"flaps={m['control']['flaps']}")

    # byte-identical replay from the persisted TSDB rows
    from pytorch_ps_mpi_tpu.control import Controller
    from pytorch_ps_mpi_tpu.telemetry.timeseries import (
        load_timeseries_rows,
    )

    rows = load_timeseries_rows(
        os.path.join(tdir, "timeseries-control-server.jsonl"))
    replayed = Controller.replay(
        rows, num_workers=STAR_WORKERS, cfg=cfg,
        depth=cfg["serving_kw"]["admission_depth"],
        ring=cfg["serving_kw"]["ring"])
    check("replay re-derives the structural actions byte-identically",
          json.dumps(replayed) == json.dumps(actions),
          f"live={len(replayed)} replayed={len(actions)}")
    return {"replica_actions": len(rep)}


def main() -> int:
    tree_out = tree_leg()
    star_out = replica_leg()
    print(f"topo_smoke: PASS — replans={tree_out['replans']} "
          f"span ratio {tree_out['span_ratio']:.3f}, "
          f"{star_out['replica_actions']} replica actions, 0 flaps")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
