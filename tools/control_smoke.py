"""Self-driving control-plane smoke gate: the controller must act, help,
replay, and never flap.

One canned straggler+NaN+overload run (CPU-only, shm transport) with the
controller armed, against the SAME scenario uncontrolled:

1. **Codec downshift.** Every healthy worker's steps are wire-dominant
   (injected delay faults ride the beacon wire bucket), so the
   controller must renegotiate the wire identity→int8 mid-run — an
   epoch bump through the frame-fingerprint handshake. Zero frames may
   be lost: in-flight old-epoch frames are consumed (counted in
   ``epoch_old_frames``), and the healthy workers end the run with zero
   rejections. A compact TCP leg re-proves the zero-loss transition on
   the second transport (native batch path bypassed mid-transition,
   re-armed after retire).
2. **Staleness de-weighting.** Worker 1 is a deliberate straggler whose
   exact staleness runs far above the fleet median — the controller
   must de-weight exactly its pushes (AsySG-InCon LR scaling), and
   nobody else's.
3. **Quarantine→probation readmission.** Worker 2 pushes NaN gradients
   early, is quarantined by the numerics layer, then runs clean — the
   controller must readmit it after the probation window, and its
   later healthy pushes must be applied (the uncontrolled run rejects
   them wholesale, which is exactly why the controlled loss wins).
4. **Read-tier tuning.** A reader storm against ``admission_depth=2``
   must shed; the controller must raise the depth until a later storm
   completes shed-free — service restored under the same offered load.
5. **Replay.** ``Controller.replay`` over the persisted TSDB input rows
   (``timeseries-control-server.jsonl``) must re-derive the action
   sequence BYTE-identically, and neither the live run nor
   ``tools/telemetry_report.py``'s flap check may find a flap.
6. **The controller helps.** The controlled run's final loss must beat
   the uncontrolled run's.

Run via ``make control-smoke``. Exits nonzero on any wrong verdict.
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

import numpy as np

from pytorch_ps_mpi_tpu.parallel import dcn
from pytorch_ps_mpi_tpu.parallel.async_train import (
    join_workers,
    make_problem,
    serve,
    spawn_worker,
)


STEPS = 30
NAN_STEPS = (2, 3)
WORKERS = 3


def check(name: str, cond: bool, detail: str = "") -> None:
    status = "ok" if cond else "FAIL"
    print(f"  [{status}] {name}" + (f" — {detail}" if detail else ""))
    if not cond:
        raise SystemExit(f"control_smoke: {name} failed ({detail})")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def scenario_cfg(workdir: str, controlled: bool) -> dict:
    tdir = os.path.join(workdir, "telemetry")
    cfg = {
        # big enough that a read reply is real work (~68 KB snapshot —
        # the admission backlog can actually build under a storm), small
        # enough that a CPU step stays sub-ms
        "model": "mlp", "model_kw": {"features": (64, 8)},
        "in_shape": (256,), "batch": 32, "seed": 3, "optim": "sgd",
        # lr 0.3 puts the run in the regime the AsySG-InCon bound is
        # ABOUT: the straggler's stale-7 pushes at full weight visibly
        # destabilize convergence, so the controller's de-weighting has
        # something real to rescue (at low lr stale pushes are benign
        # and the controlled/uncontrolled gap vanishes)
        "hyper": {"lr": 0.3}, "steps": STEPS,
        "open_timeout": 60.0, "push_timeout": 60.0,
        "frame_check": True, "codec": "identity",
        "health": True, "health_dir": os.path.join(workdir, "health"),
        "numerics": True, "numerics_dir": tdir,
        "numerics_kw": {"policy": "skip", "probe_every": 3},
        "telemetry_dir": tdir,
        "slow_ms": {"1": 600.0},
        # the wire-dominant fleet: delays land in the beacon wire bucket
        "fault_plan": (
            [{"at_step": s, "worker": w, "kind": "delay",
              "delay_ms": 80.0}
             for s in range(STEPS) for w in (0, 2)]
            + [{"at_step": s, "worker": 2, "kind": "nan"}
               for s in NAN_STEPS]),  # early: the readmitted worker's
        #                               remaining healthy pushes are the
        #                               uncontrolled run's dead loss
        "fault_seed": 1,
    }
    if controlled:
        cfg.update({
            "control": True, "control_dir": tdir,
            "control_kw": {
                "eval_every_s": 0.25, "warmup_s": 1.0,
                "cooldown_s": 1.0, "settle_s": 3.0, "window_s": 3.0,
                "probation_s": 1.5, "shed_hi_per_s": 0.5,
                "ladder": [{"codec": "identity"}, {"codec": "int8"}],
                "read_p95_target_ms": 200.0,
            },
            "read_port": _free_port(),
            "serving_kw": {"admission_depth": 2, "ring": 4,
                           "retry_after_s": 0.01},
        })
    return cfg


def run_scenario(workdir: str, controlled: bool) -> dict:
    cfg = scenario_cfg(workdir, controlled)
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_ctlsmoke_{os.getpid()}_{int(controlled)}"
    from pytorch_ps_mpi_tpu.codecs import get_codec

    server = dcn.ShmPSServer(name, num_workers=WORKERS, template=params0,
                             max_staleness=10**9, frame=True,
                             code=get_codec("identity"))
    procs = []
    storm_state = {"sheds_final_storm": None, "error": None,
                   "storms": 0}
    stop = threading.Event()

    def _storm_once(port: int) -> int:
        """One PIPELINED burst: 4 sockets × 6 back-to-back full-read
        requests each (written before any reply is read), so the
        selector parses past the admission depth in one sweep —
        overload by construction, not by thread-scheduling luck.
        Returns the number of shed (retry) replies."""
        from pytorch_ps_mpi_tpu.serving.net import _REP, pack_request

        socks = []
        sheds = 0
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
                    if kind == 3:  # retry: shed by admission control
                        sheds += 1
        finally:
            for s in socks:
                s.close()
        return sheds

    def reader_storms():
        """Storm the read tier until the controller restores service:
        repeated pipelined bursts; stop once one full burst completes
        shed-free (admission depth raised past the burst size)."""
        try:
            port = cfg["read_port"]
            while (server.serving_core is None
                   or server.serving_core.latest_version() == 0):
                if stop.is_set():
                    return
                time.sleep(0.05)
            deadline = time.time() + 45.0
            while time.time() < deadline and not stop.is_set():
                sheds = _storm_once(port)
                storm_state["storms"] += 1
                storm_state["sheds_final_storm"] = sheds
                if storm_state["storms"] >= 2 and sheds == 0:
                    return  # service restored under the same load
                time.sleep(0.6)
        except Exception as e:  # surfaced as a smoke failure below
            storm_state["error"] = repr(e)

    storm_thread = None
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(WORKERS)]
        if controlled:
            storm_thread = threading.Thread(target=reader_storms,
                                            daemon=True)
            storm_thread.start()
        params, m = serve(server, cfg, total_grads=0,
                          total_received=WORKERS * STEPS, timeout=300.0)
        codes = join_workers(procs, timeout=120.0)
        check(f"{'controlled' if controlled else 'uncontrolled'} "
              "workers exited cleanly", codes == [0] * WORKERS,
              f"codes={codes}")
        if storm_thread is not None:
            storm_thread.join(timeout=60.0)
        m["_storm"] = dict(storm_state)
        return m
    finally:
        stop.set()
        server.close()
        join_workers(procs, timeout=5.0)


def check_controlled(m: dict, tdir: str) -> list:
    ctl = m["control"]
    actions = [json.loads(line) for line in
               open(os.path.join(tdir, "control-server.jsonl"))]
    by = lambda rule, act=None: [  # noqa: E731
        a for a in actions if a["rule"] == rule
        and (act is None or a["action"] == act)]

    # 1. codec downshift through the epoch handshake, zero frames lost
    check("controller downshifted the codec (wire-bound fleet)",
          ctl["epoch"] >= 1 and ctl["ladder_idx"] == 1,
          f"epoch={ctl['epoch']} ladder_idx={ctl['ladder_idx']}")
    reneg = by("codec", "renegotiate")
    check("renegotiation carries its wire-balance verdict",
          bool(reneg) and reneg[0]["verdict"]["kind"] == "wire_bound"
          and reneg[0]["verdict"]["wire_frac"] > 0.65,
          json.dumps(reneg[0]["verdict"]) if reneg else "none")
    check("epoch retired after the fleet switched",
          bool(by("codec", "epoch_retire")))
    rej = m["frames_rejected_by_worker"]
    check("zero frames lost to the renegotiation (healthy workers "
          "never rejected)", rej.get(0, 0) == 0 and rej.get(1, 0) == 0,
          f"rejected={rej} old_epoch_consumed={ctl['epoch_old_frames']}")
    check("wire actually compressed after the downshift",
          m["compression_ratio"] > 3.0,
          f"compression={m['compression_ratio']:.2f}")

    # 2. staleness de-weighting: exactly the straggler
    scales = by("lr_scale")
    check("straggler de-weighted (AsySG-InCon LR scaling)",
          bool(scales) and min(a["new"] for a in scales) < 1.0
          and all(a["worker"] == 1 for a in scales),
          f"scales={[(a['worker'], a['new']) for a in scales]}")

    # 3. quarantine -> probation readmission, healthy pushes reapplied
    check("NaN worker quarantined then readmitted",
          bool(by("evict", "readmit_quarantine"))
          and m["numerics"]["readmissions"] == 1
          and not m["numerics"]["quarantined"],
          f"readmissions={m['numerics']['readmissions']}")

    # 4. read tier: sheds, then depth raised until a storm ran shed-free
    storm = m["_storm"]
    check("reader storms ran against the live run",
          storm["error"] is None and storm["storms"] >= 2,
          json.dumps(storm))
    depth_ups = [a for a in by("read_tier", "depth")
                 if a["new"] > a["old"]]
    check("admission depth raised under shed pressure, storm ends "
          "shed-free", bool(depth_ups)
          and storm["sheds_final_storm"] == 0
          and m["reads_shed"] > 0,
          f"depth={ctl['admission_depth']} sheds={m['reads_shed']} "
          f"final_storm={storm['sheds_final_storm']}")

    # 5. latching: every action has a verdict; no flaps anywhere
    check("every action row carries its triggering verdict",
          all(isinstance(a.get("verdict"), dict) and a["verdict"]
              for a in actions))
    check("controller never flapped", ctl["flaps"] == 0,
          f"flaps={ctl['flaps']}")
    return actions


def check_replay(actions: list, tdir: str, cfg: dict) -> None:
    from pytorch_ps_mpi_tpu.control import Controller
    from pytorch_ps_mpi_tpu.telemetry.timeseries import (
        load_timeseries_rows,
    )

    rows = load_timeseries_rows(
        os.path.join(tdir, "timeseries-control-server.jsonl"))
    # replay must start from the live engine's initial setpoints (the
    # boot admission depth / ring the serving knobs configured)
    replayed = Controller.replay(
        rows, num_workers=WORKERS, cfg=cfg,
        depth=cfg["serving_kw"]["admission_depth"],
        ring=cfg["serving_kw"]["ring"])
    check("replay re-derives the action sequence byte-identically",
          json.dumps(replayed) == json.dumps(actions),
          f"live={len(actions)} replayed={len(replayed)}")
    from tools.telemetry_report import collect_files, summarize

    summary = summarize(collect_files([tdir]))
    act = summary["actions"]
    check("telemetry_report actions section parses the run",
          act is not None and act["actions"] == len(actions),
          f"report={act and act['actions']} live={len(actions)}")
    check("report flap check is clean", not act["flap_suspects"],
          json.dumps(act["flap_suspects"]))


def tcp_renegotiation_leg() -> None:
    """Zero-frame-loss renegotiation on the second transport: old-epoch
    frame consumed mid-transition, native batch re-armed after retire."""
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.tcp import TcpPSServer, TcpPSWorker

    template = {"a": jnp.zeros((64, 8)), "b": jnp.zeros((32,))}
    srv = TcpPSServer(0, 2, template, max_staleness=10**9,
                      code=get_codec("identity"), frame=True)
    g = jax.tree.map(lambda x: jnp.ones_like(x), template)

    def push(worker, code):
        w = TcpPSWorker("127.0.0.1", srv.port, worker, template,
                        code=get_codec("identity"), frame=True)
        try:
            if code is not None:
                w.renegotiate(get_codec(code))
            w.push_grad(g, 1, timeout=30.0)
        finally:
            w.close()

    def run(worker, code):
        t = threading.Thread(target=push, args=(worker, code))
        t.start()
        deadline = time.time() + 30.0
        out = []
        try:
            while time.time() < deadline and not out:
                batch = srv.poll_grad_batch()
                if batch:
                    out.extend(batch)
                elif batch is None:
                    item = srv.poll_grad()
                    if item is not None:
                        out.append(item)
                time.sleep(0.002)
            return out
        finally:
            t.join(timeout=30.0)

    try:
        srv.publish(jax.tree.map(lambda x: x + 1.0, template))
        assert run(0, None)
        srv.renegotiate_wire(get_codec("int8"))
        old = run(1, None)          # old epoch, mid-transition
        new = run(0, "int8")        # new epoch
        srv.finish_renegotiation()
        before = srv.native_batch_frames
        again = run(0, "int8")      # native batch path re-armed
        check("tcp: renegotiation mid-run loses zero frames",
              bool(old) and bool(new) and bool(again)
              and srv.epoch_old_frames == 1 and not srv.frames_rejected
              and srv.native_batch_frames > before,
              f"old={len(old)} rejected={dict(srv.frames_rejected)} "
              f"batch={srv.native_batch_frames}>{before}")
    finally:
        srv.close()


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="control_smoke_")
    tdir = os.path.join(workdir, "telemetry")

    print("== controlled run (straggler + NaN + overload) ==")
    cfg = scenario_cfg(workdir, controlled=True)
    m_ctl = run_scenario(workdir, controlled=True)
    actions = check_controlled(m_ctl, tdir)

    print("== replay + report ==")
    check_replay(actions, tdir, cfg)

    print("== tcp renegotiation leg ==")
    tcp_renegotiation_leg()

    print("== uncontrolled run (same scenario) ==")
    workdir2 = tempfile.mkdtemp(prefix="control_smoke_un_")
    m_un = run_scenario(workdir2, controlled=False)

    loss_ctl = float(m_ctl["loss_final"])
    loss_un = float(m_un["loss_final"])
    ratio = loss_ctl / max(loss_un, 1e-12)
    # the controller de-weights the straggler's destabilizing stale
    # pushes (lr 0.3 is past the AsySG-InCon stable-LR point for
    # stale-7 at full weight) and readmits the NaN worker's healthy
    # pushes: the controlled run must genuinely WIN, not tie (measured
    # ratio 0.74-0.80 across repeats; 0.95 is the no-flake ceiling)
    check("controller helps: controlled loss beats uncontrolled",
          ratio <= 0.95,
          f"controlled={loss_ctl:.4f} uncontrolled={loss_un:.4f} "
          f"ratio={ratio:.3f}")

    print(f"control_smoke: PASS — "
          f"{len(actions)} actions, 0 flaps, loss ratio {ratio:.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
