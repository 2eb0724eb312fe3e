"""Chaos smoke gate: a short supervised async run under a canned fault
plan must RECOVER, LEARN, and REPLAY.

What it does (CPU-only, shm transport, ~a minute):

1. Runs a 2-worker async MLP job under the resilience Supervisor with a
   canned fault plan injecting one of everything: a corrupted frame, a
   delayed push, a worker crash, a dropped push, a duplicated push, and
   a server crash.
2. Asserts every injected fault was RECOVERED: the job completed (no
   hung rounds — both workers exited 0), the final loss beat the run's
   initial loss, and the respawn / server-restart / reconnect /
   frame-rejection counters are all nonzero — in the returned metrics
   AND in the Prometheus ``/metrics`` text an operator would scrape.
3. Runs the same plan + seed AGAIN and asserts the injected-event logs
   are byte-identical — chaos here is a reproducible test, not a flake.
4. Prints the recovery counters.

Run via ``make chaos-smoke`` (it sits in the default ``make test`` path
next to ``bucket-smoke``). Exits nonzero on any unrecovered fault.
"""

from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

from pytorch_ps_mpi_tpu.resilience import Supervisor, load_fault_log

FAULT_PLAN = [
    {"at_step": 2, "worker": 0, "kind": "corrupt"},
    {"at_step": 3, "worker": 0, "kind": "delay", "delay_ms": 20},
    {"at_step": 4, "worker": 1, "kind": "crash_worker"},
    {"at_step": 5, "worker": 0, "kind": "drop"},
    {"at_step": 6, "worker": 0, "kind": "duplicate"},
    {"at_step": 12, "worker": "server", "kind": "crash_server"},
]


def chaos_cfg(workdir: str) -> dict:
    return {
        "model": "mlp", "model_kw": {"features": (16, 4)}, "in_shape": (8,),
        "batch": 32, "seed": 11, "optim": "sgd", "hyper": {"lr": 0.05},
        "steps": 16,
        "open_timeout": 60.0, "push_timeout": 3.0,
        "frame_check": True, "resilient": True,
        "resilience_kw": {"backoff_base": 0.02, "backoff_max": 0.5,
                          "max_retries": 20},
        "fault_plan": FAULT_PLAN,
        "fault_seed": 7,
        "fault_log_dir": os.path.join(workdir, "faults"),
        # the control plane rides the chaos run (ISSUE 14): no ladder —
        # this wire has no codec to renegotiate — but the staleness /
        # evict / probation rules are live through every crash, respawn
        # and server restart (each generation's serve() re-arms a
        # controller; the action file appends across generations), so
        # the chaos gate proves the controller never destabilizes
        # recovery
        "control": True,
        "control_dir": os.path.join(workdir, "control"),
        "control_kw": {"eval_every_s": 0.25, "warmup_s": 1.0,
                       "cooldown_s": 1.0,
                       "read_p95_target_ms": 250.0},
    }


def run_once(workdir: str, tag: str) -> tuple:
    """One supervised chaos run; returns (metrics, sorted event tuples,
    recovery timings dict)."""
    cfg = chaos_cfg(os.path.join(workdir, tag))
    sup = Supervisor(
        cfg, 2, shm_name=f"/psq_chaos_smoke_{os.getpid()}_{tag}",
        checkpoint_dir=os.path.join(workdir, tag, "ckpt"),
        checkpoint_every=4, timeout=240.0,
    )
    params, m = sup.run()
    events = []
    for role in (0, 1, "server"):
        events.extend(load_fault_log(os.path.join(
            cfg["fault_log_dir"], f"faults-{role}.jsonl")))
    ev = sorted((e["id"], e["kind"], str(e["worker"]), e["at_step"])
                for e in events)
    return sup, m, ev


def check(m: dict, sup, ev) -> list:
    """Every injected fault must have been recovered; returns the list
    of failures (empty = pass)."""
    bad = []
    if not m["loss_final"] < m["run_loss_initial"]:
        bad.append(f"loss did not improve: {m['run_loss_initial']:.4f} -> "
                   f"{m['loss_final']:.4f}")
    if m["worker_exit_codes"] != [0, 0]:
        bad.append(f"workers did not all finish: {m['worker_exit_codes']}")
    if m["workers_abandoned"]:
        bad.append("supervisor abandoned a worker")
    for key in ("worker_respawns", "server_restarts", "worker_reconnects",
                "frames_rejected"):
        if not m[key] >= 1.0:
            bad.append(f"{key} = {m[key]} (expected >= 1)")
    if not m["versions_monotonic"]:
        bad.append("publish version went backwards across the restart")
    fired_kinds = sorted(e[1] for e in ev)
    want = sorted(f["kind"] for f in FAULT_PLAN)
    if fired_kinds != want:
        bad.append(f"fired kinds {fired_kinds} != planned {want}")
    text = sup.final_prometheus_text or ""
    for metric in ("ps_worker_respawns_total", "ps_server_restarts_total",
                   "ps_worker_reconnects_total", "ps_frames_rejected_total"):
        ok = any(
            line.startswith(metric) and not line.startswith("#")
            and float(line.rsplit(" ", 1)[1]) >= 1
            for line in text.splitlines()
        )
        if not ok:
            bad.append(f"{metric} not >= 1 in /metrics text")
    return bad


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="chaos_smoke_")
    print(f"chaos-smoke: supervised 2-worker run under {len(FAULT_PLAN)} "
          f"injected faults (workdir {workdir})")
    sup1, m1, ev1 = run_once(workdir, "run1")
    failures = check(m1, sup1, ev1)

    print("chaos-smoke: replaying the same fault plan + seed")
    sup2, m2, ev2 = run_once(workdir, "run2")
    failures += check(m2, sup2, ev2)
    if ev1 != ev2:
        failures.append(f"event logs differ across replays:\n  {ev1}\n  {ev2}")

    print("\nrecovery summary")
    print(f"  faults injected        {len(ev1)} "
          f"({', '.join(sorted(set(e[1] for e in ev1)))})")
    print(f"  worker respawns        {int(m1['worker_respawns'])}")
    print(f"  server restarts        {int(m1['server_restarts'])}")
    print(f"  worker reconnects      {int(m1['worker_reconnects'])}")
    print(f"  frames rejected        {int(m1['frames_rejected'])}")
    print(f"  loss                   {m1['run_loss_initial']:.4f} -> "
          f"{m1['loss_final']:.4f}")
    print(f"  deterministic replay   {ev1 == ev2}")

    if failures:
        print("\nCHAOS-SMOKE FAILED:", file=sys.stderr)
        for b in failures:
            print(f"  - {b}", file=sys.stderr)
        return 1
    print("\nchaos-smoke PASSED: every injected fault recovered, "
          "replay identical")
    return 0


if __name__ == "__main__":
    sys.exit(main())
