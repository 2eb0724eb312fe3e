"""Lineage smoke gate: the trace IDs must ACCOUNT for every push.

What it does (CPU-only, shm transport, ~half a minute):

1. Runs a 2-worker async MLP job with frame checking + gradient lineage
   + the HealthMonitor armed and a deliberate straggler (worker 1), all
   telemetry landing in one directory.
2. Asserts the lineage is COMPLETE and EXACT:

   - every push the serve loop consumed has a lineage row (publish
     composition or drop row) carrying the full trace ID + stage times
     (worker, step, seq, staleness, bytes, send/recv walls, e2e);
   - the exact per-push staleness histogram rebuilt from the lineage
     rows equals the serve loop's own ``staleness_hist`` accounting,
     push for push;
   - the published-version count matches the applied count (async mode:
     one push per publish);
   - exact e2e latencies are sane (positive, bounded by the run wall).

3. Merges every process's recorder JSONL into one Chrome trace with the
   per-worker clock offsets fitted from the frame send/recv pairs and
   asserts CROSS-PROCESS FLOW EVENTS landed (worker push span → server
   consume span arrows, matched ``s``/``f`` ids).
4. Re-asserts the standing telemetry-overhead budget with lineage ON:
   the tracker's self-timed bookkeeping must cost <= 5% of the serve
   wall.

Run via ``make trace-smoke`` (in the default ``make test`` path).
Exits nonzero on any incomplete or disagreeing lineage.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

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

STEPS = 20
SLOW_MS = 120.0  # worker 1 straggles -> nonzero staleness spread


def run_job(workdir: str) -> tuple:
    cfg = {
        "model": "mlp", "model_kw": {"features": (16, 4)}, "in_shape": (8,),
        "batch": 32, "seed": 5, "optim": "sgd", "hyper": {"lr": 0.05},
        "steps": STEPS,
        "open_timeout": 60.0, "push_timeout": 60.0,
        "frame_check": True,
        "slow_ms": {"1": SLOW_MS},
        "telemetry_dir": workdir,
        "lineage": True, "lineage_dir": workdir,
        "health": True, "health_dir": os.path.join(workdir, "health"),
    }
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_trace_{os.getpid()}"
    # a finite staleness bound + the deliberate straggler: some pushes
    # get stale-dropped, exercising the lineage drop rows too
    server = dcn.ShmPSServer(name, num_workers=2, template=params0,
                             max_staleness=3, frame=True)
    procs = []
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(2)]
        params, m = serve(server, cfg, total_grads=0,
                          total_received=2 * STEPS, timeout=300.0)
        codes = join_workers(procs, timeout=120.0)
        if codes != [0, 0]:
            raise SystemExit(f"workers exited {codes}")
        return m
    finally:
        server.close()
        join_workers(procs, timeout=5.0)


def check_lineage(workdir: str, m: dict) -> list:
    """Completeness + exactness of the lineage rows against the serve
    loop's own accounting."""
    from pytorch_ps_mpi_tpu.telemetry import load_lineage_rows

    bad = []
    rows = load_lineage_rows(os.path.join(workdir, "lineage-server.jsonl"))
    publishes = [r for r in rows if r.get("kind") == "publish"]
    drops = [r for r in rows if r.get("kind") == "drop"]
    pushes = [p for r in publishes for p in r["pushes"]]
    all_pushes = pushes + [r["push"] for r in drops]

    # 1. every consumed push has a complete lineage row
    consumed = int(m["grads_received"])
    if len(all_pushes) != consumed:
        bad.append(f"lineage accounts for {len(all_pushes)} pushes, "
                   f"server consumed {consumed}")
    required = ("worker", "step", "seq", "staleness", "bytes",
                "send_wall", "recv_wall")
    for p in all_pushes:
        missing = [k for k in required if p.get(k) is None]
        if missing:
            bad.append(f"incomplete lineage row (missing {missing}): {p}")
            break
    for p in pushes:
        if p.get("e2e_s") is None or p.get("decode_s") is None:
            bad.append(f"composed push lacks stage times: {p}")
            break

    # 2. exact staleness from lineage == the serve loop's version math
    lineage_hist: dict = {}
    for p in all_pushes:
        s = int(p["staleness"])
        lineage_hist[s] = lineage_hist.get(s, 0) + 1
    serve_hist = {int(k): int(v) for k, v in m["staleness_hist"].items()}
    if lineage_hist != serve_hist:
        bad.append(f"lineage staleness {lineage_hist} != serve "
                   f"accounting {serve_hist}")

    # 3. async mode: one composed push per published version
    if len(publishes) != int(m["applied"]):
        bad.append(f"{len(publishes)} publish rows != applied "
                   f"{int(m['applied'])}")
    sizes = {len(r["pushes"]) for r in publishes}
    if sizes - {1}:
        bad.append(f"async publish composed of {sizes} pushes (want 1)")

    # 4. e2e sanity: nonnegative, below the run wall (+ slack for the
    # startup window before t0), and the canonical metric keys carry
    # the same distribution
    e2es = [p["e2e_s"] for p in pushes]
    if not e2es or min(e2es) < 0 or max(e2es) > m["wall_s"] + 30.0:
        bad.append(f"e2e latencies insane: min={min(e2es or [0])} "
                   f"max={max(e2es or [0])} wall={m['wall_s']}")
    if m["push_e2e_p50_ms"] <= 0 or m["lineage_pushes"] != len(pushes):
        bad.append("canonical lineage metric keys disagree with the rows")
    return bad


def check_trace(workdir: str) -> list:
    """The merged Chrome trace must contain cross-process flow arrows."""
    from examples.train_async import _export_telemetry

    bad = []
    art = _export_telemetry(workdir)
    flows = art.get("telemetry_trace_flow_events", 0)
    if flows < 1:
        bad.append("merged trace has no cross-process flow events")
    with open(os.path.join(workdir, "trace.json")) as f:
        events = json.load(f)["traceEvents"]
    starts = {e["id"] for e in events if e.get("ph") == "s"}
    ends = {e["id"] for e in events if e.get("ph") == "f"}
    if starts != ends or not starts:
        bad.append(f"unmatched flow ids: {len(starts)} starts vs "
                   f"{len(ends)} ends")
    # the two halves of an arrow sit on DIFFERENT tracks (worker push
    # span vs server consume span) — that is what makes it cross-process
    tid_s = {e["id"]: e["tid"] for e in events if e.get("ph") == "s"}
    tid_f = {e["id"]: e["tid"] for e in events if e.get("ph") == "f"}
    if not any(tid_s[i] != tid_f.get(i) for i in tid_s):
        bad.append("flow events never cross tracks (not cross-process)")
    return bad


def check_overhead(m: dict, threshold: float = 0.05) -> list:
    """The lineage layer's own bookkeeping (self-timed around every
    observe/publish, JSONL writes included) against the standing <=5%
    telemetry budget."""
    frac = m["lineage"]["overhead_s"] / max(m["wall_s"], 1e-9)
    if frac > threshold:
        return [f"lineage overhead {frac:.1%} exceeds {threshold:.0%}"]
    print(f"lineage overhead {frac:.2%} of serve wall "
          f"({m['lineage']['overhead_s'] * 1e3:.1f}ms / "
          f"{m['wall_s']:.1f}s) — within {threshold:.0%}")
    return []


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="trace_smoke_")
    print(f"trace-smoke: 2-worker async run, lineage + flow-event trace "
          f"armed, worker 1 straggling {SLOW_MS:.0f}ms (workdir {workdir})")
    m = run_job(workdir)

    failures = check_lineage(workdir, m)
    failures += check_trace(workdir)
    failures += check_overhead(m)
    if failures:
        print("\nTRACE-SMOKE FAILED:", file=sys.stderr)
        for b in failures:
            print(f"  - {b}", file=sys.stderr)
        return 1
    print("\ntrace-smoke PASSED: every consumed push accounted, exact "
          "staleness matches the serve loop, flow arrows cross "
          "processes, lineage within the telemetry budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())
