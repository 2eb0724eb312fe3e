"""Round-anatomy what-if gate: the advisor's projections must be REAL.

A profiler that names the wrong bottleneck — or projects savings that
don't materialize — is worse than no profiler.  This smoke validates
the causal chain end-to-end with a known injected bottleneck (CPU-only,
shm transport, ~a minute):

1. **Run A** — a 3-worker sync-barrier MLP job with frame checking +
   lineage + round anatomy armed, and a deterministic ``wire_delay``
   fault plan injecting 200 ms into worker 1's WIRE stage on every step
   (the sleep runs between the frame's ``send_wall`` stamp and the
   bytes traveling — exactly the window the lineage wire stage
   measures).
2. **Run B** — the identical job with the delay removed (the measured
   ground truth of "what would speeding the wire up buy").
3. Asserts:

   - run A's advisor ranks the **wire** stage #1 (by debottleneck
     saving), and the wire stage gates the majority of decomposed
     rounds;
   - the advisor's debottleneck projection ("worker 1's wire pulled to
     the fleet median") matches the MEASURED per-round improvement
     A → B within ±30% — the Coz-style virtual speedup against its
     ground truth;
   - the offline engine (``anatomy_from_rows`` over the persisted
     ``lineage-server.jsonl``) reproduces the live advisor's ranking —
     persisted rows carry the whole story;
   - with anatomy armed the anatomy + lineage self-timed bookkeeping
     stays within the standing ≤5% telemetry budget.

Run via ``make whatif-smoke`` (in the default ``make test`` path).
"""

from __future__ import annotations

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

STEPS = 14
WORKERS = 3
DELAY_MS = 200.0
SLOW_WORKER = 1


def run_job(workdir: str, delayed: bool) -> dict:
    cfg = {
        "model": "mlp", "model_kw": {"features": (16, 4)}, "in_shape": (8,),
        "batch": 32, "seed": 7, "optim": "sgd", "hyper": {"lr": 0.05},
        "steps": STEPS,
        "open_timeout": 60.0, "push_timeout": 60.0,
        "frame_check": True,
        "telemetry_dir": workdir,
        "lineage": True, "lineage_dir": workdir,
        "health": True,
    }
    if delayed:
        cfg["fault_plan"] = [
            {"at_step": s, "worker": SLOW_WORKER, "kind": "wire_delay",
             "delay_ms": DELAY_MS}
            for s in range(STEPS)
        ]
        cfg["fault_seed"] = 7
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_whatif_{os.getpid()}_{int(delayed)}"
    server = dcn.ShmPSServer(name, num_workers=WORKERS, template=params0,
                             max_staleness=10**9, frame=True)
    procs = []
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(WORKERS)]
        params, m = serve(server, cfg, total_grads=0,
                          total_received=WORKERS * STEPS,
                          sync_barrier=True, timeout=300.0)
        codes = join_workers(procs, timeout=120.0)
        if codes != [0] * WORKERS:
            raise SystemExit(f"workers exited {codes}")
        return m
    finally:
        server.close()
        join_workers(procs, timeout=5.0)


def round_seconds(m: dict) -> float:
    """Mean decomposed round time from the anatomy engine's own rounds
    (steady-state: the first round — worker startup + first compile —
    is excluded on both runs identically via the advisor's totals)."""
    anat = m["anatomy"]
    rounds = anat["rounds"]
    assert rounds >= STEPS - 2, f"too few decomposed rounds: {rounds}"
    # total retained round seconds from any advisor row (they all share
    # the same denominator)
    total = anat["advisor"][0]["whatif_20"]["total_s"]
    return total / rounds


def main() -> int:
    failures = []
    wd_a = tempfile.mkdtemp(prefix="whatif_a_")
    wd_b = tempfile.mkdtemp(prefix="whatif_b_")
    print(f"whatif-smoke: run A — worker {SLOW_WORKER} wire-delayed "
          f"{DELAY_MS:.0f}ms/push ({wd_a})")
    m_a = run_job(wd_a, delayed=True)
    print(f"whatif-smoke: run B — no delay ({wd_b})")
    m_b = run_job(wd_b, delayed=False)

    anat = m_a["anatomy"]
    advisor = anat["advisor"]
    top = advisor[0]
    print("\nrun A advisor (ranked):")
    for a in advisor:
        print(f"  [{a['stage']}] crit={a['critical_share'] * 100:.0f}%  "
              f"p50={a['p50_ms']}ms  "
              f"-20% saves {a['whatif_20']['saving_frac'] * 100:.1f}%  "
              f"debottleneck saves "
              f"{a['debottleneck']['saving_frac'] * 100:.1f}%")

    # 1. the injected stage is ranked #1 and gates the rounds
    if top["stage"] != "wire":
        failures.append(f"advisor ranked {top['stage']!r} #1, expected "
                        "'wire' (the injected bottleneck)")
    crit = {c["stage"]: c["share"] for c in anat["critical_path"]}
    if crit.get("wire", 0.0) < 0.5:
        failures.append(f"wire gates only {crit.get('wire', 0) * 100:.0f}% "
                        "of rounds (expected the majority)")

    # 2. projection vs measurement: the debottleneck saving must match
    # the measured A->B per-round improvement within ±30%
    sec_a = round_seconds(m_a)
    sec_b = round_seconds(m_b)
    measured_frac = (sec_a - sec_b) / sec_a if sec_a > 0 else 0.0
    projected_frac = top["debottleneck"]["saving_frac"]
    rel_err = (abs(projected_frac - measured_frac) / measured_frac
               if measured_frac > 0 else float("inf"))
    print(f"\nround time: A={sec_a * 1e3:.1f}ms  B={sec_b * 1e3:.1f}ms  "
          f"measured saving {measured_frac * 100:.1f}%  "
          f"projected {projected_frac * 100:.1f}%  "
          f"(rel err {rel_err * 100:.1f}%)")
    if measured_frac < 0.3:
        failures.append(f"injected delay barely moved round time "
                        f"(measured {measured_frac:.2f}) — the scenario "
                        "is not real, fix the smoke")
    if rel_err > 0.30:
        failures.append(f"projection off by {rel_err * 100:.0f}% "
                        "(budget ±30%): projected "
                        f"{projected_frac:.3f} vs measured "
                        f"{measured_frac:.3f}")

    # 3. offline reconstruction agrees with the live engine
    from pytorch_ps_mpi_tpu.telemetry import (
        anatomy_from_rows,
        load_lineage_rows,
    )

    rows = load_lineage_rows(os.path.join(wd_a, "lineage-server.jsonl"))
    off = anatomy_from_rows(rows)
    off_adv = off.advisor()
    if not off_adv or off_adv[0]["stage"] != "wire":
        failures.append(
            f"offline advisor ranked "
            f"{off_adv[0]['stage'] if off_adv else None!r} #1 from the "
            "persisted rows, expected 'wire'")
    if off.rounds != anat["rounds"]:
        failures.append(f"offline engine decomposed {off.rounds} rounds, "
                        f"live decomposed {anat['rounds']}")
    off_proj = off_adv[0]["debottleneck"]["saving_frac"] if off_adv else 0.0
    print(f"offline reconstruction: {off.rounds} rounds, top stage "
          f"{off_adv[0]['stage'] if off_adv else None} "
          f"(debottleneck {off_proj * 100:.1f}%)")

    # 4. the armed-anatomy overhead against the ≤5% telemetry budget
    over = (anat["overhead_s"] + m_a["lineage"]["overhead_s"])
    frac = over / max(m_a["wall_s"], 1e-9)
    print(f"anatomy+lineage overhead {frac:.2%} of serve wall "
          f"({over * 1e3:.1f}ms / {m_a['wall_s']:.1f}s)")
    if frac > 0.05:
        failures.append(f"armed-anatomy overhead {frac:.1%} exceeds the "
                        "5% telemetry budget")

    # 5. the anatomy sidecar landed and is report-readable
    apath = os.path.join(wd_a, "anatomy-server.jsonl")
    from pytorch_ps_mpi_tpu.telemetry import load_anatomy_rows

    arows = load_anatomy_rows(apath)
    if len(arows) != anat["rounds"]:
        failures.append(f"anatomy-server.jsonl has {len(arows)} rows, "
                        f"engine decomposed {anat['rounds']} rounds")
    from tools.telemetry_report import summarize

    rep = summarize([apath])
    if not rep.get("anatomy") or rep["anatomy"]["rounds"] != anat["rounds"]:
        failures.append("telemetry_report anatomy section missing or "
                        "disagreeing with the live engine")

    if failures:
        print("\nWHATIF-SMOKE FAILED:", file=sys.stderr)
        for b in failures:
            print(f"  - {b}", file=sys.stderr)
        return 1
    print("\nwhatif-smoke PASSED: injected wire bottleneck ranked #1, "
          "projection within ±30% of the measured ground truth, offline "
          "reconstruction agrees, anatomy within the telemetry budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())
