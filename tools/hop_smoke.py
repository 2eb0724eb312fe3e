"""Hop-anatomy gate: the occupancy timeline must be REAL (make
hop-smoke, in the default ``make test`` path).

An occupancy tracer that misattributes stage time — or projects
streaming headroom that isn't there — would steer the topo controller's
split-vs-streaming call wrong.  This smoke validates the chain
end-to-end with a known injected fold widening (CPU-only, TCP tree,
~a minute):

1. **Run A** — a 2-group / 4-worker tree with hop anatomy armed and a
   ``slow_leader`` fault sleeping ``SLOW_MS`` inside leader 0's fold
   per folded payload — a widening of exactly the window the hop
   timeline's ``fold`` stage measures.
2. **Run B** — the identical job with the fault removed (the measured
   ground truth of leader 0's natural per-frame fold time).
3. Asserts:

   - leader 0's per-frame fold p50 widens A←B by the injected delay
     within ±30% (the whatif-style projection-vs-measured gate);
   - the timeline's serial sum reproduces the measured round wall on
     the saturated leader within ±30% (sub-stage attribution is
     honest: nothing big goes missing into ``idle``);
   - the offline engine (``hop_anatomy_from_rows`` over the persisted
     ``hop-leader*.jsonl``) recomputes every row's streaming-headroom
     projection **byte-identically** from the row's own fields, and
     its rollup agrees with the live root engine the HopTailer fed;
   - the root-side hop bookkeeping stays within the standing ≤5%
     telemetry budget;
   - ``telemetry_report`` renders a hop section that agrees with the
     replay.
"""

from __future__ import annotations

import glob
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

STEPS = 8
WORKERS = 4
SLOW_MS = 80.0


def tree_cfg(workdir: str, delayed: bool) -> dict:
    cfg = {
        "model": "mlp", "model_kw": {"features": (16, 4)},
        "in_shape": (8,), "batch": 32, "seed": 3,
        "codec": "topk", "codec_kw": {"fraction": 0.25},
        "optim": "sgd", "hyper": {"lr": 0.05}, "steps": STEPS,
        "frame_check": True, "transport": "tcp",
        "max_staleness": 10 ** 9,
        "n_workers": WORKERS, "group_size": 2,
        "lineage": True, "lineage_dir": workdir,
        "hop_anatomy": True,
        "hop_anatomy_kw": {"min_rounds": 1},
    }
    if delayed:
        # every payload folded at leader 0 sleeps SLOW_MS inside the
        # fold window — the exact interval the fold stage measures
        cfg["fault_plan"] = [{"at_step": 0, "worker": "leader0",
                              "kind": "slow_leader", "slow_ms": SLOW_MS}]
        cfg["fault_seed"] = 1
    return cfg


def run_leg(workdir: str, delayed: bool) -> dict:
    from pytorch_ps_mpi_tpu.parallel import tree

    _, m = tree.run_tree(tree_cfg(workdir, delayed), timeout=280.0)
    t = m["tree"]
    if t["worker_codes"] != [0] * WORKERS or t["leader_codes"] != [0, 0]:
        raise SystemExit(f"hop_smoke: leg exited dirty "
                         f"(workers {t['worker_codes']}, "
                         f"leaders {t['leader_codes']})")
    return m


def leader_rows(workdir: str) -> list:
    from pytorch_ps_mpi_tpu.telemetry import load_hop_rows

    rows = []
    for p in sorted(glob.glob(os.path.join(workdir, "hop-*.jsonl"))):
        rows.extend(load_hop_rows(p))
    return rows


def _med(vals):
    vals = sorted(vals)
    return vals[len(vals) // 2] if vals else 0.0


def fold_per_frame_ms(rows: list, leader: int) -> float:
    """Median per-folded-frame fold-stage time for one leader — the
    quantity the injected slow_leader delay moves by exactly SLOW_MS."""
    return _med([1e3 * r["stages"]["fold"] / max(int(r["frames"]), 1)
                 for r in rows if int(r["leader"]) == leader
                 and int(r["frames"]) > 0])


def main() -> int:
    failures = []
    wd_a = tempfile.mkdtemp(prefix="hop_a_")
    wd_b = tempfile.mkdtemp(prefix="hop_b_")
    print(f"hop-smoke: run A — leader 0 fold-delayed {SLOW_MS:.0f}ms/"
          f"frame ({wd_a})")
    m_a = run_leg(wd_a, delayed=True)
    print(f"hop-smoke: run B — clean ({wd_b})")
    m_b = run_leg(wd_b, delayed=False)

    rows_a = leader_rows(wd_a)
    rows_b = leader_rows(wd_b)
    if not rows_a or not rows_b:
        raise SystemExit(f"hop_smoke: no hop rows persisted "
                         f"(A={len(rows_a)}, B={len(rows_b)})")
    slow_rows = [r for r in rows_a if int(r["leader"]) == 0]
    print(f"hop rows: A={len(rows_a)} B={len(rows_b)} "
          f"(leader 0 in A: {len(slow_rows)} rounds)")

    # 1. the injected fold widening lands in the fold stage, ±30%
    pf_a = fold_per_frame_ms(rows_a, 0)
    pf_b = fold_per_frame_ms(rows_b, 0)
    delta = pf_a - pf_b
    rel_err = abs(delta - SLOW_MS) / SLOW_MS
    print(f"leader 0 fold/frame p50: A={pf_a:.1f}ms  B={pf_b:.1f}ms  "
          f"delta={delta:.1f}ms vs injected {SLOW_MS:.0f}ms "
          f"(rel err {rel_err * 100:.1f}%)")
    if rel_err > 0.30:
        failures.append(
            f"fold-stage widening {delta:.1f}ms is off the injected "
            f"{SLOW_MS:.0f}ms by {rel_err * 100:.0f}% (budget ±30%)")

    # 2. serial attribution reproduces the measured round wall on the
    # saturated leader (nothing big leaks into idle)
    ratios = [r["serial_s"] / r["round_s"] for r in slow_rows
              if r["round_s"] > 0]
    med_ratio = _med(ratios)
    print(f"leader 0 serial/round p50: {med_ratio:.3f} "
          f"(headroom p50 "
          f"{_med([r['headroom_ratio'] for r in slow_rows]):.3f}x)")
    if not 0.70 <= med_ratio <= 1.001:
        failures.append(
            f"serial sum reproduces only {med_ratio:.2f} of the "
            "measured round wall on the saturated leader (budget ±30%)")

    # 3. byte-identical replay: every persisted row's headroom
    # projection recomputes exactly from the row's own fields, and the
    # offline rollup agrees with the live root engine the tailer fed
    from pytorch_ps_mpi_tpu.telemetry import hop_anatomy_from_rows
    from pytorch_ps_mpi_tpu.telemetry.hop_anatomy import HopAnatomy

    for r in rows_a:
        s, o, h = HopAnatomy.project(r["stages"], int(r["frames"]))
        if (s, o, h) != (r["serial_s"], r["overlap_s"],
                         r["headroom_ratio"]):
            failures.append(
                f"replayed projection diverged on leader "
                f"{r['leader']} round {r['round']}: ({s}, {o}, {h}) != "
                f"({r['serial_s']}, {r['overlap_s']}, "
                f"{r['headroom_ratio']})")
            break
    off = hop_anatomy_from_rows(rows_a, min_rounds=1)
    live = m_a.get("hop") or {}
    print(f"replay: {off.rounds} rounds offline, root live ingested "
          f"{live.get('rounds', 0)} (busy {off.snapshot()['busy_frac']:.3f}"
          f" vs live {live.get('busy_frac', 0.0):.3f})")
    if off.rounds != len(rows_a):
        failures.append(f"offline replay kept {off.rounds} rounds from "
                        f"{len(rows_a)} persisted rows")
    if not live.get("rounds"):
        failures.append("root's live hop engine ingested no rows — the "
                        "HopTailer never fed it")

    # 4. root-side hop bookkeeping within the ≤5% telemetry budget
    over = float(live.get("overhead_s", 0.0))
    frac = over / max(m_a.get("wall_s", 0.0), 1e-9)
    print(f"root hop overhead {frac:.2%} of serve wall "
          f"({over * 1e3:.1f}ms / {m_a.get('wall_s', 0.0):.1f}s)")
    if frac > 0.05:
        failures.append(f"hop bookkeeping {frac:.1%} exceeds the 5% "
                        "telemetry budget")

    # 5. the report's hop section agrees with the replay
    from tools.telemetry_report import summarize

    rep = summarize(sorted(glob.glob(os.path.join(wd_a, "hop-*.jsonl"))))
    rep_hop = rep.get("hop") or {}
    if rep_hop.get("rounds") != off.rounds:
        failures.append(
            f"telemetry_report hop section missing or disagreeing "
            f"({rep_hop.get('rounds')} vs {off.rounds} rounds)")

    if failures:
        print("\nHOP-SMOKE FAILED:", file=sys.stderr)
        for b in failures:
            print(f"  - {b}", file=sys.stderr)
        return 1
    print("\nhop-smoke PASSED: injected fold widening measured within "
          "±30%, serial attribution honest, headroom projection replays "
          "byte-identically, hop plane within the telemetry budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())
