"""Hierarchical-aggregation smoke gate (make tree-smoke, in the default
`make test` path).

One REAL 2-group / 6-worker tree run over TCP with a leader crash
injected mid-fold, asserting the tree's load-bearing invariants:

1. **exact push accounting through every hop** — every one of the 6×N
   worker pushes is either composed into a root published version
   (its (worker, step, seq) trace ID appearing in the root's lineage
   AFTER traversing a leader re-encode or a direct fallback push) or
   positively logged LOST with the crashed leader; the two sets are
   disjoint and their union is complete;
2. **one decode per published version at the root, zero per-push
   decodes at leaders** — `decodes_per_publish == 1.0` with
   `agg_mode == 1.0` through the whole degraded run;
3. **leader-crash recovery** — the crashed group falls back to
   direct-to-root pushes, the supervisor respawns the leader on its
   pinned port, the group rejoins, and every process exits 0.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check(name: str, cond: bool, detail: str = "") -> None:
    status = "ok" if cond else "FAIL"
    print(f"  [{status}] {name}" + (f" — {detail}" if detail else ""))
    if not cond:
        raise SystemExit(f"tree_smoke: {name} failed ({detail})")


def main() -> int:
    from pytorch_ps_mpi_tpu.parallel.tree import run_tree

    tdir = tempfile.mkdtemp(prefix="tree_smoke_")
    n_workers, steps = 6, 8
    cfg = {
        "model": "mlp", "model_kw": {"features": (16, 4)},
        "in_shape": (8,), "batch": 32, "seed": 3,
        "codec": "topk", "codec_kw": {"fraction": 0.25},
        "optim": "sgd", "hyper": {"lr": 0.05}, "steps": steps,
        "frame_check": True, "transport": "tcp",
        "max_staleness": 10 ** 9, "degraded_round_after": 1.0,
        "n_workers": n_workers, "group_size": 3,
        "lineage": True, "lineage_dir": tdir,
        "leader_kw": {"crash_at_round": {"0": 1}, "rejoin_every": 2,
                      "degrade_after": 1.0, "flush_after": 2.0},
    }
    print(f"tree_smoke: 2-group/{n_workers}-worker tree, leader-0 crash "
          f"at round 1, {steps} steps/worker  ({tdir})")
    params, m = run_tree(cfg, timeout=280.0)

    tree = m["tree"]
    check("every worker exited cleanly", tree["worker_codes"] == [0] * 6,
          str(tree["worker_codes"]))
    check("every leader (final generation) exited cleanly",
          tree["leader_codes"] == [0, 0], str(tree["leader_codes"]))
    check("crashed leader was respawned", tree["leader_respawns"] >= 1,
          str(tree["leader_respawns"]))
    check("aggregation armed at the root", m["agg_mode"] == 1.0)
    check("ONE decode per published version at the root",
          m["decodes_per_publish"] == 1.0, str(m["decodes_per_publish"]))
    check("training improved through the chaos",
          m["loss_final"] < m["loss_initial"],
          f"{m['loss_initial']:.3f} -> {m['loss_final']:.3f}")
    check("degraded rounds were counted, not hung on",
          m["degraded_rounds"] >= 1.0, str(m["degraded_rounds"]))

    # -- exact accounting through every hop -------------------------------
    lost = set()
    hop_rows = 0
    for g in range(2):
        p = os.path.join(tdir, f"lineage-leader{g}.jsonl")
        if not os.path.exists(p):
            continue
        for line in open(p):
            r = json.loads(line)
            if r.get("kind") == "hop":
                hop_rows += 1
            if r.get("kind") == "leader_consume" and r.get("lost"):
                lost.add((r["worker"], r["step"], r["seq"]))
    composed = set()
    for line in open(os.path.join(tdir, "lineage-server.jsonl")):
        r = json.loads(line)
        pushes = (r.get("pushes") or []) + (
            [r["push"]] if "push" in r else [])
        for p in pushes:
            for e in p.get("composed") or []:
                composed.add((e["worker"], e["step"], e["seq"]))
    expect = {(w, s, s) for w in range(n_workers) for s in range(steps)}
    check("hop rows carry the per-stage latency breakdown", hop_rows >= 2,
          f"{hop_rows} hop rows")
    check("root-composed and leader-lost sets are disjoint",
          not (composed & lost), str(composed & lost))
    check("EVERY worker push accounted through every hop",
          composed | lost == expect,
          f"{len(composed)} composed + {len(lost)} lost "
          f"(missing {len(expect - composed - lost)}, "
          f"phantom {len((composed | lost) - expect)})")
    check("tree_composed matches the root-composed accounting",
          m["tree_composed"] >= len(composed), str(m["tree_composed"]))
    check("the crashed group's workers reached the root "
          "(fallback and/or rejoin)",
          any(w in (0, 1, 2) for w, _, _ in composed))
    print(f"  accounting: {len(composed)} composed at root + {len(lost)} "
          f"lost with the crashed leader = {len(expect)} worker pushes")
    print("tree_smoke: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
