"""Summarize FlightRecorder JSONL dumps into a per-phase table.

Usage:
  python tools/telemetry_report.py RUN_DIR_OR_JSONL [more ...] [--json]
      [--by-worker]

Accepts recorder JSONL files and/or directories containing them (a
``--telemetry-dir`` run drops ``server.jsonl`` + ``worker-N.jsonl`` +
``trace.json`` in one directory; every ``*.jsonl`` inside is merged).
Spans aggregate into count / total / mean / p50 / p95 / max wall time
per name; point events are counted. ``--by-worker`` splits rows per
worker id — the straggler view. ``--json`` emits the same summary as a
machine-readable dict.

Gradient-lineage files (``lineage-*.jsonl``, ``telemetry.lineage``) get
their own section — exact push-latency/staleness tables per worker,
per-version composition summary, critical-path stage counts — and are
routed AWAY from the recorder-span merge like the beacon/faults/numerics
side channels.

Prometheus scrape snapshots (``*.prom`` — ``serve()`` drops
``metrics.prom`` into the telemetry dir at exit) are parsed too,
INCLUDING worker-labeled series (``ps_frames_rejected_total{worker="1"}``,
``ps_worker_anomaly_total{...}`` — previously silently ignored): labeled
instruments are tabulated per worker in their own section.

Round-anatomy rows (``anatomy-*.jsonl``, ``telemetry.anatomy``) get the
**anatomy** section: per-stage critical-path shares and the ranked
what-if advisor table ("stage X 20% faster ⇒ round time −Y%"); with only
``lineage-*.jsonl`` present the section is rebuilt offline from the
lineage rows — the same decomposition either way.  Sidecar routing for
ALL of these comes from the one shared
``pytorch_ps_mpi_tpu.telemetry.SIDECAR_PREFIXES`` registry.

The fleet observability plane's artifacts get their own sections, all
routed AWAY from the recorder-span merge: ``timeseries-*.jsonl``
(``telemetry.timeseries``) → the **history** section (per-key
first/last/min/max/p95 over the retained samples),
``profile-*.txt`` (``telemetry.profiler`` collapsed stacks) → the
**profile** section (profiles from every process MERGED, top-N
self-time table + native fold/pump cycle counters), ``slo-*.jsonl``
(``telemetry.slo``) → the **slo** section (verdict counts per rule,
breach/recover listing), and ``freshness-*.jsonl``
(``telemetry.freshness``) → the **freshness** section: read-path
propagation rebuilt offline from the persisted FRS1 rows — per-hop
skew-corrected latency quantiles, publish→visible latency, and
per-reader delivery-age tables.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pytorch_ps_mpi_tpu.telemetry import load_jsonl


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def collect_files(paths: List[str]) -> List[str]:
    from pytorch_ps_mpi_tpu.telemetry import (
        SIDECAR_PREFIXES,
        sidecar_prefix,
    )

    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            # sidecar routing comes from the ONE shared registry
            # (telemetry.SIDECAR_PREFIXES): a sidecar with a report
            # route (numerics-/lineage-/anatomy-/timeseries-/slo-/
            # control-) is picked up here and dispatched to its section
            # by summarize(); a routeless sidecar (faults-/beacon-) is
            # an operator-facing raw log and never enters the report.
            # Recorder files (server.jsonl, worker-N.jsonl) pass
            # through to the span merge.  psanalyze's sidecar-registry
            # rule guarantees no prefix exists outside the registry.
            def _keep(f: str) -> bool:
                pref = sidecar_prefix(f)
                return pref is None or SIDECAR_PREFIXES[pref] is not None

            out.extend(sorted(
                f for f in glob.glob(os.path.join(p, "*.jsonl"))
                if _keep(f)
            ))
            out.extend(sorted(glob.glob(os.path.join(p, "*.prom"))))
            out.extend(sorted(glob.glob(
                os.path.join(p, "postmortem-*.json"))))
            out.extend(sorted(glob.glob(
                os.path.join(p, "profile-*.txt"))))
        else:
            out.append(p)
    if not out:
        raise SystemExit(f"no .jsonl/.prom files found under {paths}")
    return out


# the ONE prometheus-text parser — the fleet poller and this report
# share it (it moved to the package so in-process consumers need no
# tools/ import); re-exported here for existing callers
from pytorch_ps_mpi_tpu.telemetry.fleet import (  # noqa: E402
    parse_prometheus_text,
)


def _summarize_numerics(traj_rows: List[Dict[str, Any]],
                        probe_rows: List[Dict[str, Any]],
                        postmortems: List[Dict[str, Any]]
                        ) -> Optional[Dict[str, Any]]:
    """The numerics section: grad-norm trajectory summary from the
    server rows, latest codec-fidelity probe per (worker, codec), and
    the postmortem dumps found in the directory."""
    if not (traj_rows or probe_rows or postmortems):
        return None
    out: Dict[str, Any] = {"postmortems": postmortems}
    norms = [r["grad_norm"] for r in traj_rows
             if isinstance(r.get("grad_norm"), (int, float))]
    if traj_rows:
        last = traj_rows[-1]
        out["trajectory"] = {
            "rows": len(traj_rows),
            "grad_norm_first": norms[0] if norms else None,
            "grad_norm_last": norms[-1] if norms else None,
            "grad_norm_min": min(norms) if norms else None,
            "grad_norm_max": max(norms) if norms else None,
            "update_ratio_last": last.get("update_ratio"),
            "nonfinite_total": last.get("nonfinite_total", 0),
        }
    latest: Dict[Any, Dict[str, Any]] = {}
    counts: Dict[Any, int] = {}
    for r in probe_rows:  # file order == append order: keep the latest
        k = (r.get("worker"), r.get("codec"))
        latest[k] = r
        counts[k] = counts.get(k, 0) + 1
    out["probes"] = [
        {"worker": k[0], "codec": k[1],
         "rel_error": v.get("rel_error"), "cosine": v.get("cosine"),
         "bits_per_param": v.get("bits_per_param"),
         "ef_residual_norm": v.get("ef_residual_norm"),
         "probes": counts[k]}
        for k, v in sorted(latest.items(), key=lambda kv: str(kv[0]))
    ]
    return out


def _summarize_lineage(rows: List[Dict[str, Any]]
                       ) -> Optional[Dict[str, Any]]:
    """The lineage section: exact push-latency/staleness tables,
    per-version composition summary, and critical-path stage counts —
    aggregated from ``lineage-*.jsonl`` publish/drop/round rows."""
    if not rows:
        return None
    publishes = [r for r in rows if r.get("kind") == "publish"]
    drops = [r for r in rows if r.get("kind") == "drop"]
    rounds = [r for r in rows if r.get("kind") == "round"]
    per_worker: Dict[Any, Dict[str, List[float]]] = {}
    sizes: List[int] = []
    for r in publishes:
        pushes = r.get("pushes") or []
        sizes.append(len(pushes))
        for p in pushes:
            d = per_worker.setdefault(p.get("worker"),
                                      {"e2e": [], "stale": []})
            if p.get("e2e_s") is not None:
                d["e2e"].append(float(p["e2e_s"]))
            d["stale"].append(float(p.get("staleness", 0)))
    for r in drops:
        p = r.get("push") or {}
        d = per_worker.setdefault(p.get("worker"),
                                  {"e2e": [], "stale": []})
        if "staleness" in p:
            d["stale"].append(float(p["staleness"]))
    workers = []
    for w, d in sorted(per_worker.items(), key=lambda kv: str(kv[0])):
        e2e, stale = sorted(d["e2e"]), sorted(d["stale"])
        workers.append({
            "worker": w, "pushes": len(stale),
            "e2e_ms_p50": 1e3 * _percentile(e2e, 0.50) if e2e else None,
            "e2e_ms_p95": 1e3 * _percentile(e2e, 0.95) if e2e else None,
            "stale_p50": _percentile(stale, 0.50) if stale else None,
            "stale_max": stale[-1] if stale else None,
        })
    critical: Dict[Any, int] = {}
    for r in rounds:
        k = (r.get("gating_worker"), r.get("stage"))
        critical[k] = critical.get(k, 0) + 1
    # per-hop latency breakdown (hierarchical tree): leader "hop" rows
    # carry the fold / EF-re-encode / upstream-push stage walls — the
    # numbers that say where a tree's round time goes
    hop_rows = [r for r in rows if r.get("kind") == "hop"]
    per_leader: Dict[Any, Dict[str, List[float]]] = {}
    for r in hop_rows:
        d = per_leader.setdefault(r.get("leader"), {
            "fold": [], "encode": [], "push": [], "composed": [],
            "rel_error": []})
        for key, src in (("fold", "fold_s"), ("encode", "encode_s"),
                         ("push", "push_s")):
            if r.get(src) is not None:
                d[key].append(float(r[src]))
        d["composed"].append(float(len(r.get("composed") or [])))
        if r.get("hop_rel_error") is not None:
            d["rel_error"].append(float(r["hop_rel_error"]))
    hops = []
    for leader, d in sorted(per_leader.items(), key=lambda kv: str(kv[0])):
        row: Dict[str, Any] = {
            "leader": leader, "rounds": len(d["composed"]),
            "composed_total": int(sum(d["composed"])),
        }
        for key in ("fold", "encode", "push"):
            vals = sorted(d[key])
            row[f"{key}_ms_p50"] = (1e3 * _percentile(vals, 0.50)
                                    if vals else None)
            row[f"{key}_ms_p95"] = (1e3 * _percentile(vals, 0.95)
                                    if vals else None)
        row["rel_error_last"] = (d["rel_error"][-1]
                                 if d["rel_error"] else None)
        hops.append(row)
    return {
        "publishes": len(publishes),
        "pushes_composed": sum(sizes),
        "drops": len(drops),
        "composition": {
            "mean_pushes_per_version": (sum(sizes) / len(sizes)
                                        if sizes else 0.0),
            "max_pushes_per_version": max(sizes) if sizes else 0,
        },
        "workers": workers,
        "critical_path": [
            {"worker": w, "stage": s, "rounds": n}
            for (w, s), n in sorted(critical.items(),
                                    key=lambda kv: -kv[1])
        ],
        "hops": hops,
    }


def _summarize_anatomy(round_rows: List[Dict[str, Any]],
                       lineage_rows: List[Dict[str, Any]]
                       ) -> Optional[Dict[str, Any]]:
    """The anatomy section: per-stage critical-path shares + the ranked
    what-if advisor table.  Prefers the live engine's persisted
    ``anatomy-*.jsonl`` round rows; when only lineage rows exist the
    engine is rebuilt offline (``telemetry.anatomy.anatomy_from_rows``)
    — the same decomposition either way."""
    if not round_rows and not lineage_rows:
        return None
    from pytorch_ps_mpi_tpu.telemetry.anatomy import (
        STAGES,
        anatomy_from_round_rows,
        anatomy_from_rows,
    )

    # prefer the live engine's own persisted round rows (replayed
    # through the engine's loader so offline state can never drift
    # from what _observe builds live); lineage rows are the fallback
    eng = (anatomy_from_round_rows(round_rows) if round_rows
           else anatomy_from_rows(lineage_rows))
    if not eng.rounds:
        return None
    snap = eng.snapshot()
    return {
        "rounds": snap["rounds"],
        "critical_path": snap["critical_path"],
        "stages": snap["stages"],
        "advisor": eng.advisor(),
        "stage_names": list(STAGES),
    }


def _summarize_history(rows: List[Dict[str, Any]]
                       ) -> Optional[Dict[str, Any]]:
    """The history section: per-key first/last/min/max/p95 over the
    persisted ``timeseries-*.jsonl`` samples — dead keys (all zeros)
    dropped so the table shows the metrics that MOVED."""
    if not rows:
        return None
    per_key: Dict[str, List[float]] = {}
    t_first = t_last = None
    for r in rows:
        t = float(r["t"])
        t_first = t if t_first is None else min(t_first, t)
        t_last = t if t_last is None else max(t_last, t)
        for k, v in r["m"].items():
            per_key.setdefault(k, []).append(float(v))
    keys = []
    for k, vals in sorted(per_key.items()):
        if not any(v != 0.0 for v in vals):
            continue
        s = sorted(vals)
        keys.append({
            "key": k, "n": len(vals),
            "first": vals[0], "last": vals[-1],
            "min": s[0], "max": s[-1],
            "p95": _percentile(s, 0.95),
        })
    return {
        "samples": len(rows),
        "span_s": round((t_last - t_first), 3) if rows else 0.0,
        "keys": keys,
    }


def _summarize_profiles(paths: List[str]) -> Optional[Dict[str, Any]]:
    """The profile section: every process's collapsed stacks MERGED,
    top-N self-time, per-file meta (rate/overhead), and the native
    fold/pump cycle counters summed across processes."""
    if not paths:
        return None
    from pytorch_ps_mpi_tpu.telemetry.profiler import (
        load_profile,
        top_frames,
    )

    merged: Dict[str, int] = {}
    files = []
    native: Dict[str, Dict[str, int]] = {}
    for p in paths:
        meta, counts = load_profile(p)
        for stack, n in counts.items():
            merged[stack] = merged.get(stack, 0) + n
        files.append({"file": os.path.basename(p),
                      "name": meta.get("name"),
                      "samples": meta.get("samples"),
                      "hz_effective": meta.get("hz_effective"),
                      "overhead_frac": meta.get("overhead_frac")})
        for lib, stats in (meta.get("native") or {}).items():
            acc = native.setdefault(lib, {})
            for k, v in stats.items():
                acc[k] = acc.get(k, 0) + int(v)
    return {
        "files": files,
        "samples": sum(merged.values()),
        "stacks": len(merged),
        "top": top_frames(merged, 15),
        "native": native,
    }


def _summarize_slo(rows: List[Dict[str, Any]]
                   ) -> Optional[Dict[str, Any]]:
    """The slo section: verdict counts per rule + the event listing."""
    if not rows:
        return None
    per_rule: Dict[str, Dict[str, int]] = {}
    for r in rows:
        d = per_rule.setdefault(str(r.get("rule")),
                                {"breach": 0, "recover": 0})
        kind = r.get("kind")
        if kind in d:
            d[kind] += 1
    return {
        "verdicts": len(rows),
        "rules": [{"rule": k, **v} for k, v in sorted(per_rule.items())],
        "events": rows[-32:],
    }


def _summarize_freshness(rows: List[Dict[str, Any]]
                         ) -> Optional[Dict[str, Any]]:
    """The freshness section: read-path propagation rebuilt offline
    from ``freshness-*.jsonl`` publish/delivery rows — per-hop
    skew-corrected latency quantiles, publish→visible latency, and
    per-reader delivery-age tables.  Same math as the live
    :class:`~pytorch_ps_mpi_tpu.telemetry.freshness.FreshnessTracker`
    (the hop chains replay through ``hop_latencies_ms``)."""
    if not rows:
        return None
    from pytorch_ps_mpi_tpu.telemetry.freshness import hop_latencies_ms

    publishes = [r for r in rows if r.get("kind") == "publish"]
    deliveries = [r for r in rows if r.get("kind") == "delivery"]
    per_hop: Dict[int, List[float]] = {}
    visible: List[float] = []
    for r in publishes:
        try:
            lats = hop_latencies_ms(r)
        except (KeyError, TypeError):
            continue
        for h, lat in zip(r.get("hops") or [], lats):
            per_hop.setdefault(int(h["hop_index"]), []).append(lat)
        if r.get("visible_ms") is not None:
            visible.append(float(r["visible_ms"]))
    hops = []
    for idx, lats in sorted(per_hop.items()):
        s = sorted(lats)
        hops.append({"hop": idx, "n": len(s),
                     "lat_ms_p50": _percentile(s, 0.50),
                     "lat_ms_p95": _percentile(s, 0.95)})
    per_reader: Dict[Any, List[float]] = {}
    for r in deliveries:
        if r.get("age_ms") is not None:
            per_reader.setdefault(r.get("reader"), []).append(
                float(r["age_ms"]))
    readers = []
    for who, ages in sorted(per_reader.items(), key=lambda kv: str(kv[0])):
        s = sorted(ages)
        readers.append({"reader": who, "deliveries": len(s),
                        "age_ms_p50": _percentile(s, 0.50),
                        "age_ms_p95": _percentile(s, 0.95),
                        "age_ms_max": s[-1]})
    vis = sorted(visible)
    return {
        "publishes": len(publishes),
        "deliveries": len(deliveries),
        "visible_ms_p50": _percentile(vis, 0.50) if vis else None,
        "visible_ms_p95": _percentile(vis, 0.95) if vis else None,
        "hops": hops,
        "readers": readers,
    }


def _summarize_hop(rows: List[Dict[str, Any]]
                   ) -> Optional[Dict[str, Any]]:
    """The hop-anatomy section: leader-pipeline occupancy rebuilt
    offline from ``hop-*.jsonl`` rows by replaying them through the
    SAME engine the leaders ran live
    (:func:`~pytorch_ps_mpi_tpu.telemetry.hop_anatomy.
    hop_anatomy_from_rows`) — per-leader busy fractions, sub-stage
    medians, and the streaming-headroom projection, byte-identical to
    the live scoreboard."""
    if not rows:
        return None
    from pytorch_ps_mpi_tpu.telemetry.hop_anatomy import (
        hop_anatomy_from_rows,
    )

    eng = hop_anatomy_from_rows(rows)
    if not eng.rounds:
        return None
    return eng.snapshot()


def _summarize_actions(rows: List[Dict[str, Any]],
                       flap_window_s: float = 10.0
                       ) -> Optional[Dict[str, Any]]:
    """The actions section: per-rule controller action counts, a flap
    check (a double reversal on one (rule, worker) inside
    ``flap_window_s`` — e.g. evict→readmit→evict — is a flap suspect),
    and the last-action tail. Rows come from ``control-*.jsonl``
    (``pytorch_ps_mpi_tpu.control``)."""
    if not rows:
        return None
    per_rule: Dict[str, Dict[str, int]] = {}
    hist: Dict[Any, List[Dict[str, Any]]] = {}
    flaps: List[Dict[str, Any]] = []
    # time order, not file-glob order: a sharded run contributes one
    # control-*.jsonl per shard and the tail must show the NEWEST
    # actions across all of them
    rows = sorted(rows, key=lambda x: float(x.get("t", 0.0)))
    vjoin: Dict[Tuple[str, str, str], int] = {}
    for r in rows:
        rule = str(r.get("rule"))
        d = per_rule.setdefault(rule, {})
        d[str(r.get("action"))] = d.get(str(r.get("action")), 0) + 1
        # action↔verdict join: every action row carries its triggering
        # verdict (id + kind) — the audit question is "which verdict
        # fired this", answered per (rule, action, verdict kind)
        vk = str((r.get("verdict") or {}).get("kind") or "")
        if vk:
            jk = (rule, str(r.get("action")), vk)
            vjoin[jk] = vjoin.get(jk, 0) + 1
        key = (rule, r.get("worker"))
        h = hist.setdefault(key, [])
        if (len(h) >= 2
                and float(r.get("t", 0.0)) - float(h[-2].get("t", 0.0))
                < flap_window_s
                and r.get("new") == h[-1].get("old")
                and h[-1].get("new") == h[-2].get("old")):
            flaps.append({"rule": rule, "worker": r.get("worker"),
                          "t": r.get("t")})
        h.append(r)
        if len(h) > 4:
            del h[0]
    return {
        "actions": len(rows),
        "rules": [{"rule": k, **v} for k, v in sorted(per_rule.items())],
        "verdict_join": [
            {"rule": r, "action": a, "verdict": vk, "actions": n}
            for (r, a, vk), n in sorted(vjoin.items())],
        "flap_suspects": flaps,
        "tail": rows[-16:],
    }


def summarize(files: List[str], by_worker: bool = False) -> Dict[str, Any]:
    """Merged summary over every file: per-span-name stats, event counts,
    and recorder meta (dropped counts make truncation visible)."""
    spans: Dict[Any, List[float]] = {}
    events: Dict[Any, int] = {}
    meta: List[Dict[str, Any]] = []
    labeled: List[Dict[str, Any]] = []
    traj_rows: List[Dict[str, Any]] = []
    probe_rows: List[Dict[str, Any]] = []
    postmortems: List[Dict[str, Any]] = []
    lineage_rows: List[Dict[str, Any]] = []
    anatomy_rows: List[Dict[str, Any]] = []
    ts_rows: List[Dict[str, Any]] = []
    slo_rows: List[Dict[str, Any]] = []
    action_rows: List[Dict[str, Any]] = []
    fresh_rows: List[Dict[str, Any]] = []
    hop_rows: List[Dict[str, Any]] = []
    profile_paths: List[str] = []
    for path in files:
        base = os.path.basename(path)
        if base.startswith("profile-") and path.endswith(".txt"):
            # collapsed-stack profiles (telemetry.profiler) — merged
            # across processes into the profile section
            profile_paths.append(path)
            continue
        if base.startswith("timeseries-") and path.endswith(".jsonl"):
            # retained metric history (telemetry.timeseries) — routed to
            # the history section, never the recorder-span merge
            from pytorch_ps_mpi_tpu.telemetry.timeseries import (
                load_timeseries_rows,
            )

            ts_rows.extend(load_timeseries_rows(path))
            continue
        if base.startswith("slo-") and path.endswith(".jsonl"):
            # SLO verdict events (telemetry.slo) — their own section
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        slo_rows.append(json.loads(line))
                    except ValueError:
                        continue
            continue
        if base.startswith("control-") and path.endswith(".jsonl"):
            # controller action rows (pytorch_ps_mpi_tpu.control) —
            # routed to the actions section, never the span merge (the
            # replay INPUT rows ride timeseries-control-*.jsonl and are
            # routed with the other retained histories above)
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        action_rows.append(json.loads(line))
                    except ValueError:
                        continue
            continue
        if base.startswith("freshness-") and path.endswith(".jsonl"):
            # read-path FRS1 propagation rows (telemetry.freshness) —
            # routed to the freshness section, never the span merge
            from pytorch_ps_mpi_tpu.telemetry.freshness import (
                load_fresh_rows,
            )

            fresh_rows.extend(load_fresh_rows(path))
            continue
        if base.startswith("hop-") and path.endswith(".jsonl"):
            # leader hop sub-stage occupancy rows
            # (telemetry.hop_anatomy) — routed to the hop-anatomy
            # section, never the recorder-span merge
            from pytorch_ps_mpi_tpu.telemetry.hop_anatomy import (
                load_hop_rows,
            )

            hop_rows.extend(load_hop_rows(path))
            continue
        if base.startswith("postmortem-") and path.endswith(".json"):
            # a divergence postmortem dump (telemetry.numerics) — one
            # JSON document, NOT an event JSONL; surface its headline
            try:
                with open(path) as f:
                    pm = json.load(f)
            except ValueError:
                continue
            postmortems.append({
                "file": base, "reason": pm.get("reason"),
                "worker": pm.get("worker"), "applied": pm.get("applied"),
                "ring_rows": len(pm.get("step_stats_ring") or []),
            })
            continue
        if base.startswith("lineage-") and path.endswith(".jsonl"):
            # per-version push compositions (telemetry.lineage) — routed
            # to the lineage section, never the recorder-span merge
            from pytorch_ps_mpi_tpu.telemetry.lineage import (
                load_lineage_rows,
            )

            lineage_rows.extend(load_lineage_rows(path))
            continue
        if base.startswith("anatomy-") and path.endswith(".jsonl"):
            # round-anatomy critical-path rows (telemetry.anatomy) —
            # routed to the anatomy section, never the span merge
            from pytorch_ps_mpi_tpu.telemetry.anatomy import (
                load_anatomy_rows,
            )

            anatomy_rows.extend(load_anatomy_rows(path))
            continue
        if base.startswith("numerics-") and path.endswith(".jsonl"):
            # numerics trajectories: the server's grad-norm/update-ratio
            # rows and the workers' codec-fidelity probe rows
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        r = json.loads(line)
                    except ValueError:
                        continue
                    (traj_rows if r.get("worker") == "server"
                     else probe_rows).append(r)
            continue
        if path.endswith(".prom"):
            with open(path) as f:
                for s in parse_prometheus_text(f.read()):
                    # the per-worker labeled series (PR 3's rejection
                    # counters, the diagnosis layer's anomaly/gating/
                    # health instruments) are the tabulation target;
                    # unlabeled totals already ride the metrics dicts
                    if s["labels"]:
                        labeled.append({"file": os.path.basename(path),
                                        **s})
            continue
        m, rows = load_jsonl(path)
        if m:
            meta.append({"file": os.path.basename(path),
                         "worker": m.get("worker"),
                         "n_events": m.get("n_events"),
                         "dropped": m.get("dropped", 0)})
        for r in rows:
            key = ((r["name"], r.get("worker")) if by_worker
                   else (r["name"], None))
            if r.get("kind") == "span":
                spans.setdefault(key, []).append(float(r.get("dur", 0.0)))
            else:
                events[key] = events.get(key, 0) + 1

    def row(key, durs):
        durs = sorted(durs)
        name, worker = key
        return {
            "name": name,
            "worker": worker,
            "count": len(durs),
            "total_s": sum(durs),
            "mean_ms": 1e3 * sum(durs) / len(durs),
            "p50_ms": 1e3 * _percentile(durs, 0.50),
            "p95_ms": 1e3 * _percentile(durs, 0.95),
            "max_ms": 1e3 * durs[-1],
        }

    return {
        "files": meta,
        "spans": sorted(
            (row(k, v) for k, v in spans.items()),
            key=lambda r: -r["total_s"],
        ),
        "events": [
            {"name": k[0], "worker": k[1], "count": n}
            for k, n in sorted(events.items(), key=lambda kv: -kv[1])
        ],
        # worker-labeled (and any other labeled) instrument series from
        # *.prom scrape snapshots, histogram bucket rows excluded (the
        # per-worker counters are the per-worker story)
        "labeled_metrics": sorted(
            (s for s in labeled if "le" not in s["labels"]),
            key=lambda s: (s["name"], sorted(s["labels"].items())),
        ),
        "numerics": _summarize_numerics(traj_rows, probe_rows, postmortems),
        "lineage": _summarize_lineage(lineage_rows),
        "anatomy": _summarize_anatomy(anatomy_rows, lineage_rows),
        "history": _summarize_history(ts_rows),
        "profile": _summarize_profiles(profile_paths),
        "slo": _summarize_slo(slo_rows),
        "actions": _summarize_actions(action_rows),
        "freshness": _summarize_freshness(fresh_rows),
        "hop": _summarize_hop(hop_rows),
        "dropped_total": sum(m.get("dropped") or 0 for m in meta),
    }


def format_table(summary: Dict[str, Any]) -> str:
    lines: List[str] = []
    has_worker = any(r["worker"] is not None for r in summary["spans"])
    cols = (["phase"] + (["worker"] if has_worker else [])
            + ["count", "total s", "mean ms", "p50 ms", "p95 ms", "max ms"])
    rows = []
    for r in summary["spans"]:
        row = [r["name"]] + ([str(r["worker"])] if has_worker else []) + [
            str(r["count"]), f"{r['total_s']:.3f}", f"{r['mean_ms']:.2f}",
            f"{r['p50_ms']:.2f}", f"{r['p95_ms']:.2f}", f"{r['max_ms']:.2f}",
        ]
        rows.append(row)
    widths = [max(len(c), *(len(r[i]) for r in rows)) if rows else len(c)
              for i, c in enumerate(cols)]
    fmt = "  ".join(f"{{:<{w}}}" if i == 0 else f"{{:>{w}}}"
                    for i, w in enumerate(widths))
    lines.append(fmt.format(*cols))
    lines.append("  ".join("-" * w for w in widths))
    for r in rows:
        lines.append(fmt.format(*r))
    if summary["events"]:
        lines.append("")
        lines.append("events:")
        for e in summary["events"]:
            who = f" [worker {e['worker']}]" if e["worker"] is not None else ""
            lines.append(f"  {e['name']}{who}: {e['count']}")
    if summary.get("labeled_metrics"):
        lines.append("")
        lines.append("labeled metrics (scrape snapshot):")
        for s in summary["labeled_metrics"]:
            labels = ",".join(f"{k}={v}"
                              for k, v in sorted(s["labels"].items()))
            v = s["value"]
            v_txt = str(int(v)) if float(v).is_integer() else f"{v:.6g}"
            lines.append(f"  {s['name']}{{{labels}}}: {v_txt}")
    num = summary.get("numerics")
    if num:
        lines.append("")
        lines.append("numerics:")
        traj = num.get("trajectory")
        if traj:
            ur = traj.get("update_ratio_last")
            lines.append(
                f"  grad-norm trajectory ({traj['rows']} rows): "
                f"first={traj['grad_norm_first']:.4g} "
                f"last={traj['grad_norm_last']:.4g} "
                f"min={traj['grad_norm_min']:.4g} "
                f"max={traj['grad_norm_max']:.4g}"
                + (f"  update-ratio={ur:.3g}" if ur is not None else "")
            )
            lines.append(
                f"  nonfinite pushes: {int(traj.get('nonfinite_total', 0))}"
            )
        def _g(v, spec=".4g"):
            # a probe that landed on a poisoned gradient carries None
            return "-" if v is None else format(v, spec)

        for p in num.get("probes", []):
            ef = p.get("ef_residual_norm")
            lines.append(
                f"  codec fidelity [worker {p['worker']}] {p['codec']}: "
                f"rel-err={_g(p['rel_error'])} cos={_g(p['cosine'])} "
                f"bits/param={_g(p['bits_per_param'], '.3g')} "
                f"({p['probes']} probes)"
                + (f" ef-residual={ef:.4g}" if ef is not None else "")
            )
        for pm in num.get("postmortems", []):
            lines.append(
                f"  postmortem {pm['file']}: reason={pm['reason']} "
                f"worker={pm['worker']} applied={pm['applied']} "
                f"ring={pm['ring_rows']} rows"
            )
    lin = summary.get("lineage")
    if lin:
        lines.append("")
        lines.append("lineage:")
        comp = lin["composition"]
        lines.append(
            f"  {lin['publishes']} published versions composed of "
            f"{lin['pushes_composed']} pushes "
            f"(mean {comp['mean_pushes_per_version']:.2f}/version, "
            f"max {comp['max_pushes_per_version']}); "
            f"{lin['drops']} pushes dropped"
        )

        def _ms(v):
            return "-" if v is None else f"{v:.1f}ms"

        for w in lin.get("workers", []):
            stale50 = w.get("stale_p50")
            lines.append(
                f"  worker {w['worker']}: {w['pushes']} pushes  "
                f"e2e p50/p95={_ms(w.get('e2e_ms_p50'))}/"
                f"{_ms(w.get('e2e_ms_p95'))}  "
                f"stale p50/max="
                f"{'-' if stale50 is None else f'{stale50:.0f}'}/"
                f"{'-' if w.get('stale_max') is None else int(w['stale_max'])}"
            )
        for c in lin.get("critical_path", []):
            lines.append(
                f"  critical path: worker {c['worker']} "
                f"[{c['stage']}] gated {c['rounds']} rounds"
            )
        for h in lin.get("hops", []):
            rel = h.get("rel_error_last")
            lines.append(
                f"  hop [leader {h['leader']}]: {h['rounds']} rounds, "
                f"{h['composed_total']} pushes composed  "
                f"fold p50/p95={_ms(h.get('fold_ms_p50'))}/"
                f"{_ms(h.get('fold_ms_p95'))}  "
                f"encode={_ms(h.get('encode_ms_p50'))}/"
                f"{_ms(h.get('encode_ms_p95'))}  "
                f"push={_ms(h.get('push_ms_p50'))}/"
                f"{_ms(h.get('push_ms_p95'))}"
                + ("" if rel is None else f"  rel-err={rel:.4g}")
            )
    anat = summary.get("anatomy")
    if anat:
        lines.append("")
        lines.append(f"round anatomy ({anat['rounds']} rounds decomposed):")
        for c in anat.get("critical_path", []):
            st = anat.get("stages", {}).get(c["stage"]) or {}
            p50 = st.get("p50_ms")
            lines.append(
                f"  critical path [{c['stage']}]: {c['rounds']} rounds "
                f"({c['share'] * 100:.0f}%)"
                + ("" if p50 is None else f"  stage p50={p50:.1f}ms"))
        adv = anat.get("advisor") or []
        if adv:
            lines.append("  what-if advisor (ranked):")
            acols = ["stage", "crit%", "p50 ms", "p95 ms", "-20% saves",
                     "debottleneck saves"]
            arows = []
            for a in adv:
                w20 = a.get("whatif_20") or {}
                db = a.get("debottleneck") or {}
                arows.append([
                    a["stage"],
                    f"{a['critical_share'] * 100:.0f}",
                    "-" if a.get("p50_ms") is None else f"{a['p50_ms']:.1f}",
                    "-" if a.get("p95_ms") is None else f"{a['p95_ms']:.1f}",
                    f"{w20.get('saving_frac', 0) * 100:.1f}%",
                    f"{db.get('saving_frac', 0) * 100:.1f}% "
                    f"({db.get('saved_s', 0):.2f}s)",
                ])
            aw = [max(len(c), *(len(r[i]) for r in arows)) if arows
                  else len(c) for i, c in enumerate(acols)]
            afmt = "  ".join(f"{{:<{w}}}" if i == 0 else f"{{:>{w}}}"
                             for i, w in enumerate(aw))
            lines.append("    " + afmt.format(*acols))
            for r in arows:
                lines.append("    " + afmt.format(*r))
    hop = summary.get("hop")
    if hop:
        lines.append("")
        lines.append(
            f"hop anatomy ({hop['rounds']} leader rounds, "
            f"{hop['frames']} frames folded, "
            f"{hop['ring_drops']} ring drops):")
        lines.append(
            f"  occupancy: busy={hop['busy_frac'] * 100:.0f}%  "
            f"ingest-wait p50={hop['ingest_wait_ms']:.1f}ms  "
            f"serial p50={hop['serial_ms']:.1f}ms  "
            f"streaming headroom={hop['headroom_ratio']:.2f}x")
        st = hop.get("stages") or {}
        if st:
            scols = ["stage", "p50 ms", "p95 ms"]
            srows = [[name, f"{d['p50_ms']:.2f}", f"{d['p95_ms']:.2f}"]
                     for name, d in st.items()]
            sw = [max(len(c), *(len(r[i]) for r in srows)) if srows
                  else len(c) for i, c in enumerate(scols)]
            sfmt = "  ".join(f"{{:<{w}}}" if i == 0 else f"{{:>{w}}}"
                             for i, w in enumerate(sw))
            lines.append("    " + sfmt.format(*scols))
            for r in srows:
                lines.append("    " + sfmt.format(*r))
        for g, lw in (hop.get("leaders") or {}).items():
            hot = " [hot]" if g == hop.get("hot_leader") else ""
            lines.append(
                f"  leader {g}: {lw['rounds']} rounds  "
                f"busy={lw['busy_frac'] * 100:.0f}%  "
                f"headroom={lw['headroom_ratio']:.2f}x  "
                f"round p50={lw['round_ms']:.1f}ms{hot}")
    hist = summary.get("history")
    if hist:
        lines.append("")
        lines.append(
            f"history ({hist['samples']} samples over "
            f"{hist['span_s']:.1f}s):")
        hcols = ["key", "n", "first", "last", "min", "max", "p95"]
        hrows = [[k["key"], str(k["n"])]
                 + [f"{k[c]:.4g}" for c in ("first", "last", "min",
                                            "max", "p95")]
                 for k in hist["keys"]]
        hw = [max(len(c), *(len(r[i]) for r in hrows)) if hrows
              else len(c) for i, c in enumerate(hcols)]
        hfmt = "  ".join(f"{{:<{w}}}" if i == 0 else f"{{:>{w}}}"
                         for i, w in enumerate(hw))
        lines.append("  " + hfmt.format(*hcols))
        for r in hrows:
            lines.append("  " + hfmt.format(*r))
    prof = summary.get("profile")
    if prof:
        lines.append("")
        files_txt = ", ".join(
            f"{f['name'] or f['file']} ({f['samples']} samples @ "
            f"{f['hz_effective'] or 0:.0f}Hz, "
            f"{(f['overhead_frac'] or 0) * 100:.2f}% self)"
            for f in prof["files"])
        lines.append(f"profile (merged {len(prof['files'])} processes: "
                     f"{files_txt}):")
        for t in prof["top"]:
            lines.append(
                f"  {t['self_frac'] * 100:5.1f}%  self={t['self']:<6d} "
                f"cum={t['cum']:<6d} {t['frame']}")
        for lib, stats in sorted(prof.get("native", {}).items()):
            stats_txt = "  ".join(f"{k}={v}" for k, v in sorted(
                stats.items()))
            lines.append(f"  native [{lib}]: {stats_txt}")
    slo = summary.get("slo")
    if slo:
        lines.append("")
        lines.append(f"slo ({slo['verdicts']} verdicts):")
        for r in slo["rules"]:
            lines.append(f"  {r['rule']}: {r['breach']} breach / "
                         f"{r['recover']} recover")
        for e in slo["events"][-8:]:
            lines.append(
                f"  {e.get('kind')} {e.get('rule')} "
                f"burn_short={e.get('burn_short')} "
                f"burn_long={e.get('burn_long')} t={e.get('t')}")
    fresh = summary.get("freshness")
    if fresh:
        lines.append("")
        v50, v95 = fresh.get("visible_ms_p50"), fresh.get("visible_ms_p95")
        vis_txt = ("" if v50 is None else
                   f"  visible p50/p95={v50:.1f}/{v95:.1f}ms")
        lines.append(
            f"freshness ({fresh['publishes']} publishes, "
            f"{fresh['deliveries']} deliveries):{vis_txt}")
        for h in fresh.get("hops", []):
            lines.append(
                f"  hop {h['hop']}: n={h['n']}  "
                f"lat p50/p95={h['lat_ms_p50']:.2f}/"
                f"{h['lat_ms_p95']:.2f}ms")
        for r in fresh.get("readers", []):
            lines.append(
                f"  reader {r['reader']}: {r['deliveries']} deliveries  "
                f"age p50/p95/max={r['age_ms_p50']:.1f}/"
                f"{r['age_ms_p95']:.1f}/{r['age_ms_max']:.1f}ms")
    act = summary.get("actions")
    if act:
        lines.append("")
        flap_txt = ("no flaps" if not act["flap_suspects"]
                    else f"{len(act['flap_suspects'])} FLAP SUSPECT(S)")
        lines.append(f"control actions ({act['actions']} total, "
                     f"{flap_txt}):")
        for r in act["rules"]:
            counts = "  ".join(f"{k}={v}" for k, v in sorted(r.items())
                               if k != "rule")
            lines.append(f"  {r['rule']}: {counts}")
        for j in act.get("verdict_join") or ():
            lines.append(f"  {j['rule']}.{j['action']} <- "
                         f"{j['verdict']} x{j['actions']}")
        for a in act["tail"][-8:]:
            who = ("" if a.get("worker") is None
                   else f" w{a['worker']}")
            lines.append(
                f"  {a.get('rule')}.{a.get('action')}{who}: "
                f"{a.get('old')} -> {a.get('new')} "
                f"[{(a.get('verdict') or {}).get('kind')}] "
                f"t={a.get('t')}")
        for fl in act["flap_suspects"]:
            lines.append(f"  FLAP: {fl['rule']} worker={fl['worker']} "
                         f"t={fl['t']}")
    if summary["dropped_total"]:
        lines.append("")
        lines.append(
            f"WARNING: {summary['dropped_total']} records evicted by the "
            "bounded buffer — raise the recorder capacity for a complete log"
        )
    return "\n".join(lines)


def main(argv=None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("paths", nargs="+",
                    help="recorder .jsonl files and/or directories of them")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as JSON instead of a table")
    ap.add_argument("--by-worker", action="store_true",
                    help="split span rows per worker id (straggler view)")
    args = ap.parse_args(argv)
    summary = summarize(collect_files(args.paths), by_worker=args.by_worker)
    if args.json:
        print(json.dumps(summary))
    else:
        print(format_table(summary))
    return summary


if __name__ == "__main__":
    main()
