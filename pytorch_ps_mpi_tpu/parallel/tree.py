"""Hierarchical multi-hop aggregation: the PS is a tree, not a star.

The star topology (every worker pushes to one PS) was the last
flat-scaling bottleneck: root ingest bytes/sec grow linearly with worker
count even though per-push fold cost is flat in model size. This module
builds the DynamiQ-shaped fix (PAPERS.md): workers are partitioned into
**groups**, each with a **leader** process that

1. runs a :class:`~pytorch_ps_mpi_tpu.parallel.dcn.WireAggregator` over
   its group's compressed payloads — folded straight from the framed
   wire's validated payload bytes, so a per-push decode NEVER happens
   mid-tree (the leader's ``decodes_done`` stays 0);
2. finalizes ONCE per group round and **re-encodes** the aggregate for
   the upstream hop behind per-hop error feedback
   (:class:`~pytorch_ps_mpi_tpu.codecs.error_feedback.HopErrorFeedback`)
   so fidelity is bounded per hop and composes additively across hops;
3. pushes ONE frame upstream to the root PS, carrying the constituent
   worker trace IDs in the frame's composed-lineage trailer
   (``resilience.frames``) so every worker push is accounted at the
   root's published-version composition.

Topology emulation maps onto the transports: the leaf hop (worker →
leader) is the cheap intra-pod link — shm, or TCP with
``TPS_WAN_RTT_MS`` unset — and defaults to the **identity** group codec,
i.e. an exact local reduce (the multi-process stand-in for an ICI-level
``psum``); the leader → root hop is the compressed DCN link, paying the
WAN emulation's RTT where configured so the DCN tax is real in CI.

Weighting is exact by construction: leaders push group **sums** and the
root divides each round by the TOTAL composed worker-push count read
from the trailers, so degraded groups, ragged group sizes and
direct-to-root fallback pushes (leader crash) all weight correctly
without any coordination.

Resilience: a leader crash makes its group's
:class:`TreeWorkerConn` fall back to pushing **directly to the root**
(compressed, composing themselves in the trailer); the
:func:`run_tree` supervisor respawns the leader on its pinned port and
the group rejoins on its next probe. Root-side, the membership-dynamic
barrier in ``async_train.serve`` (``cfg["tree"]``) absorbs both
transitions.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

PyTree = Any

#: leader-loop tuning knobs and their defaults (``cfg["leader_kw"]``)
LEADER_KNOBS: Dict[str, Any] = {
    "group_transport": "tcp",  # leaf-hop wire: "tcp" | "shm"
    "group_codec": "identity",  # leaf-hop codec (exact local reduce)
    "group_codec_kw": {},       # its constructor kwargs
    "read_poll_s": 0.02,        # upstream snapshot poll cadence
    "degrade_after": 3.0,       # round wait before excluding dead members
    "flush_after": 6.0,         # round wait before a partial fold
    "startup_grace": 120.0,     # member startup window before idle-exit
    "idle_exit_s": 3.0,         # quiet time (members gone) before exit
    "timeout": 600.0,           # absolute leader lifetime bound
    "rejoin_every": 8,          # fallback pushes between leader probes
    "probe_timeout": 1.0,       # leader-probe connect timeout (fallback)
    "crash_at_round": None,     # TEST hook: os._exit before this round
    "max_respawns": 3,          # run_tree: leader respawn budget
}


def group_plan(n_workers: int, group_size: int) -> List[List[int]]:
    """Partition worker ids 0..n-1 into contiguous groups of
    ``group_size`` (the last group takes the remainder; a remainder of
    one still forms a group — its leader is a relay, which keeps the
    root's expected-pusher set uniform)."""
    if group_size < 1:
        raise ValueError(f"group_size must be >= 1, got {group_size}")
    return [list(range(i, min(i + group_size, n_workers)))
            for i in range(0, n_workers, group_size)]


def leader_wid(n_workers: int, group_id: int) -> int:
    """The worker id a group's leader pushes upstream under: leaders
    occupy ids ``n_workers .. n_workers+n_groups-1`` at the root, so
    leaf ids stay free for direct-to-root fallback pushes."""
    return int(n_workers) + int(group_id)


def tree_slot_capacity(n_workers: int, group_size: int) -> int:
    """The composed-lineage trailer capacity every push to the root
    carries: the largest group's size (one trace entry per composed
    worker push; a direct fallback push uses one slot)."""
    return min(int(group_size), int(n_workers))


class _HopLog:
    """Buffered JSONL writer for ``lineage-leader<g>.jsonl`` — the
    leader's half of cross-hop lineage: one ``leader_consume`` row per
    group push it ingests, one ``hop`` row per upstream push (with the
    composed trace IDs and the per-stage hop latency breakdown
    ``tools/telemetry_report.py`` tabulates)."""

    def __init__(self, dir: Optional[str], group_id: int,
                 flush_every: int = 32):
        self._f = None
        self._pending = 0
        self.flush_every = int(flush_every)
        if dir:
            os.makedirs(dir, exist_ok=True)
            self._f = open(
                os.path.join(dir, f"lineage-leader{group_id}.jsonl"), "a")

    def row(self, doc: Dict[str, Any]) -> None:
        if self._f is None:
            return
        self._f.write(json.dumps(doc) + "\n")
        self._pending += 1
        if self._pending >= self.flush_every:
            self.flush()

    def flush(self) -> None:
        if self._f is not None and self._pending:
            self._f.flush()
            self._pending = 0

    def close(self) -> None:
        if self._f is not None:
            self.flush()
            self._f.close()
            self._f = None


def _leader_knobs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    kw = dict(LEADER_KNOBS)
    kw.update(cfg.get("leader_kw") or {})
    return kw


def _upstream_codec(cfg: Dict[str, Any]):
    if not cfg.get("codec"):
        return None
    from pytorch_ps_mpi_tpu.codecs import get_codec

    return get_codec(cfg["codec"], **(cfg.get("codec_kw") or {}))


def _group_codec(kw: Dict[str, Any]):
    from pytorch_ps_mpi_tpu.codecs import get_codec

    return get_codec(kw["group_codec"], **(kw.get("group_codec_kw") or {}))


# ---------------------------------------------------------------------------
# the leader process
# ---------------------------------------------------------------------------

def leader_main(upstream: Sequence[str], group_id: int,
                group: Sequence[int], cfg: Dict[str, Any],
                port: int = 0) -> int:
    """One leader process body: group-facing PS server (compressed
    ingest, zero per-push decodes), upstream-facing worker connection(s)
    (one per root shard — path-sharding composes with key-sharding),
    and the fold → EF re-encode → one-frame-upstream hop between them.
    Returns the number of upstream pushes. ``port`` pins the group
    server's port so a supervisor respawn is rejoinable."""
    from pytorch_ps_mpi_tpu.codecs.error_feedback import HopErrorFeedback
    from pytorch_ps_mpi_tpu.parallel.async_train import make_problem
    from pytorch_ps_mpi_tpu.parallel.dcn import (
        ShmPSServer,
        _flat_size,
        _flatten,
        _unflatten,
    )
    from pytorch_ps_mpi_tpu.parallel.sharded import (
        _slice_template,
        shard_plan,
    )
    from pytorch_ps_mpi_tpu.parallel.tcp import TcpPSServer, TcpPSWorker
    from pytorch_ps_mpi_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    kw = _leader_knobs(cfg)
    group = [int(w) for w in group]
    n_workers = int(cfg["n_workers"])
    slots = int(cfg.get("tree_slots")
                or tree_slot_capacity(n_workers, len(group)))
    _, params0, _, _ = make_problem(cfg)
    lid = leader_wid(n_workers, group_id)

    # -- group-facing server: the leaf hop's compressed ingest ------------
    gcode = _group_codec(kw)
    shm_name = f"/psq_tree_{os.getppid()}_{group_id}"
    if kw["group_transport"] == "shm":
        server = ShmPSServer(shm_name, num_workers=n_workers,
                             template=params0,
                             max_staleness=int(cfg.get("max_staleness", 4)),
                             code=gcode, frame=True)
        addr = f"shm:{shm_name}"
    else:
        server = TcpPSServer(int(port), num_workers=n_workers,
                             template=params0,
                             max_staleness=int(cfg.get("max_staleness", 4)),
                             code=gcode, frame=True)
        addr = f"127.0.0.1:{server.port}"
    gwire = server.wire
    if not gwire.agg_supported:
        raise ValueError(
            f"group codec {kw['group_codec']!r} has no compressed-domain "
            "aggregation algebra — a leader would have to decode per "
            "push, which the tree forbids")

    # -- upstream-facing connections: the DCN hop --------------------------
    ucode = _upstream_codec(cfg)
    sharded = len(upstream) > 1
    flat_n = _flat_size(params0)
    plan = shard_plan(flat_n, len(upstream)) if sharded else [(0, flat_n)]
    conns: List[Any] = []
    hops: List[HopErrorFeedback] = []
    for (start, stop), a in zip(plan, upstream):
        host, p = a.rsplit(":", 1)
        tmpl = _slice_template(stop - start) if sharded else params0
        c = TcpPSWorker(host, int(p), lid, tmpl,
                        code=(_upstream_codec(cfg) if sharded else ucode),
                        timeout=float(cfg.get("open_timeout", 60.0)),
                        bucket_mb=(0.0 if sharded
                                   else float(cfg.get("bucket_mb", 0.0))),
                        frame=True, tree_slots=slots)
        conns.append(c)
        if c.wire is None:
            raise ValueError("the tree's upstream hop needs a codec wire "
                             "(cfg['codec']) — set codec='identity' for "
                             "an uncompressed DCN hop")
        hops.append(HopErrorFeedback(c.wire,
                                     enabled=bool(cfg.get("hop_ef", True))))

    # -- observability: /metrics + /fleet card (role "leader") ------------
    ocfg = dict(cfg)
    ocfg["fleet_role"] = "leader"
    ocfg.pop("fleet_name", None)
    ocfg["fleet_meta"] = {"group": int(group_id), "members": group}
    if ((ocfg.get("fleet_dir") or ocfg.get("metrics_port") is not None
         or ocfg.get("health_port") is not None)
            and getattr(server, "_metrics_http", None) is None):
        http_port = server.start_metrics_http(0)
    else:
        http_port = None
    server.arm_observability(ocfg, name=f"leader{group_id}")
    reg = server.scrape_registry()
    # hop anatomy (cfg["hop_anatomy"]): arm_observability attached the
    # profiler; arm the bounded native interval rings behind its
    # timeline — per-frame validate stamps (tcpps) and per-fold-call
    # spans (wirecodec). Both are drop-and-count on overflow and both
    # arms are no-ops under PS_NO_NATIVE or the shm transport: the
    # timeline then falls back to the Python stage walls alone
    # (validate time stays inside ingest_wait).
    from pytorch_ps_mpi_tpu.utils import native as wc_native

    hop_an = getattr(server, "hop_anatomy", None)
    hop_stamps_on = hop_spans_on = False
    if hop_an is not None:
        ring_cap = int(hop_an.knobs["ring_capacity"])
        stamp_arm = getattr(server, "hop_stamps_arm", None)
        hop_stamps_on = (bool(stamp_arm(ring_cap))
                         if stamp_arm is not None else False)
        hop_spans_on = bool(wc_native.fold_spans_arm(ring_cap))
    state = {"upstream_pushes": 0, "partial_rounds": 0, "composed": 0}

    def _collect(r):
        r.counter("ps_tree_upstream_pushes_total",
                  "aggregate frames this leader pushed upstream").set(
                      float(state["upstream_pushes"]))
        r.counter("ps_tree_partial_rounds_total",
                  "group rounds folded over a partial membership").set(
                      float(state["partial_rounds"]))
        r.gauge("ps_tree_hop_rel_error",
                "last upstream re-encode's relative L2 error "
                "(before EF correction)").set(
                    max(h.last_rel_error for h in hops))
        r.gauge("ps_tree_ef_residual_norm",
                "per-hop error-feedback residual norm").set(
                    sum(h.residual_norm for h in hops))
        r.gauge("ps_tree_leader_decodes",
                "per-push ingest decodes at this leader — the tree's "
                "zero-decodes-mid-tree invariant says this stays 0 "
                "(the EF decode-back is not an ingest decode)").set(
                    float(server.decodes_done))

    reg.add_collector(_collect)

    log = _HopLog(cfg.get("lineage_dir") or cfg.get("telemetry_dir"),
                  group_id)
    # seeded fault injection, role-addressed: a "slow_leader" fault for
    # "leader<g>" arms a per-folded-payload delay from its at_step
    # round on — the structural controller's injected hot hop
    from pytorch_ps_mpi_tpu.resilience.faults import FaultInjector

    inj = FaultInjector.from_cfg(cfg, role=f"leader{group_id}")
    slow_fold_s = 0.0
    hello = {"leader": int(group_id), "addr": addr, "wid": lid}
    if http_port is not None:
        hello["health_port"] = http_port
    print(json.dumps(hello), flush=True)

    # -- the loop ----------------------------------------------------------
    import collections

    pending: Dict[int, Any] = collections.defaultdict(collections.deque)
    v_map: Dict[int, List[int]] = {}
    dead: set = set()
    #: members the topology document reassigned AWAY from this leader
    #: (structural split): they stop gating rounds IMMEDIATELY — no
    #: degrade_after stall — but anything they already pushed here
    #: stays queued and folds exactly (acked pushes are never dropped)
    departed: set = set()
    topo_state = {"seq": 0, "mtime": 0}
    topo_dir = (cfg.get("control_dir") or cfg.get("telemetry_dir")) \
        if cfg.get("topo_actions") else None
    crash_at = kw.get("crash_at_round")
    if isinstance(crash_at, dict):
        crash_at = crash_at.get(str(group_id), crash_at.get(int(group_id)))
    rounds = 0
    up_seq = 0
    t_start = time.monotonic()
    deadline = t_start + float(kw["timeout"])
    round_t0 = time.monotonic()
    last_activity = time.monotonic()
    next_read = 0.0
    next_tick = 0.0
    can_connect = hasattr(server, "connected")
    batch_poll = getattr(server, "poll_grad_batch", None)

    upstream_down = False

    def _read_upstream(timeout: float) -> Optional[Tuple[PyTree, List[int]]]:
        """Latest root snapshot (+ per-shard versions). Cached reads make
        an unchanged poll a header-sized round trip."""
        if not sharded:
            params, v = conns[0].read_params(timeout=timeout)
            return params, [int(v)]
        flat = np.empty(flat_n, np.float32)
        vs = []
        for (start, stop), c in zip(plan, conns):
            sl, v = c.read_params(timeout=timeout)
            flat[start:stop] = sl["flat"]
            vs.append(int(v))
        return _unflatten(flat, params0), vs

    def _republish(timeout: float = 2.0) -> None:
        nonlocal upstream_down
        try:
            got = _read_upstream(timeout)
        except TimeoutError:
            # upstream slow/stalled, not provably dead: skip this poll
            # (a blocked read here must never wedge the idle-exit path)
            return
        except (RuntimeError, OSError):
            # the upstream PS closed (job done, server gone): not this
            # leader's crash — drain out and exit cleanly below
            upstream_down = True
            return
        if got is None:
            return
        params, vs = got
        if v_map and v_map[max(v_map)] == vs:
            return  # upstream unchanged — nothing to republish
        server.publish(params)
        v_map[server.version] = vs
        while len(v_map) > 64:
            v_map.pop(min(v_map))

    def _map_versions(v_local: int) -> List[int]:
        if v_local in v_map:
            return v_map[v_local]
        return v_map[max(v_map)] if v_map else [0] * len(conns)

    def _consume(item, meta) -> None:
        nonlocal last_activity
        wid, v_local, payload = item
        if not gwire.payload_finite(payload):
            server._reject_frame(wid, "nonfinite")
            return
        pending[wid].append((np.copy(payload), dict(meta or {}),
                             _map_versions(int(v_local))))
        dead.discard(wid)
        last_activity = time.monotonic()

    def _pump_ingest() -> int:
        """Drain queued group pushes (batched when the native fast path
        is armed); returns the number of frames consumed. Each item's
        trace-ID meta is taken from the ALIGNED batch-meta list — the
        per-item ``last_push_meta`` would be overwritten inside one
        batch and silently drop trace IDs from the hop's composition."""
        if batch_poll is not None:
            batch = batch_poll(raw=True)
            if batch is not None:
                metas = getattr(server, "last_batch_metas", None) or []
                for it, meta in zip(batch, metas):
                    # raw views alias the batch buffer — copied (in
                    # _consume) before the next batched pop
                    _consume(it, meta)
                return len(batch)
        item = server.poll_grad(raw=True)
        if item is None:
            return 0
        _consume(item, server.last_push_meta)
        return 1

    def _mark_dead() -> None:
        silent = (None if can_connect
                  else server.stragglers(float(kw["degrade_after"])))
        for w in group:
            if w in dead or pending[w] or w not in server.last_seen:
                continue
            alive = (server.connected(w) if can_connect
                     else (w not in silent))
            if not alive:
                dead.add(w)

    def _hop_push(active: List[int]) -> None:
        """Fold one queued payload per listed worker, EF re-encode, push
        ONE frame upstream (per shard path), log the hop row."""
        nonlocal rounds, up_seq, round_t0, slow_fold_s
        if inj is not None and slow_fold_s == 0.0:
            # fires once (one deterministic event row); the delay then
            # persists — a sustained hotspot, not a one-round blip
            for f in inj.faults_between(-1, rounds):
                if f["kind"] == "slow_leader":
                    inj.fire(f)
                    slow_fold_s = float(f.get("slow_ms", 20.0)) / 1e3
        t_fold0 = time.monotonic()
        agg = gwire.agg_begin()
        entries: List[Dict[str, Any]] = []
        root_vs: List[List[int]] = []
        for w in active:
            payload, meta, vs = pending[w].popleft()
            agg.fold(payload)
            if slow_fold_s:
                # inside the fold window by design: the slowdown lands
                # in fold_s -> the anatomy advisor's leader_fold stage
                time.sleep(slow_fold_s)
            entries.append({"worker": int(meta.get("worker", w)),
                            "step": int(meta.get("step", 0)),
                            "seq": int(meta.get("seq", 0)),
                            "send_wall": float(meta.get("send_wall", 0.0))})
            root_vs.append(vs)
        t_fin0 = time.monotonic()
        summed = agg.finalize()
        t_fin1 = time.monotonic()
        fin_s = t_fin1 - t_fin0
        # fold_s keeps its historical meaning (fold loop + finalize) —
        # the hop row below and the offline round anatomy join on it;
        # the hop-anatomy row splits finalize into its own sub-stage
        fold_s = t_fin1 - t_fold0
        # conservative per-shard version tag: the OLDEST snapshot any
        # folded gradient was computed against — staleness is never
        # under-reported upstream
        v_up = [min(vs[i] for vs in root_vs) for i in range(len(conns))]
        t_enc0 = time.monotonic()
        if sharded:
            flat = _flatten(summed)
            payloads = [
                hop.encode({"flat": flat[start:stop]})
                for hop, (start, stop) in zip(hops, plan)
            ]
        else:
            payloads = [hops[0].encode(summed)]
        enc_s = time.monotonic() - t_enc0
        t_push0 = time.monotonic()
        nonlocal upstream_down
        pushed_shards = 0
        try:
            for c, p, v in zip(conns, payloads, v_up):
                c.push_payload(p, v,
                               timeout=float(cfg.get("push_timeout", 60.0)),
                               lineage=(rounds, up_seq), composed=entries)
                pushed_shards += 1
        except (TimeoutError, RuntimeError, OSError):
            upstream_down = True
            if pushed_shards == 0:
                # nothing reached any shard: the round's pushes are
                # positively lost — log them and drain out
                for e in entries:
                    log.row({"kind": "leader_consume", "lost": True,
                             "reason": "upstream_lost", **e})
            else:
                # PARTIAL shard coverage: earlier shards already
                # composed these entries, so a "lost" row here would
                # double-count them — record the partial round as its
                # own kind instead
                log.row({"kind": "hop_partial", "leader": int(group_id),
                         "round": rounds, "up_seq": up_seq,
                         "pushed_shards": pushed_shards,
                         "n_shards": len(conns), "composed": entries,
                         "t": time.time()})
            log.flush()
            return
        push_s = time.monotonic() - t_push0
        state["upstream_pushes"] += len(conns)
        state["composed"] += len(entries)
        if len(active) < len([w for w in group if w not in dead]) or dead:
            state["partial_rounds"] += 1
        log.row({
            "kind": "hop", "leader": int(group_id), "round": rounds,
            # the upstream-facing worker id this hop pushes as — the
            # root's composed push meta carries it, so offline round
            # anatomy can join hop rows to root rounds by EITHER the
            # wid or the composed trace IDs
            "leader_wid": int(lid),
            "up_seq": up_seq, "t": time.time(),
            "composed": entries, "versions": v_up,
            "fold_s": round(fold_s, 6), "encode_s": round(enc_s, 6),
            "push_s": round(push_s, 6),
            **hops[0].probe(),
        })
        log.flush()
        if hop_an is not None:
            # the hop-anatomy round: drain the native rings (owned by
            # THIS thread — the same one that pumps the transport and
            # runs the folds), attribute the round window to sub-stages
            # and feed the streaming-headroom scoreboard. The window
            # opens at the previous round's push end (round_t0).
            t_done = time.monotonic()
            validate_s = 0.0
            ring_drops = 0
            if hop_stamps_on:
                got = server.drain_hop_stamps()
                if got is not None:
                    stamps, lost = got
                    validate_s = sum(s[1] for s in stamps) / 1e9
                    ring_drops += int(lost)
            fold_calls = 0
            fold_busy_s = 0.0
            if hop_spans_on:
                got = wc_native.fold_spans_drain()
                if got is not None:
                    spans, lost = got
                    fold_calls = len(spans)
                    fold_busy_s = sum(e - s for s, e, _ in spans) / 1e9
                    ring_drops += int(lost)
            hop_an.observe_round(
                leader=int(group_id), round=rounds,
                frames=len(entries),
                stages={
                    "ingest_wait": max(
                        t_fold0 - round_t0 - validate_s, 0.0),
                    "validate": validate_s,
                    "fold": max(fold_s - fin_s, 0.0),
                    "finalize": fin_s,
                    "encode": enc_s,
                    "upstream_push": push_s,
                },
                round_s=max(t_done - round_t0, 0.0),
                drops=ring_drops,
                native=bool(hop_stamps_on or hop_spans_on),
                fold_calls=fold_calls, fold_busy_s=fold_busy_s)
            hop_an.flush()  # the root's tailer reads rows live
        rounds += 1
        up_seq += 1
        round_t0 = time.monotonic()

    try:
        # the first read blocks until the root's first publish (workers
        # wait on this leader's first downstream snapshot)
        _republish(timeout=float(cfg.get("open_timeout", 60.0)))
        while time.monotonic() < deadline:
            now = time.monotonic()
            if now >= next_tick:
                next_tick = now + float(cfg.get("tick_interval", 0.2))
                if server.timeseries_db is not None:
                    server.observability_tick()
                if topo_dir is not None:
                    # structural control: the SAME document the moved
                    # leaves repoint from tells this leader they left —
                    # without it every post-split round would stall a
                    # full degrade_after window waiting on a member
                    # that now pushes elsewhere
                    from pytorch_ps_mpi_tpu.control.topo import poll_topo

                    tdoc = poll_topo(topo_dir, topo_state)
                    if tdoc is not None:
                        for w_s, a in (tdoc.get("assign") or {}).items():
                            try:
                                wi = int(w_s)
                            except (TypeError, ValueError):
                                continue
                            if wi not in group:
                                continue
                            if a == addr:
                                departed.discard(wi)  # merged back
                            else:
                                departed.add(wi)
            if now >= next_read:
                next_read = now + float(kw["read_poll_s"])
                _republish()
            if upstream_down:
                # upstream gone: anything still queued is positively
                # lost (logged), then exit cleanly — the supervisor
                # owns the decision to restart the tree
                for w in group:
                    for _, meta, _ in pending[w]:
                        log.row({"kind": "leader_consume", "lost": True,
                                 "reason": "upstream_lost",
                                 "worker": int(meta.get("worker", w)),
                                 "step": int(meta.get("step", 0)),
                                 "seq": int(meta.get("seq", 0))})
                log.row({"kind": "upstream_lost", "t": time.time()})
                break
            if _pump_ingest():
                continue
            # round bookkeeping: deterministic crash hook first — it
            # fires "mid-fold": pushes are consumed (acked, queued) but
            # the round has not gone upstream, so they are positively
            # LOST and logged as such (the accounting smoke's case)
            if (crash_at is not None and rounds >= int(crash_at)
                    and any(pending[w] for w in group)):
                for w in group:
                    for payload, meta, _ in pending[w]:
                        log.row({"kind": "leader_consume", "lost": True,
                                 "worker": int(meta.get("worker", w)),
                                 "step": int(meta.get("step", 0)),
                                 "seq": int(meta.get("seq", 0))})
                log.close()
                os._exit(77)  # resilience.faults.CRASH_EXIT_CODE
            # a departed (reassigned-away) member stops gating rounds
            # the moment the topo document says so, but anything it
            # already pushed here still folds — one payload per round,
            # exactly like a live member, until its queue drains
            active = [w for w in group if w not in dead
                      and (w not in departed or pending[w])]
            if active and all(pending[w] for w in active):
                _hop_push(active)
                continue
            waited = time.monotonic() - round_t0
            queued = [w for w in group if pending[w]]
            if queued and waited > float(kw["degrade_after"]):
                _mark_dead()
                active = [w for w in group if w not in dead
                          and (w not in departed or pending[w])]
                if active and all(pending[w] for w in active):
                    _hop_push(active)
                    continue
                if waited > float(kw["flush_after"]):
                    # partial fold: liveness beats completeness — the
                    # composed trailer keeps the weighting exact anyway
                    _hop_push(queued)
                    continue
            if not queued:
                round_t0 = time.monotonic()  # no round in progress
                # idle-exit: every member that ever connected is gone
                # again. Members NEVER seen don't count as gone — they
                # may still be paying the minutes-long jax-import
                # startup skew, and a clean (rc 0) exit here would
                # never be respawned, stranding them at connect — so a
                # partially-seen group holds the leader open until the
                # startup grace expires.
                up = time.monotonic() - t_start
                seen = [w for w in group if w in server.last_seen]
                if can_connect:
                    gone = bool(seen) and all(
                        not server.connected(w) for w in seen)
                else:
                    # shm has no death signal: silence is the only one
                    silent = server.stragglers(float(kw["idle_exit_s"]))
                    gone = bool(seen) and all(w in silent for w in seen)
                all_arrived = len(seen) == len(group)
                if (seen and gone
                        and (all_arrived
                             or up > float(kw["startup_grace"]))
                        and (time.monotonic() - last_activity
                             > float(kw["idle_exit_s"]))):
                    break
                if not seen and up > float(kw["startup_grace"]):
                    break
            time.sleep(0.0005)
    finally:
        log.close()
        for c in conns:
            c.close()
        server.close()
    return int(state["upstream_pushes"])


# ---------------------------------------------------------------------------
# the worker-side tree connection (leader primary, root fallback)
# ---------------------------------------------------------------------------

class TreeWorkerConn:
    """A worker's transport in a tree job: push to the group leader;
    when the leader dies, fall back to pushing DIRECTLY to the root
    (compressed with the upstream codec, composing itself in the
    lineage trailer) and periodically probe the leader's pinned address
    to rejoin. Presents the worker surface ``worker_main`` expects
    (``read_params`` / ``push_grad`` / ``wire`` / ``close`` plus
    ``retries``/``reconnects`` counters)."""

    _TRANSPORT_ERRORS = (TimeoutError, RuntimeError, OSError)

    def __init__(self, worker_id: int, template: PyTree,
                 cfg: Dict[str, Any]):
        self.worker_id = int(worker_id)
        self.template = template
        self.cfg = cfg
        self.kw = _leader_knobs(cfg)
        self.leader_addr = cfg["tree_leader"]
        # fallback is single-root only: a sharded tree's recovery path
        # is the leader respawn (a leaf cannot slice its own pushes)
        self.root_addr = cfg.get("tree_fallback")
        self.slots = int(cfg.get("tree_slots", 1) or 1)
        self.retries = 0
        self.reconnects = 0
        self.fallback_pushes = 0
        self._mode = "leader"
        self._leader = None
        self._root = None
        self._pushes_since_fallback = 0
        self._tamper = None
        self._connect_leader(
            timeout=float(cfg.get("open_timeout", 60.0)), initial=True)

    # -- plumbing ---------------------------------------------------------
    @property
    def wire(self):
        w = self._leader if self._mode == "leader" else self._root
        return getattr(w, "wire", None)

    def set_tamper(self, fn) -> None:
        self._tamper = fn
        w = self._leader if self._mode == "leader" else self._root
        if w is not None:
            w._tamper = fn

    def renegotiate(self, code, bucket_mb: float = 0.0) -> bool:
        """Decline controller wire renegotiation: a tree leaf's group
        codec (and the root's trailer-bearing upstream wire) is the
        tree topology's own agreement — the leader re-encodes the hop,
        so swapping the leaf wire unilaterally would split the group's
        fold. The leaf keeps its epoch; the root consumes it until the
        old epoch retires (the controller disables the codec rule in
        tree mode for exactly this reason)."""
        return False

    def repoint(self, addr: str) -> bool:
        """Structural re-parent (controller group split/merge): switch
        this leaf's leader to ``addr`` — the control-topo.json poll's
        actuation.  Idempotent when already attached there.  On connect
        failure it takes the STANDARD failover path (root fallback /
        pinned-address retry) instead of returning with a half-open
        state: ``AttributeError`` on a ``None`` leader is not in
        ``_TRANSPORT_ERRORS``, so leaving ``_mode == "leader"`` with no
        connection would crash the next read.  The rejoin probe — now
        aimed at the NEW pinned address — retries from fallback."""
        addr = str(addr)
        if addr == self.leader_addr and self._mode == "leader" \
                and self._leader is not None:
            return True
        self.leader_addr = addr
        old, self._leader = self._leader, None
        if old is not None:
            try:
                old.close()
            except Exception:
                pass
        if self._connect_leader(timeout=float(self.kw["probe_timeout"])):
            self.reconnects += 1
            return True
        self._failover()
        return False

    def _connect_leader(self, timeout: float, initial: bool = False) -> bool:
        from pytorch_ps_mpi_tpu.parallel.dcn import ShmPSWorker
        from pytorch_ps_mpi_tpu.parallel.tcp import TcpPSWorker

        try:
            if self.leader_addr.startswith("shm:"):
                w = ShmPSWorker(self.leader_addr[4:], self.worker_id,
                                self.template, timeout=timeout,
                                code=_group_codec(self.kw),
                                seed=int(self.cfg.get("seed", 0)),
                                frame=True)
            else:
                host, port = self.leader_addr.rsplit(":", 1)
                w = TcpPSWorker(host, int(port), self.worker_id,
                                self.template, timeout=timeout,
                                code=_group_codec(self.kw),
                                seed=int(self.cfg.get("seed", 0)),
                                frame=True)
        except self._TRANSPORT_ERRORS:
            if initial:
                if self.root_addr is None:
                    raise
                # leader not up (crashed before this worker started):
                # begin life on the fallback path; the periodic probe
                # rejoins the leader once the supervisor respawns it
                self.reconnects += 1
                self._mode = "root"
                self._connect_root()
            return False
        if self._leader is not None:
            try:
                self._leader.close()
            except Exception:
                pass
        self._leader = w
        self._leader._tamper = self._tamper
        self._mode = "leader"
        self._pushes_since_fallback = 0
        if self._root is not None:
            # drop the fallback socket on rejoin: an open root
            # connection would keep this worker in the root barrier's
            # membership forever (TCP liveness is positive there)
            try:
                self._root.close()
            except Exception:
                pass
            self._root = None
        return True

    def _connect_root(self):
        from pytorch_ps_mpi_tpu.parallel.tcp import TcpPSWorker

        if self.root_addr is None:
            raise RuntimeError(
                "group leader unreachable and no tree_fallback root is "
                "configured (sharded tree) — waiting on leader respawn")
        if self._root is None:
            host, port = self.root_addr.rsplit(":", 1)
            self._root = TcpPSWorker(
                host, int(port), self.worker_id, self.template,
                code=_upstream_codec(self.cfg),
                timeout=float(self.cfg.get("open_timeout", 60.0)),
                bucket_mb=float(self.cfg.get("bucket_mb", 0.0)),
                frame=True, tree_slots=self.slots,
                seed=int(self.cfg.get("seed", 0)))
            self._root._tamper = self._tamper
        return self._root

    def _failover(self) -> None:
        """Leader unreachable: route around it (single root) or block-
        retry the pinned leader address until its respawn (sharded tree
        — a leaf cannot slice its own pushes across shards)."""
        self.reconnects += 1
        self._pushes_since_fallback = 0
        if self._leader is not None:
            try:
                self._leader.close()
            except Exception:
                pass
            self._leader = None
        if self.root_addr is None:
            deadline = time.time() + float(self.cfg.get("open_timeout",
                                                        60.0))
            while time.time() < deadline:
                if self._connect_leader(
                        timeout=float(self.kw["probe_timeout"])):
                    return
                time.sleep(0.5)
            raise TimeoutError(
                "group leader unreachable, no tree_fallback configured, "
                "and the leader never came back within open_timeout")
        self._mode = "root"
        self._connect_root()

    # -- worker surface ---------------------------------------------------
    def read_params(self, timeout: float = 30.0) -> Tuple[PyTree, int]:
        if self._mode == "leader":
            try:
                return self._leader.read_params(timeout=timeout)
            except self._TRANSPORT_ERRORS:
                self.retries += 1
                self._failover()
            if self._mode == "leader":  # reconnected (leader respawn)
                return self._leader.read_params(timeout=timeout)
        return self._connect_root().read_params(timeout=timeout)

    def push_grad(self, grad: PyTree, version: int, timeout: float = 30.0,
                  lineage: Optional[Tuple[int, int]] = None) -> None:
        if self._mode == "root":
            self._pushes_since_fallback += 1
            if self._pushes_since_fallback >= int(self.kw["rejoin_every"]):
                # probe the (possibly respawned) leader on its pinned
                # address; on success the group rejoins the tree
                if self._connect_leader(
                        timeout=float(self.kw["probe_timeout"])):
                    # version domains differ (leader-local counter):
                    # re-read so this push is tagged in the new domain
                    try:
                        _, version = self._leader.read_params(
                            timeout=timeout)
                    except self._TRANSPORT_ERRORS:
                        self._failover()
                else:
                    self._pushes_since_fallback = 0
        if self._mode == "leader":
            try:
                self._leader.push_grad(grad, version, timeout=timeout,
                                       lineage=lineage)
                return
            except self._TRANSPORT_ERRORS:
                self.retries += 1
                self._failover()
            if self._mode == "leader":  # reconnected (leader respawn)
                self._leader.push_grad(grad, version, timeout=timeout,
                                       lineage=lineage)
                return
        # direct-to-root: re-read for a root-domain version tag (the
        # leader-local tag would be nonsense staleness), then push with
        # the worker's own trace ID composing itself in the trailer
        root = self._connect_root()
        try:
            _, v_root = root.read_params(timeout=timeout)
        except self._TRANSPORT_ERRORS:
            self.retries += 1
            v_root = int(version)
        root.push_grad(grad, v_root, timeout=timeout, lineage=lineage)
        self.fallback_pushes += 1
        self._pushes_since_fallback += 1

    def close(self) -> None:
        for w in (self._leader, self._root):
            if w is not None:
                try:
                    w.close()
                except Exception:
                    pass
        self._leader = self._root = None


# ---------------------------------------------------------------------------
# orchestration
# ---------------------------------------------------------------------------

def spawn_leader(upstream: Sequence[str], group_id: int,
                 group: Sequence[int], cfg: Dict[str, Any], port: int = 0,
                 env: Optional[Dict[str, str]] = None):
    """Launch ``leader_main`` in a fresh OS process (a leader is a host
    process: pinned to the CPU backend, it never takes a chip from a
    worker); the child prints a one-line hello with its group-facing
    address."""
    src = (
        "import json,sys\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from pytorch_ps_mpi_tpu.parallel.tree import leader_main\n"
        "up, gid, grp, cfg, port = (json.loads(sys.argv[1]),\n"
        "    int(sys.argv[2]), json.loads(sys.argv[3]),\n"
        "    json.loads(sys.argv[4]), int(sys.argv[5]))\n"
        "sys.exit(0 if leader_main(up, gid, grp, cfg, port) >= 0 else 1)\n"
    )
    e = dict(os.environ)
    e.update({"JAX_PLATFORMS": "cpu"})
    e.update(env or {})
    return subprocess.Popen(
        [sys.executable, "-c", src, json.dumps(list(upstream)),
         str(group_id), json.dumps([int(w) for w in group]),
         json.dumps(cfg), str(port)],
        env=e, stdout=subprocess.PIPE, text=True,
    )


def read_leader_hello(proc, timeout: float = 120.0) -> Dict[str, Any]:
    """Block until a spawned leader prints its hello line."""
    import select

    deadline = time.time() + timeout
    while time.time() < deadline:
        r, _, _ = select.select([proc.stdout], [], [], 0.5)
        if r:
            line = proc.stdout.readline()
            if line:
                return json.loads(line)
        if proc.poll() is not None:
            raise RuntimeError(f"leader exited early: {proc.returncode}")
    raise TimeoutError("leader never reported its address")


def run_tree(cfg: Dict[str, Any], *, total_pushes: Optional[int] = None,
             timeout: float = 300.0,
             worker_env: Optional[Dict[str, str]] = None,
             leader_env: Optional[Dict[str, str]] = None
             ) -> Tuple[PyTree, Dict[str, Any]]:
    """Spawn and drive a full aggregation tree: root PS (in-process
    ``serve()``), one leader per group, one worker process per worker.
    Returns the root's ``(params, metrics)`` with tree bookkeeping
    (leader respawns, per-leader exit codes, worker codes) merged in.

    The root's stop condition is composed-accounting based: with
    ``total_pushes`` (default: the fleet's total step count) the serve
    loop drains until every worker push is accounted — composed at the
    root or positively lost with a crashed leader — or the fleet exits.
    """
    from pytorch_ps_mpi_tpu.parallel.async_train import (
        join_workers,
        make_problem,
        serve,
        spawn_worker,
    )
    from pytorch_ps_mpi_tpu.parallel.tcp import TcpPSServer

    cfg = dict(cfg)
    n_workers = int(cfg["n_workers"])
    group_size = int(cfg.get("group_size", 4))
    kw = _leader_knobs(cfg)
    groups = group_plan(n_workers, group_size)
    slots = tree_slot_capacity(n_workers, group_size)
    lids = [leader_wid(n_workers, g) for g in range(len(groups))]
    cfg.update(tree=True, tree_slots=slots, tree_members=lids)

    code = _upstream_codec(cfg)
    if code is None:
        raise ValueError("run_tree needs cfg['codec'] (the compressed "
                         "DCN hop); use 'identity' to ship raw bytes")
    _, params0, _, _ = make_problem(cfg)
    # structural control needs spare wid headroom: each group replan
    # promotes one NEW leader wid, up to replan_max concurrent splits
    spare_wids = (int((cfg.get("control_kw") or {}).get("replan_max", 1))
                  if cfg.get("topo_actions") else 0)
    root = TcpPSServer(0, num_workers=n_workers + len(groups) + spare_wids,
                       template=params0,
                       max_staleness=int(cfg.get("max_staleness", 4)),
                       code=code, bucket_mb=float(cfg.get("bucket_mb", 0.0)),
                       frame=True, tree_slots=slots)
    root_addr = f"127.0.0.1:{root.port}"
    cfg["tree_fallback"] = root_addr

    leaders: List[Any] = []
    leader_ports: List[int] = []
    leader_addrs: List[str] = []
    respawns = [0] * len(groups)
    workers: List[Any] = []
    try:
        for g, grp in enumerate(groups):
            p = spawn_leader([root_addr], g, grp, cfg, env=leader_env)
            hello = read_leader_hello(p)
            leaders.append(p)
            leader_addrs.append(hello["addr"])
            leader_ports.append(
                0 if hello["addr"].startswith("shm:")
                else int(hello["addr"].rsplit(":", 1)[1]))
        for g, grp in enumerate(groups):
            for w in grp:
                wcfg = dict(cfg)
                wcfg["tree_leader"] = leader_addrs[g]
                workers.append(spawn_worker(root_addr, w, wcfg,
                                            env=worker_env))

        # structural control (cfg["topo_actions"]): the actuator owns
        # group split/merge through THESE supervision lists, so a
        # promoted leader is pinned-port respawned like a boot one;
        # the hop tailer feeds the leaders' lineage rows to the live
        # anatomy advisor (the engine's hot_group input)
        actuator = None
        tailer = None
        hop_tailer = None
        # hop anatomy at the root: the leaders WRITE hop-leaderN.jsonl;
        # this tailer replays their rows into the root's own HopAnatomy
        # (armed by serve()'s arm_observability) — the fleet scoreboard
        # the /health hop section, ps_top and the topo controller read
        if cfg.get("hop_anatomy"):
            from pytorch_ps_mpi_tpu.control.topo import HopTailer

            hop_dir = cfg.get("lineage_dir") or cfg.get("telemetry_dir")
            if hop_dir:
                hop_tailer = HopTailer(
                    hop_dir,
                    lambda row: (root.hop_anatomy.observe_row(row)
                                 if getattr(root, "hop_anatomy", None)
                                 is not None else None),
                    pattern="hop-*.jsonl")
        if cfg.get("topo_actions"):
            from pytorch_ps_mpi_tpu.control.topo import (
                HopTailer,
                TreeTopoActuator,
            )

            actuator = TreeTopoActuator(
                cfg=cfg, groups=groups, leaders=leaders,
                leader_ports=leader_ports, leader_addrs=leader_addrs,
                respawns=respawns, root_addr=root_addr,
                leader_env=leader_env)
            root.topo_actuator = actuator
            hop_dir = cfg.get("lineage_dir") or cfg.get("telemetry_dir")
            if hop_dir:
                tailer = HopTailer(
                    hop_dir,
                    lambda row: (root.anatomy.observe_hop(row)
                                 if getattr(root, "anatomy", None)
                                 is not None else None))
            root.topo_state = {
                "groups": len(groups), "leader_respawns": 0,
                "hot_churn_group": -1,
            }

        def on_tick():
            # leader supervision: a crashed leader is respawned on its
            # PINNED port so fallen-back workers can rejoin it. The
            # hello is NOT awaited — this runs on the serve thread, and
            # the pinned port makes the address already known.
            for g, p in enumerate(leaders):
                rc = p.poll()
                if rc is not None and rc != 0 and (
                        respawns[g] < int(kw["max_respawns"])):
                    respawns[g] += 1
                    # injected crash hooks are one-shot: the respawned
                    # generation must come back healthy (same rule as
                    # the chaos supervisor's crash-fault marking)
                    rcfg = dict(cfg)
                    lkw = dict(rcfg.get("leader_kw") or {})
                    lkw.pop("crash_at_round", None)
                    rcfg["leader_kw"] = lkw
                    leaders[g] = spawn_leader(
                        [root_addr], g, groups[g], rcfg,
                        port=leader_ports[g], env=leader_env)
            if actuator is not None:
                actuator.pump()  # non-blocking: reap split-leader hello
                root.topo_state = {
                    "groups": actuator.active_groups,
                    "leader_respawns": max(respawns) if respawns else 0,
                    "hot_churn_group": (
                        max(range(len(respawns)), key=respawns.__getitem__)
                        if respawns and max(respawns) > 0 else -1),
                }
            if tailer is not None:
                tailer.poll()
            if hop_tailer is not None:
                hop_tailer.poll()

        def stop_when():
            if total_pushes is not None and root.tree_composed >= total_pushes:
                return True
            return (all(p.poll() is not None for p in workers)
                    and all(p.poll() is not None for p in leaders))

        params, m = serve(
            root, cfg, total_grads=10 ** 9, timeout=timeout,
            sync_barrier=not cfg.get("tree_async", False),
            on_tick=on_tick, stop_when=stop_when,
        )
        worker_codes = join_workers(workers, timeout=60.0)
        leader_codes = join_workers(leaders, timeout=60.0)
        m["tree"] = {
            "groups": [list(g) for g in groups],
            "leader_wids": [leader_wid(n_workers, g)
                            for g in range(len(groups))],
            "tree_slots": slots,
            "leader_respawns": sum(respawns),
            "leader_codes": leader_codes,
            "worker_codes": worker_codes,
        }
        if actuator is not None:
            m["tree"]["topo_events"] = list(actuator.events)
        return params, m
    finally:
        for p in workers + leaders:
            if p.poll() is None:
                p.terminate()
        root.close()
