"""Sharded parameter servers: the PS scaling axis, across processes/hosts.

The reference's topology is one rank-0 server owning every parameter
(reference ``ps.py:103-193`` — the centralized PS its ``igather``/
``ibcast`` implement); that single server is the bandwidth and update-rate
bottleneck as workers scale. The classic fix (Li et al., OSDI'14,
"Scaling Distributed Machine Learning with the Parameter Server") is to
PARTITION the parameter vector across S server shards: each server owns a
contiguous slice, applies updates for its slice only, and workers
read/push per-slice. This module is that topology over the cross-host TCP
transport (``parallel/tcp.py``), composing with everything the
single-server async path already has — jitted worker compute, codec-
compressed payload bytes, per-shard bounded staleness, ack back-pressure.

In-XLA, the same idea is the ZeRO-1 ``mode='leader'`` lowering in
``ps.py:94-166`` (optimizer state partitioned 1/world per device); here it
is the host-process/DCN instantiation: S OS processes (one per host in
deployment), each a full :class:`~pytorch_ps_mpi_tpu.parallel.tcp.TcpPSServer`
for its slice. Asynchrony is genuinely per-shard — each shard advances its
own version counter at its own pace, so a worker's snapshot is a vector of
per-shard versions (the "inconsistent read" of AsySG-InCon, now also
inconsistent ACROSS shards), and staleness is measured and bounded
shard-locally.

Everything is flat-f32-slice based: optimizer update rules (SGD/momentum,
Adam) are elementwise, so updating each slice independently is EXACTLY the
single-server update — sharding changes where state lives, never the math
(tested: 1-shard and 2-shard runs from the same seed agree when run
synchronously).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from pytorch_ps_mpi_tpu.parallel.dcn import _flat_size, _flatten, _unflatten

PyTree = Any


def shard_plan(n_total: int, n_shards: int) -> List[Tuple[int, int]]:
    """Balanced contiguous [start, stop) slices of a length-``n_total``
    flat vector; earlier shards get the remainder (sizes differ by ≤1)."""
    if not 1 <= n_shards <= n_total:
        raise ValueError(f"need 1 <= n_shards <= {n_total}, got {n_shards}")
    base, rem = divmod(n_total, n_shards)
    plan, start = [], 0
    for s in range(n_shards):
        stop = start + base + (1 if s < rem else 0)
        plan.append((start, stop))
        start = stop
    return plan


def planned_shards(control_dir: Optional[str], default: int) -> int:
    """The shard count the NEXT server generation should boot with:
    the structural controller's shard split/merge verdict is recorded
    as a PLAN in ``control-topo.json`` (never applied to a live
    generation — a shard move rehashes the whole key space), and every
    sharded driver consults this at spawn time.  Falls back to
    ``default`` (the cfg value) when no plan exists."""
    from pytorch_ps_mpi_tpu.control.topo import planned_shards as _planned

    return _planned(control_dir, default)


def _slice_template(n: int) -> PyTree:
    return {"flat": np.zeros((n,), np.float32)}


def server_main(shard_id: int, n_shards: int, port: int,
                cfg: Dict[str, Any], out_path: str) -> None:
    """One shard-server process body: own slice ``shard_id`` of the flat
    parameter vector, apply jitted elementwise optimizer updates in
    arrival order with shard-local bounded staleness, and on completion
    write the final slice + metrics to ``out_path`` (.npz).

    Stops after consuming ``expected`` pushes (applied + stale-dropped):
    every worker pushes once per step per shard, so the count is exact.
    ``cfg["server_slow_ms"][str(shard_id)]`` injects a per-update sleep —
    a deliberately slow SHARD for tests to force per-shard version
    divergence (the asynchrony axis single-server PS doesn't have).

    Failure story matches the single-server loop: with
    ``cfg["checkpoint_dir"]`` set, each shard snapshots ITS OWN slice +
    optimizer state under ``<dir>/shard<i>`` every
    ``cfg["checkpoint_every"]`` applied updates; ``cfg["resume"]``
    restores it with the same crash-window version jump — shards recover
    INDEPENDENTLY (a replacement for shard 1 does not touch shard 0,
    the horizontal-recovery property Li et al.'s design calls out).
    """
    import jax

    from pytorch_ps_mpi_tpu.optim import OPTIMIZERS
    from pytorch_ps_mpi_tpu.parallel.async_train import make_problem
    from pytorch_ps_mpi_tpu.parallel.tcp import TcpPSServer
    from pytorch_ps_mpi_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()

    code = None
    if cfg.get("codec"):
        from pytorch_ps_mpi_tpu.codecs import get_codec

        code = get_codec(cfg["codec"], **cfg.get("codec_kw", {}))

    _, params0, _, _ = make_problem(cfg)
    flat0 = _flatten(params0)
    start, stop = shard_plan(flat0.size, n_shards)[shard_id]
    template = _slice_template(stop - start)
    params = {"flat": flat0[start:stop].copy()}

    hyper_cls, init_state, update_fn = OPTIMIZERS[cfg.get("optim", "sgd")]
    h = hyper_cls(**cfg.get("hyper", {"lr": 0.05}))
    state = init_state(params)
    update = jax.jit(lambda p, g, s: update_fn(p, g, s, h))

    from pytorch_ps_mpi_tpu.parallel.async_train import worker_cfg

    n_workers = int(cfg["n_workers"])
    expected = sum(worker_cfg(cfg, w)[1] for w in range(n_workers))
    slow_ms = 0.0
    if isinstance(cfg.get("server_slow_ms"), dict):
        slow_ms = float(cfg["server_slow_ms"].get(str(shard_id), 0.0))

    # hierarchical-tree composition (cfg["tree"], parallel.tree): the
    # shard's pushers are group LEADERS (ids past n_workers) shipping
    # composed group sums with lineage trailers — path-sharding stacks
    # on key-sharding. Stop/accounting switch from frames to the exact
    # composed worker-push count the trailers carry.
    tree_mode = bool(cfg.get("tree"))
    tree_slots = int(cfg.get("tree_slots", 0) or 0) if tree_mode else 0
    id_space = n_workers + len(cfg.get("tree_members") or ())
    server = TcpPSServer(port, num_workers=id_space, template=template,
                         max_staleness=int(cfg.get("max_staleness", 4)),
                         code=code, frame=bool(cfg.get("frame_check")),
                         tree_slots=tree_slots)

    # per-shard online diagnosis: each shard server gets its own
    # HealthMonitor and /metrics + /health endpoint (port auto-assigned
    # — S shards cannot share one pinned port; the bound port rides the
    # stdout handshake line below as "health_port")
    monitor = None
    health_port = None
    if (cfg.get("health") or cfg.get("health_dir")
            or cfg.get("health_port") is not None
            or cfg.get("metrics_port") is not None):
        from pytorch_ps_mpi_tpu.telemetry.diagnosis import HealthMonitor

        monitor = HealthMonitor(server, cfg)
        if (cfg.get("health_port") is not None
                or cfg.get("metrics_port") is not None):
            health_port = server.start_metrics_http(0)

    # per-shard gradient lineage: each shard tracks the trace IDs its
    # own framed pushes carry (staleness is shard-local, so lineage is
    # too) into lineage-shard<i>.jsonl — same arming rule as serve()
    tracker = None
    if ((cfg.get("lineage") or cfg.get("lineage_dir"))
            and cfg.get("frame_check")):
        from pytorch_ps_mpi_tpu.telemetry.lineage import LineageTracker

        tracker = LineageTracker(server, cfg, name=f"shard{shard_id}")
        if cfg.get("anatomy", "auto") not in (False, "off", 0):
            # per-shard round anatomy (same auto-with-lineage rule as
            # serve()): anatomy-shard<i>.jsonl rows + the anatomy_*
            # canonical keys on this shard's endpoint — a sharded
            # fleet's per-shard critical paths stay separable
            from pytorch_ps_mpi_tpu.telemetry.anatomy import RoundAnatomy

            tracker.anatomy = RoundAnatomy(server, cfg,
                                           name=f"shard{shard_id}")

    # per-shard read tier (the ServingCore extraction's point): each
    # shard serves ITS slice under a per-tenant namespace — no trainer
    # loop involved, readers hit the shard's own read port with tenant
    # "shard<i>" (the bound port rides the stdout handshake). monitors
    # stay the shard's own (built above), so monitors=False here.
    core = None
    if cfg.get("serving") or cfg.get("read_port") is not None:
        from pytorch_ps_mpi_tpu.serving import ServingCore

        # S shards on one host cannot share a pinned read port: each
        # shard auto-assigns and reports it in the handshake line
        scfg = dict(cfg)
        if cfg.get("read_port") is not None:
            scfg["read_port"] = 0
        core = ServingCore(server, scfg, monitors=False,
                           tenant=f"shard{shard_id}")

    # per-shard fleet observability plane: retained metrics history +
    # SLO watchdog + continuous profiler, and — with cfg["fleet_dir"] —
    # registration of THIS shard's endpoint under "shard<i>" so one
    # /fleet scrape covers the whole sharded fleet (a restarted shard
    # re-registers under the same name and rejoins the pane). Fleet
    # membership NEEDS a live endpoint: a fleet_dir with no explicit
    # metrics/health port still binds one (auto-assigned, in the hello)
    if (cfg.get("fleet_dir") or cfg.get("fleet")) and health_port is None:
        health_port = server.start_metrics_http(0)
    ocfg = dict(cfg)
    ocfg["fleet_role"] = "shard"
    ocfg.pop("fleet_name", None)
    server.arm_observability(ocfg, name=f"shard{shard_id}")

    # per-shard control plane: staleness LR scaling + read-tier tuning
    # on this shard's own verdicts (control-shard<i>.jsonl). The codec
    # rule is forced off — a shard cannot renegotiate the wire
    # unilaterally, every shard's fingerprint must move together with
    # the workers' (single-server runs own the epoch file).
    ctl = None
    if cfg.get("control") or cfg.get("control_kw") or cfg.get("control_dir"):
        from pytorch_ps_mpi_tpu.control import Controller

        ccfg = dict(cfg)
        ccfg["control_kw"] = {**(cfg.get("control_kw") or {}),
                              "ladder": None}
        ctl = Controller(server, ccfg, core=core,
                         name=f"shard{shard_id}")

    ckpt = None
    applied_before = 0
    checkpoint_every = int(cfg.get("checkpoint_every", 50))
    if cfg.get("resume") and not cfg.get("checkpoint_dir"):
        raise ValueError("cfg['resume'] requires cfg['checkpoint_dir']")
    if cfg.get("checkpoint_dir"):
        from pytorch_ps_mpi_tpu.parallel.async_train import (
            _restore_ps_checkpoint,
        )
        from pytorch_ps_mpi_tpu.utils.checkpoint import CheckpointManager

        ckpt = CheckpointManager(
            os.path.join(cfg["checkpoint_dir"], f"shard{shard_id}")
        )
        if cfg.get("resume"):
            params, state, applied_before, server.version = (
                _restore_ps_checkpoint(ckpt, params, state, checkpoint_every)
            )

    # the coordinator reads the auto-assigned port from this line
    hello = {"shard": shard_id, "port": server.port}
    if health_port is not None:
        hello["health_port"] = health_port
    if core is not None and core.read_port is not None:
        hello["read_port"] = core.read_port
    print(json.dumps(hello), flush=True)

    def _publish(p):
        if core is not None:
            core.publish(p)
        else:
            server.publish(p)

    try:
        _publish(params)
        applied = 0
        cadence = None
        if ckpt:
            from pytorch_ps_mpi_tpu.parallel.async_train import (
                _PSCheckpointCadence,
            )

            cadence = _PSCheckpointCadence(ckpt, checkpoint_every,
                                           applied_before)
        # Resume contract: a replacement server expects the FULL job push
        # count, because workers restart from step 0 alongside it (the
        # parameter snapshot carries the training progress; worker step
        # indices are only push bookkeeping — see
        # test_sharded_checkpoint_resume_continues_independently, where
        # phase-2 applied_total accumulates on top of applied_before).
        # Workers that instead survive a server crash and push only their
        # remaining steps exit via the bounded server_timeout, not a hang.
        deadline = time.time() + float(cfg.get("server_timeout", 300.0))
        next_tick = 0.0

        def _consumed() -> int:
            # tree mode counts composed worker pushes (the trailers'
            # exact accounting); star mode counts frames
            return (server.tree_composed if tree_mode
                    else server.grads_received)

        while _consumed() < expected and time.time() < deadline:
            now = time.monotonic()
            if now >= next_tick:
                next_tick = now + float(cfg.get("tick_interval", 0.2))
                if server.timeseries_db is not None:
                    # TSDB sample + SLO sweep, serve-thread only — the
                    # same tick discipline as the single-server loop
                    server.observability_tick()
                if ctl is not None:
                    ctl.tick()
            item = server.poll_grad()
            if item is None:
                time.sleep(0.0005)
                continue
            wid, ver, grad = item
            staleness = max(0, server.version - ver)
            if monitor is not None:
                monitor.observe_grad(wid, staleness)
            if ctl is not None:
                ctl.observe_push(wid, staleness)
            up_t0 = time.perf_counter()
            comp_n = 1
            if tree_slots:
                comp_n = (server._composed_queue.popleft()
                          if server._composed_queue else 1)
            wgt = ctl.push_weight(wid) if ctl is not None else 1.0
            if wgt != 1.0:
                # per-push staleness LR weight, shard-local (the
                # controller's lr_scale rule); comp_n folds in too
                grad = jax.tree.map(lambda x: x * wgt / comp_n, grad)
            elif comp_n > 1:
                # a leader frame carries its group's SUM — apply the
                # group mean (same rule as the tree root's loop)
                grad = jax.tree.map(lambda x: x / comp_n, grad)
            params, state = update(params, grad, state)
            applied += 1
            if slow_ms:
                time.sleep(slow_ms / 1e3)
            _publish(jax.tree.map(np.asarray, params))
            if tracker is not None:
                tracker.observe_publish(server.version,
                                        time.perf_counter() - up_t0)
            if cadence:
                cadence.maybe_save(params, state, server,
                                   applied_before + applied)
        if cadence:
            cadence.final_save(params, state, server,
                               applied_before + applied)
        m = server.metrics()
        np.savez(
            out_path,
            flat=np.asarray(params["flat"]),
            start=start,
            stop=stop,
            version=server.version,
            applied_total=applied_before + applied,
            grads_received=m["grads_received"],
            stale_drops=m["stale_drops"],
            compression_ratio=m["compression_ratio"],
            staleness_hist=json.dumps(
                {int(k): int(v) for k, v in server.staleness_seen.items()}
            ),
            health=(monitor.render_json() if monitor is not None else "{}"),
            lineage=json.dumps(tracker.snapshot()
                               if tracker is not None else {}),
            serving=json.dumps(core.serving_snapshot()
                               if core is not None else {}),
            slo=json.dumps(server.slo_watchdog.snapshot()
                           if server.slo_watchdog is not None else {}),
            control=json.dumps(ctl.snapshot()
                               if ctl is not None else {}),
        )
    finally:
        if ctl is not None:
            ctl.close()
        if tracker is not None:
            if tracker.anatomy is not None:
                tracker.anatomy.close()
            tracker.close()
        server.close()


def worker_main_sharded(addrs: Sequence[str], worker_id: int,
                        cfg: Dict[str, Any],
                        out_path: Optional[str] = None) -> int:
    """Worker process body against S shard servers: one jitted
    ``value_and_grad`` per step, then slice the flat gradient and push
    each slice to its shard tagged with THAT shard's snapshot version.
    Reads are per-shard (S request/reply round trips) and the versions
    they return may disagree — recorded and written to ``out_path`` so
    tests can assert cross-shard divergence actually happened."""
    import jax

    from pytorch_ps_mpi_tpu.parallel.async_train import make_problem
    from pytorch_ps_mpi_tpu.parallel.tcp import TcpPSWorker
    from pytorch_ps_mpi_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    code = None
    if cfg.get("codec"):
        from pytorch_ps_mpi_tpu.codecs import get_codec

        code = get_codec(cfg["codec"], **cfg.get("codec_kw", {}))

    _, params0, batch_fn, loss_fn = make_problem(cfg)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))  # ONLY grad source
    flat0 = _flatten(params0)
    plan = shard_plan(flat0.size, len(addrs))

    conns = []
    for (start, stop), addr in zip(plan, addrs):
        host, port = addr.rsplit(":", 1)
        tmpl = _slice_template(stop - start)

        def make_conn(host=host, port=int(port), tmpl=tmpl):
            return TcpPSWorker(
                host, port, worker_id, tmpl, code=code,
                timeout=float(cfg.get("open_timeout", 60.0)),
                frame=bool(cfg.get("frame_check")),
            )

        if cfg.get("resilient"):
            # per-shard resilience: each connection retries/reconnects
            # independently, so one shard's restart-from-checkpoint never
            # takes down pushes to the healthy shards
            from pytorch_ps_mpi_tpu.resilience.worker import ResilientWorker

            conns.append(ResilientWorker(
                make_conn, worker_id=worker_id,
                seed=int(cfg.get("fault_seed", cfg.get("seed", 0))),
                **cfg.get("resilience_kw", {})))
        else:
            conns.append(make_conn())

    from pytorch_ps_mpi_tpu.parallel.async_train import worker_cfg

    slow_ms, steps = worker_cfg(cfg, worker_id)

    pushed = 0
    max_version_spread = 0
    try:
        flat = np.empty_like(flat0)
        for step in range(steps):
            versions = []
            for (start, stop), w in zip(plan, conns):
                slice_params, ver = w.read_params(
                    timeout=float(cfg.get("open_timeout", 60.0)))
                flat[start:stop] = slice_params["flat"]
                versions.append(ver)
            max_version_spread = max(max_version_spread,
                                     max(versions) - min(versions))
            params = _unflatten(flat, params0)
            loss, grads = grad_fn(params, batch_fn(step, worker_id))
            jax.block_until_ready(grads)
            if slow_ms:
                time.sleep(slow_ms / 1e3)
            g_flat = _flatten(grads)
            for (start, stop), ver, w in zip(plan, versions, conns):
                # one push per shard per step: the step doubles as the
                # monotonic per-connection push seq in the trace ID
                w.push_grad({"flat": g_flat[start:stop]}, ver,
                            timeout=float(cfg.get("push_timeout", 60.0)),
                            lineage=(step, step))
            pushed += 1
    finally:
        for w in conns:
            w.close()
    if out_path:
        with open(out_path, "w") as f:
            json.dump({"pushed": pushed,
                       "max_version_spread": max_version_spread}, f)
    return pushed


def assemble(paths: Sequence[str], template: PyTree) -> PyTree:
    """Reassemble the full parameter tree from the shard .npz files the
    servers wrote (validates the slices tile the flat vector exactly)."""
    flat = np.empty(_flat_size(template), np.float32)
    covered = 0
    for p in paths:
        z = np.load(p, allow_pickle=False)
        start, stop = int(z["start"]), int(z["stop"])
        flat[start:stop] = z["flat"]
        covered += stop - start
    if covered != flat.size:
        raise ValueError(f"shards cover {covered} of {flat.size} elements")
    return _unflatten(flat, template)


def spawn_shard_server(shard_id: int, n_shards: int, cfg: Dict[str, Any],
                       out_path: str,
                       env: Optional[Dict[str, str]] = None):
    """Launch ``server_main`` in a fresh OS process (port auto-assigned;
    the child prints ``{"shard": i, "port": p}`` on stdout — use
    :func:`read_server_port`). A shard server is a host process: pinned
    to the CPU backend, it never takes a chip from a worker."""
    src = (
        "import json,sys\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from pytorch_ps_mpi_tpu.parallel.sharded import server_main\n"
        "sid, ns, cfg, out = (int(sys.argv[1]), int(sys.argv[2]),\n"
        "                     json.loads(sys.argv[3]), sys.argv[4])\n"
        "server_main(sid, ns, 0, cfg, out)\n"
    )
    e = dict(os.environ)
    e.update({"JAX_PLATFORMS": "cpu"})
    e.update(env or {})
    return subprocess.Popen(
        [sys.executable, "-c", src, str(shard_id), str(n_shards),
         json.dumps(cfg), out_path],
        env=e, stdout=subprocess.PIPE, text=True,
    )


def read_server_port(proc, timeout: float = 120.0) -> int:
    """Block until a spawned shard server prints its port line."""
    import select

    deadline = time.time() + timeout
    while time.time() < deadline:
        r, _, _ = select.select([proc.stdout], [], [], 0.5)
        if r:
            line = proc.stdout.readline()
            if line:
                return int(json.loads(line)["port"])
        if proc.poll() is not None:
            raise RuntimeError(f"shard server exited early: {proc.returncode}")
    raise TimeoutError("shard server never reported its port")


def spawn_sharded_worker(addrs: Sequence[str], worker_id: int,
                         cfg: Dict[str, Any], out_path: str,
                         env: Optional[Dict[str, str]] = None):
    """Launch ``worker_main_sharded`` in a fresh OS process (host
    backend; the chip-backed worker is ``async_train.spawn_worker``'s
    ``env`` path)."""
    src = (
        "import json,sys\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from pytorch_ps_mpi_tpu.parallel.sharded import worker_main_sharded\n"
        "addrs, wid, cfg, out = (json.loads(sys.argv[1]), int(sys.argv[2]),\n"
        "                        json.loads(sys.argv[3]), sys.argv[4])\n"
        "sys.exit(0 if worker_main_sharded(addrs, wid, cfg, out) >= 0 else 1)\n"
    )
    e = dict(os.environ)
    e.update({"JAX_PLATFORMS": "cpu"})
    e.update(env or {})
    return subprocess.Popen(
        [sys.executable, "-c", src, json.dumps(list(addrs)), str(worker_id),
         json.dumps(cfg), out_path],
        env=e,
    )
