"""Async PS training with REAL jitted compute in every process.

The full AsySG-InCon stack the reference ran — every rank doing actual
backprop, gradients shipped through the wire, a PS applying them in
arrival order (reference ``README.md:61-81`` pseudo-code; hook/pool
overlap ``ps.py:65-66,98-101``) — realized end-to-end across OS
processes:

  worker process:  read latest params (inconsistent read, seqlock)
                   → jitted ``value_and_grad`` of a flax model on device
                   → codec ``encode`` (jitted, CodecWire)
                   → payload BYTES into the shm mailbox
  server process:  poll mailboxes in arrival order
                   → codec ``decode`` (jitted)
                   → jitted fused ``sgd_update``/``adam_update``
                   → publish new snapshot (version += 1)

No gradient anywhere is computed outside ``jax.jit``. Staleness is
measured against publish versions and bounded by the server
(``max_staleness`` drops, ``stale_drops`` counter); a deliberately slow
worker exercises both the nontrivial staleness histogram and the drops.

Two serve disciplines, for the async-vs-sync wall-clock comparison the
algorithm exists for (Lian et al. 2015, arXiv:1506.08272):

- ``serve(..., sync_barrier=False)`` — AsySG: apply each gradient the
  moment it arrives. Throughput tracks the FAST workers.
- ``serve(..., sync_barrier=True)``  — synchronous PS oracle: collect one
  gradient from EVERY worker per round, apply the batch, publish once.
  Throughput collapses to the slowest worker (the straggler effect the
  reference's two-phase protocol fought, ``mpi_comms.py:190-191``).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from pytorch_ps_mpi_tpu import telemetry
from pytorch_ps_mpi_tpu.telemetry import span

PyTree = Any

# update/wait latency buckets (seconds): sub-ms jitted updates through
# multi-second straggler waits
_LATENCY_BUCKETS = (1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2,
                    5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def _telemetry_from_cfg(cfg: Dict[str, Any], worker: Any):
    """The zero-cost-when-disabled switch: ``cfg["telemetry_dir"]``
    enables the process-global FlightRecorder (server process AND every
    spawned worker — cfg rides the spawn's JSON argv, so one flag arms
    the whole fleet). Returns the active recorder or None."""
    rec = telemetry.get_recorder()
    if rec is None and cfg.get("telemetry_dir"):
        rec = telemetry.configure(
            capacity=int(cfg.get("telemetry_capacity", 65536)), worker=worker
        )
    return rec


def _dump_recorder(cfg: Dict[str, Any], rec, filename: str) -> Optional[str]:
    tdir = cfg.get("telemetry_dir")
    if rec is None or not tdir:
        return None
    os.makedirs(tdir, exist_ok=True)
    return rec.dump_jsonl(os.path.join(tdir, filename))


def _model_by_name(name: str, **kw):
    if name == "mlp":
        from pytorch_ps_mpi_tpu.models import MLP

        return MLP(features=tuple(kw.get("features", (32, 8))))
    if name == "resnet18":
        from pytorch_ps_mpi_tpu.models import ResNet18

        return ResNet18(num_classes=kw.get("num_classes", 10),
                        small_inputs=True)
    if name == "resnet50":
        from pytorch_ps_mpi_tpu.models import ResNet50

        return ResNet50(num_classes=kw.get("num_classes", 10),
                        small_inputs=True)
    if name == "gpt":
        from pytorch_ps_mpi_tpu.models import GPTLM, gpt_tiny

        # forward EVERY config knob (remat, attention, dtype, ...);
        # only the sizing defaults are overridden for fleet-test scale
        return GPTLM(gpt_tiny(**{
            "vocab_size": 256, "hidden_size": 64, "num_layers": 2,
            "num_heads": 4, "intermediate_size": 128, "max_position": 64,
            **kw,
        }))
    raise ValueError(f"unknown model {name!r}")


def make_problem(cfg: Dict[str, Any]):
    """(model, params0, batch_fn, loss_fn) deterministically from ``cfg``
    — every process (server and workers) rebuilds the same problem from
    the same dict, the rank-parameterized-oracle pattern of the
    reference's tests (SURVEY §4) applied to a train job."""
    import jax
    import jax.numpy as jnp

    model = _model_by_name(cfg["model"], **cfg.get("model_kw", {}))
    in_shape = tuple(cfg.get("in_shape", (8,)))
    batch = int(cfg.get("batch", 32))
    k = jax.random.key(int(cfg.get("seed", 0)))
    kp, kx, kw = jax.random.split(k, 3)
    if cfg["model"] != "gpt":  # token models init on int inputs below
        x0 = jnp.zeros((1,) + in_shape, jnp.float32)
        params0 = model.init(kp, x0)

    n_out = int(cfg.get("model_kw", {}).get("num_classes", 0)) or (
        tuple(cfg.get("model_kw", {}).get("features", (32, 8)))[-1]
        if cfg["model"] == "mlp" else 10
    )

    if cfg["model"] == "gpt":
        # causal LM on a fixed bigram Markov chain: the TABLE is built
        # once from cfg['seed'] (every process sees the same language);
        # sampling streams derive per (worker, step) through a
        # SeedSequence, which cannot collide the way linear seed
        # arithmetic (1000*worker + step) did at step >= 1000
        from pytorch_ps_mpi_tpu.data import markov_table, sample_markov
        from pytorch_ps_mpi_tpu.models import causal_lm_loss

        vocab = model.cfg.vocab_size
        seq = int(cfg.get("seq_len", 32))
        if seq > model.cfg.max_position:
            raise ValueError(
                f"seq_len={seq} exceeds the model's max_position="
                f"{model.cfg.max_position}: positions past it would be "
                "silently clamped to one embedding"
            )
        base_seed = int(cfg.get("seed", 0))
        cum = markov_table(vocab, base_seed)
        params0 = model.init(kp, jnp.zeros((1, seq), jnp.int32))

        def batch_fn(step: int, worker: int):
            ss = np.random.SeedSequence([base_seed, worker, step])
            rng = np.random.RandomState(ss.generate_state(1)[0])
            return jnp.asarray(sample_markov(cum, batch, seq, rng))

        def loss_fn(params, tokens):
            return causal_lm_loss(model.apply(params, tokens), tokens)

        return model, params0, batch_fn, loss_fn

    if cfg["model"] == "mlp":
        # regression against a fixed random linear teacher: smooth convex-
        # ish loss whose value cleanly separates trained from untrained
        d_in = int(np.prod(in_shape))
        w_true = jax.random.normal(kw, (d_in, n_out)) / d_in ** 0.5

        def batch_fn(step: int, worker: int):
            kk = jax.random.fold_in(jax.random.fold_in(kx, worker), step)
            x = jax.random.normal(kk, (batch,) + in_shape)
            y = x.reshape(batch, -1) @ w_true
            return x, y

        def loss_fn(params, b):
            x, y = b
            pred = model.apply(params, x)
            return jnp.mean((pred - y) ** 2)
    else:
        def batch_fn(step: int, worker: int):
            kk = jax.random.fold_in(jax.random.fold_in(kx, worker), step)
            x = jax.random.normal(kk, (batch,) + in_shape)
            y = jax.random.randint(jax.random.fold_in(kk, 1), (batch,), 0, n_out)
            return x, y

        def loss_fn(params, b):
            x, y = b
            logits = model.apply(params, x)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    return model, params0, batch_fn, loss_fn


def worker_cfg(cfg: Dict[str, Any], worker_id: int) -> Tuple[float, int]:
    """Per-worker (slow_ms, steps) from the shared job config — one
    parser for every worker body (shm, tcp, sharded)."""
    slow_ms = float(cfg.get("slow_ms", {}).get(str(worker_id), 0.0)) if isinstance(
        cfg.get("slow_ms"), dict) else 0.0
    steps = int(cfg.get("worker_steps", {}).get(str(worker_id),
                cfg.get("steps", 10))) if isinstance(
        cfg.get("worker_steps"), dict) else int(cfg.get("steps", 10))
    return slow_ms, steps


def worker_main(name: str, worker_id: int, cfg: Dict[str, Any]) -> int:
    """Worker process body: jitted fwd/bwd → encode → push bytes.
    Returns the number of gradients pushed.

    ``cfg["transport"]`` selects the wire: ``"shm"`` (default, co-hosted
    processes, ``dcn.py``) or ``"tcp"`` (cross-host DCN role, ``tcp.py``
    — ``name`` then carries ``"host:port"``). The compute path is
    identical either way: no gradient is ever produced outside jit.

    Resilience knobs (all off by default — the legacy fail-fast worker):

    - ``cfg["frame_check"]``: seal every push in a self-verifying frame
      (CRC + config fingerprint, ``resilience.frames``) — must match the
      server's setting, like the codec config it fingerprints.
    - ``cfg["resilient"]``: wrap the transport in
      :class:`~pytorch_ps_mpi_tpu.resilience.worker.ResilientWorker` —
      backoff+retry on timeouts, full reconnect on EOF — so a server
      restart-from-checkpoint is survived instead of raised on
      (``cfg["resilience_kw"]`` forwards tuning knobs).
    - ``cfg["fault_plan"]``: consult a deterministic
      :class:`~pytorch_ps_mpi_tpu.resilience.faults.FaultInjector` for
      this worker id at every step (drop/delay/duplicate/corrupt/
      crash_worker kinds).
    """
    import jax

    from pytorch_ps_mpi_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    # the way to the first acknowledged push, in the set-up log:
    # setup.worker with its phases under it
    starting = telemetry.SetupPhases("worker", worker=worker_id)
    cache = enable_compilation_cache()
    with starting.phase("attach"):
        # the backend's attach, which otherwise hides in make_problem's
        # first jax call
        starting.attrs["platform"] = jax.local_devices()[0].platform
    code = None
    if cfg.get("codec"):
        from pytorch_ps_mpi_tpu.codecs import get_codec

        code = get_codec(cfg["codec"], **cfg.get("codec_kw", {}))

    with starting.phase("problem"):
        _, params0, batch_fn, loss_fn = make_problem(cfg)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))  # ONLY grad source

    slow_ms, steps = worker_cfg(cfg, worker_id)
    frame = bool(cfg.get("frame_check"))

    def make_transport():
        if cfg.get("transport", "shm") == "tcp":
            from pytorch_ps_mpi_tpu.parallel.tcp import TcpPSWorker

            host, port = name.rsplit(":", 1)
            return TcpPSWorker(host, int(port), worker_id, params0,
                               code=code,
                               timeout=float(cfg.get("open_timeout", 60.0)),
                               bucket_mb=float(cfg.get("bucket_mb", 0.0)),
                               frame=frame)
        from pytorch_ps_mpi_tpu.parallel.dcn import ShmPSWorker

        return ShmPSWorker(name, worker_id, params0, code=code,
                           timeout=float(cfg.get("open_timeout", 60.0)),
                           bucket_mb=float(cfg.get("bucket_mb", 0.0)),
                           frame=frame)

    rec = _telemetry_from_cfg(cfg, worker=worker_id)
    with starting.phase("open"):
        if cfg.get("tree_leader"):
            # aggregation-tree leaf: push to the group leader, fall back
            # to the root when the leader dies, rejoin on its respawn —
            # the tree's own failover IS the resilience layer here
            from pytorch_ps_mpi_tpu.parallel.tree import TreeWorkerConn

            w = TreeWorkerConn(worker_id, params0, cfg)
        elif cfg.get("resilient"):
            from pytorch_ps_mpi_tpu.resilience.worker import ResilientWorker

            w = ResilientWorker(make_transport, worker_id=worker_id,
                                seed=int(cfg.get("fault_seed",
                                                 cfg.get("seed", 0))),
                                **cfg.get("resilience_kw", {}))
        else:
            w = make_transport()

    from pytorch_ps_mpi_tpu.resilience.faults import (
        CRASH_EXIT_CODE,
        FaultInjector,
    )

    inj = FaultInjector.from_cfg(cfg, role=worker_id)
    push_timeout = float(cfg.get("push_timeout", 60.0))
    # self-driving control plane, worker half: when the controller is
    # armed, the server publishes codec renegotiations (wire-epoch
    # bumps) as an atomically-replaced control-epoch.json; the worker
    # polls it between steps (one os.stat per step) and rebuilds its
    # wire onto the new epoch. No other worker-side change exists — LR
    # scaling and evict/readmit are applied entirely server-side.
    control_dir = cfg.get("control_dir") or (
        cfg.get("telemetry_dir")
        if (cfg.get("control") or cfg.get("control_kw")
            or cfg.get("topo_actions")) else None)
    epoch_state: Dict[str, Any] = {"epoch": 0, "mtime": 0}
    # structural-control half: control-topo.json carries the leader
    # re-assignment map (group split/merge); a tree leaf repoints its
    # leader connection when the map names it
    topo_state: Dict[str, Any] = {"seq": 0, "mtime": 0}
    # monotonic push seq — the third leg of the (worker, step, seq)
    # trace ID stamped into every framed push at THIS encode site;
    # duplicates get their own seq (both frames really travel)
    push_seq = 0
    prober = None
    probe_every = 0
    if cfg.get("numerics_dir") and getattr(w, "wire", None) is not None:
        # the codec-fidelity half of the numerics layer: decode-after-
        # encode probes must run HERE, on the pre-encode gradient — the
        # server only ever sees decoded values, and re-encoding those
        # measures ~0 error for sign-like codecs. Rows are tailed live
        # by the server-side NumericsMonitor.
        from pytorch_ps_mpi_tpu.telemetry.numerics import (
            NUMERICS_KNOBS,
            ProbeWriter,
        )

        probe_every = max(1, int((cfg.get("numerics_kw") or {}).get(
            "probe_every", NUMERICS_KNOBS["probe_every"])))
        prober = ProbeWriter(cfg["numerics_dir"], worker_id)
    wprof = None
    if cfg.get("profile") or cfg.get("profile_dir"):
        prof_dir = cfg.get("profile_dir") or cfg.get("telemetry_dir")
        if prof_dir:
            # continuous profiling, worker half: the same collapsed-stack
            # sampler the serve loop runs, one profile-worker-N.txt per
            # process, merged by tools/telemetry_report.py
            from pytorch_ps_mpi_tpu.telemetry.profiler import (
                SamplingProfiler,
            )

            wprof = SamplingProfiler(
                name=f"worker-{worker_id}", dir=prof_dir,
                **(cfg.get("profile_kw") or {})).start()
    beacon = None
    if cfg.get("health_dir"):
        # the online-diagnosis side channel: one appended JSONL row per
        # step with the SAME durations the recorder spans measure, so
        # the server-side HealthMonitor can attribute a straggle to
        # compute vs wire while the run is still going (the recorder
        # dump only lands at exit)
        from pytorch_ps_mpi_tpu.telemetry.diagnosis import BeaconWriter

        beacon = BeaconWriter(cfg["health_dir"], worker_id)
    pushed = 0
    first_grad_s = None
    try:
        for step in range(steps):
            t_step0 = time.monotonic()
            if control_dir is not None:
                from pytorch_ps_mpi_tpu import control as _control

                doc = _control.poll_epoch(control_dir, epoch_state)
                if doc is not None:
                    try:
                        _control.apply_epoch(w, doc)
                    except Exception:
                        pass  # a bad epoch doc must never kill a worker
                if hasattr(w, "repoint"):
                    from pytorch_ps_mpi_tpu.control.topo import poll_topo

                    tdoc = poll_topo(control_dir, topo_state)
                    if tdoc is not None:
                        addr = (tdoc.get("assign") or {}).get(
                            str(worker_id))
                        if addr:
                            try:
                                w.repoint(addr)
                            except Exception:
                                pass  # failover owns recovery; a bad
                                # repoint must never kill a worker
            drop = duplicate = poison = False
            if inj is not None:
                for f in inj.faults_at(step):
                    kind = f["kind"]
                    if kind == "crash_worker":
                        # fired (and fault-logged) BEFORE dying; os._exit
                        # skips every finally — the closest an injector
                        # gets to SIGKILL from inside the process
                        inj.fire(f)
                        _dump_recorder(cfg, rec, f"worker-{worker_id}.jsonl")
                        os._exit(CRASH_EXIT_CODE)
                    elif kind == "delay":
                        inj.fire(f)
                        time.sleep(float(f.get("delay_ms", 100.0)) / 1e3)
                    elif kind == "wire_delay":
                        # emulated wire latency: the transport sleeps
                        # AFTER sealing the frame (send_wall stamped),
                        # so the delay lands in the lineage wire stage
                        # — unlike "delay", which inflates produce
                        inj.fire(f)
                        wd = float(f.get("delay_ms", 100.0)) / 1e3
                        if hasattr(w, "set_wire_delay"):
                            w.set_wire_delay(wd)
                        else:
                            w._wire_delay_s = wd
                    elif kind == "drop":
                        inj.fire(f)
                        drop = True
                    elif kind == "duplicate":
                        inj.fire(f)
                        duplicate = True
                    elif kind == "nan":
                        # numerics chaos: poison this step's gradient
                        # with NaNs BEFORE encode — the quarantine leg's
                        # deterministic test vector
                        inj.fire(f)
                        poison = True
                    elif kind == "corrupt":
                        # fires when the tampered push actually happens
                        tamper = inj.make_tamper(f)
                        if hasattr(w, "set_tamper"):
                            w.set_tamper(tamper)
                        else:
                            w._tamper = tamper
            if drop:
                # a dropped push cannot also be corrupted or
                # wire-delayed: disarm any one-shot hooks armed this
                # step, or they would leak onto the NEXT step's push
                # (logged under the wrong step) — the faults
                # deterministically never fire instead
                if hasattr(w, "set_tamper"):
                    w.set_tamper(None)
                else:
                    w._tamper = None
                if hasattr(w, "set_wire_delay"):
                    w.set_wire_delay(0.0)
                else:
                    w._wire_delay_s = 0.0
            # one cycle, read to push, as spans (telemetry.span does
            # nothing while the recorder is off); the health beacon's
            # shared durations keep their own clock readings
            # (starting.phase: a set-up span in the first cycle, the
            # do-nothing context in every later one)
            with span("worker.step", step=step):
                with span("worker.read_params"), starting.phase("first_read"):
                    params, version = w.read_params()
                t0 = time.monotonic()
                with span("worker.grad", version=version):
                    with span("worker.batch"):
                        batch = batch_fn(step, worker_id)
                    # the gradient program's build is inside
                    with starting.phase("first_grad"):
                        with span("worker.grad_dispatch"):
                            loss, grads = grad_fn(params, batch)
                        with span("worker.grad_wait"):
                            jax.block_until_ready(grads)
                compute_s = time.monotonic() - t0
                if first_grad_s is None:
                    # compile (or cache load) included
                    first_grad_s = compute_s
                if poison:
                    import jax.numpy as jnp

                    grads = jax.tree.map(
                        lambda g: jnp.full_like(g, jnp.nan), grads
                    )
                if prober is not None and step % probe_every == 0:
                    try:
                        prober.write(step, w.wire.probe_fidelity(grads))
                    except Exception:
                        pass  # a probe must never take a worker down
                straggle_s = 0.0
                if slow_ms:
                    t0 = time.monotonic()
                    with span("worker.straggle"):
                        time.sleep(slow_ms / 1e3)  # deliberate straggler
                    straggle_s = time.monotonic() - t0
                if not drop:
                    # seq joins the span so trace export can tie this
                    # push span to the server's consume span (flow arrow)
                    with span("worker.push_grad", version=version,
                              seq=push_seq), starting.phase("first_push"):
                        w.push_grad(grads, version, timeout=push_timeout,
                                    lineage=(step, push_seq))
                        push_seq += 1
                        if duplicate:
                            w.push_grad(grads, version, timeout=push_timeout,
                                        lineage=(step, push_seq))
                            push_seq += 1
            pushed += 1
            if starting.open:  # the first cycle is over: set-up is
                starting.done()
            if beacon is not None:
                # step accounting for straggler ATTRIBUTION: the
                # deliberate slow_ms sleep emulates slow compute, so it
                # rides the compute bucket; everything else that isn't
                # the jitted grad — reads, pushes, retry backoff, and
                # injected delay faults — is wire-side
                wire_s = max(
                    0.0, (time.monotonic() - t_step0) - compute_s
                    - straggle_s)
                beacon.step(step, compute_s + straggle_s, wire_s,
                            straggle_s,
                            retries=getattr(w, "retries", 0),
                            reconnects=getattr(w, "reconnects", 0))
        if rec is not None and hasattr(w, "reconnects"):
            rec.event("resilience.summary", worker=worker_id,
                      retries=w.retries, reconnects=w.reconnects)
    finally:
        starting.done()  # a worker that never pushed says how far it got
        w.close()
        _dump_recorder(cfg, rec, f"worker-{worker_id}.jsonl")
        if prober is not None:
            prober.close()
        if beacon is not None:
            beacon.close(retries=getattr(w, "retries", 0),
                         reconnects=getattr(w, "reconnects", 0))
        if wprof is not None:
            wprof.stop()
            wprof.write()
        # which device this worker computed on, said once per process
        dev = jax.devices()[0]
        print(f"worker {worker_id}: " + json.dumps({
            "platform": dev.platform, "device_kind": dev.device_kind,
            "pushed": pushed, "first_grad_s": first_grad_s,
            "compile_cache": cache.as_dict(),
        }), file=sys.stderr, flush=True)
    return pushed


def _restore_ps_checkpoint(ckpt, params, state, checkpoint_every: int):
    """Restore the latest PS snapshot; returns (params, opt_state,
    applied_total, resumed_version). The resumed version is jumped past
    anything a surviving worker could have read in the crash window (the
    SAVED run's cadence bounds it — see serve's docstring); the restored
    step is marked already-saved so it is never re-saved (Orbax raises
    StepAlreadyExistsError). Shared by the single-server serve loop and
    the sharded shard-server loop."""
    template = {"params": params, "opt_state": state,
                "version": 0, "applied_total": 0, "checkpoint_every": 0}
    restored = ckpt.restore(template)
    applied_before = int(restored["applied_total"])
    ckpt._last_ps_step = applied_before
    jump = max(int(restored["checkpoint_every"]), int(checkpoint_every), 0)
    version = int(restored["version"]) + jump + 1
    return restored["params"], restored["opt_state"], applied_before, version


class _PSCheckpointCadence:
    """The save half of PS checkpointing, shared by the single-server
    serve loop and the sharded shard-server loop so the crash-window
    guarantees can never diverge between them: save when the APPLIED
    COUNT has advanced by ``checkpoint_every`` since the last save (not
    on divisibility — sync_barrier mode advances ``applied`` by
    n_workers per round and would hit an exact multiple only every lcm),
    plus one unconditional final save at loop exit."""

    def __init__(self, ckpt, checkpoint_every: int, applied_before: int):
        self.ckpt = ckpt
        self.every = int(checkpoint_every)
        self.last_saved = int(applied_before)

    def _save(self, params, state, server, applied_total: int) -> None:
        if getattr(self.ckpt, "_last_ps_step", None) == applied_total:
            return  # final save coinciding with a periodic one
        import jax

        self.ckpt.save(applied_total, {
            "params": jax.tree.map(np.asarray, params),
            "opt_state": jax.tree.map(np.asarray, state),
            "version": server.version,
            "applied_total": applied_total,
            # the SAVING run's cadence bounds how far past this snapshot
            # the server can have published before a crash — the resume
            # jump must use it, not the restarting run's (possibly
            # smaller) one
            "checkpoint_every": self.every,
        })
        self.ckpt._last_ps_step = applied_total

    def maybe_save(self, params, state, server, applied_total: int) -> None:
        if self.every and applied_total - self.last_saved >= self.every:
            self._save(params, state, server, applied_total)
            self.last_saved = applied_total

    def final_save(self, params, state, server, applied_total: int) -> None:
        self._save(params, state, server, applied_total)


def serve(
    server,
    cfg: Dict[str, Any],
    total_grads: int,
    *,
    sync_barrier: bool = False,
    total_received: Optional[int] = None,
    timeout: float = 300.0,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    resume: bool = False,
    on_tick=None,
    stop_when=None,
) -> Tuple[PyTree, Dict[str, float]]:
    """Server body: poll → (decode) → jitted optimizer update → publish.

    ``total_grads`` counts APPLIED gradients (stale drops don't count).
    When ``total_received`` is given, the loop instead runs until that
    many gradients were CONSUMED (applied + stale-dropped) — the right
    stop condition when workers push a fixed count and some pushes are
    expected to be dropped (otherwise their final blocked pushes would
    time out). Returns (final params, metrics incl. steps/sec and final
    loss on a held-out evaluation batch).

    Checkpointing closes the SERVER side of the failure story (workers
    are already elastic): with ``checkpoint_dir`` set, the full PS state
    (params, optimizer state, publish version, applied count) is saved
    every ``checkpoint_every`` applied gradients; a replacement server
    started with ``resume=True`` restores the latest snapshot and keeps
    the version counter monotonic, so training continues where the dead
    server left off — workers just reconnect and read the next snapshot
    (the reference's MPI job had no analog: a rank-0 death ended the
    job, SURVEY §5.4/§5.3). ``applied``/counters restart per serve call;
    the restored ``applied_total`` rides in the metrics.

    Telemetry (``cfg`` keys, so one dict arms server and workers):

    - ``telemetry_dir``: enables the FlightRecorder here AND in every
      spawned worker (cfg rides the spawn argv); each process dumps its
      JSONL into the directory at exit (``server.jsonl``,
      ``worker-N.jsonl``) and the path rides the returned metrics as
      ``telemetry_jsonl``. Disabled (the default), the loop pays one
      None-check per gradient.
    - ``metrics_port``: start the Prometheus ``/metrics`` (+ ``/health``)
      HTTP endpoint (both transports — the endpoint renders live Python
      state on a daemon thread; 0 = auto-assign). The bound port is
      returned as ``metrics_port`` in the metrics and the endpoint stays
      up until ``server.close()``. Either way the serve loop feeds
      step-latency and straggler-wait histograms into
      ``server.scrape_registry()`` — also scrapable in-process via
      ``server.prometheus_text()``.

    Online diagnosis (``telemetry.diagnosis``): ``health_dir`` (worker
    beacon files + the HealthMonitor), ``health_port`` (serve ``/health``
    + ``/metrics`` over HTTP when ``metrics_port`` isn't set; same
    endpoint), or ``health: true`` (monitor only — verdicts ride the
    returned metrics as ``health``) arm a :class:`HealthMonitor` fed
    from INSIDE this loop: per-gradient EWMA/MAD anomaly flags, beacon
    tailing at tick cadence, and sync-round critical-path gating. Armed,
    the scrape registry additionally carries ``ps_worker_anomaly_total``,
    ``ps_round_gating_seconds`` and ``ps_worker_health`` per worker.

    Numerics observability (``telemetry.numerics``): ``numerics: true``
    (or ``numerics_dir`` / ``numerics_kw``) arms a
    :class:`NumericsMonitor` — every consumed push is validated BEFORE
    it can touch the optimizer (non-finite pushes counted per worker
    through ``_reject_frame``, the worker quarantined, the push skipped
    / sanitized / run-aborting per ``numerics_kw["policy"]``), grad-norm
    and update-to-weight-ratio statistics flow into the canonical
    metrics and ``/health``'s ``numerics`` section, workers append
    codec-fidelity probe rows into ``numerics_dir`` (tailed at tick
    cadence), and a NaN or norm spike writes a ``postmortem-*.json``
    divergence capture. An abort lands in the returned metrics as
    ``numerics_abort``.

    Gradient lineage (``telemetry.lineage``): ``lineage: true`` (or
    ``lineage_dir``) arms a :class:`LineageTracker` — every framed push
    carries a causal trace ID (worker, step, seq) + encode-site
    timestamp from the v2 frame header, ``framed_poll`` feeds the
    tracker per consumed push, and every published version gets a
    recorded lineage row (the exact composing pushes with staleness,
    bytes and per-stage wall times) in ``lineage-server.jsonl``. Exact
    per-push e2e latency/staleness join the canonical metrics
    (``push_e2e_p50_ms`` etc) and the scrape registry
    (``ps_push_e2e_seconds`` histogram), sync rounds get stage-level
    critical-path rows, and the snapshot rides the returned metrics as
    ``lineage``. Requires ``frame_check`` (the trace ID rides the frame
    header); skipped with a printed notice otherwise.

    Round anatomy (``telemetry.anatomy``): armed automatically with
    lineage (``cfg["anatomy"]`` defaults to ``"auto"``; ``False`` opts
    out) — every published version is decomposed into its exact
    stage-level critical path (produce / encode / wire / leader-fold /
    root-fold / optimizer-publish, clock-skew-corrected, composed
    trailers expanding tree hops) with Coz-style what-if projections,
    written as ``anatomy-server.jsonl`` rounds. The ``anatomy_*``
    canonical keys join the metrics/scrape/TSDB surfaces, ``/health``
    gains an ``anatomy`` section, the controller's wire-vs-compute
    regime inputs switch to the lineage-derived estimator, and the
    final snapshot (incl. the ranked advisor) rides the returned
    metrics as ``anatomy``.

    Parameter serving (:mod:`pytorch_ps_mpi_tpu.serving`): the loop now
    sits on a :class:`~pytorch_ps_mpi_tpu.serving.ServingCore` that owns
    the monitor plumbing above plus — when ``cfg["serving"]`` or
    ``cfg["read_port"]`` (0 = auto) arms it — the read tier: every
    publish lands an immutable refcounted snapshot in a ring of the last
    K versions; readers issue version-conditional reads answered as
    not-modified / codec-encoded delta / full snapshot, identical
    requests coalesce onto one encode, and a bounded admission queue
    sheds overload with explicit retry-after replies
    (``cfg["serving_kw"]`` tunes ring/admission/delta knobs). Read-tier
    counters join the canonical metrics (``reads_total``,
    ``read_p50_ms/p95_ms``, ``delta_bytes_saved``, ``reads_shed``,
    ``coalesce_hits``, ``reads_not_modified``) and ``/health`` gains a
    ``serving`` section; the bound port rides the returned metrics as
    ``read_port`` and the listener lives until ``server.close()``,
    exactly like the metrics endpoint. Unarmed, publishes degrade to the
    transport's own publish — the legacy path pays nothing.

    Homomorphic aggregation (``cfg["agg"]``: ``"auto"`` default /
    ``"on"`` / ``"off"``): in sync-barrier mode over a codec wire whose
    algebra supports it (``Codec.supports_aggregate`` — int8/qsgd in the
    integer domain, top-k/random-k/threshold by sparse index-merge,
    terngrad in the ternary-count domain, PowerSGD by factor concat,
    sign by per-element vote counts), the loop stops decoding per push:
    payloads queue in compressed form, each round folds one payload per
    active worker into a :class:`~pytorch_ps_mpi_tpu.parallel.dcn.
    WireAggregator`, and exactly ONE decode runs per published version
    (``decodes_per_publish == 1`` in the canonical metrics; ``agg_mode``
    1.0). Per-push server cost becomes a function of PAYLOAD size, and
    the ``[world, ...]`` decoded stack never exists. Falls back to
    decode-sum automatically — async mode, no codec, a codec without
    the algebra, or an armed numerics monitor (its per-push validation
    needs decoded trees) — counting ``agg_fallbacks`` when ``"on"``
    asked explicitly. The sign vote algebra is APPROXIMATE (exact when
    per-push scales agree; measured rel-error in
    ``benchmarks/fidelity_bench.py --aggregate``), so ``"auto"`` never
    arms it — approximate algebras require an explicit ``"on"``, the
    opt-in to that fidelity contract.

    Fleet observability plane (``telemetry.timeseries`` / ``.slo`` /
    ``.profiler`` / ``.fleet``): ``cfg["timeseries"]`` retains every
    canonical metric key as ring-buffered history (raw + 1 s/10 s/60 s
    tiers), sampled at this loop's tick cadence on this thread,
    persisted as ``timeseries-server.jsonl`` and served at
    ``/history?key=...&window=...``; ``cfg["slo"]`` arms the burn-rate
    watchdog over that history (verdicts into ``slo-server.jsonl``, the
    flight recorder, ``/health``'s ``slo`` section and the
    ``ps_slo_*`` instruments); ``cfg["profile"]`` runs the continuous
    sampling profiler (``profile-server.txt`` collapsed stacks, and in
    every spawned worker too); ``cfg["fleet_dir"]`` registers this
    server's endpoint for the fleet pane and serves the merged
    ``/fleet`` snapshot. Final sections ride the returned metrics as
    ``history`` / ``slo`` / ``profile``; the routes stay scrapable
    until ``server.close()``.

    Self-driving control plane (:mod:`pytorch_ps_mpi_tpu.control`):
    ``cfg["control"]`` (or ``control_kw`` / ``control_dir``) arms a
    :class:`Controller` fed at this loop's tick + consume sites. It
    renegotiates the wire codec/``bucket_mb``/agg-mode online from the
    measured wire-vs-compute balance (an epoch bump through the frame
    fingerprint handshake — workers poll ``control-epoch.json`` in
    ``control_dir`` and in-flight old-epoch frames are consumed, never
    rejected), applies staleness-aware per-push LR weights (AsySG-InCon
    bound; decode paths only — a compressed payload cannot be scaled),
    backoff-evicts churn-verdict workers from the sync barrier and
    readmits quarantined workers after a clean probation, and tunes the
    read tier's admission depth + snapshot ring from shed/ageout rates.
    Every action lands in ``control-server.jsonl`` with its triggering
    verdict; the input rows persist through the TSDB
    (``timeseries-control-server.jsonl``) so ``Controller.replay``
    re-derives the identical sequence. The final snapshot rides the
    returned metrics as ``control``.

    Resilience hooks:

    - ``on_tick``: called from INSIDE the loop (same thread as every
      native-transport call — a supervisor's watchdog never races a
      pump) at most every ``cfg["tick_interval"]`` seconds (default
      0.2); used to respawn dead workers.
    - ``stop_when``: extra stop predicate, checked at tick cadence; once
      true the loop drains the already-queued gradients and returns.
      The supervisor's "every worker exited cleanly" condition — exact
      push counts are unknowable under drop/duplicate/corrupt faults.
    - ``cfg["fault_plan"]``: server-targeted faults
      (``worker: "server"``) fire when the APPLIED count crosses their
      ``at_step`` — ``crash_server`` raises
      :class:`~pytorch_ps_mpi_tpu.resilience.faults.InjectedServerCrash`
      out of the loop WITHOUT the final checkpoint save (a crash doesn't
      get one; the periodic cadence is the resume point).
    - ``sync_barrier`` degraded rounds: when a round has been waiting
      longer than ``cfg["degraded_round_after"]`` seconds (default 5),
      workers that are transport-dead (no socket / flagged straggler)
      and have nothing queued are excluded and the round completes over
      the surviving workers — counted in ``degraded_rounds`` and
      ``ps_degraded_rounds_total`` instead of hanging forever. A dead
      worker that comes back (elastic replacement) rejoins the barrier
      the moment its next gradient arrives. Caveat for the shm
      transport: silence is its only death signal, so a LIVE worker
      whose healthy round legitimately exceeds the window is
      indistinguishable from a dead one and gets temporarily excluded
      (its late gradients still apply — it rejoins on arrival, nothing
      is lost) — size ``degraded_round_after`` above the slowest
      expected round. TCP uses the open socket as a positive liveness
      signal and does not have this ambiguity.
    """
    import jax

    from pytorch_ps_mpi_tpu.optim import OPTIMIZERS

    # the way to the first published version, in the set-up log:
    # setup.serve with its phases under it
    starting = telemetry.SetupPhases(
        "serve", workers=server.num_workers, codec=cfg.get("codec"))
    with starting.phase("problem"):
        _, params, batch_fn, loss_fn = make_problem(cfg)
    with starting.phase("optimizer"):
        hyper_cls, init_state, update_fn = OPTIMIZERS[cfg.get("optim", "sgd")]
        h = hyper_cls(**cfg.get("hyper", {"lr": 0.05}))
        state = init_state(params)
        update = jax.jit(lambda p, g, s: update_fn(p, g, s, h))
        eval_loss = jax.jit(loss_fn)
        eval_batch = batch_fn(10**6, 10**6)  # never used by any worker

    ckpt = None
    applied_before = 0
    if resume and not checkpoint_dir:
        raise ValueError("resume=True requires checkpoint_dir")
    if checkpoint_dir:
        from pytorch_ps_mpi_tpu.utils.checkpoint import CheckpointManager

        ckpt = CheckpointManager(checkpoint_dir)
        if resume:
            params, state, applied_before, server.version = (
                _restore_ps_checkpoint(ckpt, params, state, checkpoint_every)
            )

    rec = _telemetry_from_cfg(cfg, worker="server")
    reg = server.scrape_registry()
    h_update = reg.histogram(
        "ps_update_seconds", _LATENCY_BUCKETS,
        "optimizer update + publish wall per applied round",
    )
    h_wait = reg.histogram(
        "ps_poll_wait_seconds", _LATENCY_BUCKETS,
        "idle poll time preceding each consumed gradient (straggler wait)",
    )
    g_applied = reg.gauge(
        "ps_applied_total", "gradients applied this serve call"
    )
    # the reusable serving core owns everything that is NOT the trainer
    # loop: monitor plumbing (health / numerics / lineage — construction
    # unchanged, just extracted), the /metrics + /health endpoint, and —
    # when cfg["serving"] / cfg["read_port"] arm it — the snapshot ring
    # + delta/coalescing/admission read tier that serves readers without
    # this loop's involvement (see pytorch_ps_mpi_tpu.serving)
    from pytorch_ps_mpi_tpu.serving import ServingCore

    core = ServingCore(server, cfg)
    monitor = core.health
    numon = core.numerics
    lint = core.lineage
    metrics_http_port = core.metrics_http_port
    numerics_probe_every = int(numon.knobs["probe_every"]) if numon else 0

    from pytorch_ps_mpi_tpu.resilience.faults import (
        FaultInjector,
        InjectedServerCrash,
    )

    inj = FaultInjector.from_cfg(cfg, role="server")

    # -- self-driving control plane (cfg["control"] / "control_kw") -------
    # The Controller closes the verdict→action loop: fed at the SAME
    # on_tick/consume sites as the monitors above (no thread ever
    # touches a native handle), it renegotiates the wire codec from the
    # measured wire-vs-compute balance (epoch bump through the frame
    # fingerprint handshake — in-flight old-epoch frames are consumed,
    # not rejected), de-weights stale workers' pushes per the
    # AsySG-InCon bound (applied below as a per-push weight — no
    # worker-side change), backoff-evicts churning workers from the
    # sync barrier and readmits quarantined ones after a clean
    # probation, and tunes the read tier's admission depth + snapshot
    # ring. Every action is a recorded, replayable, reversible event
    # row (control-server.jsonl); Controller.replay() re-derives the
    # identical sequence from the persisted TSDB input rows.
    # Constructed BEFORE the aggregation arming below: a restarted
    # generation may restore the fleet's current wire epoch here, and
    # the agg decision must see the RESTORED wire (and the restore must
    # never race an already-set agg_mode).
    ctl = None
    if cfg.get("control") or cfg.get("control_kw") or cfg.get("control_dir"):
        from pytorch_ps_mpi_tpu.control import Controller

        ctl = Controller(server, cfg, core=core)

    # -- homomorphic aggregation (cfg["agg"]: "auto" | "on" | "off") ------
    # Armed, the sync-barrier loop stops decoding per push: each arriving
    # payload is kept in its COMPRESSED form, a round folds one payload
    # per active worker into a CodecWire aggregator, and exactly one
    # decode happens per published version (decodes_per_publish == 1).
    # Requirements — any miss falls back to the decode-sum path, loudly
    # when "on" asked for it: a sync barrier (async mode publishes per
    # push, one decode per publish already), a codec wire whose algebra
    # supports aggregation (Codec.supports_aggregate + per-unit
    # can_aggregate; approximate algebras additionally need the explicit
    # "on"), and no numerics monitor (its per-push decoded-tree
    # validation needs the decode; the payload-level non-finite screen
    # below rides the aggregator instead).
    agg_req = str(cfg.get("agg", "auto")).lower()
    if agg_req not in ("auto", "on", "off"):
        raise ValueError(f"cfg['agg'] must be auto/on/off, got {agg_req!r}")
    wire = getattr(server, "wire", None)
    agg_armed = (
        agg_req != "off" and sync_barrier and wire is not None
        and getattr(wire, "agg_supported", False) and numon is None
        # an APPROXIMATE algebra (sign's vote counts, agg_exact=False)
        # changes training numerics, so "auto" never arms it — only an
        # explicit cfg["agg"] = "on" opts into the measured fidelity
        # contract; exact algebras arm under "auto" (bit-identical)
        and (agg_req == "on"
             or getattr(wire.code, "agg_exact", True))
    )
    if agg_req == "on" and not agg_armed:
        why = ("no sync barrier" if not sync_barrier
               else "no codec wire" if wire is None
               else "codec lacks an aggregation algebra"
               if not getattr(wire, "agg_supported", False)
               else "numerics monitor armed")
        print(f"compressed-domain aggregation requested but not armed "
              f"({why}); falling back to decode-sum", flush=True)
    server.agg_mode = 1.0 if agg_armed else 0.0
    if ctl is not None:
        ctl.set_agg(agg_armed)

    def _agg_now() -> bool:
        """Compressed-domain folding is live only while no controller
        transition needs the decode path: a codec renegotiation first
        suspends aggregation (mixed-epoch payloads cannot share one
        accumulator), then bumps the epoch, then re-arms — and only
        while the CURRENT wire (a renegotiation may have replaced the
        boot one) actually supports the algebra under the same
        exactness policy the boot check enforced."""
        if not agg_armed:
            return False
        if ctl is not None and ctl.agg_suspended:
            return False
        if getattr(server, "_epoch_table", None):
            return False  # old-epoch frames may still be in flight
        w = server.wire
        if w is not wire:
            # renegotiated wire: re-validate the algebra (cached per
            # wire object — agg_supported walks every unit)
            ok = w.__dict__.get("_agg_ok_cached")
            if ok is None:
                ok = w.agg_supported and (
                    agg_req == "on"
                    or getattr(w.code, "agg_exact", True))
                w.__dict__["_agg_ok_cached"] = ok
            if not ok:
                return False
        return True

    loss0 = float(eval_loss(params, eval_batch))
    core.publish(params)
    applied = 0
    degraded_rounds = 0
    last_applied_total = applied_before
    cadence = (_PSCheckpointCadence(ckpt, checkpoint_every, applied_before)
               if ckpt else None)
    n_workers = server.num_workers
    # -- hierarchical-tree root mode (cfg["tree"], parallel.tree) ---------
    # The expected pusher set is no longer range(n_workers): leaders
    # (ids cfg["tree_members"]) push composed group aggregates, and leaf
    # workers appear dynamically only when their leader died and they
    # fell back to pushing directly. The sync barrier therefore runs
    # over a MEMBERSHIP-DYNAMIC active set, and every round is averaged
    # by the TOTAL composed worker-push count carried in the frames'
    # lineage trailers (one per direct push), which keeps the weighting
    # exact across degraded groups, ragged group sizes and fallback
    # pushes without any coordination.
    tree_mode = bool(cfg.get("tree"))
    tree_members: set = set(int(w) for w in (cfg.get("tree_members") or ()))
    tree_joined: set = set()
    # sync_barrier holds a FIFO per worker: the server pops mailboxes
    # eagerly (the single-slot mailbox never back-pressures a fast
    # worker), so a worker may deliver several gradients before a
    # straggler's first — queueing them, not overwriting, keeps the
    # oracle a true synchronous PS in which EVERY gradient enters exactly
    # one averaged round.
    import collections

    pending: Dict[int, Any] = collections.defaultdict(collections.deque)
    # critical-path bookkeeping for the monitor: when each worker FIRST
    # became ready (had something queued) in the current sync round
    round_ready: Dict[int, float] = {}
    dead_workers: set = set()
    c_degraded = reg.counter(
        "ps_degraded_rounds_total",
        "sync-barrier rounds completed over a partial fleet "
        "(transport-dead workers excluded)",
    )
    degrade_after = float(cfg.get("degraded_round_after", 5.0))
    tick_interval = float(cfg.get("tick_interval", 0.2))
    t0 = time.perf_counter()
    deadline = t0 + timeout

    def keep_going():
        if total_received is not None:
            return server.grads_received < total_received
        return applied < total_grads

    wait_t0 = time.perf_counter()
    round_t0 = time.perf_counter()
    loop_entered = time.monotonic()  # setup.serve.first_update begins
    next_tick = 0.0
    draining = False
    numerics_stop = False
    next_numerics_probe = 0  # applied count of the next update-ratio probe
    # native batched ingest (TCP + frames + native fast path): one C++
    # pump-and-pop drains every queued push, validated, per call; the
    # inbox serves them to the identical per-item bookkeeping below. In
    # raw (aggregation) mode the items are VIEWS into the transport's
    # batch buffer — consumed (copied into their round queue) before the
    # next batched pop, which only happens once the inbox is empty.
    batch_poll = getattr(server, "poll_grad_batch", None)
    inbox: collections.deque = collections.deque()

    def _next_item():
        # items ride the inbox tagged with the WIRE they were validated
        # against at POLL time (None = decoded): a controller agg
        # suspension or epoch bump mid-inbox must neither reinterpret
        # already-polled payload views as decoded trees nor mis-decode
        # them with a renegotiated wire installed after the poll
        if inbox:
            return inbox.popleft()
        raw = _agg_now()
        enc = server.wire if raw else None
        if batch_poll is not None:
            batch = batch_poll(raw=raw)
            if batch is not None:
                inbox.extend((it, enc) for it in batch)
                return inbox.popleft() if inbox else None
        item = server.poll_grad(raw=raw)
        return None if item is None else (item, enc)

    def _fire_server_faults() -> None:
        """Server-targeted faults fire when the global applied count
        crosses their at_step (a sync round advances it by several at
        once). crash_server propagates AFTER the batch's faults fired
        and were logged."""
        nonlocal last_applied_total
        hi = applied_before + applied
        if inj is None or hi == last_applied_total:
            return
        crash = None
        for f in inj.faults_between(last_applied_total, hi):
            inj.fire(f)
            if f["kind"] == "crash_server":
                crash = f
            elif f["kind"] == "delay":
                time.sleep(float(f.get("delay_ms", 100.0)) / 1e3)
        last_applied_total = hi
        if crash is not None:
            raise InjectedServerCrash(crash)

    def _post_update(up_t0: float, lineage_workers=None) -> None:
        # through the serving core: the transport publish plus — when the
        # read tier is armed — one snapshot into the refcounted ring
        # (same single flatten either way)
        server.grad_publishes += 1  # decodes_per_publish denominator
        core.publish(jax.tree.map(np.asarray, params))
        up_dur = time.perf_counter() - up_t0
        h_update.observe(up_dur)
        g_applied.set(float(applied))
        if rec is not None:
            rec.event("serve.update", kind="span", ts=up_t0, dur=up_dur,
                      step=applied, version=server.version)
        if lint is not None:
            # bill the just-published version with its composing pushes
            # (one per active worker in sync-barrier mode — mirroring
            # the pending[w].popleft() above — everything pending in
            # async mode, i.e. exactly the push just applied)
            lint.observe_publish(server.version, up_dur,
                                 workers=lineage_workers)
        if cadence:
            cadence.maybe_save(params, state, server, applied_before + applied)
        if starting.open:
            # the first published version: from the loop's entry through
            # the wait for the first gradient to this publish
            telemetry.setup_event(
                "setup.serve.first_update", kind="span", parent=starting.name,
                ts=loop_entered, dur=time.monotonic() - loop_entered,
                wait_s=starting.attrs.pop("first_wait_s", None))
            starting.done()
        _fire_server_faults()

    def _mark_dead_workers() -> None:
        """Transport-level liveness sweep, consulted only once a sync
        round has waited ``degrade_after`` seconds: TCP's ``connected``
        is a positive dead-socket signal; shm falls back to the
        stragglers silence window. A worker with a queued gradient is
        never marked — its round contribution is already here. Neither
        is a worker the server has NEVER seen: a fleet member still
        paying its multi-second startup (jax import, first compile) is
        slow, not dead — declaring it would silently shrink the oracle's
        barrier at startup. Never-started workers are the supervisor's
        problem (respawn or abandon), not the barrier's."""
        can_connect = hasattr(server, "connected")
        silent = None if can_connect else server.stragglers(degrade_after)
        for w in range(n_workers):
            if w in dead_workers or pending[w] or w not in server.last_seen:
                continue
            alive = server.connected(w) if can_connect else (w not in silent)
            if not alive:
                if tree_mode and w not in tree_members and w in tree_joined:
                    # a fallback leaf that closed its root socket went
                    # BACK to its respawned leader — it leaves the
                    # barrier's membership instead of being carried as
                    # a dead worker (which would count every later
                    # healthy round degraded); a fresh direct push
                    # re-joins it
                    tree_joined.discard(w)
                    continue
                dead_workers.add(w)
                if rec is not None:
                    rec.event("serve.worker_declared_dead", worker=w)

    def _try_complete_round(only_queued: bool = False) -> bool:
        """Complete one sync round over the ACTIVE (not declared-dead)
        workers if each has a queued gradient; degraded rounds (fewer
        than n_workers contributions) are counted, never hung on.
        Numerics-quarantined workers under the ``skip`` policy are
        excluded too: their pushes never enter ``pending``, so waiting
        on them would hang the barrier exactly like a dead worker —
        and unlike one, their socket stays open. ``only_queued`` (tree
        drain tail) completes a partial round over whatever is queued
        so no consumed frame is silently dropped from the lineage."""
        nonlocal params, state, applied, degraded_rounds, wait_t0, round_t0
        nonlocal next_numerics_probe
        if tree_mode:
            # membership-dynamic barrier: every tree member (leaders by
            # construction, fallen-back leaf workers by observation)
            # that is not declared dead must have a frame queued
            active = [w for w in sorted(tree_members | tree_joined)
                      if w not in dead_workers]
            if only_queued:
                active = [w for w in active if pending[w]]
        else:
            active = [w for w in range(n_workers) if w not in dead_workers]
        if numon is not None and numon.knobs["policy"] == "skip":
            active = [w for w in active if not numon.is_quarantined(w)]
        if ctl is not None:
            # controller-evicted (churn-verdict) workers leave the
            # barrier exactly like quarantined ones: the round completes
            # degraded over the survivors, their queued pushes are held,
            # and the backoff readmission re-includes them — the
            # existing degraded-round rejoin machinery, driven by a
            # verdict instead of a dead transport
            active = [w for w in active if not ctl.is_evicted(w)]
        if not active or any(not pending[w] for w in active):
            return False
        up_t0 = time.perf_counter()
        entries = [pending[w].popleft() for w in active]
        # read the server's CURRENT wire, not the boot-time capture: a
        # controller renegotiation replaces server.wire mid-run
        cur_wire = server.wire
        if _agg_now() and all(e[3] is cur_wire for e in entries):
            # compressed-domain round: fold one queued payload per
            # active worker into the wire aggregator, then ONE decode
            # (never a [world, ...] decoded stack, never per-push
            # decodes) — the averaged result feeds the same jitted
            # update the decode-sum path does. Folding requires every
            # entry raw AND encoded with the CURRENT wire (entries
            # carry their encode wire — a renegotiation between queue
            # and round sends them down the decode path instead). The
            # mean's denominator is the COMPOSED push count (frames
            # carry group sums in tree mode; 1 per frame otherwise, so
            # this is exactly the old 1/len(active)). Controller LR
            # weights do NOT apply here — a compressed payload cannot
            # be scaled per push (documented in docs/OPERATIONS.md).
            agg = cur_wire.agg_begin()
            total_comp = 0
            for buf, comp_n, _wgt, _wire in entries:
                agg.fold(buf)
                total_comp += comp_n
            server.decodes_done += 1
            inv = np.float32(1.0 / total_comp)
            summed = jax.tree.map(lambda x: x * inv, agg.finalize())
            n_contrib = agg.frames
        else:
            batch_grads, wgts = [], []
            total_comp = 0
            for g, comp_n, wgt, enc_wire in entries:
                if enc_wire is not None:
                    # a payload queued raw before the controller
                    # suspended aggregation (or before an epoch bump):
                    # decode it now with the wire it was ENCODED with
                    # (counted in decodes_done like any decode-sum push)
                    g = server._decode_payload(g, wire=enc_wire)
                batch_grads.append(g)
                wgts.append(float(wgt))
                total_comp += comp_n
            if all(wt == 1.0 for wt in wgts):
                # bit-identical to the pre-control decode-sum round
                summed = jax.tree.map(
                    lambda *gs: sum(gs) / total_comp, *batch_grads)
            else:
                # staleness-aware per-push LR scaling (AsySG-InCon):
                # de-weighted pushes contribute a smaller step; the
                # denominator stays the composed count, so a weight
                # only ever SHRINKS the stale worker's effective LR
                summed = jax.tree.map(
                    lambda *gs: sum(wt * gg for wt, gg
                                    in zip(wgts, gs)) / total_comp,
                    *batch_grads)
            n_contrib = len(batch_grads)
        probe = numon is not None and applied >= next_numerics_probe
        old_params = params if probe else None
        params, state = update(params, summed, state)
        applied += n_contrib
        if probe:
            numon.observe_update(old_params, params,
                                 applied_before + applied)
            next_numerics_probe = applied + numerics_probe_every
        if monitor is not None:
            # bill the round's critical path to the last-ready worker,
            # then reopen the book: a fast worker with another gradient
            # already queued is ready for the NEXT round right now
            monitor.observe_round(round_ready, active)
            round_ready.clear()
            for w2 in range(n_workers):
                if pending[w2]:
                    round_ready[w2] = up_t0
        degraded = (bool(dead_workers) if tree_mode
                    else n_contrib < n_workers)
        if degraded:
            degraded_rounds += 1
            c_degraded.inc()
            if rec is not None:
                rec.event("serve.degraded_round", step=applied,
                          absent=sorted(dead_workers))
        _post_update(up_t0, lineage_workers=active)
        wait_t0 = round_t0 = time.perf_counter()
        return True

    while keep_going() and time.perf_counter() < deadline:
        now = time.perf_counter()
        if now >= next_tick:
            next_tick = now + tick_interval
            if on_tick is not None:
                on_tick()
            # monitor upkeep (beacon/probe tailing), same thread
            core.tick()
            if ctl is not None:
                # the verdict→action sweep (self-throttled): builds one
                # input row, persists it, runs the decision engine,
                # executes any actions — all on this thread
                ctl.tick()
                if agg_armed:
                    server.agg_mode = 1.0 if _agg_now() else 0.0
            if stop_when is not None and not draining and stop_when():
                draining = True  # consume what's queued, then return
            if sync_barrier and now - round_t0 > degrade_after:
                _mark_dead_workers()
                while _try_complete_round():
                    pass
        pair = _next_item()
        if pair is None:
            if draining:
                break
            time.sleep(0.0005)
            continue
        (wid, grad_version, grad), item_wire = pair
        item_raw = item_wire is not None
        # tree mode: the frame's composed worker-push count (from its
        # lineage trailer), queued by the framed consume path in item
        # order — the round mean's per-frame weight; 1 otherwise
        comp_n = (server._composed_queue.popleft()
                  if tree_mode and getattr(server, "tree_slots", 0) else 1)
        if item_raw:
            # payload-level non-finite screen (the aggregation path's
            # stand-in for the numerics monitor's decoded-tree check,
            # which can't run here — arming requires numon off): a push
            # whose float payload leaves are non-finite would poison the
            # compressed accumulator, so reject it like any bad frame
            # and let the barrier wait for the worker's next push (the
            # same consumed-but-skipped discipline as numerics "skip")
            if not item_wire.payload_finite(grad):
                server._reject_frame(wid, "nonfinite")
                if lint is not None:
                    lint.discard_last(wid, reason="nonfinite")
                wait_t0 = time.perf_counter()
                continue
            # grad is the validated payload BYTES (a view into the
            # receive buffer): one payload-sized copy queues it for its
            # round — the per-push cost, in place of a jitted decode +
            # full-tree rebuild
            grad = np.copy(grad)
        elif agg_armed:
            # the controller suspended folding (codec-renegotiation
            # window) so this push arrived DECODED — but the numerics
            # monitor is off by the agg arming rule, so the aggregation
            # path's non-finite screen must follow the push onto the
            # decode path or a NaN gradient would reach the optimizer
            # during exactly the transition window
            if not all(bool(np.all(np.isfinite(np.asarray(leaf))))
                       for leaf in jax.tree.leaves(grad)):
                server._reject_frame(wid, "nonfinite")
                if lint is not None:
                    lint.discard_last(wid, reason="nonfinite")
                wait_t0 = time.perf_counter()
                continue
        elif agg_req == "on":
            server.agg_fallbacks += 1
        wait_s = time.perf_counter() - wait_t0
        h_wait.observe(wait_s)
        if starting.open:  # until the first publish: the first gradient's
            starting.attrs.setdefault("first_wait_s", wait_s)
        staleness = max(0, server.version - grad_version)
        if rec is not None:
            rec.event("serve.grad", worker=wid, staleness=staleness,
                      step=applied, version=grad_version)
        if monitor is not None:
            monitor.observe_grad(wid, staleness, wait_s)
        if ctl is not None:
            # the controller's consume-site feed: per-worker staleness
            # (the lr_scale rule's fallback input when lineage's exact
            # windows are unarmed)
            ctl.observe_push(wid, staleness)
        if numon is not None:
            # numerics validation BEFORE the gradient can touch the
            # optimizer: count/quarantine non-finite pushes, then let
            # the policy decide the frame's fate
            action = numon.observe_push(wid, grad, applied_before + applied)
            if action == "abort":
                numerics_stop = True
                if lint is not None:
                    # the consumed push will never compose a version —
                    # give it its own drop row instead of leaking it
                    # into the next publish's lineage
                    lint.discard_last(wid, reason="numerics")
                break
            if action == "skip":
                if lint is not None:
                    lint.discard_last(wid, reason="numerics")
                wait_t0 = time.perf_counter()
                continue
            if action == "zero":
                from pytorch_ps_mpi_tpu.telemetry.numerics import (
                    sanitize_tree,
                )

                grad = sanitize_tree(grad)
        if sync_barrier:
            # synchronous oracle: a round completes when every active
            # worker has at least one queued gradient; one per worker is
            # consumed. A gradient from a declared-dead worker proves it
            # back alive (elastic replacement) — it rejoins the barrier.
            dead_workers.discard(wid)
            if tree_mode:
                tree_joined.add(wid)
            if ctl is not None and ctl.is_evicted(wid):
                # a backoff-evicted worker's pushes are DROPPED, not
                # queued: an unbounded pending backlog would re-apply
                # seconds-stale gradients one round at a time after
                # readmission. Same consumed-but-skipped discipline as
                # numerics "skip" — minus the rejection counter, which
                # feeds the churn verdict and would re-evict the worker
                # the moment it was readmitted. It rejoins the barrier
                # with its first post-readmission push.
                if lint is not None:
                    lint.discard_last(wid, reason="evicted")
                if rec is not None:
                    rec.event("serve.evicted_drop", worker=wid)
                wait_t0 = time.perf_counter()
                continue
            pending[wid].append((
                grad, comp_n,
                ctl.push_weight(wid) if ctl is not None else 1.0,
                item_wire))
            if monitor is not None and wid not in round_ready:
                round_ready[wid] = time.perf_counter()
            if not _try_complete_round():
                wait_t0 = time.perf_counter()
        else:
            up_t0 = time.perf_counter()
            probe = numon is not None and applied >= next_numerics_probe
            old_params = params if probe else None
            wgt = ctl.push_weight(wid) if ctl is not None else 1.0
            if wgt != 1.0:
                # staleness-aware per-push LR scaling (AsySG-InCon
                # bound): the stale worker's update shrinks; comp_n
                # folds into the same map below
                grad = jax.tree.map(lambda x: x * wgt / comp_n, grad)
            elif comp_n > 1:
                # a composed frame carries its group's SUM: apply the
                # group mean so the async step size is load-independent
                grad = jax.tree.map(lambda x: x / comp_n, grad)
            params, state = update(params, grad, state)
            applied += 1
            if probe:
                # ||dp||/||p|| at probe cadence only — the old params
                # are retained just long enough for one jitted diff
                numon.observe_update(old_params, params,
                                     applied_before + applied)
                next_numerics_probe = applied + numerics_probe_every
            _post_update(up_t0)
            wait_t0 = time.perf_counter()
    if tree_mode and sync_barrier:
        # drain tail: frames consumed but still queued when the stop
        # condition fired compose one final partial round each, so
        # every consumed push lands in some version's lineage
        while _try_complete_round(only_queued=True):
            pass
    wall = time.perf_counter() - t0
    if cadence:  # final state always captured, whatever the stop reason
        cadence.final_save(params, state, server, applied_before + applied)
    if numon is not None:
        # drain the last worker probe rows BEFORE any metrics snapshot:
        # server.metrics() (and the /health snapshot below) read the
        # probe-derived gauges, and the workers' final rows typically
        # land after the loop's last tick
        numon.tick()
        # one closing trajectory row so offline tooling sees the FINAL
        # grad-norm/nonfinite state, not the last probe-cadence sample
        numon._trajectory_row(applied_before + applied)
    m = dict(server.metrics())
    m.update(
        applied=float(applied),
        applied_total=float(applied_before + applied),
        wall_s=wall,
        updates_per_sec=applied / wall if wall > 0 else 0.0,
        loss_initial=loss0,
        loss_final=float(eval_loss(params, eval_batch)),
        staleness_hist={int(k): int(v) for k, v in server.staleness_seen.items()},
        publish_version=float(server.version),
        degraded_rounds=float(degraded_rounds),
        frames_rejected_by_worker={
            int(k): int(v)
            for k, v in getattr(server, "frames_rejected", {}).items()
        },
    )
    if metrics_http_port is not None:
        m["metrics_port"] = metrics_http_port
    if core.armed:
        # read-tier rollup (ring occupancy, read counts, shed/coalesce);
        # the read server itself stays up until server.close(), exactly
        # like the /metrics endpoint
        m["serving"] = core.serving_snapshot()
        if core.read_port is not None:
            m["read_port"] = core.read_port
    if monitor is not None:
        m["health"] = monitor.snapshot()
    if numon is not None:
        m["numerics"] = numon.snapshot()
        if numerics_stop:
            m["numerics_abort"] = numon.aborted
        numon.close()
    if lint is not None:
        m["lineage"] = lint.snapshot()
        lint.close()
    if core.anatomy is not None:
        # the round-anatomy section: per-stage critical-path shares and
        # the ranked what-if advisor (projected round-time savings) —
        # what tools/whatif_smoke.py gates and RESULTS.md tabulates
        m["anatomy"] = core.anatomy.snapshot()
        core.anatomy.close()
    if ctl is not None:
        snap = ctl.snapshot()
        # zero-frame-loss accounting for codec renegotiations: every
        # old-epoch frame consumed during a transition is counted here
        # (they would have been "config" rejections without the epoch
        # table)
        snap["epoch_old_frames"] = int(
            getattr(server, "epoch_old_frames", 0))
        m["control"] = snap
        ctl.close()
    if server.timeseries_db is not None:
        # one closing sample so the retained history ends on the FINAL
        # counter state, not the last tick-cadence snapshot (force: the
        # ingest throttle must not drop the run's last word)
        server.timeseries_db.sample(server.metrics(), force=True)
    obs = server.finalize_observability()
    if obs:
        # the observability-plane sections: "history" (TSDB meta),
        # "slo" (rule states + verdicts), "profile" (top-N + file).
        # /history and /fleet stay scrapable — and the fleet
        # registration stays live — until server.close().
        m.update(obs)
    if cfg.get("telemetry_dir"):
        # final scrape snapshot for offline tooling: telemetry_report
        # tabulates the labeled series (per-worker rejections, anomaly
        # counts) from this file next to the recorder JSONLs
        prom_path = os.path.join(cfg["telemetry_dir"], "metrics.prom")
        os.makedirs(cfg["telemetry_dir"], exist_ok=True)
        with open(prom_path, "w") as f:
            f.write(server.prometheus_text())
        m["metrics_prom"] = prom_path
    jsonl = _dump_recorder(cfg, rec, "server.jsonl")
    if jsonl is not None:
        m["telemetry_jsonl"] = jsonl
    return params, m


def spawn_worker(name: str, worker_id: int, cfg: Dict[str, Any],
                 env: Optional[Dict[str, str]] = None):
    """Launch ``worker_main`` in a fresh OS process (its own JAX
    runtime). Placement is the environment's: ``JAX_PLATFORMS=cpu``
    unless ``env`` says otherwise, so a fleet of host workers never
    contends for a chip — which belongs to one process at a time. A
    worker that is to own the chip gets ``env={"JAX_PLATFORMS": ...}``
    naming it."""
    import subprocess

    src = (
        "import json,sys\n"
        "from pytorch_ps_mpi_tpu.parallel.async_train import worker_main\n"
        "name, wid, cfg = sys.argv[1], int(sys.argv[2]), json.loads(sys.argv[3])\n"
        "sys.exit(0 if worker_main(name, wid, cfg) >= 0 else 1)\n"
    )
    e = dict(os.environ)
    e.update({"JAX_PLATFORMS": "cpu"})
    e.update(env or {})
    return subprocess.Popen(
        [sys.executable, "-c", src, name, str(worker_id), json.dumps(cfg)],
        env=e,
    )


def join_workers(procs, timeout: float = 120.0):
    """Reap a fleet of spawned worker processes without ever leaking one.

    Waits up to ``timeout`` seconds TOTAL for the fleet, then terminates
    (SIGTERM, escalating to SIGKILL) whatever is still running — on the
    happy path a plain join, on every failure path (timeout, exception
    mid-join, stuck worker) a guaranteed reap. Returns the list of exit
    codes in ``procs`` order (negative = killed by that signal), so
    callers can assert ``== [0, ...]`` where they used to loop
    ``p.wait()`` — which leaked every later process when an earlier one
    failed the assert.
    """
    import subprocess

    codes = [None] * len(procs)
    deadline = time.time() + timeout
    try:
        for i, p in enumerate(procs):
            left = deadline - time.time()
            if left <= 0:
                break
            try:
                codes[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                pass  # reaped in finally
    finally:
        for i, p in enumerate(procs):
            if p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    p.kill()
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        pass  # unkillable (kernel-stuck); nothing left to do
            if codes[i] is None:
                codes[i] = p.returncode
    return codes
