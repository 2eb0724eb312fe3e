"""Read-only parameter-serving tier from a checkpoint directory.

The :class:`~pytorch_ps_mpi_tpu.serving.ServingCore` without a trainer
loop, without workers, without a transport server: restore the latest PS
checkpoint (the ``_PSCheckpointCadence`` snapshots ``serve()`` /
``Supervisor`` write), publish it into the snapshot ring, and serve
version-conditional reads (not-modified / delta / full, with coalescing
and admission control) plus ``/metrics`` + ``/health`` — the deployment
shape where inference replicas read a trained model without ever
touching the training fleet.

With ``--follow`` the tier keeps polling the checkpoint directory and
republishes whenever the trainer lands a newer step, so readers track a
LIVE training run through cheap delta reads; the poll backs off
exponentially while no newer checkpoint appears, so
an idle follower stops burning a core.

With ``--follow-endpoint HOST:PORT`` the process is a REPLICA instead:
it subscribes to an upstream read tier's delta stream
(:class:`~pytorch_ps_mpi_tpu.serving.FollowerLoop`) and re-serves it
from its own ring — chain replicas to build the distribution tree that
lets one trainer-side core serve N replicas rather than N×10⁴ readers.
Replicas register fleet cards with ``role="replica"`` (upstream +
fanout in the card), export ``replica_lag_versions`` /
``follower_bytes_relayed``, and survive a root restart by reconnecting
with backoff while serving their last version.

Examples::

  # train with checkpoints, then serve them read-only
  python examples/train_async.py --model mlp --workers 2 --steps 50 \\
      --checkpoint-dir /tmp/ps_ckpt
  python examples/serve_readonly.py --checkpoint-dir /tmp/ps_ckpt \\
      --model mlp --read-port 7070 --metrics-port 9100

  # a reader
  python - <<'PY'
  from pytorch_ps_mpi_tpu.serving import ServingReader
  from pytorch_ps_mpi_tpu.parallel.async_train import make_problem
  cfg = {"model": "mlp", "model_kw": {"features": (64, 8)},
         "in_shape": [8], "batch": 1, "seed": 0}
  _, tmpl, _, _ = make_problem(cfg)
  r = ServingReader("127.0.0.1", 7070, tmpl)
  params, version = r.read_params()
  print("got version", version)
  PY
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")


def restore_latest(checkpoint_dir: str, cfg: dict):
    """(params, version, step) from the newest PS checkpoint."""
    from pytorch_ps_mpi_tpu.optim import OPTIMIZERS
    from pytorch_ps_mpi_tpu.parallel.async_train import make_problem
    from pytorch_ps_mpi_tpu.utils.checkpoint import CheckpointManager

    _, params0, _, _ = make_problem(cfg)
    _, init_state, _ = OPTIMIZERS[cfg.get("optim", "sgd")]
    template = {"params": params0, "opt_state": init_state(params0),
                "version": 0, "applied_total": 0, "checkpoint_every": 0}
    ckpt = CheckpointManager(checkpoint_dir)
    step = ckpt.latest_step()
    if step is None:
        raise FileNotFoundError(f"no checkpoints in {checkpoint_dir}")
    restored = ckpt.restore(template, step=step)
    return restored["params"], int(restored["version"]), int(step), params0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory of _PSCheckpointCadence snapshots "
                         "(required unless --follow-endpoint)")
    ap.add_argument("--model", choices=["mlp", "resnet18", "resnet50"],
                    default="mlp",
                    help="model the checkpoint was trained with (defines "
                         "the parameter template — must match training)")
    ap.add_argument("--read-port", type=int, default=0,
                    help="read-tier port (0 = auto; printed on stdout)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="/metrics + /health port (0 = auto)")
    ap.add_argument("--tenant", default="default",
                    help="tenant namespace this checkpoint serves under")
    ap.add_argument("--ring", type=int, default=8,
                    help="snapshot ring depth (versions kept for deltas)")
    ap.add_argument("--admission-depth", type=int, default=64)
    ap.add_argument("--follow", type=float, default=0.0,
                    help="poll the checkpoint dir every N seconds and "
                         "republish newer steps (0 = serve one snapshot; "
                         "idle polls back off exponentially to "
                         "max(8s, 4x this))")
    ap.add_argument("--follow-endpoint", default=None, metavar="HOST:PORT",
                    help="replica mode: subscribe to this upstream read "
                         "tier and re-serve its delta stream (no "
                         "checkpoint dir needed)")
    ap.add_argument("--fanout", type=int, default=2,
                    help="replica mode: downstream replicas this node is "
                         "provisioned to feed (advertised on the fleet "
                         "card for tree planning)")
    ap.add_argument("--serving-kw", default=None,
                    help="JSON dict merged into serving_kw (delta codec "
                         "knobs etc. — must match the upstream's codec "
                         "in replica mode)")
    ap.add_argument("--read-native", default="auto",
                    help="native C++ read tier: auto (default; falls "
                         "back to the Python loop), off")
    ap.add_argument("--fleet-dir", default=None,
                    help="register this tier's endpoint card here "
                         "(role=replica when following an endpoint)")
    ap.add_argument("--telemetry-dir", default=None,
                    help="replica mode: write reader_round anatomy rows "
                         "(anatomy-<fleet name>.jsonl) here")
    ap.add_argument("--duration", type=float, default=0.0,
                    help="exit after this many seconds (0 = forever)")
    ap.add_argument("--control-dir", default=None,
                    help="replica mode: poll control-topo.json here and "
                         "re-parent the subscription when its "
                         "replica_upstream map names this replica "
                         "(structural control's elastic read tier)")
    args = ap.parse_args(argv)
    if not args.checkpoint_dir and not args.follow_endpoint:
        ap.error("--checkpoint-dir is required unless --follow-endpoint")

    serving_kw = {"ring": args.ring,
                  "admission_depth": args.admission_depth}
    serving_kw.update(json.loads(args.serving_kw) if args.serving_kw
                      else {})
    cfg = {
        "model": args.model,
        "model_kw": {"num_classes": 10} if args.model != "mlp" else
                    {"features": (64, 8)},
        "in_shape": [8] if args.model == "mlp" else [32, 32, 3],
        "batch": 1,
        "seed": 0,
        "read_port": args.read_port,
        "read_native": args.read_native,
        "metrics_port": args.metrics_port,
        "serving_kw": serving_kw,
        "follow_endpoint": args.follow_endpoint,
        "follow_fanout": args.fanout,
    }
    if args.fleet_dir:
        cfg["fleet_dir"] = args.fleet_dir
        cfg["fleet_name"] = (f"replica-{os.getpid()}"
                             if args.follow_endpoint else "read-tier")
        if args.follow_endpoint:
            cfg["fleet_role"] = "replica"
            cfg["fleet_meta"] = {"upstream": args.follow_endpoint,
                                 "fanout": cfg.get("follow_fanout")}

    if args.checkpoint_dir:
        params, version, step, template = restore_latest(
            args.checkpoint_dir, cfg)
    else:
        from pytorch_ps_mpi_tpu.parallel.async_train import make_problem

        _, template, _, _ = make_problem(cfg)
        params, version, step = None, 0, -1

    from pytorch_ps_mpi_tpu.serving import FollowerLoop, ServingCore

    core = ServingCore(None, cfg, template=template, tenant=args.tenant)
    if params is not None:
        core.publish(params, version=max(version, 1), tenant=args.tenant)
    follower = None
    if cfg.get("follow_endpoint"):
        up_host, _, up_port = str(cfg["follow_endpoint"]).rpartition(":")
        anatomy = None
        if args.telemetry_dir:
            from pytorch_ps_mpi_tpu.telemetry.anatomy import RoundAnatomy

            anatomy = RoundAnatomy(
                None, {"telemetry_dir": args.telemetry_dir},
                num_workers=1,
                name=str(cfg.get("fleet_name") or "replica"))
        follower = FollowerLoop(
            core, up_host or "127.0.0.1", int(up_port),
            template=template, tenant=args.tenant,
            poll_s=args.follow or 0.25, serving_kw=serving_kw,
            anatomy=anatomy).start()
    hello = {"read_port": core.read_port, "tenant": args.tenant,
             "version": max(version, 1) if params is not None else 0,
             "checkpoint_step": step, "native": core.read_native}
    if follower is not None:
        hello["upstream"] = cfg["follow_endpoint"]
        hello["fanout"] = cfg.get("follow_fanout")
    if core.metrics_http_port is not None:
        hello["metrics_port"] = core.metrics_http_port
    print(json.dumps(hello), flush=True)

    deadline = time.time() + args.duration if args.duration else None
    topo_state = {"seq": 0, "mtime": 0}
    replica_name = str(cfg.get("fleet_name") or f"replica-{os.getpid()}")

    def _poll_reparent():
        # structural control: a scale event can rebuild the replica
        # tree — control-topo.json's replica_upstream map names each
        # replica's (possibly new) parent; repoint is idempotent
        if not (args.control_dir and follower is not None):
            return
        from pytorch_ps_mpi_tpu.control.topo import poll_topo

        doc = poll_topo(args.control_dir, topo_state)
        if doc is None:
            return
        up = (doc.get("replica_upstream") or {}).get(replica_name)
        if not up:
            return
        host, _, port = str(up).rpartition(":")
        try:
            if follower.repoint(host or "127.0.0.1", int(port)):
                print(json.dumps({"reparented": up}), flush=True)
        except (TypeError, ValueError):
            pass

    last_step = step
    # idle-backoff pacing: a fresh checkpoint snaps the
    # poll back to the base cadence; every empty poll doubles it
    base_sleep = min(args.follow, 1.0) if args.follow else 0.25
    max_sleep = max(8.0, 4.0 * base_sleep) if args.follow else base_sleep
    sleep_s = base_sleep
    try:
        while deadline is None or time.time() < deadline:
            time.sleep(sleep_s if deadline is None
                       else min(sleep_s, max(deadline - time.time(), 0)))
            _poll_reparent()
            if args.follow and args.checkpoint_dir:
                try:
                    params, version, step, _ = restore_latest(
                        args.checkpoint_dir, cfg)
                except (FileNotFoundError, ValueError, OSError):
                    sleep_s = min(sleep_s * 2.0, max_sleep)
                    continue  # trainer mid-write; next poll gets it
                if step > last_step:
                    v = core.publish(params, version=max(version, 1),
                                     tenant=args.tenant)
                    last_step = step
                    sleep_s = base_sleep
                    print(json.dumps({"republished": v,
                                      "checkpoint_step": step}),
                          flush=True)
                else:
                    sleep_s = min(sleep_s * 2.0, max_sleep)
    except KeyboardInterrupt:
        pass
    finally:
        if follower is not None:
            follower.close()
        snap = core.serving_snapshot()
        core.close()
        final = {k: snap[k] for k in ("reads_total", "reads_delta",
                                      "reads_not_modified", "reads_shed",
                                      "coalesce_hits")}
        if follower is not None:
            final["republished"] = follower.republished
            final["replica_lag_versions"] = snap["replica_lag_versions"]
            final["follower_bytes_relayed"] = snap[
                "follower_bytes_relayed"]
        print(json.dumps({"final_serving": final}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
