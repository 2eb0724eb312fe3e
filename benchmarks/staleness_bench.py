"""Staleness → convergence tradeoff for AsySG-InCon.

The algorithm's literature claim (Lian et al. 2015, cited by the
reference ``README.md:56-59``) is a CONVERGENCE statement: bounded
staleness costs convergence quality, bought back by asynchrony's
throughput. This bench makes the tradeoff an artifact:

1. **In-XLA curve** — ``AsyncPS`` sweeps staleness bounds {0,1,2,4,8}
   at MATCHED update counts (same rounds x workers, same lr, same data
   stream, uniform lag sampling up to the bound), recording the eval-
   loss trajectory against applied-update count. Sampling noise is
   averaged over ``--repeats`` seeds.
2. **Shm-fleet ground truth** — real multi-process runs (jitted
   workers, native shm PS) at two bounds, recording the measured
   arrival histogram, applied/dropped counts, and final loss: the
   validation points behind the in-XLA curve (the histogram replay
   test ties the two stacks together).
3. **The verdict** — per bound, the update-count inflation
   ``I(S) = updates_to_target(S) / updates_to_target(0)``. Asynchrony
   nets out ahead iff ``I(S) < measured async/sync throughput gain``
   (2.7x under the forced-straggler bench, ``async_bench.py``): the doc
   section states where that crossover lands.

Run: ``python benchmarks/staleness_bench.py [--rounds 80] [--repeats 3]
[--skip-fleet]`` (CPU-friendly; convergence semantics are backend-
independent).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

BOUNDS = [0, 1, 2, 4, 8]
WORKERS = 4
EVAL_EVERY = 5


def emit(**rec):
    rec.setdefault("backend", jax.default_backend())
    print(json.dumps(rec), flush=True)


def _problem():
    from pytorch_ps_mpi_tpu.parallel.async_train import make_problem

    cfg = {
        "model": "mlp",
        "model_kw": {"features": (32, 4)},
        "in_shape": (8,),
        "batch": 64,
        "seed": 11,
        "optim": "sgd",
        "hyper": {"lr": 0.05},
    }
    _, params0, batch_fn, loss_fn = make_problem(cfg)
    return cfg, params0, batch_fn, loss_fn


def inxla_curve(rounds: int, repeats: int):
    """Mean eval-loss trajectory per staleness bound, matched updates."""
    from pytorch_ps_mpi_tpu.parallel.async_ps import AsyncPS

    cfg, params0, batch_fn, loss_fn = _problem()
    eval_batch = batch_fn(10**6, 10**6)
    eval_loss = jax.jit(loss_fn)

    curves = {}
    for bound in BOUNDS:
        trajs = []
        for rep in range(repeats):
            ps = AsyncPS(
                params0, loss_fn, num_workers=WORKERS, optim="sgd",
                lr=cfg["hyper"]["lr"], max_staleness=bound, seed=100 + rep,
            )
            traj = [(0, float(eval_loss(ps.params, eval_batch)))]
            for step in range(rounds):
                batches = jax.tree.map(
                    lambda *xs: jnp.stack(xs),
                    *[batch_fn(step, w) for w in range(WORKERS)],
                )
                ps.step(batches)
                if (step + 1) % EVAL_EVERY == 0:
                    traj.append(((step + 1) * WORKERS,
                                 float(eval_loss(ps.params, eval_batch))))
            trajs.append(traj)
        updates = [u for u, _ in trajs[0]]
        mean_losses = [
            float(np.mean([t[i][1] for t in trajs]))
            for i in range(len(trajs[0]))
        ]
        curves[bound] = (updates, mean_losses)
        emit(
            metric="staleness_convergence_inxla",
            staleness_bound=bound,
            workers=WORKERS,
            rounds=rounds,
            updates=rounds * WORKERS,
            repeats=repeats,
            lr=cfg["hyper"]["lr"],
            loss_initial=mean_losses[0],
            loss_final=mean_losses[-1],
            trajectory={str(u): round(l, 5)
                        for u, l in zip(updates, mean_losses)},
        )
    return curves


def updates_to_target(curves, target_frac=0.35):
    """Applied updates to reach target_frac * initial loss, per bound
    (linear interpolation on the mean trajectory; None if never)."""
    out = {}
    for bound, (updates, losses) in curves.items():
        target = target_frac * losses[0]
        hit = None
        for i in range(1, len(losses)):
            if losses[i] <= target:
                u0, u1 = updates[i - 1], updates[i]
                l0, l1 = losses[i - 1], losses[i]
                frac = (l0 - target) / max(l0 - l1, 1e-12)
                hit = u0 + frac * (u1 - u0)
                break
        out[bound] = hit
    return out


def fleet_points(bounds=(1, 4)):
    """Real shm-fleet runs: measured arrival staleness + final loss."""
    from pytorch_ps_mpi_tpu.parallel import dcn
    from pytorch_ps_mpi_tpu.parallel.async_train import serve, spawn_worker

    if dcn.get_lib() is None:
        emit(metric="staleness_convergence_fleet",
             skipped="native psqueue unavailable")
        return

    base_cfg, params0, _, _ = _problem()
    steps_per_worker = 40
    for bound in bounds:
        cfg = dict(base_cfg)
        cfg["worker_steps"] = {str(i): steps_per_worker
                               for i in range(WORKERS)}
        # one paced straggler induces real staleness spread
        cfg["slow_ms"] = {str(WORKERS - 1): 40.0}
        name = f"/psq_stale_{bound}_{os.getpid()}"
        server = dcn.ShmPSServer(
            name, num_workers=WORKERS, template=params0, max_staleness=bound,
        )
        try:
            procs = [spawn_worker(name, i, cfg) for i in range(WORKERS)]
            _, m = serve(
                server, cfg, total_grads=0,
                total_received=WORKERS * steps_per_worker, timeout=300.0,
            )
            for p in procs:
                assert p.wait(timeout=120) == 0
        finally:
            server.close()
        emit(
            metric="staleness_convergence_fleet",
            staleness_bound=bound,
            workers=WORKERS,
            pushed=WORKERS * steps_per_worker,
            applied=m["applied"],
            stale_drops=m.get("stale_drops"),
            loss_initial=m["loss_initial"],
            loss_final=m["loss_final"],
            staleness_hist=m["staleness_hist"],
        )


def main():
    # pin the platform HERE, not at import: tests import this module for
    # its pure helpers, and a collection-time config update would pin
    # the whole pytest process to CPU
    jax.config.update("jax_platforms", "cpu")
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=80)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--target-fracs", type=str, default="0.35,0.1,0.03",
                    help="comma list: loss targets as fractions of the "
                         "initial loss (tighter target -> later in the "
                         "curve, where the staleness tax compounds)")
    ap.add_argument("--skip-fleet", action="store_true")
    args = ap.parse_args()

    curves = inxla_curve(args.rounds, args.repeats)
    # the throughput gain asynchrony buys (measured under a forced
    # straggler, benchmarks/async_bench.py + committed artifact)
    measured_gain = 2.7
    for frac in [float(f) for f in args.target_fracs.split(",")]:
        utt = updates_to_target(curves, frac)
        base = utt.get(0)
        inflation = {
            str(b): ((u / base) if (u and base) else None)
            for b, u in utt.items()
        }
        emit(
            metric="staleness_convergence_verdict",
            target_frac=frac,
            updates_to_target={str(b): (round(u, 1) if u else None)
                               for b, u in utt.items()},
            update_inflation_vs_sync={
                b: (round(i, 3) if i is not None else None)
                for b, i in inflation.items()
            },
            async_throughput_gain_measured=measured_gain,
            nets_out_ahead={
                b: (i is not None and i < measured_gain)
                for b, i in inflation.items()
            },
            note=(
                "asynchrony wins end-to-end at bound S iff its update-"
                "count inflation I(S) stays under the measured "
                "throughput gain (2.7x, forced-straggler A/B); I(S) "
                "from the mean in-XLA curve at matched update counts"
            ),
        )
    if not args.skip_fleet:
        fleet_points()


if __name__ == "__main__":
    main()
