"""ResNet-50 async (AsySG shm PS) vs synchronous-barrier PS — BASELINE
config #3's async/straggler story, measured.

Same worker fleet both times (real jitted ResNet-50 fwd/bwd in every
worker process — no closed-form gradients anywhere), one deliberate
straggler. The synchronous PS applies one gradient from EVERY worker per
round, so its update rate is paced by the straggler; AsySG applies each
gradient on arrival, so fast workers keep streaming. The measured ratio
is the wall-clock benefit asynchrony exists for (Lian et al. 2015).

Honest labeling: this host is a single CPU core driving N worker
processes, so absolute steps/sec are meaningless — the async/sync RATIO
under an injected straggler is the evidence (and the protocol is
host-side by design; the device compute inside each worker is whatever
JAX backend the worker runs).

Run: ``python benchmarks/async_bench.py [--workers 4] [--batch 2]``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # protocol bench: never touch the TPU

from pytorch_ps_mpi_tpu.parallel import dcn
from pytorch_ps_mpi_tpu.parallel.async_train import (
    join_workers,
    make_problem,
    serve,
    spawn_worker,
)
from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache

enable_compilation_cache()


def run(cfg, n_workers: int, sync_barrier: bool, total: int, code=None,
        max_staleness: int = 10**9):
    """One complete async job: server (shm or tcp per ``cfg['transport']``)
    + spawned jitted workers + serve loop + cleanup. The ONE server-
    lifecycle harness every protocol bench uses (transport_bench imports
    it) — fixes to worker-exit handling or cleanup land everywhere."""
    _, params0, _, _ = make_problem(cfg)
    if cfg.get("transport") == "tcp":
        from pytorch_ps_mpi_tpu.parallel import tcp

        server = tcp.TcpPSServer(
            0, num_workers=n_workers, template=params0,
            max_staleness=max_staleness, code=code,
        )
        name = f"127.0.0.1:{server.port}"
    else:
        name = f"/psq_bench_{os.getpid()}_{int(sync_barrier)}"
        server = dcn.ShmPSServer(
            name, num_workers=n_workers, template=params0,
            max_staleness=max_staleness, code=code,
        )
    procs = []
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(n_workers)]
        _, m = serve(server, cfg, total_grads=0, total_received=total,
                     sync_barrier=sync_barrier, timeout=3600.0)
        for rc in join_workers(procs, timeout=600.0):
            if rc != 0:
                raise RuntimeError(f"worker exited {rc}")
    finally:
        server.close()
        join_workers(procs, timeout=5.0)  # failure path: reap, don't leak
    return m


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--fast-steps", type=int, default=8)
    ap.add_argument("--slow-steps", type=int, default=2)
    ap.add_argument("--slow-ms", type=float, default=4000.0)
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--transport", default="shm", choices=["shm", "tcp"],
                    help="PS wire: shm (co-hosted) or tcp (the cross-host "
                         "DCN-role transport, here over localhost)")
    args = ap.parse_args()

    w = args.workers
    base = {
        "transport": args.transport,
        "model": args.model,
        "model_kw": {"num_classes": 10},
        "in_shape": (32, 32, 3),
        "batch": args.batch,
        "seed": 5,
        "optim": "sgd",
        # per-arrival updates (no averaging) need a cooler rate than a
        # synchronous sweep or the ResNet-50 loss visibly diverges
        "hyper": {"lr": 1e-4},
        "slow_ms": {str(w - 1): args.slow_ms},
        "open_timeout": 600.0,
        "push_timeout": 600.0,
    }

    # sync barrier: every worker contributes to every round, so all push
    # the same count; async: fast workers stream while the straggler naps
    sync_cfg = dict(base)
    sync_cfg["worker_steps"] = {str(i): args.slow_steps for i in range(w)}
    m_sync = run(sync_cfg, w, sync_barrier=True, total=w * args.slow_steps)

    async_cfg = dict(base)
    async_cfg["worker_steps"] = {
        **{str(i): args.fast_steps for i in range(w - 1)},
        str(w - 1): args.slow_steps,
    }
    m_async = run(
        async_cfg, w, sync_barrier=False,
        total=(w - 1) * args.fast_steps + args.slow_steps,
    )

    from pytorch_ps_mpi_tpu.utils.devtime import safe_ratio

    ratio = round(
        safe_ratio(m_async["updates_per_sec"], m_sync["updates_per_sec"]), 2
    )  # 0.0 = "sync run applied nothing before its deadline; not measured"
    print(json.dumps({
        "metric": f"{args.model}_async_vs_syncbarrier_updates_per_sec_ratio",
        "value": ratio,
        "unit": "x",
        "vs_baseline": ratio,
        "async_updates_per_sec": round(m_async["updates_per_sec"], 3),
        "sync_updates_per_sec": round(m_sync["updates_per_sec"], 3),
        "async_loss": round(m_async["loss_final"], 4),
        "sync_loss": round(m_sync["loss_final"], 4),
        # the staleness half of the tradeoff the ratio buys (canonical
        # schema quantiles — what the ps_staleness_p* gauges export)
        "async_staleness_p50": m_async["staleness_p50"],
        "async_staleness_p95": m_async["staleness_p95"],
        "async_staleness_p99": m_async["staleness_p99"],
        "workers": w,
        "transport": args.transport,
        "straggler_ms": args.slow_ms,
        "backend": "cpu (protocol bench; single-core host, ratio is the "
                   "evidence, absolute rates are not)",
    }, ensure_ascii=False), flush=True)


if __name__ == "__main__":
    main()
