"""Transport overhead: the SAME async training job over shm vs TCP.

Identical worker fleets (real jitted compute, no stragglers), identical
server loop; the only variable is the wire — native shared memory
(``parallel/dcn.py``) vs native TCP over localhost (``parallel/tcp.py``).
The updates/sec ratio is the transport tax a single-host deployment pays
for choosing the cross-host-capable wire; across real hosts TCP is the
only option and the number to compare is the reference's MPI-over-
Ethernet throughput (which shipped pickled full-f32 buffers — here the
codec keeps payloads small either way).

Honest labeling: single-core host, absolute rates meaningless, the
RATIO between the two runs (same machine, same contention) is the
evidence.

Run: ``python benchmarks/transport_bench.py [--model mlp] [--workers 3]``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # protocol bench: host only

from async_bench import run as run_job  # the one server-lifecycle harness
from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache
from pytorch_ps_mpi_tpu.utils.devtime import safe_ratio

enable_compilation_cache()


def run(transport: str, cfg, n_workers: int, total: int, code):
    cfg = dict(cfg)
    if transport == "tcp":
        cfg["transport"] = "tcp"
    else:
        cfg.pop("transport", None)
    return run_job(cfg, n_workers, sync_barrier=False, total=total, code=code)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="mlp")
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--codec", default="sign")
    ap.add_argument("--rounds", type=int, default=3,
                    help="alternating shm/tcp rounds; medians reported")
    args = ap.parse_args()

    cfg = {
        "model": args.model,
        "model_kw": ({"features": (64, 8)} if args.model == "mlp"
                     else {"num_classes": 10}),
        "in_shape": [8] if args.model == "mlp" else [32, 32, 3],
        "batch": args.batch,
        "seed": 0,
        "optim": "sgd",
        "hyper": {"lr": 0.02},
        "steps": args.steps,
        "open_timeout": 600.0,
        "push_timeout": 600.0,
    }
    if args.codec and args.codec != "identity":
        cfg["codec"] = args.codec
        cfg["codec_kw"] = ({"use_pallas": False} if args.codec == "sign"
                           else {})

    from statistics import median

    from pytorch_ps_mpi_tpu.codecs import get_codec

    code = (get_codec(args.codec, **cfg.get("codec_kw", {}))
            if "codec" in cfg else None)
    total = args.workers * args.steps

    # alternate A/B rounds so slow load drift hits both transports
    # equally; report medians (single runs swung 0.77x-1.06x on this
    # loaded 1-core host)
    shm_rates, tcp_rates = [], []
    m_shm = m_tcp = None
    for _ in range(args.rounds):
        m_shm = run("shm", cfg, args.workers, total, code)
        shm_rates.append(m_shm["updates_per_sec"])
        m_tcp = run("tcp", cfg, args.workers, total, code)
        tcp_rates.append(m_tcp["updates_per_sec"])

    ratio = round(safe_ratio(median(tcp_rates), median(shm_rates)), 3)
    print(json.dumps({
        "metric": f"{args.model}_async_tcp_vs_shm_updates_per_sec_ratio",
        "value": ratio,
        "unit": "x (1.0 = no transport tax)",
        "vs_baseline": ratio,
        "shm_updates_per_sec_median": round(median(shm_rates), 3),
        "tcp_updates_per_sec_median": round(median(tcp_rates), 3),
        "shm_rates": [round(r, 3) for r in shm_rates],
        "tcp_rates": [round(r, 3) for r in tcp_rates],
        "shm_loss_final": round(m_shm["loss_final"], 4),
        "tcp_loss_final": round(m_tcp["loss_final"], 4),
        "rounds": args.rounds,
        "workers": args.workers,
        "codec": args.codec,
        "wire_bytes_per_grad": m_tcp["wire_bytes_per_grad"],
        "backend": "cpu (protocol bench; single-core localhost, the "
                   "shm-vs-tcp RATIO is the evidence)",
    }, ensure_ascii=False), flush=True)


if __name__ == "__main__":
    main()
