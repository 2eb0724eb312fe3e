"""Async PS behavior at WAN-like RTT.

Every multi-host artifact so far ran its sockets over bare loopback
(~0.05 ms RTT) — nothing like the reference's cluster deployment
(`/root/reference/README.md:19-23`). This kernel has no netem qdisc, so
the TCP transport carries its own WAN emulation (``native/tcpps.cpp``:
``TPS_WAN_RTT_MS`` / ``TPS_WAN_JITTER_MS``, worker-side propagation
delays). This bench sweeps RTT in {0, 5, 20, 50} ms (+ jitter at the
top point) over the REAL multi-process TCP fleet and records, per RTT:

- the async-vs-sync-barrier update-rate ratio under a forced straggler
  (does asynchrony's win survive when every message pays the WAN tax?);
- the measured arrival-staleness histogram (bounded staleness under
  latency: lags grow with RTT, the bound still caps them);
- the live wire compression ratio with the sign codec (server-counted
  bytes — DCN doctrine at WAN RTT).

Run: ``python benchmarks/wan_bench.py [--workers 4]`` (CPU protocol
bench; absolute rates are single-core-host numbers, the RATIOS and
histograms are the evidence).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

from benchmarks.async_bench import run
from pytorch_ps_mpi_tpu.codecs import get_codec
from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache
from pytorch_ps_mpi_tpu.utils.devtime import safe_ratio

enable_compilation_cache()

RTTS_MS = [0.0, 5.0, 20.0, 50.0]


def emit(**rec):
    rec.setdefault(
        "backend",
        "cpu (protocol bench; ratios/histograms are the evidence)",
    )
    print(json.dumps(rec), flush=True)


def set_wan(rtt_ms: float, jitter_ms: float = 0.0) -> None:
    """Spawned workers inherit the parent env; the server side of the
    shim never sleeps, so setting it here affects exactly the worker-
    side propagation paths."""
    os.environ["TPS_WAN_RTT_MS"] = str(rtt_ms)
    os.environ["TPS_WAN_JITTER_MS"] = str(jitter_ms)


def sweep_point(rtt_ms: float, jitter_ms: float, w: int,
                fast_steps: int, slow_steps: int, slow_ms: float):
    set_wan(rtt_ms, jitter_ms)
    base = {
        "transport": "tcp",
        "model": "mlp",
        "model_kw": {"features": (64, 8)},
        "in_shape": (16,),
        "batch": 32,
        "seed": 5,
        "optim": "sgd",
        "hyper": {"lr": 0.02},
        "slow_ms": {str(w - 1): slow_ms},
        "open_timeout": 600.0,
        "push_timeout": 600.0,
    }

    sync_cfg = dict(base)
    sync_cfg["worker_steps"] = {str(i): slow_steps for i in range(w)}
    m_sync = run(sync_cfg, w, sync_barrier=True, total=w * slow_steps)

    async_cfg = dict(base)
    async_cfg["worker_steps"] = {
        **{str(i): fast_steps for i in range(w - 1)},
        str(w - 1): slow_steps,
    }
    m_async = run(
        async_cfg, w, sync_barrier=False,
        total=(w - 1) * fast_steps + slow_steps, max_staleness=8,
    )

    # sign-codec wire at this RTT (server-counted bytes). Workers read
    # the codec from cfg ("codec"/"codec_kw"); the server gets the
    # matching instance via run(code=...)
    codec_cfg = dict(async_cfg)
    codec_cfg["codec"] = "sign"
    codec_cfg["codec_kw"] = {"use_pallas": False}
    m_codec = run(
        codec_cfg, w, sync_barrier=False,
        total=(w - 1) * fast_steps + slow_steps, max_staleness=8,
        code=get_codec("sign", use_pallas=False),
    )

    ratio = round(
        safe_ratio(m_async["updates_per_sec"], m_sync["updates_per_sec"]), 2
    )
    emit(
        metric="wan_async_vs_sync_updates_per_sec_ratio",
        value=ratio,
        unit="x",
        rtt_ms=rtt_ms,
        jitter_ms=jitter_ms,
        workers=w,
        straggler_ms=slow_ms,
        async_updates_per_sec=round(m_async["updates_per_sec"], 3),
        sync_updates_per_sec=round(m_sync["updates_per_sec"], 3),
        async_loss_final=round(m_async["loss_final"], 4),
        sync_loss_final=round(m_sync["loss_final"], 4),
        async_staleness_hist=m_async["staleness_hist"],
        async_stale_drops=m_async.get("stale_drops"),
        sign_codec_compression_ratio=round(
            m_codec.get("compression_ratio", 0.0), 2),
        sign_codec_loss_final=round(m_codec["loss_final"], 4),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--fast-steps", type=int, default=12)
    ap.add_argument("--slow-steps", type=int, default=3)
    ap.add_argument("--slow-ms", type=float, default=500.0)
    args = ap.parse_args()

    try:
        for rtt in RTTS_MS:
            sweep_point(rtt, 0.0, args.workers, args.fast_steps,
                        args.slow_steps, args.slow_ms)
        # jittered top point: WAN tails, not just mean latency
        sweep_point(RTTS_MS[-1], 20.0, args.workers, args.fast_steps,
                    args.slow_steps, args.slow_ms)
    finally:
        set_wan(0.0, 0.0)


if __name__ == "__main__":
    main()
