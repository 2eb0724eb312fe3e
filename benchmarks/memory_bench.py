"""Peak-HBM measurement for ``donate_buffers``.

``MPI_PS(donate_buffers=True)`` claims an in-place update cuts peak HBM
by roughly one params+opt-state copy (``ps.py`` docstring: ~2 GB at
BERT-base/Adam scale). This bench MEASURES it: each config runs in a
fresh subprocess (PJRT's ``peak_bytes_in_use`` is cumulative per
process, so a fresh process is the only honest per-config peak) that
takes 3 fused BERT-base MLM Adam steps on the accelerator and reports
the device's peak allocation. A chip belongs to one process at a time,
so the parent never imports jax: the children own the chip one after
another.

Run on a TPU: ``python benchmarks/memory_bench.py``; emits one row per
config plus a summary with the measured savings. A backend that reports
no device memory stats (XLA:CPU) fails the run.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIGS = [
    {"donate": False, "remat": False},
    {"donate": True, "remat": False},
    # remat rides along: activation memory traded for recompute — the
    # other HBM lever, measured under the same protocol
    {"donate": True, "remat": True},
]


def run_one(donate: bool, remat: bool, batch: int, seq: int) -> None:
    """Subprocess body: 3 fused steps, then print peak HBM JSON."""
    import jax
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu import Adam
    from pytorch_ps_mpi_tpu.models.bert import BertConfig, BertMLM, mlm_loss
    from pytorch_ps_mpi_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    cfg = BertConfig(dtype=jnp.bfloat16, max_position=max(512, seq),
                     remat=remat)
    model = BertMLM(cfg)
    key = jax.random.key(1)
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    targets = jax.random.randint(jax.random.fold_in(key, 1), (batch, seq),
                                 0, cfg.vocab_size)
    mask = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.15,
                                (batch, seq))
    params = jax.jit(model.init)(jax.random.key(0), tokens[:1])

    def loss_fn(p, b):
        t, tg, m = b
        return mlm_loss(model.apply(p, t), tg, m)

    opt = Adam(params, lr=1e-4, donate_buffers=donate)
    del params  # donation demands no outside reference
    for _ in range(3):
        loss, _ = opt.step(loss_fn=loss_fn, batch=(tokens, targets, mask))
    jax.block_until_ready(opt.params)
    dev = jax.devices()[0]
    stats = dev.memory_stats()
    if not stats or stats.get("peak_bytes_in_use") is None:
        raise SystemExit(
            f"memory_bench: platform {dev.platform!r} ({dev.device_kind!r}) "
            "reports no peak_bytes_in_use; nothing was measured")
    print(json.dumps({
        "metric": "bert_base_adam_peak_hbm_bytes",
        "donate_buffers": donate,
        "remat": remat,
        "batch": batch,
        "seq": seq,
        "value": stats["peak_bytes_in_use"],
        "unit": "bytes",
        "bytes_in_use_after": stats.get("bytes_in_use"),
        "largest_alloc": stats.get("largest_alloc_size"),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "loss_finite": bool(jnp.isfinite(loss)),
    }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--one", type=str, default=None,
                    help="internal: run one config json in-process")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    if args.one is not None:
        cfg = json.loads(args.one)
        run_one(cfg["donate"], cfg["remat"], args.batch, args.seq)
        return

    rows = []
    for cfg in CONFIGS:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--one", json.dumps(cfg),
             "--batch", str(args.batch), "--seq", str(args.seq)],
            stdout=subprocess.PIPE, text=True, timeout=900, check=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for line in out.stdout.splitlines():
            if line.startswith("{"):
                print(line, flush=True)
                rows.append(json.loads(line))

    peaks = {(r["donate_buffers"], r["remat"]): r["value"] for r in rows}
    saved = peaks[(False, False)] - peaks[(True, False)]
    print(json.dumps({
        "metric": "donate_buffers_peak_hbm_saving_bytes",
        "value": saved,
        "unit": "bytes",
        "saved_gb": round(saved / 2 ** 30, 3),
        "peak_no_donate": peaks[(False, False)],
        "peak_donate": peaks[(True, False)],
        "peak_donate_remat": peaks[(True, True)],
        "platform": rows[0]["platform"],
        "device_kind": rows[0]["device_kind"],
    }), flush=True)


if __name__ == "__main__":
    main()
