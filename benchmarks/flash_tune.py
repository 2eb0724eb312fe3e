"""Flash-attention block-size sweep vs the XLA dense path.

Measures attention-only fwd+bwd device time (``utils/devtime.timed``)
for BERT-base head geometry (h=12, d=64) across
sequence lengths and (block_q, block_k) choices, against the fused-dense
einsum oracle XLA compiles for the same shapes. This is the measurement
behind the ``full``-attention dispatch policy in ``models/bert.py``: the
dense path owns short sequences (its matmuls batch perfectly on the MXU
and the O(L^2) scores still fit HBM traffic comfortably); the flash
kernel must EARN the dispatch at the crossover where score
materialization starts to dominate.

Run on a TPU: ``python benchmarks/flash_tune.py [--quick]``.
One JSON line per (seq, config), then a summary line per seq.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.ops.attention_pallas import (
    _attention_jnp,
    flash_attention,
)
from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache
from pytorch_ps_mpi_tpu.utils.devtime import timed


def emit(**rec):
    rec.setdefault("backend", jax.default_backend())
    print(json.dumps(rec), flush=True)


def bench_one(fn, q, k, v, reps: int = 5) -> float:
    """Device seconds per fwd+bwd of ``fn(q, k, v) -> [b, l, h, d]``."""

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    return timed(lambda: grad(q, k, v), reps=reps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewest configs: one block choice per seq")
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=64)
    args = ap.parse_args()
    enable_compilation_cache()

    h, d = args.heads, args.head_dim
    # token budget ~constant: b*l = 16k; s1024 sits ON the default-tier
    # boundary (_default_block_targets switches at 1024), so its row
    # decides the boundary rather than bracketing it
    cases = [(128, 128), (32, 512), (16, 1024), (8, 2048), (2, 8192)]
    blocks = [(128, 128)] if args.quick else [
        (128, 128), (128, 256), (256, 256), (128, 512), (256, 512),
        (512, 512), (256, 1024), (512, 1024),
    ]

    for b, l in cases:
        key = jax.random.key(l)
        mk = lambda i: jax.random.normal(
            jax.random.fold_in(key, i), (b, l, h, d), jnp.bfloat16
        )
        q, k, v = mk(0), mk(1), mk(2)

        # the dense path can legitimately die at the long end (f32 scores
        # b*h*l*l ~ 6.4 GB at s8192 + backward), and a tile that does
        # not fit VMEM fails to compile: in a sweep over sizes each such
        # failure IS a data point, reported in its own row
        try:
            dense_s = bench_one(
                lambda q, k, v: _attention_jnp(
                    q, k, v, 0, 0, True, d ** -0.5)[0],
                q, k, v,
            )
            emit(metric="attn_fwd_bwd_ms", seq=l, batch=b,
                 config="dense-einsum", value=round(dense_s * 1e3, 3))
        except Exception as e:
            dense_s = None
            emit(metric="attn_fwd_bwd_ms", seq=l, batch=b,
                 config="dense-einsum",
                 error=f"{type(e).__name__}: {str(e)[:160]}")

        best = None
        for bq, bk in blocks:
            if bq > l or bk > l:
                continue
            fa = functools.partial(
                flash_attention, causal=True, block_q=bq, block_k=bk
            )
            try:
                dev_s = bench_one(fa, q, k, v)
            except Exception as e:
                emit(metric="attn_fwd_bwd_ms", seq=l, batch=b,
                     config=f"flash-{bq}x{bk}",
                     error=f"{type(e).__name__}: {str(e)[:160]}")
                continue
            emit(metric="attn_fwd_bwd_ms", seq=l, batch=b,
                 config=f"flash-{bq}x{bk}", value=round(dev_s * 1e3, 3))
            if best is None or dev_s < best[1]:
                best = ((bq, bk), dev_s)

        if best:
            verdict = ("dense errored" if dense_s is None
                       else bool(best[1] < dense_s))
            emit(metric="attn_crossover_summary", seq=l, batch=b,
                 dense_ms=(round(dense_s * 1e3, 3)
                           if dense_s is not None else None),
                 best_flash_ms=round(best[1] * 1e3, 3),
                 best_block=f"{best[0][0]}x{best[0][1]}",
                 flash_wins=verdict)


if __name__ == "__main__":
    main()
