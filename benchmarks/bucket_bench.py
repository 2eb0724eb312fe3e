"""Flat-bucket aggregation sweep: collective launch count + step ms over
``bucket_mb ∈ {0, 4, 16, 32}`` on resnet18- and bert-base-shaped trees.

Two measurements per (model, bucket_mb) point:

- **launch count** — collective ops in the LOWERED grads-only step
  (``bucketing.lowered_collective_counts``; abstract args, nothing is
  executed, so the 110M-param bert tree costs only a trace). This is the
  per-message-overhead quantity bucketing exists to shrink, and the
  number the acceptance gate checks (≥ 5× fewer launches at 16 MB on
  bert-base).
- **step ms** — wall time of the executed aggregation+update step, for
  the resnet18-size tree by default (the bert tree is ~3.5 GB of stacked
  per-worker gradients on a CPU host; pass ``--run-bert`` to time it on
  real hardware).

Runs on the devices JAX initialises and fails below two (aggregation
needs a mesh); for the host-CPU form: ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Emits one JSON line per point (benchmarks/results/ schema: metric /
value / unit / backend + sweep fields).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_ps_mpi_tpu.bucketing import lowered_collective_counts
from pytorch_ps_mpi_tpu.ps import SGD

SWEEP_MB = (0, 4, 16, 32)
REPS = 5


def resnet18_tree():
    """~60 tensors, ~11M f32 elements (the leader_bench shape census)."""
    n = 11_000_000
    sizes = [n // 60] * 59 + [n - 59 * (n // 60)]
    return {f"p{i}": jnp.zeros((s,), jnp.float32) for i, s in enumerate(sizes)}


def bert_base_tree():
    """BERT-base shape census: ~199 leaves, ~110M params, f32."""
    H, FF, L = 768, 3072, 12
    t = {
        "embed/word": (30522, H),
        "embed/pos": (512, H),
        "embed/type": (2, H),
        "embed/ln_g": (H,),
        "embed/ln_b": (H,),
    }
    for i in range(L):
        p = f"layer{i}"
        t.update({
            f"{p}/q_w": (H, H), f"{p}/q_b": (H,),
            f"{p}/k_w": (H, H), f"{p}/k_b": (H,),
            f"{p}/v_w": (H, H), f"{p}/v_b": (H,),
            f"{p}/attn_out_w": (H, H), f"{p}/attn_out_b": (H,),
            f"{p}/ln1_g": (H,), f"{p}/ln1_b": (H,),
            f"{p}/ffn_in_w": (H, FF), f"{p}/ffn_in_b": (FF,),
            f"{p}/ffn_out_w": (FF, H), f"{p}/ffn_out_b": (H,),
            f"{p}/ln2_g": (H,), f"{p}/ln2_b": (H,),
        })
    t.update({"pooler/w": (H, H), "pooler/b": (H,)})
    return {k: jnp.zeros(s, jnp.float32) for k, s in t.items()}


def grad_structs(params, world):
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((world,) + p.shape, p.dtype), params
    )


def launch_counts(params, world, bucket_mb, mode):
    opt = SGD(params, lr=0.1, mode=mode, bucket_mb=bucket_mb)
    fn = opt._build_grads_only_step()
    return lowered_collective_counts(
        fn, opt.params, opt.opt_state, opt.codec_state,
        grad_structs(params, world), jax.random.key(0),
    ), opt


def timed_step_ms(opt, grads):
    opt.step(grads=grads)  # compile + warm
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        opt.step(grads=grads)
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e3)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--run-bert", action="store_true",
                    help="also EXECUTE the bert-base step (3.5 GB of "
                         "stacked grads; launch counts are always taken)")
    ap.add_argument("--modes", default="allgather,leader")
    args = ap.parse_args()
    world = len(jax.devices())
    backend = jax.default_backend()
    if world < 2:
        raise SystemExit(
            f"bucket_bench: {world} {backend} device: aggregation needs a "
            "multi-device mesh (>= 2)")
    modes = args.modes.split(",")

    for model, make, execute in (
        ("resnet18", resnet18_tree, True),
        ("bert-base", bert_base_tree, args.run_bert),
    ):
        params = make()
        n_leaves = len(jax.tree.leaves(params))
        grads = None
        if execute:
            grads = jax.tree.map(
                lambda p: jnp.zeros((world,) + p.shape, p.dtype), params
            )
        for mode in modes:
            base_total = None
            for mb in SWEEP_MB:
                counts, opt = launch_counts(params, world, mb, mode)
                if mb == 0:
                    base_total = counts["total"]
                row = {
                    "metric": f"{model}_bucket_agg_{mode}",
                    "unit": "collective launches",
                    "value": counts["total"],
                    "bucket_mb": mb,
                    "buckets": (opt._bucket_plan.num_buckets
                                if opt._bucket_plan else 0),
                    "leaves": n_leaves,
                    "all_reduce": counts["all_reduce"],
                    "all_gather": counts["all_gather"],
                    "reduce_scatter": counts["reduce_scatter"],
                    "launch_reduction_x": round(
                        base_total / counts["total"], 2
                    ) if base_total else 1.0,
                    "workers": world,
                    "backend": backend,
                    "device_kind": jax.devices()[0].device_kind,
                }
                if execute:
                    row["step_ms"] = round(timed_step_ms(opt, grads), 3)
                print(json.dumps(row), flush=True)
                del opt


if __name__ == "__main__":
    main()
