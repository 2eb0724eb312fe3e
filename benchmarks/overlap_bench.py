"""Compute/communication overlap of the fused data-parallel step.

The reference's signature design is overlapping encode/serialize/comm
with backprop via autograd hooks feeding a 200-thread pool
(``/root/reference/ps.py:65-66,85``). This framework's claim is that the
fused ``MPI_PS.step`` program lets XLA's scheduler do the same job —
this bench stops taking that on faith: it traces the fused ResNet-18
data-parallel train step and measures, from event timelines, how much of
the collective's execution interval actually rides under backward
compute (``utils.tracing.profiled_overlap``), under XLA's default
scheduler.

Overlap needs collectives, and collectives need >1 device: the bench
runs on the devices JAX initialises and fails below two. On a four-chip
host it measures the real ICI overlap; for the host-CPU form run it
under ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8`` (XLA:CPU
collectives are synchronous, so that number bounds nothing about a
chip). Every line names the platform it ran on.

Output: one JSON line; append to ``benchmarks/results/`` for the record.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BATCH = 256


def main() -> None:
    """Trace one fused DP train step on this process's backend."""
    import jax
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.mesh import make_mesh
    from pytorch_ps_mpi_tpu.models import ResNet18
    from pytorch_ps_mpi_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )
    from pytorch_ps_mpi_tpu.utils.tracing import profiled_overlap

    enable_compilation_cache()
    dev = jax.devices()[0]
    n_dev = jax.device_count()
    if n_dev < 2:
        raise SystemExit(
            f"overlap_bench: {n_dev} {dev.platform} device: no collective "
            "to trace (needs >= 2)")
    rec = {
        "metric": "resnet18_dp_step_comm_compute_overlap",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "devices": n_dev,
        "batch": BATCH,
        "unit": "fraction of collective time under compute",
    }

    model = ResNet18(num_classes=10, small_inputs=True)
    x = jax.random.normal(jax.random.key(1), (BATCH, 32, 32, 3))
    y = jax.random.randint(jax.random.key(2), (BATCH,), 0, 10)
    params = jax.jit(model.init)(jax.random.key(0), x[:1])

    def loss_fn(p, batch):
        xb, yb = batch
        logits = model.apply(p, xb)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=1))

    opt = SGD(params, mesh=make_mesh(), lr=0.01, momentum=0.9)
    opt.step(loss_fn=loss_fn, batch=(x, y))  # compile + warm
    _, split = profiled_overlap(
        lambda: opt.step(loss_fn=loss_fn, batch=(x, y))
    )
    rec.update({k: round(v, 6) if isinstance(v, float) else v
                for k, v in split.items()})
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
