"""Shared constants/predicates for the Pallas kernel family, and what a
layer's checkpoint keeps."""

from __future__ import annotations

import jax
from jax.ad_checkpoint import checkpoint_name

from pytorch_ps_mpi_tpu.telemetry.recorder import setup_event

LANE = 128      # TPU lane width (last-dim tile)
SUBLANE = 8     # float32 sublane tile

# What a layer's checkpoint saves: values dear to recompute and small to
# hold, each named by the operator that produces it (``keep``). The flash
# forward kernel's output and per-row logsumexp (a second run of the
# kernel otherwise, only to hand them to the backward kernel), and the
# expert layer's plan (two sorts and a handful of int32 vectors).
KEPT = ("flash.out", "flash.lse", "moe.plan")


def interpret() -> bool:
    """Run kernels in Pallas interpret mode off-TPU (CPU test meshes)."""
    return jax.default_backend() != "tpu"


def keep(x: jax.Array, name: str) -> jax.Array:
    """``x`` under ``name`` (one of ``KEPT``): inside ``checkpoint_layer``
    the backward pass reads the saved array, and what only produced it
    falls to dead-code elimination; outside any checkpoint a name is the
    identity and lowers to nothing. One set-up log row ``remat.keep``
    (``kept``, ``shape``, ``dtype``, ``bytes``) a value named, each time
    its layer is traced (layers that share a function and shapes share
    one trace)."""
    assert name in KEPT, name
    setup_event("remat.keep", kept=name, shape=list(x.shape),
                dtype=str(x.dtype), bytes=x.size * x.dtype.itemsize)
    return checkpoint_name(x, name)


def checkpoint_layer(fn, **kw):
    """``jax.checkpoint(fn)`` that saves the ``KEPT`` names and recomputes
    everything else (the matrix products are cheap to redo)."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(*KEPT), **kw)
