"""Functional sync data-parallel train step.

The same pipeline ``MPI_PS.step`` runs (grad → encode → collective →
decode+sum → fused update), exposed as a pure function builder for users
who want explicit state threading instead of the optimizer object — the
idiomatic-JAX face of the reference's ``step`` engine (``ps.py:103-193``).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_ps_mpi_tpu.bucketing import exchange_plan
from pytorch_ps_mpi_tpu.codecs import Codec, IdentityCodec
from pytorch_ps_mpi_tpu.mesh import DATA_AXIS
from pytorch_ps_mpi_tpu.optim import OPTIMIZERS
from pytorch_ps_mpi_tpu.ps import (
    aggregate,
    bucketed_aggregate,
    encode_tree,
    fused_allreduce_tree,
    leader_init_state,
    leader_scatter_shards,
    leader_shard_update,
    leader_slice_shards,
    leader_state_spec,
)

PyTree = Any


def make_sync_train_step(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    mesh: Mesh,
    *,
    optim: str = "sgd",
    code: Optional[Codec] = None,
    axis_name: str = DATA_AXIS,
    mode: str = "allgather",
    average: bool = False,
    donate: bool = True,
    bucket_mb: float = 0.0,
    **hyper,
):
    """Build ``(init_fn, step_fn)``.

    ``init_fn(params) -> (opt_state, codec_state)``;
    ``step_fn(params, opt_state, codec_state, batch, rng) ->
    (params, opt_state, codec_state, loss)`` — one fused XLA program,
    batch sharded over ``axis_name``, params replicated.

    ``bucket_mb > 0`` runs a payload codec's exchange (sign, int8, qsgd,
    terngrad, f16, randomk's fraction form) on dtype-grouped flat buckets
    for ``mode='allgather'``: one encode, all-gather and decode a bucket,
    as ``bucketing.exchange_plan`` decides for ``MPI_PS``. Psum codecs
    (identity, bf16) keep one psum a leaf, which the compiler groups.
    The functional leader mode keeps the per-leaf path (its ZeRO-1 state
    layout is built by ``init_fn`` per leaf); use ``MPI_PS(mode='leader',
    bucket_mb=...)`` for bucket-sharded ZeRO-1.
    """
    code = code if code is not None else IdentityCodec()
    hyper_cls, init_state, update_fn = OPTIMIZERS[optim]
    h = hyper_cls(**hyper)
    size = int(mesh.shape[axis_name])

    def init_fn(params):
        def leaf(p):
            s = code.init_state(p.shape, p.dtype)
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (size,) + x.shape), s
            )
        codec_state = jax.tree.map(leaf, params)
        if mode == "leader":
            # ZeRO-1: master param shards + sharded inner state (see
            # ps.LeaderState); the step all-gathers fresh replicated params
            return leader_init_state(params, init_state, size), codec_state
        return init_state(params), codec_state

    def spmd(params, opt_state, codec_state, batch, rng):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = lax.pmean(loss, axis_name)
        plan = (exchange_plan(code, grads, bucket_mb)
                if mode == "allgather" else None)
        if plan is not None:
            # one collective per dtype-grouped flat bucket; the codec is
            # stateless by the bucketable contract, state passes through
            summed = bucketed_aggregate(
                code, grads, plan, axis_name, average, size, rng=rng
            )
            new_params, new_opt_state = update_fn(params, summed, opt_state, h)
            return new_params, new_opt_state, codec_state, loss
        if code.supports_fused_allreduce:
            # collective-protocol codec (PowerSGD two-psum): aggregation
            # IS the codec — same lowering as MPI_PS's fused step
            summed, new_codec_state = fused_allreduce_tree(
                code, grads, codec_state, axis_name, average, size
            )
        else:
            payloads, new_codec_state = encode_tree(
                code, grads, codec_state, rng, axis_name
            )
            summed = None
        if mode == "leader":
            if summed is not None:
                grad_shards = leader_slice_shards(summed, axis_name, size)
            elif code.supports_psum:
                grad_shards = leader_scatter_shards(
                    grads, axis_name, size,
                    getattr(code, "wire_dtype", None), average,
                )
            else:
                summed = aggregate(code, grads, payloads, axis_name, average, size)
                grad_shards = leader_slice_shards(summed, axis_name, size)
            new_params, new_opt_state = leader_shard_update(
                params, opt_state, grad_shards, update_fn, h, axis_name
            )
        else:
            if summed is None:
                summed = aggregate(code, grads, payloads, axis_name, average, size)
            new_params, new_opt_state = update_fn(params, summed, opt_state, h)
        return new_params, new_opt_state, new_codec_state, loss

    def step_fn(params, opt_state, codec_state, batch, rng):
        state_spec = jax.tree.map(lambda _: P(axis_name), codec_state)
        opt_spec = (
            leader_state_spec(opt_state, axis_name) if mode == "leader" else P()
        )
        mapped = jax.shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(), opt_spec, state_spec, P(axis_name), P()),
            out_specs=(P(), opt_spec, state_spec, P()),
            check_vma=False,
        )
        return mapped(params, opt_state, codec_state, batch, rng)

    return init_fn, jax.jit(step_fn, donate_argnums=(0, 1, 2) if donate else ())
