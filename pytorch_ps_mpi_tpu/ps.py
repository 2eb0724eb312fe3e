"""``MPI_PS`` / ``SGD`` / ``Adam`` — the drop-in distributed-optimizer API.

The TPU-native rebuild of the reference's ``ps.py``: an optimizer-style
object whose ``step`` (1) obtains per-worker gradients, (2) encodes them
through a pluggable codec, (3) exchanges them across workers with on-chip
collectives, (4) decodes + sums, and (5) applies a fused SGD/Adam update —
returning ``(loss, data)`` where ``data`` is the per-step timing/bytes
metrics dict (the reference's contract, ``ps.py:193``; schema keys
``ps.py:116-148``).

What changed architecturally (SURVEY §3.1 vs. this file):

- The reference overlapped encode with backprop via autograd hooks feeding
  a 200-thread pool (``ps.py:65-66,85,98-101``). Here the *whole* pipeline
  — grad, encode, collective, decode, update — is one XLA program per step;
  where the backend emits async collectives (TPU/GPU), the compiler
  overlaps them with the remaining backward compute — the TPU-native form
  of the same optimization, with no threads, futures, or GIL reasoning
  (the races of SURVEY §5.2 are gone by construction). This is measured
  on the chip, not assumed: a ``chipbench.run --trace 1`` run reads the
  collectives' time and the part of it no compute hides
  (``coll.time_ms`` / ``coll.exposed_ms``), and
  ``step_memory_analysis()["collectives" / "async_collectives"]`` counts
  them in the compiled program. On the XLA:CPU test backend the collective
  thunks are synchronous and nothing overlaps.
- The two-phase size exchange (``prepare``/``Iallgatherv``,
  ``ps.py:140-147``) is compile-time: payload shapes are static.
- The per-parameter reverse-order receive loop (``ps.py:155-176``)
  becomes a tree-mapped collective; XLA schedules transfers.
- Both reference topologies are kept: ``mode='allgather'`` is the live
  decentralized path (every rank decodes+steps redundantly, ``ps.py:75``);
  ``mode='leader'`` is the rank-0 PS path (gather→step-on-leader→broadcast,
  ``mpi_comms.py:60-133``, README pseudo-code), lowered TPU-natively as a
  ZeRO-1 sharded-optimizer step: per-leaf reduce_scatter of the summed
  gradient, each worker updates only its 1/world shard (owning that
  shard's optimizer state AND the master parameter copy, see
  :class:`LeaderState`), then all_gather the updated shards. Same
  numerics, but update FLOPs and optimizer-state memory divide by world
  size instead of every rank redundantly stepping the full model.

Async (AsySG-InCon) training lives in ``parallel/async_ps.py``.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_ps_mpi_tpu import comms
from pytorch_ps_mpi_tpu.bucketing import (
    BucketPlan,
    flatten_into_buckets,
    plan_buckets,
    unflatten_from_buckets,
)
from pytorch_ps_mpi_tpu.codecs import Codec, ErrorFeedback, IdentityCodec
from pytorch_ps_mpi_tpu.telemetry import (
    get_recorder,
    setup_event,
    setup_span,
    span,
)
from pytorch_ps_mpi_tpu.mesh import DATA_AXIS, make_mesh
from pytorch_ps_mpi_tpu.optim import (
    OPTIMIZERS,
    AdafactorState,
    AdamState,
    adafactor_check_sharding,
    adafactor_state_specs,
    adafactor_update,
)

PyTree = Any


def _tree_bytes(tree: PyTree) -> int:
    """Total raw bytes of a pytree's arrays (the reference's ``_bytes_of``,
    ``ps.py:25-43`` — without its self-documented 2-D bug, SURVEY §2.3)."""
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(tree)
    )


def _spec_axes(spec) -> Tuple[str, ...]:
    """Flattened mesh-axis names a PartitionSpec shards over (in spec
    order); () for a replicated leaf."""
    out = []
    for entry in tuple(spec or ()):
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            out.extend(entry)
        else:
            out.append(entry)
    return tuple(out)


def _local_shape(shape, spec, mesh: Mesh) -> Tuple[int, ...]:
    """Per-device shard shape of a leaf with PartitionSpec ``spec`` on
    ``mesh`` (each sharded dim divided by its mesh-axis size)."""
    shape = list(shape)
    for i, entry in enumerate(tuple(spec or ())):
        if entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        for a in axes:
            n = int(mesh.shape[a])
            if shape[i] % n:
                raise ValueError(
                    f"dim {i} of shape {tuple(shape)} is not divisible by "
                    f"mesh axis {a!r} (size {n})"
                )
            shape[i] //= n
    return tuple(shape)


class LeaderState(NamedTuple):
    """Optimizer state for ``mode='leader'`` (ZeRO-1): each worker owns a
    1/world shard of every parameter (``param_shards`` leaves are
    ``[world, shard_len]``, partitioned over the mesh) plus the matching
    shard of the inner optimizer state. The master copy of the parameters
    lives HERE, sharded — the replicated ``MPI_PS.params`` is the
    all-gathered working copy for the forward pass, re-derived every step
    (so reassigning ``opt.params`` directly is overwritten; go through
    ``load_state_dict``)."""

    param_shards: Any
    inner: Any


def _to_shards(x: jax.Array, world: int) -> jax.Array:
    """ravel + zero-pad to a multiple of ``world`` + reshape so row r is
    worker r's shard (the layout ``lax.psum_scatter``/``all_gather``
    tiled=True use)."""
    flat = jnp.ravel(x)
    ss = -(-flat.shape[0] // world)
    return jnp.pad(flat, (0, ss * world - flat.shape[0])).reshape(world, ss)


def leader_init_state(
    params: PyTree, init_state: Callable, world: int,
    param_specs: Optional[PyTree] = None, mesh: Optional[Mesh] = None,
) -> LeaderState:
    """Host-side construction of the sharded leader (ZeRO-1) state: the
    master param shards plus the inner optimizer state, leaves stacked
    ``[world, shard_len]`` for a ``P(axis)`` sharding.

    With ``param_specs`` (model-parallel composition): a model-sharded
    leaf — REQUIRED to follow the leading-shard-axis convention, spec
    ``P(model_axis)`` on dim 0 only (``parallel/tp.py``'s layout) — is
    raveled PER model shard and data-scattered within it, stacked
    ``[world * n_model, shard_len]`` data-major for a
    ``P((data, *model_axes))`` joint sharding: each (data, model) device
    owns the ZeRO-1 shard of its own model shard."""
    struct = jax.tree.structure(params)
    if param_specs is None:
        factors = [1] * struct.num_leaves
        shards = jax.tree.map(lambda p: _to_shards(p, world), params)
    else:
        spec_leaves = struct.flatten_up_to(param_specs)

        def build(p, sp):
            axes = _spec_axes(sp)
            if not axes:
                return _to_shards(p, world), 1
            nm = int(np.prod([mesh.shape[a] for a in axes]))
            per = p.reshape(nm, -1)       # [n_model, local_numel]
            ss = -(-per.shape[1] // world)
            per = jnp.pad(per, ((0, 0), (0, ss * world - per.shape[1])))
            # data-major layout matches P((data, *model)) linearization
            per = per.reshape(nm, world, ss).transpose(1, 0, 2)
            return per.reshape(world * nm, ss), nm

        built = [build(p, sp)
                 for p, sp in zip(jax.tree.leaves(params), spec_leaves)]
        shards = jax.tree.unflatten(struct, [b[0] for b in built])
        factors = [b[1] for b in built]

    shard_tmpl = jax.tree.map(lambda s: jnp.zeros(s.shape[1:], s.dtype), shards)
    inner = init_state(shard_tmpl)
    tmpl_struct = jax.tree.structure(shard_tmpl)
    tmpl_shapes = [x.shape for x in jax.tree.leaves(shard_tmpl)]

    def bcast_field(val):
        leaves_v = jax.tree.leaves(val)
        if (jax.tree.structure(val) == tmpl_struct
                and [x.shape for x in leaves_v] == tmpl_shapes):
            # params-mirroring field: stack with each leaf's own factor
            return jax.tree.unflatten(tmpl_struct, [
                jnp.broadcast_to(x[None], (world * f,) + x.shape)
                for x, f in zip(leaves_v, factors)
            ])
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (world,) + x.shape)
            if x.ndim > 0 else x,
            val,
        )

    inner = type(inner)(*[bcast_field(v) for v in inner])
    return LeaderState(shards, inner)


def leader_state_spec(opt_state: LeaderState, axis_name,
                      param_specs: Optional[PyTree] = None):
    """PartitionSpec pytree for :class:`LeaderState` (arrays sharded over
    ``axis_name``, scalars replicated). With ``param_specs``
    (model-parallel composition) the ``[world * n_model, shard_len]``
    leaves are jointly sharded ``P((data axes, *leaf model axes))``."""
    if param_specs is None:
        return jax.tree.map(
            lambda x: P(axis_name) if x.ndim > 0 else P(), opt_state
        )
    agg = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    shard_struct = jax.tree.structure(opt_state.param_shards)
    spec_leaves = shard_struct.flatten_up_to(param_specs)
    leaf_specs = [
        P(agg + axes) if (axes := _spec_axes(sp)) else P(axis_name)
        for sp in spec_leaves
    ]
    shard_shapes = [x.shape for x in jax.tree.leaves(opt_state.param_shards)]

    def field_spec(val):
        lv = jax.tree.leaves(val)
        if (jax.tree.structure(val) == shard_struct
                and [x.shape for x in lv] == shard_shapes):
            return jax.tree.unflatten(shard_struct, leaf_specs)
        return jax.tree.map(
            lambda x: P(axis_name) if x.ndim > 0 else P(), val
        )

    return LeaderState(
        jax.tree.unflatten(shard_struct, leaf_specs),
        type(opt_state.inner)(*[field_spec(v) for v in opt_state.inner]),
    )


def leader_scatter_shards(
    grads: PyTree, axis_name: str, world: int, comm_dtype=None,
    average: bool = False,
) -> PyTree:
    """Per-leaf reduce_scatter of local gradients: each worker receives
    only its shard's cross-worker sum (half of a psum's work)."""

    def scatter(g):
        rows = _to_shards(g, world).reshape(-1)  # row-major == tiled layout
        if comm_dtype is not None:
            rows = rows.astype(comm_dtype)
        sh = lax.psum_scatter(
            rows, axis_name, scatter_dimension=0, tiled=True
        ).astype(g.dtype)
        return sh / world if average else sh

    return jax.tree.map(scatter, grads)


def leader_slice_shards(summed: PyTree, axis_name: str, world: int) -> PyTree:
    """When every worker already holds the full summed gradient (non-psum
    codec decode path), index out each leaf's local shard row."""
    idx = lax.axis_index(axis_name)
    return jax.tree.map(
        lambda g: _to_shards(g, world)[idx], summed
    )


def clip_by_global_norm(grads: PyTree, clip_norm: float,
                        axis_name: Optional[str] = None,
                        leaf_extra_axes: Optional[list] = None) -> PyTree:
    """Scale ``grads`` so their global L2 norm is at most ``clip_norm``
    (torch ``clip_grad_norm_`` semantics, applied to the AGGREGATED
    gradient). With ``axis_name`` the leaves are device-local SHARDS of
    the global gradient (the ZeRO-1 psum_scatter fast path) and the
    norm is psum'd across the axis — shard-local norms would clip each
    device differently and silently diverge from the dense path.

    ``leaf_extra_axes`` (model-parallel composition): flat list aligned
    with ``jax.tree.leaves(grads)`` of extra mesh-axis tuples; each
    leaf's sum-square is psum'd over its tuple BEFORE the total, so a
    model-sharded leaf contributes its full cross-shard norm while
    replicated leaves are counted once."""
    leaves = jax.tree.leaves(grads)
    extras = leaf_extra_axes or [()] * len(leaves)
    sumsq = 0.0
    for g, axes in zip(leaves, extras):
        s = jnp.sum(jnp.square(g.astype(jnp.float32)))
        if axes:
            s = lax.psum(s, tuple(axes))
        sumsq = sumsq + s
    if axis_name is not None:
        sumsq = lax.psum(sumsq, axis_name)
    gnorm = jnp.sqrt(sumsq)
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-12))
    return jax.tree.map(lambda g: (g * scale.astype(g.dtype)), grads)


def leader_shard_update(
    params: PyTree, opt_state: LeaderState, grad_shards: PyTree,
    update_fn: Callable, hyper, axis_name: str,
) -> Tuple[PyTree, LeaderState]:
    """Shard-local optimizer step + all_gather back to replicated params
    (runs inside shard_map; ``opt_state`` leaves carry the local ``[1,
    shard_len]`` slice)."""
    p_shards = jax.tree.map(lambda x: x[0], opt_state.param_shards)
    inner = jax.tree.map(lambda x: x[0] if x.ndim > 0 else x, opt_state.inner)
    new_shards, new_inner = update_fn(p_shards, grad_shards, inner, hyper)

    def gather(sh, p):
        full = lax.all_gather(sh, axis_name, tiled=True)
        n = int(np.prod(p.shape)) if p.shape else 1
        return lax.slice(full, (0,), (n,)).reshape(p.shape)

    new_params = jax.tree.map(gather, new_shards, params)
    new_opt_state = LeaderState(
        jax.tree.map(lambda x: x[None], new_shards),
        jax.tree.map(lambda x: x[None] if x.ndim > 0 else x, new_inner),
    )
    return new_params, new_opt_state


class _IdKey:
    """Hash/eq by object identity while holding a strong reference, so an
    id() can never be recycled into a false cache hit after GC."""

    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, _IdKey) and other.obj is self.obj


def _fn_cache_key(fn: Optional[Callable]) -> Any:
    """Compile-cache key for a user loss function that survives fresh
    function *objects* with identical behavior — ``(code, closure cells,
    defaults, bound self)`` instead of bare identity — so
    ``step(loss_fn=lambda p, b: ...)`` in a loop, or a bound method
    (``model.loss`` creates a new object per attribute access), compiles
    once. Anything that can change behavior distinguishes the key:
    closure cell values, default args, and the method receiver; unhashable
    values are wrapped in :class:`_IdKey` (identity + strong ref).
    Known limits (same caveats as ``jax.jit`` identity keying avoids): a
    function reading a rebound module-level *global* is indistinguishable,
    and a captured hashable object *mutated in place* yields a stale hit —
    pass a fresh closure when either changes behavior."""
    if fn is None or not hasattr(fn, "__code__"):
        return fn

    def h(v):
        try:
            hash(v)
            return v
        except TypeError:
            return _IdKey(v)

    def cell(c):
        try:
            return h(c.cell_contents)
        except ValueError:  # empty (not-yet-assigned) cell
            return _IdKey(c)

    cells = tuple(cell(c) for c in (fn.__closure__ or ()))
    defaults = tuple(h(d) for d in (fn.__defaults__ or ()))
    bound_self = _IdKey(fn.__self__) if hasattr(fn, "__self__") else None
    return (fn.__code__, cells, defaults, bound_self)


# ---------------------------------------------------------------------------
# SPMD pipeline pieces, shared with the functional API in parallel/dp.py.
# All run *inside* shard_map.
# ---------------------------------------------------------------------------

def encode_tree(code: Codec, grads: PyTree, codec_state: PyTree, rng, axis_name: str):
    """Per-worker encode of every gradient leaf (the reference's autograd
    hook + thread pool, ``ps.py:94-101``, collapsed into the traced step).

    ``codec_state`` leaves carry a leading local-shard axis of size 1 (the
    shard_map slice of the host-side ``[world, ...]`` stack).
    """
    leaves, treedef = jax.tree.flatten(grads)
    keys = None
    if code.needs_rng:
        worker_rng = jax.random.fold_in(rng, lax.axis_index(axis_name))
        keys = list(jax.random.split(worker_rng, len(leaves)))
    flat_states = treedef.flatten_up_to(codec_state)
    payloads, new_states = [], []
    for i, g in enumerate(leaves):
        st = jax.tree.map(lambda x: x[0], flat_states[i])  # squeeze shard axis
        payload, new_st = code.encode(g, st, keys[i] if keys is not None else None)
        payloads.append(payload)
        new_states.append(jax.tree.map(lambda x: x[None], new_st))
    return (
        jax.tree.unflatten(treedef, payloads),
        jax.tree.unflatten(treedef, new_states),
    )


def _accumulate_grads(loss_fn, accum_steps: int, params: PyTree,
                      batches: PyTree, axis_name: str, *,
                      reduce_loss: Callable):
    """Microbatch gradient accumulation inside one SPMD program: scan
    ``accum_steps`` microbatches, mean the local grads, cross-worker-
    reduce the mean loss via ``reduce_loss`` (REQUIRED — every caller
    must pass the optimizer's own reduction so the reported loss can
    never fork between the fused accum step and the instrumented grad
    stage; they are asserted numerically equal in tests)."""
    def micro(acc, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return jax.tree.map(jnp.add, acc, grads), loss

    zero = jax.tree.map(jnp.zeros_like, params)
    grads, losses = lax.scan(micro, zero, batches)
    grads = jax.tree.map(lambda g: g / accum_steps, grads)
    return reduce_loss(losses.mean()), grads


def decode_sum_payloads(code: Codec, gathered: PyTree, shape, dtype):
    """The ONE payload-summing call site discipline (used by
    :func:`aggregate`, :func:`bucketed_aggregate` and the instrumented
    decode stage): route through the codec's compressed-domain
    ``Codec.aggregate`` algebra when it is EXACT — sum in the integer /
    sparse-index / factor domain, then decode once — and fall back to
    ``decode_sum`` otherwise. Approximate algebras (sign's vote counts,
    ``agg_exact=False``) never enter the training path implicitly; they
    ride only the host wire, behind the measured fidelity contract."""
    if (getattr(code, "supports_aggregate", False)
            and getattr(code, "agg_exact", True)
            and code.can_aggregate(shape, dtype)):
        agg_payload, meta = code.aggregate(gathered, shape, dtype)
        return code.agg_decode(agg_payload, meta, shape, dtype)
    return code.decode_sum(gathered, shape, dtype)


def aggregate(
    code: Codec,
    grads: PyTree,
    payloads: PyTree,
    axis_name,
    average: bool,
    size: int,
    comm_dtype=None,
    leaf_axes: Optional[list] = None,
    leaf_sizes: Optional[list] = None,
) -> PyTree:
    """Collective + decode + sum across workers (reference
    ``ps.py:140-176``). Identity-like codecs lower to one fused ``psum``;
    everything else all-gathers static-shape payloads and scatter/sums.

    ``comm_dtype`` (e.g. ``jnp.bfloat16``) narrows the psum path's wire
    dtype — halving ICI bytes, the cheap always-on compression every TPU
    program should use — and casts back for the f32 update. A psum-capable
    codec that declares a ``wire_dtype`` (the bf16/f16 cast codecs) is
    lowered the same way: the cast IS its encode, so the fused path must
    narrow the collective or the codec would silently be an identity
    no-op.

    ``leaf_axes`` (model-parallel composition): flat list aligned with
    ``jax.tree.leaves(grads)`` of per-leaf aggregation-axis tuples — a
    leaf SHARDED over one of the data axes (expert parallelism, where
    the expert axis carries both the shard and extra tokens) aggregates
    only over the remaining axes; ``()`` means the local gradient is
    already complete (codec filtering still applies via its own
    payload). ``leaf_sizes`` carries each leaf's worker count for
    ``average``."""
    leaves, treedef = jax.tree.flatten(grads)
    axes_list = leaf_axes if leaf_axes is not None else [axis_name] * len(leaves)
    sizes = leaf_sizes if leaf_sizes is not None else [size] * len(leaves)
    summed_leaves = []
    if code.supports_psum:
        wire = comm_dtype if comm_dtype is not None else getattr(
            code, "wire_dtype", None
        )
        for g, axes in zip(leaves, axes_list):
            if isinstance(axes, tuple) and not axes:
                # sharded over every data axis: local grad is complete,
                # but the wire cast must still round-trip (the cast IS
                # the codec's lossy encode — skipping it would silently
                # treat this leaf at full precision)
                summed_leaves.append(
                    g.astype(wire).astype(g.dtype) if wire is not None else g
                )
            elif wire is not None:
                summed_leaves.append(
                    lax.psum(g.astype(wire), axes).astype(g.dtype)
                )
            else:
                summed_leaves.append(lax.psum(g, axes))
    else:
        payload_list = treedef.flatten_up_to(payloads)
        for g, payload, axes in zip(leaves, payload_list, axes_list):
            if isinstance(axes, tuple) and not axes:
                # decode own payload only (codec filter still applies)
                gathered = jax.tree.map(lambda x: x[None], payload)
            else:
                gathered = jax.tree.map(
                    lambda x: lax.all_gather(x, axes), payload
                )
            summed_leaves.append(
                decode_sum_payloads(code, gathered, g.shape, g.dtype))
    if average:
        summed_leaves = [x / n for x, n in zip(summed_leaves, sizes)]
    return jax.tree.unflatten(treedef, summed_leaves)


def _encode_buckets(code: Codec, buckets, rng, axis_name):
    """Per-worker, per-bucket codec encode (stateless by the
    ``bucketable`` contract): ONE rng-derivation for every bucketed
    lowering, so the allgather and leader dense_scatter paths can never
    drift onto different randomness."""
    keys = None
    if code.needs_rng:
        worker_rng = jax.random.fold_in(rng, lax.axis_index(axis_name))
        keys = list(jax.random.split(worker_rng, len(buckets)))
    return [
        code.encode(b, (), keys[i] if keys is not None else None)[0]
        for i, b in enumerate(buckets)
    ]


def bucketed_aggregate(
    code: Codec,
    grads: PyTree,
    plan: BucketPlan,
    axis_name,
    average: bool,
    size: int,
    comm_dtype=None,
    rng=None,
) -> PyTree:
    """Flat-bucket form of :func:`aggregate` (mode='allgather' and the
    leader payload-gather lowering): flatten the gradient tree into
    dtype-grouped buckets, run ONE collective per bucket instead of one
    per leaf, and unflatten the summed buckets back to the tree. Runs
    inside shard_map.

    psum-capable codecs psum each bucket (wire-narrowed exactly as the
    per-leaf path would be, so numerics are bit-identical — a bucket is a
    permutation-into-concatenation of the leaves and psum is elementwise).
    Non-psum ``bucketable`` codecs encode each bucket as if it were one
    large leaf (stateless by the ``bucketable`` contract), all-gather the
    per-bucket payloads, and decode_sum per bucket — per-input statistics
    (sign's mean|g|, int8's absmax) then apply per bucket, the documented
    semantics shift for those lossy codecs."""
    buckets = flatten_into_buckets(plan, grads)
    if code.supports_psum:
        wire = comm_dtype if comm_dtype is not None else getattr(
            code, "wire_dtype", None
        )
        summed_b = comms.allreduce_sum_buckets(buckets, axis_name, wire)
    else:
        payloads = _encode_buckets(code, buckets, rng, axis_name)
        summed_b = []
        for b, payload in zip(buckets, payloads):
            gathered = jax.tree.map(
                lambda x: lax.all_gather(x, axis_name), payload
            )
            summed_b.append(
                decode_sum_payloads(code, gathered, b.shape, b.dtype))
    if average:
        summed_b = [x / size for x in summed_b]
    return unflatten_from_buckets(plan, summed_b)


def fused_allreduce_tree(
    code: Codec, grads: PyTree, codec_state: PyTree, axis_name,
    average: bool, size: int, comm_dtype=None,
    leaf_axes: Optional[list] = None, leaf_sizes: Optional[list] = None,
):
    """Tree-mapped collective-protocol aggregation for codecs declaring
    ``supports_fused_allreduce`` (PowerSGD's two-psum form): returns
    ``(summed, new_codec_state)``. Runs inside shard_map. ``leaf_axes``
    / ``leaf_sizes`` as in :func:`aggregate` (model-parallel per-leaf
    aggregation); codec-state leaves carry the leading local-shard axis
    of 1 (the shard_map slice), like :func:`encode_tree`."""
    leaves, treedef = jax.tree.flatten(grads)
    flat_states = treedef.flatten_up_to(codec_state)
    axes_list = leaf_axes if leaf_axes is not None else [axis_name] * len(leaves)
    sizes = leaf_sizes if leaf_sizes is not None else [size] * len(leaves)
    summed, new_states = [], []
    for g, st_stacked, axes in zip(leaves, flat_states, axes_list):
        st = jax.tree.map(lambda x: x[0], st_stacked)
        if isinstance(axes, tuple) and not axes:
            # sharded over every data axis (EP): local grad is complete
            s, new_st = g, st
        else:
            s, new_st = code.fused_allreduce(g, st, axes, comm_dtype=comm_dtype)
        summed.append(s)
        new_states.append(jax.tree.map(lambda x: x[None], new_st))
    if average:
        summed = [x / n for x, n in zip(summed, sizes)]
    return (
        jax.tree.unflatten(treedef, summed),
        jax.tree.unflatten(treedef, new_states),
    )


class MPI_PS:
    """Distributed parameter-server optimizer over a device mesh.

    Parameters mirror the reference constructor (``ps.py:54-59``) where
    they still make sense; MPI/cuda knobs are replaced by mesh/codec ones:

    Args:
      params: pytree of parameter arrays (replicated across the mesh).
      optim: ``'sgd'`` or ``'adam'`` (reference ``ps.py:181-188``).
      code: a :class:`Codec` (reference ``code=`` hook); default identity.
      mesh: ``jax.sharding.Mesh``; default 1-D data mesh over all devices.
      axis_name: mesh axis to aggregate over.
      mode: ``'allgather'`` (decentralized replicated step — the
        reference's live path) or ``'leader'`` (PS topology: the update
        runs once, sharded over workers ZeRO-1 style, not redundantly —
        optimizer state and the master parameter copy are partitioned
        1/world per device, per leaf, preserving leaf dtypes).
      average: if True, average worker gradients instead of the
        reference's sum semantics (``ps.py:176``).
      instrument: if True, ``step`` runs the pipeline as separate stages
        with host-side timing to fill the full metrics schema; if False,
        one fused XLA program (fast path) and only end-to-end time.
      seed: base PRNG seed for stochastic codecs.
      clip_norm: if > 0, clip the AGGREGATED gradient to this global L2
        norm before the update (torch ``clip_grad_norm_`` semantics) —
        in leader mode the norm is psum'd across shard sum-squares so
        both topologies clip identically.
      donate_buffers: if True, the fused step donates the params /
        optimizer-state / codec-state buffers to XLA (in-place update on
        device: peak HBM drops by roughly one params+state copy — at
        BERT-base/Adam scale ~2 GB). The PREVIOUS step's ``opt.params``
        etc. become invalid after each step; only enable when no outside
        reference holds them.
      param_specs: optional PartitionSpec pytree (matching ``params``)
        for MODEL-PARALLEL composition: leaves sharded over non-data
        mesh axes (e.g. ``parallel.tp.tp_param_spec`` for Megatron TP,
        ``parallel.pp.stage_spec`` for pipeline stages) stay sharded
        through the whole pipeline — the codec encodes each device's
        LOCAL shard gradient and the collective aggregates over the
        data axis only, so the drop-in optimizer (codecs, leader
        ZeRO-1, clip, metrics) drives 2-D/3-D meshes. The loss_fn must
        produce per-device local losses with
        vma-unchecked-correct collectives (``tp_mlp(...,
        local_grads=True)`` / ``pipeline_loss(..., local_grads=True)``)
        and a STATIC global normalizer; the reported loss is then the
        SUM of local losses across the aggregation axes (matching the
        gradient-sum semantics — a pmean would deflate it by the world
        size). Default None: fully-replicated params (pure DP, the
        reference's regime, ``ps.py:54-59``).
      bucket_mb: if > 0, fuse per-leaf collectives into dtype-grouped
        flat buckets of about this many megabytes (``bucketing.BucketPlan``)
        — one psum (allgather mode) / psum_scatter (leader mode, each
        worker owning a contiguous bucket shard) per BUCKET instead of
        per leaf, cutting a BERT-size tree's collective launch count by
        an order of magnitude. Bit-exact vs. the per-leaf path for
        identity/cast codecs; shape-agnostic stateless codecs
        (``Codec.bucketable``: sign, int8, qsgd, terngrad, and randomk's
        fraction form) encode per bucket (their per-input statistics
        then apply per bucket); per-tensor codecs (PowerSGD, top-k,
        absolute-k randomk) keep the per-leaf path automatically. ``0`` (default) preserves per-leaf behavior
        exactly. Requires pure-DP layouts (no ``param_specs``).
      numerics: if True, fuse on-device gradient statistics into the
        lowered step programs (``telemetry.numerics``): global finite
        grad norm, NaN/Inf element count, update-to-weight ratio
        ``||dp||/||p||``, per-BUCKET grad norms when ``bucket_mb`` is
        active, and the error-feedback residual norm when ``code`` is an
        :class:`~pytorch_ps_mpi_tpu.codecs.ErrorFeedback`. All
        reductions run inside the jit (XLA fuses them into the step for
        ~free) and land in the returned metrics dict as ``grad_norm`` /
        ``nonfinite_total`` / ``update_ratio`` / ``bucket_grad_norms``
        / ``ef_residual_norm`` — one tiny stats vector fetched per
        step. The fused and accumulation paths compute them;
        ``instrument=True`` stages and ``run_steps`` (one opaque scanned
        program) do not. Requires pure-DP layouts (no ``param_specs``).
      batch_spec: optional PartitionSpec for the batch pytree's leaves
        (default ``P(axis_name)``: leading dim split over the data
        axis). With model parallelism e.g. ``P('data')`` replicates the
        batch across model shards, or ``P('data', 'seq')`` also splits
        the sequence dim.
      loss_reduction: how the per-device loss is reduced for reporting:
        ``'pmean'`` (pure-DP local-batch-mean convention) or ``'psum'``
        (local loss with a static global normalizer — the param_specs /
        tuple-axes contract). Default None picks by convention:
        psum when param_specs or tuple aggregation axes are in play,
        pmean otherwise.
      **hyper: optimizer hyperparameters (lr, momentum, betas, ...).
        ``lr`` may be a float or a schedule callable ``step -> scalar``
        from :data:`pytorch_ps_mpi_tpu.optim.SCHEDULES` (e.g.
        ``warmup_cosine``): it is evaluated on the optimizer's traced
        step counter inside the compiled program, so the rate varies per
        step with no recompiles.

    ``axis_name`` may also be a TUPLE of mesh axes (e.g. ``('data',
    'seq')``): gradients aggregate over their product — the sequence-
    parallel composition where every seq shard holds the same params
    and contributes partial gradients.
    """

    def __init__(
        self,
        params: PyTree,
        *,
        optim: str = "sgd",
        code: Optional[Codec] = None,
        mesh: Optional[Mesh] = None,
        axis_name=DATA_AXIS,
        mode: str = "allgather",
        average: bool = False,
        instrument: bool = False,
        comm_dtype=None,
        seed: int = 0,
        donate_buffers: bool = False,
        clip_norm: float = 0.0,
        bucket_mb: float = 0.0,
        numerics: bool = False,
        param_specs: Optional[PyTree] = None,
        batch_spec=None,
        loss_reduction: Optional[str] = None,
        **hyper,
    ):
        entered = time.monotonic()  # the set-up log's setup.state begins
        if optim not in OPTIMIZERS:
            raise ValueError(f"optim must be one of {sorted(OPTIMIZERS)}")
        if mode not in ("allgather", "leader"):
            raise ValueError("mode must be 'allgather' or 'leader'")
        if clip_norm < 0:
            # a negative threshold would flip scale's sign and silently
            # turn the update into gradient ASCENT
            raise ValueError(f"clip_norm must be >= 0, got {clip_norm}")
        if loss_reduction not in (None, "pmean", "psum"):
            raise ValueError(
                f"loss_reduction must be 'pmean', 'psum', or None "
                f"(auto), got {loss_reduction!r}"
            )
        self._loss_reduction = loss_reduction
        hyper_cls, init_state, update_fn = OPTIMIZERS[optim]
        self.hyper = hyper_cls(**hyper)
        if optim == "adam":  # the AMSGrad maximum exists only where it is used
            init_state = functools.partial(init_state,
                                           amsgrad=self.hyper.amsgrad)
        self._update_fn = update_fn
        self.params = params
        self.code = code if code is not None else IdentityCodec()
        if mesh is None and not isinstance(axis_name, str):
            mesh = make_mesh(axis_names=tuple(axis_name))
        self.mesh = mesh if mesh is not None else make_mesh(axis_names=(axis_name,))
        self.axis_name = axis_name
        self._agg_axes = (
            (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        )
        self.mode = mode
        self.average = average
        self.donate_buffers = donate_buffers
        self.clip_norm = float(clip_norm)
        self.instrument = instrument
        self.comm_dtype = comm_dtype
        self.rank = jax.process_index()           # reference ps.py:71-72
        self.size = int(np.prod(                  # reference ps.py:73
            [self.mesh.shape[a] for a in self._agg_axes]
        ))
        # -- model-parallel composition (param_specs) ---------------------
        if param_specs is None:
            param_specs = jax.tree.map(lambda _: P(), params)
        struct = jax.tree.structure(params)
        self._spec_leaves = struct.flatten_up_to(param_specs)
        # canonical full tree (exact params structure, P leaves) so
        # jax.tree.map over (params, param_specs) is always legal
        self.param_specs = jax.tree.unflatten(struct, self._spec_leaves)
        # Per-leaf aggregation axes: a leaf sharded over one of the data
        # axes (expert parallelism — the expert axis carries both the
        # shard and extra tokens) aggregates only over the remaining
        # axes; its shard gradient over its own axis is already complete.
        self._leaf_agg_axes = [
            tuple(a for a in self._agg_axes if a not in _spec_axes(sp))
            for sp in self._spec_leaves
        ]
        self._leaf_agg_sizes = [
            int(np.prod([self.mesh.shape[a] for a in axes]) if axes else 1)
            for axes in self._leaf_agg_axes
        ]
        self._model_parallel = any(_spec_axes(sp) for sp in self._spec_leaves)
        self._uniform_agg = all(
            axes == self._agg_axes for axes in self._leaf_agg_axes
        )
        if mode == "leader" and not self._uniform_agg:
            raise ValueError(
                "leader (ZeRO-1) mode requires every leaf to aggregate "
                "over the full data axes — param_specs must not shard "
                "over the aggregation axes; use mode='allgather' for "
                "expert-parallel layouts"
            )
        if optim == "adafactor" and mode == "leader":
            # leader mode flattens leaves to 1-D per-worker shards —
            # Adafactor's factored moments depend on each leaf's GLOBAL
            # 2-D shape, so the sharded step would silently compute a
            # DIFFERENT update than the allgather form.
            raise NotImplementedError(
                "optim='adafactor' does not support mode='leader': "
                "ZeRO-1's 1-D shards destroy the leaf shapes the "
                "factored second moments are defined over (and its "
                "state-sharding win is marginal for a sublinear-state "
                "optimizer). Use mode='allgather'"
            )
        if optim == "adafactor" and self._model_parallel:
            # model-parallel Adafactor is exactly shard-local
            # decomposable iff no FACTORED dim is sharded (then the
            # row/col means never span devices); the two per-leaf
            # scalar reductions (clip RMS, parameter scale) become
            # global via pmean over the model axes — identity on
            # replicated leaves, exact global mean on uniform shards.
            if not self._uniform_agg:
                raise NotImplementedError(
                    "optim='adafactor' with expert-parallel layouts "
                    "(leaves sharded over a data axis) is unsupported: "
                    "the per-leaf scalar reductions would need per-leaf "
                    "axis sets. Use optim='adam'/'sgd' for EP"
                )
            adafactor_check_sharding(params, self.param_specs)
            model_axes = tuple(a for a in self.mesh.axis_names
                               if a not in self._agg_axes)
            self._update_fn = functools.partial(
                adafactor_update,
                scalar_mean=lambda s: lax.pmean(s, model_axes),
            )
        if self._model_parallel and mode == "leader":
            for p, sp in zip(jax.tree.leaves(params), self._spec_leaves):
                entries = tuple(sp)
                sharded_dims = [i for i, e in enumerate(entries)
                                if e is not None]
                if sharded_dims and sharded_dims != [0]:
                    raise ValueError(
                        "leader mode requires model-sharded leaves to use "
                        "the leading-shard-axis convention (spec P(axis) on "
                        f"dim 0 only); got {sp} for shape {p.shape}"
                    )
        # -- flat-bucket aggregation (bucket_mb) --------------------------
        if bucket_mb < 0:
            raise ValueError(f"bucket_mb must be >= 0, got {bucket_mb}")
        self.bucket_mb = float(bucket_mb)
        self._bucket_plan: Optional[BucketPlan] = None
        if self.bucket_mb > 0:
            if self._model_parallel or not self._uniform_agg:
                raise NotImplementedError(
                    "bucket_mb > 0 requires pure-DP layouts: model-sharded "
                    "or expert-parallel leaves aggregate over per-leaf axis "
                    "sets that one flat bucket cannot represent. Drop "
                    "param_specs or set bucket_mb=0"
                )
            if (self.code.bucketable
                    and not self.code.supports_fused_allreduce):
                if jax.tree.leaves(self.code.init_state((1,), jnp.float32)):
                    raise TypeError(
                        f"{type(self.code).__name__}.bucketable=True but "
                        "init_state is non-empty — bucketable codecs must "
                        "be stateless (see codecs.base.Codec.bucketable)"
                    )
                self._bucket_plan = plan_buckets(params, self.bucket_mb)
            # else: per-tensor codec — keep the per-leaf path (the
            # documented Codec.bucketable opt-out), no error
        self._bucket_templates = (
            self._bucket_plan.bucket_templates()
            if self._bucket_plan is not None else None
        )
        # -- fused numerics statistics (numerics=True) --------------------
        self.numerics = bool(numerics)
        if self.numerics and self._model_parallel:
            raise NotImplementedError(
                "numerics=True requires pure-DP layouts: model-sharded "
                "leaves would need per-leaf reduction axis sets for the "
                "global norms. Drop param_specs or set numerics=False"
            )
        self.batch_spec = batch_spec if batch_spec is not None else P(axis_name)
        if self._model_parallel and instrument:
            raise NotImplementedError(
                "instrument=True (the staged host-timed pipeline) is not "
                "supported with param_specs — for the comm/compute split "
                "of the fused step read a chipbench --trace 1 run's coll.* "
                "metrics or step_memory_analysis()"
            )
        if mode == "leader":
            # ZeRO-1-style sharded optimizer: each worker owns a 1/world
            # shard of every parameter and the optimizer state for it —
            # the TPU-native lowering of the reference's rank-0 PS
            # (gather to rank 0, rank 0 alone steps, broadcast back,
            # mpi_comms.py:60-133, README.md:61-77), generalized so every
            # chip is the "leader" of its own shard: per-leaf
            # reduce_scatter → shard-local update → all_gather. Update
            # FLOPs and optimizer-state memory divide by world size; comm
            # volume matches a psum (which IS reduce_scatter+all_gather
            # on a ring). Per-leaf sharding (not one flat concat)
            # preserves leaf dtypes and lets XLA fuse per-tensor.
            from jax.sharding import NamedSharding

            specs_arg = self.param_specs if self._model_parallel else None

            # Construct the state *directly sharded* (jit + out_shardings)
            # so no device ever materializes the full [world, shard_len]
            # stack — a host-side build-then-reshard would transiently use
            # world× the sharded memory, defeating ZeRO-1's point at the
            # model scales it targets.
            #
            # With a bucket plan the master copy is kept in BUCKET form:
            # LeaderState.param_shards leaves are per-bucket [world, ss]
            # stacks, so the step's psum_scatter of a flat bucket lands
            # directly on the shard the optimizer owns — no re-slicing
            # between the wire layout and the state layout. The update is
            # elementwise (SGD/Adam; adafactor is rejected in leader mode
            # above), so per-bucket state is numerically identical to
            # per-leaf state, and dtype grouping preserves leaf dtypes.
            def build(p):
                if self._bucket_plan is not None:
                    p = flatten_into_buckets(self._bucket_plan, p)
                return leader_init_state(
                    p, init_state, self.size, specs_arg, self.mesh
                )

            structs = jax.eval_shape(build, params)
            spec_tree = leader_state_spec(structs, axis_name, specs_arg)
            shardings = jax.tree.map(
                lambda s, sp: NamedSharding(self.mesh, sp), structs, spec_tree
            )
            self.opt_state = jax.jit(build, out_shardings=shardings)(params)
        else:
            self.opt_state = init_state(params)
        self._rng = jax.random.key(seed)
        self.codec_state = self._init_codec_state()
        self._codec_spec = self._codec_state_spec()
        self._place_state()
        setup_event(
            "setup.state", kind="span", ts=entered,
            dur=time.monotonic() - entered,
            leaves=len(self._spec_leaves), param_bytes=_tree_bytes(params),
            state_bytes=_tree_bytes((self.opt_state, self.codec_state)),
            devices=int(self.mesh.devices.size), mode=mode)
        self.aux_state = None  # mutable model state (e.g. BN batch_stats)
        self._compiled: Dict[Any, Callable] = {}
        self._step_count = 0
        # loss of the fused step that was launched and not waited for
        self._in_flight: Optional[jax.Array] = None
        self._payload_bytes_per_leaf = float(sum(
            self.code.payload_bits(
                _local_shape(p.shape, sp, self.mesh), p.dtype
            ) // 8
            for p, sp in zip(jax.tree.leaves(params), self._spec_leaves)
        ))
        if self._bucket_plan is not None:
            # encode (when used) runs per BUCKET: the payload accounting
            # must match or packaged_bytes would overstate per-leaf
            # overheads (e.g. sign's one scale scalar per unit). The
            # per-leaf figure is kept for the staged instrument pipeline,
            # whose encode/gather stages stay per-leaf.
            self._payload_bytes = float(sum(
                self.code.payload_bits((b.size,), b.dtype) // 8
                for b in self._bucket_plan.buckets
            ))
        else:
            self._payload_bytes = self._payload_bytes_per_leaf
        self._local_param_bytes = float(sum(
            int(np.prod(_local_shape(p.shape, sp, self.mesh)) if p.shape else 1)
            * jnp.dtype(p.dtype).itemsize
            for p, sp in zip(jax.tree.leaves(params), self._spec_leaves)
        ))
        self._init_wire_accounting()
        # static per-step launch accounting for the metrics dict / trace:
        # aggregation units = buckets when a plan is active, leaves
        # otherwise (the quantity bucketing exists to shrink)
        if self._bucket_plan is not None:
            self._agg_units = self._bucket_plan.num_buckets
            self._bucket_bytes_total = float(self._bucket_plan.total_bytes)
        else:
            self._agg_units = len(self._spec_leaves)
            self._bucket_bytes_total = 0.0

    # -- codec state: per-worker, stored host-side stacked on a leading
    #    [world] axis so shard_map can scatter/gather it. Model-sharded
    #    leaves build state from the LOCAL shard shape and stack
    #    [world * n_model_shards] for a joint P((data, *model)) sharding:
    #    per-(data, model)-device codec state (e.g. error feedback is per
    #    shard of the gradient each device actually encodes) ---------------
    def _leaf_state_axes(self, sp) -> Tuple[str, ...]:
        """Mesh axes a leaf's codec state varies over: its aggregation
        axes (one state per data worker) then its shard axes (one per
        model/expert shard) — every distinct (worker, shard) cell."""
        spec_axes = _spec_axes(sp)
        agg = tuple(a for a in self._agg_axes if a not in spec_axes)
        return agg + spec_axes

    def _init_codec_state(self) -> PyTree:
        def leaf(p, sp):
            lshape = _local_shape(p.shape, sp, self.mesh)
            s = self.code.init_state(lshape, p.dtype)
            axes = self._leaf_state_axes(sp)
            n = int(np.prod([self.mesh.shape[a] for a in axes]) if axes else 1)
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), s
            )
        return jax.tree.map(leaf, self.params, self.param_specs)

    def _codec_state_spec(self) -> PyTree:
        """Per-leaf PartitionSpec pytree matching ``codec_state``
        (abstract eval only — re-materializing real state arrays here
        would transiently double the param-sized error-feedback buffers
        at BERT scale)."""
        def leaf(p, sp):
            axes = self._leaf_state_axes(sp)
            ax = P(axes) if _spec_axes(sp) else P(self.axis_name)
            lshape = _local_shape(p.shape, sp, self.mesh)
            s = jax.eval_shape(
                lambda: self.code.init_state(lshape, p.dtype)
            )
            return jax.tree.map(lambda _: ax, s)
        return jax.tree.map(leaf, self.params, self.param_specs)

    # -- SPMD pipeline pieces (run inside shard_map) ----------------------
    def _encode_tree(self, grads, codec_state, rng):
        return encode_tree(self.code, grads, codec_state, rng, self.axis_name)

    def _aggregate(self, grads, payloads):
        return aggregate(
            self.code, grads, payloads, self.axis_name, self.average, self.size,
            self.comm_dtype,
            leaf_axes=None if self._uniform_agg else self._leaf_agg_axes,
            leaf_sizes=None if self._uniform_agg else self._leaf_agg_sizes,
        )

    def _reduce_loss(self, loss):
        """Cross-worker reduction of the per-device loss for reporting.

        Pure DP: loss_fn computes a local-batch MEAN, so pmean over the
        data axis is the global mean. With param_specs — or tuple
        aggregation axes (the SP composition) — the documented
        convention is a local loss with a STATIC GLOBAL normalizer
        (matching the optimizer's gradient-sum semantics), so the local
        losses SUM to the global loss — pmean would deflate the reported
        value by the world size. ``loss_reduction`` overrides either
        default."""
        how = self._loss_reduction
        if how is None:
            how = ("psum" if self._model_parallel
                   or not isinstance(self.axis_name, str) else "pmean")
        if how == "psum":
            return lax.psum(loss, self.axis_name)
        return lax.pmean(loss, self.axis_name)

    def _leaf_clip_axes(self):
        """Per-leaf extra psum axes for the global clip norm: a model-
        sharded leaf's sum-square spans its shards; replicated leaves
        count once."""
        if not self._model_parallel:
            return None
        return [_spec_axes(sp) for sp in self._spec_leaves]

    def _update(self, params, opt_state, summed):
        if self.clip_norm:
            summed = clip_by_global_norm(
                summed, self.clip_norm, leaf_extra_axes=self._leaf_clip_axes()
            )
        if self.mode == "leader":
            # Every rank already holds the full summed gradient (non-psum
            # codec decode path, or the instrumented stages); slice out
            # each leaf's local shard and run the sharded step.
            if self._bucket_plan is not None:
                # bucket-sharded state: slice each worker's contiguous
                # BUCKET shard (the layout the opt state was built in)
                buckets = flatten_into_buckets(self._bucket_plan, summed)
                shards = leader_slice_shards(buckets, self.axis_name, self.size)
                return self._leader_bucket_update(opt_state, shards)
            grad_shards = leader_slice_shards(summed, self.axis_name, self.size)
            return leader_shard_update(
                params, opt_state, grad_shards, self._update_fn, self.hyper,
                self.axis_name,
            )
        return self._update_fn(params, summed, opt_state, self.hyper)

    def _leader_bucket_update(self, opt_state, bucket_shards):
        """Shard-local optimizer step on contiguous bucket shards +
        all_gather + unflatten back to replicated params (the bucketed
        leader/ZeRO-1 lowering: opt state and master params live per
        bucket, see ``__init__``). Runs inside shard_map."""
        new_bucket_params, new_opt_state = leader_shard_update(
            self._bucket_templates, opt_state, bucket_shards,
            self._update_fn, self.hyper, self.axis_name,
        )
        new_params = unflatten_from_buckets(self._bucket_plan, new_bucket_params)
        return new_params, new_opt_state

    def _bucketed_encode_aggregate_update(self, params, opt_state,
                                          codec_state, grads, rng):
        """Flat-bucket lowering of the encode → aggregate → update seam
        (``_bucket_plan`` is set: bucketable codec, pure-DP layout). The
        codec is stateless by the ``bucketable`` contract, so
        ``codec_state`` passes through untouched."""
        plan = self._bucket_plan
        lowering = self._leader_lowering()
        if lowering in ("psum_scatter", "dense_scatter"):
            if lowering == "psum_scatter":
                to_scatter = flatten_into_buckets(plan, grads)
                wire = self.comm_dtype if self.comm_dtype is not None else (
                    getattr(self.code, "wire_dtype", None)
                )
            else:
                # decode the own-bucket payload to the codec-filtered
                # dense bucket, then reduce_scatter that (numerics match
                # the gather form exactly as in the per-leaf path)
                buckets = flatten_into_buckets(plan, grads)
                payloads = _encode_buckets(
                    self.code, buckets, rng, self.axis_name
                )
                to_scatter = [
                    self.code.decode(p, b.shape, b.dtype)
                    for b, p in zip(buckets, payloads)
                ]
                wire = self.comm_dtype
            grad_shards = leader_scatter_shards(
                to_scatter, self.axis_name, self.size, wire, self.average
            )
            if self.clip_norm:
                # bucket shards partition the aggregated gradient exactly
                # as leaf shards do (padding is zeros): same global norm
                grad_shards = clip_by_global_norm(
                    grad_shards, self.clip_norm, self.axis_name
                )
            new_params, new_opt_state = self._leader_bucket_update(
                opt_state, grad_shards
            )
            return new_params, new_opt_state, codec_state
        # allgather mode, or the leader payload_gather lowering (strongly
        # compressing codec): bucketed collective + decode, then the
        # shared update path (which re-buckets for the leader slice)
        summed = bucketed_aggregate(
            self.code, grads, plan, self.axis_name, self.average, self.size,
            self.comm_dtype, rng,
        )
        new_params, new_opt_state = self._update(params, opt_state, summed)
        return new_params, new_opt_state, codec_state

    def _tree_wire_bytes(self, wire_dtype) -> float:
        """Dense gradient bytes at the collective's wire dtype (per-leaf
        LOCAL-shard numel x itemsize — global numel when replicated;
        ``wire_dtype=None`` keeps each leaf's own)."""
        return float(sum(
            int(np.prod(_local_shape(p.shape, sp, self.mesh)) if p.shape
                else 1)
            * (jnp.dtype(wire_dtype).itemsize if wire_dtype is not None
               else jnp.dtype(p.dtype).itemsize)
            for p, sp in zip(jax.tree.leaves(self.params), self._spec_leaves)
        ))

    def _init_wire_accounting(self) -> None:
        """Chosen aggregation lowering + analytic bytes RECEIVED per
        worker per step — computed ONCE (static per instance) and
        surfaced in every step's metrics dict. This is the reference's
        msg-bytes accounting (``ps.py:135-136``) extended to make each
        topology's traffic comparable.

        Leader-mode lowering choice, by minimum received bytes (the PS
        topology's whole point is less traffic per worker — reference
        ``README.md:61-77``):

        - ``psum_scatter``: psum-capable codec — per-leaf reduce_scatter
          (wire dtype: ``comm_dtype`` or the codec's ``wire_dtype``).
        - ``dense_scatter``: non-psum codec with a WEAK wire ratio:
          decode the OWN payload to the dense codec-filtered gradient
          locally, then reduce_scatter that (wire dtype: ``comm_dtype``
          only — a non-psum codec's wire_dtype, e.g. f16's, is excluded
          from on-chip collectives by design, see codecs/cast.py).
          psum(decode(own)) == decode_sum(allgather(payloads)) by
          decode_sum's definition, so numerics are identical; received
          bytes drop from (W-1)·p to (W-1)/W·n_w.
        - ``payload_gather``: strongly-compressing sparse codec —
          all-gather the payloads and decode-sum. UNAVOIDABLE for this
          class under SPMD collectives: payload indices are
          data-dependent, XLA collectives cannot route by content, and
          a dense reduce_scatter would receive (W-1)/W·n_w per worker —
          more than the whole (W-1)·p payload exchange when p is small.
          What leader mode still buys is the 1/W update FLOPs and
          optimizer-state HBM (ZeRO-1), paid for with the param
          all_gather; ``wire_bytes_per_worker`` makes that trade
          visible per configuration.
        """
        w = self.size
        frac = (w - 1) / w
        n = self._local_param_bytes  # == _tree_bytes(params) when pure-DP
        p = self._payload_bytes
        psum_wire = self.comm_dtype if self.comm_dtype is not None else (
            getattr(self.code, "wire_dtype", None)
        )
        if self.code.supports_fused_allreduce:
            # two rank-sized ring psums per compressed leaf (plain psum
            # for uncompressed ones): received bytes are world-size-
            # INDEPENDENT in the payload term — the protocol's headline
            # property (Vogels et al. 2019 Alg. 1)
            fused = float(sum(
                self.code.fused_wire_bits(
                    _local_shape(pp.shape, sp, self.mesh), pp.dtype,
                    comm_dtype=self.comm_dtype,
                ) // 8
                for pp, sp in zip(jax.tree.leaves(self.params),
                                  self._spec_leaves)
            ))
            recv = 2 * frac * fused
            if self.mode == "leader":
                recv += frac * n  # sharded update's param all_gather
            self._wire_accounting = ("two_psum_lowrank", recv)
            return
        if self.mode == "leader":
            if self.code.supports_psum:
                self._wire_accounting = (
                    "psum_scatter",
                    frac * (self._tree_wire_bytes(psum_wire) + n),
                )
                return
            dense_recv = frac * self._tree_wire_bytes(self.comm_dtype)
            payload_recv = (w - 1) * p
            if dense_recv < payload_recv:
                self._wire_accounting = (
                    "dense_scatter", dense_recv + frac * n
                )
            else:
                self._wire_accounting = (
                    "payload_gather", payload_recv + frac * n
                )
            return
        if self.code.supports_psum:
            self._wire_accounting = (
                "psum", 2 * frac * self._tree_wire_bytes(psum_wire)
            )
        else:
            self._wire_accounting = ("allgather", (w - 1) * p)

    def _leader_lowering(self) -> str:
        return self._wire_accounting[0] if self.mode == "leader" else ""

    def _aggregate_update(self, params, opt_state, grads, payloads):
        """Aggregate + update, choosing the cheapest lowering per mode
        (see :meth:`_leader_lowering`)."""
        lowering = self._leader_lowering()
        if lowering in ("psum_scatter", "dense_scatter"):
            if lowering == "psum_scatter":
                to_scatter = grads
                # a cast codec's wire_dtype narrows the scatter exactly
                # as comm_dtype would (same rationale as aggregate())
                wire = self.comm_dtype if self.comm_dtype is not None else (
                    getattr(self.code, "wire_dtype", None)
                )
            else:
                # decode the local payload to the codec-filtered dense
                # gradient; the scatter then sums those across workers
                leaves, treedef = jax.tree.flatten(grads)
                pls = treedef.flatten_up_to(payloads)
                to_scatter = jax.tree.unflatten(
                    treedef,
                    [self.code.decode(pl_, g.shape, g.dtype)
                     for g, pl_ in zip(leaves, pls)],
                )
                wire = self.comm_dtype
            grad_shards = leader_scatter_shards(
                to_scatter, self.axis_name, self.size, wire, self.average
            )
            if self.clip_norm:
                # shards partition the aggregated gradient: the global
                # norm is the psum of shard sum-squares (model-sharded
                # leaves additionally psum over their model axes)
                grad_shards = clip_by_global_norm(
                    grad_shards, self.clip_norm, self.axis_name,
                    self._leaf_clip_axes(),
                )
            return leader_shard_update(
                params, opt_state, grad_shards, self._update_fn, self.hyper,
                self.axis_name,
            )
        summed = self._aggregate(grads, payloads)
        return self._update(params, opt_state, summed)

    def _fused_allreduce_tree(self, grads, codec_state):
        """Per-leaf collective-protocol aggregation (codec declares
        ``supports_fused_allreduce``, e.g. PowerSGD's two-psum shared-Q
        form): returns ``(summed, new_codec_state)``. Runs inside
        shard_map; the module-level :func:`fused_allreduce_tree` is the
        one implementation (dp.py's functional step shares it)."""
        return fused_allreduce_tree(
            self.code, grads, codec_state, self.axis_name, self.average,
            self.size, self.comm_dtype,
            leaf_axes=None if self._uniform_agg else self._leaf_agg_axes,
            leaf_sizes=None if self._uniform_agg else self._leaf_agg_sizes,
        )

    def _encode_aggregate_update(self, params, opt_state, codec_state,
                                 grads, rng):
        """The ONE seam every step builder (fused, accum, grads-only,
        scan) lowers through: encode → aggregate → update, dispatching
        on the codec's collective capability."""
        if self.code.supports_fused_allreduce:
            summed, new_codec_state = self._fused_allreduce_tree(
                grads, codec_state
            )
            new_params, new_opt_state = self._update(params, opt_state, summed)
            return new_params, new_opt_state, new_codec_state
        if self._bucket_plan is not None:
            return self._bucketed_encode_aggregate_update(
                params, opt_state, codec_state, grads, rng
            )
        payloads, new_codec_state = self._encode_tree(grads, codec_state, rng)
        new_params, new_opt_state = self._aggregate_update(
            params, opt_state, grads, payloads
        )
        return new_params, new_opt_state, new_codec_state

    def _numerics_vec(self, old_params, new_params, grads, codec_state):
        """On-device numerics statistics, computed INSIDE the lowered
        step (runs under shard_map; XLA fuses the reductions into the
        surrounding program). Returns one f32 vector::

            [grad_sumsq, nonfinite, update_sumsq, param_sumsq,
             ef_residual_sumsq, *per_bucket_sumsq]

        grad sums are finite-masked (a NaN element must not erase the
        healthy part's norm) and psum'd across the data axis — the
        GLOBAL gradient energy and total NaN/Inf count; update/param
        sums read the replicated params, no collective needed."""
        def finite_sumsq(x):
            xf = x.astype(jnp.float32)
            return jnp.sum(jnp.square(jnp.where(jnp.isfinite(xf), xf, 0.0)))

        leaves = jax.tree.leaves(grads)
        gss = sum(finite_sumsq(g) for g in leaves)
        nonf = sum(
            jnp.sum(~jnp.isfinite(g.astype(jnp.float32))).astype(jnp.float32)
            for g in leaves
        )
        gss = lax.psum(gss, self.axis_name)
        nonf = lax.psum(nonf, self.axis_name)
        upd = sum(
            jnp.sum(jnp.square((n.astype(jnp.float32)
                                - o.astype(jnp.float32))))
            for o, n in zip(jax.tree.leaves(old_params),
                            jax.tree.leaves(new_params))
        )
        psq = sum(
            jnp.sum(jnp.square(o.astype(jnp.float32)))
            for o in jax.tree.leaves(old_params)
        )
        if isinstance(self.code, ErrorFeedback):
            flat_states = jax.tree.structure(self.params).flatten_up_to(
                codec_state
            )
            ef = sum(
                jnp.sum(jnp.square(st["memory"].astype(jnp.float32)))
                for st in flat_states
            )
            ef = lax.psum(ef, self.axis_name)
        else:
            ef = jnp.float32(0.0)
        parts = [gss, nonf, upd, psq, ef]
        if self._bucket_plan is not None:
            parts.extend(
                lax.psum(finite_sumsq(b), self.axis_name)
                for b in flatten_into_buckets(self._bucket_plan, grads)
            )
        return jnp.stack([jnp.asarray(p, jnp.float32) for p in parts])

    def _fill_numerics(self, data: Dict[str, float], nvec) -> None:
        """Unpack the fetched stats vector into the step's metrics dict
        (the one device fetch the numerics leg costs per step)."""
        v = np.asarray(nvec, np.float32)
        data["grad_norm"] = float(np.sqrt(v[0]))
        data["nonfinite_total"] = float(v[1])
        data["update_ratio"] = float(np.sqrt(v[2])) / max(
            float(np.sqrt(v[3])), 1e-30
        )
        if isinstance(self.code, ErrorFeedback):
            data["ef_residual_norm"] = float(np.sqrt(v[4]))
        if self._bucket_plan is not None:
            data["bucket_grad_norms"] = [
                float(np.sqrt(x)) for x in v[5:]
            ]

    def _opt_state_spec(self):
        """shard_map PartitionSpec pytree for the optimizer state: sharded
        over the mesh axis in leader mode (ZeRO-1); with param_specs the
        params-mirroring fields (momentum/adam moments) inherit each
        param's model sharding; replicated otherwise."""
        if self.mode == "leader":
            return leader_state_spec(
                self.opt_state, self.axis_name,
                self.param_specs if self._model_parallel else None,
            )
        if not self._model_parallel:
            return P()
        if isinstance(self.opt_state, AdafactorState):
            # factored moments are NOT param-shaped: v_row/v_col carry
            # the leaf's spec minus the deleted (unsharded) factored
            # dim — a replicated spec here broadcasts global state
            # against shard-local updates (shape corruption)
            return adafactor_state_specs(self.params, self.param_specs)
        ptd = jax.tree.structure(self.params)
        pshapes = [x.shape for x in jax.tree.leaves(self.params)]

        def field_spec(val):
            lv = jax.tree.leaves(val)
            if (jax.tree.structure(val) == ptd
                    and [x.shape for x in lv] == pshapes):
                return self.param_specs
            return jax.tree.map(lambda _: P(), val)

        return type(self.opt_state)(*[field_spec(v) for v in self.opt_state])

    def _place_state(self) -> None:
        """Put params, optimizer state and codec state on the mesh with the
        shardings every step program returns them in, so that the first
        step is the program of every later one. Left as the caller made
        them (on one device, or on the host) they trace the step with
        another input type than its own outputs have, and the step
        compiled twice: ~30 s each for BERT-base on one v5e chip, ~72 s
        each over four (my chip runs, PRs 22 and 28). An array that
        already lies so is re-used, not copied. A mesh that spans
        processes is left alone: there each process hands jit its own
        host-local copy."""
        if self.mesh.is_multi_process:
            return
        from jax.sharding import NamedSharding

        def put(tree, specs):
            shardings = jax.tree.map(
                lambda sp: NamedSharding(self.mesh, sp), specs,
                is_leaf=lambda x: isinstance(x, P))
            return jax.device_put(tree, shardings)

        self.params = put(
            self.params, self.param_specs if self._model_parallel else P())
        self.opt_state = put(self.opt_state, self._opt_state_spec())
        self.codec_state = put(self.codec_state, self._codec_spec)

    # -- compiled step builders -------------------------------------------
    def _jit_spmd(self, fn, in_specs, out_specs, donate: bool = False):
        """``jax.jit(jax.shard_map(fn))`` over this optimizer's mesh: the
        ONE place a step program (fused, accumulating, grads-only,
        scanned, or an instrumented stage) is handed to the compiler, so
        that every path gets the same ``compiler_options`` — those of
        ``comms.async_allreduce_options`` on more than one TPU, none
        anywhere else (the program and its compile-cache entry are then
        what they were without this helper), and none where the exchange
        is already in flat buckets of this optimizer's own making
        (``bucket_mb`` > 0): there the options were a loss, the
        ``bucket_mb`` 16 step of BERT-base on four v5e chips taking
        62.18 ms with them and 60.06 without (by the wall, my chip runs
        X3 and R1, PR 28; 48.0 per leaf), so a bucketed program is
        compiled as it always was. ``.lower(...).compile()``
        of the result carries the options too, so
        :meth:`step_memory_analysis` describes the program that runs.
        ``donate`` donates params / optimizer state / codec state
        (arguments 0-2) where ``donate_buffers`` is set: the outputs
        re-use their buffers, cutting peak HBM by one params+opt-state
        copy (see ``donate_buffers`` in ``__init__``)."""
        return jax.jit(
            jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False),
            donate_argnums=(0, 1, 2) if donate and self.donate_buffers else (),
            compiler_options=None if self.bucket_mb > 0 else
            comms.async_allreduce_options(self.mesh, self._agg_axes),
        )

    def _step_program(self, key, path: str, build: Callable[[], Callable]):
        """The program of ``key``: the ONE place the fused, the
        grads-only and the accumulating step look theirs up. On a miss
        what comes back builds the program and makes its FIRST call —
        the trace, the lowering and the compile or the cache load are
        all inside it — under one ``setup.step_build`` span of the
        set-up log (``key``: the ``path``; ``program``: the jit's name,
        which a device trace's ``XLA Modules`` line shows as
        ``jit_spmd(<hash>)`` and the log's ``compile.program`` row
        carries). On a hit, the program and nothing else."""
        fn = self._compiled.get(key)
        if fn is not None:
            return fn

        def first_call(*args):
            with setup_span("setup.step_build", key=path) as row:
                fn = self._compiled[key] = build()
                row["program"] = f"jit({fn.__name__})"
                return fn(*args)

        return first_call

    def _build_instrumented_stages(self, loss_fn, has_aux: bool = False,
                                   accum_steps: int = 0):
        """Pipeline as four separately-dispatched programs so host timers
        can fill the reference's per-stage schema (``ps.py:116-148``) with
        real wall times: encode → collective → decode+sum → update.
        Slower than the fused path (extra dispatches + no cross-stage
        fusion); for measurement, not production.

        ``has_aux`` stages the aux pmean into the grad stage (mutable-state
        models under instrument). ``accum_steps > 0``
        makes the grad stage the microbatch-accumulation scan — one fused
        program by design, so instrument reports its total wall plus a
        per-microbatch mean, while the encode/comm/decode/update stages
        time exactly as in the plain step."""
        axis = self.axis_name
        state_spec = jax.tree.map(lambda _: P(axis), self.codec_state)
        grads_spec = jax.tree.map(lambda _: P(axis), self.params)

        if accum_steps:
            def grad_spmd(params, batches):
                loss, grads = _accumulate_grads(
                    loss_fn, accum_steps, params, batches, axis,
                    reduce_loss=self._reduce_loss,
                )
                return loss, jax.tree.map(lambda g: g[None], grads)

            grad_in, grad_out = (P(), P(None, axis)), (P(), grads_spec)
        elif has_aux:
            def grad_spmd(params, aux, batch):
                (loss, new_aux), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, aux, batch)
                new_aux = jax.tree.map(lambda x: lax.pmean(x, axis), new_aux)
                return (
                    self._reduce_loss(loss),
                    jax.tree.map(lambda g: g[None], grads),
                    new_aux,
                )

            grad_in, grad_out = (P(), P(), P(axis)), (P(), grads_spec, P())
        else:
            def grad_spmd(params, batch):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                return self._reduce_loss(loss), jax.tree.map(
                    lambda g: g[None], grads
                )

            grad_in, grad_out = (P(), P(axis)), (P(), grads_spec)

        grad_fn = self._jit_spmd(
            grad_spmd, grad_in, grad_out) if loss_fn is not None else None

        def encode_spmd(grads_stacked, codec_state, rng):
            grads = jax.tree.map(lambda x: x[0], grads_stacked)
            payloads, new_state = encode_tree(self.code, grads, codec_state, rng, axis)
            return jax.tree.map(lambda x: x[None], payloads), new_state

        payload_spec = jax.tree.map(lambda _: P(axis), self._payload_struct())
        encode_fn = self._jit_spmd(
            encode_spmd, (grads_spec, state_spec, P()),
            (payload_spec, state_spec))

        def gather_spmd(payloads_stacked):
            local = jax.tree.map(lambda x: x[0], payloads_stacked)
            return jax.tree.map(lambda x: lax.all_gather(x, axis), local)

        def sum_spmd(grads_stacked):
            grads = jax.tree.map(lambda x: x[0], grads_stacked)
            if self._bucket_plan is not None:
                # measure the same launch-fused collective topology the
                # fused step runs (one psum per bucket, not per leaf)
                return bucketed_aggregate(
                    self.code, grads, self._bucket_plan, axis, False,
                    self.size, self.comm_dtype,
                )
            return aggregate(
                self.code, grads, None, axis, False, self.size, self.comm_dtype
            )

        def update_spmd(params, opt_state, summed):
            if self.average:
                summed = jax.tree.map(lambda x: x / self.size, summed)
            # self._update includes the mode='leader' broadcast, so the
            # instrumented optim_step_time covers the same collective the
            # fused path pays; run under shard_map so the axis is bound.
            return self._update(params, opt_state, summed)

        opt_spec = self._opt_state_spec()

        return {
            "grad": grad_fn,
            "encode": encode_fn,
            "gather": self._jit_spmd(gather_spmd, (payload_spec,), P()),
            "psum": self._jit_spmd(sum_spmd, (grads_spec,), P()),
            "decode": jax.jit(
                lambda gathered: jax.tree.unflatten(
                    jax.tree.structure(self.params),
                    [
                        decode_sum_payloads(self.code, pl, p.shape, p.dtype)
                        for p, pl in zip(
                            jax.tree.leaves(self.params),
                            jax.tree.structure(self.params).flatten_up_to(gathered),
                        )
                    ],
                )
            ),
            "update": self._jit_spmd(update_spmd, (P(), opt_spec, P()),
                                     (P(), opt_spec)),
        }

    def _payload_struct(self):
        """Shape-structs of the stacked (leading local-shard axis of 1)
        per-worker payload pytree, used as shard_map out_specs prefix."""
        def leaf(p, sp):
            lshape = _local_shape(p.shape, sp, self.mesh)
            payload, _ = jax.eval_shape(
                lambda: self.code.encode(
                    jnp.zeros(lshape, p.dtype),
                    self.code.init_state(lshape, p.dtype),
                    jax.random.key(0) if self.code.needs_rng else None,
                )
            )
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((1,) + s.shape, s.dtype), payload
            )
        return jax.tree.map(leaf, self.params, self.param_specs)

    def _step_instrumented(self, data, rng, grads=None, loss_fn=None,
                           batch=None, aux_state=None, microbatches=None):
        """Staged pipeline with host-side timing (reference schema,
        ``ps.py:116-148``)."""
        has_aux = aux_state is not None
        accum_steps = (
            int(jax.tree.leaves(microbatches)[0].shape[0])
            if microbatches is not None else 0
        )
        key = ("instr", _fn_cache_key(loss_fn), has_aux, accum_steps)
        if key not in self._compiled:
            self._compiled[key] = self._build_instrumented_stages(
                loss_fn, has_aux, accum_steps
            )
        stages = self._compiled[key]
        timer = time.perf_counter
        loss = None

        # the staged pipeline's collective topology differs from the
        # fused lowering _schema_dict describes (it always full-psums or
        # payload-gathers; never the dense/psum scatter): relabel so the
        # reported bytes match the comm_wait actually measured
        w, frac = self.size, (self.size - 1) / self.size
        n = float(_tree_bytes(self.params))
        if self.code.supports_psum:
            wire_dt = self.comm_dtype if self.comm_dtype is not None else (
                getattr(self.code, "wire_dtype", None)
            )
            data["wire_lowering"] = "psum_staged"
            data["wire_bytes_per_worker"] = 2 * frac * self._tree_wire_bytes(
                wire_dt
            )
        else:
            # the staged encode/gather stages run PER LEAF even when a
            # bucket plan is active (only the psum stage is bucketed), so
            # the reported bytes/launches must describe the per-leaf
            # topology actually measured — not the fused step's buckets
            data["wire_lowering"] = "payload_gather_staged"
            data["wire_bytes_per_worker"] = (
                (w - 1) * self._payload_bytes_per_leaf
            )
            data["packaged_bytes"] = self._payload_bytes_per_leaf
            data["bucket_count"] = 0.0
            data["agg_launches"] = float(len(self._spec_leaves))
        if self.mode == "leader":
            # the staged update stage all_gathers the sharded params back
            data["wire_bytes_per_worker"] += frac * n

        if accum_steps:
            t0 = timer()
            loss, grads = stages["grad"](self.params, microbatches)
            jax.block_until_ready(grads)
            data["grad_time"] = timer() - t0
            # the scan is one fused program by design; the per-microbatch
            # mean is the documented estimate, not a separable wall
            data["grad_time_per_microbatch"] = data["grad_time"] / accum_steps
        elif loss_fn is not None:
            t0 = timer()
            if has_aux:
                loss, grads, new_aux = stages["grad"](
                    self.params, aux_state, batch
                )
                self.aux_state = new_aux
            else:
                loss, grads = stages["grad"](self.params, batch)
            jax.block_until_ready(grads)
            data["grad_time"] = timer() - t0

        t0 = timer()
        payloads, new_codec_state = stages["encode"](grads, self.codec_state, rng)
        jax.block_until_ready(payloads)
        data["code_wait"] = timer() - t0          # reference ps.py:138

        if self.code.supports_psum:
            t0 = timer()
            summed = stages["psum"](grads)
            jax.block_until_ready(summed)
            data["comm_wait"] = timer() - t0      # reference ps.py:162
        else:
            t0 = timer()
            gathered = stages["gather"](payloads)
            data["isend_time"] = timer() - t0     # dispatch (ps.py:148)
            jax.block_until_ready(gathered)
            data["comm_wait"] = timer() - t0
            t0 = timer()
            summed = stages["decode"](gathered)
            jax.block_until_ready(summed)
            data["decode_time"] = timer() - t0    # reference ps.py:168

        t0 = timer()
        self.params, self.opt_state = stages["update"](
            self.params, self.opt_state, summed
        )
        jax.block_until_ready(self.params)
        data["optim_step_time"] = timer() - t0    # reference ps.py:191
        self.codec_state = new_codec_state
        return loss

    def _build_grad_step(self, loss_fn, has_aux: bool = False):
        """Fused grad→encode→collective→decode→update step.

        With ``has_aux``, ``loss_fn(params, aux_state, batch) -> (loss,
        new_aux_state)`` supports mutable-state models (flax
        ``batch_stats``): each step's per-worker aux is cross-replica
        averaged with ``pmean``. By default that averages only the
        *running* stats — normalization inside the forward still uses
        per-replica batch statistics (plain per-device BN). For TRUE
        SyncBatchNorm semantics, build the model with its BN axis bound
        to this optimizer's data axis (e.g. ``ResNet(norm='batch',
        bn_axis='data')``): flax's BatchNorm then psum-averages the batch
        statistics across replicas inside this shard_map, matching a
        single device seeing the global batch (equivalence tested in
        ``tests/test_models.py::test_syncbn_matches_global_batch_oracle``)."""
        axis = self.axis_name

        def spmd(params, opt_state, codec_state, batch, rng, *maybe_aux):
            if has_aux:
                (loss, new_aux), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, maybe_aux[0], batch)
                new_aux = jax.tree.map(lambda x: lax.pmean(x, axis), new_aux)
            else:
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                new_aux = ()
            loss = self._reduce_loss(loss)
            new_params, new_opt_state, new_codec_state = (
                self._encode_aggregate_update(
                    params, opt_state, codec_state, grads, rng
                )
            )
            out = (new_params, new_opt_state, new_codec_state, loss, new_aux)
            if self.numerics:
                out += (self._numerics_vec(params, new_params, grads,
                                           new_codec_state),)
            return out

        state_spec = self._codec_spec
        opt_spec = self._opt_state_spec()
        pspec = self.param_specs if self._model_parallel else P()
        in_specs = (pspec, opt_spec, state_spec, self.batch_spec, P()) + (
            (P(),) if has_aux else ()
        )
        out_specs = (pspec, opt_spec, state_spec, P(), P()) + (
            (P(),) if self.numerics else ()
        )
        return self._jit_spmd(spmd, in_specs, out_specs, donate=True)

    def _build_accum_grad_step(self, loss_fn, accum_steps: int):
        """Gradient accumulation: each worker scans ``accum_steps``
        microbatches, summing local grads, then one aggregate+update.
        Trades HBM (no giant activation batch) for sequential compute —
        the standard big-model batch-scaling tool the reference never
        needed at MNIST scale."""
        axis = self.axis_name

        def spmd(params, opt_state, codec_state, batches, rng):
            loss, grads = _accumulate_grads(
                loss_fn, accum_steps, params, batches, axis,
                reduce_loss=self._reduce_loss,
            )
            new_params, new_opt_state, new_codec_state = (
                self._encode_aggregate_update(
                    params, opt_state, codec_state, grads, rng
                )
            )
            out = (new_params, new_opt_state, new_codec_state, loss)
            if self.numerics:
                out += (self._numerics_vec(params, new_params, grads,
                                           new_codec_state),)
            return out

        state_spec = self._codec_spec
        opt_spec = self._opt_state_spec()
        pspec = self.param_specs if self._model_parallel else P()
        mb_spec = P(*((None,) + tuple(self.batch_spec)))
        out_specs = (pspec, opt_spec, state_spec, P()) + (
            (P(),) if self.numerics else ()
        )
        return self._jit_spmd(
            spmd, (pspec, opt_spec, state_spec, mb_spec, P()), out_specs,
            donate=True)

    def step_memory_analysis(
        self, loss_fn: Callable, batch: PyTree, rng=None,
        aux_state: PyTree = None,
    ) -> Dict[str, Optional[int]]:
        """HBM footprint of the fused step from XLA's own buffer
        assignment (``compiled.memory_analysis()``), available before
        the step has run and on backends whose ``memory_stats()`` is
        None (XLA:CPU): ``donate_buffers`` shows up as
        ``alias_size_in_bytes`` (outputs re-using argument buffers), so
        ``argument + output + temp - alias`` estimates the step's peak
        working set either way. Beside it, ``collectives`` and
        ``async_collectives``: how many collectives the optimized
        program runs and how many of them are asynchronous
        (``comms.count_scheduled_collectives``; 0 of 5 in BERT-base over
        four chips before the overlapping schedule, 51 of 52 with it),
        with one ``ps.step_program`` row in the set-up log each time
        they are read. Pass ``aux_state`` iff the step does
        (the loss_fn signature changes with it). NOTE the first call
        per loss_fn pays a full AOT compile — ``jitted.lower()`` does
        not consult the jit dispatch cache — so the compiled object is
        memoized here for repeat calls."""
        has_aux = aux_state is not None
        key = ("grad", _fn_cache_key(loss_fn), has_aux)
        if key not in self._compiled:
            self._compiled[key] = self._build_grad_step(loss_fn, has_aux)
        rng = jax.random.key(0) if rng is None else rng
        extra = (aux_state,) if has_aux else ()
        # the batch's avals join the key — jit keys its dispatch cache
        # the same way, and without them a second call with a larger
        # batch would silently return the first batch's footprint
        batch_avals = tuple(
            (getattr(l, "shape", ()), str(jnp.asarray(l).dtype))
            for l in jax.tree.leaves((batch,) + extra)
        )
        ma_key = ("memory_analysis",) + key + (batch_avals,)
        if ma_key not in self._compiled:
            self._compiled[ma_key] = self._compiled[key].lower(
                self.params, self.opt_state, self.codec_state, batch, rng,
                *extra
            ).compile()
        self._analysed = self._compiled[ma_key]
        ma = self._analysed.memory_analysis()
        out = {
            k: int(getattr(ma, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes")
            if getattr(ma, k, None) is not None
        }
        if {"argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes"} <= out.keys():
            out["estimated_peak_bytes"] = (
                out["argument_size_in_bytes"] + out["output_size_in_bytes"]
                + out["temp_size_in_bytes"] - out.get("alias_size_in_bytes", 0)
            )
        # whether the overlapping schedule engaged: the program's
        # collectives, and how many of them run beside other work
        out.update(comms.count_scheduled_collectives(self._analysed.as_text()))
        setup_event("ps.step_program",
                    collectives=out["collectives"],
                    async_collectives=out["async_collectives"])
        return out

    def step_program_text(self) -> Optional[str]:
        """The optimized HLO text of the step program
        ``step_memory_analysis`` last compiled (None before its first
        call). A device trace names an operation by its instruction and
        nothing else; this text gives every instruction's ``op_name``,
        which carries the ``jax.named_scope`` it was traced under —
        the way from a trace event to a scope of the model."""
        compiled = getattr(self, "_analysed", None)
        return None if compiled is None else compiled.as_text()

    def step_accumulate(
        self, loss_fn: Callable, microbatches: PyTree,
    ) -> Tuple[jax.Array, Dict[str, float]]:
        """One optimizer step over ``accum_steps`` microbatches per worker.
        ``microbatches`` leaves are ``[accum_steps, global_batch, ...]``;
        returns ``(mean_loss, data)``.

        ``instrument=True`` stage-times this path like :meth:`step`: the
        accumulation scan is one fused program (grad stage), timed whole
        with a per-microbatch mean in ``grad_time_per_microbatch``; the
        encode/comm/decode/update stages get real per-stage walls."""
        accum_steps = int(jax.tree.leaves(microbatches)[0].shape[0])
        if self.instrument:
            t0 = time.perf_counter()
            data = self._schema_dict()
            data["accum_steps"] = float(accum_steps)
            self._rng, rng = jax.random.split(self._rng)
            loss = self._step_instrumented(
                data, rng, loss_fn=loss_fn, microbatches=microbatches
            )
            self._step_count += 1
            data["step_time"] = time.perf_counter() - t0
            self._record_step("ps.step_accumulate", data)
            return loss, data
        fn = self._step_program(
            ("accum", _fn_cache_key(loss_fn), accum_steps), "accum",
            lambda: self._build_accum_grad_step(loss_fn, accum_steps))
        t0 = time.perf_counter()
        data = self._schema_dict()
        data["accum_steps"] = float(accum_steps)
        self._rng, rng = jax.random.split(self._rng)
        out = fn(
            self.params, self.opt_state, self.codec_state, microbatches, rng
        )
        if self.numerics:
            (self.params, self.opt_state, self.codec_state, loss,
             nvec) = out
            self._fill_numerics(data, nvec)
        else:
            self.params, self.opt_state, self.codec_state, loss = out
        jax.block_until_ready(self.params)
        self._step_count += 1
        data["step_time"] = time.perf_counter() - t0
        self._record_step("ps.step_accumulate", data)
        return loss, data

    def _build_grads_only_step(self):
        """Aggregation-only step: caller supplies per-worker grads stacked
        on a leading [world] axis (the reference's usage: backward already
        ran, ``step`` only aggregates + updates)."""
        axis = self.axis_name

        def spmd(params, opt_state, codec_state, grads_stacked, rng):
            grads = jax.tree.map(lambda x: x[0], grads_stacked)  # local shard
            new_params, new_opt_state, new_codec_state = (
                self._encode_aggregate_update(
                    params, opt_state, codec_state, grads, rng
                )
            )
            out = (new_params, new_opt_state, new_codec_state)
            if self.numerics:
                out += (self._numerics_vec(params, new_params, grads,
                                           new_codec_state),)
            return out

        state_spec = self._codec_spec
        grads_spec = jax.tree.map(lambda _: P(axis), self.params)
        opt_spec = self._opt_state_spec()
        out_specs = (P(), opt_spec, state_spec) + (
            (P(),) if self.numerics else ()
        )
        return self._jit_spmd(
            spmd, (P(), opt_spec, state_spec, grads_spec, P()), out_specs,
            donate=True)

    def _schema_dict(self) -> Dict[str, float]:
        """The reference's per-step metrics schema (``ps.py:116-148,
        162-191``), initialized; step paths fill in what they can
        observe. The byte fields are static per instance, computed once
        in ``__init__`` (``payload_bits`` eval-shapes every leaf — too
        expensive to re-derive per step)."""
        lowering, wire_bytes = self._wire_accounting
        return {
            "code_wait": 0.0,
            "iallgather_prepare_time": 0.0,  # compile-time now (static shapes)
            "isend_time": 0.0,
            "comm_wait": 0.0,
            "decode_time": 0.0,
            "optim_step_time": 0.0,
            "msg_bytes": float(_tree_bytes(self.params)),
            "packaged_bytes": self._payload_bytes,
            "wire_lowering": lowering,
            "wire_bytes_per_worker": wire_bytes,
            # flat-bucket aggregation accounting (bucketing.py): 0 buckets
            # means the per-leaf path; agg_launches is the per-step
            # collective launch count of the aggregation stage
            "bucket_count": float(
                self._bucket_plan.num_buckets
                if self._bucket_plan is not None else 0
            ),
            "bucket_bytes_total": self._bucket_bytes_total,
            "agg_launches": float(self._agg_units),
        }

    def _record_step(self, name: str, data: Dict[str, float]) -> None:
        """Mirror one step's metrics dict into the run-wide
        FlightRecorder as a span ending now — the reference's returned-
        timings contract joining the unified timeline. Disabled
        telemetry costs exactly this method's None-check."""
        rec = get_recorder()
        if rec is None:
            return
        dur = float(data.get("step_time", 0.0))
        rec.event(
            name, kind="span", ts=time.monotonic() - dur, dur=dur,
            step=self._step_count,
            **{k: v for k, v in data.items()
               if isinstance(v, (int, float, str))},
        )

    # -- public API --------------------------------------------------------
    def step(
        self,
        grads: Optional[PyTree] = None,
        *,
        loss_fn: Optional[Callable] = None,
        batch: Optional[PyTree] = None,
        aux_state: Optional[PyTree] = None,
        closure: Optional[Callable] = None,
    ) -> Tuple[Optional[jax.Array], Dict[str, float]]:
        """Run one distributed step; returns ``(loss, data)`` exactly like
        the reference (``ps.py:193`` — its known deviation from the torch
        Optimizer contract, kept deliberately for API parity).

        Either pass ``loss_fn`` + ``batch`` (fused grad+aggregate+update),
        or pass ``grads`` stacked per-worker on a leading ``[world]`` axis
        (aggregation-only, the reference's own division of labor).
        ``closure`` is accepted for signature parity (``ps.py:110-112``)
        and invoked for its loss value if given.

        ``comm_wait`` (the reference's collective-wait metric,
        ``ps.py:162``) reads 0.0 here: the fused program has no stage the
        host could time. ``instrument=True`` fills it, with the other
        per-stage walls, by running the stages as separate programs; the
        fused program's own comm/compute split comes from a device trace
        (``chipbench.run --trace 1``: ``coll.time_ms`` /
        ``coll.exposed_ms``) and from
        ``step_memory_analysis()["collectives" / "async_collectives"]``.

        **One step in flight** (fused ``loss_fn`` + ``batch`` path). The
        call launches step n and returns without waiting for it: ``loss``
        is the ``jax.Array`` the program will fill, usually not ready
        yet, and ``params`` / ``opt_state`` / ``codec_state`` are assigned
        at once — whoever reads them (``state_dict``, a checkpoint,
        ``float(loss)``) waits through JAX as with any jitted function.
        What the call does wait for is step n-1, so at most one step is
        queued behind the running one and the host prepares step n+1
        while the device works. ``data["step_time"]`` is entry to return
        of the call: in a loop the device's period; the first call after
        the device has drained reads short (the launch alone).
        ``data["host_ahead"]`` is 1.0 when step n-1 was still running as
        the host came to wait for it (the device had its next program
        queued and never waited for the host), else 0.0.
        A call that needs THIS step's values on the host still waits for
        this step: a ``numerics`` monitor, a ``closure``; so does the ``grads=`` path, ``instrument=True``,
        :meth:`step_accumulate` and :meth:`run_steps`.
        """
        t0 = time.perf_counter()
        loss = None

        if self.instrument:
            data = self._schema_dict()
            self._rng, rng = jax.random.split(self._rng)
            if loss_fn is None and grads is None:
                raise ValueError("pass grads or loss_fn+batch")
            if loss_fn is not None and batch is None:
                raise ValueError("loss_fn requires batch")
            if loss_fn is None and aux_state is not None:
                raise NotImplementedError(
                    "aux_state requires the loss_fn path (grads-only steps "
                    "have no forward pass to produce new aux state)"
                )
            loss = self._step_instrumented(
                data, rng, grads=grads, loss_fn=loss_fn, batch=batch,
                aux_state=aux_state,
            )
            if closure is not None:
                loss = closure()
            data["step_time"] = time.perf_counter() - t0
            self._step_count += 1
            self._record_step("ps.step", data)
            return loss, data

        # The fused program: one span around the step and one around each
        # host phase of it (telemetry.span does nothing while the recorder
        # is off), so that a device trace says what the host was in
        # whenever the chip waited.
        with span("ps.step", step=self._step_count + 1) as attrs:
            with span("ps.prepare"):
                data = self._schema_dict()
                self._rng, rng = jax.random.split(self._rng)
                has_aux = aux_state is not None
                if loss_fn is not None:
                    if batch is None:
                        raise ValueError("loss_fn requires batch")
                    fn = self._step_program(
                        ("grad", _fn_cache_key(loss_fn), has_aux), "fused",
                        lambda: self._build_grad_step(loss_fn, has_aux))
                    args = (self.params, self.opt_state, self.codec_state,
                            batch, rng) + ((aux_state,) if has_aux else ())
                elif grads is not None:
                    if has_aux:
                        raise NotImplementedError(
                            "aux_state requires the loss_fn path (grads-only "
                            "steps have no forward pass to produce new aux "
                            "state)"
                        )
                    if self._model_parallel:
                        raise NotImplementedError(
                            "grads-only steps are not supported with "
                            "param_specs: a host-side [world, ...] gradient "
                            "stack is ambiguous for model-sharded leaves — "
                            "use the loss_fn path"
                        )
                    fn = self._step_program(
                        ("grads-only",), "grads",
                        self._build_grads_only_step)
                    args = (self.params, self.opt_state, self.codec_state,
                            grads, rng)
                else:
                    raise ValueError("pass grads or loss_fn+batch")
            with span("ps.dispatch"):
                out = fn(*args)
            # the donated buffers die with their last reference: here,
            # while the device runs, and not as this frame is left
            del args
            if self.numerics:
                *out, nvec = out
                self._fill_numerics(data, nvec)
            if loss_fn is not None:
                (self.params, self.opt_state, self.codec_state, loss,
                 new_aux) = out
                if has_aux:
                    self.aux_state = new_aux
            else:
                self.params, self.opt_state, self.codec_state = out

            if closure is not None:
                loss = closure()

            # Keep one step in flight: wait for the step BEFORE this one
            # (its loss is the output this step did not donate), unless
            # the call asked for this step's values on the host.
            own = (loss_fn is None or self.numerics
                   or closure is not None)
            waits_for, self._in_flight = (
                (self.params, None) if own else (self._in_flight, loss))
            with span("ps.wait") as waited:
                # still running as the host arrives: the host kept ahead
                data["host_ahead"] = float(
                    not own and waits_for is not None
                    and not waits_for.is_ready())
                if waited is not None:
                    waited["host_ahead"] = data["host_ahead"]
                jax.block_until_ready(waits_for)
            # The fused program has no separable comm/decode/update stages
            # — step_time is entry to return of this call (see the
            # docstring); instrument=True (separate mode) fills the
            # per-stage keys with host wall times.
            data["step_time"] = time.perf_counter() - t0
            self._step_count += 1
            if attrs is not None:
                attrs.update((k, v) for k, v in data.items()
                             if isinstance(v, (int, float, str)))
        return loss, data

    def _map_adam(self, fn, opt_state=None):
        """``opt_state`` (default: this optimizer's) with ``fn`` applied
        to its ``AdamState``, bare or inside leader mode's state."""
        state = self.opt_state if opt_state is None else opt_state
        if isinstance(state, AdamState):
            return fn(state)
        if isinstance(getattr(state, "inner", None), AdamState):
            return state._replace(inner=fn(state.inner))
        return state

    def state_dict(self, legacy_adam: bool = False) -> Dict[str, Any]:
        """Checkpointable state in this repo's schema (params/opt_state/
        codec_state/aux_state/step_count/rng) — the role of torch's
        ``Optimizer.state_dict()`` (which the reference inherited but never
        called, SURVEY §5.4), NOT its format: there is no
        ``state``/``param_groups`` layout and the dict holds live array
        references, not copies, so it is not interchangeable with torch
        checkpoints. Pair with ``utils.checkpoint.CheckpointManager`` for
        sharded on-disk saves. ``legacy_adam`` gives the shape of a
        checkpoint written when Adam's state always held the AMSGrad
        maximum (a restore template; ``load_state_dict`` drops the dead
        tree again)."""
        return {
            "params": self.params,
            "opt_state": tuple(self._map_adam(
                lambda s: s._replace(max_exp_avg_sq=s.exp_avg_sq)
                if legacy_adam and s.max_exp_avg_sq == () else s)),
            "codec_state": self.codec_state,
            "aux_state": self.aux_state,
            "step_count": self._step_count,
            "rng_data": jax.random.key_data(self._rng),
        }

    def _decommit_restored(self, tree: PyTree) -> PyTree:
        """Make a restored checkpoint tree steppable on this mesh.

        A restore can hand back arrays committed to the WRONG device set
        (e.g. a single device from the numpy fallback, or a stale
        sharding), which the compiled shard_map step rejects. Leaves
        already committed to exactly this mesh's devices (the common
        orbax case — StandardRestore with a correctly-sharded template,
        incl. ZeRO-1's sharded opt_state) are kept as-is, zero copies;
        everything else is gathered to host numpy in ONE batched
        ``jax.device_get`` (uncommitted, so the next step reshards it)."""
        mesh_devs = set(self.mesh.devices.flat)
        leaves, treedef = jax.tree.flatten(tree)

        def keeps(x):
            if not hasattr(x, "ndim"):
                return True  # python scalar
            devs = getattr(x, "devices", None)
            if devs is None:
                return True  # host numpy already
            try:
                return set(devs()) == mesh_devs
            except Exception:
                return False

        flags = [keeps(l) for l in leaves]
        fetched = iter(jax.device_get(
            [l for l, k in zip(leaves, flags) if not k]
        ))
        out = [l if k else next(fetched) for l, k in zip(leaves, flags)]
        return jax.tree.unflatten(treedef, out)

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        self.params = self._decommit_restored(sd["params"])
        self.opt_state = self._map_adam(
            lambda s: s if self.hyper.amsgrad else s._replace(
                max_exp_avg_sq=()),
            type(self.opt_state)(
                *self._decommit_restored(tuple(sd["opt_state"]))))
        self.codec_state = self._decommit_restored(sd["codec_state"])
        self.aux_state = self._decommit_restored(sd.get("aux_state"))
        self._place_state()  # or the next step is compiled again
        self._step_count = int(sd["step_count"])
        # rng too: a restored key committed to the restore sharding would
        # commit every subsequent step's rng arg and poison jit's device
        # resolution against uncommitted batches
        self._rng = jax.random.wrap_key_data(
            jnp.asarray(np.asarray(sd["rng_data"]))
        )

    def run_steps(
        self, loss_fn: Callable, batches: PyTree, *, unroll: int = 1
    ) -> Tuple[jax.Array, Dict[str, float]]:
        """Run N training steps as ONE fused XLA program (``lax.scan`` over
        the step pipeline inside shard_map), amortizing per-step host
        dispatch — the TPU-native answer to the reference's thread-pool
        overlap: nothing to overlap on the host because the host is out of
        the loop entirely.

        ``batches``: pytree whose leaves are stacked ``[n_steps,
        global_batch, ...]``. Returns ``(losses[n_steps], data)``.
        """
        axis = self.axis_name

        key = ("scan", _fn_cache_key(loss_fn), unroll)
        if key not in self._compiled:
            def spmd(params, opt_state, codec_state, batches, rng):
                def one_step(carry, batch_and_key):
                    params, opt_state, codec_state = carry
                    batch, rng = batch_and_key
                    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                    loss = self._reduce_loss(loss)
                    params, opt_state, codec_state = (
                        self._encode_aggregate_update(
                            params, opt_state, codec_state, grads, rng
                        )
                    )
                    return (params, opt_state, codec_state), loss

                n_steps = jax.tree.leaves(batches)[0].shape[0]
                keys = jax.random.split(rng, n_steps)
                (params, opt_state, codec_state), losses = lax.scan(
                    one_step, (params, opt_state, codec_state), (batches, keys),
                    unroll=unroll,
                )
                return params, opt_state, codec_state, losses

            state_spec = self._codec_spec
            step_spec = P(*((None,) + tuple(self.batch_spec)))
            batch_spec = jax.tree.map(lambda _: step_spec, batches)
            opt_spec = self._opt_state_spec()
            pspec = self.param_specs if self._model_parallel else P()
            self._compiled[key] = self._jit_spmd(
                spmd, (pspec, opt_spec, state_spec, batch_spec, P()),
                (pspec, opt_spec, state_spec, P()), donate=True)
        t0 = time.perf_counter()
        self._rng, rng = jax.random.split(self._rng)
        self.params, self.opt_state, self.codec_state, losses = self._compiled[key](
            self.params, self.opt_state, self.codec_state, batches, rng
        )
        jax.block_until_ready(self.params)
        n_steps = int(jax.tree.leaves(batches)[0].shape[0])
        self._step_count += n_steps
        wall = time.perf_counter() - t0
        data = {
            "step_time": wall / n_steps,
            "steps_per_sec": n_steps / wall,
            "n_steps": float(n_steps),
        }
        rec = get_recorder()
        if rec is not None:
            # ONE span for the fused scan (there are no separable
            # per-step host walls inside one XLA program)
            rec.event("ps.run_steps", kind="span",
                      ts=time.monotonic() - wall, dur=wall,
                      step=self._step_count, **data)
        return losses, data


class SGD(MPI_PS):
    """PS-fused SGD (reference ``ps.py:195-214``)."""

    def __init__(self, params, **kwargs):
        kwargs.setdefault("optim", "sgd")
        super().__init__(params, **kwargs)


class Adam(MPI_PS):
    """PS-fused Adam with amsgrad (reference ``ps.py:217-261``)."""

    def __init__(self, params, **kwargs):
        kwargs.setdefault("optim", "adam")
        super().__init__(params, **kwargs)


class Adafactor(MPI_PS):
    """PS-fused Adafactor (Shazeer & Stern 2018) — beyond the
    reference's SGD/Adam family: factored second moments make the
    optimizer state sublinear in params (``optim.py::adafactor_update``,
    optax-pinned), freeing the ~2x-params Adam state for batch size.
    Composes with codecs, accumulation, and model-parallel
    ``param_specs`` whose sharded axes avoid the factored (two
    largest) dims — the leading-stack-axis TP/PP convention — where
    the step is exactly shard-local-decomposable (row/col means stay
    local; the two per-leaf scalar reductions pmean over the model
    axes; oracle-equality proven in ``tests/test_ps_model_parallel``).
    Leader/ZeRO-1, factored-dim sharding, and EP layouts are rejected
    loudly (see the constructor guards)."""

    def __init__(self, params, **kwargs):
        kwargs.setdefault("optim", "adafactor")
        kwargs.setdefault("lr", None)  # paper's relative step size
        super().__init__(params, **kwargs)
