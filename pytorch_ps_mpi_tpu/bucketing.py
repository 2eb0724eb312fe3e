"""Flat-bucket gradient aggregation: fuse per-leaf collectives into
dtype-grouped flat buffers.

The reference received gradients one parameter at a time in a reverse-order
loop (``ps.py:155-176``); our tree-mapped rebuild kept that granularity —
one ``psum``/``all_gather``/``psum_scatter`` launch per leaf, hundreds for a
BERT-size tree, each paying the fixed collective dispatch latency the ICI
cannot amortize (the per-message-overhead effect of "On the Utility of
Gradient Compression in Distributed Training Systems"; SparCML applies the
same fix at the MPI layer by streaming many small contributions through few
large buffers).

This module is the compile-time answer: a :class:`BucketPlan` groups a
pytree's leaves **by dtype** (a bucket is a single flat array, so its dtype
must be uniform — grouping also preserves each leaf's precision end to end)
into contiguous ~``bucket_mb``-MB buffers, with exact offset bookkeeping for
every leaf including 0-d scalars. The transforms are pure and cheap inside
jit — ``pack`` is one concatenate per bucket, ``unpack`` one slice per leaf
— so XLA fuses them into the surrounding program; what changes is the
*collective launch count*: one per bucket instead of one per leaf.

Consumers:

- ``ps.MPI_PS(bucket_mb=...)`` and
  ``parallel.dp.make_sync_train_step(bucket_mb=...)`` — the device
  exchange of a PAYLOAD codec (sign, int8, qsgd, terngrad, f16, randomk's
  fraction form) encodes, all-gathers and decodes one bucket at a time, in
  ``MPI_PS`` in both topologies (``mode='allgather'`` and the ZeRO-1
  ``mode='leader'``, where each worker owns a contiguous bucket shard).
  :func:`exchange_plan` is the one place that decides it;
- ``parallel.dcn.CodecWire(bucket_mb=...)`` — the host wire ships one
  contiguous per-bucket payload per push instead of per-leaf fragments,
  for every ``Codec.bucketable`` codec, identity and cast included.

A psum codec (identity, the bf16 cast) gets no plan on the device: its
exchange is one ``psum`` a leaf, and the compiler groups those itself
(``comms.ALONE_BYTES``; flat buckets were ~25 % slower on four v5e chips,
PERF.md section 6). Per-tensor codecs (PowerSGD, top-k) keep the
per-leaf path. ``bucket_mb=0`` everywhere is the per-leaf path.
"""

from __future__ import annotations

import re
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


class LeafSlot(NamedTuple):
    """Where one pytree leaf lives inside the bucket set: exact offset
    bookkeeping (0-d leaves occupy one element; ``shape=()`` restores
    them on unpack)."""

    bucket: int            # index into BucketPlan.buckets
    offset: int            # element offset inside that bucket
    size: int              # element count (1 for 0-d scalars)
    shape: Tuple[int, ...]
    dtype: Any             # canonical jnp dtype


class BucketSpec(NamedTuple):
    """One flat bucket: uniform dtype, ``size`` total elements."""

    dtype: Any
    size: int

    @property
    def nbytes(self) -> int:
        return self.size * jnp.dtype(self.dtype).itemsize


class BucketPlan:
    """Compile-time bucketing plan for one pytree structure.

    Built once per (tree structure, ``bucket_mb``) from shapes/dtypes only
    (array leaves and ``ShapeDtypeStruct`` templates both work); the
    ``pack``/``unpack`` transforms are pure functions of the plan, safe to
    trace inside jit/shard_map and bit-exact inverses of each other
    (``unpack(pack(t)) == t`` element-for-element — buckets are a
    permutation-into-concatenation, no arithmetic).
    """

    def __init__(self, treedef, leaf_slots: List[LeafSlot],
                 buckets: List[BucketSpec], bucket_mb: float):
        self.treedef = treedef
        self.leaf_slots = leaf_slots
        self.buckets = buckets
        self.bucket_mb = float(bucket_mb)

    # -- accounting -------------------------------------------------------
    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    @property
    def num_leaves(self) -> int:
        return len(self.leaf_slots)

    @property
    def bucket_bytes(self) -> List[int]:
        return [b.nbytes for b in self.buckets]

    @property
    def total_bytes(self) -> int:
        return sum(self.bucket_bytes)

    def __repr__(self) -> str:
        return (
            f"BucketPlan(leaves={self.num_leaves}, "
            f"buckets={self.num_buckets}, "
            f"bytes={[b.nbytes for b in self.buckets]})"
        )

    # -- transforms -------------------------------------------------------
    def pack_leaves(self, leaves: Sequence[jax.Array]) -> List[jax.Array]:
        """Flat-leaf form of :func:`flatten_into_buckets` (wire code that
        already holds the flat list skips the treedef round-trip)."""
        if len(leaves) != self.num_leaves:
            raise ValueError(
                f"plan built for {self.num_leaves} leaves, got {len(leaves)}"
            )
        per_bucket: List[List[jax.Array]] = [[] for _ in self.buckets]
        for slot, leaf in zip(self.leaf_slots, leaves):
            flat = jnp.reshape(leaf, (-1,))
            if flat.dtype != jnp.dtype(slot.dtype):
                raise TypeError(
                    f"leaf dtype {flat.dtype} != planned {slot.dtype} "
                    f"(tree changed since the plan was built?)"
                )
            per_bucket[slot.bucket].append(flat)
        # slots were assigned in leaf order, so in-order concatenation
        # reproduces exactly the planned offsets
        return [
            jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            for parts in per_bucket
        ]

    def unpack_leaves(self, buckets: Sequence[jax.Array]) -> List[jax.Array]:
        if len(buckets) != self.num_buckets:
            raise ValueError(
                f"plan has {self.num_buckets} buckets, got {len(buckets)}"
            )
        out = []
        for slot in self.leaf_slots:
            flat = buckets[slot.bucket][slot.offset: slot.offset + slot.size]
            out.append(jnp.reshape(flat, slot.shape))
        return out

    def pack(self, tree: PyTree) -> List[jax.Array]:
        return self.pack_leaves(jax.tree.leaves(tree))

    def unpack(self, buckets: Sequence[jax.Array]) -> PyTree:
        return jax.tree.unflatten(self.treedef, self.unpack_leaves(buckets))

    def bucket_templates(self) -> List[jax.ShapeDtypeStruct]:
        """Abstract per-bucket templates (shape/dtype only) — e.g. the
        ZeRO-1 bucket-shard update needs target sizes without
        materializing a second copy of the parameters."""
        return [
            jax.ShapeDtypeStruct((b.size,), jnp.dtype(b.dtype))
            for b in self.buckets
        ]


def plan_buckets(tree: PyTree, bucket_mb: float) -> Optional[BucketPlan]:
    """Group ``tree``'s leaves by dtype into ~``bucket_mb``-MB flat buckets.

    Leaves keep their flatten order within each dtype group (locality: a
    transformer block's weights land in the same or adjacent buckets). A
    single leaf larger than the cap gets a bucket of its own — it is
    already one large transfer, splitting it would only add launches.
    ``bucket_mb <= 0`` returns ``None``: the per-leaf path, exactly
    today's behavior.
    """
    if bucket_mb is None or bucket_mb <= 0:
        return None
    cap_bytes = float(bucket_mb) * (1 << 20)
    leaves, treedef = jax.tree.flatten(tree)

    # dtype groups in first-appearance order, leaf order preserved within
    groups: dict = {}
    for i, leaf in enumerate(leaves):
        dt = jnp.dtype(getattr(leaf, "dtype", jnp.result_type(leaf)))
        groups.setdefault(dt.name, []).append(i)

    buckets: List[BucketSpec] = []
    slots: List[Optional[LeafSlot]] = [None] * len(leaves)
    for dt_name, idxs in groups.items():
        dt = jnp.dtype(dt_name)
        cur_size = 0  # elements in the open bucket
        cur_bucket = -1
        for i in idxs:
            leaf = leaves[i]
            shape = tuple(np.shape(leaf))
            size = int(np.prod(shape)) if shape else 1
            nbytes = size * dt.itemsize
            if cur_bucket < 0 or (
                cur_size > 0 and (cur_size * dt.itemsize + nbytes) > cap_bytes
            ):
                buckets.append(BucketSpec(dt, 0))
                cur_bucket = len(buckets) - 1
                cur_size = 0
            slots[i] = LeafSlot(cur_bucket, cur_size, size, shape, dt)
            cur_size += size
            buckets[cur_bucket] = BucketSpec(dt, cur_size)
    return BucketPlan(treedef, [s for s in slots], buckets, bucket_mb)


def exchange_plan(code, tree: PyTree, bucket_mb: float) -> Optional[BucketPlan]:
    """The bucket plan the device exchange of ``code`` uses for ``tree``,
    or None for the per-leaf exchange.

    Only a payload codec is bucketed: ``code.bucketable``, neither
    ``supports_psum`` (its per-leaf psums are grouped by the compiler)
    nor ``supports_fused_allreduce`` (its protocol is per tensor), and
    ``bucket_mb > 0``. A bucketable codec must be stateless (per-bucket
    state has no home): one that is not raises TypeError."""
    if bucket_mb <= 0 or not code.bucketable:
        return None
    if jax.tree.leaves(code.init_state((1,), jnp.float32)):
        raise TypeError(
            f"{type(code).__name__}.bucketable=True but init_state is "
            "non-empty — bucketable codecs must be stateless (see "
            "codecs.base.Codec.bucketable)"
        )
    if code.supports_psum or code.supports_fused_allreduce:
        return None
    return plan_buckets(tree, bucket_mb)


def flatten_into_buckets(plan: BucketPlan, tree: PyTree) -> List[jax.Array]:
    """Pure transform: pytree -> list of flat dtype-uniform buckets
    (inverse: :func:`unflatten_from_buckets`; bit-exact round trip)."""
    return plan.pack(tree)


def unflatten_from_buckets(
    plan: BucketPlan, buckets: Sequence[jax.Array]
) -> PyTree:
    """Pure transform: bucket list -> the original pytree structure."""
    return plan.unpack(buckets)


# ---------------------------------------------------------------------------
# Launch counting: make the win checkable (tests) and visible (bench).
# ---------------------------------------------------------------------------

_COLLECTIVE_OPS = (
    "all_reduce", "all_gather", "reduce_scatter", "all_to_all",
    "collective_permute",
)


def count_collectives(lowered_text: str) -> dict:
    """Count collective ops in a lowered (StableHLO/HLO) program text.

    This counts *launches at the program level* — what the per-leaf tree-map
    emits one-per-leaf and bucketing emits one-per-bucket. (XLA's own
    all-reduce combiner may later merge some launches; counting the
    pre-optimization program keeps the number deterministic across
    backends, and the host DCN wire never gets XLA's help at all.)
    """
    out = {}
    for op in _COLLECTIVE_OPS:
        # stablehlo spells them "stablehlo.all_reduce"; HLO text spells
        # "all-reduce" — normalize both
        pat = re.compile(
            r"\b(?:stablehlo\.)?" + op.replace("_", "[-_]") + r"\b"
        )
        out[op] = len(pat.findall(lowered_text))
    out["total"] = sum(out[op] for op in _COLLECTIVE_OPS)
    return out


def lowered_collective_counts(jit_fn, *args, **kwargs) -> dict:
    """Lower a jitted function (ShapeDtypeStruct args welcome — nothing is
    executed or materialized) and count its collective launches."""
    return count_collectives(jit_fn.lower(*args, **kwargs).as_text())
