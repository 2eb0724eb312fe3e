"""Codec interface + registry.

Contract (the TPU-native version of the reference's inferred hook interface,
SURVEY §2.2):

- ``encode(grad, state, rng) -> (payload, new_state)`` — called per
  parameter on each worker's *local* gradient, before the collective
  (reference ``code.encode``, ``ps.py:94``, ran in the autograd-hook thread
  pool). Payload is a pytree of arrays with **static shapes** so it can ride
  ``lax.all_gather`` under jit — the analog of the reference's fixed
  ``max_bytes`` padding for ragged messages (``mpi_comms.py:82-85``).
- ``decode(payload, shape, dtype) -> grad`` — inverse, called per
  (parameter × rank) on the receive side (reference ``code.decode``,
  ``ps.py:166``).
- ``decode_sum(payloads, shape, dtype) -> grad`` — decode a stacked
  ``[world, ...]`` payload batch and sum over ranks in one shot (the
  reference's ``sum(grads)`` loop, ``ps.py:176``); the default is a
  ``lax.scan`` fold (peak memory = ONE decoded tensor + the accumulator,
  never a ``[world, ...]`` decoded stack) and codecs override it when a
  fused form exists (e.g. top-k scatter-add).
- ``aggregate(payloads, shape, dtype) -> (agg_payload, meta)`` /
  ``agg_decode(agg_payload, meta, shape, dtype) -> grad`` — homomorphic
  aggregation (THC / SparCML, PAPERS.md): sum a stacked payload batch in
  the COMPRESSED domain, then decode ONCE. ``agg_payload`` is sized by
  the payloads, never by a ``[world, decoded]`` stack; codecs without an
  exact or probe-certified algebra leave ``supports_aggregate`` False
  and every consumer falls back to ``decode_sum`` automatically.
- ``agg_init(shape, dtype)`` / ``agg_fold(acc, payload)`` /
  ``agg_finalize(acc, shape, dtype)`` — the STREAMING (host-side, numpy)
  form of the same algebra, used by the async serve loop's
  ``CodecWire`` aggregator: each arriving push folds into a compressed
  accumulator and the one decode happens at publish time
  (``decodes_per_publish == 1``). The hierarchical tree
  (``parallel.tree``) runs the SAME streaming algebra at every
  intermediate hop: a leader folds its group's payloads without any
  per-push decode, finalizes once per upstream round, and re-encodes
  the aggregate for the next hop behind per-hop error feedback
  (``codecs.error_feedback.HopErrorFeedback``), so the fold algebra is
  the tree's one aggregation primitive and its SUM semantics must hold
  recursively — a folded-then-re-encoded payload is a valid input to
  the parent's fold. Codecs whose payload statistics are per-input
  (sign's mean|g|, int8's absmax) keep working because the re-encode
  recomputes them on the aggregate; nothing mid-tree ever assumes a
  payload came from a single worker.
- ``init_state(shape, dtype)`` — per-leaf codec state (e.g. error-feedback
  memory); ``()`` for stateless codecs. Explicit state threading replaces
  the reference's mutable ``code.codes`` side channel (``ps.py:165``).
- ``payload_bits(shape, dtype)`` — wire size in bits, for the
  ``msg_bytes``/``packaged_bytes`` metrics (reference ``ps.py:135-136``).
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

#: accepted values of the lossy codecs' ``nonfinite=`` constructor kwarg
NONFINITE_MODES = ("propagate", "zero", "raise")


def check_nonfinite_mode(mode: str) -> str:
    """Constructor-time validation of the ``nonfinite=`` kwarg — a typo
    must fail where the config was written, not at the first encode on
    a worker mid-startup."""
    if mode not in NONFINITE_MODES:
        raise ValueError(
            f"nonfinite must be one of {NONFINITE_MODES}, got {mode!r}"
        )
    return mode


def guard_nonfinite(flat: jax.Array, mode: str, codec_name: str) -> jax.Array:
    """The non-finite input guard the lossy codecs share.

    ``mode`` is the codec's ``nonfinite=`` kwarg:

    - ``"propagate"`` — legacy behavior: NaN/Inf flow into the payload
      statistics (sign's mean|g|, terngrad's max|g|, qsgd's norm all go
      NaN and poison every decoded element) undetected.
    - ``"zero"`` — sanitize: non-finite elements become 0 before any
      statistic or quantization, so one bad element can no longer wipe
      the whole payload. jit-safe (a ``where``, fused for free).
    - ``"raise"`` — eager (concrete-array) encodes raise
      ``FloatingPointError`` on any non-finite input — the fail-fast
      debugging mode. Under tracing a data-dependent raise is
      impossible, so traced encodes degrade to the ``"zero"`` sanitize
      (the payload stays finite either way); pair with the serve loop's
      NumericsMonitor for the online detection story.
    """
    if mode == "propagate":
        return flat
    if mode not in NONFINITE_MODES:
        raise ValueError(
            f"nonfinite must be one of {NONFINITE_MODES}, got {mode!r}"
        )
    if mode == "raise" and not isinstance(flat, jax.core.Tracer):
        bad = int(jnp.sum(~jnp.isfinite(flat)))
        if bad:
            raise FloatingPointError(
                f"{codec_name}.encode: {bad} non-finite gradient "
                "element(s) in input (nonfinite='raise')"
            )
        return flat
    return jnp.where(jnp.isfinite(flat), flat, jnp.zeros_like(flat))


class Codec:
    """Base codec: subclasses override encode/decode (+ optionally
    decode_sum, init_state, payload_bits)."""

    #: identity-like codecs set this so the train step can use a single
    #: fused psum instead of all_gather + decode + sum.
    supports_psum: bool = False
    #: codecs that consume randomness (random-k, QSGD) set this so the
    #: train step threads a per-worker PRNG key in.
    needs_rng: bool = False
    #: shape-agnostic AND stateless codecs set this so flat-bucket
    #: aggregation (``bucketing.BucketPlan``) may encode one dtype-uniform
    #: ~MB-scale bucket instead of hundreds of per-leaf fragments. The
    #: host wire (``parallel.dcn.CodecWire``) buckets every such codec;
    #: the device exchange (``bucketing.exchange_plan``) only those that
    #: are not ``supports_psum``, whose per-leaf psums the compiler groups.
    #: Contract: ``init_state`` returns ``()`` (per-bucket state has no
    #: home — bucket boundaries are a transport detail, not a training
    #: one) and ``encode``/``decode``/``decode_sum`` treat the input as an
    #: opaque flat array (any per-input statistic — sign's mean|g|, int8's
    #: absmax — is then computed per bucket instead of per tensor, a
    #: documented semantics change for those lossy codecs). Per-tensor
    #: codecs (PowerSGD's 2-D factorization, top-k's per-tensor selection,
    #: stateful error feedback) leave this False and keep the per-leaf
    #: path even when bucketing is on.
    bucketable: bool = False
    #: codecs whose aggregation IS a collective protocol (PowerSGD's
    #: two-psum shared-Q form) set this and implement
    #: ``fused_allreduce(grad, state, axis_name, comm_dtype=None) ->
    #: (summed, new_state)`` (+ ``fused_wire_bits(shape, dtype,
    #: comm_dtype=None)`` for metrics): the fused on-mesh step then runs
    #: it in place of encode → all_gather → decode_sum, threading the
    #: optimizer's ``comm_dtype`` so uncompressed leaves still ride a
    #: narrowed wire. ``encode``/``decode_sum`` remain the payload form
    #: for wires with no synchronous collective (async/DCN/host PS).
    supports_fused_allreduce: bool = False
    #: codecs whose payload algebra allows compressed-domain aggregation
    #: set this and implement ``aggregate``/``agg_decode`` (+ optionally
    #: the streaming ``agg_init``/``agg_fold``/``agg_finalize`` overrides
    #: when an O(payload) accumulator exists). False means every consumer
    #: (ps.aggregate, the CodecWire serve-loop aggregator) falls back to
    #: decode_sum — the always-correct path.
    supports_aggregate: bool = False
    #: True when ``aggregate`` is bit-identical to ``decode_sum`` (the
    #: integer/sparse algebras); False for probe-certified approximations
    #: (sign's vote-count algebra), which the SPMD training path never
    #: uses implicitly and the host wire ships behind the measured
    #: fidelity contract in ``benchmarks/fidelity_bench.py --aggregate``.
    agg_exact: bool = True

    def init_state(self, shape: Tuple[int, ...], dtype) -> PyTree:
        return ()

    def encode(self, grad: jax.Array, state: PyTree = (),
               rng: Optional[jax.Array] = None) -> Tuple[PyTree, PyTree]:
        raise NotImplementedError

    def decode(self, payload: PyTree, shape: Tuple[int, ...], dtype) -> jax.Array:
        raise NotImplementedError

    def decode_sum(self, payloads: PyTree, shape: Tuple[int, ...], dtype) -> jax.Array:
        """Decode a [world, ...]-stacked payload pytree, summed over ranks.

        Default: a ``lax.scan`` fold — one rank decoded per step into a
        running accumulator, so peak memory is ONE decoded tensor plus
        the accumulator instead of the ``[world, ...]`` decoded stack the
        old vmap-then-sum form materialized (at BERT scale × 8 workers
        that stack was a ~4 GB cliff). Order note: the fold accumulates
        ranks sequentially (bit-exact to the left-fold definition,
        ``tests/test_agg.py``); XLA's axis-0 reduce used a tree order,
        so the two forms agree to 1 ulp per element, not bitwise, for
        world > 2."""
        def body(acc, p):
            return acc + self.decode(p, shape, dtype).astype(acc.dtype), None

        summed, _ = jax.lax.scan(body, jnp.zeros(shape, dtype), payloads)
        return summed

    # -- homomorphic aggregation (THC / SparCML; see module docstring) ----
    def can_aggregate(self, shape: Tuple[int, ...], dtype) -> bool:
        """Per-unit refinement of ``supports_aggregate``: a codec may
        support the algebra in general but not for a particular wire
        unit (sign's Pallas bit layout has no host-side unpack). The
        CodecWire aggregator checks every unit and falls back to
        decode_sum wholesale when any says no."""
        return self.supports_aggregate

    def aggregate(self, payloads: PyTree, shape: Tuple[int, ...], dtype
                  ) -> Tuple[PyTree, Dict[str, Any]]:
        """Compressed-domain sum of a [world, ...]-stacked payload batch:
        returns ``(agg_payload, meta)`` where ``agg_payload`` is sized by
        the payloads (sparse index-merge, widened integer counts, summed
        low-rank factors) and one :meth:`agg_decode` call yields the
        summed gradient. jnp ops only — runs under jit/shard_map."""
        raise NotImplementedError(
            f"{type(self).__name__} has no compressed-domain aggregation "
            "algebra (supports_aggregate=False); use decode_sum"
        )

    def agg_decode(self, agg_payload: PyTree, meta: Dict[str, Any],
                   shape: Tuple[int, ...], dtype) -> jax.Array:
        """The ONE decode of an aggregated payload → summed gradient."""
        raise NotImplementedError

    # -- streaming form (host-side numpy; the serve-loop accumulator) -----
    def agg_init(self, shape: Tuple[int, ...], dtype) -> Dict[str, Any]:
        """Fresh streaming accumulator for one wire unit. The default
        keeps the raw payloads (payload-sized memory — for sparse codecs
        this IS the index-merge accumulator) and defers the algebra to
        :meth:`aggregate` at finalize; codecs with an O(1)-frames
        accumulator (int8's scale-folded sum, sign's vote counts)
        override all three methods."""
        return {"frames": 0, "payloads": []}

    def agg_fold(self, acc: Dict[str, Any], payload: PyTree) -> None:
        """Fold ONE worker's payload (numpy array views into the receive
        buffer — anything retained must be copied) into ``acc``."""
        acc["payloads"].append(jax.tree.map(np.copy, payload))
        acc["frames"] += 1

    def agg_finalize(self, acc: Dict[str, Any], shape: Tuple[int, ...],
                     dtype):
        """One decode of the accumulated state → summed gradient (numpy
        or jax array, ``shape``-shaped)."""
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *acc["payloads"])
        agg, meta = self.aggregate(stacked, shape, dtype)
        return self.agg_decode(agg, meta, shape, dtype)

    def payload_bits(self, shape: Tuple[int, ...], dtype) -> int:
        """Encoded wire size in bits per gradient (for metrics)."""
        payload, _ = jax.eval_shape(
            lambda: self.encode(jnp.zeros(shape, dtype), self.init_state(shape, dtype),
                                jax.random.key(0) if self.needs_rng else None)
        )
        return sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize * 8
            for leaf in jax.tree.leaves(payload)
        )

    def fidelity_probe(self, grad: jax.Array, state: PyTree = (),
                       rng: Optional[jax.Array] = None) -> Dict[str, float]:
        """Decode-after-encode fidelity of THIS codec on a real gradient:
        what the wire actually does to the values it carries, measured
        online instead of assumed from the paper. Returns relative L2
        reconstruction error, cosine similarity, and achieved
        bits-per-parameter — the three numbers the compression-utility
        literature gates wins on. Read-only: codec state is consulted
        (error feedback probes through its residual memory) but NEVER
        updated, so a probe is safe mid-training at any cadence.

        ``state`` defaults to a fresh ``init_state``; stochastic codecs
        need ``rng`` (a default key is used when omitted). Identity-like
        codecs report ~0 error / ~1 cosine — the sanity anchor the
        numerics smoke asserts."""
        grad = jnp.asarray(grad)
        if not jax.tree.leaves(state):
            state = self.init_state(grad.shape, grad.dtype)
        if rng is None and self.needs_rng:
            rng = jax.random.key(0)
        payload, _ = self.encode(grad, state, rng)
        rec = self.decode(payload, grad.shape, grad.dtype)
        g = grad.astype(jnp.float32).reshape(-1)
        r = rec.astype(jnp.float32).reshape(-1)
        gn = jnp.linalg.norm(g)
        rel = jnp.linalg.norm(r - g) / jnp.maximum(gn, 1e-30)
        cos = jnp.dot(r, g) / jnp.maximum(jnp.linalg.norm(r) * gn, 1e-30)
        n = int(np.prod(grad.shape)) if grad.shape else 1
        return {
            "rel_error": float(rel),
            "cosine": float(cos),
            "bits_per_param": self.payload_bits(grad.shape, grad.dtype) / n,
            "grad_norm": float(gn),
        }


# -- shared streaming accumulator for the sparse index-merge family --------
# (top-k / block-top-k / random-k / threshold): the accumulator IS the
# concatenated (values, indices) list — O(payload) per fold, and the one
# finalize scatter-adds world×k entries into the dense gradient. Pure
# numpy: the serve loop's per-push cost carries no jit dispatch.
#
# With the native fast path (utils/native.fold_lib, PS_NO_NATIVE off),
# the accumulator is the dense f32 gradient itself and each fold is ONE
# C++ scatter-add pass over the payload (wc_fold_sparse) — same O(k) per
# push, no per-push array copies, no finalize concat. Accumulation order
# (push order, then element order) matches np.add.at over the concat
# exactly, so the two paths are bit-identical.
#
# The dense buffers are POOLED across rounds: allocating + first-touch
# faulting a fresh zeros(n) costs ~3 ms at 8M elements — it would
# dominate the whole round and make the "per-push cost is O(payload)"
# claim false. Instead each round remembers which entries its folds
# touched, releases the buffer at finalize (or aggregator GC), and the
# next round scatter-zeroes ONLY those entries on reuse — O(world × k)
# per round, flat in model size, and bit-identical to a fresh zeros
# buffer. A buffer is handed out again only once the pool holds at
# least two (FIFO), so a finalize's returned view stays valid until a
# LATER agg_begin — the serve loop derives the averaged gradient from
# it immediately, well inside that window.

_SPARSE_POOL: Dict[int, Any] = {}
_SPARSE_POOL_LOCK = threading.Lock()
_SPARSE_POOL_MIN_READY = 2   # buffers that must sit in the pool before reuse
_SPARSE_POOL_MAX = 4         # kept per size; beyond this they drop to the GC


def _sparse_pool_take(n: int):
    """A recycled dense buffer plus the index arrays its last round's
    folds touched (the caller re-zeroes exactly those entries), or None
    (pool cold — caller allocates a fresh zeros)."""
    with _SPARSE_POOL_LOCK:
        q = _SPARSE_POOL.get(n)
        if not q or len(q) < _SPARSE_POOL_MIN_READY:
            return None
        return q.pop(0)


def _sparse_pool_give(n: int, buf: np.ndarray, touched) -> None:
    with _SPARSE_POOL_LOCK:
        q = _SPARSE_POOL.setdefault(n, [])
        if len(q) < _SPARSE_POOL_MAX:
            q.append((buf, list(touched)))


def sparse_agg_release(acc: Dict[str, Any]) -> None:
    """Return a native sparse accumulator's dense buffer to the pool
    (idempotent). Called at finalize and from ``WireAggregator`` GC so
    abandoned rounds don't leak pool capacity."""
    # "touched" marks a NATIVE SPARSE acc — scale-fold/dense accs also
    # carry "acc"+"lib" but their buffers hold arbitrary sums that a
    # touched-entry zero pass could never clean, so they must never pool
    if acc.get("lib") is not None and "touched" in acc:
        buf = acc.pop("acc", None)
        if buf is not None:
            _sparse_pool_give(acc["n"], buf, acc.pop("touched"))


def sparse_agg_init(shape=None) -> Dict[str, Any]:
    from pytorch_ps_mpi_tpu.utils import native as _native

    lib = _native.fold_lib() if shape is not None else None
    if lib is not None:
        n = int(np.prod(shape)) if shape else 1
        taken = _sparse_pool_take(n)
        if taken is None:
            buf = np.zeros(n, np.float32)
            ptr = _native._f32(buf)
        else:
            buf, dirty = taken
            ptr = _native._f32(buf)
            for idx in dirty:  # O(touched) recycle, not O(n)
                if idx.size:
                    _native.zero_sparse(lib, buf, idx, acc_ptr=ptr)
        return {"frames": 0, "acc": buf, "n": n, "lib": lib,
                "touched": [], "ptr": ptr}
    return {"frames": 0, "values": [], "indices": []}


def sparse_agg_fold(acc: Dict[str, Any], values, indices) -> None:
    lib = acc.get("lib")
    if lib is not None:
        from pytorch_ps_mpi_tpu.utils import native as _native

        # the index copy is retained in `touched` (payload buffers are
        # transport-owned views) — it is both the C++ argument and the
        # record of which entries to re-zero when the buffer recycles
        idx = np.array(indices, np.int32, copy=True).reshape(-1)
        _native.fold_sparse(
            lib, acc["acc"],
            np.ascontiguousarray(values, np.float32).reshape(-1), idx,
            acc_ptr=acc["ptr"])
        acc["touched"].append(idx)
        acc["frames"] += 1
        return
    acc["values"].append(np.array(values, np.float32,
                                  copy=True).reshape(-1))
    acc["indices"].append(np.array(indices, copy=True).reshape(-1))
    acc["frames"] += 1


def sparse_agg_finalize(acc: Dict[str, Any], shape, dtype) -> np.ndarray:
    if acc.get("lib") is not None and "touched" in acc:
        out = acc["acc"].astype(dtype, copy=False).reshape(shape)
        # release to the pool NOW (not at GC): `out` may be a view of
        # the buffer, valid until a later agg_begin re-issues it — see
        # the pool contract above
        sparse_agg_release(acc)
        return out
    n = int(np.prod(shape)) if shape else 1
    idx = np.concatenate(acc["indices"]).astype(np.int64)
    val = np.concatenate(acc["values"])
    keep = (idx >= 0) & (idx < n)  # mode='drop' for block pad-slot picks
    flat = np.zeros(n, np.float32)
    np.add.at(flat, idx[keep], val[keep])
    return flat.astype(dtype, copy=False).reshape(shape)


# -- shared streaming accumulator for the dense cast-up family -------------
# (identity / bf16 / f16): ONE running f32 array per unit; each fold
# casts its frame up and adds in place, mirroring decode_sum's
# cast-before-sum rule. The per-frame cast stays with the codec.

def dense_agg_init(shape) -> Dict[str, Any]:
    n = int(np.prod(shape)) if shape else 1
    return {"frames": 0, "acc": np.zeros(n, np.float32)}


def dense_agg_finalize(acc: Dict[str, Any], shape, dtype) -> np.ndarray:
    # np.asarray: the accumulator may be a jax array (scale-fold jit path)
    return np.asarray(acc["acc"]).astype(dtype, copy=False).reshape(shape)


# -- shared streaming accumulator for the scale-folded integer family ------
# (int8 / qsgd / terngrad: decode is scale × integer payload). ONE f32
# accumulator per unit with a three-way fold path, picked at init:
# native (utils/native.fold_lib — one C++ SIMD dequant-multiply-add pass
# per push, no jit dispatch, bit-exact to the numpy form) when the fast
# path is armed; else the codec's jitted fused kernel at or above the
# crossover (numpy's multiply-into-temp + add pays ~3x the memory
# traffic there); else pure numpy, where a jit dispatch would dominate.
# The per-codec fused kernel stays with the codec; finalize is
# dense_agg_finalize.

FOLD_JIT_MIN = 1 << 16


def scalefold_agg_init(shape) -> Dict[str, Any]:
    from pytorch_ps_mpi_tpu.utils import native as _native

    n = int(np.prod(shape)) if shape else 1
    lib = _native.fold_lib()
    if lib is not None:
        return {"frames": 0, "acc": np.zeros(n, np.float32), "n": n,
                "lib": lib}
    if n >= FOLD_JIT_MIN:
        return {"frames": 0, "acc": jnp.zeros(n, jnp.float32), "n": n,
                "jit": True}
    return {"frames": 0, "acc": np.zeros(n, np.float32),
            "tmp": np.empty(n, np.float32), "n": n}


_REGISTRY: Dict[str, Type[Codec]] = {}


def register_codec(name: str) -> Callable[[Type[Codec]], Type[Codec]]:
    def deco(cls: Type[Codec]) -> Type[Codec]:
        _REGISTRY[name] = cls
        return cls
    return deco


def get_codec(name: str, **kwargs) -> Codec:
    """Instantiate a registered codec by name (e.g. ``get_codec('topk',
    fraction=0.01)``)."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown codec {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)
