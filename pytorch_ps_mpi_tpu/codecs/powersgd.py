"""PowerSGD: low-rank gradient compression (Vogels et al. 2019,
arXiv:1905.13727 — see PAPERS.md).

Each ≥2-D gradient, viewed as a matrix M [n, m], is approximated as
P @ Qᵀ with rank r ≪ min(n, m): one power-iteration step against the
warm-started Q from the previous round, orthonormalized via QR. Error
feedback is built in (the residual is carried in codec state and added
back next round), as the algorithm requires for convergence.
Vectors/scalars (ndim < 2) ride uncompressed.

TWO protocols live here, matching the paper's own split:

- **All-reducible (the headline, paper §2/Alg. 1)** — the fused
  in-collective form ``fused_allreduce`` used by ``MPI_PS``'s on-mesh
  step: every worker shares ONE warm Q, so ``P = psum(M_w @ Q)`` →
  QR → ``Q = psum(M_wᵀ @ P̂)`` yields the rank-r approximation of the
  *summed* gradient in two rank-sized psums. Wire cost per worker is
  ``~2·(W-1)/W·r·(n+m)`` — **independent of world size** — where the
  gather form ships ``(W-1)·r·(n+m)``. Per-worker error feedback keeps
  exactly what the protocol transmitted on this worker's behalf:
  ``e_w ← M_w − P̂ P̂ᵀ M_w``.
- **Per-worker factors (``encode``/``decode_sum``)** — each worker ships
  its own ``(P_w, Q_w)`` and the receiver sums W separate rank-r
  approximations. This is NOT the paper's all-reduced algorithm, but it
  needs no collective inside the codec, which is exactly what the
  async/DCN wires require (host PS, shm/TCP fleets): there IS no
  synchronous collective to ride, payloads arrive one worker at a time.

MXU note: encode/decode are three tall-skinny matmuls per tensor —
exactly the shape XLA tiles onto the systolic array; the QR is r×r-sized
and negligible.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pytorch_ps_mpi_tpu.codecs.base import Codec, register_codec


def _matrix_shape(shape):
    """Matrix view [n, m] of a tensor: first dim x rest — SKIPPING
    leading singleton dims. The model-parallel shard convention carries
    a leading [1] local-shard axis ([1, d, f/tp] TP leaves); without the
    skip that axis becomes n=1, the rank clips to 1, r*(n+m) >= n*m,
    and PowerSGD silently refuses to compress every TP leaf."""
    i = 0
    while i < len(shape) - 1 and shape[i] == 1:
        i += 1
    n = shape[i]
    m = int(np.prod(shape[i + 1:]))
    return n, m


@register_codec("powersgd")
class PowerSGDCodec(Codec):
    supports_fused_allreduce = True
    # exact factor-domain aggregation: W rank-r payloads concatenate into
    # ONE rank-W·r factor pair ([n, Wr] and [m, Wr]) whose single
    # reconstruct equals Σ_w P_w Q_wᵀ — the factors are summed/stacked in
    # the compressed domain and the O(n·m) reconstruct happens once per
    # round instead of once per worker (the all-reduced shared-Q protocol
    # remains the true factor-SUM form, fused_allreduce)
    supports_aggregate = True

    def __init__(self, rank: int = 2, min_compression_elems: int = 1024):
        """``rank``: approximation rank r. Tensors with fewer than
        ``min_compression_elems`` elements (or ndim < 2) are sent raw —
        compressing tiny biases costs more wire than it saves."""
        self.rank = int(rank)
        self.min_elems = int(min_compression_elems)

    def _compresses(self, shape) -> bool:
        if len(shape) < 2:
            return False
        n, m = _matrix_shape(shape)
        r = min(self.rank, n, m)
        return n * m >= self.min_elems and r * (n + m) < n * m

    def init_state(self, shape, dtype):
        if not self._compresses(shape):
            return ()
        n, m = _matrix_shape(shape)
        r = min(self.rank, n, m)
        # deterministic warm-start Q, identical on every worker
        key = jax.random.key(np.int64(hash((n, m, r))) % (2 ** 31))
        q = jax.random.normal(key, (m, r), dtype)
        return {"Q": q, "memory": jnp.zeros(shape, dtype)}

    def encode(self, grad, state=(), rng=None):
        if not self._compresses(grad.shape):
            return {"raw": grad}, state
        n, m = _matrix_shape(grad.shape)
        corrected = grad + state["memory"]
        M = corrected.reshape(n, m)
        P = M @ state["Q"]                       # [n, r] power iteration
        P, _ = jnp.linalg.qr(P)                  # orthonormalize columns
        Q = M.T @ P                              # [m, r]
        decoded = (P @ Q.T).reshape(grad.shape)
        new_state = {"Q": Q, "memory": corrected - decoded}
        return {"P": P, "Q": Q}, new_state

    def fused_allreduce(self, grad, state, axis_name, comm_dtype=None):
        """Vogels et al.'s all-reduced protocol (module docstring):
        returns ``(summed_decoded, new_state)`` — the rank-r
        approximation of the cross-worker gradient SUM, via two
        rank-sized psums over ``axis_name``. Runs inside shard_map.

        ``comm_dtype`` narrows the UNCOMPRESSED leaves' psum wire (the
        always-on bf16 doctrine); the low-rank factors keep their own
        dtype — they feed a QR whose orthonormality the error-feedback
        analysis leans on, and at r(n+m) elements they are already the
        cheap part of the wire."""
        if not self._compresses(grad.shape):
            if comm_dtype is not None:
                return lax.psum(
                    grad.astype(comm_dtype), axis_name
                ).astype(grad.dtype), state
            return lax.psum(grad, axis_name), state
        n, m = _matrix_shape(grad.shape)
        corrected = grad + state["memory"]
        M = corrected.reshape(n, m)
        # psum #1: P = M @ Q summed across workers (Q is shared/warm,
        # identical everywhere, so this IS (Σ M_w) @ Q)
        P = lax.psum(M @ state["Q"], axis_name)
        P, _ = jnp.linalg.qr(P)          # deterministic: same P̂ everywhere
        Qw = M.T @ P                     # this worker's factor
        # psum #2: Q = (Σ M_w)ᵀ @ P̂
        Q = lax.psum(Qw, axis_name)
        summed = (P @ Q.T).reshape(grad.shape)
        # error feedback keeps what was NOT transmitted on this worker's
        # behalf: its share of the decode is P̂ Q_wᵀ = P̂ P̂ᵀ M_w, and
        # Σ_w P̂ Q_wᵀ == the summed decode, so the global residual is
        # exactly the sum of these local memories
        new_state = {"Q": Q, "memory": corrected - (P @ Qw.T).reshape(grad.shape)}
        return summed, new_state

    def fused_wire_bits(self, shape, dtype, comm_dtype=None) -> int:
        """Per-worker wire bits of one two-psum round (both rank-sized
        ring reductions; world-size-independent). Uncompressed leaves
        ride a plain psum at ``comm_dtype`` when set."""
        bits = jnp.dtype(dtype).itemsize * 8
        if not self._compresses(shape):
            n = int(np.prod(shape)) if shape else 1
            wire_bits = (jnp.dtype(comm_dtype).itemsize * 8
                         if comm_dtype is not None else bits)
            return n * wire_bits  # rides a plain psum
        n, m = _matrix_shape(shape)
        r = min(self.rank, n, m)
        return r * (n + m) * bits

    def decode(self, payload, shape, dtype):
        if "raw" in payload:
            return payload["raw"].astype(dtype)
        return (payload["P"] @ payload["Q"].T).reshape(shape).astype(dtype)

    def decode_sum(self, payloads, shape, dtype):
        # Σ_w P_w Q_wᵀ through the factor-concat aggregation (one
        # [n, Wr] @ [Wr, m] contraction — same reduction the old
        # "wnr,wmr->nm" einsum performed, single source of truth now)
        agg, meta = self.aggregate(payloads, shape, dtype)
        return self.agg_decode(agg, meta, shape, dtype)

    def aggregate(self, payloads, shape, dtype):
        if "raw" in payloads:
            return ({"raw": payloads["raw"].sum(axis=0)},
                    {"frames": int(payloads["raw"].shape[0])})
        w, n, r = payloads["P"].shape
        m = payloads["Q"].shape[1]
        # [w, n, r] -> [n, w*r]: stack the per-worker factors side by
        # side; the concatenated pair IS the aggregated payload (rank
        # W·r), sized by the factors, never by the decoded matrix
        p_cat = jnp.transpose(payloads["P"], (1, 0, 2)).reshape(n, w * r)
        q_cat = jnp.transpose(payloads["Q"], (1, 0, 2)).reshape(m, w * r)
        return {"P": p_cat, "Q": q_cat}, {"frames": int(w)}

    def agg_decode(self, agg_payload, meta, shape, dtype):
        if "raw" in agg_payload:
            return agg_payload["raw"].astype(dtype)
        out = agg_payload["P"] @ agg_payload["Q"].T
        return out.reshape(shape).astype(dtype)

    def payload_bits(self, shape, dtype):
        bits = jnp.dtype(dtype).itemsize * 8
        if not self._compresses(shape):
            n = int(np.prod(shape)) if shape else 1
            return n * bits
        n, m = _matrix_shape(shape)
        r = min(self.rank, n, m)
        return r * (n + m) * bits
