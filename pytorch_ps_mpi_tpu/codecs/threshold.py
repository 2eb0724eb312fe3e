"""Threshold sparsification — the genuinely RAGGED codec.

Keeps every entry with ``|g| > tau * mean|g|`` (Strom-2015-style relative
threshold). Unlike top-k, the number of surviving entries is
**data-dependent**: it varies per worker, per parameter, and per step. This
is the payload class the reference's whole two-phase variable-length
protocol existed for (``mpi_comms.py:144-174``: exchange byte counts
first, then ``Iallgatherv`` the ragged payloads), and its TPU-native wire
convention is the one the reference's ``max_bytes`` high-water padding
approximated (``mpi_comms.py:82-85``):

- the payload buffer has a **static cap** (``max_fraction`` of the tensor),
  so it can ride ``lax.all_gather`` under jit;
- the slots past each worker's true count hold *garbage* (whatever
  ``flat[0]`` gather produced) — they are NOT zeroed on the send side;
- an int32 ``length`` sidecar rides along, and the **receive side masks**
  ``arange(cap) < length`` before the scatter-add. Consumers that ignore
  the sidecar get corrupt sums — the sidecar is load-bearing, exactly like
  the reference's count exchange (and unlike its 32-byte ``0x29`` sentinel,
  which could collide with payload bytes, SURVEY §2.3).

Overflow (more survivors than the cap) drops the tail entries in index
order — the high-water buffer is the contract, as in the reference. Wrap
in :class:`~pytorch_ps_mpi_tpu.codecs.error_feedback.ErrorFeedback`
(``get_codec('ef', inner_name='threshold', ...)``) to accumulate both
sub-threshold and overflow residuals into later steps.

With ``target_fraction`` set, ``tau`` becomes adaptive codec state: a
multiplicative controller nudges it so the mean kept fraction tracks the
target (kept > target → raise the bar, and vice versa).

Performance note (no chip number; see ``PERF.md`` section 7): the
``nonzero(size=cap)`` compaction lowers to an n-sized scatter, which
TPUs execute serially. The default TPU path therefore compacts with one
``lax.sort`` instead (``compaction='sort'``: bitonic, vectorized; see
``__init__``), keeping the scatter path for CPUs. Even so, for on-chip
compression where raggedness is NOT the point, prefer ``topk-approx``
or ``sign``/``terngrad``; use this codec where the ragged protocol
itself is (DCN wires with real byte budgets).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_ps_mpi_tpu.codecs.base import (
    Codec,
    register_codec,
    sparse_agg_finalize,
    sparse_agg_fold,
    sparse_agg_init,
)


@register_codec("threshold")
class ThresholdCodec(Codec):
    # exact sparse index-merge, with each rank's garbage tail masked by
    # ITS OWN length sidecar before the concat — the ragged protocol's
    # receive half applied in the compressed domain
    supports_aggregate = True

    def __init__(
        self,
        tau: float = 2.0,
        max_fraction: float = 0.25,
        target_fraction: float = 0.0,
        eta: float = 0.25,
        compaction: str | None = None,
        chunk: int = 1 << 16,
    ):
        """Args:
          tau: initial threshold in units of the gradient's mean |g|.
          max_fraction: static payload cap as a fraction of the tensor —
            the compile-time high-water mark (reference ``max_bytes``).
          target_fraction: if >0, adapt tau so the kept fraction tracks
            this value (tau becomes codec state).
          eta: controller gain for the tau adaptation.
          compaction: ``'sort'`` compacts survivor indices with a
            sort — a bitonic network the TPU runs vectorized;
            ``'scatter'`` uses ``jnp.nonzero(size=cap)``, which lowers to
            an n-sized scatter TPUs execute serially but CPUs run cheaply
            (measured: scatter 3.4× faster than sort on the host CPU at
            1M elems, while on TPU the n-scatter is the 72 ms outlier of
            the codec table). Default ``None`` picks by the ambient
            backend: sort on TPU, scatter elsewhere. Both produce
            identical decoded gradients; only the garbage tail beyond
            ``length`` differs (and decode masks it either way).
          chunk: sort-path tensors with at least ``4 * chunk`` elements
            compact CHUNKED: one vectorized per-chunk sort over
            ``[n_chunks, chunk]`` (a bitonic network of depth log²(chunk)
            instead of log²(n) — the fix for the superlinear
            BERT-flat-grad encode) followed by a
            sequential cursor merge of the per-chunk survivor prefixes
            (``dynamic_update_slice`` per chunk; each write is a full
            static-size chunk and the next chunk's write overlap-
            overwrites the garbage tail, so the merged prefix is exactly
            the global survivors in index order). Identical decoded
            payloads to the unchunked sort — only the garbage tail past
            ``length`` differs. 0 disables chunking.
        """
        if not 0.0 < max_fraction <= 1.0:
            raise ValueError(f"max_fraction must be in (0, 1], got {max_fraction}")
        if target_fraction and target_fraction > max_fraction:
            raise ValueError("target_fraction must be <= max_fraction")
        if compaction is None:
            compaction = "sort" if jax.default_backend() == "tpu" else "scatter"
        if compaction not in ("sort", "scatter"):
            raise ValueError(f"compaction must be 'sort' or 'scatter', "
                             f"got {compaction!r}")
        if chunk and (chunk < 1024 or chunk & (chunk - 1)):
            raise ValueError(f"chunk must be 0 or a power of two >= 1024, "
                             f"got {chunk}")
        self.tau = float(tau)
        self.max_fraction = float(max_fraction)
        self.target_fraction = float(target_fraction)
        self.eta = float(eta)
        self.compaction = compaction
        self.chunk = int(chunk)

    def _cap(self, shape) -> int:
        n = int(np.prod(shape)) if shape else 1
        return max(1, int(round(n * self.max_fraction)))

    def init_state(self, shape, dtype):
        return {"tau": jnp.float32(self.tau)}

    def encode(self, grad, state=None, rng=None):
        state = state if state else {"tau": jnp.float32(self.tau)}
        flat = grad.reshape(-1)
        n = flat.shape[0]
        cap = self._cap(grad.shape)
        tau = state["tau"]
        thr = tau * jnp.mean(jnp.abs(flat))
        mask = jnp.abs(flat) > thr
        kept = jnp.sum(mask)  # true survivor count — data-dependent
        # static-size compaction: indices of the first `cap` survivors in
        # index order; slots past min(kept, cap) hold garbage by design
        # (see module doc) — decode masks them by `length` either way.
        if (self.compaction == "sort" and self.chunk
                and n >= 4 * self.chunk):
            idx = self._chunked_compact(mask, n, cap)
        elif self.compaction == "sort" and 2 * n < 2**31:
            # survivors keep their index as the sort key, non-survivors
            # get index+n: one ascending sort puts survivor indices
            # first IN INDEX ORDER. The sort is bitonic — vectorized on
            # TPU, unlike nonzero's serial n-sized scatter. The 2n < 2^31
            # guard keeps the biased keys inside int32 (beyond it, pos+n
            # would wrap negative and sort garbage BEFORE survivors —
            # silently wrong decode); such tensors take the scatter path
            # (large tensors normally hit the chunked branch above,
            # whose local keys never approach the int32 bound).
            pos = jnp.arange(n, dtype=jnp.int32)
            keys = jnp.where(mask, pos, pos + n)
            idx = jax.lax.sort(keys)[:cap]
            idx = jnp.where(idx >= n, idx - n, idx)  # unbias garbage tail
        else:
            (idx,) = jnp.nonzero(mask, size=cap, fill_value=0)
        payload = {
            "values": jnp.take(flat, idx),
            "indices": idx.astype(jnp.int32),
            "length": jnp.minimum(kept, cap).astype(jnp.int32),
        }
        if self.target_fraction > 0.0:
            target = self.target_fraction * n
            ratio = kept.astype(jnp.float32) / target
            new_tau = jnp.clip(tau * ratio**self.eta, 1e-4, 1e4)
        else:
            new_tau = tau
        return payload, {"tau": new_tau}

    def _chunked_compact(self, mask, n: int, cap: int):
        """Chunked data-dependent compaction: the first ``cap`` survivor
        indices of ``mask`` in GLOBAL index order, without an n-sized
        sort. Per-chunk biased-key sorts run as ONE vectorized
        ``lax.sort`` over ``[n_chunks, chunk]`` (bitonic depth
        log²(chunk), not log²(n)); a sequential ``fori_loop`` then
        merges each chunk's survivor prefix at a running cursor with a
        full-chunk ``dynamic_update_slice`` — the next chunk's write
        lands AT its predecessor's survivor count, overwriting the
        garbage tail, so out[:kept_total] is exactly the concatenation
        of survivor prefixes = the global survivors in index order.
        Bit-identical payload semantics to the unchunked sort path for
        every slot decode ever reads (the masked ``length`` prefix)."""
        C = self.chunk
        nc = -(-n // C)
        pad = nc * C - n
        m2 = (jnp.concatenate([mask, jnp.zeros((pad,), mask.dtype)])
              if pad else mask).reshape(nc, C)
        pos = jnp.arange(C, dtype=jnp.int32)[None, :]
        keys = jnp.where(m2, pos, pos + C)  # local keys: always < 2^31
        skeys = jax.lax.sort(keys, dimension=-1)
        counts = m2.sum(axis=1, dtype=jnp.int32)  # survivors per chunk
        take = min(C, cap)  # a chunk's rank >= cap entries can never
        # land inside the global first-cap prefix, so a static
        # take-per-chunk write loses nothing
        out0 = jnp.zeros((cap + take,), jnp.int32)

        def body(c, state):
            out, cursor = state
            glob = skeys[c, :take]
            glob = jnp.where(glob >= C, glob - C, glob) + c * C
            # clamp only the WRITE position: past cap the write lands in
            # the slack region (sliced off below); the cursor itself
            # keeps the true running survivor count
            out = jax.lax.dynamic_update_slice(
                out, glob, (jnp.minimum(cursor, cap),))
            return out, cursor + counts[c]

        out, _ = jax.lax.fori_loop(0, nc, body, (out0, jnp.int32(0)))
        return out[:cap]

    def _masked_values(self, payload, dtype):
        cap = payload["values"].shape[-1]
        valid = jnp.arange(cap) < payload["length"][..., None]
        return jnp.where(valid, payload["values"], 0).astype(dtype)

    def decode(self, payload, shape, dtype):
        n = int(np.prod(shape)) if shape else 1
        vals = self._masked_values(payload, dtype)
        flat = jnp.zeros((n,), dtype)
        return flat.at[payload["indices"]].add(vals).reshape(shape)

    def decode_sum(self, payloads, shape, dtype):
        # Masked fused scatter-add over all workers: each worker's garbage
        # tail is zeroed by ITS OWN length before the sum — the receive
        # half of the ragged protocol.
        agg, meta = self.aggregate(payloads, shape, dtype)
        return self.agg_decode(agg, meta, shape, dtype)

    def aggregate(self, payloads, shape, dtype):
        idx = payloads["indices"]
        return {
            "values": self._masked_values(payloads, dtype).reshape(-1),
            "indices": idx.reshape(-1),
        }, {"frames": int(idx.shape[0])}

    def agg_decode(self, agg_payload, meta, shape, dtype):
        n = int(np.prod(shape)) if shape else 1
        return jnp.zeros((n,), dtype).at[agg_payload["indices"]].add(
            agg_payload["values"].astype(dtype)).reshape(shape)

    # streaming form: each frame contributes only its length-prefix
    # (survivors live at the front in index order; the tail is garbage
    # by the wire contract) — O(length) per fold
    def agg_init(self, shape, dtype):
        return sparse_agg_init(shape)

    def agg_fold(self, acc, payload):
        k = int(payload["length"])
        sparse_agg_fold(acc, np.asarray(payload["values"]).reshape(-1)[:k],
                        np.asarray(payload["indices"]).reshape(-1)[:k])

    def agg_finalize(self, acc, shape, dtype):
        return sparse_agg_finalize(acc, shape, dtype)

    def payload_bits(self, shape, dtype):
        # static wire size (the cap); true occupancy varies per step
        cap = self._cap(shape)
        return cap * (jnp.dtype(dtype).itemsize * 8 + 32) + 32
