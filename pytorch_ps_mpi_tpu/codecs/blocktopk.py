"""Blockwise top-k sparsification: selection tiled for the TPU.

Global top-k over a 132M-element flat gradient is the sparse-codec cost
problem. The global selection is the expensive part, not the gather: it sorts/scans
the full vector with cross-chip-of-the-array data movement.

Blockwise selection removes it. The flat gradient is viewed as
``[n_blocks, block_size]`` (lane-aligned ``block_size``, default 1024)
and each block keeps its own top ``round(block_size * fraction)``
entries — an embarrassingly parallel batched ``lax.top_k`` over rows,
mapping onto the VPU with zero cross-block traffic. The wire format is
identical to :class:`~.topk.TopKCodec` (values[k] + int32 global
indices[k]), so transports, EF wrapping and ``decode_sum`` fusion are
unchanged.

Selection quality: block-local top-k equals global top-k when large
entries are spread across blocks (the common case for gradient noise;
dense layers' gradients have no privileged memory order), and degrades
gracefully when they cluster — every block still ships its local
maxima, which is exactly the "each worker's own largest coordinates"
error-feedback literature tolerates (PAPERS.md: Stich et al. 2018 — EF
absorbs ANY contraction-factor selection, block-local included; pair
with ``ef`` for convergence-critical runs). The reference's external
``codings`` hook (SURVEY §2.2) put no constraint on selection semantics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_ps_mpi_tpu.codecs.base import Codec, register_codec
from pytorch_ps_mpi_tpu.codecs.topk import TopKCodec


@register_codec("blocktopk")
class BlockTopKCodec(TopKCodec):
    def __init__(self, fraction: float = 0.01, block_size: int = 1024,
                 approx: bool = False):
        """``fraction`` of each block survives (>= 1 entry per block).
        ``block_size`` should stay a multiple of the 128-lane register
        width; 1024 = one row of 8 sublanes. ``approx=True`` uses the
        TPU's hardware ``approx_max_k`` per block instead of exact
        ``top_k`` (only worth it for large per-block k)."""
        super().__init__(fraction=fraction, approx=approx)
        if block_size <= 0 or block_size % 128:
            raise ValueError("block_size must be a positive multiple of 128")
        self.block_size = int(block_size)

    def _block_k(self) -> int:
        return max(1, int(round(self.block_size * self.fraction)))

    def _n_blocks(self, n: int) -> int:
        """Block count for an n-element gradient; 1 == the single-block
        plain-top-k fallback regime. The ONE place the fallback
        threshold and ceil-div rule live (four call sites)."""
        return 1 if n <= self.block_size else -(-n // self.block_size)

    def _k_for(self, shape) -> int:
        """Total payload length: per-block k x number of blocks (the
        wire-size contract ``payload_bits`` inherits). Tensors no larger
        than one block take plain top-k's fraction-of-n (matching the
        ``encode`` fallback)."""
        n = int(np.prod(shape)) if shape else 1
        nb = self._n_blocks(n)
        if nb == 1:
            return super()._k_for(shape)
        # NOT capped at n: a ragged tail block still emits block_k pairs
        # (pad-slot picks carry out-of-range indices, dropped at scatter),
        # and the wire carries every one of them
        return nb * self._block_k()

    def encode(self, grad, state=(), rng=None):
        flat = grad.reshape(-1)
        n = flat.shape[0]
        nb = self._n_blocks(n)
        if nb == 1:
            return super().encode(grad, state, rng)  # plain top-k
        pad = nb * self.block_size - n
        # padding must never win selection, and if a short final block
        # still selects a padded slot its global index lands >= n and is
        # dropped at scatter time (mode='drop' in decode/decode_sum)
        blocks = jnp.concatenate(
            [flat, jnp.zeros((pad,), flat.dtype)]
        ).reshape(nb, self.block_size)
        kb = self._block_k()
        if self.approx:
            _, local = jax.lax.approx_max_k(jnp.abs(blocks), kb)
        else:
            _, local = jax.lax.top_k(jnp.abs(blocks), kb)
        glob = (jnp.arange(nb, dtype=jnp.int32)[:, None] * self.block_size
                + local.astype(jnp.int32))
        values = jnp.take_along_axis(blocks, local, axis=1)
        return {
            "values": values.reshape(-1),
            "indices": glob.reshape(-1),
        }, state
    # decode/decode_sum are inherited: TopKCodec scatters with
    # mode='drop', which discards this codec's >= n pad-slot indices and
    # is a no-op for plain top-k's always-in-range ones


@register_codec("blocktopk8")
class BlockTopK8Codec(BlockTopKCodec):
    """Compressed-sparse: blockwise top-k survivors with int8-quantized
    values (per-block symmetric scale). The two compression axes the
    reference's codings research explored separately — sparsification
    and quantization — composed: at fraction 1% the wire drops from
    top-k's 64 bits/survivor (f32 value + int32 index) to 40
    (int8 value + int32 index), ~1.6x less wire for one extra
    VPU-elementwise pass; selection cost is unchanged (same per-block
    ``top_k``). Survivors within a block share magnitude order (they ARE
    the block's largest), so a per-block scale loses little precision.
    Pair with ``ef`` to absorb the combined bias, as with any lossy
    codec."""

    def encode(self, grad, state=(), rng=None):
        payload, state = super().encode(grad, state, rng)
        v = payload["values"]  # [k_total] f32 (single-block: plain top-k)
        kb = v.shape[0] if self._n_blocks(grad.size) == 1 else self._block_k()
        blocks = v.reshape(-1, kb)
        scale = jnp.maximum(
            jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0, 1e-12
        )
        q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
        return {
            "values": q.reshape(-1),
            "scale": scale.astype(jnp.float32),
            "indices": payload["indices"],
        }, state

    @staticmethod
    def _dequant(payload, dtype):
        """int8 [.., k_total] x scale [.., nb, 1] -> float [.., k_total]
        (leading worker axis preserved for decode_sum's stacked form)."""
        q = payload["values"]
        nb = payload["scale"].shape[-2]
        blocks = q.reshape(q.shape[:-1] + (nb, -1)).astype(jnp.float32)
        return (blocks * payload["scale"]).reshape(q.shape).astype(dtype)

    def decode(self, payload, shape, dtype):
        return super().decode(
            {"values": self._dequant(payload, dtype),
             "indices": payload["indices"]},
            shape, dtype,
        )

    def decode_sum(self, payloads, shape, dtype):
        # via aggregate (which dequantizes): decode_sum(raw int8 payload)
        # and the compressed-domain path are one code path
        agg, meta = self.aggregate(payloads, shape, dtype)
        return self.agg_decode(agg, meta, shape, dtype)

    def aggregate(self, payloads, shape, dtype):
        # dequantize per rank (payload-sized), then the inherited sparse
        # index-merge — identical values/order to decode_sum (bit-exact)
        return super().aggregate(
            {"values": self._dequant(payloads, dtype),
             "indices": payloads["indices"]},
            shape, dtype,
        )

    def agg_fold(self, acc, payload):
        # dequant of the int8 survivors (per-block scale), then the
        # sparse fold. Native fast path: wc_fold_sparse_q8 fuses the
        # dequantize-multiply and the scatter-add into one C++ pass over
        # the payload; otherwise numpy dequant + shared concat fold.
        from pytorch_ps_mpi_tpu.codecs.base import sparse_agg_fold
        from pytorch_ps_mpi_tpu.utils import native as _native

        q = np.asarray(payload["values"])
        scale = np.asarray(payload["scale"], np.float32)
        lib = acc.get("lib")
        if lib is not None:
            # retained copy feeds both the C++ call and the pooled
            # buffer's re-zero record (see base.py sparse pool)
            idx = np.array(payload["indices"], np.int32,
                           copy=True).reshape(-1)
            _native.fold_sparse_q8(
                lib, acc["acc"],
                np.ascontiguousarray(q, np.int8).reshape(-1),
                np.ascontiguousarray(scale).reshape(-1), idx,
                acc_ptr=acc["ptr"])
            acc["touched"].append(idx)
            acc["frames"] += 1
            return
        val = (q.reshape(scale.shape[0], -1).astype(np.float32)
               * scale).reshape(-1)
        sparse_agg_fold(acc, val, payload["indices"])

    def payload_bits(self, shape, dtype):
        n = int(np.prod(shape)) if shape else 1
        return self._k_for(shape) * (8 + 32) + self._n_blocks(n) * 32
