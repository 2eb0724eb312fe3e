"""Quantization codecs: deterministic int8 and stochastic QSGD.

The on-device replacement for the reference's host-side blosc byte
compression (``mpi_comms.py:18-30``): instead of entropy-coding pickled
bytes on the CPU (which an ICI link outruns by orders of magnitude), the
gradient itself is narrowed to 8 or fewer bits per element before the
collective. The int8 path has a fused Pallas kernel on TPU
(``ops/quant_pallas.py``); this module is the portable jnp reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_ps_mpi_tpu.codecs.base import (
    Codec,
    check_nonfinite_mode,
    dense_agg_finalize,
    guard_nonfinite,
    register_codec,
    scalefold_agg_init,
)


@jax.jit
def _fused_scale_fold(acc, q, scale):
    """acc + scale * q in ONE fused pass (int8 payload in, f32 out)."""
    return acc + q.astype(jnp.float32) * scale


class _ScaleFoldedInt8(Codec):
    """Shared exact integer-domain aggregation for codecs whose decode is
    ``scale × q`` over an int8 payload (int8's absmax scale, QSGD's
    norm/levels). The batch form contracts the [world, n] int8 payload
    against the per-frame scale vector in ONE widened-accumulator einsum
    — never materializing the [world, n] f32 dequantized intermediate
    (at ResNet scale × 8 workers that is ~1.4 GB of HBM traffic just to
    feed a sum) — and ``decode_sum`` routes through it, so the two paths
    are one code path (bit-exact by construction). The streaming form
    folds scale_w × q_w into an f32 accumulator per push: the jitted
    fused kernel above the ``base.FOLD_JIT_MIN`` crossover (one SIMD
    dequant-multiply-add pass), pure numpy below it (no dispatch cost).
    Subclasses provide the scale in both shapes."""

    supports_aggregate = True

    def _batch_scales(self, payloads) -> jax.Array:
        """Per-frame scale vector, [world] f32."""
        raise NotImplementedError

    def _frame_scale(self, payload) -> np.float32:
        """One frame's scale scalar (numpy, host-side)."""
        raise NotImplementedError

    def decode_sum(self, payloads, shape, dtype):
        agg, meta = self.aggregate(payloads, shape, dtype)
        return self.agg_decode(agg, meta, shape, dtype)

    def aggregate(self, payloads, shape, dtype):
        q = payloads["q"]                     # [world, n] int8
        acc = jnp.einsum("wn,w->n", q, self._batch_scales(payloads),
                         preferred_element_type=jnp.float32)
        return {"acc": acc}, {"frames": int(q.shape[0])}

    def agg_decode(self, agg_payload, meta, shape, dtype):
        return agg_payload["acc"].astype(dtype).reshape(shape)

    def agg_init(self, shape, dtype):
        return scalefold_agg_init(shape)

    def agg_fold(self, acc, payload):
        scale = self._frame_scale(payload)
        lib = acc.get("lib")
        if lib is not None:
            # native fast path: ONE fused dequant-multiply-add pass in
            # C++ over the int8 payload view — no temp, no dispatch
            from pytorch_ps_mpi_tpu.utils import native as _native

            _native.fold_scaled_i8(
                lib, acc["acc"],
                np.ascontiguousarray(payload["q"], np.int8).reshape(-1),
                scale)
        elif acc.get("jit"):
            acc["acc"] = _fused_scale_fold(
                acc["acc"], payload["q"].reshape(-1), scale)
        else:
            np.multiply(payload["q"].reshape(-1), scale, out=acc["tmp"])
            acc["acc"] += acc["tmp"]
        acc["frames"] += 1

    def agg_finalize(self, acc, shape, dtype):
        return dense_agg_finalize(acc, shape, dtype)

    def payload_bits(self, shape, dtype):
        n = int(np.prod(shape)) if shape else 1
        return n * 8 + 32


@register_codec("int8")
class Int8Codec(_ScaleFoldedInt8):
    """Per-tensor symmetric int8: q = round(g / scale), scale = max|g|/127.

    ``use_pallas`` defaults to False: no chip number; see ``PERF.md``
    section 7.
    """

    # shape-agnostic + stateless: bucketed aggregation quantizes with a
    # per-BUCKET absmax scale instead of per-tensor (coarser scale group)
    bucketable = True

    def __init__(self, use_pallas: bool = False,
                 nonfinite: str = "propagate"):
        self.use_pallas = use_pallas
        # one Inf element drives the absmax scale to Inf (every other
        # element quantizes to 0); a NaN scale poisons the whole decode —
        # guard per codecs/base.guard_nonfinite
        self.nonfinite = check_nonfinite_mode(nonfinite)

    def encode(self, grad, state=(), rng=None):
        flat = guard_nonfinite(grad.reshape(-1), self.nonfinite, "Int8Codec")
        if self.use_pallas:
            from pytorch_ps_mpi_tpu.ops.quant_pallas import quantize_int8
            q, scale = quantize_int8(flat)
        else:
            scale = jnp.maximum(jnp.max(jnp.abs(flat)) / 127.0, 1e-12)
            q = jnp.clip(jnp.round(flat / scale), -127, 127).astype(jnp.int8)
        return {"q": q, "scale": scale.astype(jnp.float32)}, state

    def decode(self, payload, shape, dtype):
        return (payload["q"].astype(dtype) * payload["scale"].astype(dtype)).reshape(shape)

    def _batch_scales(self, payloads):
        return payloads["scale"].astype(jnp.float32)

    def _frame_scale(self, payload):
        return np.float32(payload["scale"])


@register_codec("qsgd")
class QSGDCodec(_ScaleFoldedInt8):
    """QSGD (Alistarh et al. 2017): stochastic uniform quantization to
    ``levels`` buckets of the normalized magnitude; unbiased."""

    needs_rng = True
    # per-bucket norm instead of per-tensor under bucketing; still unbiased
    bucketable = True

    def __init__(self, levels: int = 16, nonfinite: str = "propagate"):
        # levels must fit the int8 payload: encode stores q in [-levels,
        # levels], so levels > 127 would silently overflow int8.
        if not 1 <= levels <= 127:
            raise ValueError(f"levels must be in [1, 127], got {levels}")
        self.levels = int(levels)
        # a non-finite element makes the L2 norm NaN/Inf, turning every
        # quantized magnitude into garbage (NaN probabilities round the
        # stochastic rounding to 0) — guard per codecs/base.guard_nonfinite
        self.nonfinite = check_nonfinite_mode(nonfinite)

    def encode(self, grad, state=(), rng=None):
        assert rng is not None, "QSGDCodec needs a PRNG key"
        flat = guard_nonfinite(grad.reshape(-1), self.nonfinite, "QSGDCodec")
        norm = jnp.maximum(jnp.linalg.norm(flat), 1e-12)
        scaled = jnp.abs(flat) / norm * self.levels          # in [0, levels]
        lower = jnp.floor(scaled)
        prob_up = scaled - lower
        up = jax.random.uniform(rng, flat.shape) < prob_up
        q = (lower + up.astype(flat.dtype)).astype(jnp.int8)  # levels ≤ 127
        signs = jnp.signbit(flat)
        return {
            "q": jnp.where(signs, -q, q).astype(jnp.int8),
            "norm": norm.astype(jnp.float32),
        }, state

    def decode(self, payload, shape, dtype):
        g = payload["q"].astype(dtype) * (payload["norm"].astype(dtype) / self.levels)
        return g.reshape(shape)

    def _batch_scales(self, payloads):
        return payloads["norm"].astype(jnp.float32) / self.levels

    def _frame_scale(self, payload):
        return np.float32(payload["norm"]) / np.float32(self.levels)
