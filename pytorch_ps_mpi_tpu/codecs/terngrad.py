"""TernGrad codec: stochastic ternary gradients, 2 bits per element.

Wen et al. 2017 (arXiv:1705.07878): each coordinate becomes
``s·sign(g)·b`` with ``b ~ Bernoulli(|g|/s)`` and ``s = max|g|`` — an
unbiased estimator (``E[decode] = g``), the midpoint of the compression
curve between int8 (4x) and sign (32x). One more point on the research
surface the reference's external ``codings`` hook existed to explore
(SURVEY §2.2).

Wire format: ternary digits {0,1,2} (= value -1,0,+1) packed 4 per byte
base-4, plus a float32 scale — a true 16x wire reduction on float32
gradients, all on-device (no host compressor, SURVEY §2.4).
"""

from __future__ import annotations

from functools import partial

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

_WEIGHTS = (1, 4, 16, 64)  # base-4 digit weights, 4 ternary digits per byte


def _packed_len(n: int) -> int:
    return (n + 3) // 4


@partial(jax.jit, static_argnames=("n",))
def _fused_tern_fold(acc, packed, scale, n):
    """acc + scale · unpack(packed) in one fused pass."""
    digits = (packed[:, None]
              // jnp.asarray(_WEIGHTS, jnp.uint8)[None, :]) % 4
    tern = digits.reshape(-1)[:n].astype(jnp.int8) - 1
    return acc + tern.astype(jnp.float32) * scale


@register_codec("terngrad")
class TernGradCodec(Codec):
    needs_rng = True
    # per-bucket max|g| scale instead of per-tensor under bucketing;
    # unbiasedness is preserved (scale is shared, Bernoulli stays exact)
    bucketable = True
    # exact ternary-count algebra: the batch form contracts the unpacked
    # {-1,0,+1} digits against the per-frame scale vector in one widened-
    # accumulator einsum (decode_sum routes through it); the streaming
    # form folds scale × ternary per push into an f32 accumulator —
    # integer unpack, one fused multiply-add, no per-push jitted decode
    supports_aggregate = True

    def __init__(self, nonfinite: str = "propagate",
                 scan_block: int = 1 << 20, scan_threshold: int = 0,
                 use_pallas: bool = False):
        """``scan_block``/``scan_threshold``: gradients with at least
        ``scan_threshold`` elements (default ``4 * scan_block``) encode
        through a ``lax.scan`` over ``scan_block``-element chunks so XLA
        never materializes a full-size f32 intermediate — the fix for
        the HLO temps the whole-tensor form allocates on a BERT-base
        gradient (the uniform draw + keep probability both go [132M]
        f32; 16 B/element, tests/test_agg.py pins the chunked bound).
        Per-chunk PRNG keys derive from the round key by fold-in, so the
        stream differs from the whole-tensor form — irrelevant for an
        unbiased stochastic codec — while wire format and size are
        unchanged.

        ``use_pallas=True`` routes sizes divisible by 512 through the
        fused ternarize+pack kernel (``ops/tern_pallas.tern_pack``):
        compare → digit → base-4 pack in ONE VMEM pass over the
        gradient and a tile of raw random bits, so the f32 uniform
        draw, keep mask, and digit tensor never hit HBM. NOTE: the
        Pallas bit layout groups by sublane (digit s of packed byte
        [r, lane] holds element r*512 + s*128 + lane) while the jnp
        path packs 4 consecutive elements per byte — payloads are only
        self-consistent within one codec configuration, and the native
        C++ wire fold (flat layout) declines Pallas-layout units (the
        numpy fold handles both layouts)."""
        # a NaN/Inf element drives the max|g| scale non-finite AND makes
        # its keep-probability NaN (uniform < NaN is False, so the digit
        # silently collapses to 0) — guard per codecs/base.guard_nonfinite
        self.nonfinite = check_nonfinite_mode(nonfinite)
        if scan_block <= 0 or scan_block % 4:
            raise ValueError("scan_block must be a positive multiple of 4")
        self.scan_block = int(scan_block)
        self.scan_threshold = (int(scan_threshold) if scan_threshold > 0
                               else 4 * self.scan_block)
        self.use_pallas = bool(use_pallas)

    def _pallas_ok(self, n: int) -> bool:
        # 512 = one packed Pallas row (4 sublanes × 128 lanes). Above
        # the scan threshold the chunks must divide into rows too: with
        # scan_block % 512 == 0 every full chunk AND the ragged tail
        # inherit n's divisibility (tail ≡ n mod scan_block), and the
        # per-chunk packs concatenate into exactly the whole-tensor
        # Pallas layout (chunks are whole numbers of packed rows)
        if not (self.use_pallas and n > 0 and n % 512 == 0):
            return False
        return n < self.scan_threshold or self.scan_block % 512 == 0

    def _digits(self, g, scale, rng):
        """g (any shape) → ternary digits {0,1,2} (uint8, same shape)."""
        keep = jax.random.uniform(rng, g.shape) < (jnp.abs(g) / scale)
        # ternary digit: 0 -> -1, 1 -> 0, 2 -> +1
        return jnp.where(keep, jnp.where(g >= 0, 2, 0), 1).astype(jnp.uint8)

    def encode(self, grad, state=(), rng=None):
        assert rng is not None, "TernGradCodec needs a PRNG key"
        g = guard_nonfinite(grad.astype(jnp.float32), self.nonfinite,
                            "TernGradCodec")
        n = int(np.prod(g.shape)) if g.shape else 1
        weights = jnp.asarray(_WEIGHTS, jnp.uint8)

        def pack_digits(d):
            return (d.reshape(-1, 4) * weights).sum(axis=1).astype(jnp.uint8)

        pallas = self._pallas_ok(n)
        if pallas:
            from pytorch_ps_mpi_tpu.ops.tern_pallas import tern_pack
        if n >= self.scan_threshold:
            # chunked encode: scan over scan_block-element slices — the
            # absmax pass AND the Bernoulli/pack pass both run one chunk
            # at a time, so peak temp is a chunk's intermediates (XLA
            # reuses the loop-body buffers), never an n-sized f32 tensor
            # (the whole-tensor form materializes abs|g| + the uniform
            # draw). A ragged tail (< scan_block elements)
            # encodes outside the scan with chunk-sized temps; its digit
            # offset stays 4-aligned because scan_block is.
            blk = self.scan_block
            nb_full = n // blk
            tail_n = n - nb_full * blk
            flat = g.reshape(-1)
            idxs = jnp.arange(nb_full, dtype=jnp.int32)

            def chunk(i):
                # dynamic_slice, not a pre-reshaped xs array: the scan
                # reads blk elements straight out of the input buffer,
                # so no n-sized copy exists even at ragged sizes
                return jax.lax.dynamic_slice(flat, (i * blk,), (blk,))

            def mx_body(m, i):
                return jnp.maximum(m, jnp.max(jnp.abs(chunk(i)))), None

            scale, _ = jax.lax.scan(mx_body, jnp.float32(1e-12), idxs)
            tail = flat[nb_full * blk:] if tail_n else None
            if tail_n:
                scale = jnp.maximum(scale, jnp.max(jnp.abs(tail)))

            def body(_, i):
                key = jax.random.fold_in(rng, i)
                c = chunk(i)
                if pallas:
                    # fused compare/digit/pack: per-chunk raw bits are
                    # the only full-chunk temp (u32, reused across scan
                    # iterations) — the uniform f32 / keep / digit
                    # tensors never exist
                    bits = jax.random.bits(key, (blk,), jnp.uint32)
                    return 0, tern_pack(c, bits, scale)
                return 0, pack_digits(self._digits(c, scale, key))

            _, packed = jax.lax.scan(body, 0, idxs)
            parts = [packed.reshape(-1)]
            if tail_n:
                key = jax.random.fold_in(rng, nb_full)
                if pallas:
                    # tail_n ≡ n mod 512 == 0 (see _pallas_ok), so the
                    # tail packs with the same fused kernel and its
                    # bytes continue the global sublane layout exactly
                    bits = jax.random.bits(key, (tail_n,), jnp.uint32)
                    parts.append(tern_pack(tail, bits, scale))
                else:
                    d = self._digits(tail, scale, key)
                    pad = _packed_len(tail_n) * 4 - tail_n
                    parts.append(pack_digits(
                        jnp.pad(d, (0, pad), constant_values=1)))
            packed = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            return {"packed": packed,
                    "scale": scale.astype(jnp.float32)}, state
        scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12)
        if pallas:
            bits = jax.random.bits(rng, (n,), jnp.uint32)
            return {"packed": tern_pack(g.reshape(-1), bits, scale),
                    "scale": scale.astype(jnp.float32)}, state
        # draw the Bernoulli randoms in the gradient's NATIVE shape and
        # flatten only the resulting uint8 digits: fusing a 132M-element
        # threefry with a reshape-derived probability tensor crashes the
        # TPU compile helper (observed on v5e; 1-D and native-shape forms
        # compile fine)
        digit = self._digits(g, scale, rng)
        pad = _packed_len(n) * 4 - n
        packed = pack_digits(
            jnp.pad(digit.reshape(-1), (0, pad), constant_values=1))
        return {"packed": packed, "scale": scale.astype(jnp.float32)}, state

    def _unpack(self, packed, n):
        if self._pallas_ok(n):
            # sublane-grouped layout: byte [r, lane] holds digits of
            # elements r*512 + s*128 + lane — the [rows, 4, 128] digit
            # cube flattens back to element order
            digits = (packed.reshape(-1, 128)[:, None, :]
                      // jnp.asarray(_WEIGHTS, jnp.uint8)[None, :, None]) % 4
        else:
            digits = (packed[:, None]
                      // jnp.asarray(_WEIGHTS, jnp.uint8)[None, :]) % 4
        return digits.reshape(-1)[:n].astype(jnp.int8) - 1  # {-1, 0, +1}

    def decode(self, payload, shape, dtype):
        n = int(np.prod(shape)) if shape else 1
        if self._pallas_ok(n):
            # fused dequantizing unpack (digits and the ±scale values
            # never exist separately)
            from pytorch_ps_mpi_tpu.ops.tern_pallas import tern_unpack

            g = tern_unpack(payload["packed"], payload["scale"])
            return g.astype(dtype).reshape(shape)
        tern = self._unpack(payload["packed"], n)
        return (tern.astype(dtype) * payload["scale"].astype(dtype)).reshape(shape)

    def decode_sum(self, payloads, shape, dtype):
        # Sum of per-rank scaled ternaries without materializing [world, n]
        # floats — routed through the exact ternary-count aggregation.
        agg, meta = self.aggregate(payloads, shape, dtype)
        return self.agg_decode(agg, meta, shape, dtype)

    def aggregate(self, payloads, shape, dtype):
        # ternary-count contraction: the [world, n] int8 digit matrix
        # meets the [world] scale vector inside one widened-accumulator
        # einsum — the integer payloads never become a float stack
        n = int(np.prod(shape)) if shape else 1
        tern = jax.vmap(lambda p: self._unpack(p, n))(payloads["packed"])
        acc = jnp.einsum("wn,w->n", tern,
                         payloads["scale"].astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        return {"acc": acc}, {"frames": int(tern.shape[0])}

    def agg_decode(self, agg_payload, meta, shape, dtype):
        return agg_payload["acc"].astype(dtype).reshape(shape)

    def agg_init(self, shape, dtype):
        return scalefold_agg_init(shape)

    def agg_fold(self, acc, payload):
        # base-4 unpack (integer ops), then one per-frame scale-folded
        # multiply-add into the f32 accumulator; the native fast path
        # fuses unpack + MA into one C++ pass, large units otherwise run
        # the jitted fused kernel, small ones pure numpy
        packed = payload["packed"].reshape(-1)
        if self._pallas_ok(acc["n"]):
            # sublane-grouped Pallas layout: the native kernel and the
            # jitted fused fold both assume the flat base-4 grouping —
            # layout-aware numpy unpack + multiply-add instead (still
            # exact; only the fast paths decline)
            p = np.ascontiguousarray(packed, np.uint8).reshape(-1, 128)
            digits = (p[:, None, :]
                      // np.asarray(_WEIGHTS, np.uint8)[None, :, None]) % 4
            tern = digits.reshape(-1)[: acc["n"]].astype(np.int8) - 1
            acc["acc"] = acc["acc"] + (tern.astype(np.float32)
                                       * np.float32(payload["scale"]))
            acc["frames"] += 1
            return
        lib = acc.get("lib")
        if lib is not None:
            from pytorch_ps_mpi_tpu.utils import native as _native

            _native.fold_tern(
                lib, acc["acc"], np.ascontiguousarray(packed, np.uint8),
                np.float32(payload["scale"]))
            acc["frames"] += 1
            return
        if acc.get("jit"):
            acc["acc"] = _fused_tern_fold(
                acc["acc"], packed, np.float32(payload["scale"]),
                acc["n"])
        else:
            digits = (packed[:, None] //
                      np.asarray(_WEIGHTS, np.uint8)[None, :]) % 4
            tern = digits.reshape(-1)[: acc["n"]].astype(np.int8) - 1
            np.multiply(tern, np.float32(payload["scale"]), out=acc["tmp"])
            acc["acc"] += acc["tmp"]
        acc["frames"] += 1

    def agg_finalize(self, acc, shape, dtype):
        return dense_agg_finalize(acc, shape, dtype)

    def payload_bits(self, shape, dtype):
        n = int(np.prod(shape)) if shape else 1
        return _packed_len(n) * 8 + 32
