"""Ring attention: sequence/context parallelism over a mesh axis.

No reference analog — the reference scales workers, never sequence length
(constraint "models fit on one device", reference ``README.md:6``; SURVEY
§5.7) — but long-context is first-class here. Each device holds a shard of
the sequence; K/V blocks rotate around the ring via ``lax.ppermute`` (one
neighbor ICI hop per step) while attention accumulates online with the
numerically-stable streaming softmax (Milakov & Gimelshein / flash-
attention style max-shift rescaling). Peak memory per chip is O(L_local²)
instead of O(L²), and XLA overlaps each block's compute with the next
block's permute — the collective/compute overlap the reference built from
threads + MPI requests (``ps.py:65-66``), here falling out of the dataflow.

Call inside ``shard_map`` with q/k/v sharded on the sequence axis.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG_BIG = -1e30


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """Ring attention over sequence shards.

    Args:
      q, k, v: ``[batch, seq_local, heads, head_dim]`` — this device's
        sequence shard (global seq = seq_local × axis_size).
      axis_name: mesh axis the sequence is sharded over.
      causal: apply a causal mask in *global* sequence coordinates.
      scale: logit scale; default ``head_dim ** -0.5``.
      use_flash: compute each rotating block with the Pallas flash
        kernel (``ops/attention_pallas.py``) instead of a dense jnp
        block — per-block outputs combine via their logsumexp (the lse
        cotangent path keeps it differentiable). Default: auto (kernel
        on TPU when the local shard tiles; dense jnp otherwise).

    Returns ``[batch, seq_local, heads, head_dim]``: this shard's rows of
    full-sequence attention.
    """
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, l_q, h, d = q.shape
    l_k = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    if use_flash is None:
        from pytorch_ps_mpi_tpu.ops.attention_pallas import flash_auto_ok

        use_flash = flash_auto_ok(l_q, l_k, q.dtype)

    q_pos = my_idx * l_q + jnp.arange(l_q)            # global query positions

    def block(q, k_blk, v_blk, src_idx):
        """Attend local q against one rotating K/V block."""
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale
        if causal:
            k_pos = src_idx * k_blk.shape[1] + jnp.arange(k_blk.shape[1])
            mask = k_pos[None, :] <= q_pos[:, None]    # [q, k]
            s = jnp.where(mask[None, None], s, _NEG_BIG)
        return s

    def step(carry, _):
        k_cur, v_cur, src_idx, num, den, mx = carry
        if use_flash:
            # block attention in VMEM; combine normalized block outputs
            # by their logsumexp (max-shift weights — same streaming
            # softmax, one level up)
            from pytorch_ps_mpi_tpu.ops.attention_pallas import (
                flash_attention,
            )

            o_blk, lse_blk = flash_attention(
                q, k_cur, v_cur, causal=causal, scale=scale,
                q_offset=(my_idx * l_q).astype(jnp.int32),
                k_offset=(src_idx * l_k).astype(jnp.int32),
                return_lse=True,
            )
            o_blk = o_blk.transpose(0, 2, 1, 3)        # [b, h, q, d]
            new_mx = jnp.maximum(mx, lse_blk)
            corr = jnp.exp(mx - new_mx)
            # explicit guard: a fully-masked block's lse is ~-1e30; if mx
            # is ALSO still at its init floor, exp(lse-new_mx)=exp(0)=1
            # would smuggle the masked block in
            w = jnp.where(lse_blk > -1e29,
                          jnp.exp(lse_blk - new_mx), 0.0)
            num = num * corr[..., None] + o_blk * w[..., None]
            den = den * corr + w
        else:
            s = block(q, k_cur, v_cur, src_idx)        # [b, h, q, k]
            blk_max = s.max(axis=-1)                   # [b, h, q]
            new_mx = jnp.maximum(mx, blk_max)
            corr = jnp.exp(mx - new_mx)
            p = jnp.exp(s - new_mx[..., None])         # [b, h, q, k]
            num = num * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_cur
            )
            den = den * corr + p.sum(axis=-1)
        # rotate K/V to the next rank; we now hold the previous rank's block
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        src_nxt = (src_idx - 1) % n
        return (k_nxt, v_nxt, src_nxt, num, den, new_mx), None

    # Under shard_map with check_vma=True the scan carry's
    # varying-manual-axes type must be loop-invariant; freshly-built
    # zeros are device-invariant while the loop body makes them vary over
    # every axis q varies over (seq, plus data/model when composed with
    # DP/TP). Deriving the initial accumulators FROM q inherits exactly
    # q's vma — version-portable, and XLA folds the arithmetic away.
    # The isfinite select keeps ±inf activations (overflowed upstream)
    # from poisoning the accumulators via 0 * inf = NaN.
    zq = jnp.transpose(q, (0, 2, 1, 3))                # [b, h, l_q, d]
    z = jnp.where(jnp.isfinite(zq), zq * 0, 0.0)
    num0 = z
    den0 = z[..., 0]
    mx0 = z[..., 0] + _NEG_BIG
    carry0 = (k, v, my_idx, num0, den0, mx0)
    (_, _, _, num, den, _), _ = lax.scan(step, carry0, None, length=n)

    out = num / jnp.maximum(den, 1e-30)[..., None]     # [b, h, q, d]
    return out.transpose(0, 2, 1, 3)                   # [b, q, h, d]


def ring_self_attention(
    x_qkv: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
) -> jax.Array:
    """Convenience wrapper: ``x_qkv`` is ``[3, batch, seq_local, heads,
    head_dim]`` (stacked q/k/v)."""
    return ring_attention(x_qkv[0], x_qkv[1], x_qkv[2], axis_name, causal=causal)
