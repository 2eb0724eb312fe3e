"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

The second canonical long-context design (Jacobs et al. 2023, DeepSpeed-
Ulysses, arXiv:2309.14509 — public technique), complementing
``parallel/ring.py``: where ring attention keeps heads whole and rotates
K/V blocks around the ring (N-1 ppermute hops, O(L_local²) memory),
Ulysses transposes the sharding with ONE ``lax.all_to_all`` each way —
tokens-sharded activations become heads-sharded, every device then runs
ordinary full-sequence attention for its subset of heads, and a second
all_to_all restores token sharding. Two collectives total, O(L²/N) score
memory per device, requires ``heads % axis_size == 0``.

When to choose which (both ride the same mesh axis):
- ring: unbounded sequence growth, heads can be few; overlaps compute
  with neighbor hops.
- ulysses: plenty of heads, wants the plain fused attention kernel
  unchanged; minimal collective count.

Call inside ``shard_map`` with q/k/v sharded on the sequence axis
(``[batch, seq_local, heads, head_dim]`` — same convention as ring).
No reference analog (the reference never scales sequence length,
``README.md:6``); the all_to_all is the op class its MPI exploration
stopped at (``test_mpi.py:20`` Ialltoallv).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _seq_to_heads(x: jax.Array, axis_name: str) -> jax.Array:
    """[b, l_loc, h, d] (seq-sharded) -> [b, l_loc*N, h_loc, d]
    (head-sharded, full sequence) with one all_to_all."""
    n = lax.axis_size(axis_name)
    b, l_loc, h, d = x.shape
    h_loc = h // n
    # [b, l_loc, n, h_loc, d] -> [n, b, l_loc, h_loc, d]
    x = x.reshape(b, l_loc, n, h_loc, d).transpose(2, 0, 1, 3, 4)
    # send head-group j to device j; receive every device's tokens for
    # MY head group: leading dim becomes the source (= seq block) index
    x = lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0)
    # [n, b, l_loc, h_loc, d] -> [b, n*l_loc, h_loc, d] (seq blocks in
    # device order = global token order)
    return x.transpose(1, 0, 2, 3, 4).reshape(b, n * l_loc, h_loc, d)


def _heads_to_seq(x: jax.Array, axis_name: str) -> jax.Array:
    """Inverse of :func:`_seq_to_heads`."""
    n = lax.axis_size(axis_name)
    b, l_full, h_loc, d = x.shape
    l_loc = l_full // n
    x = x.reshape(b, n, l_loc, h_loc, d).transpose(1, 0, 2, 3, 4)
    x = lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0)
    # leading dim now indexes head groups -> fold back into the head axis
    return x.transpose(1, 2, 0, 3, 4).reshape(b, l_loc, n * h_loc, d)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """Full-sequence attention under Ulysses sequence parallelism.

    Args:
      q, k, v: ``[batch, seq_local, heads, head_dim]`` — this device's
        sequence shard; ``heads`` must divide by the axis size.
      axis_name: mesh axis the sequence is sharded over.
      causal: standard causal mask (global coordinates are naturally
        correct here — every device sees the full sequence).
      scale: logit scale; default ``head_dim ** -0.5``.
      use_flash: run the post-exchange local attention through the
        Pallas flash kernel (this is exactly Ulysses' selling point —
        "the plain fused attention kernel unchanged"). Default: auto
        (kernel on TPU when the full sequence tiles).

    Returns ``[batch, seq_local, heads, head_dim]``.
    """
    if q.shape[2] % lax.axis_size(axis_name) != 0:
        raise ValueError(
            f"heads={q.shape[2]} must divide by axis size "
            f"{lax.axis_size(axis_name)} for Ulysses SP (use ring "
            "attention when heads are scarce)"
        )
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    # one outbound exchange for all of q/k/v (identical shape+sharding):
    # stacking keeps the module's two-collectives-total cost claim true
    qkv = _seq_to_heads(
        jnp.concatenate([q, k, v], axis=0), axis_name
    )                                                   # [3b, L, h_loc, d]
    b = q.shape[0]
    qh, kh, vh = qkv[:b], qkv[b:2 * b], qkv[2 * b:]
    l_full = qh.shape[1]
    if use_flash is None:
        from pytorch_ps_mpi_tpu.ops.attention_pallas import flash_auto_ok

        use_flash = flash_auto_ok(l_full, l_full, qh.dtype)
    if use_flash:
        from pytorch_ps_mpi_tpu.ops.attention_pallas import flash_attention

        out = flash_attention(qh, kh, vh, causal=causal, scale=scale)
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * scale
        if causal:
            mask = jnp.tril(jnp.ones((l_full, l_full), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, vh)     # [b, L, h_loc, d]
    return _heads_to_seq(out, axis_name)
