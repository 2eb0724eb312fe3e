"""Fused TernGrad ternarize + base-4 pack as Pallas TPU kernels.

The jnp encode path runs four separate full-size passes per gradient:
the uniform draw (f32), the keep-probability compare, the ternary digit
select, and the reshape-weight-sum pack — each materializing an n-sized
intermediate in HBM. Here
the compare → digit → pack pipeline is ONE gridded VMEM pass: the
kernel reads the gradient tile and a tile of raw uint32 random bits and
writes packed bytes directly — the f32 uniform tensor, the bool keep
mask, and the digit tensor never exist.

Randomness comes in as raw ``jax.random.bits`` uint32 (the TPU Pallas
PRNG primitives have no interpret-mode lowering on this jax, and the
caller already owns chunked key derivation for the scan path): the top
24 bits compare against ``|x|/s * 2^24``, the same 24-bit Bernoulli
resolution ``jax.random.uniform`` has via the f32 mantissa.

Layout: the flat input is viewed as ``[rows, 4, 128]`` — 4 consecutive
*sublanes* fold into one packed row of 128 lanes, so digit ``s`` of
packed byte ``[r, lane]`` holds element ``r*512 + s*128 + lane``. Like
``sign_pallas``, this differs from the jnp path's 4-consecutive-
elements-per-byte grouping: payloads are self-consistent within one
codec configuration (every worker runs the same codec), and the codec
declines host-side aggregation for Pallas-layout units.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.ops._common import LANE as _LANE
from pytorch_ps_mpi_tpu.ops._common import interpret as _interpret

_GROUP = 4 * _LANE  # one packed row of 128 bytes encodes 512 ternaries

_BLOCK_ROWS = 256  # 256×4×128 f32 ×2 inputs = 1 MiB of VMEM tiles


def _weights():
    # base-4 digit weights [1, 4, 16, 64]; int32 (Mosaic has no
    # unsigned reductions)
    return (4 ** jnp.arange(4, dtype=jnp.int32))[None, :, None]


def _pack_kernel(x_ref, u_ref, scale_ref, out_ref):
    x = x_ref[:]                                   # [rows, 4, 128] f32
    u = u_ref[:]                                   # [rows, 4, 128] u32
    s = scale_ref[0, 0]
    # Bernoulli(|x|/s) at 24-bit resolution: top 24 random bits vs
    # p·2^24 — both exact in f32, so the compare is deterministic
    p24 = jnp.abs(x) * (16777216.0 / s)
    # Mosaic has no uint32 -> float32 cast; the top 24 bits fit int32
    u24 = jax.lax.bitcast_convert_type(u >> 8, jnp.int32).astype(jnp.float32)
    keep = u24 < p24
    # ternary digit: 0 -> -1, 1 -> 0, 2 -> +1
    digit = jnp.where(keep, jnp.where(x >= 0, 2, 0), 1).astype(jnp.int32)
    out_ref[:] = (digit * _weights()).sum(axis=1).astype(jnp.uint8)


def tern_pack(flat: jax.Array, rand_u32: jax.Array, scale: jax.Array):
    """float32[n] + uint32[n] bits + scalar scale -> uint8[n/4] packed
    ternary digits (n % 512 == 0). One fused compare/digit/pack pass."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = flat.shape[0]
    assert n % _GROUP == 0, n
    rows = n // _GROUP
    x3d = flat.reshape(rows, 4, _LANE)
    u3d = rand_u32.reshape(rows, 4, _LANE)
    grid = ((rows + _BLOCK_ROWS - 1) // _BLOCK_ROWS,)
    out = pl.pallas_call(
        _pack_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), jnp.uint8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, 4, _LANE), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_BLOCK_ROWS, 4, _LANE), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(x3d, u3d, jnp.asarray(scale, jnp.float32).reshape(1, 1))
    return out.reshape(n // 4)


def _unpack_kernel(p_ref, scale_ref, out_ref):
    p = p_ref[:].astype(jnp.int32)                 # [rows, 128]
    digits = (p[:, None, :] // _weights()) % 4     # [rows, 4, 128]
    out_ref[:] = (digits - 1).astype(jnp.float32) * scale_ref[0, 0]


def tern_unpack(packed: jax.Array, scale: jax.Array) -> jax.Array:
    """uint8[m] (m % 128 == 0) + scalar scale -> float32[4m] of
    scale·{-1, 0, +1} — the fused dequantizing unpack."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m = packed.shape[0]
    assert m % _LANE == 0, m
    rows = m // _LANE
    p2d = packed.reshape(rows, _LANE)
    grid = ((rows + _BLOCK_ROWS - 1) // _BLOCK_ROWS,)
    out = pl.pallas_call(
        _unpack_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, 4, _LANE), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, 4, _LANE), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(p2d, jnp.asarray(scale, jnp.float32).reshape(1, 1))
    return out.reshape(m * 4)
