"""Exact top-k selection via per-block threshold refine — Pallas TPU.

``lax.top_k`` on a multi-million-element flat gradient lowers to a full
bitonic sort, several times the cost of ``lax.approx_max_k`` — a tax for
exactness. This module aims to close the gap without giving up exactness
by splitting selection into the two parts with very different costs:

1. **Threshold refine (Pallas count kernel).** The k-th largest |x| is
   found WITHOUT sorting: |x| is viewed as its int32 bit pattern (for
   non-negative floats the bit order IS the value order), and the
   threshold is built bit by bit from the MSB — 31 rounds of "does
   count(key >= candidate) still reach k?", each round one gridded
   Pallas pass that accumulates per-block counts into an SMEM scalar
   (sequential TPU grid, race-free — the per-block threshold refine).
   Each pass is a memory-bound read of n int32s.

2. **Chunked compaction.** With the exact threshold in hand, survivor
   indices are compacted by per-chunk biased-key sorts — ONE vectorized
   ``lax.sort`` over ``[n_chunks, chunk]``, bitonic depth log²(chunk)
   instead of log²(n) — followed by a sequential cursor merge
   (``dynamic_update_slice`` per chunk, each write's garbage tail
   overwritten by its successor). Strict survivors (> threshold) land
   first in global index order, then exactly ``k - m`` threshold ties
   fill the remainder.

The returned (values, indices) hold EXACTLY the k largest-magnitude
elements (ties broken in index order, where ``lax.top_k`` breaks them in
its sort order — same value multiset, asserted by the tests). Runs in
interpret mode off-TPU, so CPU CI tests the algorithm end to end.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_ps_mpi_tpu.ops._common import LANE as _LANE
from pytorch_ps_mpi_tpu.ops._common import interpret as _interpret

_BLOCK_ROWS = 1024           # 1024×128 i32 = 512 KiB per count tile
_TILE = _BLOCK_ROWS * _LANE


def _count_kernel(t_ref, x_ref, out_ref):
    """Per-block ge/gt counts vs the SMEM threshold, accumulated across
    the sequential grid into one SMEM (1, 2) vector."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[0, 0] = 0
        out_ref[0, 1] = 0

    x = x_ref[:]
    t = t_ref[0, 0]
    out_ref[0, 0] += jnp.sum((x >= t).astype(jnp.int32))
    out_ref[0, 1] += jnp.sum((x > t).astype(jnp.int32))


def _counts(keys2d: jax.Array, t: jax.Array):
    """(count_ge, count_gt) of the padded int32 key plane vs scalar t."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = keys2d.shape[0]
    grid = ((rows + _BLOCK_ROWS - 1) // _BLOCK_ROWS,)
    out = pl.pallas_call(
        _count_kernel,
        out_shape=jax.ShapeDtypeStruct((1, 2), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=_interpret(),
    )(t.reshape(1, 1), keys2d)
    return out[0, 0], out[0, 1]


def _kth_threshold(keys2d: jax.Array, k: int):
    """The k-th largest key, built bit by bit (31 count passes): the
    largest t with count(key >= t) >= k. Keys are non-negative (float
    bit patterns of |x|; padding is -1 and never counted)."""

    def body(b, t):
        cand = t | (jnp.int32(1) << (30 - b))
        ge, _ = _counts(keys2d, cand)
        return jnp.where(ge >= k, cand, t)

    return jax.lax.fori_loop(0, 31, body, jnp.int32(0))


def _compact_two_phase(skeys, counts_strict, counts_tie, chunk, k):
    """Cursor-merge the per-chunk sorted prefixes: strict survivors
    first (global index order), then threshold ties filling to k.
    ``skeys`` is [nc, chunk + take] — per-chunk ascending 3-level biased
    keys (strict -> pos, tie -> pos + C, rest -> pos + 2C) padded with
    take sentinel columns so the tie-phase dynamic slice never clamps."""
    C = chunk
    nc = skeys.shape[0]
    take = min(C, k)
    out0 = jnp.zeros((k + take,), jnp.int32)

    def unbias(key, c):
        local = jnp.where(key >= 2 * C, key - 2 * C,
                          jnp.where(key >= C, key - C, key))
        return local + c * C

    def strict_body(c, state):
        out, cursor = state
        glob = unbias(skeys[c, :take], c)
        out = jax.lax.dynamic_update_slice(
            out, glob, (jnp.minimum(cursor, k),))
        return out, cursor + counts_strict[c]

    out, m = jax.lax.fori_loop(0, nc, strict_body, (out0, jnp.int32(0)))

    def tie_body(c, state):
        out, cursor = state
        # this chunk's ties start right after its strict prefix —
        # dynamic start, static size; the sentinel pad guarantees
        # start + take never exceeds the row
        row = jax.lax.dynamic_slice(
            skeys[c], (counts_strict[c],), (take,))
        glob = unbias(row, c)
        out = jax.lax.dynamic_update_slice(
            out, glob, (jnp.minimum(cursor, k),))
        return out, cursor + counts_tie[c]

    out, _ = jax.lax.fori_loop(0, nc, tie_body, (out, m))
    return out[:k]


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def exact_topk(flat: jax.Array, k: int, chunk: int = 2048):
    """(values[k], indices[k]) of the k largest-|x| elements — exact.

    Selection = Pallas threshold refine + chunked compaction (module
    doc). ``chunk`` must be a power of two; tensors smaller than
    4×chunk (or with k >= n) fall back to ``lax.top_k``."""
    n = flat.shape[0]
    if k >= n or n < 4 * chunk or n > (1 << 30):
        _, idx = jax.lax.top_k(jnp.abs(flat), k)
        return jnp.take(flat, idx), idx.astype(jnp.int32)

    # |x| as monotonic int32 keys, padded to the count tile with -1
    # (never counted: every real key is >= 0)
    keys = jax.lax.bitcast_convert_type(
        jnp.abs(flat.astype(jnp.float32)), jnp.int32)
    unit = max(chunk, _TILE)  # powers of two: a multiple of both
    padded_n = ((n + unit - 1) // unit) * unit
    nc = padded_n // chunk
    keys_pad = jnp.concatenate(
        [keys, jnp.full((padded_n - n,), -1, jnp.int32)]) if padded_n > n \
        else keys
    t = _kth_threshold(keys_pad.reshape(-1, _LANE), k)

    # 3-level biased per-chunk keys: strict survivor -> local pos, tie
    # -> pos + C, rest -> pos + 2C; one vectorized per-chunk sort puts
    # [strict..., ties..., rest...] each in index order
    k2 = keys_pad.reshape(nc, chunk)
    pos = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    biased = jnp.where(k2 > t, pos,
                       jnp.where(k2 == t, pos + chunk, pos + 2 * chunk))
    counts_strict = jnp.sum(k2 > t, axis=1, dtype=jnp.int32)
    counts_tie = jnp.sum(k2 == t, axis=1, dtype=jnp.int32)
    skeys = jax.lax.sort(biased, dimension=-1)
    take = min(chunk, k)
    skeys = jnp.concatenate(
        [skeys, jnp.full((nc, take), 3 * chunk, jnp.int32)], axis=1)
    idx = _compact_two_phase(skeys, counts_strict, counts_tie, chunk, k)
    # padding keys are -1: never strict, never tied (t >= 0), never
    # selected — idx entries are always < n
    return jnp.take(flat, idx), idx
