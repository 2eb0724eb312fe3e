"""The expert layer's sum back, by the rows HELD: ``out[to[r]] += y[r]``.

``parallel/dropless.py`` sums every position's rows out of a buffer that
is sorted by expert and, within an expert, by position. XLA's form (one
``take`` a slot) reads a row for every (position, slot) pair, held here
or not; this kernel reads the rows that are there. It is told where they
are: for every block of ``block`` output rows and every group (expert),
``runs`` holds the contiguous range of ``y``'s rows that belong to the
block, which one ``searchsorted`` over the already sorted plan gives.

``y`` stays in HBM as it is. A DMA cannot address one row of a 2-D tiled
array (a row is a sublane of ``d / 128`` tiles; Mosaic refuses a slice
below a tile), so the kernel copies the TILES of 8 rows a run touches,
each one contiguous piece (32 KB at d 2,048 bfloat16), through a ring of
``DEPTH`` tiles in VMEM, and picks the rows out there: a 2-byte row is a
half of a row of 32-bit words, shifted into the high half, which IS its
float32. Rows are added in float32 in the order they lie in ``y`` and
rounded once, when the block's accumulator is written out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.ops._common import LANE as _LANE
from pytorch_ps_mpi_tpu.ops._common import interpret as _interpret

DEPTH = 32                  # tile copies in flight (a power of two)
_ROWS = 8                   # rows of a tile in HBM, whatever the element
_ACC_BYTES = 4 << 20        # the float32 accumulator of one block
_SMEM_ROWS = 128 * 1024     # ``to`` rides in SMEM: 4 bytes a row of y


def movable(y) -> bool:
    """Whether the kernel can sum ``y [rows, d]``'s rows: whole lane
    tiles of 2- or 4-byte floats, and few enough rows for SMEM to hold
    where each goes."""
    return (y.ndim == 2 and y.shape[1] % _LANE == 0
            and y.shape[0] <= _SMEM_ROWS and y.dtype.itemsize in (2, 4)
            and jnp.issubdtype(y.dtype, jnp.floating))


def block_rows(d: int) -> int:
    """Output rows a grid step: the largest power of two whose float32
    accumulator fits ``_ACC_BYTES`` (512 rows at d = 2,048; 256 at
    3,584)."""
    return max(16, 1 << ((_ACC_BYTES // (4 * d)).bit_length() - 1))


def _kernel(to_ref, runs_ref, y_ref, out_ref, stage, acc, meta, sems, *,
            block: int, groups: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    packed = y_ref.dtype.itemsize == 2
    # 2-byte rows ride as pairs in 32-bit words: row r is a half of word
    # row r // 2, and a tile of 8 rows is 4 word rows
    y_words = y_ref.bitcast(jnp.uint32) if packed else y_ref
    per_tile = stage.shape[1]

    def tile_copy(tile, k):
        first = pl.multiple_of(tile * per_tile, per_tile)
        return pltpu.make_async_copy(y_words.at[pl.ds(first, per_tile)],
                                     stage.at[k], sems.at[k])

    def add_row(r, k):
        # block_runs hands a block its own rows; the clip keeps a wrong
        # ``runs`` from writing outside the accumulator
        i = jnp.clip(to_ref[r] - b * block, 0, block - 1)
        at = r % _ROWS
        if packed:
            word = stage[k, pl.ds(at // 2, 1), :]
            # the even row is the low half; a bfloat16 is the high half
            # of the float32 of the same value
            row = pltpu.bitcast(
                (word << (16 * (1 - at % 2)).astype(jnp.uint32))
                & jnp.uint32(0xFFFF0000), jnp.float32)
        else:
            row = stage[k, pl.ds(at, 1), :].astype(jnp.float32)
        acc[pl.ds(i, 1), :] = acc[pl.ds(i, 1), :] + row

    def consume(k):
        tile_copy(0, k).wait()
        jax.lax.fori_loop(meta[k, 0], meta[k, 1],
                          lambda r, c: (add_row(r, k), c)[1], 0)

    def run(g, n):
        lo = runs_ref[b * groups + g]
        hi = jnp.maximum(runs_ref[(b + 1) * groups + g], lo)

        def tile(t, n):
            k = n % DEPTH

            @pl.when(n >= DEPTH)
            def _():
                consume(k)

            meta[k, 0] = jnp.maximum(lo, t * _ROWS)
            meta[k, 1] = jnp.minimum(hi, (t + 1) * _ROWS)
            tile_copy(t, k).start()
            return n + 1

        return jax.lax.fori_loop(lo // _ROWS, pl.cdiv(hi, _ROWS), tile, n)

    acc[...] = jnp.zeros_like(acc)
    n = jax.lax.fori_loop(0, groups, run, jnp.int32(0))
    jax.lax.fori_loop(jnp.maximum(n - DEPTH, 0), n,
                      lambda m, c: (consume(m % DEPTH), c)[1], 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def block_runs(keys, to, groups: int, n_out: int, block: int):
    """``[blocks + 1, groups]`` int32: row ``b`` holds, for every group,
    the first row of ``y`` whose output row is in block ``b`` or later,
    so block ``b``'s rows of group ``g`` are ``[runs[b, g], runs[b + 1,
    g])``. ``keys [R]`` (the group of each row, ascending; ``groups`` and
    above: none) and ``to [R]`` (its output row, ascending within a
    group) are the sorted plan's."""
    blocks = -(-n_out // block)
    span = (blocks + 1) * block
    if (groups + 1) * span >= 2 ** 31:
        raise ValueError(f"{groups} groups of {n_out} output rows do not "
                         "fit one int32 search key")
    where = jnp.minimum(keys, groups) * span + jnp.minimum(to, span - 1)
    asked = (jnp.arange(groups, dtype=jnp.int32)[None, :] * span
             + jnp.arange(blocks + 1, dtype=jnp.int32)[:, None] * block)
    return jnp.searchsorted(where, asked).astype(jnp.int32)


def sum_rows(y, to, runs, n_out: int, block: int | None = None):
    """``out [n_out, d]``: row ``i`` is the float32 sum, in the order of
    ``y``'s rows and rounded once, of the rows ``y[r]`` with ``to[r] ==
    i`` that lie in block ``i // block``'s ``runs`` (``block_runs`` at
    the same ``block``; ``block_rows(d)`` unless given); a row outside
    every run is not read."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not movable(y):
        raise ValueError(f"{y.shape[0]} rows of {y.shape[1:]} {y.dtype} "
                         "are not whole lane tiles of 2- or 4-byte floats "
                         f"that SMEM can index ({_SMEM_ROWS} rows)")
    rows, d = y.shape
    block = block or block_rows(d)
    blocks, groups = runs.shape[0] - 1, runs.shape[1]
    if blocks != pl.cdiv(n_out, block):
        raise ValueError(f"runs of {blocks} blocks for {n_out} rows in "
                         f"blocks of {block}")
    # the last tile of rows is read whole
    y = jnp.pad(y, ((0, -rows % _ROWS), (0, 0)))
    word, per_tile = ((jnp.uint32, _ROWS // 2) if y.dtype.itemsize == 2
                      else (y.dtype, _ROWS))
    return pl.pallas_call(
        functools.partial(_kernel, block=block, groups=groups),
        out_shape=jax.ShapeDtypeStruct((n_out, d), y.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, d), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((DEPTH, per_tile, d), word),
                            pltpu.VMEM((block, d), jnp.float32),
                            pltpu.SMEM((DEPTH, 2), jnp.int32),
                            pltpu.SemaphoreType.DMA((DEPTH,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="moe_sum_rows",
        interpret=_interpret(),
    )(to.astype(jnp.int32), jnp.minimum(runs, rows).reshape(-1), y)
