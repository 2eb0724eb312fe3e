"""Flash attention as a Pallas TPU kernel (forward AND backward).

The dense-attention path this replaces (``models/bert.py``: plain einsum
softmax) materializes the [l, l] score matrix in HBM per head — the
classic O(L²) memory wall. This kernel is the standard online-softmax
tiling (Dao et al.; Milakov & Gimelshein max-shift streaming): q tiles stay
resident in VMEM while k/v tiles stream past; the score block, running
row-max, exp-sum and output accumulator never leave VMEM; HBM traffic
drops from O(L²) to O(L·d).

Design choices:

- **Grid** ``(batch*heads, q_tiles, k_tiles)`` — TPU grids execute
  sequentially per core with the last dimension innermost, so the VMEM
  scratch accumulators (acc, running max m, running sum l) persist
  across the k sweep of one q tile; initialized at ``k==0``, finalized
  (normalize + logsumexp write) at ``k==nk-1``.
- **Dynamic position offsets** (SMEM scalars): the causal mask is
  evaluated in GLOBAL coordinates ``k_off + col <= q_off + row``, so the
  same compiled kernel serves dense attention (offsets 0) and ring
  attention's rotating blocks (``parallel/ring.py`` passes the block's
  traced global offset; a fully-future block masks itself to nothing).
- **The grid tile** (what one grid step holds resident) is as large as
  the chip numbers allow, because every grid step pays for its own
  start and DMAs (~0.5 us, ten times the MXU work of a 128 x 128 tile
  at head_dim 64): 1024 x 1024 under a mask, 512 x 1024 without one,
  and under 1024 positions the largest power of two that divides the
  length, so a head of 512 x 64 is ONE grid step a kernel (0.848 ms a
  layer of 16 x 12 such heads against 4.608 in 128 x 128 tiles;
  ``_default_block_targets`` holds every reading).
- **A sub-tile sweep inside each grid step** (``_sweep``, both
  kernels). The grid tile sets the DMAs and the number of grid steps;
  the work inside it is done sub-tile by sub-tile in a rolled loop over
  ``pl.ds`` slices of the resident blocks, and each sub-tile gets a class
  from its corner positions, as scalars at run time: DEAD (no allowed
  pair: skipped, no product, no ``exp``), FULL (every pair allowed: the
  products and the online softmax with no mask and no select) or CUT
  (the mask passes through it: masked element by element). So the
  kernels compute only what the mask leaves, to the sub-tile, and mask
  only where the mask cuts: at 256 x 256 a causal 1024 x 1024 head is 6
  dead + 4 cut + 6 full sub-tiles, a block-diffusion head of 2 x 4096
  positions 736 + 48 + 240 (``tile_census``; one ``attn.flash_tiles``
  row in the set-up log a trace). A grid tile with no allowed pair
  is skipped whole by a predicated ``pl.when``: ring's future blocks
  cost ~0. The score tile in flight is a sub-tile, never the grid tile.
- **Backward is ONE Pallas kernel** (``_bwd_kernel``, PR 40) on the
  forward's grid, recomputing p from the saved logsumexp — no O(L²)
  residual. A sub-tile's visit computes ``p``, ``dp = do·vᵀ`` and ``ds =
  p·(dp - Dm)`` once and feeds all three gradients from them: ``dv +=
  pᵀ·do``, ``dk += dsᵀ·q``, ``dq += ds·k`` — five products, one
  ``exp``, one mask, where a dq kernel and a dk/dv kernel did seven and two of
  each. dq is summed over a q tile's k sweep in a ``(bq, d)`` scratch,
  like the forward's output; dk and dv are summed over q tiles, so they
  stay resident: float32 accumulators of one WHOLE key-value head
  (``lk·(d + dv)·4`` bytes: 10.5 MB at 8,192 x (192 + 128)), zeroed at
  the head's first grid step, scaled, cast and written to output blocks
  ``(1, lk, d)`` / ``(1, lk, dv)`` at its last; the blocks' index moves
  only with the key-value head, so Pallas writes them to HBM once a
  head. With their output blocks and a tile's that is over Mosaic's 16
  MiB default, so the call asks for ``_VMEM_BYTES``; a head too long to
  fit (about 45,000 positions of 128-wide bf16) is refused when the
  backward is traced (``_backward_vmem``). Measured on a v5e, a layer's
  backward alone, two kernels -> one (``PERF.md`` section 6, PR 40, run
  K1): 22.59 -> 15.20 ms at 32 heads of 8,192 x 192 over 128; 17.88 ->
  12.68 under the block-diffusion mask at 2 x 32 over 4 heads of 8,192 x
  128; 0.642 -> 0.475 at 16 x 12 unmasked heads of 512 x 64.
  The custom VJP also accepts a cotangent for the returned logsumexp
  (folded into ``Dm = D - g_lse``), which is what lets ring attention
  combine per-block normalized outputs differentiably.
- **MXU precision**: scores and accumulators are f32
  (``preferred_element_type``); the p@v contraction runs in the input
  dtype (bf16 on TPU) like standard flash implementations.

- **Masks and head counts.** ``mask`` is ``None``, ``'causal'``,
  ``'window'`` (causal within ``window`` positions: ``0 <= q_pos - k_pos <
  window``; the grid's inner axis then runs over the k tiles the band
  crosses and NO OTHER: 2 of 16 at a window of 512 in 8,192 positions,
  the index maps and the kernels' positions both offset by the band's
  first tile) or ``'block_diffusion'`` (the training mask
  of block-diffusion language
  models over a doubled sequence: a noised copy at positions
  ``0..half-1`` and the clean copy at ``half..2*half-1``; with
  ``beta(i) = (i mod half) // block``, noised sees noised of its own
  block and clean of earlier blocks, clean sees clean up to its own
  block, clean never sees noised). Under the block-diffusion mask the
  index maps also clamp a dead grid tile onto the nearest live one, so
  that it costs no DMA either (40 of 64 grid tiles a head are dead at
  2 x 4096 positions). ``k``/``v`` may carry fewer heads than ``q``
  (grouped-query attention): query head ``h`` reads key-value head
  ``h // (heads // kv_heads)``, and dk and dv sum over the group: its
  query heads are consecutive grid steps, through which the key-value
  head's accumulators stay resident. ``v`` may be wider or narrower than
  ``q`` and ``k`` (differential attention's value is two heads side by
  side, 128 against 64): the output and ``dv`` take ``v``'s width, the
  scores ``q``'s. The
  block-diffusion kernels carry a stable ``name`` each (``flash_bd_fwd``
  / ``flash_bd_dqkv``: the benchmark's readers match ``flash_bd_(fwd|dq|
  dkv)`` and whatever follows): it is the HLO instruction's name in a
  device trace; so do the window kernels (``flash_win_*``)
  and those with a value of another width (``flash_wide_*``). The
  unmasked and causal kernels of one width keep the name XLA gives them
  (the calling module's), which the benchmark's accepted readers match.

Off-TPU the kernel runs in Pallas interpret mode (CPU test meshes);
``flash_attention`` falls back to a jnp oracle for shapes the tiling
cannot serve (sequence not a multiple of the minimal sublane tile).
On TPU a Mosaic failure at a shape the tiling admits raises: nothing
here catches a kernel compile error.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_ps_mpi_tpu.ops._common import LANE as _LANE, keep
from pytorch_ps_mpi_tpu.ops._common import interpret as _interpret
from pytorch_ps_mpi_tpu.telemetry.recorder import setup_event

_MASKED = -1e30        # additive mask value
_MASK_THRESH = -1e29   # "this score was masked" test (real scores are tiny)

# Minimum sequence length at which 'full' attention auto-dispatches to
# the kernel. XLA's fused dense attention suits short sequences — its
# matmuls batch across heads on the MXU while the kernel pays a
# sequential batch*heads grid — and the kernel takes over where O(L^2)
# score materialization dominates. Both sides read on a v5e (PERF.md
# section 7, D4 (b)): at 1024 the kernels win (gpt2-small.lm1024:
# 127,316 tokens/s against 100,636 with attention='einsum', PR 30). AT
# 512 THEY STILL LOSE, by 6 %: bert-base.mlm512 reads 149,534 tokens/s
# with the kernels at one 512 x 512 tile a head and 159,413 with
# attention='einsum' (PR 32; 81,913 at the 128 x 128 tile before it);
# a layer alone, forward and backward, 16 x 12 heads of 64: 1.425 ms
# (kernels 0.848, the layout transposes and logsumexp rides around
# them 0.577) against XLA's 0.796; at 256 1.577 against 0.345, at 768
# 5.321 against 3.419. 512 stays until a PR of its own moves the floor
# (ROADMAP D4 (b)). Overridable for re-measurement on other chip
# generations (FLASH_MIN_SEQ env var).
import os as _os

FLASH_MIN_SEQ = int(_os.environ.get("FLASH_MIN_SEQ", "512"))


def _pick_block(length: int, target: int, min_block: int = 8) -> Optional[int]:
    """Largest power-of-two block <= target that divides ``length``
    (>= ``min_block``: 8 = the f32 sublane; bf16 tiles need 16);
    None if the length cannot tile."""
    b = 1
    while b * 2 <= min(target, length) and length % (b * 2) == 0:
        b *= 2
    return b if b >= min_block and length % b == 0 else None


def _min_block_for(dtype) -> int:
    """Minimal sublane tile for the dtype (f32: 8, bf16/f16: 16)."""
    return 16 if jnp.dtype(dtype).itemsize < 4 else 8



# ---------------------------------------------------------------------------
# masks: a static tuple ("none",) | ("causal",) | ("window", w)
#                       | ("bd", block, half)
# ---------------------------------------------------------------------------

def _mask_spec(causal: bool, mask: Optional[str], block, half,
               window=None) -> tuple:
    if mask is None:
        mask = "causal" if causal else "none"
    elif causal and mask != "causal":
        raise ValueError(f"causal=True contradicts mask={mask!r}")
    if mask in ("none", "causal"):
        return (mask,)
    if mask == "window":
        if not window or window < 1:
            raise ValueError(f"mask='window' needs window >= 1; got {window}")
        return ("window", int(window))
    if mask != "block_diffusion":
        raise ValueError(f"unknown mask={mask!r}: expected None, 'causal', "
                         "'window' or 'block_diffusion'")
    if not block or not half or block & (block - 1) or half % block:
        raise ValueError("mask='block_diffusion' needs block (a power of "
                         f"two) dividing half; got block={block} half={half}")
    return ("bd", int(block), int(half))


def _bd_corners(mask, start, size):
    """Of a tile under the block-diffusion mask (it lies within one half):
    is it in the noised half, is it in the clean one, and the blocks of
    its first and last position."""
    _, block, half = mask
    shift = block.bit_length() - 1
    clean = start >= half
    lo = start - half * clean
    return start < half, clean, lo >> shift, (lo + size - 1) >> shift


def _bd_rule(q, k, noised_noised, noised_clean, clean_clean):
    """The rule of the pair of halves the tile lies in (clean never sees
    noised). Plain comparisons and boolean algebra: the same text serves
    traced scalars inside a kernel and numpy arrays in ``tile_census``."""
    return ((q[0] & k[0] & noised_noised) | (q[0] & k[1] & noised_clean)
            | (q[1] & k[1] & clean_clean))


def _tile_live(mask, q_start, k_start, bq, bk):
    """Does the tile with these corner positions hold an allowed pair?"""
    if mask[0] == "none":
        return True
    if mask[0] == "causal":
        return k_start <= q_start + bq - 1
    if mask[0] == "window":     # the lower left corner is the nearest pair
        return (k_start <= q_start + bq - 1) & (
            q_start - (k_start + bk - 1) < mask[1])
    q, k = _bd_corners(mask, q_start, bq), _bd_corners(mask, k_start, bk)
    (_, _, qb0, qb1), (_, _, kb0, kb1) = q, k
    # noised/noised: kb == qb; noised/clean: kb < qb; clean/clean: kb <= qb
    return _bd_rule(q, k, (kb0 <= qb1) & (qb0 <= kb1), kb0 < qb1, kb0 <= qb1)


def _tile_full(mask, q_start, k_start, bq, bk):
    """Is every pair of the tile allowed? Such a tile needs no mask."""
    if mask[0] == "none":
        return True
    if mask[0] == "causal":
        return k_start + bk - 1 <= q_start
    if mask[0] == "window":     # the upper right and the lower left corner
        return (k_start + bk - 1 <= q_start) & (
            q_start + bq - 1 - k_start < mask[1])
    q, k = _bd_corners(mask, q_start, bq), _bd_corners(mask, k_start, bk)
    (_, _, qb0, qb1), (_, _, kb0, kb1) = q, k
    return _bd_rule(q, k, (kb0 == qb0) & (kb1 == qb0) & (qb1 == qb0),
                    kb1 < qb0, kb1 <= qb0)


def _mask_scores(mask, s, q_start, k_start, bq, bk):
    """Scores with the disallowed pairs of this tile at ``_MASKED``. Under
    the block-diffusion mask a tile lies within one half (the tiles divide
    ``half``), so which rule applies is a scalar of the tile."""
    if mask[0] == "none":
        return s
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if mask[0] == "causal":
        return jnp.where(cols <= rows, s, _MASKED)
    if mask[0] == "window":
        return jnp.where((cols <= rows) & (rows - cols < mask[1]), s, _MASKED)
    _, block, half = mask
    shift = block.bit_length() - 1
    q_clean = (q_start >= half).astype(jnp.int32)
    k_clean = (k_start >= half).astype(jnp.int32)
    qb = jax.lax.shift_right_logical(rows - q_clean * half, shift)
    kb = jax.lax.shift_right_logical(cols - k_clean * half, shift)
    # noised/noised: kb == qb; noised/clean: kb < qb; clean/clean: kb <= qb
    # (clean/noised tiles are never live)
    hi = qb - k_clean * (1 - q_clean)
    lo = qb * ((1 - k_clean) * (1 - q_clean))
    return jnp.where((kb <= hi) & (kb >= lo), s, _MASKED)


def _bd_k_tile(mask, j, kk, bq, bk):
    """The k tile to fetch for grid step (q tile ``j``, k tile ``kk``):
    ``kk`` itself where the tile is live, else the nearest live one
    (a block index that does not change costs no DMA)."""
    if mask[0] != "bd":
        return kk
    _, block, half = mask
    qs = j * bq
    noised = qs < half
    lo1 = jnp.where(noised, qs // bk, -1)
    hi1 = jnp.where(noised, (qs + bq - 1) // bk, -1)
    lo2 = half // bk
    hi2 = jnp.where(noised, (half + qs + bq - block - 1) // bk,
                    (qs + bq - 1) // bk)
    return jnp.where(kk <= hi1, jnp.maximum(kk, lo1),
                     jnp.minimum(jnp.maximum(kk, lo2), jnp.maximum(hi2, lo2)))


def _band(mask, nq, nk, bq, bk):
    """The inner grid axis under the window mask: (its length, the k tile
    that step ``kk`` of q tile ``j`` stands for, the tile to fetch for
    it). A q tile's band is the k tiles from the one holding ``q_start -
    window + 1`` to the one holding its last row; the axis is as long as
    the widest band, a step past the last tile stands for a tile that does
    not exist (the kernels skip it) and fetches the last. Any other mask:
    every tile, step ``kk`` is tile ``kk``."""
    if mask[0] != "window":
        return nk, (lambda j, kk: kk), (lambda j, kk: kk)
    first = lambda j, lib=jnp: lib.maximum(j * bq - mask[1] + 1, 0) // bk
    last = lambda j: (j * bq + bq - 1) // bk
    j = np.arange(nq)
    steps = int(np.max(last(j) - first(j, np) + 1))
    return (steps, lambda j, kk: first(j) + kk,
            lambda j, kk: jnp.minimum(first(j) + kk, nk - 1))


def _kernel_name(mask, which, wide=False):
    """``flash_bd_*`` / ``flash_win_*`` / ``flash_wide_*`` (``fwd``,
    ``dqkv``); the unmasked and causal kernels of one width keep the name
    XLA gives them (module docstring)."""
    kind = {"bd": "bd", "window": "win"}.get(mask[0], "wide" if wide else None)
    return kind and f"flash_{kind}_{which}"


# ---------------------------------------------------------------------------
# the sub-tile sweep inside a grid step
# ---------------------------------------------------------------------------

def _loop(n, body):
    """``body(i)`` for ``i`` in ``range(n)``, rolled: Mosaic compiles one
    copy of the body however many sub-tiles a tile holds. A single turn
    is no loop at all: ``i`` is the integer 0 and every slice is static."""
    if n == 1:
        body(0)
        return

    def step(i, carry):
        body(i)
        return carry

    jax.lax.fori_loop(0, n, step, 0)


def _rows(i, n):
    """Rows ``i * n .. i * n + n - 1`` of a resident block."""
    return pl.ds(i * n if isinstance(i, int) else pl.multiple_of(i * n, n), n)


def _sweep(mask, q_start, k_start, bq, bk, sq, sk, visit):
    """Walk the ``sq x sk`` sub-tiles of the resident ``bq x bk`` tile (a
    q sub-tile's k sub-tiles in turn) and class each from its corner
    positions, as scalars at run time (the offsets may be traced): a DEAD
    one (no allowed pair) is skipped, a FULL one (every pair allowed) gets
    ``visit(i, j, q0, k0, False)``, a CUT one (the mask passes through it)
    ``visit(i, j, q0, k0, True)``; the last argument is static, so the
    unmasked body carries no mask."""
    def one(i, j):
        q0, k0 = q_start + i * sq, k_start + j * sk
        if mask[0] == "none":
            visit(i, j, q0, k0, False)
            return
        live = _tile_live(mask, q0, k0, sq, sk)
        full = _tile_full(mask, q0, k0, sq, sk)
        pl.when(full)(lambda: visit(i, j, q0, k0, False))
        pl.when(live & jnp.logical_not(full))(
            lambda: visit(i, j, q0, k0, True))

    _loop(bq // sq, lambda i: _loop(bk // sk, lambda j: one(i, j)))


def _lanes(col, n):
    """A lane-replicated ``[rows, LANE]`` column stretched to ``n`` lanes
    by reusing its registers: no move across lanes."""
    if n % _LANE == 0:
        return jnp.tile(col, (1, n // _LANE))
    if n < _LANE:
        return col[:, :n]
    return jnp.broadcast_to(col[:, :1], (col.shape[0], n))


def _fold_lanes(x):
    """``[rows, n]`` summed into ``[rows, LANE]`` lane by lane (element-wise
    adds of whole registers); the sum over its lanes is the row sum. The
    one move across lanes is left to whoever needs the row sum."""
    rows, n = x.shape
    if n % _LANE:
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANE), 1)
        return jnp.where(lane == 0, jnp.sum(x, axis=-1, keepdims=True), 0.0)
    out = x[:, :_LANE]
    for c in range(1, n // _LANE):
        out = out + x[:, c * _LANE:(c + 1) * _LANE]
    return out


def _scores(q, k, scale):
    """``q @ k.T`` in float32, scaled there (not in the bf16 operands)."""
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc, m_sc, l_sc, *, mask, scale, bq, bk, sq, sk, nk, tile):
    j = pl.program_id(1)
    kk = pl.program_id(2)       # the kk-th k tile of q tile j's band

    @pl.when(kk == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _MASKED)
        l_sc[:] = jnp.zeros_like(l_sc)

    q_start = qo_ref[0] + j * bq
    k_start = ko_ref[0] + tile(j, kk) * bk

    # m and l ride lane-replicated ([bq, LANE]): a sub-tile's visit costs one
    # move across lanes a row (the row maximum); the row sum is kept
    # folded lane by lane and reduced once, when the q tile is finished
    def visit(i, jj, q0, k0, cut):
        rq, rk = _rows(i, sq), _rows(jj, sk)
        s = _scores(q_ref[0, rq, :], k_ref[0, rk, :], scale)
        if cut:
            s = _mask_scores(mask, s, q0, k0, sq, sk)
        m_prev = m_sc[rq, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, sk))
        if cut:
            # a row with no visible key keeps m == _MASKED; exp(s - m)
            # would be exp(0) = 1 there: mask p explicitly, never through
            # the exp. A full sub-tile has no such row and no such score
            p = jnp.where(s > _MASK_THRESH, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[rq, :] = l_sc[rq, :] * corr + _fold_lanes(p)
        v = v_ref[0, rk, :]
        acc[rq, :] = acc[rq, :] * _lanes(corr, acc.shape[1]) + (
            jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        m_sc[rq, :] = m_new

    # skip tiles the mask empties (causal: the future), without a sweep
    @pl.when(_tile_live(mask, q_start, k_start, bq, bk))
    def _():
        _sweep(mask, q_start, k_start, bq, bk, sq, sk, visit)

    @pl.when(kk == nk - 1)
    def _():
        l_safe = jnp.maximum(jnp.sum(l_sc[:], axis=-1, keepdims=True), 1e-30)
        o_ref[0] = (acc[:] / l_safe).astype(o_ref.dtype)
        # lane-replicated write: lse rides as [bh, lq, LANE] so its block
        # (1, bq, LANE) satisfies Mosaic's (8, 128) tile rule for ANY bh —
        # a (1, bq) block over [bh, lq] only lowers when bh == 1
        lse_ref[0] = m_sc[:] + jnp.log(l_safe)


def _fwd(q3, k3, v3, q_off, k_off, mask, scale, bq, bk, sq, sk):
    bh, lq, d = q3.shape
    lk = k3.shape[1]
    group = bh // k3.shape[0]       # query heads per key-value head
    nq, nk = lq // bq, lk // bk
    dv = v3.shape[2]
    steps, tile, fetched = _band(mask, nq, nk, bq, bk)
    kern = functools.partial(
        _fwd_kernel, mask=mask, scale=scale, bq=bq, bk=bk, sq=sq, sk=sk,
        nk=steps, tile=tile
    )

    def kv_map(i, j, kk):
        return (i // group, _bd_k_tile(mask, j, fetched(j, kk), bq, bk), 0)

    return pl.pallas_call(
        kern,
        grid=(bh, nq, steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, bq, _LANE), lambda i, j, kk: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, dv), q3.dtype),
            jax.ShapeDtypeStruct((bh, lq, _LANE), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, _LANE), jnp.float32),
            pltpu.VMEM((bq, _LANE), jnp.float32),
        ],
        interpret=_interpret(),
        name=_kernel_name(mask, "fwd", dv != d),
    )(q_off, k_off, q3, k3, v3)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _recompute_p(q, k, lse_rep, q0, k0, mask, scale, cut):
    """p = exp(s - lse) of one sub-tile, masked entries exactly zero.
    ``lse_rep`` is the lane-replicated [rows, LANE] ride; ``cut`` (static)
    says whether the mask passes through the sub-tile."""
    s = _scores(q, k, scale)
    lse = _lanes(lse_rep, s.shape[1])
    if not cut:
        return jnp.exp(s - lse)
    s = _mask_scores(mask, s, q0, k0, *s.shape)
    return jnp.where(s > _MASK_THRESH, jnp.exp(s - lse), 0.0)


def _when(cond):
    """``pl.when`` that also takes a condition known when the kernel is
    traced (a head that is one grid step has nothing to carry)."""
    if isinstance(cond, bool):
        return lambda fn: fn() if cond else None
    return pl.when(cond)


def _bwd_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                dm_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                *, mask, scale, bq, bk, sq, sk, nq, nk, tile, group):
    """dq, dk and dv from ONE recomputation of a sub-tile's p and dp, on
    the forward kernel's grid and visits: dq is summed over a q tile's k
    sweep like the forward's output, dk and dv of the whole key-value head
    stay resident in float32 across the head's sweep and the sweeps of
    the ``group`` query heads that read it. A row of dq, dk or dv receives
    its terms in the order the two kernels before PR 40 gave them (head of
    the group, q tile, q sub-tile; k tile, k sub-tile): interpreted, the
    gradients equal theirs to the last bit."""
    head = pl.program_id(0) % group if group > 1 else 0
    j = pl.program_id(1) if nq > 1 else 0
    kk = pl.program_id(2) if nk > 1 else 0
    t = tile(j, kk)             # the k tile this step stands for
    lk = dk_acc.shape[0]

    @_when((head == 0) & (j == 0) & (kk == 0))
    def _():
        def zero(c):
            dk_acc[_rows(c, bk), :] = jnp.zeros((bk, dk_acc.shape[1]),
                                                jnp.float32)
            dv_acc[_rows(c, bk), :] = jnp.zeros((bk, dv_acc.shape[1]),
                                                jnp.float32)

        _loop(lk // bk, zero)

    @_when(kk == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = qo_ref[0] + j * bq
    k_start = ko_ref[0] + t * bk

    def visit(i, jj, q0, k0, cut):
        rq, rk = _rows(i, sq), _rows(jj, sk)
        ra = _rows(t * (bk // sk) + jj, sk)     # of the resident dk and dv
        q, do = q_ref[0, rq, :], do_ref[0, rq, :]
        k, v = k_ref[0, rk, :], v_ref[0, rk, :]
        p = _recompute_p(q, k, lse_ref[0, rq, :], q0, k0, mask, scale, cut)
        dv_acc[ra, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - _lanes(dm_ref[0, rq, :], sk))).astype(q.dtype)
        dk_acc[ra, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_acc[rq, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # a step past the band's last tile stands for a tile that is not
    # there: it is not live, so no row of dk or dv past lk is touched
    @pl.when(_tile_live(mask, q_start, k_start, bq, bk))
    def _():
        _sweep(mask, q_start, k_start, bq, bk, sq, sk, visit)

    @_when(kk == nk - 1)
    def _():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)

    @_when((head == group - 1) & (j == nq - 1) & (kk == nk - 1))
    def _():
        def write(c):
            rows = _rows(c, bk)
            dk_ref[0, rows, :] = (dk_acc[rows, :] * scale).astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv_acc[rows, :].astype(dv_ref.dtype)

        _loop(lk // bk, write)


_VMEM_BYTES = 100 << 20     # of the chip's 128 MiB, as ops/hyper_connection.py


def _backward_vmem(lk, d, dv, dtype, bq, bk, sq, sk) -> int:
    """The VMEM the backward call holds, from the shape alone and counted a
    little high: the float32 dk and dv of one key-value head and their
    output blocks at whole lane tiles (Pallas keeps two of each output
    block), the tile's q, k, v, do, dq and the two rides, two of each,
    dq's accumulator, and four float32 sub-tiles of scores in flight (on a
    v5e Mosaic reports 34.0 MB used where this counts 36.7, at 8,192 x
    (192 + 128) bf16)."""
    lanes = lambda n: -(-n // _LANE) * _LANE
    item = jnp.dtype(dtype).itemsize
    heads = lk * (lanes(d) + lanes(dv)) * (4 + 2 * item)
    tile = (2 * item * ((2 * bq + bk) * lanes(d) + (bq + bk) * lanes(dv))
            + 4 * bq * (4 * _LANE + lanes(d)) + 16 * sq * sk)
    return heads + tile


def _bwd(q3, k3, v3, q_off, k_off, out, lse, g_out, g_lse,
         mask, scale, bq, bk, sq, sk):
    bh, lq, d = q3.shape
    bkv, lk = k3.shape[:2]
    dv = v3.shape[2]
    group = bh // bkv
    nq, nk = lq // bq, lk // bk
    steps, tile, fetched = _band(mask, nq, nk, bq, bk)
    held = _backward_vmem(lk, d, dv, q3.dtype, bq, bk, sq, sk)
    if held > _VMEM_BYTES:
        # no cell and no test comes near (10.5 MB of dk and dv in the
        # largest cell; about 45,000 positions of 128-wide bf16 heads fit)
        raise ValueError(
            f"the backward flash kernel keeps dk and dv of one key-value "
            f"head of {lk} positions in VMEM: about {held} bytes with a "
            f"tile's blocks, over the {_VMEM_BYTES} it may ask for; shard "
            "the sequence further (ring attention's blocks)")
    # D folds the out-cotangent; the lse-cotangent enters with opposite
    # sign in ds = p * (dp - (D - g_lse)). lse arrives lane-replicated
    # [bh, lq, LANE] (see _fwd); dm rides the same layout so both block
    # as tile-aligned (1, bq, LANE)
    dm = (jnp.sum(g_out.astype(jnp.float32) * out.astype(jnp.float32),
                  axis=-1) - g_lse)
    dm = jnp.broadcast_to(dm[..., None], (bh, lq, _LANE))
    lse = jnp.broadcast_to(lse[..., None], (bh, lq, _LANE))

    def q_map(i, j, kk):
        return (i, j, 0)

    def kv_map(i, j, kk):
        return (i // group, _bd_k_tile(mask, j, fetched(j, kk), bq, bk), 0)

    # dk and dv of a whole key-value head are one block each, whose index
    # moves only with the key-value head: Pallas writes them back once the
    # `group` query heads that read the head are through
    def whole(i, j, kk):
        return (i // group, 0, 0)

    # the forward kernel's grid: a q tile's blocks arrive once a q tile, k
    # and v once a visit
    return pl.pallas_call(
        functools.partial(_bwd_kernel, mask=mask, scale=scale, bq=bq, bk=bk,
                          sq=sq, sk=sk, nq=nq, nk=steps, tile=tile,
                          group=group),
        grid=(bh, nq, steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, dv), kv_map),
            pl.BlockSpec((1, bq, dv), q_map),
            pl.BlockSpec((1, bq, _LANE), q_map),
            pl.BlockSpec((1, bq, _LANE), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, lk, d), whole),
            pl.BlockSpec((1, lk, dv), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, d), q3.dtype),
            jax.ShapeDtypeStruct((bkv, lk, d), k3.dtype),
            jax.ShapeDtypeStruct((bkv, lk, dv), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((lk, d), jnp.float32),
            pltpu.VMEM((lk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=_interpret(),
        name=_kernel_name(mask, "dqkv", dv != d),
    )(q_off, k_off, q3, k3, v3, g_out, lse, dm)


# ---------------------------------------------------------------------------
# custom-vjp core on [b*heads, l, d] arrays (k, v: [b*kv_heads, l, d])
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q3, k3, v3, q_off, k_off, mask, scale, tile, sub):
    out, lse = _fwd(q3, k3, v3, q_off, k_off, mask, scale, *tile, *sub)
    return out, lse


def _flash_fwd(q3, k3, v3, q_off, k_off, mask, scale, tile, sub):
    out, lse = _fwd(q3, k3, v3, q_off, k_off, mask, scale, *tile, *sub)
    # the residual keeps lane 0 only — every lane is identical, and holding
    # the [bh, lq, LANE] ride through the whole model backward would cost
    # 128x the memory; _bwd re-broadcasts (same pattern as dm). `out` and
    # that column are what a layer's checkpoint keeps (`_common.KEPT`), so
    # the kernel does not run again in the backward pass; the NAMED `out`
    # is both the primal output and the residual, so nothing downstream
    # asks for an unnamed twin and brings the kernel back
    out = keep(out, "flash.out")
    return (out, lse), (q3, k3, v3, q_off, k_off, out,
                        keep(lse[..., 0], "flash.lse"))


def _flash_bwd(mask, scale, tile, sub, res, g):
    q3, k3, v3, q_off, k_off, out, lse = res
    g_out, g_lse = g
    # lse is returned lane-replicated [bh, lq, LANE]; the adjoint of that
    # replication is the lane-sum of the cotangent (the API slices lane 0,
    # so in practice only that column is nonzero)
    g_lse = g_lse.sum(axis=-1)
    dq, dk, dv = _bwd(q3, k3, v3, q_off, k_off, out, lse, g_out, g_lse,
                      mask, scale, *tile, *sub)
    zero_off = np.zeros((1,), jax.dtypes.float0)  # int inputs: no tangent
    return dq, dk, dv, zero_off, zero_off


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# public API on [b, l, h, d] arrays (the models' layout)
# ---------------------------------------------------------------------------

def allowed_pairs(mask: tuple, rows, cols):
    """The dense boolean mask [len(rows), len(cols)] of a mask spec over
    global positions: what the kernels' tile logic must equal."""
    rows, cols = rows[:, None], cols[None, :]
    if mask[0] == "none":
        return jnp.ones((rows.shape[0], cols.shape[1]), bool)
    if mask[0] == "causal":
        return cols <= rows
    if mask[0] == "window":
        return (cols <= rows) & (rows - cols < mask[1])
    _, block, half = mask
    q_clean, k_clean = rows >= half, cols >= half
    qb, kb = (rows % half) // block, (cols % half) // block
    return jnp.where(q_clean, k_clean & (kb <= qb),
                     jnp.where(k_clean, kb < qb, kb == qb))


def _attention_jnp(q, k, v, q_offset, k_offset, mask, scale):
    """Dense oracle with identical semantics (global-coordinate mask,
    masked-row-safe, grouped heads, returns lse). Differentiable; used as
    the fallback for untileable shapes and as the test oracle. ``mask``
    is a mask spec, or a bool meaning causal."""
    if isinstance(mask, bool):
        mask = ("causal",) if mask else ("none",)
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask[0] != "none":
        ok = allowed_pairs(mask, q_offset + jnp.arange(q.shape[1]),
                           k_offset + jnp.arange(k.shape[1]))
        s = jnp.where(ok, s, _MASKED)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(s > _MASK_THRESH, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.maximum(l, 1e-30)
    out = jnp.einsum("bhqk,bkhd->bqhd", (p / l_safe),
                     v.astype(jnp.float32)).astype(q.dtype)
    lse = (m + jnp.log(l_safe))[..., 0]               # [b, h, q]
    return out, lse


def _default_block_targets(causal: bool = False) -> tuple:
    """The grid tile (what a grid step holds resident: its DMAs and the
    number of grid steps), as TARGETS: ``_pick_block`` clamps each to the
    largest power of two that divides its length, so the mask alone
    decides here and the lengths decide there. From 1024 up, measured on
    a v5e at GPT-2's shape (8 x 12 heads, 1024 x 64, bf16; forward + dq +
    dk/dv of one layer, ``PERF.md`` section 6, PR 30): causal 1.215 ms at
    1024 x 1024 against 1.250 at 512 x 1024 (both swept in 512 x 512
    sub-tiles; 1.337 against 1.391 at 2 x 16 heads of 2048 x 128): the
    whole k/v of a 1024-long head stays resident and a head is one grid
    step, which the scores no longer forbid because only a sub-tile of
    them is ever in flight. Unmasked: 512 x 1024 (1.451 ms, the tile as
    one sub-tile; 1.531 at 1024 x 1024 swept in 512 x 512).

    Under 1024 the same targets, clamped, are the whole head at 512 and
    256 and 256 x 256 at 768; there was a 128 x 128 tier here, without a
    chip number, until PR 32 measured it (``PERF.md`` section 6, run K1:
    the three kernels' events in a device trace, ms a layer, bf16, heads
    of 64; the whole program with its transposes and logsumexp rides
    costs 0.577 more at 512 and 256, 0.865 at 768). 16 x 12 heads of 512,
    unmasked (``bert-base.mlm512``): **0.848 at 512 x 512**, 1.136 at
    256 x 512, 1.295 at 512 x 256, 1.961 at 256 x 256, 4.608 at
    128 x 128 (0.50 us a grid step for 0.05 us of MXU work). Causal
    (ring's and ulysses' blocks, ``attention='flash'``): **0.882**,
    1.207, 1.297, 1.906, 4.160: one CUT sub-tile that masks every score
    beats four tiles of which one is skipped. 32 x 12 heads of 256:
    **0.999 at 256 x 256**, 1.455 at 256 x 128, 1.470 at 128 x 256, 2.488
    at 128 x 128 (causal 1.058, 1.492, 1.556, 2.457). 16 x 12 heads of
    768, where 256 is the largest power of two that divides: **4.456 at
    256 x 256**, 6.371, 6.495, 10.576 (causal 3.989, 5.782, 5.883,
    9.170). XLA's own attention reads 0.796 / 0.345 / 3.419 at 512 / 256
    / 768: see ``FLASH_MIN_SEQ``."""
    return (1024, 1024) if causal else (512, 1024)


def _window_block_targets() -> tuple:
    """Grid tiles under the window mask: 512 x 512, each its own
    sub-tile; under 1024 positions it follows ``_default_block_targets``
    (the clamp gives the tiles measured there). Measured on a v5e at a
    window of 512 in 8,192 positions, 40 query over 20 key-value heads of
    64 with a 128-wide value, bf16, forward + dq + dk/dv of one layer
    (``PERF.md`` section 6, PR 31, run K1): 7.52 ms (forward 2.26) at
    512 x 512, a band of two k tiles a q tile, both cut; 7.73 at
    1024 x 1024 swept in 512 x 512 (two tiles too, each twice as long);
    8.45 at 512 x 1024; 9.82 at 256 x 512."""
    return 512, 512


def _bd_block_targets() -> tuple:
    """Grid tiles under the block-diffusion mask: 1024 x 1024; a half
    under 1024 positions follows ``_default_block_targets`` (the clamp
    gives the tiles measured there). At 2 x 4096 positions, 32 query over
    4 key-value heads of 128, bf16, 2 rows, on a v5e (``PERF.md`` section
    6): before the sub-tile sweep the three kernels took 30.5 ms at
    1024 x 1024, 32.4 at 512 x 1024, 36.9 at 512 x 512, 47.2 at
    256 x 512 (PR 27: smaller GRID tiles lose, each pays its own grid
    step and DMAs); with the sweep at 256 x 256 sub-tiles 51.4 at
    1024 x 1024, 51.8 at 2048 x 1024, 50.8 at 2048 x 2048 (PR 30: a
    larger grid tile returns 1 %, not kept)."""
    return 1024, 1024


def _sub_tile_targets(mask: tuple, bq: int, bk: int) -> tuple:
    """The sub-tile (q rows, k rows) a grid tile is swept in. Measured on
    a v5e, forward + dq + dk/dv of one layer in ms (``PERF.md`` section 6,
    PR 30). Causal, 96 heads of 1024 x 64, grid tile 1024 x 1024: 1.215
    at 512 x 512; 1.478 at 1024 x 512; 1.489 at 256 x 512; 1.492 at
    1024 x 1024 (nothing skipped); 1.770 at 512 x 256; 2.121 at
    256 x 256; 4.165 at 128 x 128. Block diffusion, 2 x 32 over 4 heads of
    8192 x 128, grid tile 1024 x 1024: 23.60 at 512 x 512; 25.10 at
    1024 x 1024; 25.26 at 1024 x 512; 28.67 at 256 x 512; 33.94 at
    512 x 256; 43.65 at 256 x 256; 57.94 at 128 x 256. A visit of a
    sub-tile is a chain of MXU round trips that nothing overlaps across the
    rolled loop's branches, so under 512 a side the chain's latency costs
    more than the finer skipping returns, at head_dim 64 and at 128 alike.
    Unmasked there is nothing to skip and the sweep only costs (1.451
    whole against 1.555 at 512 x 512): the tile is its own sub-tile."""
    if mask[0] == "none":
        return bq, bk
    return 512, 512


def tile_census(mask: tuple, lq: int, lk: int, bq: int, bk: int,
                sub_q: int, sub_k: int) -> dict:
    """How many ``sub_q x sub_k`` sub-tiles of one head's ``lq x lk``
    scores (offsets 0, grid tiles ``bq x bk``) the kernels skip (``dead``),
    compute under the mask (``cut``) and compute without it (``full``):
    the classes ``_sweep`` gives them at run time."""
    if bq % sub_q or bk % sub_k or lq % bq or lk % bk:
        raise ValueError(f"sub-tiles {sub_q} x {sub_k} do not tile grid "
                         f"tiles {bq} x {bk} of {lq} x {lk}")
    q0 = np.arange(0, lq, sub_q)[:, None]
    k0 = np.arange(0, lk, sub_k)[None, :]
    shape = (q0.size, k0.size)
    live = np.broadcast_to(_tile_live(mask, q0, k0, sub_q, sub_k), shape)
    full = np.broadcast_to(_tile_full(mask, q0, k0, sub_q, sub_k), shape)
    return {"dead": int((~live).sum()), "cut": int((live & ~full).sum()),
            "full": int(full.sum())}


def flash_tiles(spec: tuple, lq: int, lk: int, dtype,
                block_q: Optional[int] = None,
                block_k: Optional[int] = None,
                d: Optional[int] = None,
                dv: Optional[int] = None) -> Optional[dict]:
    """What ``flash_attention`` does with one head of ``lq x lk`` scores
    under the mask spec ``spec``: the grid tile, the sub-tile, the census
    of sub-tiles by class and, where the widths of q / k (``d``) and of v
    (``dv``) are given, the ``backward`` pass (``fused``: one kernel gives
    dq, dk and dv) with its ``resident_bytes``, the float32 dk and dv of
    one key-value head that stay in VMEM across the head's sweep: the
    ``attn.flash_tiles`` row of a trace is this dictionary. None where
    the tiling cannot serve the shape and the dense path runs."""
    mb = _min_block_for(dtype)
    dbq, dbk = (_bd_block_targets() if spec[0] == "bd"
                else _window_block_targets() if spec[0] == "window"
                else _default_block_targets(spec[0] == "causal"))
    # a tile of the block-diffusion mask lies within one half
    tile_q, tile_k = (spec[2], spec[2]) if spec[0] == "bd" else (lq, lk)
    bq = _pick_block(tile_q, block_q if block_q is not None else dbq, mb)
    bk = _pick_block(tile_k, block_k if block_k is not None else dbk, mb)
    if bq is None or bk is None or (spec[0] == "bd"
                                    and min(bq, bk) <= spec[1]):
        return None
    tsq, tsk = _sub_tile_targets(spec, bq, bk)
    sq, sk = _pick_block(bq, tsq, mb), _pick_block(bk, tsk, mb)
    plan = dict(mask=spec[0], block_q=bq, block_k=bk, sub_q=sq, sub_k=sk,
                **tile_census(spec, lq, lk, bq, bk, sq, sk))
    if d is not None:
        plan.update(backward="fused",
                    resident_bytes=lk * (d + (d if dv is None else dv)) * 4)
    return plan


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = False,
    mask: Optional[str] = None,
    block: Optional[int] = None, half: Optional[int] = None,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset=None, k_offset=None,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    return_lse: bool = False,
):
    """Tiled attention over ``q`` ``[batch, seq, heads, head_dim]`` and
    ``k``/``v`` ``[batch, seq, kv_heads, head_dim]`` (``kv_heads``
    divides ``heads``; query head ``h`` reads key-value head
    ``h // (heads // kv_heads)``; ``v``'s last dimension may differ from
    ``q``'s and ``k``'s, and is the output's).

    ``mask`` is ``None`` (all pairs, or causal with ``causal=True``),
    ``'causal'``, ``'window'`` with a static ``window`` (``0 <= q_pos -
    k_pos < window``) or ``'block_diffusion'`` with static ``block`` and
    ``half`` (see the module docstring). ``q_offset``/``k_offset`` (int
    scalars, may be traced) place the q/k blocks in global sequence
    coordinates for the causal mask — ring attention passes its rotating
    block offsets here. With ``return_lse=True`` also returns the per-row
    logsumexp ``[b, h, q]`` (differentiable), which is what
    block-combining needs.
    """
    b, lq, h, d = q.shape
    lk, kvh = k.shape[1], k.shape[2]
    if h % kvh or v.shape[2] != kvh:
        raise ValueError(f"{h} query heads over {kvh} / {v.shape[2]} "
                         "key / value heads")
    spec = _mask_spec(causal, mask, block, half, window)
    if spec[0] == "window" and (q_offset is not None or k_offset is not None
                                or lk != lq):
        raise ValueError("mask='window' covers one whole sequence: q and k "
                         f"of one length, no offsets; got {lq} and {lk}")
    if spec[0] == "bd" and (q_offset is not None or k_offset is not None
                            or lq != 2 * spec[2] or lk != lq):
        raise ValueError("mask='block_diffusion' covers the whole doubled "
                         f"sequence: 2 * half = {2 * spec[2]} positions of "
                         f"q and k, no offsets; got {lq} and {lk}")
    if scale is None:
        scale = d ** -0.5
    q_offset = jnp.zeros((), jnp.int32) if q_offset is None else q_offset
    k_offset = jnp.zeros((), jnp.int32) if k_offset is None else k_offset

    plan = flash_tiles(spec, lq, lk, q.dtype, block_q, block_k, d, v.shape[3])
    if plan is None:
        out, lse = _attention_jnp(q, k, v, q_offset, k_offset, spec, scale)
        return (out, lse) if return_lse else out
    bq, bk, sq, sk = (plan[key] for key in ("block_q", "block_k", "sub_q",
                                            "sub_k"))
    # how often the sweep engages, once a trace (the set-up log's row)
    setup_event("attn.flash_tiles", **plan)

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], x.shape[3])

    q_off = jnp.broadcast_to(q_offset, (1,)).astype(jnp.int32)
    k_off = jnp.broadcast_to(k_offset, (1,)).astype(jnp.int32)
    out3, lse3 = _flash(to3(q), to3(k), to3(v), q_off, k_off,
                        spec, float(scale), (bq, bk), (sq, sk))
    out = out3.reshape(b, h, lq, v.shape[3]).transpose(0, 2, 1, 3)
    if not return_lse:
        return out
    return out, lse3[..., 0].reshape(b, h, lq)


def flash_supported(lq: int, lk: int, block_q: int = 128,
                    block_k: int = 128, dtype=jnp.float32) -> bool:
    """Can the tiled kernel serve these sequence lengths (at this
    dtype's minimal sublane tile)?"""
    mb = _min_block_for(dtype)
    return (_pick_block(lq, block_q, mb) is not None
            and _pick_block(lk, block_k, mb) is not None)


def flash_auto_ok(lq: int, lk: int, dtype) -> bool:
    """The ONE auto-dispatch gate every attention entry point (BERT
    'full', ring, ulysses) consults, decided from what can be observed
    without compiling: the backend is a TPU (off-TPU the kernel would
    run interpreted), the longer side reaches ``FLASH_MIN_SEQ``, and
    both lengths tile at this dtype. A Mosaic failure at a shape this
    gate admits raises at compile time instead of silently handing the
    model the dense path. The explicit ``attention='flash'`` mode
    bypasses this gate entirely."""
    return (jax.default_backend() == "tpu"
            and max(lq, lk) >= FLASH_MIN_SEQ
            and flash_supported(lq, lk, dtype=dtype))
