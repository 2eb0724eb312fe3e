"""Hyper-connections over n residual streams, the residual mixing matrix
projected onto the doubly stochastic matrices (hyper-connections,
arXiv:2409.19606; the manifold-constrained form whose knobs a source
``config.json`` names ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps`` and
``mhc_h_res_clamp_*``).

The streams ride as ``[n, b, s, d]`` (the stream index LEADING: the last
two dimensions tile as any activation's, where ``[b, s, n, d]`` would pad
n = 4 to a sublane tile of 16). Per position, with X in R^{n x d}:

- ``mixing_weights``: ``x~ = vec(X) / rms(vec(X))`` (no gain);
  ``H~_pre = a_pre (x~ W_pre) + b_pre`` (n), ``H~_post = a_post (x~
  W_post) + b_post`` (n), ``H~_res = a_res mat(x~ W_res) + b_res`` (n x
  n); ``H_pre = sigmoid(H~_pre)``, ``H_post = 2 sigmoid(H~_post)``,
  ``H_res = sinkhorn(exp(clamp(H~_res)))``. The three products are ONE
  pass over the streams (the weights side by side, 2n + n^2 columns) and
  the norm is a scalar a position, applied to the product.
- ``width_mix``: ``u = H_pre X`` (d), what the sub-layer reads.
- ``depth_mix``: ``X' = H_res X + H_post^T y``.

``connect(streams, p, sub_layer)`` is a whole hyper-connected sub-layer,
``X' = H_res X + H_post^T F(H_pre X)``, by one of two movers that it
chooses from the shapes (``tile``; the ``hc.plan`` row names it):

- ``kernel``: where ``d`` is whole lane tiles and the positions divide by
  a tile of P, four Pallas kernels that each bring a tile's n streams
  into VMEM ONCE and do there everything the pass needs of them; in
  values of ``hidden`` a position (what ``chipbench/flops_xing.py::
  hc_mix_cost`` says a sub-layer must move, and no more):

  ========== ============================== ===================== =====
  kernel     reads                          writes                moves
  ========== ============================== ===================== =====
  hc_pre_fwd X                              u                     n + 1
  hc_post_fwd X, y                          X'                    2n + 1
  hc_post_bwd dX', X, y                     dy                    2n + 2
  hc_pre_bwd du, X, dX'                     dX                    3n + 1
  ========== ============================== ===================== =====

  plus, a position, the weights as ONE float32 row of 128 lanes (``C``:
  ``H_pre`` in lanes 0..n, ``H_post`` n..2n, ``H_res[i, j]`` in lane
  ``base + 8 i + j``), the normalised product ``Z`` beside it and their
  gradients: 0.5 KB each against 28.7 KB of streams. ``hc_pre_fwd`` does
  the ``[P, n d] x [n d, 128]`` product on the MXU, the sum of squares,
  the sigmoids and, with the position turned onto the LANES, the Sinkhorn
  iterations on ``[n, 8, P]`` float32; ``hc_pre_bwd`` runs the iterations
  again, keeps every half step in VMEM and walks them back, then writes
  ``dX = H_res^T dX' + H_pre (x) du + d(raw) W^T + the norm's term`` once
  and sums ``dW = X^T d(raw)`` over the tiles into a resident float32
  block. The two halves are ``jax.custom_vjp``s SPLIT AT THE SUB-LAYER:
  the first hands the streams through to the second, whose backward rule
  hands ``dX'`` back along the same edge untouched, so that ``H_res^T
  dX'`` is formed where ``dX`` is written and never as an array of its
  own.
- ``jnp``: the three public functions above, plain ``jax.numpy`` with
  JAX's own backward pass (the Sinkhorn iterations unrolled): every other
  shape, and the tests' second opinion.

The weights and the Sinkhorn run in float32, the streams in their own
dtype with float32 sums, the weights' product in the streams' dtype with
float32 accumulation, under either mover. Named scopes ``hc.mix`` and
``hc.sinkhorn`` (behind ``tag``) are in the step program's instruction
metadata; every kernel, forward, recomputed and backward, sits under
``hc.mix``. Off the TPU the kernels run interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.ops._common import LANE as _LANE
from pytorch_ps_mpi_tpu.ops._common import interpret as _interpret
from pytorch_ps_mpi_tpu.telemetry.recorder import setup_event

# Chip numbers (K-run of PR 36, one v5e, ``[4, 1, 4096, 3584]`` bf16, ms a
# sub-layer: hc_pre_fwd / hc_post_fwd / hc_post_bwd / hc_pre_bwd, then value
# and gradient under ``jax.checkpoint``): P 256 0.481 / 0.439 / 0.460 /
# 1.045, 3.106; P 128 0.502 / 0.435 / 0.453 / 0.996, 3.081: a tie, and 256
# is half the grid steps. Inner loops of 8 / 16 / 32 positions: 3.165 /
# 3.106 / 3.108. The jax.numpy functions: 8.753; their depth mix 1.530 where
# hc_post_fwd takes 0.439 (XLA's fusion over the kernels' ``C``: 1.585).
TILE = 256                  # positions a grid step
_ROWS = 16                  # positions a pass of the inner loops: one
#                             sublane tile of bfloat16, two of float32
_GROUP = 8                  # lanes between two rows of H_res in ``C``
_BLOCK_BYTES = 8 << 20      # a tile's n streams, one buffer
_VMEM_BYTES = 100 << 20     # of the chip's 128 MiB


def sinkhorn(m, iters: int, eps: float):
    """Sinkhorn-Knopp on positive ``m [n, n, ...]`` (``m[i, j]`` weighs
    stream j in new stream i): rows, then columns, normalised ``iters``
    times. Ends on the columns, whose sums are then 1 up to ``eps``."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def init(key, n: int, d: int, *, scale: float = 0.02, gate: float = 0.01,
         bias: float = 8.0):
    """One hyper-connection's float32 parameters. ``a_*`` start at
    ``gate``; ``b_pre`` is ``+bias`` at stream 0 and ``-bias`` elsewhere
    (the sub-layer reads stream 0), ``b_post`` zero (``H_post``
    one: every stream takes the sub-layer's output), ``b_res`` zero on the
    diagonal and ``-bias`` off it (``H_res`` near the identity): with
    ``gate`` zero and ``bias`` large the streams are n copies of the plain
    residual stream."""
    k_pre, k_post, k_res = jax.random.split(key, 3)

    def normal(k, cols):
        return scale * jax.random.normal(k, (n, d, cols), jnp.float32)

    return {
        "w_pre": normal(k_pre, n), "w_post": normal(k_post, n),
        "w_res": normal(k_res, n * n),
        "a_pre": jnp.float32(gate), "a_post": jnp.float32(gate),
        "a_res": jnp.float32(gate),
        "b_pre": jnp.where(jnp.arange(n) == 0, bias, -bias
                           ).astype(jnp.float32),
        "b_post": jnp.zeros((n,), jnp.float32),
        "b_res": (bias * (jnp.eye(n) - 1.0)).astype(jnp.float32)}


# ---------------------------------------------------------------------------
# the jax.numpy mover
# ---------------------------------------------------------------------------

def mixing_weights(streams, p, *, iters: int, eps: float, clamp,
                   norm_eps: float, tag: str = ""):
    """``streams [n, b, s, d]`` -> float32 ``H_pre [n, b, s]``, ``H_post
    [n, b, s]``, ``H_res [n, n, b, s]`` (``H_res[i, j]``: stream j into
    new stream i)."""
    n, _, _, d = streams.shape
    with jax.named_scope(tag + "hc.mix"):
        w = jnp.concatenate([p["w_pre"], p["w_post"], p["w_res"]], axis=-1)
        raw = jnp.einsum("nbsd,ndk->kbs", streams, w.astype(streams.dtype),
                         preferred_element_type=jnp.float32)
        x32 = streams.astype(jnp.float32)
        raw = raw * jax.lax.rsqrt(
            jnp.sum(x32 * x32, axis=(0, 3)) / (n * d) + norm_eps)
        col = lambda b: b.reshape(-1, 1, 1)
        h_pre = jax.nn.sigmoid(p["a_pre"] * raw[:n] + col(p["b_pre"]))
        h_post = 2.0 * jax.nn.sigmoid(
            p["a_post"] * raw[n:2 * n] + col(p["b_post"]))
        h_res = p["a_res"] * raw[2 * n:] + col(p["b_res"])
    with jax.named_scope(tag + "hc.sinkhorn"):
        h_res = jnp.exp(jnp.clip(h_res, *clamp)).reshape(n, n, *raw.shape[1:])
        return h_pre, h_post, sinkhorn(h_res, iters, eps)


def width_mix(streams, h_pre, tag: str = ""):
    """``u = H_pre X``: ``[b, s, d]`` in the streams' dtype."""
    with jax.named_scope(tag + "hc.mix"):
        u = sum(h_pre[i][..., None] * streams[i].astype(jnp.float32)
                for i in range(streams.shape[0]))
        return u.astype(streams.dtype)


def depth_mix(streams, y, h_res, h_post, tag: str = ""):
    """``X' = H_res X + H_post^T y``: ``[n, b, s, d]``."""
    n = streams.shape[0]
    with jax.named_scope(tag + "hc.mix"):
        x32, y32 = streams.astype(jnp.float32), y.astype(jnp.float32)
        return jnp.stack([
            (sum(h_res[i, j][..., None] * x32[j] for j in range(n))
             + h_post[i][..., None] * y32).astype(streams.dtype)
            for i in range(n)])


# ---------------------------------------------------------------------------
# the kernel mover
# ---------------------------------------------------------------------------

def tile(streams) -> int | None:
    """Positions a grid step of the kernels over ``streams [n, b, s, d]``,
    or None where the ``jax.numpy`` functions take them: ``d`` in whole
    lane tiles, bfloat16 or float32, at most 8 streams (a row of ``H_res``
    is a group of 8 lanes), and ``b s`` a multiple of ``TILE`` or of 128
    whose n streams fit ``_BLOCK_BYTES`` a buffer."""
    n, b, s, d = streams.shape
    if (d % _LANE or n > _GROUP
            or streams.dtype not in (jnp.bfloat16, jnp.float32)):
        return None
    for positions in (TILE, _LANE):
        if (b * s) % positions == 0 and (
                n * positions * d * streams.dtype.itemsize <= _BLOCK_BYTES):
            return positions
    return None


def _base(n: int) -> int:
    """The lane of ``H_res[0, 0]`` in a row of ``C``: the first group of
    8 after ``H_pre`` and ``H_post``."""
    return -(-2 * n // _GROUP) * _GROUP


def _pack(p, n: int):
    """``W [n, d, 128]`` (float32: the three weights in ``C``'s lanes,
    zero elsewhere) and ``ab [8, 128]``: row 0 the gate and row 1 the bias
    of each lane. Linear in ``p``: its transpose unpacks the gradients."""
    wide = lambda r: jnp.pad(
        r.reshape(*r.shape[:-1], n, n),
        [(0, 0)] * r.ndim + [(0, _GROUP - n)]).reshape(*r.shape[:-1], -1)

    def lanes(pre, post, res):
        row = jnp.concatenate([pre, post], axis=-1)
        row = jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, _base(n) - 2 * n)])
        row = jnp.concatenate([row, wide(res)], axis=-1)
        return jnp.pad(row, [(0, 0)] * (row.ndim - 1)
                       + [(0, _LANE - row.shape[-1])])

    ones = jnp.ones((n,), jnp.float32)
    ab = jnp.stack([
        lanes(p["a_pre"] * ones, p["a_post"] * ones,
              p["a_res"] * jnp.ones((n * n,), jnp.float32)),
        lanes(p["b_pre"], p["b_post"], p["b_res"].reshape(-1))])
    return (lanes(p["w_pre"], p["w_post"], p["w_res"]),
            jnp.pad(ab, ((0, _GROUP - 2), (0, 0))))


def _chunks(positions: int, body, first: int = 0):
    """``body(rows)`` over the ``positions`` of a tile from ``first`` on,
    ``_ROWS`` at a time."""
    from jax.experimental import pallas as pl

    def step(r, carry):
        body(pl.ds(pl.multiple_of(first + r * _ROWS, _ROWS), _ROWS))
        return carry

    jax.lax.fori_loop(0, positions // _ROWS, step, 0)


def _lane(c, k: int):
    """Lane ``k`` of ``c [rows, 128]`` as a column ``[rows, 1]``."""
    return c[:, k:k + 1]


def _columns(values, shape):
    """``[rows, 128]`` float32 holding ``values[k] [rows, 1]`` in lane
    ``k`` and zero elsewhere."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    out = jnp.zeros(shape, jnp.float32)
    for k, v in values.items():
        out = jnp.where(lane == k, v, out)
    return out


def _exp_clamped(t, i: int, n: int, clamp):
    """Row i of ``exp(clamp(H~_res))`` out of the turned ``H~ [128, P]``:
    ``[8, P]``, the lanes' padding zero; and where the clamp lets a
    gradient through."""
    first = _base(n) + _GROUP * i
    logit = t[first:first + _GROUP, :]
    held = jax.lax.broadcasted_iota(jnp.int32, logit.shape, 0) < n
    e = jnp.where(held, jnp.exp(jnp.clip(logit, *clamp)), 0.0)
    return e, held & (logit >= clamp[0]) & (logit <= clamp[1])


def _sinkhorn_steps(m, iters: int, eps: float, save=None):
    """``sinkhorn`` on ``m [n, 8, P]`` (the padding zero and staying so),
    one loop; ``save [2 iters + 1, n, 8, P]`` keeps the matrix before
    every half step and after the last."""
    def step(k, m):
        if save is not None:
            save[2 * k] = m
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        if save is not None:
            save[2 * k + 1] = m
        return m / (jnp.sum(m, axis=0, keepdims=True) + eps)

    m = jax.lax.fori_loop(0, iters, step, m)
    if save is not None:
        save[2 * iters] = m
    return m


def _sinkhorn_back(g, iters: int, eps: float, save):
    """The gradient at ``save[0]`` from ``g`` at ``save[2 iters]``: a half
    step ``m' = m / (sum_a m + eps)`` turns ``g'`` into ``(g' - sum_a g'
    m') / (sum_a m + eps)``; the padding is held at zero (its sums are
    ``eps`` alone)."""
    held = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1) < g.shape[0]

    def half(g, k, axis):
        norm = jnp.sum(save[k], axis=axis, keepdims=True) + eps
        return jnp.where(held, (g - jnp.sum(
            g * save[k + 1], axis=axis, keepdims=True)) / norm, 0.0)

    def step(k, g):
        k = iters - 1 - k
        return half(half(g, 2 * k + 1, 0), 2 * k, 1)

    return jax.lax.fori_loop(0, iters, step, g)


def _pre_fwd_kernel(ab_ref, x_ref, w_ref, u_ref, c_ref, z_ref, ssq, turned,
                    *, iters, eps, clamp, norm_eps):
    n, positions, d = x_ref.shape
    f32 = jnp.float32

    def squares(rows):
        s = sum(jnp.sum(jnp.square(x_ref[i, rows, :].astype(f32)), axis=-1,
                        keepdims=True) for i in range(n))
        ssq[rows, :] = jnp.broadcast_to(s, (_ROWS, _LANE))

    _chunks(positions, squares)
    raw = sum(jnp.dot(x_ref[i], w_ref[i], preferred_element_type=f32)
              for i in range(n))
    rs = jax.lax.rsqrt(ssq[...] / (n * d) + norm_eps)
    z = raw * rs
    logit = z * ab_ref[0:1, :] + ab_ref[1:2, :]
    gate = jax.nn.sigmoid(logit)
    t = logit.T                                     # the position on the lanes
    m = _sinkhorn_steps(
        jnp.stack([_exp_clamped(t, i, n, clamp)[0] for i in range(n)]),
        iters, eps)
    turned[...] = jnp.zeros_like(turned)
    for i in range(n):
        turned[_base(n) + _GROUP * i:_base(n) + _GROUP * (i + 1), :] = m[i]
    lane = jax.lax.broadcasted_iota(jnp.int32, gate.shape, 1)
    c_ref[...] = jnp.where(lane < n, gate, jnp.where(
        lane < 2 * n, 2.0 * gate, turned[...].T))
    # the norm's factor rides in the lanes the product leaves empty
    z_ref[...] = jnp.where(lane < _base(n) + _GROUP * n, z, rs)

    def mix(rows):
        c = c_ref[rows, :]
        u_ref[rows, :] = sum(_lane(c, i) * x_ref[i, rows, :].astype(f32)
                             for i in range(n)).astype(u_ref.dtype)

    _chunks(positions, mix)


def _post_fwd_kernel(x_ref, y_ref, c_ref, out_ref):
    n, positions, _ = x_ref.shape
    f32 = jnp.float32

    def mix(rows):
        c, y = c_ref[rows, :], y_ref[rows, :].astype(f32)
        x = [x_ref[j, rows, :].astype(f32) for j in range(n)]
        for i in range(n):
            out_ref[i, rows, :] = (_lane(c, n + i) * y + sum(
                _lane(c, _base(n) + _GROUP * i + j) * x[j] for j in range(n))
            ).astype(out_ref.dtype)

    _chunks(positions, mix)


def _post_bwd_kernel(x_ref, g_ref, y_ref, c_ref, dy_ref, dc_ref):
    n, positions, _ = x_ref.shape
    f32 = jnp.float32
    dot = lambda a, b: jnp.sum(a * b, axis=-1, keepdims=True)

    def back(rows):
        c, y = c_ref[rows, :], y_ref[rows, :].astype(f32)
        g = [g_ref[i, rows, :].astype(f32) for i in range(n)]
        dy_ref[rows, :] = sum(_lane(c, n + i) * g[i] for i in range(n)
                              ).astype(dy_ref.dtype)
        found = {n + i: dot(g[i], y) for i in range(n)}
        for j in range(n):
            x = x_ref[j, rows, :].astype(f32)
            for i in range(n):
                found[_base(n) + _GROUP * i + j] = dot(g[i], x)
        dc_ref[rows, :] = _columns(found, (_ROWS, _LANE))

    _chunks(positions, back)


def _pre_bwd_kernel(ab_ref, x_ref, g_ref, du_ref, c_ref, z_ref, dc_ref,
                    wt_ref, dx_ref, dh_ref, dwt_ref, col, turned, save, mm,
                    *, iters, eps, clamp):
    from jax.experimental import pallas as pl

    n, positions, d = x_ref.shape
    f32 = jnp.float32
    base, used = _base(n), _base(n) + _GROUP * n

    def widths(rows):
        du = du_ref[rows, :].astype(f32)
        col[rows, :] = _columns({i: jnp.sum(
            du * x_ref[i, rows, :].astype(f32), axis=-1, keepdims=True)
            for i in range(n)}, (_ROWS, _LANE))

    _chunks(positions, widths)
    c, z, a = c_ref[...], z_ref[...], ab_ref[0:1, :]
    t = (z * a + ab_ref[1:2, :]).T
    gt = dc_ref[...].T
    start = [_exp_clamped(t, i, n, clamp) for i in range(n)]
    _sinkhorn_steps(jnp.stack([e for e, _ in start]), iters, eps, save)
    g = _sinkhorn_back(
        jnp.stack([gt[base + _GROUP * i:base + _GROUP * (i + 1), :]
                   for i in range(n)]), iters, eps, save)
    turned[...] = jnp.zeros_like(turned)
    for i in range(n):
        e, inside = start[i]
        turned[base + _GROUP * i:base + _GROUP * (i + 1), :] = jnp.where(
            inside, g[i] * e, 0.0)
    lane = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
    dh = jnp.where(lane < n, (col[...] + dc_ref[...]) * c * (1.0 - c),
                   jnp.where(lane < 2 * n, dc_ref[...] * c * (1.0 - 0.5 * c),
                             turned[...].T))
    dh_ref[...] = dh
    dz = dh * a
    rs = _lane(z, _LANE - 1)
    draw = dz * rs
    # the norm's term of dX is this, a position, times X
    col[...] = jnp.broadcast_to(
        -rs * rs / (n * d) * jnp.sum(jnp.where(lane < used, dz * z, 0.0),
                                     axis=-1, keepdims=True), col.shape)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dwt_ref[...] = jnp.zeros_like(dwt_ref)

    drawt = draw.T[:dwt_ref.shape[1], :].astype(x_ref.dtype)
    for i in range(n):
        dwt_ref[i] += jnp.dot(drawt, x_ref[i], preferred_element_type=f32)
    for first in range(0, positions, _LANE):
        for i in range(n):
            mm[i] = jnp.dot(draw[first:first + _LANE].astype(wt_ref.dtype),
                            wt_ref[i], preferred_element_type=f32)

        def back(at, first=first):
            rows = pl.ds(pl.multiple_of(at.start - first, _ROWS), _ROWS)
            w, norm = c_ref[at, :], _lane(col[at, :], 0)
            du = du_ref[at, :].astype(f32)
            gs = [g_ref[j, at, :].astype(f32) for j in range(n)]
            for i in range(n):
                dx_ref[i, at, :] = (
                    mm[i, rows, :] + norm * x_ref[i, at, :].astype(f32)
                    + _lane(w, i) * du + sum(
                        _lane(w, base + _GROUP * j + i) * gs[j]
                        for j in range(n))).astype(dx_ref.dtype)

        _chunks(_LANE, back, first)


def _call(kernel, name, positions, ins, outs, scratch=(), carried=False):
    """One ``pallas_call`` over the position tiles. ``ins`` / ``outs``: an
    array (a ShapeDtypeStruct) and whether it is tiled by position (on its
    second-to-last axis) or resident."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(a, tiled):
        if not tiled:
            return pl.BlockSpec(a.shape, lambda t: (0,) * a.ndim)
        lead = a.ndim - 2
        return pl.BlockSpec((*a.shape[:lead], positions, a.shape[-1]),
                            lambda t: (0,) * lead + (t, 0))

    steps = {a.shape[-2] // positions for a, tiled in (*ins, *outs) if tiled}
    (steps,) = steps
    return pl.pallas_call(
        kernel, grid=(steps,),
        in_specs=[spec(a, tiled) for a, tiled in ins],
        out_specs=[spec(a, tiled) for a, tiled in outs],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a, _ in outs],
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if carried else "parallel",),
            vmem_limit_bytes=_VMEM_BYTES),
        name=name, interpret=_interpret(),
    )(*(a for a, _ in ins))


def _small(positions_all):
    return jax.ShapeDtypeStruct((positions_all, _LANE), jnp.float32)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _pre_fwd(x, p, positions, cfg):
    """``x [n, T, d]`` -> ``u [T, d]``, ``C [T, 128]``, ``Z [T, 128]``."""
    from jax.experimental.pallas import tpu as pltpu

    n, total, d = x.shape
    iters, eps, clamp, norm_eps = cfg
    w, ab = _pack(p, n)
    return _call(
        functools.partial(_pre_fwd_kernel, iters=iters, eps=eps, clamp=clamp,
                          norm_eps=norm_eps),
        "hc_pre_fwd", positions,
        [(ab, False), (x, True), (w.astype(x.dtype), False)],
        [(jax.ShapeDtypeStruct((total, d), x.dtype), True),
         (_small(total), True), (_small(total), True)],
        [pltpu.VMEM((positions, _LANE), jnp.float32),
         pltpu.VMEM((_LANE, positions), jnp.float32)])


@functools.partial(jax.jit, static_argnums=(4, 5))
def _pre_bwd(x, p, c, z, positions, cfg, du, dc, g):
    """-> ``dX [n, T, d]`` and ``p``'s gradients."""
    from jax.experimental.pallas import tpu as pltpu

    n, total, d = x.shape
    iters, eps, clamp, _ = cfg
    (w, ab), unpack = jax.vjp(lambda p: _pack(p, n), p)
    held = -(-(_base(n) + _GROUP * n) // 16) * 16
    dx, dh, dwt = _call(
        functools.partial(_pre_bwd_kernel, iters=iters, eps=eps, clamp=clamp),
        "hc_pre_bwd", positions,
        [(ab, False), (x, True), (g, True), (du, True), (c, True), (z, True),
         (dc, True), (w.astype(x.dtype).swapaxes(1, 2), False)],
        [(x, True), (_small(total), True),
         (jax.ShapeDtypeStruct((n, held, d), jnp.float32), False)],
        [pltpu.VMEM((positions, _LANE), jnp.float32),
         pltpu.VMEM((_LANE, positions), jnp.float32),
         pltpu.VMEM((2 * iters + 1, n, _GROUP, positions), jnp.float32),
         pltpu.VMEM((n, _LANE, d), jnp.float32)], carried=True)
    dw = jnp.pad(dwt, ((0, 0), (0, _LANE - held), (0, 0))).swapaxes(1, 2)
    dab = jnp.stack([jnp.sum(dh * z, axis=0), jnp.sum(dh, axis=0)])
    (dp,) = unpack((dw, jnp.pad(dab, ((0, _GROUP - 2), (0, 0)))))
    return dx, dp


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _pre(x, p, positions, cfg):
    """``(u, C, X)``: the third is ``x`` itself, for ``_post`` alone."""
    u, c, _ = _pre_fwd(x, p, positions, cfg)
    return u, c, x


def _pre_rule(x, p, positions, cfg):
    u, c, z = _pre_fwd(x, p, positions, cfg)
    return (u, c, x), (x, p, c, z)


_pre.defvjp(_pre_rule, lambda positions, cfg, kept, cts: _pre_bwd(
    *kept, positions, cfg, *cts))


@functools.partial(jax.jit, static_argnums=3)
def _post_fwd(x, y, c, positions):
    (out,) = _call(_post_fwd_kernel, "hc_post_fwd", positions,
                   [(x, True), (y, True), (c, True)], [(x, True)])
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _post(x, y, c, positions):
    """``X' = H_res X + H_post^T y`` of ``_pre``'s third output. Its
    backward rule hands ``dX'`` back AS the gradient of ``x``: only
    ``_pre``'s rule, which adds ``H_res^T`` where it writes ``dX``, may
    receive it."""
    return _post_fwd(x, y, c, positions)


@functools.partial(jax.jit, static_argnums=0)
def _post_bwd(positions, kept, g):
    x, y, c = kept
    dy, dc = _call(_post_bwd_kernel, "hc_post_bwd", positions,
                   [(x, True), (g, True), (y, True), (c, True)],
                   [(y, True), (c, True)])
    return g, dy, dc


_post.defvjp(lambda x, y, c, positions: (_post_fwd(x, y, c, positions),
                                         (x, y, c)), _post_bwd)


def weights_of(c, shape):
    """``C [b s, 128]`` -> ``H_pre [n, b, s]``, ``H_post [n, b, s]``,
    ``H_res [n, n, b, s]`` as ``mixing_weights`` gives them."""
    n, b, s, _ = shape
    rows = c.T.reshape(_LANE, b, s)
    return (rows[:n], rows[n:2 * n],
            rows[_base(n):_base(n) + _GROUP * n].reshape(n, _GROUP, b, s)[:, :n])


def connect(streams, p, sub_layer, *, iters: int, eps: float, clamp,
            norm_eps: float, tag: str = ""):
    """A hyper-connected sub-layer: ``streams [n, b, s, d]`` -> (``X' =
    H_res X + H_post^T F(H_pre X)``, what ``sub_layer`` returned beside
    ``F(u)`` or None). ``sub_layer(u [b, s, d])`` returns ``F(u)`` or a
    pair."""
    positions = tile(streams)
    split = lambda out: out if isinstance(out, tuple) else (out, None)
    if positions is None:
        h_pre, h_post, h_res = mixing_weights(
            streams, p, iters=iters, eps=eps, clamp=clamp, norm_eps=norm_eps,
            tag=tag)
        y, more = split(sub_layer(width_mix(streams, h_pre, tag)))
        return depth_mix(streams, y, h_res, h_post, tag), more
    n, b, s, d = streams.shape
    with jax.named_scope(tag + "hc.mix"):
        u, c, x = _pre(streams.reshape(n, b * s, d), p, positions,
                       (iters, eps, tuple(clamp), norm_eps))
    y, more = split(sub_layer(u.reshape(b, s, d)))
    with jax.named_scope(tag + "hc.mix"):
        out = _post(x, y.reshape(b * s, d).astype(streams.dtype), c, positions)
        return out.reshape(streams.shape), more


def record_plan(streams, iters: int, sub_layers: int) -> None:
    """One ``hc.plan`` row in the set-up log a trace: how many
    streams, Sinkhorn iterations and hyper-connected sub-layers the
    program holds, the bytes of the streams at a layer boundary, and the
    mover ``connect`` takes for them (``kernel`` with its tile of
    positions, or ``jnp`` with 0)."""
    positions = tile(streams)
    setup_event("hc.plan", streams=int(streams.shape[0]),
                iterations=int(iters), sub_layers=int(sub_layers),
                stream_bytes=int(streams.size * streams.dtype.itemsize),
                mover="kernel" if positions else "jnp",
                tile=int(positions or 0))
