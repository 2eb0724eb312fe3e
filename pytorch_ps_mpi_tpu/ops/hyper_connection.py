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

Everything here is ``jax.numpy``: the backward pass is JAX's own (the
Sinkhorn iterations are unrolled; their 2 x ``iters`` intermediates are n
x n floats a position, 0.3 MB each at 4,096 positions). The work is
memory-bound: a sub-layer reads the streams three times and writes them
once, forward. The weights and the Sinkhorn run in float32 with the
position on the LANES (``[n, n, b, s]``), the streams in their own dtype
with float32 sums. Named scopes ``hc.mix`` and ``hc.sinkhorn`` (behind
``tag``) are in the step program's instruction metadata.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.telemetry.recorder import setup_event


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


def record_plan(streams, iters: int, sub_layers: int) -> None:
    """One ``hc.plan`` row in the set-up log a trace: how many
    streams, Sinkhorn iterations and hyper-connected sub-layers the
    program holds, and the bytes of the streams at a layer boundary."""
    setup_event("hc.plan", streams=int(streams.shape[0]),
                iterations=int(iters), sub_layers=int(sub_layers),
                stream_bytes=int(streams.size * streams.dtype.itemsize))
