"""The gated delta rule with a decay a key channel (Kimi Linear's KDA
form), in its chunked training form.

For one row and one head, with ``q_t, k_t`` in R^dk, ``v_t`` in R^dv, a
log-decay ``g_t`` in (-inf, 0]^dk and ``beta_t``:

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,
    S_0 = 0 at the row's first position,
    o_t = S_t^T q_t.

Written as ``S_t = D_t S_{t-1} + k_t u_t^T`` with ``u_t = beta_t (v_t -
(D_t S_{t-1})^T k_t)``, a chunk of C positions that starts from the state
``S`` unrolls, with ``G_t`` the log-decay summed from the chunk's start
through ``t`` (a vector a key channel), into

    (I + A) U = diag(beta) (V - K~ S),  A_ts = beta_t sum_c k_t k_s exp(G_t - G_s), s < t
    O        = Q~ S + P U,               P_ts = sum_c q_t k_s exp(G_t - G_s),        s <= t
    S'       = Diag(exp G_C) S + K-^T U

with ``K~ = K exp(G)``, ``Q~ = Q exp(G)`` (decayed from the chunk's start)
and ``K- = K exp(G_C - G)`` (decayed to its end). So:

- inside a chunk, batched over every chunk of every row and head at
  once: ``A`` and ``P``, then ONE unit lower-triangular solve of ``(I +
  A)`` against ``diag(beta) [K~ | V]``: ``W`` and ``U~`` (the WY / UT
  transform), ``U = U~ - W S``;
- between chunks, one pass (``lax.scan`` over the chunks) that carries
  only the ``[dk, dv]`` state: ``U = U~ - W S``, ``S' = Diag(exp G_C) S +
  K-^T U``;
- then, batched again: ``O = Q~ S + P U``.

No per-token loop runs anywhere. ``exp(G_t - G_s)`` is never formed as
``exp(G_t) exp(-G_s)``, which overflows float32 where a chunk's decay
passes exp(-88). ``A`` and ``P`` are built by halves: at each level the
later half of a block of ``2h`` positions meets its earlier half through
the block's middle ``r`` (the last position of the earlier half), ``exp(G_t
- G_r) exp(G_r - G_s)``, both factors at most 1 since the log-decay only
falls; the diagonal blocks are the next level's. ``log2 C`` levels
count every pair ``s < t`` once, whatever the decay.

bf16 or float32 inputs ``[b, T, h, d]`` (rows x positions x heads);
the log-decays, the betas, the state and every product are float32 at
full precision, and the result is float32. Any ``T``: the last chunk is
padded with positions that change nothing (zero key, value, query, beta
and log-decay). The backward pass is JAX's own of this form: the scan's
states at the chunks' boundaries are its residuals, ``T / C x dk x dv``
float32 a row and head. Under the scope ``kda.scan``; one ``kda.plan``
row in the set-up log a trace.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.telemetry.recorder import setup_event

CHUNK = 64
HIGHEST = jax.lax.Precision.HIGHEST


def record_plan(q, v, g, chunk: int) -> None:
    """One ``kda.plan`` row in the set-up log a trace: rows, heads,
    positions, the chunk, the widths, and what one forward pass must read
    (q, k, v, the log-decays and the betas) and write (the result)."""
    rows, steps, heads, dk = q.shape
    dv = v.shape[-1]
    per = rows * steps * heads
    setup_event("kda.plan", rows=int(rows), heads=int(heads), T=int(steps),
                chunk=int(chunk), dk=int(dk), dv=int(dv),
                bytes_read=int(per * ((2 * dk) * q.dtype.itemsize
                                      + dv * v.dtype.itemsize
                                      + dk * g.dtype.itemsize + 4)),
                bytes_written=int(per * dv * 4))


def decayed_products(xs, k, gamma):
    """``L[m, ..., t, s] = sum_c xs[m, ..., t, c] k[..., s, c] exp(
    gamma[..., t, c] - gamma[..., s, c])`` for ``s < t``, zero elsewhere:
    ``xs [m, ..., C, d]``, ``k`` and ``gamma [..., C, d]`` (``gamma``
    falling along C, C a power of two) -> ``[m, ..., C, C]``, built by
    halves through each block's middle (the module's docstring)."""
    *lead, c, d = k.shape
    m = xs.shape[0]
    out = jnp.zeros((m, *lead, c, 1, 1), jnp.float32)
    h = 1
    while h < c:
        def halves(a, *front):
            return a.reshape(*front, *lead, c // (2 * h), 2, h, a.shape[-1])

        x2, k2, g2 = halves(xs, m), halves(k), halves(gamma)
        middle = g2[..., 0, h - 1:, :]
        later = x2[..., 1, :, :] * jnp.exp(g2[..., 1, :, :] - middle)
        earlier = k2[..., 0, :, :] * jnp.exp(middle - g2[..., 0, :, :])
        z = jnp.einsum("m...td,...sd->m...ts", later, earlier,
                       precision=HIGHEST)
        below = halves(out, m)  # [m, ..., blocks, 2, h, h]
        top = jnp.concatenate([below[..., 0, :, :], jnp.zeros_like(z)], -1)
        bottom = jnp.concatenate([z, below[..., 1, :, :]], -1)
        out = jnp.concatenate([top, bottom], -2)
        h *= 2
    return out.reshape(m, *lead, c, c)


def delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK):
    """``q, k [b, T, h, dk]``, ``v [b, T, h, dv]``, log-decays ``g [b, T,
    h, dk]`` (at most 0) and ``beta [b, T, h]`` -> ``o [b, T, h, dv]``
    float32, each row from a zero state."""
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk}: a power of two")
    rows, steps, heads, dk = q.shape
    dv = v.shape[-1]
    record_plan(q, v, g, chunk)
    with jax.named_scope("kda.scan"):
        pad = -steps % chunk

        def chunks(a):
            """[b, T, h, ...] -> [b h, T / C, C, ...] float32."""
            a = jnp.moveaxis(a.astype(jnp.float32), 2, 1)
            a = jnp.pad(a, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 3))
            return a.reshape(rows * heads, -1, chunk, *a.shape[3:])

        q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
        gamma = jnp.cumsum(g, axis=2)
        end = gamma[:, :, -1:, :]
        lower = decayed_products(jnp.stack([k, q]), k, gamma)
        a = beta[..., None] * lower[0]
        p = lower[1] + jnp.sum(q * k, -1)[..., None] * jnp.eye(chunk)
        wu = jax.lax.linalg.triangular_solve(
            a, beta[..., None] * jnp.concatenate([k * jnp.exp(gamma), v], -1),
            left_side=True, lower=True, unit_diagonal=True)
        w, u = wu[..., :dk], wu[..., dk:]
        k_end = k * jnp.exp(end - gamma)

        def state_pass(s, xs):
            w, u, k_end, decay = xs
            u = u - jnp.einsum("nck,nkv->ncv", w, s, precision=HIGHEST)
            return decay[:, :, None] * s + jnp.einsum(
                "nck,ncv->nkv", k_end, u, precision=HIGHEST), (s, u)

        over_chunks = lambda a: jnp.moveaxis(a, 1, 0)
        _, (s, u) = jax.lax.scan(
            state_pass, jnp.zeros((rows * heads, dk, dv), jnp.float32),
            tuple(map(over_chunks, (w, u, k_end, jnp.exp(end[:, :, 0])))))
        s, u = jnp.moveaxis(s, 0, 1), jnp.moveaxis(u, 0, 1)
        o = jnp.einsum("nick,nikv->nicv", q * jnp.exp(gamma), s,
                       precision=HIGHEST) + jnp.einsum(
            "nits,nisv->nitv", p, u, precision=HIGHEST)
        o = o.reshape(rows, heads, -1, dv)[:, :, :steps]
        return jnp.moveaxis(o, 1, 2)
